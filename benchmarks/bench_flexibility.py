"""Paper Table 1: lines of configuration to insert a new service.

Counts the serialized declarative config (tiles + route entries) needed to
add each paper application — the exact metric the paper reports for its
XML tooling — plus the deadlock re-analysis result after insertion."""
from __future__ import annotations

from benchmarks.common import row
from repro.apps import echo, reed_solomon, vr_witness
from repro.core import analyze
from repro.launch.compile_cache import enable_compile_cache
from repro.net.stack import tcp_topology, udp_topology


def run():
    out = []
    # Reed-Solomon: 4 replica tiles added to the UDP stack
    base = udp_topology([echo.make(port=7)])
    with_rs = udp_topology([echo.make(port=7),
                            reed_solomon.make(port=9000, n_replicas=4)])
    rs_names = [f"rs.{i}" for i in range(4)]
    loc = with_rs.config_loc(rs_names)
    ok = analyze(with_rs).ok
    out.append(row("table1_loc_reed_solomon", 0,
                   f"loc={loc} deadlock_free={ok} (paper: 25+6 xml / 13 verilog)"))

    # VR witness: 4 shard tiles
    with_vr = udp_topology([vr_witness.make(base_port=9100, n_shards=4)])
    vr_names = [f"vr.{i}" for i in range(4)]
    loc = with_vr.config_loc(vr_names)
    ok = analyze(with_vr).ok
    out.append(row("table1_loc_vr_witness", 0,
                   f"loc={loc} deadlock_free={ok} (paper: 18+6k xml / 17)"))

    # TCP migration: two NAT tiles inserted between IP and TCP without
    # touching either protocol tile (the paper's headline flexibility claim)
    plain = tcp_topology(with_nat=False)
    with_nat = tcp_topology(with_nat=True)
    loc = with_nat.config_loc(["nat_rx", "nat_tx"])
    ok = analyze(with_nat).ok
    shared = {t.name for t in plain.tiles} & {t.name for t in with_nat.tiles}
    untouched = all(
        plain.tile(n).kind == with_nat.tile(n).kind for n in shared
        if n not in ("ip_rx", "tcp_tx"))  # only their route tables changed
    out.append(row("table1_loc_tcp_migration", 0,
                   f"loc={loc} deadlock_free={ok} protocols_untouched="
                   f"{untouched} (paper: 2x(34+6) xml / 2x15)"))

    # NAT inserted into the *UDP* stack via insert_on_path — the compiled
    # executor makes this a pure topology edit, so the metric is the same
    # config-LoC count as the paper's XML story
    nat_udp = udp_topology([echo.make(port=7)])
    nat_udp.dim_x += 1
    nat_udp.tile("udp_rx").x += 1
    nat_udp.tile("echo").x += 1
    nat_udp.insert_on_path("nat_rx", "nat_rx", 2, 0, "ip_rx", "udp_rx")
    loc = nat_udp.config_loc(["nat_rx"])
    ok = analyze(nat_udp).ok
    out.append(row("table1_loc_nat_into_udp", 0,
                   f"loc={loc} deadlock_free={ok} (topology-only insertion; "
                   "no tile function changed)"))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
