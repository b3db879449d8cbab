"""Beyond-paper: the LM roofline table — reads the dry-run artifacts and
prints every (arch x shape x mesh) cell's roofline terms (the §Roofline
deliverable; launch/roofline.py renders the same data as markdown)."""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import row
from repro.launch.compile_cache import enable_compile_cache

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def run():
    out = []
    for path in sorted(glob.glob(os.path.join(ART, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        r = rec["roofline"]
        name = rec["cell"].replace("__", "/")
        out.append(row(
            f"roofline_{name}", rec.get("compile_s", 0) * 1e6,
            f"bound={r['bottleneck']} t_c={r['t_compute_s']:.4f} "
            f"t_m={r['t_memory_s']:.4f} t_x={r['t_collective_s']:.4f} "
            f"frac={r['roofline_fraction']:.3f} "
            f"fits={rec['memory']['fits_16GiB']}"))
    if not out:
        out.append(row("roofline_missing", 0,
                       "run: python -m repro.launch.dryrun --all"))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
