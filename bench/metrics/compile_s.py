"""Set-up: the program's compile counters as the run left them
(``launch/compile_cache.py``): seconds spent tracing, lowering, and
compiling or reading the compile cache, events nested in others counted
once.  No compile runs in the window (``compiles_in_window``), so this
is what set-up spent."""
from bench import scopes

KINDS = ("compile/trace", "compile/lower", "compile/backend")


def read(ctx):
    c = scopes.host_counters(ctx) or {}
    if not any(k in c for k in KINDS):
        return None
    return sum(c.get(k, {}).get("seconds", 0.0) for k in KINDS)
