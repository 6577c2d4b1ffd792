"""Per-layer metric ``host_gc_pct.batch``: layer "server loop and
scheduler", moves ``serve_tok_s`` (better lower, source program_counter).
Share of the window the host spent inside Python's cyclic collector
(``pt:host.gc``): 100 x the window's ``gc_pause_time_s`` over its length.
The collector holds the interpreter lock, so every thread of the process
(the engine's loop, the client) stands still for a pause."""
from benchmark.harness.inside import log


def read(ctx):
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if not s0 or not s1 or "gc_pause_time_s" not in s0:
        return None
    secs = s1["gc_pause_time_s"] - s0["gc_pause_time_s"]
    n = s1["gc_pauses"] - s0["gc_pauses"]
    grew = s1["gc_pause_max_s"] > s0["gc_pause_max_s"]
    log(f"[gc] {n} collections inside the window, {secs * 1e3:.3f} ms in "
        f"all ({n / ctx['window_s']:.1f} a second, mean "
        f"{secs / n * 1e3 if n else 0.0:.4f} ms); the longest since the "
        f"engine was built {s1['gc_pause_max_s'] * 1e3:.3f} ms, "
        + ("inside the window" if grew else
           f"before it (no pause of the window passed "
           f"{min(secs, s0['gc_pause_max_s']) * 1e3:.3f} ms: its "
           f"collections' sum, or the longest before it)"))
    return 100.0 * secs / ctx["window_s"]


UNIT = "%"
LAYER = "server loop and scheduler"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "program_counter"
