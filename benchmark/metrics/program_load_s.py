"""Per-layer metric ``program_load_s``: layer "programs", moves ``setup_s``
(better lower, source program_counter). Seconds of the run's program builds
inside jax's ``compile_or_get_cached``: the executable read from the
persistent cache on a warm start (a hit), compiled on a cold one (a miss),
summed over ``paddle_tpu.profiler.builds()``. What a smaller executable, or
builds made concurrently or ahead of time, would cut.

Also the one place the run's build records are read and printed (the builds
by program, the cache's hits and misses, any retrace by name), for
``program_trace_s`` beside this file."""
from benchmark.harness.inside import log


def records(ctx):
    """This run's build records, oldest first, printed once a run: the
    engine's in a serving cell (its last ``programs_built``: a process
    that ran an engine before keeps that one's records too), the train
    step's in a training cell. None where the program keeps no such
    records (the parent of the PR that brought them)."""
    if "build_records" in ctx:
        return ctx["build_records"]
    ctx["build_records"] = None
    import paddle_tpu.profiler as profiler
    if not hasattr(profiler, "builds"):
        return None
    if ctx["kind"] == "serve":
        s1 = ctx.get("stats1") or {}
        recs = [r for r in profiler.builds() if r["owner"] == "engine"]
        recs = recs[len(recs) - int(s1.get("programs_built", len(recs))):]
    else:
        recs = [r for r in profiler.builds() if r["owner"] == "train"]
    ctx["build_records"] = recs
    for r in recs:
        parts = r["trace_s"] + r["lower_s"] + r["compile_or_load_s"]
        log(f"[startup] {'RETRACE ' if r['retrace'] else 'build '}"
            f"{r['program']} (step {r.get('step_id')}): wall "
            f"{r['wall_s']:.3f}s = trace {r['trace_s']:.3f} + lower "
            f"{r['lower_s']:.3f} + compile or load "
            f"{r['compile_or_load_s']:.3f} + other "
            f"{r['wall_s'] - parts:.3f}; cache {r['cache_hits']} hit, "
            f"{r['cache_misses']} miss")
    retraced = [r["program"] for r in recs if r["retrace"]]
    log(f"[startup] {len(recs)} builds, wall "
        f"{sum(r['wall_s'] for r in recs):.3f}s; cache "
        f"{sum(r['cache_hits'] for r in recs)} hits, "
        f"{sum(r['cache_misses'] for r in recs)} misses; retraces (a "
        f"window must hold none): {len(retraced)} {retraced}")
    return recs


def read(ctx):
    recs = records(ctx)
    if recs is None:
        return None
    return sum(r["compile_or_load_s"] for r in recs)


UNIT = "s"
LAYER = "programs"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_counter"
