"""Per-layer metric ``program_trace_s``: layer "programs", moves ``setup_s``
(better lower, source program_counter). Seconds of the run's program builds
that were the host's Python work: jax's trace of the program and its
lowering to a module, summed over ``paddle_tpu.profiler.builds()``. What
tracing less of a program (a scan over layers) would cut. The records are
read, and printed by program, by ``program_load_s``'s file."""
from benchmark.harness import loader


def read(ctx):
    recs = loader.module("metrics", "program_load_s").records(ctx)
    if recs is None:
        return None
    return sum(r["trace_s"] + r["lower_s"] for r in recs)


UNIT = "s"
LAYER = "programs"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_counter"
