"""Per-layer metric ``package_import_s``: layer "programs", moves
``setup_s`` (better lower, source program_counter). The wall of ``import
paddle_tpu`` (``paddle_tpu.profiler.startup()["import_s"]``: the package's
``__init__`` from its first line to its last, jax's own import inside it
unless jax was loaded already)."""
from benchmark.harness.inside import log


def read(ctx):
    import paddle_tpu.profiler as profiler
    if not hasattr(profiler, "startup"):
        return None
    s = profiler.startup()
    log(f"[startup] import paddle_tpu: {s['import_s']:.3f}s (jax "
        f"{'was loaded already' if s['jax_preimported'] else 'inside it'}); "
        f"the process's builds: {s['builds']} and {s['retraces']} retraces, "
        f"wall {s['wall_s']:.3f}s = trace {s['trace_s']:.3f} + lower "
        f"{s['lower_s']:.3f} + compile or load {s['compile_or_load_s']:.3f}"
        f" + other")
    return s["import_s"]


UNIT = "s"
LAYER = "programs"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_counter"
