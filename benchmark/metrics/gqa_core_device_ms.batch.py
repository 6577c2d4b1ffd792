"""Per-layer metric ``gqa_core_device_ms.batch``: layer "programs", moves
``serve_tok_s`` (better lower, source device_trace). Device ms a step
program (all kinds of the stretch together) of the paged K/V kernels,
``paged_attention_append`` in a mixed step and ``paged_attention_decode``
in a decode scan, found by their kernel files' ``PATTERN``: the softmax
layers' core beside ``mixer_core_device_ms.batch``, whose rest is the
recurrent layers' in a model that mixes the two kinds. It says which of
them sets the mixer's time as contexts grow. Nothing to read (None) where
the trace has neither kernel or no step program, or the configuration
names no ``gqa_layers`` (a model whose every layer is K/V reads
``mixer_core_device_ms.batch`` itself)."""
from benchmark.harness import loader
from benchmark.harness.readers import SERVE_MODULES
from benchmark.harness.trace import MODULES_LINE, TraceError

UNIT = "ms"
LAYER = "programs"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "device_trace"
KERNELS = ("paged_attention_append", "paged_attention_decode")


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or "gqa_layers" not in ctx["cell"].config:
        return None
    try:
        _, programs = tr.op_seconds(SERVE_MODULES, MODULES_LINE)
    except TraceError:          # no step program inside the window
        return None
    secs, shown = 0.0, []
    for name in KERNELS:
        try:
            s, n = tr.op_seconds(loader.module("kernels", name).PATTERN)
        except TraceError:      # no such kernel in this trace
            continue
        secs += s
        shown.append(f"{n:.0f} calls of {name} at {s / n * 1e3:.3f} ms")
    if not shown or not programs:
        return None
    print(f"[trace] gqa_core_device_ms.batch: {'; '.join(shown)}; "
          f"{programs:.0f} step programs", flush=True)
    return 1e3 * secs / programs
