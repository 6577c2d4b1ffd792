"""Per-layer metric ``expert_matmul_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). Trace time of the
grouped expert product against the least the chip could take for the
calls the traced stretch holds: the calls and their seconds are the
trace's (two calls an expert layer); the experts held and their widths
are the configuration's; the non-empty experts and the held rows a layer
are the STRETCH's own means, from what the program's ``pt:engine.emit``
spans inside it carry (``experts_read`` of ``experts_held``,
``held_rows``: a step's sums of the counters ``moe_experts_nonempty``,
``moe_experts_held`` and ``moe_assignments_held``, which
``expert_weights_read_pct.batch`` reads over the window). A stretch's mix
of mixed steps and all-decode scans differs from the window's, and a
scan's layer reads a sixth of the experts a mixed step's reads, so the
window's means would not do. The one assumption: a step is emitted up to
``pipeline_depth`` steps after the device ran it, so the emits inside the
stretch and the kernel's calls inside it are offset by that many steps of
some forty. The weights' read time binds (a few rows an expert). Nothing
to read (None) where the trace has no such kernel or the program writes
no such ids."""
from benchmark.harness import loader
from benchmark.harness.inside import inside
from benchmark.harness.readers import _roofline_pct
from benchmark.harness.trace import TraceError

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
IDS = ("experts_read", "experts_held", "held_rows")


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    k = loader.module("kernels", "grouped_expert_matmul")
    try:
        secs, calls = tr.op_seconds(k.PATTERN)
    except TraceError:          # no such kernel in this trace
        return None
    ins = inside(ctx)
    emits = [s.ids for s in (ins.named("pt:engine.emit") if ins else [])
             if all(key in s.ids for key in IDS)]
    nonempty, held, rows = (sum(int(ids[key]) for ids in emits)
                            for key in IDS)
    if not calls or secs <= 0 or not held:
        return None
    c = ctx["cell"].config
    experts = int(c.get("num_experts") or c["n_routed_experts"])
    layers_e = held / experts           # expert layers the emits speak of
    layers = calls / k.CALLS_A_LAYER    # and those the kernel ran
    print(f"[trace] expert_matmul_roofline: {calls:.0f} calls of "
          f"{secs / calls * 1e3:.3f} ms inside the traced stretch "
          f"({layers:.0f} expert layers); its {len(emits)} emitted steps "
          f"speak of {layers_e:.0f} layers, {nonempty / layers_e:.1f} of "
          f"{experts} experts non-empty and {rows / layers_e:.1f} held rows "
          f"a layer", flush=True)
    f, b = k.least(rows / layers_e, nonempty / layers_e,
                   int(c["hidden_size"]), int(c["moe_intermediate_size"]))
    return _roofline_pct([(f * layers, b * layers)], secs, ctx["peaks"],
                         ctx["chips"])
