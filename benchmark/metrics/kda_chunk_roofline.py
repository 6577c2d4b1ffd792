"""Per-layer metric ``kda_chunk_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). Trace time of the
KDA kernel against the least the chip could take for the calls the traced
stretch holds: the calls and their seconds are the trace's (one call a
KDA layer of a mixed step); the rows and the slots a step are the
STRETCH's own means, from what the program's ``pt:engine.dispatch`` spans
of its mixed steps carry (``prefill_rows``, ``decode_rows``: every live
row counts, decode rows included; a decode row is a slot, and the prefill
rows lie in at least ``prefill_rows / chunk_size`` slots, rounded up).
The one assumption is ``expert_matmul_roofline``'s: the device runs a
step a little after its dispatch, so the spans and the calls inside the
stretch are offset by a step or two of some seventy. The bytes bind (the
states' read and write, the rows' operands). Nothing to read (None) where
the trace has no such kernel or the program writes no such ids."""
from benchmark.harness import loader
from benchmark.harness.inside import inside
from benchmark.harness.readers import _roofline_pct
from benchmark.harness.trace import TraceError

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
IDS = ("kind", "prefill_rows", "decode_rows")
#: ``kind`` of a mixed step's dispatch span (the program's
#: ``DISPATCH_KINDS``: decode, mixed, spec)
MIXED = 1


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    lin = ctx["cell"].config.get("linear_attn_config")
    if not lin:
        return None
    k = loader.module("kernels", "kda_chunk")
    try:
        secs, calls = tr.op_seconds(k.PATTERN)
    except TraceError:          # no such kernel in this trace
        return None
    ins = inside(ctx)
    steps = [s.ids for s in (ins.named("pt:engine.dispatch") if ins else [])
             if all(key in s.ids for key in IDS)
             and int(s.ids["kind"]) == MIXED]
    if not calls or secs <= 0 or not steps:
        return None
    chunk = int(ctx["cell"].config["engine"]["chunk_size"])
    rows = sum(int(i["prefill_rows"]) + int(i["decode_rows"]) for i in steps)
    slots = sum(int(i["decode_rows"]) + -(-int(i["prefill_rows"]) // chunk)
                for i in steps)
    layers = calls / k.CALLS_A_LAYER
    print(f"[trace] kda_chunk_roofline: {calls:.0f} calls of "
          f"{secs / calls * 1e3:.3f} ms inside the traced stretch; its "
          f"{len(steps)} mixed steps hold {rows / len(steps):.1f} live rows "
          f"in {slots / len(steps):.2f} slots a step", flush=True)
    heads, width = int(lin["num_heads"]), int(lin["head_dim"])
    f, b = k.least(rows / len(steps), slots / len(steps), heads, width,
                   width)
    return _roofline_pct([(f * layers, b * layers)], secs, ctx["peaks"],
                         ctx["chips"])
