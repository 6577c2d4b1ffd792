"""Per-layer metric ``retention_core_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). The least time the
chip could take for the power-retention work of the traced stretch
(``benchmark/kernels/power_retention.py``: every live (slot, layer) state
read once and written once in float32, a live row's operands once, its two
products' flops; a step's least is the larger of its byte time and its
flop time) over the device time of the ``mixer.core`` component of the
stretch's step programs (``harness/components.py``: everything under
``self_attn/pt.core``; the core is plain XLA, so there is no kernel name
to sum). The live states and rows are the STRETCH's own: the sums of what
the program's ``pt:engine.emit`` spans inside it carry (``live_states``,
``ret_rows``: a step's ``ret_state_live`` and ``ret_rows_chunk +
ret_rows_step``, summed over the layers). The one assumption is
``expert_matmul_roofline``'s: a step is emitted up to ``pipeline_depth``
steps after the device ran it, so the emits and the programs inside the
stretch are offset by that many steps of some hundred. Nothing to read
(None) where there is no trace, no component table or no such ids."""
from benchmark.harness import loader
from benchmark.harness.components import components
from benchmark.harness.inside import inside

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
IDS = ("live_states", "ret_rows")


def read(ctx):
    if ctx.get("trace") is None:
        return None
    ins = inside(ctx)
    emits = [s.ids for s in (ins.named("pt:engine.emit") if ins else [])
             if all(key in s.ids for key in IDS)]
    if not emits:
        return None
    table = components(ctx)
    k = loader.module("kernels", "power_retention")
    secs = table.seconds(lambda comp, _: comp == k.COMPONENT) \
        if table is not None else 0.0
    if secs <= 0:
        return None
    c, peaks = ctx["cell"].config, ctx["peaks"]
    heads, kvh, d = (int(c[key]) for key in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    least, states, rows = 0.0, 0, 0
    for ids in emits:
        f, b = k.least(int(ids["live_states"]), int(ids["ret_rows"]),
                       heads, kvh, d, d)
        least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
        states += int(ids["live_states"])
        rows += int(ids["ret_rows"])
    print(f"[trace] retention_core_roofline: {len(emits)} emitted steps "
          f"inside the traced stretch speak of {states} live (slot, layer) "
          f"states and {rows} live (row, layer) pairs, least "
          f"{least * 1e3:.2f} ms; {k.COMPONENT} took {secs * 1e3:.2f} ms "
          f"over {table.programs()} step programs", flush=True)
    return 100.0 * least / secs / ctx["chips"]
