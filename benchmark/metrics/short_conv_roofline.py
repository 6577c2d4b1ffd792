"""Per-layer metric ``short_conv_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). The least time the
chip could take for the gated short convolutions' core of the traced
stretch (``benchmark/kernels/short_conv.py``: ``W_in``'s output read once,
``[rows, hidden]`` written once, each live slot's tail read and written
once, 8 flops a channel a row; the bytes bind; a step's least is the
larger of its byte time and its flop time) over the device time of the
step programs' operations whose innermost scope is ``pt.conv`` (or a
kernel's of the name that file gives), whatever implements it. The rows
and the live tails are the STRETCH's own: the sums of what the program's
``pt:engine.emit`` spans inside it carry (``conv_rows``, ``conv_tails``: a
step's ``conv_rows`` and ``conv_tails_live``, summed over the conv
layers). The one assumption is ``expert_matmul_roofline``'s: a step is
emitted up to ``pipeline_depth`` steps after the device ran it. Nothing to
read (None) where there is no trace, no component table or no such ids."""
from benchmark.harness import loader
from benchmark.harness.components import components

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
IDS = ("conv_rows", "conv_tails")


def read(ctx):
    if ctx.get("trace") is None:
        return None
    emits = loader.module("metrics", "dsa_index_roofline").emitted(ctx, IDS)
    if not emits:
        return None
    table = components(ctx)
    if table is None:
        return None
    k = loader.module("kernels", "short_conv")
    secs = loader.module("kernels", "dsa_index").leaf_seconds(
        table, k.LEAF, k.PATTERN)
    if secs <= 0:
        return None
    c, peaks = ctx["cell"].config, ctx["peaks"]
    hidden, taps = int(c["hidden_size"]), int(c["conv_L_cache"])
    least, rows, tails = 0.0, 0, 0
    for ids in emits:
        f, b = k.least(int(ids["conv_rows"]), int(ids["conv_tails"]),
                       hidden, taps)
        least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
        rows += int(ids["conv_rows"])
        tails += int(ids["conv_tails"])
    print(f"[trace] short_conv_roofline: {len(emits)} emitted steps inside "
          f"the traced stretch speak of {rows} live (row, layer) pairs on "
          f"{tails} live (slot, layer) tails, least {least * 1e3:.2f} ms; "
          f"{k.LEAF} took {secs * 1e3:.2f} ms over {table.programs()} step "
          f"programs", flush=True)
    return 100.0 * least / secs / ctx["chips"]
