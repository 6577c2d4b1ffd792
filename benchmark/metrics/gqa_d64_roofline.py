"""Per-layer metric ``gqa_d64_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). The least time the
chip could take for the USEFUL work of both paged K/V kernels in the traced
stretch (``benchmark/kernels/gqa_paged_d64.py``: each live slot's context K
and V once a K/V head at the published head size of 64 x 2 B, q and the
output once a row, the new K and V written once; a step's least is the
larger of its byte time and its flop time) over the summed trace time of
``paged_attention_append`` and ``paged_attention_decode`` inside it. It
counts EVERY live row, decode rows included (the older append rooflines
count prefill chunks alone, PERF.md section 7 (p)), and it counts 64 values
a head: where the chip moves 128 lanes for 64 it reads 50 at most. The
rows, the contexts they attend and the live slots' contexts are the
STRETCH's own: the sums of what the program's ``pt:engine.emit`` spans
inside it carry (``kv_rows``, ``kv_ctx_tokens``, ``kv_slot_tokens``, each
summed over the K/V layers). The one assumption is
``expert_matmul_roofline``'s: a step is emitted up to ``pipeline_depth``
steps after the device ran it. Nothing to read (None) where the trace has
neither kernel or the program writes no such ids."""
from benchmark.harness import loader
from benchmark.harness.trace import TraceError

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
IDS = ("kv_rows", "kv_ctx_tokens", "kv_slot_tokens")


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    emits = loader.module("metrics", "dsa_index_roofline").emitted(ctx, IDS)
    if not emits:
        return None
    k = loader.module("kernels", "gqa_paged_d64")
    secs, shown = 0.0, []
    for pattern in k.PATTERNS:
        try:
            s, n = tr.op_seconds(pattern)
        except TraceError:      # no such kernel in this trace
            continue
        secs += s
        shown.append(f"{n:.0f} calls of {pattern} at {s / n * 1e3:.3f} ms")
    if secs <= 0:
        return None
    c, peaks = ctx["cell"].config, ctx["peaks"]
    heads, kvh = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = int(c.get("head_dim") or int(c["hidden_size"]) // heads)
    least, rows, ctx_tokens, slot_tokens = 0.0, 0, 0, 0
    for ids in emits:
        f, b = k.least(int(ids["kv_rows"]), int(ids["kv_ctx_tokens"]),
                       int(ids["kv_slot_tokens"]), heads, kvh, hd)
        least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
        rows += int(ids["kv_rows"])
        ctx_tokens += int(ids["kv_ctx_tokens"])
        slot_tokens += int(ids["kv_slot_tokens"])
    print(f"[trace] gqa_d64_roofline: {'; '.join(shown)}; the "
          f"{len(emits)} emitted steps inside the traced stretch speak of "
          f"{rows} live (row, layer) pairs attending {ctx_tokens} positions "
          f"in slots of {slot_tokens} (token, layer) pairs, least "
          f"{least * 1e3:.2f} ms of {secs * 1e3:.2f}", flush=True)
    return 100.0 * least / secs / ctx["chips"]
