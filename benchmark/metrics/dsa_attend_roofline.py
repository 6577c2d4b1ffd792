"""Per-layer metric ``dsa_attend_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). The least time the
chip could take to gather and attend the SELECTED latents of the traced
stretch (``benchmark/kernels/dsa_attend.py``: a selected latent of
``kv_lora_rank + qk_rope_head_dim`` values read once a row, 2 x heads x
(width + ``kv_lora_rank``) flops a selected pair) over the device time of
the operations whose innermost scope is the kernel ``dsa_sparse_attend``
or ``pt.sparse`` itself (the kernel's preparation, or the plain form) in
the stretch's step programs, whatever implements them. The selected pairs and the rows
are the stretch's own, a step at a time, from its ``pt:engine.emit``
spans (``selected_keys``, ``indexed_rows``), as ``dsa_index_roofline``
reads them. Nothing to read (None) where there is no trace, no component
table, no such scope or no such ids."""
from benchmark.harness import loader

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
IDS = ("selected_keys", "indexed_rows")


def read(ctx):
    k = loader.module("kernels", "dsa_attend")
    got = loader.module("metrics", "dsa_index_roofline").stretch(
        ctx, IDS, k.LEAF, k.PATTERN)
    if got is None:
        return None
    emits, secs, table = got
    c, peaks = ctx["cell"].config, ctx["peaks"]
    heads, dv = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
    width = dv + int(c["qk_rope_head_dim"])
    least, pairs = 0.0, 0
    for ids in emits:
        f, b = k.least(int(ids["selected_keys"]), int(ids["indexed_rows"]),
                       heads, width, dv)
        least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
        pairs += int(ids["selected_keys"])
    print(f"[trace] dsa_attend_roofline: {len(emits)} emitted steps inside "
          f"the traced stretch speak of {pairs} selected (row, latent) "
          f"pairs, least {least * 1e3:.2f} ms; {k.PATTERN} and {k.LEAF} took "
          f"{secs * 1e3:.2f} ms over {table.programs()} step programs",
          flush=True)
    return 100.0 * least / secs / ctx["chips"]
