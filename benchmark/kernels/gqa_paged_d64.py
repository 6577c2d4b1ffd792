"""Both paged K/V kernels of a step (``paddle_tpu/ops/kernels/
paged_attention.py``: ``paged_attention_append`` on a mixed step's packed
rows, ``paged_attention_decode`` in a decode scan) in a model whose heads
are narrower than the chip's 128 lanes, counted at the PUBLISHED head size:
what the mathematics needs, not what a padded or packed pool moves."""
#: the two kernels' names in a trace
PATTERNS = (r"paged_attention_append", r"paged_attention_decode")


def least(rows, ctx_tokens, slot_tokens, heads, kv_heads, head_dim,
          bytes_per_el=2):
    """(flops, bytes) of the least work of ONE step over all its K/V
    layers: ``rows`` live (row, layer) pairs that attend ``ctx_tokens``
    (position, row, layer) triples between them, in slots whose contexts
    hold ``slot_tokens`` (token, layer) pairs, the step's own rows
    included. Each query head meets every key of its row's context once
    (2 flops a multiply-add, for QK and for PV); a live slot's K and V are
    read once a K/V head at ``head_dim`` values (its rows share them: a
    decode row's slot is its own context, a chunk's slot is read once for
    the chunk); q read and the output written once a row; the new K and V
    written once a row."""
    flops = 4.0 * heads * head_dim * ctx_tokens
    els = (2 * kv_heads * slot_tokens + rows * (2 * heads + 2 * kv_heads)) \
        * head_dim
    return flops, float(els * bytes_per_el)
