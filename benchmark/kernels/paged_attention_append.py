"""Paged attention, append form: a chunk of a prompt (positions [a, b))
attends causally to everything before it and to itself."""
PATTERN = r"paged_attention_append"


def least(a, b, heads, kv_heads, head_dim, layers, bytes_per_el=2):
    """(flops, bytes) of one chunk [a, b) in ``layers`` layers: every query
    row i meets keys 0..i (2 flops per multiply-add, for QK and for PV);
    keys and values 0..b read once, queries read and outputs written once,
    the chunk's own keys and values written once."""
    pairs = (b - a) * (a + b + 1) / 2.0
    flops = 4.0 * heads * head_dim * pairs * layers
    els = (b * 2 * kv_heads + (b - a) * 2 * heads
           + (b - a) * 2 * kv_heads) * head_dim * layers
    return flops, float(els * bytes_per_el)
