"""Flash attention in training: forward, and the two backward kernels.
Causal, so half of the score matrix. The forward pass that a recomputing
backward runs again is not useful work and is not counted."""
PATTERN = r"flash_attention_(fwd|bwd_dkv|bwd_dq)"


def least(batch, seq, heads, kv_heads, head_dim, layers, bytes_per_el=2):
    """(flops, bytes) of one training step's attention: forward 2 matmuls
    (QK, PV), backward 4 (dV, dP, dQ, dK; recomputing QK inside the
    backward kernels is the algorithm's own and not counted), each
    2 * S^2/2 * D flops per head; q, k, v, o, do read and dq, dk, dv, o
    written once."""
    pairs = seq * (seq + 1) / 2.0
    flops = 6 * 2.0 * pairs * head_dim * heads * batch * layers
    els = batch * seq * head_dim * layers * (
        (3 * heads + 2 * kv_heads)      # q, o, do read; k, v read
        + (2 * heads + 2 * kv_heads))   # o, dq written; dk, dv written
    return flops, float(els * bytes_per_el)
