"""Chunked Kimi Delta Attention over a mixed step's live rows
(``paddle_tpu/ops/kernels/kda_chunk_walk.py``): every live row of every
slot updates its slot's state of ``heads`` x ``[k, v]`` float32 and reads
it. One call a KDA layer."""
PATTERN = r"kda_chunk_walk"
#: calls of the kernel one KDA layer makes
CALLS_A_LAYER = 1


def least(rows, slots, heads, k, v, bytes_per_el=4):
    """(flops, bytes) of ONE KDA layer over ``rows`` live rows, decode rows
    included, that lie in ``slots`` slots: the one-token form's two
    matvecs and rank-1 update a row a head, 2 flops a multiply-add; a
    row's ``q, k, g`` and ``v`` read, its ``beta`` read and its ``o``
    written; each slot with a live row its state read once and written
    once. Float32 throughout."""
    flops = 2.0 * 3 * rows * heads * k * v
    els = rows * heads * (3 * k + 2 * v + 1) + 2 * slots * heads * k * v
    return flops, float(els * bytes_per_el)
