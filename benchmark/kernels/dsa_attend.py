"""Attention of a row over ITS selected latents (``paddle_tpu/ops/kernels/
sparse_latent_attention.py``: ``sparse_attend``, under ``self_attn/
pt.core/pt.sparse``): the gather of a row's ``min(index_topk, pos + 1)``
latents and the absorbed attention over them. On the chip the Pallas
kernel ``dsa_sparse_attend``, whose operations a component table files
under the kernel's own name, and the pass that packs the slots' contexts
for it, under ``pt.sparse`` itself; in plain XLA all of it lies under
``pt.sparse``. The time it is held to is the device time of both rows
(``benchmark/kernels/dsa_index.py``'s ``leaf_seconds``), so the share
reads the same work whatever implements it."""
#: the kernel's name in a trace, and the scope its plain form (and the
#: kernel's preparation) lies under
PATTERN = "dsa_sparse_attend"
LEAF = "pt.sparse"


def least(pairs, rows, heads, width, dv, bytes_per_el=2):
    """(flops, bytes) of the least work for ``pairs`` selected (row,
    latent) pairs of ``rows`` live (row, layer) pairs: a selected latent
    of ``width`` values read once a ROW (rows select different sets:
    nothing is shared by construction), 2 flops a multiply-add over
    ``width`` for the score and over ``dv`` for the output in each head;
    a row's absorbed queries read and its outputs written once."""
    flops = 2.0 * heads * (width + dv) * pairs
    els = pairs * width + rows * heads * (width + dv)
    return flops, float(els * bytes_per_el)
