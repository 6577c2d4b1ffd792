"""Attention over a windowed layer's ring of latents
(``paddle_tpu/ops/kernels/latent_attention.py``: ``latent_attention_append(
..., window=)``): the same kernel as the whole-context form, held to a
least work that counts the WINDOW's positions only."""
PATTERN = r"latent_attention_append"


def least(pairs, rows, heads, width, dv, chunk, bytes_per_el=2):
    """(flops, bytes) for ``pairs`` (row, position) pairs inside the rows'
    windows (a row's ``min(window, pos + 1)``) of ``rows`` live (row,
    layer) pairs in ONE step: 2 flops a multiply-add over ``width`` for
    the score and over ``dv`` for the output in each head; a slot's window
    latents read once a step, counted from below as ``pairs / chunk``
    (``kernels/dsa_index.py``'s argument); a row's absorbed queries read
    and its outputs written once."""
    flops = 2.0 * heads * (width + dv) * pairs
    els = pairs / float(chunk) * width + rows * heads * (width + dv)
    return flops, float(els * bytes_per_el)
