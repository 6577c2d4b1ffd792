"""Attention over the paged latent pool, append form
(``paddle_tpu/ops/kernels/latent_attention.py``): a chunk of a prompt
(positions [a, b)) attends causally to every latent before it and to its
own, ``heads`` query heads against ONE shared key of ``width`` values whose
first ``dv`` are the values."""
PATTERN = r"latent_attention_append"


def least(a, b, heads, width, dv, layers, bytes_per_el=2):
    """(flops, bytes) of one chunk [a, b) in ``layers`` latent-attention
    layers, from its live rows only: query row i meets latents 0..i, 2
    flops a multiply-add over ``width`` for the score and over ``dv`` for
    the output, in each head; latents 0..b read once a chunk (one shared
    head), the absorbed queries read and the outputs written once. The
    chunk's own latents are written by the step before the kernel runs and
    are not the kernel's bytes."""
    pairs = (b - a) * (a + b + 1) / 2.0
    flops = 2.0 * heads * (width + dv) * pairs * layers
    els = (b * width + (b - a) * heads * (width + dv)) * layers
    return flops, float(els * bytes_per_el)
