"""The grouped expert product
(``paddle_tpu/ops/kernels/grouped_expert_matmul.py``): rows sorted by
expert, each expert's rows times that expert's gate, up and down
matrices. One expert layer is two calls of the kernel: gate and up in
one, down in the other."""
PATTERN = r"grouped_expert_matmul"
#: calls of the kernel one expert layer makes
CALLS_A_LAYER = 2


def least(rows, experts_read, hidden, width, bytes_per_el=2):
    """(flops, bytes) of ONE expert layer over ``rows`` held assignments
    that land on ``experts_read`` non-empty experts of widths ``hidden`` x
    ``width``: 2 flops a multiply-add for each of the three projections of
    every row; a non-empty expert's three matrices read once; the rows
    read once, the activations written and read once, the result written
    once in float32. An expert that got no row is not read."""
    flops = 2.0 * 3 * rows * hidden * width
    els = 3 * experts_read * hidden * width + rows * (hidden + 2 * width)
    return flops, float(els * bytes_per_el + rows * hidden * 4)
