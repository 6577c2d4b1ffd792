"""The gated short convolution's core (``paddle_tpu/models/lfm2_moe.py``:
everything under ``self_attn/pt.conv`` of a conv layer): ``s = B * z``, the
depthwise causal taps over ``s`` and a slot's tail, ``C * c``, and the new
tail. Plain XLA today, so there is no kernel name to find in a trace: the
time it is held to is the device time of the step programs' operations
whose innermost scope is ``pt.conv`` (``benchmark/harness/components.py``'s
finer rows). A Pallas kernel would be filed under its own name, the
innermost scope, and is read too if it is called :data:`PATTERN`. The
projections on either side (``in_proj``, ``out_proj``) have scopes of their
own and are not in it."""
#: the scope the plain form lies under, and the name a kernel would take
LEAF = "pt.conv"
PATTERN = "lfm2_short_conv"


def least(rows, tails, hidden, taps, bytes_per_el=2):
    """(flops, bytes) of the least work of ``rows`` live (row, layer) pairs
    whose slots hold ``tails`` live (slot, layer) tails, however they are
    grouped into layers: ``W_in``'s output ``[rows, 3 hidden]`` read once,
    ``[rows, hidden]`` written once, each live tail ``[taps - 1, hidden]``
    read once and written once; a channel of a row costs the gate's
    product, ``taps`` multiply-adds and the output gate's product, 8 flops
    at 3 taps. The bytes bind (0.06 flops a byte... the chip has 240)."""
    flops = (2.0 + 2.0 * taps) * rows * hidden
    els = 4 * rows * hidden + 2 * tails * (taps - 1) * hidden
    return flops, float(els * bytes_per_el)
