"""The power-retention core of a step (``paddle_tpu/ops/kernels/
power_retention.py``: ``phi``, the state update, the read-out and a
chunk's inner attention, everything under ``self_attn/pt.core``). Plain XLA,
so there is no kernel name to find in a trace: the time it is held to is
the ``mixer.core`` component of the step programs
(``benchmark/harness/components.py``). A Pallas kernel would state its
name pattern here and be divided by its own trace time instead."""
#: no kernel of that name: the divisor is the component's device time
PATTERN = None
COMPONENT = "mixer.core"


def feature_dim(d):
    """``D``: the symmetric degree-2 monomials of a head's ``d`` values."""
    return d * (d + 1) // 2


def state_bytes(kv_heads, d, dv):
    """A (slot, layer) state: ``S`` [kv_heads, D, dv] and ``z`` [kv_heads,
    D], float32."""
    return 4 * kv_heads * feature_dim(d) * (dv + 1)


def least(states, rows, q_heads, kv_heads, d, dv):
    """(flops, bytes) of the least work for ``states`` live (slot, layer)
    states and ``rows`` live (row, layer) pairs, however the rows are
    grouped into steps: every live state read once and written once in
    float32; a live row's ``q, k, v`` read and ``o`` written once in
    bfloat16 and its ``log g`` in float32; a row's products ``phi(q)^T S``
    and ``phi(k) v^T`` (and their normaliser's) at 2 flops a multiply-add.
    A true floor: the one-token form run over a whole chunk would cost a
    state pass a ROW, a chunk form pays the inner attention on top, and
    neither is counted."""
    D = feature_dim(d)
    flops = 2.0 * rows * D * (dv + 1) * (q_heads + kv_heads)
    els = rows * (q_heads * (d + dv) + kv_heads * (d + dv))
    nbytes = 2.0 * states * state_bytes(kv_heads, d, dv) + 2.0 * els \
        + 4.0 * rows * kv_heads
    return flops, nbytes
