"""Pallas TPU fused int4-dequant matmul — weight-only int4 decode GEMM.

Reference analog: the reference's weight-only quantized GEMMs
(paddle/phi/kernels/fusion/cutlass/ weight-only int4/int8 paths behind
nn/quant/quantized_linear.py weight_only_linear). On TPU the XLA lowering of
"unpack nibbles, then matmul" MATERIALIZES the two unpacked int8 planes in
HBM every call — the unpack traffic erases int4's bandwidth win (measured:
int4 split-nibble 7.7k decode tok/s vs int8 10.4k at the 879M config).

This kernel streams the PACKED bytes (half of int8's weight traffic) and
extracts nibbles in registers:

  * packed int8 tile [kt2, ot] -> int32 -> low = (p<<28)>>28 (sign-extended
    low nibble), high = p>>4 (arithmetic shift; byte sign = high-nibble sign)
  * the activation row-pairing is handled OUTSIDE the kernel: x splits once
    into even/odd columns (x is tiny next to W), so the kernel is two plain
    MXU dots per tile: acc += xe @ low + xo @ high
  * per-output scales apply on the final k tile.

Falls back to the split-nibble jax path off-TPU or for non-tileable shapes
(callers guard; see nn/quant weight_only_linear).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Z = np.int32(0)

# measured on v5e at the llama ff shape (4096x11264, 8 rows): (512, 512)
# runs 0.52 ms/mm vs int8's 0.73 and the split-nibble XLA path's 0.94 —
# both XLA baselines stream ~107 GB/s effective here, so halving the weight
# bytes halves the time once the unpack stays in registers
_KT2 = 512   # packed-k tile (int8 sublane multiple)
_OT = 512    # out tile (lane multiple)


def _int4_mm_kernel(xe_ref, xo_ref, p_ref, s_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = p_ref[...].astype(jnp.int32)                 # [kt2, ot]
    low = jnp.right_shift(jnp.left_shift(p, 28), 28)
    high = jnp.right_shift(p, 4)
    xe = xe_ref[...]                                 # [B, kt2]
    xo = xo_ref[...]
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    acc_ref[...] += dot(xe, low.astype(xe.dtype)) + \
        dot(xo, high.astype(xo.dtype))

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] *
                      s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def int4_matmul_tileable(n_in, n_out):
    """Shapes this kernel serves without padding weights."""
    return n_in % (2 * _KT2) == 0 and n_out % _OT == 0


def int4_matmul(x, packed, scales, out_dtype=None):
    """x [rows, n_in] @ dequant(packed [n_in/2, n_out] int4-pairs) * scales.

    Nibble convention matches weight_quantize: packed row r = original rows
    2r (low nibble) and 2r+1 (high). Requires int4_matmul_tileable shapes;
    rows pad to the MXU's 8-row granule internally.
    """
    rows, n_in = x.shape
    kt2_rows, n_out = packed.shape
    # rows bound = the VMEM budget: whole (rows_p, _KT2) x-blocks and a
    # (rows_p, _OT) fp32 accumulator stay resident per grid step
    assert n_in == 2 * kt2_rows and int4_matmul_tileable(n_in, n_out) \
        and rows <= 128, (rows, n_in, n_out)
    if out_dtype is None:
        out_dtype = x.dtype

    rows_p = max(8, -(-rows // 8) * 8)
    if rows_p != rows:
        x = jnp.pad(x, ((0, rows_p - rows), (0, 0)))
    xe = x[:, 0::2]                                  # pairs with low nibble
    xo = x[:, 1::2]
    nk = kt2_rows // _KT2
    no = n_out // _OT

    out = pl.pallas_call(
        functools.partial(_int4_mm_kernel, nk=nk),
        grid=(no, nk),
        in_specs=[
            pl.BlockSpec((rows_p, _KT2), lambda o, k: (Z, k)),
            pl.BlockSpec((rows_p, _KT2), lambda o, k: (Z, k)),
            pl.BlockSpec((_KT2, _OT), lambda o, k: (k, o)),
            pl.BlockSpec((1, _OT), lambda o, k: (Z, o)),
        ],
        out_specs=pl.BlockSpec((rows_p, _OT), lambda o, k: (Z, o)),
        out_shape=jax.ShapeDtypeStruct((rows_p, n_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((rows_p, _OT), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="int4_matmul",
        interpret=jax.default_backend() not in ("tpu",),
    )(xe, xo, packed, scales.reshape(1, -1))
    return out[:rows]
