"""The grouped product of an expert layer: rows sorted by expert, each
expert's run of rows times that expert's own weight matrix.

``xs`` is ``[rows, k]`` with the rows of expert 0 first, then expert 1's
and so on; ``sizes[g]`` is the rows expert ``g`` got and ``w`` is
``[E, k, n]``. The result's row ``r`` is ``xs[r] @ w[g(r)]``. At an
expert layer's traffic an expert sees a handful of rows, so the product
is weight streaming, and the Pallas kernel is built for that:

- **A grid step is one VISIT: one (row tile, expert) pair that shares a
  row.** The visits are laid out on the device from ``sizes``
  (:func:`_visits`: the expert and the row tile of every visit, in row
  order, and their number) and handed to the kernel by scalar prefetch;
  the grid's visit axis ends at the live visits. An expert with no row is
  never visited, so its weights are never read; a row tile beyond the
  last held row is never run. Rows of a tile that belong to a
  neighbouring expert are masked at the store.
- **Tall weight tiles, short row tiles.** A weight block spans the whole
  contraction and as many output columns as ``_VMEM_BUDGET`` allows
  double-buffered (:func:`_col_tile`), so there is no partial sum, and
  consecutive visits of one expert (its rows straddle a row tile's edge)
  find its block still in VMEM: every non-empty expert's weights cross
  HBM once a column tile. The row tile is :func:`row_tile`'s.
- **One weight operand or two.** With two (gate and up) the rows are read
  once, both experts' blocks are in flight together and the epilogue is
  ``silu(gate) * up`` in float32 before the one cast.

Operands as stored (bf16 in the serving cells), float32 accumulation.
:func:`grouped_expert_ffn` is an expert layer's three projections in two
calls; what is beyond the held rows in its result is unspecified (the
caller masks it, as it did for ``jax.lax.ragged_dot``).

**The rule on shapes**: the kernel serves widths that fill the lanes,
``k`` and ``n`` both multiples of 128 (every published width does).
Other widths (the toy models of the tests: 16 and 8) go through
``jax.lax.ragged_dot``, the same mathematics left to XLA; nothing else
chooses the path. On a CPU the kernel runs interpreted.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa
from .paged_attention import Z

#: VMEM a grid step may hold (the attention kernels' budget); the lanes of
#: a vector register; rows of a row tile at most
_VMEM_BUDGET = 40 << 20
_LANES = 128
_ROW_TILE_MAX = 64


def _interpret():
    return _pa._interpret()


def serves(k, n):
    """The rule on shapes (module docstring): True where the Pallas
    kernel takes a product of contraction ``k`` and ``n`` columns. It is
    symmetric, so one answer holds for an expert layer's two calls."""
    return k % _LANES == 0 and n % _LANES == 0


def row_tile(rows, h, f, e, dtype):
    """Rows of one row tile for an expert layer of widths ``h`` and ``f``
    over ``e`` experts whose product is at most ``rows`` high; the
    caller rounds the height to it. A power of two from the dtype's
    sublane packing (16 rows of bf16, 8 of float32) to ``_ROW_TILE_MAX``:
    the least that holds the rows an expert gets when they spread evenly,
    ``rows / e`` (read on the chip at both expert cells' mixed steps: 16 /
    32 / 64 / 128 / 256 rows run a layer in 1.39 / 1.33 / 1.30 / 1.31 /
    1.34 ms and 3.16 / 2.97 / 2.88 / 2.91 / 2.94 ms, PERF.md section 6, PR
    35). Shapes the kernel does not serve take 8, the height
    XLA's grouped matmul wants (at another it multiplies every row by
    every expert: ``benchmark/tests/test_aot_deepseek_v2.py``)."""
    if not serves(h, f):
        return 8
    tm = 32 // jnp.dtype(dtype).itemsize
    while tm < _ROW_TILE_MAX and tm * e < rows:
        tm *= 2
    return tm


def _col_tile(tm, k, n, n_w, isz, osz):
    """Output columns of one weight block: the widest divisor of ``n``
    in whole lanes whose buffers fit ``_VMEM_BUDGET``: ``n_w`` weight
    blocks, the row tile and the output tile, double-buffered, and the
    float32 products."""
    for parts in range(1, n // _LANES + 1):
        tn = n // parts
        if n % parts or tn % _LANES:
            continue
        if (2 * n_w * k * tn * isz + 2 * tm * k * isz + 2 * tm * tn * osz
                + n_w * tm * tn * 4) <= _VMEM_BUDGET:
            return tn
    return _LANES


def _visits(sizes, rows, tm):
    """The walk over (row tile, expert) pairs that share a row, in row
    order. Returns (expert [V], row tile [V], first row [E], end row [E],
    live visits), V = the static most: a visit a row tile and one more
    for every further expert. Entries past the live visits repeat the
    last live one, so an index map is in range wherever it is asked."""
    e = sizes.shape[0]
    n_max = -(-rows // tm) + e - 1
    ends = jnp.minimum(jnp.cumsum(sizes.astype(jnp.int32)), jnp.int32(rows))
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = starts // tm
    n_g = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    v_end = jnp.cumsum(n_g)
    v = jnp.minimum(jnp.arange(n_max, dtype=jnp.int32),
                    jnp.maximum(v_end[-1] - 1, 0))
    gid = jnp.minimum(jnp.sum(v[:, None] >= v_end[None, :], axis=1),
                      e - 1).astype(jnp.int32)
    tid = first[gid] + v - (v_end - n_g)[gid]
    return gid, tid.astype(jnp.int32), starts, ends, v_end[-1]


def _kernel(gid_ref, tid_ref, start_ref, end_ref, x_ref, *rest):
    *w_refs, o_ref = rest
    v = pl.program_id(1)
    g = gid_ref[v]
    x = x_ref[...]
    acc = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
    if len(w_refs) == 2:
        up = jnp.dot(x, w_refs[1][...], preferred_element_type=jnp.float32)
        acc = jax.nn.silu(acc) * up
    row = tid_ref[v] * np.int32(x_ref.shape[0]) + jax.lax.broadcasted_iota(
        jnp.int32, acc.shape, 0)
    mine = (row >= start_ref[g]) & (row < end_ref[g])
    o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


def _call(xs, ws, visits, tm, out_dtype, interpret):
    """One grouped product of ``xs`` with every operand of ``ws`` (one:
    the product; two: ``silu(xs w0) * (xs w1)``), on laid-out visits."""
    gid, tid, starts, ends, n_visits = visits
    rows, k = xs.shape
    n = ws[0].shape[2]
    isz, osz = xs.dtype.itemsize, jnp.dtype(out_dtype).itemsize
    tn = _col_tile(tm, k, n, len(ws), isz, osz)

    def x_map(j, v, gid, tid, starts, ends):
        return tid[v], Z

    def w_map(j, v, gid, tid, starts, ends):
        return gid[v], Z, j

    def o_map(j, v, gid, tid, starts, ends):
        return tid[v], j

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, k), x_map)] + [
                pl.BlockSpec((None, k, tn), w_map) for _ in ws],
            out_specs=pl.BlockSpec((tm, tn), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET + (16 << 20)),
        name="grouped_expert_matmul",
        interpret=interpret,
    )(gid, tid, starts, ends, xs, *ws)


def grouped_expert_ffn(xs, w_gate, w_up, w_down, sizes, tm):
    """xs: [rows, h] sorted by expert; w_gate, w_up: [E, h, f]; w_down:
    [E, f, h]; sizes: [E] int32, the rows an expert got; ``tm``:
    :func:`row_tile` of these shapes. Returns ``[rows, h]`` float32,
    ``(silu(xs w_gate) * (xs w_up)).astype(xs.dtype) @ w_down`` with every
    row on its own expert's matrices; rows at or past ``sum(sizes)`` are
    unspecified."""
    h, f = w_gate.shape[1:]
    if not serves(h, f):
        return _ragged_ffn(xs, w_gate, w_up, w_down, sizes)
    return _ffn_call(xs, w_gate, w_up, w_down, sizes, tm=int(tm),
                     interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("tm", "interpret"), inline=True)
def _ffn_call(xs, w_gate, w_up, w_down, sizes, *, tm, interpret):
    """The walk and the two Pallas calls, under one inlined inner jit so
    that a model's layers share a trace (``latent_attention._append_call``)."""
    visits = _visits(sizes, xs.shape[0], tm)
    act = _call(xs, (w_gate, w_up), visits, tm, xs.dtype, interpret)
    return _call(act, (w_down,), visits, tm, jnp.float32, interpret)


def _ragged_ffn(xs, w_gate, w_up, w_down, sizes):
    """The same layer left to XLA, for widths the kernel does not serve."""
    gate = jax.lax.ragged_dot(xs, w_gate, sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, w_up, sizes,
                            preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gate) * up).astype(xs.dtype)
    return jax.lax.ragged_dot(act, w_down, sizes,
                              preferred_element_type=jnp.float32)
