"""Chunked KDA (``ops/kernels/kda.py``: the recurrence and its chunked
form) as one Pallas kernel that walks only the LIVE chunks of a step.

A mixed step hands a recurrent layer the per-slot view ``[B, S, H, K]`` of
which a slot's first ``q_lens[b]`` rows hold a token: one slot a prompt
chunk of ``S`` rows, the others one decode row or none. The kernel's grid
is (slot, group of heads, chunk of ``CHUNK`` rows), the chunk axis
innermost and sequential, with ``(q_lens, seq_lens)`` scalar-prefetched:

- **Only live chunks do work.** A grid step whose chunk starts at or past
  ``q_lens[b]`` computes nothing and its operands' index maps stay on the
  slot's last live block, so nothing is fetched; it stores zeros, which is
  what every dead row's output is. A slot without a live row reads and
  writes no state: its blocks map to a neighbouring live slot's, which
  stay where they are, and its state is aliased through.
- **The state stays in VMEM across a slot's chunks.** ``S`` ``[K, V]``
  float32 a head lives in the output block from the slot's first chunk to
  its last: read from HBM once and written once a (slot, head). A slot at
  position 0 (``seq_lens[b] == 0``) starts from zeros there, when its
  first row comes.
- **No ``[C, C, K]`` tensor in HBM.** A chunk of 64 rows is eight
  sub-blocks of ``SUB`` = 8 (a sublane tile: at 16 a column's work is
  two registers of which one is masked half the time, and the kernel is a
  sixth slower). A diagonal sub-block's ``A`` / ``B`` is
  formed elementwise over the channel a column at a time (exponent ``G_s -
  G_r <= 0`` in VMEM, as ``kda_chunk`` forms it); an off-diagonal
  sub-block is a matmul of ``[q_s; k_s] exp(G_s - G_m)`` with ``k_r exp(G_m
  - G_r)``, ``G_m`` the running sum at the row before the sub-block: both
  exponents are <= 0, so nothing overflows however strong the decay.
  ``U = (I + diag(beta) A)^-1 diag(beta) (V - k_in S_0)`` is forward
  elimination of the right-hand side on the VPU, a column of the matrix
  at a time (exact float32; no inverse is formed). Both are written out:
  as loops over 8-row tiles of scratch the same sums took twice as long
  (read on a v5e, PR 38).
- **A decode row does not pay for a chunk.** A slot with ONE live row
  takes the one-token update of ``kda_recurrent`` on its resident state,
  all of it on the VPU; the kernel chooses from ``q_lens[b]``.

Dead rows inside a live chunk are masked to the identity update (``g = 0,
beta = 0``) here, from ``q_lens``: the caller masks nothing. Every product
that carries the recurrence runs at ``Precision.HIGHEST`` (Mosaic lowers
it on this toolchain; at its default a float32 product is ONE bf16 pass,
``paged_attention._mxu_dtype``): the state is float32 and stays so.

**The rule on shapes** (:func:`serves`): the kernel takes head widths that
fill the lanes, ``K`` and ``V`` multiples of 128 (the published width
is). Other widths (the toy models of the tests: 16) stay on
``kda.kda_chunk``; nothing else chooses the path. On a CPU the kernel runs
interpreted (the tests call :func:`kda_chunk_walk` at small widths).
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

HI = jax.lax.Precision.HIGHEST
#: rows of one chunk (a grid step); of one sub-block inside it; heads a
#: grid step serves; the lanes of a vector register
CHUNK = 64
SUB = 8
_HEADS = 8
_LANES = 128
#: what :func:`grid_counts` counts, in order
COUNTERS = ("kda_grid_steps", "kda_grid_live")

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _interpret():
    return _pa._interpret()


def serves(k, v):
    """The rule on shapes (module docstring): True where the kernel takes
    a recurrence of key width ``k`` and value width ``v``."""
    return k % _LANES == 0 and v % _LANES == 0


def heads_per_step(h):
    """Heads one grid step serves: a sublane tile of them (a block of
    ``[rows, heads, width]`` then keeps the operands' own layout), or all
    where ``h`` is no multiple of it."""
    return _HEADS if h % _HEADS == 0 else h


def grid_counts(q_lens, s):
    """int32 [2] in :data:`COUNTERS` order: the (slot, chunk) grid steps
    of one call over ``s`` rows a slot, and those that held a live row."""
    n = -(-int(s) // CHUNK)
    q = jnp.clip(q_lens.astype(jnp.int32), 0, int(s))
    return jnp.stack([jnp.int32(q.shape[0] * n),
                      jnp.sum((q + (CHUNK - 1)) // CHUNK)]).astype(jnp.int32)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=HI,
                               preferred_element_type=jnp.float32)


def _walk(q_lens, n_chunks, hg_last):
    """Where each slot's grid steps point their operands, from ``q_lens``
    [B] (clipped): (block slot [B], head group [B] or -1, chunk [B]). A
    live slot addresses its own blocks (head group -1: the grid's), the
    chunk index capped at its last live chunk. An idle slot addresses the
    block the walk is on when it gets there -- the last block of the live
    slot before it, or the first block of the first live slot when none is
    before it (slot 0's when every slot is idle) -- so its steps move
    nothing."""
    b = q_lens.shape[0]
    live = q_lens > 0
    idx = jnp.arange(b, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, idx, -1))           # [B]
    first = jnp.where(jnp.any(live), jnp.argmax(live), 0).astype(jnp.int32)
    src = jnp.where(prev >= 0, prev, first)
    last = jnp.maximum((q_lens + (CHUNK - 1)) // CHUNK - 1, 0)
    last = jnp.minimum(last, n_chunks - 1)
    hgrp = jnp.where(live, -1, jnp.where(prev >= 0, hg_last, 0))
    chunk = jnp.where(live | (prev >= 0), last[src], 0)
    return src.astype(jnp.int32), hgrp.astype(jnp.int32), \
        chunk.astype(jnp.int32)


def _in_map(b, h, c, ql, lens, src, hgrp, chunk):
    idle = hgrp[b] >= Z
    return (src[b], jnp.where(idle, chunk[b], jnp.minimum(c, chunk[b])),
            jnp.where(idle, hgrp[b], h), Z)


def _beta_map(b, h, c, ql, lens, src, hgrp, chunk):
    return _in_map(b, h, c, ql, lens, src, hgrp, chunk)[:2] + (Z,)


def _state_map(b, h, c, ql, lens, src, hgrp, chunk):
    return (src[b], jnp.where(hgrp[b] >= Z, hgrp[b], h), Z, Z)


def _out_map(b, h, c, ql, lens, src, hgrp, chunk):
    return (b, c, h, Z)


def _kernel(ql_ref, lens_ref, src_ref, hgrp_ref, chunk_ref, q_ref, k_ref,
            v_ref, g_ref, beta_ref, s_in_ref, o_ref, s_out_ref, *, hg, kd):
    f32 = jnp.float32
    b, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    C = q_ref.shape[0]
    n = ql_ref[b]
    here = n - c * np.int32(C)                # live rows of this chunk
    first = c == Z
    fresh = lens_ref[b] == Z

    def each_head(fn):
        # an int32 index (a ``fori_loop`` between two constants counts in
        # a weak int64 under x64, which Mosaic cannot cast). One head an
        # iteration: two in one basic block are no faster, and the
        # kernel's size is what a warm start pays for (PERF.md section 6)
        def body(i):
            fn(i)
            return i + np.int32(1)
        jax.lax.while_loop(lambda i: i < np.int32(hg), body, Z)

    def beta_of(i, rows):
        """beta of head ``i`` of this step's group, [rows, 1]."""
        blk = beta_ref[0:rows, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
        mine = lane == h * np.int32(hg) + i
        return jnp.sum(jnp.where(mine, blk, 0.0), axis=1, keepdims=True)

    def state_of(i):
        """The head's state as this chunk finds it."""
        return jnp.where(first & fresh, 0.0,
                         jnp.where(first, s_in_ref[i], s_out_ref[i]))

    @pl.when(here <= Z)
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    @pl.when((b == Z) & (h == Z) & first & (ql_ref[src_ref[b]] == Z))
    def _all_idle():
        # slot 0 addresses an idle slot only when no slot is live: the one
        # state block such a walk addresses goes back as it came
        s_out_ref[...] = s_in_ref[...]

    @pl.when((here > Z) & (n == 1))
    def _one_row():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)
        eye = jax.lax.broadcasted_iota(jnp.int32, (kd, kd), 0) == \
            jax.lax.broadcasted_iota(jnp.int32, (kd, kd), 1)

        def col(x):                            # [1, K] -> [K, 1], exact
            return jnp.sum(jnp.where(eye, x, 0.0), axis=1, keepdims=True)

        def head(i):
            S = state_of(i) * col(jnp.exp(g_ref[0:1, i, :]))
            kc = col(k_ref[0:1, i, :])
            u = beta_of(i, 1) * (v_ref[0:1, i, :]
                                 - jnp.sum(S * kc, axis=0, keepdims=True))
            S = S + kc * u
            s_out_ref[i] = S
            o_ref[0:1, i, :] = jnp.sum(S * col(q_ref[0:1, i, :]), axis=0,
                                       keepdims=True)
        each_head(head)

    @pl.when((here > Z) & (n > 1))
    def _chunk():
        row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        alive = row < here
        sub_row = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0)
        lane_c = jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 1)
        rr = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        cc = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        tri = (cc <= rr).astype(f32)
        eye = jax.lax.broadcasted_iota(jnp.int32, (kd, kd), 0) == \
            jax.lax.broadcasted_iota(jnp.int32, (kd, kd), 1)
        neg = np.float32(-np.inf)

        def head(i):
            q, k = q_ref[:, i, :], k_ref[:, i, :]
            g = jnp.where(alive, g_ref[:, i, :], 0.0)
            beta = jnp.where(alive, beta_of(i, C), 0.0)
            G = _dot(tri, g, _NN)                         # running sum
            # A (r < s) and B (r <= s), a sub-block of rows at a time
            a_rows, b_rows = [], []
            for j in range(C // SUB):
                r0 = j * SUB
                Gj, kj, qj = (x[r0:r0 + SUB] for x in (G, k, q))
                Aj = jnp.zeros((SUB, C), f32)
                Bj = jnp.zeros((SUB, C), f32)
                for r in range(SUB):
                    # k_r decayed to every row s >= r of the sub-block
                    kr = kj[r:r + 1] * jnp.exp(jnp.where(
                        sub_row >= r, Gj - Gj[r:r + 1], neg))
                    at = lane_c == r0 + r
                    Aj = jnp.where(at, jnp.sum(kj * kr, axis=1,
                                               keepdims=True), Aj)
                    Bj = jnp.where(at, jnp.sum(qj * kr, axis=1,
                                               keepdims=True), Bj)
                if j:
                    Gm = G[r0 - 1:r0]
                    e = jnp.exp(Gj - Gm)
                    rhs = k * jnp.exp(jnp.where(row < r0, Gm - G, neg))
                    ab = _dot(jnp.concatenate([qj * e, kj * e]), rhs, _NT)
                    Bj, Aj = Bj + ab[:SUB], Aj + ab[SUB:]
                a_rows.append(Aj)
                b_rows.append(Bj)
            L = beta * jnp.where(cc < rr, jnp.concatenate(a_rows), 0.0)
            Bm = jnp.concatenate(b_rows)
            S = state_of(i)
            eG = jnp.exp(G)
            kq = _dot(jnp.concatenate([k * eG, q * eG]), S, _NN)
            # U: forward elimination of beta (V - k_in S), 8 rows a tile
            rhs = beta * (v_ref[:, i, :] - kq[:C])
            u = [rhs[t:t + 8] for t in range(0, C, 8)]
            lt = [L[t:t + 8] for t in range(0, C, 8)]
            for r in range(C - 1):
                ur = u[r // 8][r % 8:r % 8 + 1]
                for t in range((r + 1) // 8, C // 8):
                    u[t] = u[t] - lt[t][:, r:r + 1] * ur
            U = jnp.concatenate(u)
            o = kq[C:] + _dot(Bm, U, _NN)
            o_ref[:, i, :] = jnp.where(alive, o, 0.0)
            g_end = G[C - 1:C]
            dec = jnp.sum(jnp.where(eye, jnp.exp(g_end), 0.0), axis=1,
                          keepdims=True)                  # [K, 1]
            s_out_ref[i] = S * dec + _dot(k * jnp.exp(g_end - G), U, _TN)
        each_head(head)


def kda_chunk_walk(q, k, v, g, beta, state, q_lens, seq_lens):
    """q, k, g: [B, S, H, K]; v: [B, S, H, V]; beta: [B, S, H]; state: [B,
    H, K, V] float32; q_lens: [B] live rows of each slot (its first);
    seq_lens: [B] tokens a slot holds before them (0: the slot starts from
    zeros). Returns (o [B, S, H, V] float32, the state after each slot's
    live rows): what :func:`kda.kda_recurrent` gives on the live rows; a
    dead row's output is 0, and a slot without a live row keeps its state
    as it is (zeroed only when its first row comes)."""
    return _walk_call(q, k, v, g, beta, state, q_lens, seq_lens,
                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _walk_call(q, k, v, g, beta, state, q_lens, seq_lens, *, interpret):
    """The walk and the Pallas call, under one inlined inner jit so that a
    model's layers share a trace (``latent_attention._append_call``)."""
    f32 = jnp.float32
    B, S, H, K = q.shape
    V = v.shape[-1]
    pad = (-S) % CHUNK
    n_chunks = (S + pad) // CHUNK
    hg = heads_per_step(H)

    def rows(a):
        a = a.astype(f32)
        return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) \
            if pad else a

    ql = jnp.clip(q_lens.astype(jnp.int32), 0, S)
    walk = _walk(ql, n_chunks, H // hg - 1)
    wide = lambda w: pl.BlockSpec((None, CHUNK, hg, w),  # noqa: E731
                                  _in_map)
    o, state = pl.pallas_call(
        functools.partial(_kernel, hg=hg, kd=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, H // hg, n_chunks),
            in_specs=[wide(K), wide(K), wide(V), wide(K),
                      pl.BlockSpec((None, CHUNK, H), _beta_map),
                      pl.BlockSpec((None, hg, K, V), _state_map)],
            out_specs=[pl.BlockSpec((None, CHUNK, hg, V), _out_map),
                       pl.BlockSpec((None, hg, K, V), _state_map)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, S + pad, H, V), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            # the blocks double-buffered, and room for a head's values
            vmem_limit_bytes=8 * (CHUNK * (hg * (3 * K + 2 * V) + H)
                                  + 2 * hg * K * V) + (16 << 20)),
        name="kda_chunk_walk",
        interpret=interpret,
    )(ql, seq_lens.astype(jnp.int32), *walk, rows(q), rows(k), rows(v),
      rows(g), rows(beta), state.astype(f32))
    return o[:, :S], state
