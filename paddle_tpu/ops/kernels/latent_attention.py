"""Attention over a paged LATENT pool (multi-head latent attention in its
absorbed form): one shared "kv head" of width ``D`` (the compressed latent
and the position-free key part, 512 + 64) that every query head reads, the
values being the entry's first ``dv`` columns.

The pool is ``[num_blocks, block_size, D]`` on the block tables of the
paged K/V pools (``ops/kernels/paged_attention.py``): a token costs
``D`` values a layer, whatever the number of heads, and no step expands
the context into per-head keys and values. The caller absorbs the key
half of the up-projection into the queries (``q_abs = W_kb q_nope``) and
applies the value half to the output, so the kernel is plain attention of
``H`` query heads against one key of ``D`` with values ``key[:dv]``.

Two functions: :func:`latent_pool_write` scatters a step's new entries
into their blocks (plain XLA, in place under donation; ``-1`` table
entries go to the pool's trailing scratch block, as in the paged K/V
kernels), and :func:`latent_attention_append` attends the step's rows
against the pool, the rows just written included. The Pallas kernel
follows ``paged_attention_append``'s conventions (PR 26): ``(seq_lens,
q_lens)`` scalar-prefetched, a slot's rows POSITION-major so the live ones
are a prefix, only the row tiles that a table entry's block is not wholly
masked for are computed (the same :func:`_tile_span` rule), a decode row
runs one short tile a block, an idle slot walks nothing. A grid step is
one WIDE entry of one slot's table for ``hq`` of the heads: ``n``
consecutive table entries (:func:`entries_per_step`) whose blocks make one
key tile of ``n * block_size`` latents, read once for all of those heads
and attended in one update a row tile. On a CPU the dense fallback gathers
the slot's context (tests only; :func:`latent_attention_enabled`).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa
from .paged_attention import (NEG_INF, Z, _apd_blk, _apd_walk, _div_i32,
                              _tile_span)

#: rows of one query row tile at most; latents of one key tile at most;
#: VMEM a grid step may hold
_ROW_TILE_MAX = 512
_ROW_SUBTILE = 32
_KEY_TILE_MAX = 256
_VMEM_BUDGET = 40 << 20
#: what a ``-1`` table entry inside a wide entry adds to its columns'
#: positions: past every row's, and times a head group still an int32
_DEAD_ENTRY = np.int32(1 << 20)


def _interpret():
    return _pa._interpret()


def latent_attention_enabled():
    """True where the Pallas kernel serves (a real TPU, the paged-attention
    flag on): the CPU takes the dense fallback, as the paged K/V path."""
    return _pa.paged_attention_enabled()


def latent_pool_write(pool, new, block_tables, seq_lens, q_lens):
    """Write ``new`` [B, S, D] (row i of slot b is position ``seq_lens[b] +
    i``; rows at or past ``q_lens[b]`` are dropped) into ``pool`` [NB, BS,
    D]. A position whose table entry is ``-1`` lands in the trailing
    scratch block ``NB - 1``."""
    nb, bs, _ = pool.shape
    b, s, d = new.shape
    mb = block_tables.shape[1]
    i = jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = seq_lens.astype(jnp.int32)[:, None] + i
    blk = jnp.minimum(pos // bs, mb - 1)
    phys = jnp.take_along_axis(block_tables.astype(jnp.int32), blk, axis=1)
    phys = jnp.where(phys < 0, nb - 1, phys)
    live = (i < q_lens.astype(jnp.int32)[:, None]) & (pos // bs < mb)
    # dead rows scatter out of range and are dropped
    phys = jnp.where(live, phys, nb)
    flat = (phys * bs + pos % bs).reshape(-1)
    out = pool.reshape(nb * bs, d).at[flat].set(
        new.reshape(b * s, d).astype(pool.dtype), mode="drop")
    return out.reshape(pool.shape)


def latent_attention_dense(q, pool, block_tables, seq_lens, q_lens, dv):
    """The plain-XLA form: gather each slot's context from the pool and
    attend with a mask. For the CPU tests; gathers a whole context."""
    b, s, h, d = q.shape
    nb, bs, _ = pool.shape
    mb = block_tables.shape[1]
    ctx = pool[jnp.maximum(block_tables, 0)].reshape(b, mb * bs, d)
    sc = jnp.einsum("bshd,btd->bhst", q.astype(jnp.float32),
                    ctx.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
    pos = seq_lens.astype(jnp.int32)[:, None] + \
        jnp.arange(s, dtype=jnp.int32)[None, :]
    t = jnp.arange(mb * bs, dtype=jnp.int32)
    mask = t[None, None, :] <= pos[:, :, None]
    p = jax.nn.softmax(jnp.where(mask[:, None], sc, NEG_INF), axis=-1)
    out = jnp.einsum("bhst,btd->bshd", p, ctx[..., :dv].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    live = jnp.arange(s)[None, :] < q_lens[:, None]
    return jnp.where(live[:, :, None, None], out, 0.0).astype(q.dtype)


def _row_tile(hq, s):
    rows = hq * s
    if rows <= _ROW_TILE_MAX:
        return rows
    for tr in range(_ROW_TILE_MAX, 15, -16):
        if rows % tr == 0:
            return tr
    return rows


def entries_per_step(mb, bs):
    """Table entries one grid step holds (``n``): the most, halving from
    a key tile of ``_KEY_TILE_MAX`` latents, that divide a table of ``mb``
    entries; 1 is a grid step a table entry. The kernel's wrapper and the
    engine's booking of the walk's grid steps both ask this."""
    n = max(_KEY_TILE_MAX // bs, 1)
    while mb % n:
        n //= 2
    return n


def _vmem_bytes(hq, s, d, dv, kt, isz):
    """VMEM of one grid step with ``hq`` heads against a key tile of
    ``kt`` latents: the q and out tiles and the tile's blocks
    (double-buffered), the tile itself, a row tile's f32 scores, the f32
    accumulator and the running max and norm (one lane wide, padded to
    128)."""
    rows = hq * s
    return (2 * rows * d * isz + 2 * rows * dv * isz + 3 * kt * d * isz
            + _row_tile(hq, s) * kt * 4 + rows * (dv + 2 * 128) * 4)


def heads_per_step(h, s, d, dv, bs, isz=2):
    """Query heads one grid step serves: the most (a divisor of ``h``)
    whose buffers fit ``_VMEM_BUDGET`` beside the widest key tile a table
    of ``bs``-latent blocks may get."""
    kt = max(_KEY_TILE_MAX, bs)
    for hq in range(h, 1, -1):
        if h % hq == 0 and _vmem_bytes(hq, s, d, dv, kt, isz) <= _VMEM_BUDGET:
            return hq
    return 1


def _q_index_map(b, h, j, tables_ref, lens_ref, qlens_ref):
    return (b, h, Z, Z)


def _pool_index_map(bs, mb, n, i):
    def im(b, h, j, tables_ref, lens_ref, qlens_ref):
        # block ``i`` of wide entry ``j``, on the append kernel's walk:
        # entries past the window's last block re-map to it (no copy); an
        # idle slot stays on one block
        jj = _apd_walk(lens_ref, qlens_ref, b, j * np.int32(n) + np.int32(i),
                       bs, mb)
        return (jnp.maximum(tables_ref[b, jj], Z), Z, Z)
    return im


def _kernel(tables_ref, lens_ref, qlens_ref, q_ref, *rest, bs, mb, n,
            s_chunk, g, tr, ts, dv):
    k_refs, (o_ref, m_ref, l_ref, acc_ref) = rest[:n], rest[n:]
    f32 = jnp.float32
    b = pl.program_id(0)
    j = pl.program_id(2)
    kt = n * bs                               # latents of the key tile
    kt_i, tr_i = np.int32(kt), np.int32(tr)
    L = lens_ref[b]
    QL = jnp.minimum(qlens_ref[b], np.int32(s_chunk))
    j_last = _apd_blk(lens_ref, qlens_ref, b, bs, mb, True)
    # the wide entry's ``n`` table entries; positions come from the
    # UNCLAMPED index, so one past the window's last block (its operand
    # re-read that block) lies past every row and the causal rule masks it
    ents = [j * np.int32(n) + np.int32(i) for i in range(n)]
    held = [tables_ref[b, _apd_walk(lens_ref, qlens_ref, b, e, bs, mb)] >= Z
            for e in ents]
    live = (ents[0] <= j_last) & functools.reduce(jnp.logical_or, held) \
        & (QL > Z)
    t_lo, t_end = _tile_span(L, QL, j, g, kt, tr, jnp, _div_i32)

    def tiles(lo, hi, fn):
        def body(t, c):
            fn(pl.multiple_of(t * tr_i, tr))
            return c
        jax.lax.fori_loop(lo, hi, body, Z)

    @pl.when(j == Z)
    def _init():
        def tile(r0):
            rows = pl.ds(r0, tr)
            m_ref[rows, :] = jnp.full((tr, 1), NEG_INF, f32)
            l_ref[rows, :] = jnp.zeros((tr, 1), f32)
            acc_ref[rows, :] = jnp.zeros((tr, dv), f32)
        tiles(Z, t_end, tile)

    def attend(masked):
        k_tile = k_refs[0][0] if n == 1 else jnp.concatenate(
            [r[0] for r in k_refs], axis=0)   # [kt, D]
        v_tile = k_tile[:, :dv]
        if masked:
            # a column's position less ``lens``; a ``-1`` entry's columns
            # are sent past every row
            col = jax.lax.broadcasted_iota(jnp.int32, (1, kt), 1)
            rel = j * kt_i - L + col
            for i in range(n):
                dead = jnp.where(held[i], Z, _DEAD_ENTRY)
                rel = jnp.where((col >= i * bs) & (col < (i + 1) * bs),
                                rel + dead, rel)

        def update(r0, nr):
            rows = pl.ds(r0, nr)
            s = jax.lax.dot_general(q_ref[0, 0, rows, :], k_tile,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32)
            if masked:
                # row r (chunk index r // g) sees position p iff
                # (p - lens) * g <= r
                r = r0 + jax.lax.broadcasted_iota(jnp.int32, (nr, kt), 0)
                s = jnp.where(rel * np.int32(g) <= r, s, NEG_INF)
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
                p, axis=1, keepdims=True)
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + \
                jax.lax.dot_general(p.astype(v_tile.dtype), v_tile,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=f32)
            m_ref[rows, :] = m_new

        def tile(r0):
            if ts == tr:
                return update(r0, tr)
            short = QL * np.int32(g) - r0 <= np.int32(ts)
            pl.when(short)(lambda: update(r0, ts))
            pl.when(jnp.logical_not(short))(lambda: update(r0, tr))
        tiles(t_lo, t_end, tile)

    # a wide entry whose last latent lies before ``lens`` is history: every
    # live row sees all of it, unless one of its table entries is ``-1``
    needs_mask = (j * kt_i + np.int32(kt - 1) >= L) | \
        jnp.logical_not(functools.reduce(jnp.logical_and, held))

    @pl.when(live & needs_mask)
    def _attend_window():
        attend(True)

    @pl.when(live & jnp.logical_not(needs_mask))
    def _attend_history():
        attend(False)

    @pl.when(j == np.int32(mb // n - 1))
    def _finalize():
        def live_tile(r0):
            rows = pl.ds(r0, tr)
            l = jnp.maximum(l_ref[rows, :], np.float32(1e-30))
            o_ref[0, 0, rows, :] = (acc_ref[rows, :] / l).astype(o_ref.dtype)

        def idle_tile(r0):
            o_ref[0, 0, pl.ds(r0, tr), :] = jnp.zeros((tr, dv), o_ref.dtype)
        tiles(Z, t_end, live_tile)
        tiles(t_end, np.int32(q_ref.shape[2] // tr), idle_tile)


def latent_attention_append(q, pool, block_tables, seq_lens, q_lens, dv):
    """q: [B, S, H, D], scaled and with the key up-projection absorbed;
    pool: [NB, BS, D] holding every position below ``seq_lens + q_lens``
    (write the step's rows first: :func:`latent_pool_write`);
    block_tables: [B, MB]; row i of slot b attends positions ``<=
    seq_lens[b] + i``. Returns [B, S, H, dv] in q's dtype: the
    attention-weighted first ``dv`` columns. Rows at or past ``q_lens``
    are padding whose outputs the caller ignores (zeros from the
    fallback and from row tiles the kernel never ran)."""
    if not latent_attention_enabled():
        return latent_attention_dense(q, pool, block_tables, seq_lens,
                                      q_lens, dv)
    return _append_call(q, pool, block_tables, seq_lens, q_lens, dv=int(dv),
                        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("dv", "interpret"), inline=True)
def _append_call(q, pool, block_tables, seq_lens, q_lens, *, dv, interpret):
    """The transposes and the Pallas call, under one inlined inner jit so
    that a model's layers share a trace (``paged_attention._append_call``)."""
    B, S, H, D = q.shape
    NB, BS, Dk = pool.shape
    assert D == Dk, (q.shape, pool.shape)
    MB = block_tables.shape[1]
    hq = heads_per_step(H, S, D, dv, BS, q.dtype.itemsize)
    n = entries_per_step(MB, BS)
    HG = H // hq
    tr = _row_tile(hq, S)
    ts = _ROW_SUBTILE if tr % _ROW_SUBTILE == 0 else tr
    # [B, S, H, D] -> [B, HG, S*hq, D]: row i*hq + g of group hg is
    # position i of head hg*hq + g
    q4 = jnp.transpose(q.reshape(B, S, HG, hq, D),
                       (0, 2, 1, 3, 4)).reshape(B, HG, S * hq, D)
    q4 = q4.astype(pool.dtype)
    kernel = functools.partial(_kernel, bs=BS, mb=MB, n=n, s_chunk=S, g=hq,
                               tr=tr, ts=ts, dv=dv)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, HG, MB // n),
            in_specs=[pl.BlockSpec((1, 1, S * hq, D), _q_index_map)] + [
                pl.BlockSpec((1, BS, D), _pool_index_map(BS, MB, n, i))
                for i in range(n)],
            out_specs=pl.BlockSpec((1, 1, S * hq, dv), _q_index_map),
            scratch_shapes=[
                pltpu.VMEM((S * hq, 1), jnp.float32),
                pltpu.VMEM((S * hq, 1), jnp.float32),
                pltpu.VMEM((S * hq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, HG, S * hq, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(
                32 << 20,
                _vmem_bytes(hq, S, D, dv, n * BS, q.dtype.itemsize)
                + (16 << 20))),
        name="latent_attention_append",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_lens.astype(jnp.int32), q4, *([pool] * n))
    out = out.reshape(B, HG, S, hq, dv)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, S, H, dv)
