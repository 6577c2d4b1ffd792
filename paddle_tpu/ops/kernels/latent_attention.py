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
against the pool, the rows just written included. Both take a step's rows
on ONE row axis: a mixed step's packed ``[T, ...]`` as the decoder holds
it (``cache_layout.RowMap``: slot ``b``'s rows are the ``q_lens[b]`` from
``start[b]`` on), so a latent layer never builds the per-slot view; the
per-slot ``[B, S, ...]`` of a one-token step, a plain forward and the
tests is that axis with ``start[b] = b * S``, through the same
``pallas_call``.

The Pallas kernel follows ``paged_attention_append``'s conventions (PR
26): ``(seq_lens, q_lens)`` scalar-prefetched, and ``start`` beside them;
a slot's rows POSITION-major so the live ones are a prefix, only the row
tiles that a table entry's block is not wholly masked for are computed
(the same :func:`_tile_span` rule), a decode row runs one short tile a
block, an idle slot walks nothing. The wrapper makes ONE head-major
transpose of the rows (``[T, HG, hq, D] -> [HG, T * hq, D]``: row ``t *
hq + g`` of a group is row ``t`` of its head ``g``) and the grid is (head
group, slot, wide entry): a head group's whole q block and output block
stay in VMEM across its slots, fetched and written once, and slot ``b``'s
row tile is ``pl.ds(start[b] * hq + r0, rows)`` of them. That offset has
to lie on a sublane tile (16 rows of bfloat16): where ``hq`` is a
multiple of 16 it does for any ``start`` and the rows are taken as they
come; otherwise the wrapper first moves every slot's first row to a
multiple of :func:`slot_step` with two gathers
(``power_retention_walk``'s). A slot's last row tile is read and written
whole: the rows past its own belong to slots the grid comes to later (the
slots ascend along the axis), or to nobody, and the wrapper hands back
zeros for every row that holds no token. A grid step is one WIDE entry
of one slot's table for ``hq`` of the heads: ``n`` consecutive table
entries (:func:`entries_per_step`) whose blocks make one key tile of ``n
* block_size`` latents, read once for all of those heads and attended in
one update a row tile. On a CPU the dense fallback gathers the slot's
context, through the per-slot view where the rows come packed (tests
only; :func:`latent_attention_enabled`).

``window`` (static, None by default): a row at position ``t`` attends
``t - window < s <= t`` alone. The mask gains that rule beside the causal
one and a wide entry that ends before the first row's window is skipped;
None traces none of it. A windowed layer's cache is a ring a slot
(``cache_layout.WindowedLatent``): the table it hands this kernel is a
short one derived from the lengths, and ``seq_lens`` and a packed row's
position come less the position that table starts at, which changes
nothing here: the kernel reads differences of positions alone.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa
from .paged_attention import (NEG_INF, Z, _apd_blk, _apd_walk, _div_i32,
                              _tile_span)
from .power_retention_walk import _aligned

#: rows of one query row tile at most; latents of one key tile at most;
#: VMEM a grid step may hold: at the two served shapes (128 heads x 528
#: rows, 32 x 272) it admits 16 and 32 heads a step, multiples of a
#: sublane tile, so the rows are taken as they come
_ROW_TILE_MAX = 512
_ROW_SUBTILE = 32
_KEY_TILE_MAX = 256
_VMEM_BUDGET = 64 << 20
#: rows of a sublane tile of the widest packing (bfloat16; float32's is 8)
_SUBLANE = 16
#: what a ``-1`` table entry inside a wide entry adds to its columns'
#: positions: past every row's, and times a head group still an int32
_DEAD_ENTRY = np.int32(1 << 20)


def _interpret():
    return _pa._interpret()


def latent_attention_enabled():
    """True where the Pallas kernel serves (a real TPU, the paged-attention
    flag on): the CPU takes the dense fallback, as the paged K/V path."""
    return _pa.paged_attention_enabled()


def latent_pool_write(pool, new, block_tables, seq_lens, q_lens, rows=None):
    """Write ``new`` [B, S, D] (row i of slot b is position ``seq_lens[b] +
    i``; rows at or past ``q_lens[b]`` are dropped) into ``pool`` [NB, BS,
    D]. With ``rows`` (a mixed step's ``cache_layout.RowMap``) ``new`` is
    the packed [T, D]: row ``t`` is position ``rows.pos[t]`` of slot
    ``rows.slot[t]`` where ``rows.live[t]``. A position whose table entry
    is ``-1`` lands in the trailing scratch block ``NB - 1``."""
    nb, bs, d = pool.shape
    mb = block_tables.shape[1]
    # the table lookup and the rows that hold a token, each taken where
    # the scatter below asks for it
    if rows is None:
        i = jnp.arange(new.shape[1], dtype=jnp.int32)[None, :]
        pos = seq_lens.astype(jnp.int32)[:, None] + i
        entry = lambda blk: jnp.take_along_axis(  # noqa: E731
            block_tables.astype(jnp.int32), blk, axis=1)
        held = lambda: i < q_lens.astype(jnp.int32)[:, None]  # noqa: E731
    else:
        pos = rows.pos
        entry = lambda blk: block_tables.astype(  # noqa: E731
            jnp.int32)[rows.slot, blk]
        held = lambda: rows.live  # noqa: E731
    phys = entry(jnp.minimum(pos // bs, mb - 1))
    phys = jnp.where(phys < 0, nb - 1, phys)
    live = held() & (pos // bs < mb)
    # dead rows scatter out of range and are dropped
    phys = jnp.where(live, phys, nb)
    flat = (phys * bs + pos % bs).reshape(-1)
    out = pool.reshape(nb * bs, d).at[flat].set(
        new.reshape(-1, d).astype(pool.dtype), mode="drop")
    return out.reshape(pool.shape)


def latent_attention_dense(q, pool, block_tables, seq_lens, q_lens, dv,
                           window=None):
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
    if window is not None:
        mask &= t[None, None, :] > pos[:, :, None] - np.int32(window)
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


def slot_step(hq):
    """Rows of the row axis a slot's first row is a multiple of, so that
    its first row of a head group (``hq`` kernel rows a row) lies on a
    sublane tile: 1 where ``hq`` is a multiple of the tile and the axis
    is taken as it comes."""
    return _SUBLANE // math.gcd(hq, _SUBLANE)


def _room(hq, b, t):
    """Rows of the axis once each of ``b`` slots may start on its
    :func:`slot_step`."""
    a = slot_step(hq)
    return -(-(t + b * (a - 1)) // a) * a


def _tail(hq, s):
    """Rows past the axis' last: a slot's last row tile is read and
    written whole, whatever it holds."""
    return -(-_row_tile(hq, s) // hq)


def held_rows(hq, b, t, s):
    """Kernel rows of one head group's block (``hq`` a row of the axis):
    the ``t`` rows with their slots' room, and the tail."""
    return (_room(hq, b, t) + _tail(hq, s)) * hq


def _vmem_bytes(hq, held, s, d, dv, kt, isz):
    """VMEM of one grid step with ``hq`` heads against a key tile of
    ``kt`` latents: a head group's q and out blocks of ``held`` rows and
    the tile's blocks (double-buffered), the tile itself, a row tile's
    f32 scores, and for the ``hq * s`` rows one slot may hold the f32
    accumulator and the running max and norm (one lane wide, padded to
    128)."""
    return (2 * held * (d + dv) * isz + 3 * kt * d * isz
            + _row_tile(hq, s) * kt * 4 + hq * s * (dv + 2 * 128) * 4)


def heads_per_step(h, b, t, s, d, dv, bs, isz=2):
    """Query heads one grid step serves, for ``t`` rows of ``b`` slots of
    at most ``s`` rows each: the most (a divisor of ``h``) whose buffers
    fit ``_VMEM_BUDGET`` beside the widest key tile a table of
    ``bs``-latent blocks may get."""
    kt = max(_KEY_TILE_MAX, bs)
    for hq in range(h, 1, -1):
        if h % hq == 0 and _vmem_bytes(hq, held_rows(hq, b, t, s), s, d, dv,
                                       kt, isz) <= _VMEM_BUDGET:
            return hq
    return 1


def _q_index_map(h, b, j, tables_ref, lens_ref, qlens_ref, start_ref):
    # a head group's rows of every slot: one block for the whole of its
    # walk, fetched and written once
    return (h, Z, Z)


def _pool_index_map(bs, mb, n, i):
    def im(h, b, j, tables_ref, lens_ref, qlens_ref, start_ref):
        # block ``i`` of wide entry ``j``, on the append kernel's walk:
        # entries past the window's last block re-map to it (no copy); an
        # idle slot stays on one block
        jj = _apd_walk(lens_ref, qlens_ref, b, j * np.int32(n) + np.int32(i),
                       bs, mb)
        return (jnp.maximum(tables_ref[b, jj], Z), Z, Z)
    return im


def _kernel(tables_ref, lens_ref, qlens_ref, start_ref, q_ref, *rest, bs, mb,
            n, s_chunk, g, tr, ts, dv, window):
    k_refs, (o_ref, m_ref, l_ref, acc_ref) = rest[:n], rest[n:]
    f32 = jnp.float32
    b = pl.program_id(1)
    j = pl.program_id(2)
    kt = n * bs                               # latents of the key tile
    kt_i, tr_i = np.int32(kt), np.int32(tr)
    L = lens_ref[b]
    QL = jnp.minimum(qlens_ref[b], np.int32(s_chunk))
    # the slot's first row in the head group's block: on a sublane tile
    # (the wrapper's promise), so is every row tile's first row
    base = pl.multiple_of(start_ref[b] * np.int32(g), _SUBLANE)
    j_last = _apd_blk(lens_ref, qlens_ref, b, bs, mb, True)
    # the wide entry's ``n`` table entries; positions come from the
    # UNCLAMPED index, so one past the window's last block (its operand
    # re-read that block) lies past every row and the causal rule masks it
    ents = [j * np.int32(n) + np.int32(i) for i in range(n)]
    held = [tables_ref[b, _apd_walk(lens_ref, qlens_ref, b, e, bs, mb)] >= Z
            for e in ents]
    live = (ents[0] <= j_last) & functools.reduce(jnp.logical_or, held) \
        & (QL > Z)
    if window is not None:
        # a wide entry whose last latent lies before the first row's window
        # is nobody's
        live &= j * kt_i + np.int32(kt - 1 + window) > L
    t_lo, t_end = _tile_span(L, QL, j, g, kt, tr, jnp, _div_i32)

    def tiles(lo, hi, fn):
        def body(t, c):
            fn(pl.multiple_of(t * tr_i, tr))
            return c
        jax.lax.fori_loop(lo, hi, body, Z)

    def block_rows(r0, nr):
        """Rows ``[r0, r0 + nr)`` of the slot, in the head group's block."""
        return pl.ds(pl.multiple_of(base + r0, math.gcd(tr, _SUBLANE)), nr)

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
            s = jax.lax.dot_general(q_ref[0, block_rows(r0, nr), :], k_tile,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32)
            if masked:
                # row r (chunk index r // g) sees position p iff
                # (p - lens) * g <= r
                r = r0 + jax.lax.broadcasted_iota(jnp.int32, (nr, kt), 0)
                seen = rel * np.int32(g) <= r
                if window is not None:
                    # and iff p - lens > r // g - window
                    seen &= (rel + np.int32(window)) * np.int32(g) > r
                s = jnp.where(seen, s, NEG_INF)
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
    if window is not None:
        # ... and its first latent inside the last row's window
        needs_mask |= j * kt_i + np.int32(window) <= L + QL - np.int32(1)

    @pl.when(live & needs_mask)
    def _attend_window():
        attend(True)

    @pl.when(live & jnp.logical_not(needs_mask))
    def _attend_history():
        attend(False)

    @pl.when(j == np.int32(mb // n - 1))
    def _finalize():
        # whole tiles: the last one's rows past the slot's own are the
        # rows of slots the grid comes to later, or nobody's
        def tile(r0):
            rows = pl.ds(r0, tr)
            l = jnp.maximum(l_ref[rows, :], np.float32(1e-30))
            o_ref[0, block_rows(r0, tr), :] = (acc_ref[rows, :] / l).astype(
                o_ref.dtype)
        tiles(Z, t_end, tile)


def latent_attention_append(q, pool, block_tables, seq_lens, q_lens, dv,
                            rows=None, window=None):
    """q: [B, S, H, D], scaled and with the key up-projection absorbed;
    pool: [NB, BS, D] holding every position below ``seq_lens + q_lens``
    (write the step's rows first: :func:`latent_pool_write`);
    block_tables: [B, MB]; row i of slot b attends positions ``<=
    seq_lens[b] + i``. Returns [B, S, H, dv] in q's dtype: the
    attention-weighted first ``dv`` columns. With ``rows`` (a mixed
    step's ``cache_layout.RowMap``) q is the packed [T, H, D], slot b's
    rows the ``q_lens[b]`` from ``rows.start[b]`` on, and so is what
    comes back, [T, H, dv]. A row that holds no token (at or past
    ``q_lens`` of its slot, the packed axis' padding) comes back zero.
    ``window`` (static; None: the whole context): a row at position ``t``
    attends ``t - window < s <= t`` alone, itself and the ``window - 1``
    before it; None lowers to the kernel without one."""
    window = None if window is None else int(window)
    if latent_attention_enabled():
        if rows is None:
            return _append_call(q, pool, block_tables, seq_lens, q_lens,
                                dv=int(dv), window=window,
                                interpret=_interpret())
        return _append_rows(q, pool, block_tables, seq_lens, q_lens,
                            rows.start, width=rows.width, dv=int(dv),
                            every=None, window=window,
                            interpret=_interpret())
    if rows is None:
        return latent_attention_dense(q, pool, block_tables, seq_lens,
                                      q_lens, dv, window)
    o = rows.from_slots(latent_attention_dense(
        rows.to_slots(q), pool, block_tables, seq_lens, q_lens, dv, window))
    return jnp.where(rows.live[:, None, None], o, 0.0)


@functools.partial(jax.jit, static_argnames=("dv", "window", "interpret"),
                   inline=True)
def _append_call(q, pool, block_tables, seq_lens, q_lens, *, dv, interpret,
                 window=None):
    """The per-slot form ``q [B, S, H, D]``: the row axis ``[B * S]`` with
    slot ``b``'s rows from ``b * S``."""
    B, S, H, D = q.shape
    start = jnp.arange(B, dtype=jnp.int32) * np.int32(S)
    out = _append_rows(q.reshape(B * S, H, D), pool, block_tables, seq_lens,
                       q_lens, start, width=S, dv=dv, every=S,
                       window=window, interpret=interpret)
    return out.reshape(B, S, H, dv)


@functools.partial(jax.jit, static_argnames=("width", "dv", "every",
                                             "window", "interpret"),
                   inline=True)
def _append_rows(q, pool, block_tables, seq_lens, q_lens, start, *, width,
                 dv, every, interpret, window=None):
    """The rows head-major with every slot's first row on a sublane tile,
    and the Pallas call, under one inlined inner jit so that a model's
    layers share a trace (``paged_attention._append_call``). ``q`` [T, H,
    D]: slot ``b``'s rows are the ``min(q_lens[b], width)`` from
    ``start[b]`` on, in position order, slots ascending; ``every``: the
    starts are known to be this many rows apart (None: they are not)."""
    T, H, D = q.shape
    NB, BS, Dk = pool.shape
    assert D == Dk, (q.shape, pool.shape)
    B, MB = block_tables.shape
    S = int(width)
    hq = heads_per_step(H, B, T, S, D, dv, BS, q.dtype.itemsize)
    n = entries_per_step(MB, BS)
    HG = H // hq
    tr = _row_tile(hq, S)
    ts = _ROW_SUBTILE if tr % _ROW_SUBTILE == 0 else tr
    step = slot_step(hq)
    ql = jnp.minimum(q_lens.astype(jnp.int32), np.int32(S))
    start = start.astype(jnp.int32)
    moved = step > 1 and (every is None or every % step != 0)
    nal = _room(hq, B, T) if moved else T
    first, old_of, new_of, live = _aligned(start, ql, T, nal, step)
    if moved:
        q = jnp.take(q, old_of, axis=0)
    else:
        first = start
    # a row tile of nobody's rows at the end, then [rows, H, D] -> [HG,
    # rows * hq, D]: row i * hq + g of group hg is row i of head hg * hq
    # + g
    rows = nal + _tail(hq, S)
    held = rows * hq
    q = jnp.pad(q.astype(pool.dtype), [(0, rows - nal), (0, 0), (0, 0)])
    q3 = jnp.transpose(q.reshape(rows, HG, hq, D),
                       (1, 0, 2, 3)).reshape(HG, held, D)
    kernel = functools.partial(_kernel, bs=BS, mb=MB, n=n, s_chunk=S, g=hq,
                               tr=tr, ts=ts, dv=dv, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(HG, B, MB // n),
            in_specs=[pl.BlockSpec((1, held, D), _q_index_map)] + [
                pl.BlockSpec((1, BS, D), _pool_index_map(BS, MB, n, i))
                for i in range(n)],
            out_specs=pl.BlockSpec((1, held, dv), _q_index_map),
            scratch_shapes=[
                pltpu.VMEM((S * hq, 1), jnp.float32),
                pltpu.VMEM((S * hq, 1), jnp.float32),
                pltpu.VMEM((S * hq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((HG, held, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(
                32 << 20,
                _vmem_bytes(hq, held, S, D, dv, n * BS, q.dtype.itemsize)
                + (16 << 20))),
        name="latent_attention_append",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), ql, first,
      q3, *([pool] * n))
    out = jnp.transpose(out[:, :nal * hq].reshape(HG, nal, hq, dv),
                        (1, 0, 2, 3)).reshape(nal, H, dv)
    if moved:
        out = jnp.take(out, new_of, axis=0)
    return jnp.where(live[:, None, None], out, 0.0)
