"""Learned-sparse attention over a paged latent pool: a row attends the
``top_k`` positions of its context that a lightweight INDEXER scores
highest, not the whole context. Beside the latent pool ``[NB, BS, D]`` a
layer keeps an INDEX pool ``[NB, BS, di]`` on the same block tables: one
index key a token, written with :func:`latent_attention.latent_pool_write`.

Three functions, each on ONE row axis (a mixed step's packed rows, a
one-token step's row a slot, a plain forward's ``[B * S]``; :class:`Rows`
says which slot and position each row is):

1. :func:`index_scores`: ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])``
   of a row against every index key of ITS slot's table, float32, ``[T,
   MB * BS]``. Only the key tiles up to a slot's last position are
   computed; what lies past a row's position is unspecified and
   :func:`select` masks it. On a TPU the Pallas kernel
   ``dsa_index_scores`` (:func:`_scores_call`: a row tile for one slot
   against one key tile a grid step, every head in one product);
   elsewhere :func:`index_scores_xla`, which the tests hold it to.
2. :func:`select`: the exact ``min(top_k, pos + 1)`` causal positions of
   largest score a row, ties to the lower position, as positions ``[T,
   k]`` in ascending order and which of them a short row has. By a
   search for the ``k``-th largest value over the float32 bits and a
   two-level compaction, in plain XLA and without a gather: a block's
   counts reach an output by a product with a one-hot on the MXU
   (:func:`_compact`). ``jax.lax.top_k`` gives the same set and on the
   TPU sorts the whole row (20 ms for 528 rows of 33k against 3.4;
   PERF.md section 6, PRs 45 and 47): the tests hold the search to it.
3. :func:`sparse_attend`: absorbed latent attention of a row over ITS
   ``k`` selected latents, values the first ``dv`` columns. A row's
   latents are ``k`` separate entries of the pool, and a copy an entry is
   what a plain gather costs on the chip (26 ns an entry, 29 of 36 ms a
   layer at the cell's shapes). On a TPU the Pallas kernel
   ``dsa_sparse_attend`` (:func:`_attend_call`) copies a slot's whole
   context into VMEM once, packed two values a 32-bit word, and a row
   gathers its entries THERE, a vector load and store a 128 words;
   elsewhere :func:`sparse_attend_xla`, the plain gather, which the tests
   hold it to.

The pool is never attended whole (a mixed step at 32k of context would
pay sixteen times the selected set's work); what stays dense is the
indexer's scoring, which is the mechanism's own cost. The operations and
bytes each part is held to are in ``benchmark/kernels/dsa_index.py`` and
``dsa_attend.py``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import latent_attention as _lat
from .paged_attention import NEG_INF, Z

#: device-side counts of one step, in order: live rows of the indexed
#: layers, causal pairs their indexers scored, positions selected (the sum
#: of ``min(top_k, pos + 1)``), and the positions the windowed layers' rows
#: read (the sum of ``min(window, pos + 1)``)
COUNTERS = ("dsa_rows", "dsa_keys_scored", "dsa_keys_selected",
            "win_keys_live")
#: id on a step's ``pt:engine.emit`` span -> the counters whose sum of that
#: step it carries
EMIT_IDS = {"selected_keys": ("dsa_keys_selected",),
            "scored_keys": ("dsa_keys_scored",),
            "indexed_rows": ("dsa_rows",),
            "window_keys": ("win_keys_live",)}

#: index keys of one tile of the scoring kernel, at most
_KEY_TILE_MAX = 512


class Rows:
    """Which slot and position each row of the axis is: ``slot`` [T],
    ``pos`` [T] (absolute), ``live`` [T] (it holds a token), ``start`` [B]
    a slot's first row, ``q_lens`` [B] its live rows, ``width`` the most
    rows a slot may have (static). From a mixed step's
    ``cache_layout.RowMap``, or from the per-slot form ``[B, S]``."""
    __slots__ = ("slot", "pos", "live", "start", "q_lens", "width")

    def __init__(self, lead, seq_lens, q_lens, rowmap=None):
        if rowmap is not None:
            for name in self.__slots__:
                setattr(self, name, getattr(rowmap, name))
            return
        b, s = lead
        t = jnp.arange(b * s, dtype=jnp.int32)
        self.slot, col = t // np.int32(s), t % np.int32(s)
        self.q_lens = jnp.minimum(q_lens.astype(jnp.int32), np.int32(s))
        self.pos = seq_lens.astype(jnp.int32)[self.slot] + col
        self.live = col < self.q_lens[self.slot]
        self.start = jnp.arange(b, dtype=jnp.int32) * np.int32(s)
        self.width = int(s)


def entries_per_step(mb, bs):
    """Table entries one tile of the scoring loop holds: the most that
    divide a table of ``mb`` entries and make a tile of at most
    ``_KEY_TILE_MAX`` index keys."""
    n = max(_KEY_TILE_MAX // bs, 1)
    while mb % n:
        n -= 1
    return n


def counts(rows, top_k=None, window=None):
    """int32 [4] in :data:`COUNTERS` order for one layer's step: an
    indexed layer (``top_k``) counts the first three, a windowed one
    (``window``) the last."""
    live = rows.live.astype(jnp.int32)
    ctx = rows.pos + 1
    zero = jnp.int32(0)

    def total(x):
        return jnp.sum(live * x, dtype=jnp.int32)
    if window is not None:
        return jnp.stack([zero, zero, zero,
                          total(jnp.minimum(ctx, np.int32(window)))])
    return jnp.stack([total(1), total(ctx),
                      total(jnp.minimum(ctx, np.int32(top_k))), zero])


def index_scores(qi, w, index_pool, block_tables, rows):
    """qi: [T, J, di] the rows' index queries (the pool's dtype), w: [T,
    J] float32 head weights; index_pool: [NB, BS, di]; block_tables: [B,
    MB]. Returns [T, MB * BS] float32: row t against the index keys of
    slot ``rows.slot[t]``'s table in position order, for the positions up
    to the slot's last; the rest (and a dead row) is unspecified. On a
    TPU the Pallas kernel (:func:`_scores_call`), elsewhere plain XLA."""
    if _lat.latent_attention_enabled():
        return _scores_call(qi, w, index_pool, block_tables, rows.start,
                            rows.q_lens, _first_pos(rows),
                            interpret=_lat._interpret())
    return index_scores_xla(qi, w, index_pool, block_tables, rows)


def _first_pos(rows):
    """[B] the position of a slot's first row of the step (0 for a slot
    without one)."""
    at = jnp.minimum(rows.start, rows.pos.shape[0] - 1)
    return jnp.where(rows.q_lens > 0, rows.pos[at], 0)


#: rows of one work item of the scoring kernel (a row tile), index heads
#: of one product in it, and the VMEM its call may use
_ROW_TILE = 32
_HEAD_GROUP = 16
_VMEM_LIMIT = 56 << 20


def _work_table(start, q_lens, n_tiles):
    """The scoring kernel's walk: its work items are the (row tile, slot)
    pairs in which the slot has a live row, slots ascending (so the tiles
    never descend): at most ``n_tiles + B - 1`` of them. Returns int32
    ``(tile [W], slot [W], flags [W])``: bit 0 of ``flags`` the item is
    live, bit 1 it is the first of its tile. An item past the last live
    one repeats it (its blocks are already there) and does nothing."""
    R = np.int32(_ROW_TILE)
    B = q_lens.shape[0]
    W = n_tiles + B - 1
    t0 = start // R
    per = jnp.where(q_lens > 0, (start + q_lens - 1) // R - t0 + 1, 0)
    ends = jnp.cumsum(per, dtype=jnp.int32)
    total = ends[-1]
    w = jnp.arange(W, dtype=jnp.int32)
    wc = jnp.minimum(w, jnp.maximum(total - 1, 0))
    slot = jnp.minimum(jnp.sum(wc[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), np.int32(B - 1))
    tile = jnp.clip(t0[slot] + wc - (ends[slot] - per[slot]), 0,
                    np.int32(n_tiles - 1))
    live = w < total
    first = live & ((w == 0) | (tile != jnp.roll(tile, 1)))
    return tile, slot, live.astype(jnp.int32) + 2 * first.astype(jnp.int32)


def _scores_kernel(tables_ref, tile_ref, slot_ref, flag_ref, lens_ref,
                   qlens_ref, start_ref, q_ref, w_ref, *rest, n, bs, heads):
    k_refs, o_ref = rest[:n], rest[n]
    f32 = jnp.float32
    R, G = _ROW_TILE, min(_HEAD_GROUP, heads)
    kt = n * bs
    j, it = pl.program_id(0), pl.program_id(1)
    flag, b = flag_ref[it], slot_ref[it]
    r0 = tile_ref[it] * np.int32(R)
    s0, ql = start_ref[b], qlens_ref[b]
    # the slot's last row inside this tile, and its position
    last = lens_ref[b] + jnp.minimum(s0 + ql, r0 + np.int32(R)) \
        - np.int32(1) - s0

    @pl.when(flag >= np.int32(2))
    def _first_of_its_tile():
        o_ref[...] = jnp.zeros((R, kt), f32)

    @pl.when(((flag & np.int32(1)) == np.int32(1))
             & (j * np.int32(kt) <= last))
    def _score():
        k_tile = k_refs[0][0] if n == 1 else jnp.concatenate(
            [r[0] for r in k_refs], axis=0)                 # [kt, di]
        rows = pl.ds(pl.multiple_of(r0, R), R)
        acc = jnp.zeros((R, kt), f32)
        for h0 in range(0, heads, G):
            # G heads' rows, head-major: one product, then a head a slab
            q = q_ref[h0:h0 + G, rows, :]
            s = jax.lax.dot_general(
                q.reshape(G * R, q.shape[-1]), k_tile,
                (((1,), (1,)), ((), ())), preferred_element_type=f32)
            s = jnp.maximum(s, 0.0).reshape(G, R, kt)
            wt = w_ref[rows, h0:h0 + G]                     # [R, G]
            for g in range(G):
                acc = acc + wt[:, g:g + 1] * s[g]
        t = r0 + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        mine = (t >= s0) & (t < s0 + ql)
        o_ref[...] = jnp.where(mine, acc, o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _scores_call(qi, w, index_pool, block_tables, start, q_lens, first_pos,
                 *, interpret):
    """The Pallas form of :func:`index_scores`. Grid (key tile, work
    item): a work item is one row tile of ``_ROW_TILE`` packed rows for
    ONE slot that has rows in it (:func:`_work_table`, scalar-prefetched
    with the table and ``(first position, q_lens, start)`` a slot); a
    step scores the tile's rows, all heads, against one tile of the
    slot's index keys (``n`` table entries, read through ``n`` block
    specs) and writes the rows that are the slot's. The output block
    ``(row tile, key tile)`` stays in VMEM over the items of one row
    tile. The index queries are held whole in VMEM, head-major ``[J, T,
    di]`` (one relayout of them in XLA), so that a head group's rows are
    one operand of ``G x R`` rows and the sum over heads adds slabs of
    whole vregs. Nothing is aligned to a slot's first row: a row tile is
    computed whole and masked."""
    T, J, di = qi.shape
    NB, BS, _ = index_pool.shape
    B, MB = block_tables.shape
    n = entries_per_step(MB, BS)
    kt = n * BS
    R = _ROW_TILE
    n_tiles = -(-T // R)
    Ta = n_tiles * R
    q3 = jnp.transpose(jnp.pad(qi.astype(index_pool.dtype),
                               ((0, Ta - T), (0, 0), (0, 0))), (1, 0, 2))
    wp = jnp.pad(w.astype(jnp.float32), ((0, Ta - T), (0, 0)))
    tile, slot, flags = _work_table(start.astype(jnp.int32),
                                    q_lens.astype(jnp.int32), n_tiles)
    W = tile.shape[0]

    def whole(*_):
        return (Z, Z, Z)

    def keys(i):
        def im(j, it, tables_ref, tile_ref, slot_ref, *_):
            e = jnp.minimum(j * np.int32(n) + np.int32(i), np.int32(MB - 1))
            return (jnp.maximum(tables_ref[slot_ref[it], e], Z), Z, Z)
        return im

    out = pl.pallas_call(
        functools.partial(_scores_kernel, n=n, bs=BS, heads=J),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(MB // n, W),
            in_specs=[pl.BlockSpec((J, Ta, di), whole),
                      pl.BlockSpec((Ta, J), lambda *_: (Z, Z))] + [
                pl.BlockSpec((1, BS, di), keys(i)) for i in range(n)],
            out_specs=pl.BlockSpec(
                (R, kt), lambda j, it, tables_ref, tile_ref, *_:
                (tile_ref[it], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((Ta, MB * BS), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="dsa_index_scores",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), tile, slot, flags,
      first_pos.astype(jnp.int32), q_lens.astype(jnp.int32),
      start.astype(jnp.int32), q3, wp, *([index_pool] * n))
    return out[:T]


def index_scores_xla(qi, w, index_pool, block_tables, rows):
    """:func:`index_scores` in plain XLA (a CPU's form, and what the
    kernel is held to): every row against the whole table of its slot."""
    B, MB = block_tables.shape
    NB, BS, di = index_pool.shape
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)
    keys = index_pool[tables].reshape(B, MB * BS, di)[rows.slot]
    s = jnp.einsum("tjd,tsd->tjs", qi.astype(index_pool.dtype), keys,
                   preferred_element_type=jnp.float32)
    return jnp.sum(w[:, :, None] * jax.nn.relu(s), axis=1)


def _sortable(x):
    """float32 -> uint32 keys of the same order (``-inf`` lowest)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> np.uint32(31) == 1, ~u, u | np.uint32(1 << 31))


def _kth_largest(keys, k):
    """[T] the ``k``-th largest key of each row of ``keys`` [T, S] uint32:
    the largest value ``v`` with ``count(keys >= v) >= k``, a bit at a time
    from the top (32 passes of a compare and a count)."""
    def bit(i, thr):
        cand = thr | (np.uint32(1) << (np.uint32(31) - jnp.uint32(i)))
        enough = jnp.sum(keys >= cand[:, None], axis=1,
                         dtype=jnp.int32) >= np.int32(k)
        return jnp.where(enough, cand, thr)
    return jax.lax.fori_loop(np.int32(0), np.int32(32), bit,
                             jnp.zeros((keys.shape[0],), jnp.uint32))


#: positions of one block of the two-level counts and compaction
_BLOCK = 128


def _block_ranks(mask):
    """For ``mask`` [T, n, _BLOCK] bool: (exclusive count of the blocks
    before [T, n], inclusive count inside the block [T, n, _BLOCK]), both
    int32. The count inside a block is one product with a triangle
    (counts <= 128 are exact in bfloat16 with a float32 sum)."""
    tri = jnp.triu(jnp.ones((_BLOCK, _BLOCK), jnp.bfloat16))
    inside = jnp.einsum("tnb,bc->tnc", mask.astype(jnp.bfloat16), tri,
                        preferred_element_type=jnp.float32).astype(jnp.int32)
    total = inside[..., -1]
    return jnp.cumsum(total, axis=1, dtype=jnp.int32) - total, inside


def _compact(sel, k):
    """The positions of the first ``k`` True of each row of ``sel`` [T, S]
    in ascending order, [T, k] int32 (past a row's count: unspecified
    positions inside [0, S)), with dense compares and NO gather: the block
    that holds output ``r`` is the number of blocks whose inclusive count
    is ``<= r``, and its place inside the block the number of places whose
    inclusive count is ``<=`` what is left. The block's 128 inclusive
    counts are read by a product with the one-hot of the block's index,
    ``[k, n] @ [n, 128]`` a row on the MXU: exact (one term of a sum is
    not zero, and it is an integer ``<= 128``), and on the TPU one fusion
    with the compare that makes the one-hot and the count that reads the
    product, so that neither is ever held: 0.63 ms a layer at the cell's
    shapes (528 rows, ``k`` 2,048, 260 blocks), where a gather of the
    block an output, 1.08 M rows of 128 bytes, was a copy a row and 8.33
    (PERF.md section 6, PR 47)."""
    T, S = sel.shape
    n = S // _BLOCK
    blocks = sel.reshape(T, n, _BLOCK)
    before, inside = _block_ranks(blocks)
    upto = before + inside[..., -1]                       # inclusive
    r = jnp.arange(k, dtype=jnp.int32)[None, :, None]
    passed = upto[:, None, :] <= r                        # [T, k, n]
    blk = jnp.minimum(jnp.sum(passed, axis=2, dtype=jnp.int32),
                      np.int32(n - 1))
    # what the blocks before hold, as a sum again (a gather of a million
    # scalars costs what the gather of their blocks does)
    left = r[..., 0] - jnp.sum(
        jnp.where(passed, inside[..., -1][:, None, :], 0), axis=2,
        dtype=jnp.int32)
    here = blk[:, :, None] == jnp.arange(n, dtype=jnp.int32)
    # ``dot_general`` (batch t), not ``einsum``, whose call would be a
    # scope of its own inside the caller's
    rank = jax.lax.dot_general(                           # tkn,tnc->tkc
        here.astype(jnp.bfloat16), inside.astype(jnp.bfloat16),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    place = jnp.sum(rank <= left[:, :, None].astype(jnp.float32), axis=2,
                    dtype=jnp.int32)
    return blk * np.int32(_BLOCK) + jnp.minimum(place, np.int32(_BLOCK - 1))


def select(scores, rows, top_k):
    """The ``min(top_k, pos + 1)`` positions ``<= pos`` of largest score a
    row, exact, ties to the lower position, in ascending order of
    position. Returns ``(idx [T, k] int32 positions, ok [T, k])``, ``k =
    min(top_k, scores.shape[1])``; ``ok`` is False for the entries a short
    row (or a dead one) does not have, which come last. By a search for the
    ``k``-th largest value over the float32 bits (:func:`_kth_largest`),
    everything above it and the lowest positions that tie with it, and a
    compaction (:func:`_compact`); ``jax.lax.top_k`` gives the same SET and
    on the TPU sorts the whole row (20 ms for 528 rows of 33k against 3.4
    in plain XLA, and 12.1 while the compaction gathered; of the device's
    2.1 ms a layer in the cell's step the 32 passes are 0.6 and the
    compaction's product 0.6; PERF.md section 6, PRs 45 and 47)."""
    T, S = scores.shape
    k = min(int(top_k), S)
    Sp = -(-S // _BLOCK) * _BLOCK
    scores = jnp.pad(scores, ((0, 0), (0, Sp - S)))
    col = jnp.arange(Sp, dtype=jnp.int32)[None, :]
    keys = _sortable(jnp.where(col <= rows.pos[:, None], scores, -jnp.inf))
    thr = _kth_largest(keys, k)[:, None]
    above = keys > thr
    ties = keys == thr
    need = np.int32(k) - jnp.sum(above, axis=1, dtype=jnp.int32)
    before, inside = _block_ranks(ties.reshape(T, Sp // _BLOCK, _BLOCK))
    tie_rank = (before[:, :, None] + inside).reshape(T, Sp)
    idx = _compact(above | (ties & (tie_rank <= need[:, None])), k)
    return idx, (idx <= rows.pos[:, None]) & rows.live[:, None]


def sparse_attend(q, pool, block_tables, rows, idx, ok, dv):
    """q: [T, H, D] scaled, with the key up-projection absorbed; pool:
    [NB, BS, D]; idx / ok: :func:`select`'s. Row t attends the latents at
    positions ``idx[t]`` of its slot's table where ``ok[t]``. Returns [T,
    H, dv] in q's dtype; a row that holds no token comes back zero. On a
    TPU the Pallas kernel over a bfloat16 pool (:func:`_attend_call`),
    elsewhere plain XLA."""
    if _lat.latent_attention_enabled() and pool.dtype == jnp.bfloat16 \
            and idx.shape[1] % _GATHER_UNROLL == 0:
        return _attend_call(q, pool, block_tables, rows.slot,
                            _first_pos(rows) + rows.q_lens, idx, ok, dv=dv,
                            interpret=_lat._interpret())
    return sparse_attend_xla(q, pool, block_tables, rows, idx, ok, dv)


def sparse_attend_xla(q, pool, block_tables, rows, idx, ok, dv):
    """:func:`sparse_attend` in plain XLA (a CPU's form, and what the
    kernel is held to): a gather of each row's ``k`` latents and two
    batched products. On the chip the gather costs 26 ns an entry whatever
    the entry's form (PERF.md section 6, PR 45)."""
    NB, BS, D = pool.shape
    tables = block_tables.astype(jnp.int32)
    phys = jnp.take_along_axis(tables[rows.slot], idx // np.int32(BS),
                               axis=1)
    flat = jnp.maximum(phys, 0) * np.int32(BS) + idx % np.int32(BS)
    ent = pool.reshape(NB * BS, D)[flat]                    # [T, k, D]
    # both products as ``dot_general`` (batch t), not ``einsum``, whose
    # call would be a scope of its own inside the caller's
    s = jax.lax.dot_general(                                # thd,tkd->thk
        q.astype(ent.dtype), ent, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(                                # thk,tkv->thv
        p.astype(ent.dtype), ent[..., :dv], (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, np.float32(1e-30))
    return jnp.where(rows.live[:, None, None], o, 0.0).astype(q.dtype)


#: of the attending kernel: selected positions one step of the gathering
#: loop moves; selected positions of one tile of the scores; the VMEM its
#: call may use (a v5e's is 128 MiB, and a slot's whole context is held:
#: 51 MB at 33k)
_GATHER_UNROLL = 8
_SEL_TILE = 256
_ATTEND_VMEM = 100 << 20


def _words(d):
    """uint32 words of one packed entry of ``d`` bfloat16 values: half of
    them, up to whole vregs of 128 lanes."""
    return -(-d // 256) * 128


def _pack(x, W):
    """[..., D] bfloat16 -> [..., W] uint32: word ``j`` holds value ``j`` in
    its low half and value ``W + j`` in its high half (zeros past ``D``),
    so that ONE row of 32-bit words is one entry and both halves unpack
    with a shift or a mask."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint16)

    def half(h):
        h = jnp.pad(h, [(0, 0)] * (x.ndim - 1) + [(0, W - h.shape[-1])])
        return h.astype(jnp.uint32)
    return half(u[..., :W]) | (half(u[..., W:]) << np.uint32(16))


def _attend_kernel(tables_ref, slot_ref, n_ref, need_ref, idx_ref, q_ref,
                   pool_hbm, o_ref, ctx_v, g_ref, lo_ref, hi_ref, s_ref,
                   p_ref, cur_ref, sem, *, k, W, dv, bs):
    f32, bf = jnp.float32, jnp.bfloat16
    U, KT = _GATHER_UNROLL, min(_SEL_TILE, k)
    H = q_ref.shape[1]
    r = pl.program_id(0)
    b, n = slot_ref[r], n_ref[r]

    @pl.when(r == 0)
    def _first_row():
        cur_ref[0] = np.int32(-1)
        # what a short row leaves of the gathered entries is multiplied by
        # a weight of zero: it has to be a number
        g_ref[...] = jnp.zeros(g_ref.shape, g_ref.dtype)

    @pl.when(n == 0)
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when((n > 0) & (cur_ref[0] != b))
    def _bring():
        # the slot's context so far, whole: a copy a table entry
        entries = (need_ref[b] + np.int32(bs - 1)) // np.int32(bs)

        def copy(e):
            return pltpu.make_async_copy(
                pool_hbm.at[jnp.maximum(tables_ref[b, e], Z)],
                ctx_v.at[pl.ds(pl.multiple_of(e * np.int32(bs), bs), bs)],
                sem)

        def start(e, _):
            copy(e).start()
            return _

        def wait(e, _):
            copy(e).wait()
            return _
        jax.lax.fori_loop(Z, entries, start, Z)
        jax.lax.fori_loop(Z, entries, wait, Z)
        cur_ref[0] = b

    @pl.when(n > 0)
    def _attend():
        def gather(i, _):
            for u in range(U):
                at = i * np.int32(U) + np.int32(u)
                g_ref[pl.ds(at, 1), :] = ctx_v[pl.ds(idx_ref[0, 0, at], 1), :]
            return _
        jax.lax.fori_loop(Z, (n + np.int32(U - 1)) // np.int32(U), gather, Z)

        q_lo, q_hi = q_ref[0, :, :W], q_ref[0, :, W:]
        nt = (((1,), (1,)), ((), ()))
        for c in range(k // KT):
            at = slice(c * KT, (c + 1) * KT)
            g = g_ref[at, :]
            lo = jax.lax.bitcast_convert_type(
                g << np.uint32(16), f32).astype(bf)
            hi = jax.lax.bitcast_convert_type(
                g & np.uint32(0xFFFF0000), f32).astype(bf)
            lo_ref[at, :] = lo
            hi_ref[at, :] = hi
            s_ref[:, at] = jax.lax.dot_general(
                q_lo, lo, nt, preferred_element_type=f32
            ) + jax.lax.dot_general(q_hi, hi, nt, preferred_element_type=f32)
        col = jax.lax.broadcasted_iota(jnp.int32, (H, k), 1)
        s = jnp.where(col < n, s_ref[...], NEG_INF)
        p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        l = jnp.sum(p, axis=1, keepdims=True)
        p_ref[...] = p.astype(bf)
        nn = (((1,), (0,)), ((), ()))
        o = jax.lax.dot_general(p_ref[...], lo_ref[...], nn,
                                preferred_element_type=f32)
        if dv > W:
            more = -(-(dv - W) // 128) * 128
            o = jnp.concatenate([o, jax.lax.dot_general(
                p_ref[...], hi_ref[:, :more], nn,
                preferred_element_type=f32)], axis=1)
        o_ref[0] = (o[:, :dv] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dv", "interpret"), inline=True)
def _attend_call(q, pool, block_tables, slot, need, idx, ok, *, dv,
                 interpret):
    """The Pallas form of :func:`sparse_attend`, ``dsa_sparse_attend``. A
    row's ``k`` latents are ``k`` separate entries of the pool, and a copy
    an entry is what the plain gather costs; so a slot's whole context is
    copied into VMEM ONCE, a copy a table entry, when the walk over the
    packed rows reaches the slot (as rows of uint32 words, two values a
    word: :func:`_pack`, one pass over the pool in XLA), and a row gathers
    its entries from there, one vector load and store a 128 words. Grid
    (packed row): the row's positions come into SMEM a row, its query block
    ``[H, 2W]`` and its output ``[H, dv]`` through the pipeline; the
    products are
    ``[H, W] x [tile, W]`` on both halves of the words, the softmax is
    over the row's own ``n <= k`` entries, and the values are the first
    ``dv`` columns of the same gathered rows. ``need`` [B]: the positions
    of a slot's context its rows of this step reach."""
    T, H, D = q.shape
    NB, BS, _ = pool.shape
    B, MB = block_tables.shape
    S, k, W = MB * BS, idx.shape[1], _words(D)
    qw = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, 0), (0, 2 * W - D)))
    n = jnp.sum(ok, axis=1, dtype=jnp.int32)
    at = jnp.where(ok, idx, 0).astype(jnp.int32)[:, None, :]
    return pl.pallas_call(
        functools.partial(_attend_kernel, k=k, W=W, dv=dv, bs=BS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(T,),
            in_specs=[pl.BlockSpec((1, 1, k), lambda r, *_: (r, Z, Z),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((1, H, 2 * W), lambda r, *_: (r, Z, Z)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, dv), lambda r, *_: (r, Z, Z)),
            scratch_shapes=[pltpu.VMEM((S, W), jnp.uint32),
                            pltpu.VMEM((k, W), jnp.uint32),
                            pltpu.VMEM((k, W), jnp.bfloat16),
                            pltpu.VMEM((k, W), jnp.bfloat16),
                            pltpu.VMEM((H, k), jnp.float32),
                            pltpu.VMEM((H, k), jnp.bfloat16),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((T, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_ATTEND_VMEM),
        name="dsa_sparse_attend",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), slot.astype(jnp.int32), n,
      need.astype(jnp.int32), at, qw, _pack(pool, W))
