"""Chunked KDA (``ops/kernels/kda.py``: the recurrence and its chunked
form) as one Pallas kernel that walks only the LIVE chunks of a step, on
the step's rows as they lie.

**The row axis.** A mixed step hands a recurrent layer its rows on ONE
packed axis ``[T, H, K]``: slot ``b``'s rows are the ``q_lens[b]`` from
``start[b]`` on (``cache_layout.RowMap.start``), one slot a prompt chunk,
the others one decode row or none. The kernel addresses them there:
``(start, q_lens, seq_lens)`` are scalar-prefetched, chunk ``c`` of slot
``b`` is the :data:`CHUNK` rows from ``start[b] + CHUNK c`` on, and no
``[B, S, H, K]`` operand and no ``[B, S, H, V]`` output exist. The
per-slot form ``[B, S, ...]`` (a plain forward, the tests) is the same
call on the row axis ``[B S]`` with ``start[b] = b S``.

**The walk is a flat table of the live (slot, chunk) pairs.** The grid is
(group of heads, step of the table), the head group OUTERMOST. The table
(:func:`_table`, made from ``q_lens`` by the wrapper and prefetched) lists
the chunks that hold a live row, slots ascending: at most
:func:`table_steps` of them, ``(T + 63 B) / 64`` and not ``B x chunks a
slot``. The steps past its end do nothing and stay on the blocks of the
last live one, so they move nothing.

- **A head group's rows are resident.** Its operand blocks ``(T + CHUNK,
  hg, K)`` and its output block have the index ``(0, h, 0)``: fetched once
  and written once a head group, whatever the slots hold. A chunk is read
  and written at a dynamic offset on the LEADING axis of the block, which
  no tiling covers, so a slot's rows may start anywhere: no gather, no
  alignment. ``beta`` comes as ``[T, 1, H]`` for the same reason (in two
  dimensions its rows would lie on the sublanes). The blocks are a chunk
  LONGER than the arrays (a chunk read from a slot's last rows may end
  past row ``T``): only the arrays' rows are fetched and written, so the
  operands are padded nowhere. A chunk's rows past the slot's own are the
  rows of later slots, or nobody's, or past the axis (whatever VMEM held):
  q, k, v, g and beta are masked on the way in, and what the chunk stores
  there (zeros) a later slot's chunk writes over. A row no chunk covered
  is never written: the wrapper sends every row that holds no token back
  as zero.
- **Where the rows do not fit VMEM at once** (:data:`_RESIDENT_BUDGET`:
  the per-slot form of a long plain forward) the same kernel takes a
  chunk a block, ``(CHUNK, hg, K)`` at block ``start[b] / CHUNK + c``,
  which needs every slot's first row on a chunk's boundary: the per-slot
  form pads ``S`` to one. Packed rows that large go through the per-slot
  view (``RowMap.to_slots``), the one case that still builds it.
- **The state stays in VMEM across a slot's chunks.** ``S`` ``[K, V]``
  float32 a head lives in the output block from the slot's first chunk to
  its last: read from HBM once and written once a (slot, head). A slot at
  position 0 (``seq_lens[b] == 0``) starts from zeros there, when its
  first row comes. A slot without a live row is in no step of the table:
  its state is aliased through, neither read nor written.
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
#: what a head group's resident row blocks (every one double-buffered) may
#: take of VMEM; more rows than that go a chunk a block
_RESIDENT_BUDGET = 64 << 20
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


def table_steps(slots, rows, width):
    """Steps of the walk's table a head group, for ``slots`` slots of at
    most ``width`` rows each on an axis of ``rows`` rows: the most chunks
    that can hold a live row. A slot's last chunk may be one row full, so
    ``sum(ceil(q / CHUNK)) <= (rows + (CHUNK - 1) slots) / CHUNK``."""
    return max(1, min(slots * -(-int(width) // CHUNK),
                      (int(rows) + (CHUNK - 1) * slots) // CHUNK))


def grid_counts(q_lens, rows, width):
    """int32 [2] in :data:`COUNTERS` order: the steps of the table one
    call walks a head group (:func:`table_steps`), and those that hold a
    live (slot, chunk) pair."""
    q = jnp.clip(q_lens.astype(jnp.int32), 0, int(width))
    return jnp.stack([jnp.int32(table_steps(q.shape[0], rows, width)),
                      jnp.sum((q + (CHUNK - 1)) // CHUNK)]).astype(jnp.int32)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=HI,
                               preferred_element_type=jnp.float32)


def _table(q_lens, n):
    """The walk of one head group, from ``q_lens`` [B] (clipped): (slot
    [n], chunk [n], live steps [1]). Step ``t`` below the live count is
    chunk ``chunk[t]`` of slot ``slot[t]``, the live chunks in order, slots
    ascending; a step past it repeats the last live one (the last slot's
    chunk 0 where nothing is live), so its blocks are already there."""
    per = (q_lens + (CHUNK - 1)) // CHUNK
    ends = jnp.cumsum(per)
    t = jnp.minimum(jnp.arange(n, dtype=jnp.int32),
                    jnp.maximum(ends[-1] - 1, 0))
    done = t[:, None] >= ends[None, :]         # [n, B] the slots before
    slot = jnp.minimum(jnp.sum(done, axis=1), q_lens.shape[0] - 1)
    chunk = t - jnp.sum(jnp.where(done, per[None, :], 0), axis=1)
    return slot.astype(jnp.int32), chunk.astype(jnp.int32), \
        ends[-1:].astype(jnp.int32)


def _row_map(resident, every_head):
    """The index map of a row block ``(rows, heads, width)``. Resident: a
    head group's rows of every slot are one block for the whole of its
    walk, fetched and written once; else the step's chunk, which lies on
    a block's boundary. ``every_head``: the block holds all heads
    (``beta``'s), not the grid's group."""
    def im(h, t, ql, lens, start, slot, chunk, total):
        row = Z if resident else \
            start[slot[t]] // np.int32(CHUNK) + chunk[t]
        return (row, Z if every_head else h, Z)
    return im


def _state_map(h, t, ql, lens, start, slot, chunk, total):
    return (slot[t], h, Z, Z)


def _kernel(ql_ref, lens_ref, start_ref, slot_ref, chunk_ref, total_ref,
            q_ref, k_ref, v_ref, g_ref, beta_ref, s_in_ref, o_ref, s_out_ref,
            *, hg, kd, resident):
    f32 = jnp.float32
    h, t = pl.program_id(0), pl.program_id(1)
    C = CHUNK
    b, c = slot_ref[t], chunk_ref[t]
    n = ql_ref[b]
    live = t < total_ref[0]
    here = n - c * np.int32(C)                # live rows of this chunk
    first = c == Z
    fresh = lens_ref[b] == Z
    # where the chunk's rows lie in the row blocks
    row0 = start_ref[b] + c * np.int32(C) if resident else 0

    def span(rows):
        return pl.ds(row0, rows) if resident else slice(0, rows)

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
        blk = beta_ref[span(rows), 0, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
        mine = lane == h * np.int32(hg) + i
        return jnp.sum(jnp.where(mine, blk, 0.0), axis=1, keepdims=True)

    def state_of(i):
        """The head's state as this chunk finds it."""
        return jnp.where(first & fresh, 0.0,
                         jnp.where(first, s_in_ref[i], s_out_ref[i]))

    @pl.when((t == Z) & (total_ref[0] == Z))
    def _all_idle():
        # no slot is live: the one state block such a walk addresses goes
        # back as it came
        s_out_ref[...] = s_in_ref[...]

    @pl.when(live & (n == 1))
    def _one_row():
        eye = jax.lax.broadcasted_iota(jnp.int32, (kd, kd), 0) == \
            jax.lax.broadcasted_iota(jnp.int32, (kd, kd), 1)

        def col(x):                            # [1, K] -> [K, 1], exact
            return jnp.sum(jnp.where(eye, x, 0.0), axis=1, keepdims=True)

        def head(i):
            S = state_of(i) * col(jnp.exp(g_ref[span(1), i, :]))
            kc = col(k_ref[span(1), i, :])
            u = beta_of(i, 1) * (v_ref[span(1), i, :]
                                 - jnp.sum(S * kc, axis=0, keepdims=True))
            S = S + kc * u
            s_out_ref[i] = S
            o_ref[span(1), i, :] = jnp.sum(
                S * col(q_ref[span(1), i, :]), axis=0, keepdims=True)
        each_head(head)

    @pl.when(live & (n > 1))
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
            # rows past the slot's own are a later slot's or nobody's
            # (past the axis: whatever the block holds there)
            q = jnp.where(alive, q_ref[span(C), i, :], 0.0)
            k = jnp.where(alive, k_ref[span(C), i, :], 0.0)
            v = jnp.where(alive, v_ref[span(C), i, :], 0.0)
            g = jnp.where(alive, g_ref[span(C), i, :], 0.0)
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
            rhs = beta * (v - kq[:C])
            u = [rhs[t:t + 8] for t in range(0, C, 8)]
            lt = [L[t:t + 8] for t in range(0, C, 8)]
            for r in range(C - 1):
                ur = u[r // 8][r % 8:r % 8 + 1]
                for t in range((r + 1) // 8, C // 8):
                    u[t] = u[t] - lt[t][:, r:r + 1] * ur
            U = jnp.concatenate(u)
            o = kq[C:] + _dot(Bm, U, _NN)
            o_ref[span(C), i, :] = jnp.where(alive, o, 0.0)
            g_end = G[C - 1:C]
            dec = jnp.sum(jnp.where(eye, jnp.exp(g_end), 0.0), axis=1,
                          keepdims=True)                  # [K, 1]
            s_out_ref[i] = S * dec + _dot(k * jnp.exp(g_end - G), U, _TN)
        each_head(head)


def kda_chunk_walk(q, k, v, g, beta, state, q_lens, seq_lens, rows=None):
    """q, k, g: [B, S, H, K]; v: [B, S, H, V]; beta: [B, S, H]; state: [B,
    H, K, V] float32; q_lens: [B] live rows of each slot (its first);
    seq_lens: [B] tokens a slot holds before them (0: the slot starts from
    zeros). Returns (o [B, S, H, V] float32, the state after each slot's
    live rows): what :func:`kda.kda_recurrent` gives on the live rows; a
    dead row's output is 0, and a slot without a live row keeps its state
    as it is (zeroed only when its first row comes). With ``rows`` (a
    mixed step's ``cache_layout.RowMap``) q, k, g, v and beta are the
    packed ``[T, H, ...]`` as the layer computed them, slot ``b``'s rows
    the ``q_lens[b]`` from ``rows.start[b]`` on, and so is ``o``, ``[T, H,
    V]``: a row that holds no token comes back zero."""
    if rows is None:
        return _walk_call(q, k, v, g, beta, state, q_lens, seq_lens,
                          interpret=_interpret())
    if not _resident(q.shape[0], q.shape[1], q.shape[2], v.shape[2]):
        # more rows than a head group holds at once, at starts that are
        # no chunk's: through the per-slot view
        o, state = _walk_call(*(rows.to_slots(a) for a in (q, k, v, g, beta)),
                              state, q_lens, seq_lens, interpret=_interpret())
        return jnp.where(rows.live[:, None, None], rows.from_slots(o),
                         0.0), state
    return _walk_rows(q, k, v, g, beta, state, q_lens, seq_lens, rows.start,
                      width=rows.width, every=None, interpret=_interpret())


def _resident(t, h, k, v):
    """Whether the ``t`` rows of an axis (and the chunk of room behind
    them) fit :data:`_RESIDENT_BUDGET` as a head group's resident blocks:
    q, k, g, v and o, and ``beta`` a (sublane, lane) tile a row."""
    hg = heads_per_step(h)
    return 2 * 4 * (t + CHUNK) * (hg * (3 * k + 2 * v) + 8 * _LANES) \
        <= _RESIDENT_BUDGET


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _walk_call(q, k, v, g, beta, state, q_lens, seq_lens, *, interpret):
    """The per-slot form ``[B, S, ...]``: the row axis ``[B * S]`` with
    slot ``b``'s rows from ``b * S``, ``S`` padded to whole chunks."""
    B, S = q.shape[:2]
    pad = (-S) % CHUNK
    Sp = S + pad

    def flat(a):
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return a.reshape((B * Sp,) + a.shape[2:])

    o, state = _walk_rows(flat(q), flat(k), flat(v), flat(g), flat(beta),
                          state, q_lens, seq_lens,
                          jnp.arange(B, dtype=jnp.int32) * np.int32(Sp),
                          width=S, every=Sp, interpret=interpret)
    return o.reshape((B, Sp) + o.shape[1:])[:, :S], state


@functools.partial(jax.jit, static_argnames=("width", "every", "interpret"),
                   inline=True)
def _walk_rows(q, k, v, g, beta, state, q_lens, seq_lens, start, *, width,
               every, interpret):
    """The table and the ONE Pallas call, under an inlined inner jit so
    that a model's layers share a trace
    (``latent_attention._append_rows``). ``q`` [T, H, K]: slot ``b``'s rows
    are the ``min(q_lens[b], width)`` from ``start[b]`` on, slots
    ascending; ``every``: the starts are known to be this many rows apart
    (None: they are not)."""
    f32 = jnp.float32
    T, H, K = q.shape
    V = v.shape[-1]
    B = state.shape[0]
    hg = heads_per_step(H)
    ql = jnp.clip(q_lens.astype(jnp.int32), 0, int(width))
    start = start.astype(jnp.int32)
    resident = _resident(T, H, K, V)
    on_chunks = every is not None and every % CHUNK == 0
    if not (resident or on_chunks):
        raise ValueError(
            f"kda_chunk_walk: {T} rows do not fit a head group's resident "
            f"blocks, and a chunk a block needs the slots {CHUNK} rows "
            f"apart (every={every})")
    n = table_steps(B, T, width)
    # a chunk read from a slot's last rows may end past the axis: the
    # resident blocks are a chunk longer than the arrays, whose rows are
    # all that is fetched and written
    held = CHUNK if not resident else T if on_chunks else T + CHUNK
    wide = lambda w: pl.BlockSpec(  # noqa: E731
        (held, hg, w), _row_map(resident, False))
    state_spec = pl.BlockSpec((None, hg, K, V), _state_map)
    o, state = pl.pallas_call(
        functools.partial(_kernel, hg=hg, kd=K, resident=resident),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(H // hg, n),
            in_specs=[wide(K), wide(K), wide(V), wide(K),
                      pl.BlockSpec((held, 1, H), _row_map(resident, True)),
                      state_spec],
            out_specs=[wide(V), state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((T, H, V), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the blocks double-buffered, and room for a head's values
            vmem_limit_bytes=8 * (held * (hg * (3 * K + 2 * V) + 8 * _LANES)
                                  + 2 * hg * K * V) + (16 << 20)),
        name="kda_chunk_walk",
        interpret=interpret,
    )(ql, seq_lens.astype(jnp.int32), start, *_table(ql, n), q.astype(f32),
      k.astype(f32), v.astype(f32), g.astype(f32),
      beta.astype(f32)[:, None, :], state.astype(f32))
    at = jnp.arange(T, dtype=jnp.int32)[:, None] - start[None, :]
    live = jnp.any((at >= 0) & (at < ql[None, :]), axis=1)
    return jnp.where(live[:, None, None], o, 0.0), state
