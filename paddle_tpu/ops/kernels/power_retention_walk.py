"""Power retention's core (``ops/kernels/power_retention.py``: the function,
the state and the order of the monomials) as one Pallas kernel that reads a
live slot's state once a step and writes it once.

A step hands the core its rows on one flat axis, slot ``b``'s ``q_lens[b]``
rows adjacent from ``start[b]``. The kernel's grid is (key/value head,
slot), the slot axis innermost, with ``(start, q_lens, lens)``
scalar-prefetched; a grid step holds ONE head's whole state ``S [D, dv]``
of one slot in VMEM (4.2 MB at the published widths) and decides its form
from ``q_lens[b]``:

- **0 rows: nothing.** The step computes nothing and its state block's
  index stays on the block the walk is on (the last live slot's before it,
  or the first live slot's), so nothing is fetched or written; the slot's
  state stays where it lies (``input_output_aliases``).
- **1 row: the one-token form on the VPU.** ``S <- g S + phi(k) v^T`` and,
  on the UPDATED values, ``phi(q_a)^T S`` for the head's ``G`` query heads,
  eight rows of ``S`` at a time. ``phi``'s index runs along the sublanes
  there, so ``k`` and each ``q_a`` are turned into COLUMNS broadcast along
  the lanes, eight copies each, copy ``t`` holding ``x[(j + t) % d]`` at
  row ``j``: diagonal ``r = 8 m + t`` of ``phi`` at strip ``s`` is copy 0
  at rows ``8 s`` times copy ``t`` at rows ``8 (s + m)``, both aligned (a
  dynamic sublane index has to be a multiple of eight for Mosaic). The
  loops run strip of eight rows OUTSIDE, diagonal INSIDE, so a query
  head's partial sum is one register and ``q_a[i]`` is applied once a
  strip (``sum_i q[i] sum_r c_r q[i + r] S_r[i]``). On the chip this form
  runs at what its DMAs take (4.2 MB in and out a grid step), not at what
  it computes.
- **more rows: the chunk form on the MXU**, sub-chunk of :data:`SUB` rows
  outside, diagonal inside, on the state resident in the output block: a
  diagonal's ``phi(Q)`` ``[G * SUB, d]`` is a lane rotation and a product
  away from ``Q``, read against the block of ``S`` that entered the
  sub-chunk, and that block is advanced by ``(phi(K) decay)^T V`` in the
  same visit. ``phi(Q)`` and ``phi(K)`` exist a diagonal at a time, in
  registers; :data:`UNROLL` diagonals are written out an iteration, so
  that one's rotations and products run under another's matmuls. ``z`` is
  held through a slot's sub-chunks as the matrix of its quadratic form
  (``phi(q) . z == q^T Zq q``): one product a sub-chunk reads it and
  ``Kw^T K`` advances it. The sub-chunk's own attention form and the
  division sit in the same kernel.

``z`` is handed in transposed, ``[Hk, B, D]``: a head's rows of every slot
are one block that stays in VMEM across the slot axis (in its own layout a
block would hold a slot's eight heads, and eight grid steps far apart
would each rewrite it); slot ``b``'s row is read by loading its tile of
eight slots and masking. The rows come head-major in float32, UNSCALED
(the ``1 / sqrt d`` of a product of two values is applied to the sums),
every slot's rows moved to a multiple of eight by the wrapper. The last
diagonal (``r = d / 2``) is half a block and is visited after the loop
over the whole ones.

**Precision.** The state is float32 and every product that reads it or
``phi`` is made to float32 accuracy. Where q, k and v come in float32 that
is ``Precision.HIGHEST`` (six bfloat16 passes). Where they come in
bfloat16, as the served model hands them, the kernel writes the passes
out: a value of q, k or v is ONE bfloat16 term, a product of two values of
q exactly two, the state three, so ``phi(Q) S`` is five one-pass products
(every pair of terms but lo x third, 2^-24 of the product), ``(phi(K)
decay)^T V`` three and ``Q K^T`` one: the same sums ``HIGHEST`` would
form, less the passes whose term is zero. On the VPU everything is
float32. The grid's steps, the strips and the diagonals are loops, not
straight-line code: a call site embeds its own copy of the kernel, and a
warm start pays for its size (PERF.md section 6, PR 38). On a CPU the
kernel runs interpreted; a program lowered for the TPU from a CPU host
(the ahead-of-time compiles of the tests) takes the Mosaic kernel, chosen
by the platform it is lowered for.
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

F32 = jnp.float32
BF16 = jnp.bfloat16
HI = jax.lax.Precision.HIGHEST
#: rows of one sub-chunk of the chunk form (64 / 128 / 256 read 2.20 / 1.97
#: / 2.06 ms a 512-row chunk a layer on the v5e, PERF.md section 6); diagonals
#: one iteration of its loop over them serves, written out, so that one
#: diagonal's vector work runs under another's matmuls (1 / 3 / 7: 3.40 /
#: 2.46 / 2.20 ms at 64 rows); rows of one strip of the one-token form (a
#: sublane tile)
SUB = 128
UNROLL = 7
STRIP = 8
ONE = np.int32(1)
ROOT2 = np.float32(2.0 ** 0.5)

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=HI,
                               preferred_element_type=F32)


def _split(x, terms):
    """``x`` float32 as ``terms`` bfloat16 arrays that sum to it (exactly,
    where it has no more than ``8 * terms`` significant bits)."""
    parts = []
    for _ in range(terms):
        parts.append(x.astype(BF16))
        x = x - parts[-1].astype(F32)
    return parts


def _passes(pairs, dims):
    """The sum of one-pass bfloat16 products, smallest terms first."""
    out = None
    for a, b in pairs:
        one = jax.lax.dot_general(a, b, dims, preferred_element_type=F32)
        out = one if out is None else out + one
    return out


def _loop(lo, hi, body, carry=()):
    """``carry = body(i, carry)`` for int32 ``i`` in ``[lo, hi)`` (a
    ``fori_loop`` between constants counts in a weak int64 under x64, which
    Mosaic cannot cast)."""
    def step(c):
        return (c[0] + ONE, body(c[0], c[1]))
    return jax.lax.while_loop(lambda c: c[0] < hi, step,
                              (jnp.int32(lo), carry))[1]


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _walk(q_lens):
    """(block slot [B], any live [1]) from ``q_lens`` [B]: a live slot
    addresses its own state, an idle slot the one the walk is on when it
    gets there -- the live slot's before it, or the first live slot's
    when none is before it (slot 0's when every slot is idle)."""
    live = q_lens > 0
    idx = jnp.arange(q_lens.shape[0], dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.where(jnp.any(live), jnp.argmax(live), 0).astype(jnp.int32)
    return jnp.where(prev >= 0, prev, first).astype(jnp.int32), \
        jnp.any(live).astype(jnp.int32)[None]


def _head_map(h, b, *_):
    return (h, Z, Z)


def _state_map(h, b, start, ql, lens, src, any_live):
    # every slot idle: the one block (0, 0), which goes back as it came
    return (src[b], jnp.where(any_live[0] > Z, h, Z), Z, Z)


def _kernel(start_ref, ql_ref, lens_ref, src_ref, any_ref, q_ref, k_ref,
            v_ref, lg_ref, s_in, z_in, o_ref, s_out, z_out, kb, qb, kbv, zq,
            *, G, d, eps, exact):
    h, b = pl.program_id(0), pl.program_id(1)
    n = ql_ref[b]
    row0 = pl.multiple_of(start_ref[b], STRIP)
    fresh = lens_ref[b] == Z
    half = d // 2
    D = s_in.shape[0]
    tail = D - half                       # first row of the half diagonal
    Gp = -(-G // STRIP) * STRIP
    eps = np.float32(eps)
    i32 = np.int32
    # q and k come unscaled: a product of two of their values carries
    # 1 / sqrt d
    scale = np.float32(d ** -0.5)

    def shift_of(r):
        # roll(a, -r): a[i + r] lands on lane i
        return jnp.where(r == Z, Z, i32(d) - r)

    def coef(r):
        return jnp.where(r == Z, np.float32(1.0), ROOT2)

    def rows_at(r):
        return pl.ds(pl.multiple_of(r * i32(d), d), d)

    def strip_at(i):
        return pl.ds(pl.multiple_of(i, STRIP), STRIP)

    # slot b's row of z's block: the tile of eight slots it lies in
    ztile = strip_at((b // i32(STRIP)) * i32(STRIP))
    zmine = _iota((STRIP, 1), 0) == b % i32(STRIP)

    def z_row(tile):
        return jnp.sum(jnp.where(zmine, tile, 0.0), axis=0, keepdims=True)

    @pl.when(b == Z)
    def _first_slot():
        # rows no slot owns read 0; z's block holds every slot's rows
        for a in range(G):
            o_ref[a] = jnp.zeros(o_ref.shape[1:], F32)
        z_out[...] = z_in[...]

    @pl.when((b == Z) & (h == Z) & (any_ref[0] == Z))
    def _all_idle():
        def copy(r, c):
            s_out[rows_at(r), :] = s_in[rows_at(r), :]
            return c
        _loop(0, half, copy)
        s_out[tail:D, :] = s_in[tail:D, :]

    @pl.when(n == ONE)
    def _one_row():
        at = strip_at(row0)                # the row and seven dead ones
        K8 = jnp.broadcast_to(k_ref[at, :][0:1], (STRIP, d))
        vrow = v_ref[at, :][0:1]
        g1 = jnp.exp(lg_ref[at, :][0:1])                   # [1, d], uniform
        Q8 = jnp.concatenate(
            [q_ref[a, at, :][0:1] for a in range(G)]
            + [jnp.zeros((Gp - G, d), F32)] * (Gp > G), axis=0)

        # ---- the columns: x[(j + t) % d] down the sublanes, along every
        # lane, for t = 0 .. 7 (a diagonal r = 8 m + t then reads copy t
        # at the ALIGNED row 8 (s + m)). The rotated rows of k and of
        # every q are stacked, transposed once, and a column of the
        # transpose is broadcast along the lanes ---------------------------
        sub8 = _iota((STRIP, 1), 0)

        def rotated(x):                    # [1, d] -> row t = roll(x, -t)
            X8 = jnp.broadcast_to(x, (STRIP, d))
            out = X8
            for t in range(1, STRIP):
                out = jnp.where(sub8 == t, pltpu.roll(X8, i32(d - t), 1), out)
            return out
        stack = [rotated(K8[0:1])] + [rotated(Q8[a:a + 1]) for a in range(G)]
        if len(stack) * STRIP % d:
            stack.append(jnp.zeros((-len(stack) * STRIP % d, d), F32))
        R = jnp.concatenate(stack, axis=0)
        T = [R[i:i + d].T for i in range(0, R.shape[0], d)]
        for c in range((G + 1) * STRIP):
            col = jnp.broadcast_to(T[c // d][:, c % d:c % d + 1], (d, d))
            ref = kb.at[c] if c < STRIP else \
                qb.at[c // STRIP - 1, c % STRIP]
            ref[0:d, :] = col
            ref[d:d + half, :] = col[0:half]
        kbv[...] = kb[0, 0:d, :] * (scale * vrow)

        # ---- z and the normaliser, D along the lanes, eight diagonals an
        # iteration -------------------------------------------------------
        def z_diag(r, sh, c, den):
            pk = (scale * c * K8 * pltpu.roll(K8, sh, 1))[0:1]
            old = z_out[ztile, rows_at(r)]
            new = g1 * jnp.where(fresh, 0.0, old) + pk
            z_out[ztile, rows_at(r)] = jnp.where(zmine, new, old)
            return den + c * Q8 * pltpu.roll(Q8, sh, 1) * z_row(new)

        def z_eight(j, den):
            r = j * i32(STRIP)
            den = z_diag(r, shift_of(r), coef(r), den)
            for u in range(1, STRIP):
                den = z_diag(r + i32(u), i32(d - u) - r, ROOT2, den)
            return den
        den = _loop(0, half // STRIP, z_eight, jnp.zeros((Gp, d), F32))
        pk = (scale * ROOT2 * K8 * pltpu.roll(K8, i32(half), 1))[0:1, 0:half]
        old = z_out[ztile, tail:D]
        new = g1[:, 0:half] * jnp.where(fresh, 0.0, old) + pk
        z_out[ztile, tail:D] = jnp.where(zmine, new, old)
        pq = (ROOT2 * Q8 * pltpu.roll(Q8, i32(half), 1))[:, 0:half]
        den = jnp.sum(den, axis=1, keepdims=True) + jnp.sum(
            pq * z_row(new), axis=1, keepdims=True)         # [Gp, 1]

        # ---- S: strips of eight rows outside, diagonals inside ------------
        G8 = jnp.broadcast_to(g1, (STRIP, d))
        zeros = tuple(jnp.zeros((STRIP, d), F32) for _ in range(G))

        def visit(at_s, t, at_k, kv):
            """Rows ``at_s`` of S advanced by ``kv`` times copy ``t`` of the
            key's column at rows ``at_k``; the updated rows."""
            Sn = G8 * jnp.where(fresh, 0.0, s_in[at_s, :]) \
                + kv * kb[t, at_k, :]
            s_out[at_s, :] = Sn
            return Sn

        def strip(s, num):
            base = s * i32(STRIP)
            own = strip_at(base)
            kv1 = kbv[own, :]
            kv2 = ROOT2 * kv1

            def eight(m, acc, first):
                """Diagonals r = 8 m + t, t = first .. 7."""
                rows = strip_at(base + m * i32(STRIP))
                for t in range(first, STRIP):
                    r = m * i32(STRIP) + i32(t)
                    Sn = visit(strip_at(r * i32(d) + base), t, rows, kv2)
                    acc = tuple(acc[a] + qb[a, t, rows, :] * Sn
                                for a in range(G))
                return acc
            S0 = visit(own, 0, own, kv1)
            acc = eight(Z, zeros, 1)
            acc = _loop(1, half // STRIP,
                        lambda m, acc: eight(m, acc, 0), acc)
            return tuple(num[a] + qb[a, 0, own, :] * (
                S0 * qb[a, 0, own, :] + ROOT2 * acc[a]) for a in range(G))
        num = _loop(0, d // STRIP, strip, zeros)

        def strip_of_the_half(s, num):
            base = s * i32(STRIP)
            own, off = strip_at(base), strip_at(base + i32(half))
            Sn = visit(strip_at(i32(tail) + base), 0, off,
                       ROOT2 * kbv[own, :])
            return tuple(num[a] + ROOT2 * qb[a, 0, own, :]
                         * qb[a, 0, off, :] * Sn for a in range(G))
        num = _loop(0, half // STRIP, strip_of_the_half, num)
        for a in range(G):
            o_ref[a, at, :] = jnp.broadcast_to(
                scale * jnp.sum(num[a], axis=0, keepdims=True)
                / (scale * den[a:a + 1] + eps), (STRIP, d))

    @pl.when(n > ONE)
    def _chunk():
        def enter(r, c):
            s_out[rows_at(r), :] = jnp.where(fresh, 0.0, s_in[rows_at(r), :])
            return c
        _loop(0, half, enter)
        s_out[tail:D, :] = jnp.where(fresh, 0.0, s_in[tail:D, :])
        z_out[ztile, :] = jnp.where(zmine & fresh, 0.0, z_out[ztile, :])

        lower = _iota((SUB, SUB), 1) <= _iota((SUB, SUB), 0)
        upper = _iota((SUB, SUB), 0) <= _iota((SUB, SUB), 1)
        tri = lower.astype(F32)
        ones = jnp.ones((SUB, SUB), F32)
        row = _iota((SUB, 1), 0)

        def tile(x):                      # a sub-chunk's rows, a query head
            return jnp.concatenate([x] * G, axis=0)

        # The products, by what the operands are known to be (module
        # docstring, "Precision"): the passes written out where q, k and v
        # came in bfloat16, ``Precision.HIGHEST`` where they did not.
        if exact:
            def qk(Q, K):
                return _passes([(Q.astype(BF16), K.astype(BF16))], _NT)

            def by_v(x, V, dims):
                Vb = V.astype(BF16)
                return _passes([(p, Vb) for p in _split(x, 3)[::-1]], dims)

            def read(PQ, Sr):
                (hi, lo), (s1, s2, s3) = _split(PQ, 2), _split(Sr, 3)
                return _passes([(hi, s3), (lo, s2), (lo, s1), (hi, s2),
                                (hi, s1)], _NN)

            def q_by(Q, M):
                Qb = Q.astype(BF16)
                return _passes([(Qb, p) for p in _split(M, 3)[::-1]], _NN)
        else:
            def qk(Q, K):
                return _dot(Q, K, _NT)

            def by_v(x, V, dims):
                return _dot(x, V, dims)

            def read(PQ, Sr):
                return _dot(PQ, Sr, _NN)

            def q_by(Q, M):
                return _dot(Q, M, _NN)

        # z inside a chunk: as the matrix of its quadratic form, phi(q) . z
        # == q^T Zq q with Zq[i + r, i] = c_r z_r[i] (c_0 = 1, else sqrt 2;
        # z_r the diagonal r of z). A sub-chunk then reads it by ONE
        # product and advances it by ``Kw^T K`` (phi(k) . phi(q) == (k .
        # q)^2), in place of a product and a sum a diagonal; ``diff`` is
        # (row - column) mod d, the diagonal an entry lies on.
        diff = _iota((d, d), 0) - _iota((d, d), 1)
        diff = jnp.where(diff < Z, diff + i32(d), diff)
        left = _iota((d, d), 1) < i32(half)

        def z_to_zq(r, Zq):
            zr = z_row(z_out[ztile, rows_at(r)])
            return jnp.where(diff == r, coef(r) * zr, Zq)
        Zq = _loop(0, half, z_to_zq, jnp.zeros((d, d), F32))
        spread = (_iota((half, d), 0) == _iota((half, d), 1)).astype(F32)
        zr = _dot(jnp.broadcast_to(z_row(z_out[ztile, tail:D]),
                                   (STRIP, half)), spread, _NN)[0:1]
        zq[...] = jnp.where((diff == i32(half)) & left, ROOT2 * zr, Zq)

        def sub_chunk(c, carry):
            at = pl.ds(pl.multiple_of(row0 + c * i32(SUB), STRIP), SUB)
            valid = row < n - c * i32(SUB)
            Q = jnp.concatenate([q_ref[a, at, :] for a in range(G)], axis=0)
            K = jnp.where(valid, k_ref[at, :], 0.0)
            V = jnp.where(valid, v_ref[at, :], 0.0)
            LG = jnp.where(valid, lg_ref[at, :], 0.0)      # uniform on lanes
            Gl = _dot(tri, LG, _NN)                        # sum_{j <= t}
            Gc = Gl[:, 0:1]
            # G_s along the lanes, the same in every row
            Gs = _dot(ones, jnp.where(upper, LG[:, 0:1], 0.0), _NN)
            decay = jnp.exp(jnp.where(lower, Gc - Gs, -jnp.inf))
            # inside the sub-chunk: the attention form, exponents <= 0
            sc = scale * qk(Q, K)                          # [G SUB, SUB]
            a_in = sc * sc * tile(decay)
            num = by_v(a_in, V, _NN)
            den = jnp.sum(a_in, axis=1, keepdims=True)
            # the state: read as it entered, advanced once
            g_end = jnp.exp(Gl[SUB - 1:SUB])               # [1, d], uniform
            Kw = K * (scale * jnp.exp(Gc[SUB - 1:SUB] - Gc))
            Kw2 = ROOT2 * Kw

            d_in = scale * jnp.sum(q_by(Q, zq[...]) * Q, axis=1,
                                   keepdims=True)
            zq[...] = g_end * zq[...] + by_v(Kw, K, _TN)

            def advance(at_s, PQ, PK):
                """One diagonal: phi(Q) S against the state as it entered,
                without the diagonal's sqrt 2; the state advanced."""
                Sr = s_out[at_s, :]
                s_out[at_s, :] = g_end * Sr + by_v(PK, V, _TN)
                return read(PQ, Sr)

            def diag(r):
                sh = i32(d) - r
                return advance(rows_at(r), Q * pltpu.roll(Q, sh, 1),
                               Kw2 * pltpu.roll(K, sh, 1))

            def diags(j, n_in):
                for u in range(UNROLL):
                    n_in = n_in + diag(j * i32(UNROLL) + i32(u + 1))
                return n_in
            n_in = _loop(0, (half - 1) // UNROLL, diags,
                         jnp.zeros((G * SUB, d), F32))
            for r in range((half - 1) // UNROLL * UNROLL + 1, half):
                n_in = n_in + diag(i32(r))
            # the half diagonal: its first d / 2 lanes
            n_in = n_in + advance(
                slice(tail, D),
                (Q * pltpu.roll(Q, i32(half), 1))[:, 0:half],
                (Kw2 * pltpu.roll(K, i32(half), 1))[:, 0:half])
            n_in = scale * (advance(rows_at(Z), Q * Q, Kw * K)
                            + ROOT2 * n_in)
            eG = tile(jnp.exp(Gc))                         # [G SUB, 1]
            o = (num + eG * n_in) / (den + eG * d_in + eps)
            for a in range(G):
                # a ragged sub-chunk's dead rows belong to other slots
                o_ref[a, at, :] = jnp.where(
                    valid, o[a * SUB:(a + 1) * SUB], o_ref[a, at, :])
            return carry
        _loop(0, (n + i32(SUB - 1)) // i32(SUB), sub_chunk)
        # z back to its diagonals: c_r z_r[i] = Zq[i + r, i] + Zq[i, i + r]
        Zs = zq[...] + zq[...].T

        def zq_to_z(r, c):
            zr = jnp.sum(jnp.where(diff == r, Zs, 0.0), axis=0,
                         keepdims=True) / jnp.where(r == Z, np.float32(2.0),
                                                    ROOT2)
            z_out[ztile, rows_at(r)] = jnp.where(
                zmine, zr, z_out[ztile, rows_at(r)])
            return c
        _loop(0, half, zq_to_z)
        zr = jnp.sum(jnp.where(diff == i32(half), Zs, 0.0), axis=0,
                     keepdims=True)[:, 0:half] / ROOT2
        z_out[ztile, tail:D] = jnp.where(zmine, zr, z_out[ztile, tail:D])


def retention_walk(q, k, v, log_g, S, z, start, q_lens, lens, eps=1e-6):
    """``power_retention.retention_walk`` (its arguments, its results) by
    the kernel: a step of ``q_lens[b]`` rows a slot on one flat row axis.
    q: [N, Hq, d]; k: [N, Hk, d]; v: [N, Hk, dv]; log_g: [N, Hk]; S: [B,
    Hk, D, dv], z: [B, Hk, D] float32. Returns (o [N, Hq, dv] in q's
    dtype, 0 on rows no slot owns; S; z). A slot without a live row keeps
    its state as it lies."""
    call = functools.partial(_walk_call, eps=float(eps))
    args = (q, k, v, log_g, S, z, start, q_lens, lens)
    if not _pa._interpret():
        return call(*args, interpret=False)
    # a CPU host: interpreted, unless the program is lowered for the TPU
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))


def retention_step(q, k, v, log_g, S, z, live, lens, eps=1e-6):
    """The one-token form, every slot at once: slot ``b``'s row is row
    ``b``. q: [B, Hq, d]; k: [B, Hk, d]; v: [B, Hk, dv]; log_g: [B, Hk];
    live: [B] bool. Returns (o [B, Hq, dv] in q's dtype, S, z)."""
    return retention_walk(q, k, v, log_g, S, z,
                          jnp.arange(q.shape[0], dtype=jnp.int32),
                          live.astype(jnp.int32), lens, eps)


def _aligned(start, ql, n, nal, step=STRIP):
    """Every slot's rows moved to a multiple of ``step`` (a dynamic row
    index inside the kernel has to be one of the sublane tile; the latent
    append kernel asks for its own): (the slots' new first rows
    [B]; for each new row the old row it holds [nal] (any row where it
    holds none); for each old row its new row [n]; the old rows a slot
    owns [n] bool)."""
    room = -(-ql // step) * step
    new = (jnp.cumsum(room) - room).astype(jnp.int32)

    def owner(rows, first):
        at = rows[:, None] - first[None, :]                   # [rows, B]
        mine = (at >= 0) & (at < ql[None, :])
        return mine, at
    mine, at = owner(jnp.arange(nal, dtype=jnp.int32), new)
    old_of = jnp.sum(jnp.where(mine, start[None, :] + at, 0), axis=1)
    mine, at = owner(jnp.arange(n, dtype=jnp.int32), start)
    new_of = jnp.sum(jnp.where(mine, new[None, :] + at, 0), axis=1)
    return new, old_of, new_of, jnp.any(mine, axis=1)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"), inline=True)
def _walk_call(q, k, v, log_g, S, z, start, q_lens, lens, *, eps, interpret):
    """The operands head-major with every slot's rows on a sublane tile,
    and the Pallas call, under one inlined inner jit so that a model's
    layers share a trace."""
    n, hq, d = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    G = hq // hk
    B, _, D, _ = S.shape
    if d % (2 * STRIP) or dv != d or D != d * (d + 1) // 2:
        raise ValueError(
            f"power_retention_walk is written for a head width that is a "
            f"multiple of {2 * STRIP} and equal for keys and values, and "
            f"a state of d (d + 1) / 2 rows: got d={d}, dv={dv}, D={D}")
    ql = q_lens.astype(jnp.int32)
    # room for every slot's rows on a tile of their own, and a slice of
    # SUB rows from any live row on stays inside the axis
    nal = -(-n // STRIP) * STRIP + STRIP * B + SUB
    new, old_of, new_of, owned = _aligned(start.astype(jnp.int32), ql, n,
                                          nal)
    exact = all(a.dtype == BF16 for a in (q, k, v))

    def heads_first(a):
        return jnp.moveaxis(jnp.take(a, old_of, axis=0).astype(F32), 1, 0)

    lg = jnp.broadcast_to(heads_first(log_g)[..., None], (hk, nal, d))
    Bp = -(-B // STRIP) * STRIP
    zt = jnp.pad(jnp.swapaxes(z.astype(F32), 0, 1),
                 [(0, 0), (0, Bp - B), (0, 0)])
    rows = lambda heads, w: pl.BlockSpec(  # noqa: E731
        (heads, nal, w), _head_map)
    state = pl.BlockSpec((None, None, D, dv), _state_map)
    zs = pl.BlockSpec((None, Bp, D), _head_map)
    columns = (STRIP, d + d // 2, d)
    o, S, zt = pl.pallas_call(
        functools.partial(_kernel, G=G, d=d, eps=eps, exact=exact),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(hk, B),
            in_specs=[rows(G, d), rows(None, d), rows(None, dv),
                      rows(None, d), state, zs],
            out_specs=[rows(G, dv), state, zs],
            scratch_shapes=[pltpu.VMEM(columns, F32),
                            pltpu.VMEM((G,) + columns, F32),
                            pltpu.VMEM((d, dv), F32),
                            pltpu.VMEM((d, d), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((hq, nal, dv), F32),
                   jax.ShapeDtypeStruct(S.shape, F32),
                   jax.ShapeDtypeStruct(zt.shape, F32)],
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # every block double-buffered, the columns, and room for a
            # sub-chunk's values
            vmem_limit_bytes=8 * (nal * (2 * G + 3) * d + 2 * D * (dv + Bp)
                                  + (G + 2) * STRIP * 2 * d * d)
            + (24 << 20)),
        name="power_retention_walk",
        interpret=interpret,
    )(new, ql, lens.astype(jnp.int32), *_walk(ql), heads_first(q),
      heads_first(k), heads_first(v), lg, S.astype(F32), zt)
    o = jnp.take(jnp.moveaxis(o, 0, 1), new_of, axis=0)
    return jnp.where(owned[:, None, None], o, 0.0).astype(q.dtype), S, \
        jnp.swapaxes(zt[:, :B], 0, 1)
