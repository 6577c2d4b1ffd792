"""Power retention (arXiv:2507.04239, "Scaling Context Requires Rethinking
Attention"; the mixer of Brumby-14B-Base): causal attention whose weight is
a POWER of the score, ``(q.k / sqrt d)^p``, under a scalar gate a head, and
which therefore has an exact recurrent form with a fixed-size state. Degree
``p = 2`` only. Float32 state.

Per key/value head (``G`` query heads read one key/value head's state),
with ``log g_t <= 0`` and ``G_t = sum_{r<=t} log g_r``::

    attention form   A[t, s] = (q_t . k_s / sqrt d)^2 exp(G_t - G_s), s <= t
                     o_t = sum_s A[t, s] v_s / (sum_s A[t, s] + eps)
    recurrent form   S_t = g_t S_{t-1} + phi(k_t') v_t^T        [D, dv]
                     z_t = g_t z_{t-1} + phi(k_t')              [D]
                     o_t = phi(q_t')^T S_t / (phi(q_t')^T z_t + eps)

with ``x' = x / d^(1/4)`` and :func:`phi` such that ``phi(a) . phi(b) ==
(a . b)^2`` exactly: the symmetric degree-2 monomials ``a_i a_j``, the
off-diagonal ones times sqrt 2, ``D = d (d + 1) / 2`` of them.

**The served path runs none of the forms below**: ``models/brumby.py``
calls ``power_retention_walk`` (a Pallas kernel, the same function on the
same state, which reads and writes a live slot's state once a step). The
three forms here are plain XLA, the same function, and what the tests hold
the kernel to (``tests/test_power_retention_kernel.py``):

- :func:`retention_attention`: the attention form (what holds the others
  and the kernel; never run over a whole served context);
- :func:`retention_step`: one row a slot against its state, all slots at
  once, in one pass over every slot's state;
- :func:`retention_walk`: a step of several rows a slot. The rows lie on
  ONE flat axis, slot ``b``'s ``q_lens[b]`` rows adjacent from
  ``start[b]`` (a mixed step's packed row axis as it is; no per-slot
  ``[B, S]`` view is ever made). The slots with ONE live row take the
  one-token form in one pass over every slot's state, the other slots the
  identity. Then a loop over the slots with MORE rows, each a loop over
  its live sub-chunks of :data:`SUB` rows in the chunk form: inside a
  sub-chunk the attention form against its own keys, plus ``phi(Q)``
  against the state that entered it, decayed by the gates up to and
  including the row; the state advanced once a sub-chunk.

**The order of the monomials** is by diagonals of the ``d x d`` product:
``phi(a) = [a * roll(a, -r) for r = 0 .. d/2]``, the first block the
squares, the blocks after it times sqrt 2, the last (``r = d/2``, whose
pairs each occur twice) cut to its first half. ``d`` lanes a block and no
gather: a lane rotation and a product. Any order is the same model.

A slot whose ``lens`` is 0 starts from ``S = 0, z = 0`` IN THE GRAPH when
its first live row comes (``cache_layout.Recurrent``); a dead row is the
identity (``g = 1``, ``phi(k) = 0``) and its output is never read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: rows of one sub-chunk of :func:`retention_walk`
SUB = 64
#: precision of the products that read the float32 state and ``phi``
STATE_PRECISION = jax.lax.Precision.HIGHEST
#: device-side counts a retention layer makes a step, in the order of the
#: vector :func:`step_counts` / :func:`walk_counts` return (``engine.stats``
#: names): slot states the served path's kernel fetched (each read once and
#: written once), those of them with a live row (all of them: a regression
#: to walking dead slots shows as a gap), live rows through the chunk form,
#: live rows through the one-token form
COUNTERS = ("ret_state_walked", "ret_state_live", "ret_rows_chunk",
            "ret_rows_step")
#: id on a step's ``pt:engine.emit`` span -> the counters whose sum of that
#: step it carries, over the layers: (slot, layer) states with a live row,
#: and the live rows through either form
EMIT_IDS = {"live_states": ("ret_state_live",),
            "ret_rows": ("ret_rows_chunk", "ret_rows_step")}


def feature_dim(d):
    """``D``: the symmetric degree-2 monomials of ``d`` values."""
    return d * (d + 1) // 2


def phi(a):
    """``[..., d] -> [..., d (d + 1) / 2]`` float32 with ``phi(a) . phi(b)
    == (a . b)^2``: by diagonals, see the module's docstring. (The rotated
    copies as ONE product with a constant 0/1 matrix compile to 17
    operations a layer of a one-token step where this form's 65 blocks
    compile to 400, and read the same there and SLOWER in a chunk on the
    chip: 8.70 against 6.93 ms a layer of the cell's mixed step, PERF.md
    section 6.)"""
    a = a.astype(F32)
    d = a.shape[-1]
    if d % 2:
        raise ValueError(f"phi is written for an even width, got {d}")
    root2 = jnp.float32(2.0 ** 0.5)
    blocks = [a * a] + [root2 * a * jnp.roll(a, -r, axis=-1)
                        for r in range(1, d // 2)]
    blocks.append((root2 * a * jnp.roll(a, -(d // 2), axis=-1))
                  [..., :d // 2])
    return jnp.concatenate(blocks, axis=-1)


def retention_attention(q, k, v, log_g, eps=1e-6):
    """The attention form on one batch of whole sequences from position 0.
    q: [B, T, Hq, d]; k: [B, T, Hk, d]; v: [B, T, Hk, dv]; log_g: [B, T,
    Hk]. Returns o [B, T, Hq, dv] float32."""
    hi = jax.lax.Precision.HIGHEST
    b, t, hq, d = q.shape
    hk = k.shape[2]
    qg = q.astype(F32).reshape(b, t, hk, hq // hk, d)
    s = jnp.einsum("bthgd,bshd->bhgts", qg, k.astype(F32),
                   precision=hi) / d ** 0.5
    G = jnp.cumsum(log_g.astype(F32), axis=1)                 # [B, T, Hk]
    dG = jnp.moveaxis(G, 1, 2)[..., :, None] - \
        jnp.moveaxis(G, 1, 2)[..., None, :]                   # [B, Hk, t, s]
    causal = jnp.tril(jnp.ones((t, t), bool))
    a = s * s * jnp.exp(jnp.where(causal, dG, -jnp.inf))[:, :, None]
    num = jnp.einsum("bhgts,bshv->bthgv", a, v.astype(F32), precision=hi)
    den = jnp.moveaxis(jnp.sum(a, -1), 3, 1)                  # [B, t, Hk, G]
    return (num / (den[..., None] + eps)).reshape(b, t, hq, v.shape[-1])


def _read(pq, S, z):
    """``phi(q)`` [..., Hk, G, D] against a state ``S`` [..., Hk, D, dv],
    ``z`` [..., Hk, D] -> (numerator [..., Hk, G, dv], denominator [...,
    Hk, G])."""
    num = jnp.einsum("...hgd,...hdv->...hgv", pq, S,
                     precision=STATE_PRECISION)
    den = jnp.einsum("...hgd,...hd->...hg", pq, z,
                     precision=STATE_PRECISION)
    return num, den


def _features(q, k):
    """``phi`` of one row a slot, scaled. q: [B, Hq, d]; k: [B, Hk, d] ->
    (phi(q') [B, Hk, G, D], phi(k') [B, Hk, D])."""
    b, hq, d = q.shape
    scale = jnp.float32(d ** -0.25)
    pq = phi(q.astype(F32) * scale).reshape(b, k.shape[1], hq // k.shape[1],
                                            -1)
    return pq, phi(k.astype(F32) * scale)


def _advance(pq, pk, v, log_g, S, z, live, fresh, eps):
    """One row a slot against its state, from the rows' features. pq: [B,
    Hk, G, D]; pk: [B, Hk, D]; v: [B, Hk, dv]; log_g: [B, Hk]; live,
    fresh: [B]. Returns (o [B, Hk * G, dv] float32, S, z)."""
    pk = jnp.where(live[:, None, None], pk, 0.0)
    g = jnp.where(live[:, None], jnp.exp(log_g.astype(F32)), 1.0)
    reset = (live & fresh)
    S = jnp.where(reset[:, None, None, None], 0.0, S)
    z = jnp.where(reset[:, None, None], 0.0, z)
    S = g[..., None, None] * S + pk[..., None] * v.astype(F32)[:, :, None, :]
    z = g[..., None] * z + pk
    num, den = _read(pq, S, z)
    o = num / (den[..., None] + eps)
    return o.reshape(o.shape[0], -1, o.shape[-1]), S, z


def retention_step(q, k, v, log_g, S, z, live, fresh, eps=1e-6):
    """The one-token form, every slot at once. q: [B, Hq, d]; k: [B, Hk,
    d]; v: [B, Hk, dv]; log_g: [B, Hk]; S: [B, Hk, D, dv] and z: [B, Hk,
    D] float32; live, fresh: [B] bool (a slot without a live row keeps
    its state; a fresh slot with one starts from zeros). Returns (o [B,
    Hq, dv] float32, S, z)."""
    pq, pk = _features(q, k)
    return _advance(pq, pk, v, log_g, S, z, live, fresh, eps)


def _sub_chunk(q, k, v, log_g, valid, S, z, eps):
    """The chunk form on ONE slot's ``C`` rows. q: [C, Hq, d]; k: [C, Hk,
    d]; v: [C, Hk, dv]; log_g: [C, Hk]; valid: [C] bool (dead rows are the
    identity); S: [Hk, D, dv]; z: [Hk, D]. Returns (o [C, Hq, dv], S,
    z)."""
    hi = jax.lax.Precision.HIGHEST
    c, hq, d = q.shape
    hk = k.shape[1]
    scale = jnp.float32(d ** -0.25)
    qs = (q.astype(F32) * scale).reshape(c, hk, hq // hk, d)
    ks = jnp.where(valid[:, None, None], k.astype(F32) * scale, 0.0)
    vf = jnp.where(valid[:, None, None], v.astype(F32), 0.0)
    G = jnp.cumsum(jnp.where(valid[:, None], log_g.astype(F32), 0.0), 0)
    Gh = G.T                                                  # [Hk, C]
    # inside the sub-chunk: the attention form, exponents <= 0
    s = jnp.einsum("thgd,shd->hgts", qs, ks, precision=hi)
    causal = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(causal, Gh[:, :, None] - Gh[:, None, :],
                              -jnp.inf))                      # [Hk, t, s]
    a = s * s * decay[:, None]
    num = jnp.einsum("hgts,shv->thgv", a, vf, precision=hi)
    den = jnp.moveaxis(jnp.sum(a, -1), 2, 0)                  # [t, Hk, G]
    # against the state that entered it, decayed up to and including t
    n_in, d_in = _read(phi(qs), S, z)
    eG = jnp.exp(G)                                           # [C, Hk]
    num = num + eG[:, :, None, None] * n_in
    den = den + eG[:, :, None] * d_in
    o = (num / (den[..., None] + eps)).reshape(c, hq, -1)
    # the state once a sub-chunk
    pk = phi(ks) * jnp.exp(G[-1][None] - G)[:, :, None]       # [C, Hk, D]
    g_end = jnp.exp(G[-1])                                    # [Hk]
    S = g_end[:, None, None] * S + jnp.einsum(
        "shd,shv->hdv", pk, vf, precision=STATE_PRECISION)
    z = g_end[:, None] * z + jnp.sum(pk, 0)
    return o, S, z


def _slot(x, b):
    return jax.lax.dynamic_index_in_dim(x, b, 0, keepdims=False)


def retention_walk(q, k, v, log_g, S, z, start, q_lens, lens, eps=1e-6,
                   sub=SUB):
    """A step of ``q_lens[b]`` rows a slot on one flat row axis. q: [N, Hq,
    d]; k: [N, Hk, d]; v: [N, Hk, dv]; log_g: [N, Hk]; slot ``b``'s rows
    are ``[start[b], start[b] + q_lens[b])`` in position order; S: [B, Hk,
    D, dv], z: [B, Hk, D] float32; lens: [B] tokens a slot had absorbed
    before (0: it starts from zeros). Returns (o [N, Hq, dv] in q's dtype,
    0 on rows no slot owns; S; z)."""
    n, hq, _ = q.shape
    dv = v.shape[-1]
    sub = int(sub)
    q_lens = q_lens.astype(jnp.int32)
    start = start.astype(jnp.int32)
    fresh = lens.astype(jnp.int32) == 0
    # a slice of ``sub`` rows from any live row on stays inside the axis
    q, k, v, log_g = (jnp.pad(a, [(0, sub)] + [(0, 0)] * (a.ndim - 1))
                      for a in (q, k, v, log_g))
    o = jnp.zeros((n + sub, hq, dv), q.dtype)
    one, many = q_lens == 1, q_lens > 1
    # the slots with a chunk first, in slot order (a stable sort)
    order_many = jnp.argsort(~many, stable=True).astype(jnp.int32)

    def rows(a, at, width):
        return jax.lax.dynamic_slice_in_dim(a, at, width, 0)

    def put(a, new, at):
        return jax.lax.dynamic_update_slice_in_dim(a, new, at, 0)

    # every slot's first row (a one-row slot's only one; the other slots'
    # is not used)
    first = [jnp.take(a, start, axis=0) for a in (q, k, v, log_g)]
    pq_first, pk_first = _features(first[0], first[1])

    def chunks(i, carry):
        S, z, o = carry
        b = order_many[i]
        at, rows_b = start[b], q_lens[b]
        Sb = jnp.where(fresh[b], 0.0, _slot(S, b))
        zb = jnp.where(fresh[b], 0.0, _slot(z, b))

        def chunk(c, inner):
            Sb, zb, o = inner
            lo = at + c * sub
            valid = jnp.arange(sub, dtype=jnp.int32) < rows_b - c * sub
            oc, Sb, zb = _sub_chunk(rows(q, lo, sub), rows(k, lo, sub),
                                    rows(v, lo, sub), rows(log_g, lo, sub),
                                    valid, Sb, zb, eps)
            # a ragged sub-chunk's dead rows belong to the slots after it
            oc = jnp.where(valid[:, None, None], oc.astype(o.dtype),
                           rows(o, lo, sub))
            return Sb, zb, put(o, oc, lo)

        Sb, zb, o = jax.lax.fori_loop(0, -(-rows_b // sub), chunk,
                                      (Sb, zb, o))
        return put(S, Sb[None], b), put(z, zb[None], b), o

    # the one-row slots: every slot's state in one pass, the slots without
    # exactly one live row the identity; their rows of o land on the axis'
    # last (padding) row
    o1, S, z = _advance(pq_first, pk_first, first[2], first[3],
                        S.astype(F32), z.astype(F32), one, fresh, eps)
    o = o.at[jnp.where(one, start, n + sub - 1)].set(o1.astype(o.dtype))
    S, z, o = jax.lax.fori_loop(0, jnp.sum(many, dtype=jnp.int32), chunks,
                                (S, z, o))
    return o[:n], S, z


def step_counts(live):
    """:data:`COUNTERS` of one one-token step over ``live`` [B] as the
    served path's kernel walks it: the live slots' states, each once."""
    n = jnp.sum(live, dtype=jnp.int32)
    return jnp.stack([n, n, jnp.int32(0), n])


def walk_counts(q_lens):
    """:data:`COUNTERS` of one step of ``q_lens[b]`` rows a slot as the
    served path's kernel walks it (``power_retention_walk``): it fetches
    the state of every slot with a live row once, whatever the slot's
    form, and no other."""
    q_lens = q_lens.astype(jnp.int32)
    one = jnp.sum(q_lens == 1, dtype=jnp.int32)
    many = jnp.sum(q_lens > 1, dtype=jnp.int32)
    return jnp.stack([one + many, one + many,
                      jnp.sum(jnp.where(q_lens > 1, q_lens, 0),
                              dtype=jnp.int32), one])
