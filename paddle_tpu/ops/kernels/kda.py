"""Kimi Delta Attention's recurrence: a gated delta rule with a decay per
key channel, in its one-token and its chunked form, and the short causal
convolution in front of it. Plain ``jax.numpy`` on raw arrays; float32
state, ``highest`` matmul precision inside the recurrence. On the chip a
step of more than one row a slot runs the recurrence in the Pallas kernel
beside this file (``kda_chunk_walk.py``: 64-row chunks, only a step's live
ones, read off a mixed step's packed rows where they lie);
:func:`kda_recurrent` serves the one-row steps, and
:func:`kda_chunk` is the form the kernel is held to by the tests and what
a head width off the lanes (a toy model's) still takes.

Per head, with state ``S`` [K, V], log-decay ``g_t`` [K] (<= 0), write
strength ``beta_t`` and unit-norm ``k_t``::

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

A DEAD row is the identity update ``g = 0, beta = 0``: the callers mask the
rows of a step that are not live that way, so a slot's state after a step
is exactly its state after the step's live rows.

The chunked form (:func:`kda_chunk`) walks ``C`` rows at a time. Inside a
chunk, with ``G_t`` the running sum of ``g`` from the chunk's start::

    A[s, r] = sum_c k_s[c] k_r[c] exp(G_s[c] - G_r[c])      (r <  s)
    B[s, r] = sum_c q_s[c] k_r[c] exp(G_s[c] - G_r[c])      (r <= s)
    U = (I + diag(beta) A)^-1 diag(beta) (V - (k * exp(G)) S_0)
    O = (q * exp(G)) S_0 + B U
    S_C = diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T U

Every exponent is <= 0 (``G`` only falls), so nothing overflows however
strong the decay: ``A`` and ``B`` are formed elementwise over the channel
axis and never as a product of ``exp(G)`` and ``exp(-G)``. That is why the
chunk is short (16 rows: a [C, C, K] tensor a head); the longer chunk with
secondary chunking is the kernel's (``kda_chunk_walk.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
#: rows of one chunk of :func:`kda_chunk`
CHUNK = 16


def causal_conv(x, tail, w, q_lens=None):
    """Depthwise causal convolution of ``w.shape[0]`` taps a channel over
    rows that continue a stream. x: [B, S, D] new inputs; tail: [B, taps-1,
    D] the stream's last inputs before them; w: [taps, D] (tap ``taps-1``
    multiplies the current input). Returns (y [B, S, D] float32, the new
    tail [B, taps-1, D] in the tail's dtype): the last ``taps-1`` inputs
    after the first ``q_lens[b]`` rows (all ``S`` when None)."""
    taps = w.shape[0]
    s = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(ext[:, j:j + s].astype(jnp.float32) * wf[j] for j in range(taps))
    if q_lens is None:
        new_tail = ext[:, s:]
    else:
        idx = q_lens.astype(jnp.int32)[:, None] + \
            jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
        new_tail = jnp.take_along_axis(ext, idx[:, :, None], axis=1)
    return y, new_tail.astype(tail.dtype)


def causal_conv_packed(x, tail, w, rows):
    """:func:`causal_conv` on a mixed step's PACKED rows, so that the
    convolution runs on the rows the step granted and not on a ``[slots,
    chunk]`` view of them (at 16 slots of 512 rows and 24,576 channels the
    view's float32 output is 805 MB for 527 live rows). x: [T, D] the
    packed inputs; tail: [B, taps-1, D] each slot's last inputs before
    its rows; rows: the step's :class:`~paddle_tpu.models.cache_layout
    .RowMap` (a slot's rows adjacent and in order). Packed row ``t`` of
    slot ``b`` at column ``i`` reads ``x[t - d]`` for a tap ``d`` rows back
    while ``i >= d`` and the slot's tail before that; a padding row's
    output is finite and nobody's. Returns (y [T, D] float32, the new tail
    [B, taps-1, D]: the slot's last ``taps-1`` inputs after its
    ``q_lens[b]`` live rows, its old tail where it has none)."""
    taps, n = w.shape[0], w.shape[0] - 1
    t = x.shape[0]
    wf = w.astype(jnp.float32)
    tl = tail.astype(x.dtype)
    col = rows.col
    y = 0.0
    for j in range(taps):
        d = n - j                         # how many rows back tap j reads
        if d == 0:
            xin = x
        else:
            back = jnp.concatenate(
                [jnp.zeros((d,) + x.shape[1:], x.dtype), x[:t - d]])
            old = tl[rows.slot, jnp.clip(n - d + col, 0, n - 1)]
            xin = jnp.where((col >= d)[:, None], back, old)
        y = y + xin.astype(jnp.float32) * wf[j]
    e = rows.q_lens[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    new = x[jnp.clip(rows.start[:, None] + e - n, 0, t - 1)]  # [B, n, D]
    kept = jnp.take_along_axis(tl, jnp.clip(e, 0, n - 1)[:, :, None],
                               axis=1)
    new_tail = jnp.where((e >= n)[:, :, None], new, kept)
    return y, new_tail.astype(tail.dtype)


def kda_recurrent(q, k, v, g, beta, state):
    """The one-token form, a ``lax.scan`` over the rows. q, k, g: [B, T, H,
    K]; v: [B, T, H, V]; beta: [B, T, H]; state: [B, H, K, V] float32.
    Returns (o [B, T, H, V] float32, the state after the last row)."""
    f32 = jnp.float32

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt,
                                             precision=HI))
        S = S + kt[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=HI)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(n):
    """(I + n)^-1 for strictly lower triangular ``n`` [..., C, C] by forward
    substitution, a row at a time: row s of the inverse is ``e_s - n[s] @
    rows before it``."""
    c = n.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(c, dtype=n.dtype), n.shape)

    def body(s, t):
        row = jax.lax.dynamic_slice_in_dim(n, s, 1, axis=-2)     # [..,1,C]
        new = jax.lax.dynamic_slice_in_dim(eye, s, 1, axis=-2) - \
            jnp.einsum("...or,...rc->...oc", row, t, precision=HI)
        return jax.lax.dynamic_update_slice_in_dim(t, new, s, axis=-2)

    return jax.lax.fori_loop(1, c, body, eye)


def kda_chunk(q, k, v, g, beta, state, chunk=CHUNK):
    """The chunked form; same arguments and results as
    :func:`kda_recurrent`. ``T`` is padded to a multiple of ``chunk`` with
    dead rows."""
    f32 = jnp.float32
    b, t, h, _ = q.shape
    vd = v.shape[-1]
    c = min(int(chunk), t)
    pad = (-t) % c
    n = (t + pad) // c

    def prep(a):
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        # [B, T, H, ...] -> [n, B, H, C, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qc, kc, vc, gc = prep(q), prep(k), prep(v), prep(g)
    bc = prep(beta[..., None])[..., 0]                    # [n, B, H, C]
    G = jnp.cumsum(gc, axis=-2)                           # [n, B, H, C, K]
    # pairwise decays, exponent <= 0 on and below the diagonal
    dG = G[..., :, None, :] - G[..., None, :, :]          # [.., s, r, K]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where((col <= row)[..., None], dG, -jnp.inf))
    kk = kc[..., None, :, :] * decay                      # k_r decayed to s
    # one reduction for both (k and q stacked in front)
    AB = jnp.sum(jnp.stack([kc, qc])[..., :, None, :] * kk, axis=-1)
    A = jnp.where(col < row, AB[0], 0.0)
    Bm = AB[1]                                            # r <= s kept
    T = _unit_lower_inverse(bc[..., None] * A)            # [n, B, H, C, C]
    eG = jnp.exp(G)
    k_in, q_in = kc * eG, qc * eG
    k_out = kc * jnp.exp(G[..., -1:, :] - G)
    g_end = eG[..., -1, :]                                # [n, B, H, K]
    Tb = T * bc[..., None, :]                             # T diag(beta)

    def step(S, xs):
        k_i, q_i, k_o, v_i, Tb_i, B_i, ge = xs
        rhs = v_i - jnp.einsum("bhck,bhkv->bhcv", k_i, S, precision=HI)
        U = jnp.einsum("bhcr,bhrv->bhcv", Tb_i, rhs, precision=HI)
        o = jnp.einsum("bhck,bhkv->bhcv", q_i, S, precision=HI) + \
            jnp.einsum("bhcr,bhrv->bhcv", B_i, U, precision=HI)
        S = S * ge[..., None] + jnp.einsum("bhck,bhcv->bhkv", k_o, U,
                                           precision=HI)
        return S, o

    state, o = jax.lax.scan(step, state.astype(f32),
                            (k_in, q_in, k_out, vc, Tb, Bm, g_end))
    # [n, B, H, C, V] -> [B, T, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * c, h, vd)
    return o[:, :t], state
