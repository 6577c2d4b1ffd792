"""Pallas TPU flash attention (forward + backward).

Reference analog: the FA2 CUDA library behind phi/kernels/gpu/flash_attn_kernel.cu
and python/paddle/nn/functional/flash_attention.py. This is a from-scratch TPU
kernel: online-softmax tiles sized for the MXU (q blocks x kv blocks, fp32
accumulators in VMEM), causal block skipping via dynamic loop bounds, GQA handled
zero-copy by mapping q-head grid indices onto kv heads in the BlockSpec index_map.

Layout contract: public API takes paddle's [B, S, H, D]; kernels run [B*H, S, D].
On non-TPU backends the same kernels run under interpret mode (tests), so CPU and
TPU execute identical code.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import os

DEFAULT_BLOCK_Q = int(os.environ.get("PT_FLASH_BLOCK_Q", "256"))
DEFAULT_BLOCK_K = int(os.environ.get("PT_FLASH_BLOCK_K", "512"))
# the backward kernels prefer a larger q block than the forward (they loop
# q-blocks innermost for dk/dv). Measured in-process, n=100 reps (B=3 S=2048
# H=32 D=128, v5e): fwd(256,512)+bwd(512,512) = 5.24 ms vs 6.02 ms with
# shared (256,512) — ~69 TF/s combined.
# bwd defaults are independent of the fwd env overrides: tuning the fwd
# q-block (e.g. down to 128 for VMEM) must not silently drop the measured
# 512 bwd default — set PT_FLASH_BLOCK_*_BWD explicitly to change these
DEFAULT_BLOCK_Q_BWD = int(os.environ.get("PT_FLASH_BLOCK_Q_BWD", "512"))
DEFAULT_BLOCK_K_BWD = int(os.environ.get("PT_FLASH_BLOCK_K_BWD", "512"))
NEG_INF = np.float32(-1e30)
# Index-map literals MUST be i32: python ints become i64 constants under the
# framework's jax_enable_x64 and Mosaic then fails to legalize the index-map
# functions ("failed to legalize operation 'func.return'").
Z = np.int32(0)


def _interpret():
    return jax.default_backend() not in ("tpu",)


def _keep_mask(seed_i, bh_i, rows, cols, sq, sk, dropout_p):
    """Deterministic per-ELEMENT dropout mask from the absolute (head, row,
    col) position — a murmur3-style integer hash, so forward and backward
    reproduce the identical mask even with DIFFERENT block tilings (the
    bwd kernels use larger q blocks). int32 arithmetic wraps (two's
    complement) — the few collisions from wraparound are irrelevant for
    dropout. Uses 31 uniform bits via an unsigned-free compare."""
    idx = (bh_i * np.int32(sq) + rows) * np.int32(sk) + cols
    h = idx * np.int32(-1640531527) + seed_i          # 0x9E3779B9
    h = h ^ jax.lax.shift_right_logical(h, np.int32(16))
    h = h * np.int32(-2048144789)                     # 0x85EBCA6B
    h = h ^ jax.lax.shift_right_logical(h, np.int32(13))
    h = h * np.int32(-1028477387)                     # 0xC2B2AE35
    h = h ^ jax.lax.shift_right_logical(h, np.int32(16))
    hb = h & np.int32(0x7FFFFFFF)
    thr = np.int32(int(dropout_p * 2147483648.0))
    return hb >= thr


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------



def _kv_index_map(group):
    """Map q-head grid index -> kv-head row (GQA). lax.div keeps i32 under x64
    (a plain `//` promotes and breaks Mosaic's index-map lowering)."""
    if group == 1:
        return lambda i, j: (i, Z, Z)
    return lambda i, j: (jax.lax.div(i, np.int32(group)), Z, Z)


def _kv_block_index_map(group):
    if group == 1:
        return lambda i, j: (i, j, Z)
    return lambda i, j: (jax.lax.div(i, np.int32(group)), j, Z)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                causal, bq, bk, sq, sk, dropout_p):
    bq_i, bk_i = np.int32(bq), np.int32(bk)  # i32 scalars for index math (x64 on)
    q = q_ref[0].astype(jnp.float32) * np.float32(scale)   # [bq, D]
    bh_i = pl.program_id(0)
    jq = pl.program_id(1)
    num_kv = sk // bk
    seed_i = jax.lax.bitcast_convert_type(seed_ref[...],
                                          jnp.int32)[0, 0]

    if causal:
        # last kv block that intersects rows [jq*bq, jq*bq+bq)
        limit = jnp.minimum((jq * bq_i + bq_i + bk_i - np.int32(1)) // bk_i,
                            np.int32(num_kv)).astype(jnp.int32)
    else:
        limit = jnp.int32(num_kv)

    def body(kv_i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kv_i * bk_i, bk), :]        # [bk, D]
        v = v_ref[0, pl.ds(kv_i * bk_i, bk), :]
        s = jax.lax.dot_general(q, k.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        rows = jq * bq_i + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kv_i * bk_i + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        # the normalizer uses the UNmasked p: dropout applies to the
        # normalized probabilities (reference softmax-then-dropout), and the
        # lse must stay a dropout-free statistic for the backward
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_i, bh_i, rows, cols, sq, sk, dropout_p)
            p = jnp.where(keep, p, 0.0) * np.float32(1.0 / (1.0 - dropout_p))
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    D = q_ref.shape[-1]
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(jnp.int32(0), limit, body, (m0, l0, a0))
    l = jnp.maximum(l, np.float32(1e-30))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)            # [bq, 1]


def _fwd(q, k, v, causal, scale, bq, bk, dropout_p, seed_f):
    """q: [BHq, Sq, D]; k/v: [BHkv, Sk, D]."""
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    group = bh // bh_kv
    grid = (bh, sq // bq)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                               bk=bk, sq=sq, sk=sk, dropout_p=dropout_p)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (Z, Z)),
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, Z)),
            pl.BlockSpec((1, sk, d), _kv_index_map(group)),
            pl.BlockSpec((1, sk, d), _kv_index_map(group)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, Z)),
            pl.BlockSpec((1, bq, 1), lambda i, j: (i, j, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=_interpret(),
    )(seed_f, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, bq, bk, sq, sk,
                    dropout_p):
    bq_i, bk_i = np.int32(bq), np.int32(bk)
    scale = np.float32(scale)
    k = k_ref[0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0].astype(jnp.float32)
    bh_i = pl.program_id(0)
    jk = pl.program_id(1)
    num_q = sq // bq
    start = ((jk * bk_i) // bq_i).astype(jnp.int32) if causal else jnp.int32(0)
    seed_i = jax.lax.bitcast_convert_type(seed_ref[...],
                                          jnp.int32)[0, 0]
    inv_keep = np.float32(1.0 / (1.0 - dropout_p)) if dropout_p > 0.0 else None

    D = k_ref.shape[-1]

    def body(q_i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(q_i * bq_i, bq), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(q_i * bq_i, bq), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(q_i * bq_i, bq), :]                         # [bq,1]
        delta = delta_ref[0, pl.ds(q_i * bq_i, bq), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)       # [bq,bk]
        rows = q_i * bq_i + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = jk * bk_i + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                                              # [bq,bk]
        # with dropout, the weights actually used were z = keep*p/keep_prob
        # (same position-hashed mask as the forward); d/dp gets the same
        # mask: softmax-bwd delta is unchanged (delta = sum(do*o))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_i, bh_i, rows, cols, sq, sk, dropout_p)
            z = jnp.where(keep, p, 0.0) * inv_keep
            dp = jnp.where(keep, dp, 0.0) * inv_keep
        else:
            z = p
        dv = dv + jax.lax.dot_general(z, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                                             # [bq,bk]
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((bk, D), jnp.float32)
    dv0 = jnp.zeros((bk, D), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, jnp.int32(num_q), body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)   # note: dk already includes `scale` via q
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, scale, causal, bq, bk, sq, sk, dropout_p):
    bq_i, bk_i = np.int32(bq), np.int32(bk)
    scale = np.float32(scale)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]          # [bq, 1]
    delta = delta_ref[0]
    bh_i = pl.program_id(0)
    jq = pl.program_id(1)
    num_kv = sk // bk
    limit = (jnp.minimum((jq * bq_i + bq_i + bk_i - np.int32(1)) // bk_i,
                         np.int32(num_kv)).astype(jnp.int32)
             if causal else jnp.int32(num_kv))
    seed_i = jax.lax.bitcast_convert_type(seed_ref[...],
                                          jnp.int32)[0, 0]
    inv_keep = np.float32(1.0 / (1.0 - dropout_p)) if dropout_p > 0.0 else None
    D = q_ref.shape[-1]

    def body(kv_i, dq):
        k = k_ref[0, pl.ds(kv_i * bk_i, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kv_i * bk_i, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows = jq * bq_i + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kv_i * bk_i + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_i, bh_i, rows, cols, sq, sk, dropout_p)
            dp = jnp.where(keep, dp, 0.0) * inv_keep
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(jnp.int32(0), limit, body,
                           jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, scale, bq, bk, dropout_p, seed_f):
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    group = bh // bh_kv
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [BH, Sq, 1]

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                   bq=bq, bk=bk, sq=sq, sk=sk,
                                   dropout_p=dropout_p)
    # dk/dv computed per Q-head then summed over the GQA group
    dk_h, dv_h = pl.pallas_call(
        dkv_kernel,
        grid=(bh, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (Z, Z)),
            pl.BlockSpec((1, sq, d), lambda i, j: (i, Z, Z)),
            pl.BlockSpec((1, bk, d), _kv_block_index_map(group)),
            pl.BlockSpec((1, bk, d), _kv_block_index_map(group)),
            pl.BlockSpec((1, sq, d), lambda i, j: (i, Z, Z)),
            pl.BlockSpec((1, sq, 1), lambda i, j: (i, Z, Z)),
            pl.BlockSpec((1, sq, 1), lambda i, j: (i, Z, Z)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, Z)),
            pl.BlockSpec((1, bk, d), lambda i, j: (i, j, Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sk, d), jnp.float32),
        ],
        name="flash_attention_bwd_dkv",
        interpret=_interpret(),
    )(seed_f, q, k, v, do, lse, delta)
    if group > 1:
        dk = dk_h.reshape(bh_kv, group, sk, d).sum(axis=1).astype(k.dtype)
        dv = dv_h.reshape(bh_kv, group, sk, d).sum(axis=1).astype(v.dtype)
    else:
        dk, dv = dk_h.astype(k.dtype), dv_h.astype(v.dtype)

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                  bq=bq, bk=bk, sq=sq, sk=sk,
                                  dropout_p=dropout_p)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (Z, Z)),
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, Z)),
            pl.BlockSpec((1, sk, d), _kv_index_map(group)),
            pl.BlockSpec((1, sk, d), _kv_index_map(group)),
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, Z)),
            pl.BlockSpec((1, bq, 1), lambda i, j: (i, j, Z)),
            pl.BlockSpec((1, bq, 1), lambda i, j: (i, j, Z)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, Z)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        name="flash_attention_bwd_dq",
        interpret=_interpret(),
    )(seed_f, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API with custom VJP — [B, S, H, D] layout
# ---------------------------------------------------------------------------

def _to_bhsd(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d), (b, h)


def _from_bhsd(x, bh_shape):
    b, h = bh_shape
    bhd, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


def _pick_blocks(s, default):
    blk = min(default, s)
    while s % blk != 0:
        blk //= 2
    return max(blk, 1)


def _zero_seed():
    # host constant, NEVER a cached jnp array: the first call can happen
    # inside a trace (remat/jit) and a cached tracer would leak out of it
    return np.zeros((1, 1), np.float32)


def seed_carrier(key):
    """Fold a jax PRNG key into the (1,1) f32 bit-carrier the kernels take
    (f32 so it can pass through custom_vjp with a plain zero cotangent;
    kernels bitcast it back to int32 for the position-hashed dropout)."""
    bits = jax.random.bits(key, (1, 1), jnp.uint32)
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint32),
                                        jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core(q, k, v, causal, scale, dropout_p, seed_f):
    out, _ = _flash_fwd_res(q, k, v, causal, scale, dropout_p, seed_f)
    return out


def flash_attention_fwd(q, k, v, causal=False, scale=None, dropout_p=0.0,
                        seed_f=None):
    """Flash attention with optional in-kernel dropout. ``seed_f``: the
    (1,1) f32 bit-carrier from :func:`seed_carrier` (required when
    dropout_p > 0 and training randomness should vary per step)."""
    if seed_f is None:
        seed_f = _zero_seed()
    return _flash_core(q, k, v, causal, scale, float(dropout_p), seed_f)


def flash_attention_tp(q, k, v, mesh, axis, causal=False, scale=None,
                       dropout_p=0.0, seed_f=None):
    """:func:`flash_attention_fwd` under tensor parallelism: heads (dim 2
    of [B, S, H, D]) split over mesh axis ``axis`` and every shard runs the
    unmodified kernels — forward and, through the custom VJP, backward —
    on its local heads. GSPMD cannot partition a Mosaic call (interpret
    mode on CPU hides that: there the kernel is ordinary XLA ops), so a
    program whose heads are sharded must come through here. kv heads split
    alongside (GQA: q heads [h*G, (h+1)*G) follow kv head h, so an even
    kv-head split carries its q groups with it). No collective is issued:
    output heads stay sharded for the row-parallel o_proj to reduce. Mesh
    axes other than ``axis`` see replicated operands."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(
            f"flash_attention_tp: {q.shape[2]} q heads / {k.shape[2]} kv "
            f"heads do not split over mesh axis {axis!r} of size {n}")
    if seed_f is None:
        seed_f = _zero_seed()
    dropout_p = float(dropout_p)

    def local(q_s, k_s, v_s, seed_s):
        if dropout_p > 0.0:
            # the dropout hash is keyed on the LOCAL (batch*head) index:
            # fold the shard's position in so shards draw distinct masks
            si = jax.lax.bitcast_convert_type(seed_s, jnp.int32)
            si = si ^ (jax.lax.axis_index(axis).astype(jnp.int32)
                       * np.int32(-1640531527))
            seed_s = jax.lax.bitcast_convert_type(si, jnp.float32)
        return _flash_core(q_s, k_s, v_s, causal, scale, dropout_p, seed_s)

    heads = P(None, None, axis, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(heads, heads, heads, P()),
                         out_specs=heads, check_vma=False)(q, k, v, seed_f)


def _flash_fwd_res(q, k, v, causal, scale, dropout_p=0.0, seed_f=None):
    # kernel masks top-left aligned; bottom-right (paddle) semantics only
    # coincide for equal lengths — hard error beats silent corruption.
    assert not causal or q.shape[1] == k.shape[1], \
        "flash_attention_fwd: causal requires seq_q == seq_k (decode goes " \
        "through scaled_dot_product_attention's XLA path)"
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if seed_f is None:
        seed_f = _zero_seed()
    q3, bhq = _to_bhsd(q)
    k3, _ = _to_bhsd(k)
    v3, _ = _to_bhsd(v)
    bq = _pick_blocks(q3.shape[1], DEFAULT_BLOCK_Q)
    bk = _pick_blocks(k3.shape[1], DEFAULT_BLOCK_K)
    o3, lse = _fwd(q3, k3, v3, causal, scale, bq, bk, dropout_p, seed_f)
    out = _from_bhsd(o3, bhq)
    return out, (q3, k3, v3, o3, lse, bhq, scale, seed_f)


def _flash_vjp_fwd(q, k, v, causal, scale, dropout_p, seed_f):
    out, res = _flash_fwd_res(q, k, v, causal, scale, dropout_p, seed_f)
    return out, res


def _flash_vjp_bwd(causal, scale_arg, dropout_p, res, g):
    q3, k3, v3, o3, lse, bhq, scale, seed_f = res
    b, h = bhq
    do3, _ = _to_bhsd(g)
    bq_b = _pick_blocks(q3.shape[1], DEFAULT_BLOCK_Q_BWD)
    bk_b = _pick_blocks(k3.shape[1], DEFAULT_BLOCK_K_BWD)
    dq3, dk3, dv3 = _bwd(q3, k3, v3, o3, lse, do3, causal, scale, bq_b, bk_b,
                         dropout_p, seed_f)
    kv_h = k3.shape[0] // b
    dq = _from_bhsd(dq3, (b, h))
    dk = _from_bhsd(dk3, (b, kv_h))
    dv = _from_bhsd(dv3, (b, kv_h))
    return dq, dk, dv, jnp.zeros_like(seed_f)


_flash_core.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
