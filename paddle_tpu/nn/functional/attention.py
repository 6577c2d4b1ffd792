"""Attention functionals.

Reference: python/paddle/nn/functional/flash_attention.py (flash_attention:358,
scaled_dot_product_attention:1139, flashmask_attention:1299) → FA2 CUDA library.
TPU-native: the public API accepts paddle's [batch, seq, heads, head_dim] layout and
routes to a Pallas flash-attention kernel on TPU (ops/kernels/flash_attention.py);
elsewhere (CPU tests) it uses the exact jnp reference path. Dropout inside attention
uses the global RNG stream.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, dispatch
from ...core import random as _random
from ...core.flags import define_flag, flag_value

define_flag("use_pallas_flash_attention", True,
            "route scaled_dot_product_attention to the Pallas kernel on TPU")


def _sdpa_reference(q, k, v, mask, causal, dropout_p, dropout_key, scale=None):
    """Exact attention in [B, S, H, D] layout; fp32 softmax accumulation."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    # grouped-query: broadcast kv heads
    if kt.shape[1] != qt.shape[1]:
        rep = qt.shape[1] // kt.shape[1]
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cmask, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), vt)
    return jnp.swapaxes(out, 1, 2)  # back to [B, S, H, D]


#: trace-time TP routing of the flash kernel: (mesh, axis) while an
#: attention layer whose heads are stored sharded over ``axis`` calls
#: scaled_dot_product_attention, else None. Thread-local, like the paged
#: kernels' context (ops/kernels/paged_attention.py).
_FLASH_TP = threading.local()


@contextlib.contextmanager
def flash_tp_context(tp):
    """Route the Pallas flash kernel through its shard_map wrapper over
    ``tp = (mesh, axis)`` for the duration (None: no-op). Set by the layer
    that knows its heads are sharded — a Mosaic call cannot be partitioned
    by GSPMD."""
    prev = getattr(_FLASH_TP, "value", None)
    _FLASH_TP.value = tp
    try:
        yield
    finally:
        _FLASH_TP.value = prev


def _use_pallas(q_val):
    if not flag_value("use_pallas_flash_attention"):
        return False
    try:
        dev = next(iter(q_val.devices()))
        return dev.platform == "tpu"
    except Exception:
        # tracer (jit/checkpoint/vmap): no device on the value — decide from
        # the backend. Returning False here would silently downgrade remat'd
        # attention to the O(S^2)-memory einsum path.
        return jax.default_backend() == "tpu"


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """paddle.nn.functional.scaled_dot_product_attention — [B, S, H, D] layout."""
    if not training:
        dropout_p = 0.0
    dropout_key = _random.next_key() if dropout_p > 0.0 else None

    q_val = query._value if isinstance(query, Tensor) else query
    k_val = key._value if isinstance(key, Tensor) else key
    # Pallas kernel masks top-left aligned (rows >= cols); the reference
    # semantics are bottom-right aligned (tril k=sk-sq), which only coincide
    # when sq == sk — route unequal lengths (e.g. kv-cache decode) to the
    # XLA path.
    if (_use_pallas(q_val) and attn_mask is None and dropout_p < 1.0
            and (not is_causal or q_val.shape[1] == k_val.shape[1])):
        from ...ops.kernels.flash_attention import (flash_attention_fwd,
                                                    flash_attention_tp,
                                                    seed_carrier)
        tp = getattr(_FLASH_TP, "value", None)
        if tp is not None:
            flash_attention_fwd = functools.partial(flash_attention_tp,
                                                    mesh=tp[0], axis=tp[1])
        if dropout_p > 0.0:
            # dropout runs INSIDE the kernel (position-hashed mask, same in
            # fwd and bwd) — without this, every dropout-using transformer
            # (bert/vit) would fall off the flash path onto O(S^2) einsum.
            # The seed crosses the DISPATCH boundary as int32 so AMP's
            # cast-all-float-leaves autocast can't corrupt the bit pattern
            # (the op name is AMP white-listed — q/k/v still downcast).
            seed_i = jax.lax.bitcast_convert_type(seed_carrier(dropout_key),
                                                  jnp.int32)

            def fn(q, k, v, si):
                sf = jax.lax.bitcast_convert_type(si, jnp.float32)
                return flash_attention_fwd(q, k, v, causal=is_causal,
                                           dropout_p=dropout_p, seed_f=sf)
            return dispatch(fn, (query, key, value, seed_i), {},
                            name="flash_attention_dropout")

        def fn(q, k, v):
            return flash_attention_fwd(q, k, v, causal=is_causal)
        return dispatch(fn, (query, key, value), {}, name="flash_attention")

    def fn(q, k, v, *m):
        return _sdpa_reference(q, k, v, m[0] if m else None, is_causal, dropout_p,
                               dropout_key)
    args = (query, key, value) + ((attn_mask,) if attn_mask is not None else ())
    return dispatch(fn, args, {}, name="scaled_dot_product_attention")


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity wrapper."""
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal,
                                       training)
    return (out, None) if return_softmax else (out, None)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                        max_seqlen_k, scale=None, dropout=0.0, causal=False,
                        return_softmax=False, training=True, name=None):
    """Varlen flash attention: ragged batches packed as one sequence with cu_seqlens.

    Implemented by segment-masking the packed sequence (TPU-friendly static shapes;
    the reference calls FA2's varlen CUDA path)."""
    def fn(q, k, v, cq, ck):
        # q: [total_q, H, D]
        total_q = q.shape[0]
        total_k = k.shape[0]
        seg_q = jnp.searchsorted(cq, jnp.arange(total_q), side="right") - 1
        seg_k = jnp.searchsorted(ck, jnp.arange(total_k), side="right") - 1
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / (d ** 0.5)
        logits = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) * s
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cq, seg_q)
            pos_k = jnp.arange(total_k) - jnp.take(ck, seg_k)
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.where(mask[None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(mask[None], probs, 0.0)
        return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)
    out = dispatch(fn, (query, key, value, cu_seqlens_q, cu_seqlens_k), {},
                   name="flash_attn_unpadded")
    return out, None


def flashmask_attention(query, key, value, startend_row_indices=None, dropout=0.0,
                        causal=False, training=True, name=None):
    """Column-sparse masked attention (reference: flash_attention.py:1299).

    startend_row_indices: [B, KVH, S_k, {1,2,4}] — per-key-column row bounds that mask
    out rows of the attention matrix. We materialize the boolean mask from the bounds
    (jnp path); the Pallas kernel path can consume the same bounds blockwise.
    """
    def fn2(q, k, v, *ri):
        b, sq, h, d = q.shape
        sk = k.shape[1]
        qi = jnp.arange(sq)[:, None]   # [Sq,1]
        ki = jnp.arange(sk)[None, :]   # [1,Sk]
        base = (qi >= ki) if causal else jnp.ones((sq, sk), bool)
        allow = jnp.broadcast_to(base, (b, 1, sq, sk))
        if ri:
            r = ri[0].astype(jnp.int32)  # [B, KVH, Sk, n]
            n = r.shape[-1]
            kvh = r.shape[1]
            rT = jnp.swapaxes(r, 2, 3)  # [B, KVH, n, Sk]
            q_idx = qi[None, None]      # [1,1,Sq,1]
            if causal:
                if n == 1:  # LT start: mask rows >= start (except diagonal region)
                    start = rT[:, :, 0][:, :, None, :]  # [B,KVH,1,Sk]
                    m = q_idx < start
                else:       # n == 2: LT start/end band
                    start = rT[:, :, 0][:, :, None, :]
                    end = rT[:, :, 1][:, :, None, :]
                    m = (q_idx < start) | (q_idx >= end)
                allow = allow & m
            else:
                if n == 2:  # LT start + UT end
                    lts = rT[:, :, 0][:, :, None, :]
                    ute = rT[:, :, 1][:, :, None, :]
                    m = (q_idx < lts) & (q_idx >= ute)
                else:       # n == 4: LT start/end + UT start/end
                    lts = rT[:, :, 0][:, :, None, :]
                    lte = rT[:, :, 1][:, :, None, :]
                    uts = rT[:, :, 2][:, :, None, :]
                    ute = rT[:, :, 3][:, :, None, :]
                    m = ((q_idx < lts) | (q_idx >= lte)) & \
                        ((q_idx >= ute) | (q_idx < uts))
                allow = allow & m
            if kvh != h and kvh == 1:
                pass  # broadcast over heads
        scale = 1.0 / (d ** 0.5)
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        if kt.shape[1] != qt.shape[1]:
            rep = qt.shape[1] // kt.shape[1]
            kt = jnp.repeat(kt, rep, axis=1)
            vt = jnp.repeat(vt, rep, axis=1)
            if ri and allow.shape[1] not in (1, qt.shape[1]):
                allow = jnp.repeat(allow, rep, axis=1)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt).astype(jnp.float32) * scale
        logits = jnp.where(allow, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(jnp.isnan(probs), 0.0, probs)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), vt)
        return jnp.swapaxes(out, 1, 2)
    args = (query, key, value) + ((startend_row_indices,)
                                  if startend_row_indices is not None else ())
    return dispatch(fn2, args, {}, name="flashmask_attention")


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    raise NotImplementedError(
        "sparse_attention: use flashmask_attention or scaled_dot_product_attention")


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False, return_softmax=False,
                         fixed_seed_offset=None, rng_name="", training=True,
                         name=None):
    """Packed-QKV flash attention (reference: flash_attention.py
    flash_attn_qkvpacked): qkv [B, S, G + 2, Hk, D] — the first G slots along
    axis 2 are Q head-groups, the LAST two are K and V (the FA2 packing).
    Flattened q head j = g*Hk + h attends kv head j // G, which is exactly the
    repeat-broadcast rule in _sdpa_reference."""
    num_g = qkv.shape[2] - 2
    q = qkv[:, :, :-2]
    k = qkv[:, :, -2]
    v = qkv[:, :, -1]
    B, S = q.shape[0], q.shape[1]
    q = q.reshape([B, S, num_g * qkv.shape[3], qkv.shape[4]])
    return flash_attention(q, k, v, dropout, causal, return_softmax,
                           fixed_seed_offset, rng_name, training)


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                                max_seqlen_k, scale=None, dropout=0.0,
                                causal=False, return_softmax=False,
                                fixed_seed_offset=None, rng_name="",
                                varlen_padded=True, training=True, name=None):
    """Varlen packed-QKV flash attention (reference: flash_attention.py
    flash_attn_varlen_qkvpacked): qkv [total, G + 2, Hk, D] — Q groups first,
    K and V in the last two slots."""
    num_g = qkv.shape[1] - 2
    q = qkv[:, :-2].reshape([qkv.shape[0], num_g * qkv.shape[2], qkv.shape[3]])
    k = qkv[:, -2]
    v = qkv[:, -1]
    return flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                               max_seqlen_q, max_seqlen_k, scale, dropout,
                               causal, return_softmax, training)
