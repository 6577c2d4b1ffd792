"""paddle.incubate.nn.functional analog — fused NN ops.

Reference: python/paddle/incubate/nn/functional/ (fused_rms_norm.py,
fused_rotary_position_embedding.py, swiglu.py, fused_moe.py,
masked_multihead_attention.py, block_multihead_attention.py,
memory_efficient_attention.py — each a thin wrapper over a fused CUDA kernel).

TPU-native: these are jnp compositions XLA fuses into single kernels on TPU
(rms_norm/rope/swiglu are textbook elementwise-into-matmul fusions); the
attention variants route to the Pallas flash kernel where profitable. The
"fused_" names are kept for API parity.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ....core.tensor import Tensor, dispatch
from ....core import random as _random
from ....nn.functional.activation import swiglu  # noqa: F401  (parity re-export)
from ....nn.functional.attention import (
    scaled_dot_product_attention, flash_attn_unpadded,
)


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None, name=None):
    """RMSNorm with optional pre-norm bias/residual add (reference:
    incubate/nn/functional/fused_rms_norm.py). Returns (out, residual_out) when
    residual is given, else out. Stats in fp32."""
    def fn(xv, *rest):
        i = 0
        w = b = bi = res = None
        if norm_weight is not None:
            w = rest[i]; i += 1
        if norm_bias is not None:
            b = rest[i]; i += 1
        if bias is not None:
            bi = rest[i]; i += 1
        if residual is not None:
            res = rest[i]; i += 1
        if bi is not None:
            xv = xv + bi
        res_out = xv if res is None else xv + res
        x32 = res_out.astype(jnp.float32)
        axis = begin_norm_axis if begin_norm_axis >= 0 else x32.ndim + begin_norm_axis
        dims = tuple(range(axis, x32.ndim))
        var = jnp.mean(jnp.square(x32), axis=dims, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + epsilon)
        if w is not None:
            y = y * w.astype(jnp.float32)
        if b is not None:
            y = y + b.astype(jnp.float32)
        y = y.astype(res_out.dtype)
        return (y, res_out) if res is not None else y

    args = (x,) + tuple(a for a in (norm_weight, norm_bias, bias, residual)
                        if a is not None)
    return dispatch(fn, args, {}, name="fused_rms_norm")


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None, name=None):
    """LayerNorm with optional fused bias/residual add (reference:
    incubate/nn/functional/fused_layer_norm.py)."""
    def fn(xv, *rest):
        i = 0
        w = b = bi = res = None
        if norm_weight is not None:
            w = rest[i]; i += 1
        if norm_bias is not None:
            b = rest[i]; i += 1
        if bias is not None:
            bi = rest[i]; i += 1
        if residual is not None:
            res = rest[i]; i += 1
        if bi is not None:
            xv = xv + bi
        res_out = xv if res is None else xv + res
        x32 = res_out.astype(jnp.float32)
        axis = begin_norm_axis if begin_norm_axis >= 0 else x32.ndim + begin_norm_axis
        dims = tuple(range(axis, x32.ndim))
        mu = jnp.mean(x32, axis=dims, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=dims, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + epsilon)
        if w is not None:
            y = y * w.astype(jnp.float32)
        if b is not None:
            y = y + b.astype(jnp.float32)
        y = y.astype(res_out.dtype)
        return (y, res_out) if res is not None else y

    args = (x,) + tuple(a for a in (norm_weight, norm_bias, bias, residual)
                        if a is not None)
    return dispatch(fn, args, {}, name="fused_layer_norm")


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0,
                                    name=None):
    """RoPE on [B, S, H, D] tensors (reference:
    incubate/nn/functional/fused_rotary_position_embedding.py).

    sin/cos: [1, S, 1, D] (or [S, D]); computed from rotary_emb_base when absent.
    use_neox_rotary_style=True → rotate-half; False → rotate-every-two (GPT-J).
    """
    have_sincos = sin is not None and cos is not None

    def fn(qv, *rest):
        i = 0
        kv = vv = sn = cs = pid = None
        if k is not None:
            kv = rest[i]; i += 1
        if v is not None:
            vv = rest[i]; i += 1
        if have_sincos:
            sn = rest[i]; cs = rest[i + 1]; i += 2
        if position_ids is not None:
            pid = rest[i]; i += 1
        b, s, h, d = qv.shape
        if sn is None:
            inv = 1.0 / (rotary_emb_base
                         ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
            t = jnp.arange(s, dtype=jnp.float32)
            freqs = jnp.outer(t, inv)                       # [S, D/2]
            emb = (jnp.concatenate([freqs, freqs], -1) if use_neox_rotary_style
                   else jnp.repeat(freqs, 2, -1))
            sn, cs = jnp.sin(emb), jnp.cos(emb)             # [S, D]
        sn = sn.reshape(-1, d).astype(jnp.float32)
        cs = cs.reshape(-1, d).astype(jnp.float32)
        if pid is not None:
            sn = jnp.take(sn, pid, axis=0)                  # [B, S, D]
            cs = jnp.take(cs, pid, axis=0)
            sn = sn[:, :, None, :]
            cs = cs[:, :, None, :]
        else:
            sn = sn[None, :s, None, :]
            cs = cs[None, :s, None, :]

        def rot(x):
            x32 = x.astype(jnp.float32)
            if use_neox_rotary_style:
                half = d // 2
                x1, x2 = x32[..., :half], x32[..., half:]
                rotated = jnp.concatenate([-x2, x1], axis=-1)
            else:
                x1 = x32[..., 0::2]
                x2 = x32[..., 1::2]
                rotated = jnp.stack([-x2, x1], axis=-1).reshape(x32.shape)
            return (x32 * cs + rotated * sn).astype(x.dtype)

        outs = [rot(qv)]
        outs.append(rot(kv) if kv is not None else None)
        outs.append(rot(vv) if vv is not None else None)
        return tuple(outs)

    args = (q,) + tuple(a for a in (k, v) if a is not None)
    if have_sincos:
        args = args + (sin, cos)
    if position_ids is not None:
        args = args + (position_ids,)
    return dispatch(fn, args, {}, name="fused_rope")


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    """x @ W (+ b); reference incubate/nn/functional/fused_matmul_bias.py."""
    def fn(xv, wv, *bv):
        if transpose_weight:
            wv = wv.T
        y = jnp.matmul(xv, wv)
        return y + bv[0] if bv else y
    args = (x, weight) + ((bias,) if bias is not None else ())
    return dispatch(fn, args, {}, name="fused_linear")


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu", name=None):
    """GEMM + bias + activation epilogue (reference fused_gemm_epilogue op)."""
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "none": lambda a: a}[activation]

    def fn(xv, yv, bv):
        if trans_x:
            xv = xv.T
        if trans_y:
            yv = yv.T
        return act(jnp.matmul(xv, yv) + bv)
    return dispatch(fn, (x, y, bias), {}, name="fused_linear_activation")


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.0, ln_epsilon=1e-5,
                                           training=True, name=None):
    """(x + bias -> dropout) + residual -> LayerNorm (reference fused op)."""
    from ....core import random as _random
    key = _random.next_key() if (dropout_rate > 0.0 and training) else None

    def fn(xv, res, *rest):
        i = 0
        bv = sc = lb = None
        if bias is not None:
            bv = rest[i]; i += 1
        if ln_scale is not None:
            sc = rest[i]; i += 1
        if ln_bias is not None:
            lb = rest[i]; i += 1
        h = xv if bv is None else xv + bv
        if key is not None:
            keep = jax.random.bernoulli(key, 1.0 - dropout_rate, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout_rate), 0.0)
        h = h + res
        x32 = h.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + ln_epsilon)
        if sc is not None:
            y = y * sc.astype(jnp.float32)
        if lb is not None:
            y = y + lb.astype(jnp.float32)
        return y.astype(h.dtype)

    args = (x, residual) + tuple(a for a in (bias, ln_scale, ln_bias)
                                 if a is not None)
    return dispatch(fn, args, {}, name="fused_bias_dropout_residual_ln")


def memory_efficient_attention(query, key, value, attn_bias=None, p=0.0,
                               scale=None, training=True, name=None):
    """[B, S, H, D] attention with O(S) memory (reference:
    incubate/nn/functional/memory_efficient_attention.py → xformers kernel).
    On TPU this is the Pallas flash kernel via scaled_dot_product_attention."""
    return scaled_dot_product_attention(query, key, value, attn_mask=attn_bias,
                                        dropout_p=p, is_causal=False,
                                        training=training)


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0, name=None):
    """Varlen attention over [B, H, S, D] with per-batch valid lengths."""
    def fn(q, k, v, sl, kl, *m):
        b, h, s, d = q.shape
        sc = scale if scale is not None else 1.0 / math.sqrt(d)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sc
        q_idx = jnp.arange(s)
        k_idx = jnp.arange(k.shape[2])
        sl = sl.reshape(-1)
        kl = kl.reshape(-1)
        valid = (q_idx[None, :, None] < sl[:, None, None]) & \
                (k_idx[None, None, :] < kl[:, None, None])
        if causal:
            # bottom-right aligned (paddle semantics): query i of the sl valid
            # rows sits at global position offset+i among the kl valid keys,
            # where offset = pre_cache_length (explicit cache) or kl - sl
            off = (jnp.full_like(kl, pre_cache_length) if pre_cache_length > 0
                   else kl - sl)
            valid = valid & (q_idx[None, :, None] + off[:, None, None]
                             >= k_idx[None, None, :])
        logits = jnp.where(valid[:, None], logits, -jnp.inf)
        if m:
            logits = logits + m[0].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(jnp.isnan(probs), 0.0, probs)
        return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)
    args = (query, key, value, seq_lens, kv_seq_lens) + \
        ((mask,) if mask is not None else ())
    return dispatch(fn, args, {}, name="varlen_mem_efficient_attention")


def masked_multihead_attention(x, cache_kv, src_mask=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None, out_smooth=None,
                               seq_len=1, rotary_emb_dims=0,
                               use_neox_rotary_style=False, compute_dtype="default",
                               out_scale=-1, quant_round_type=1, quant_max_bound=0,
                               quant_min_bound=0, name=None):
    """Single-token decode attention with an in-place KV cache (reference:
    incubate/nn/functional/masked_multihead_attention.py).

    x: [B, 3*H*D] fused QKV for ONE step; cache_kv: [2, B, H, MaxLen, D];
    sequence_lengths: [B] current lengths (cache write position).
    Returns (out [B, H*D], updated cache_kv) — functional cache update,
    TPU-style, instead of the reference's in-place CUDA write.
    """
    def fn(xv, cache, *rest):
        i = 0
        mask = seqlen = None
        if src_mask is not None:
            mask = rest[i]; i += 1
        if sequence_lengths is not None:
            seqlen = rest[i]; i += 1
        two, b, h, max_len, d = cache.shape
        qkv = xv.reshape(b, 3, h, d)
        q, knew, vnew = qkv[:, 0], qkv[:, 1], qkv[:, 2]    # [B, H, D]
        pos = (seqlen if seqlen is not None
               else jnp.zeros((b,), jnp.int32))             # write index per batch
        onehot = jax.nn.one_hot(pos, max_len, dtype=cache.dtype)  # [B, L]
        kcache = cache[0] * (1 - onehot[:, None, :, None]) + \
            knew[:, :, None, :] * onehot[:, None, :, None]
        vcache = cache[1] * (1 - onehot[:, None, :, None]) + \
            vnew[:, :, None, :] * onehot[:, None, :, None]
        sc = 1.0 / math.sqrt(d)
        logits = jnp.einsum("bhd,bhld->bhl", q, kcache).astype(jnp.float32) * sc
        l_idx = jnp.arange(max_len)
        visible = l_idx[None, :] <= pos[:, None]            # [B, L]
        logits = jnp.where(visible[:, None, :], logits, -jnp.inf)
        if mask is not None:
            logits = logits + mask.reshape(b, 1, -1)[..., :max_len].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhl,bhld->bhd", probs.astype(vcache.dtype), vcache)
        return out.reshape(b, h * d), jnp.stack([kcache, vcache])

    args = (x, cache_kv) + tuple(a for a in (src_mask, sequence_lengths)
                                 if a is not None)
    return dispatch(fn, args, {}, name="masked_multihead_attention")


def fused_moe(x, gate_weight, ffn1_weight, ffn2_weight, ffn1_bias=None,
              ffn2_bias=None, quant_method="None", moe_topk=2, norm_topk_prob=True,
              name=None):
    """Dense-device MoE over stacked experts (reference:
    incubate/nn/functional/fused_moe.py). x: [B, S, D] or [T, D];
    ffn1_weight: [E, D, 2F] (swiglu packed) or [E, D, F]; ffn2: [E, F, D]."""
    from ....ops.kernels.moe import top_k_gating

    def fn(xv, gw, w1, w2, *rest):
        i = 0
        b1 = b2 = None
        if ffn1_bias is not None:
            b1 = rest[i]; i += 1
        if ffn2_bias is not None:
            b2 = rest[i]; i += 1
        shp = xv.shape
        xt = xv.reshape(-1, shp[-1])
        t = xt.shape[0]
        e = gw.shape[1]
        # the reference drops nothing (ragged dispatch); at static shapes an
        # ample 2x-expected capacity approximates that while keeping the
        # dispatch buffers O(topk*T*D) instead of O(E*T*D)
        capacity = min(t, 2 * moe_topk * t // e + 8)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            gw.astype(jnp.float32))
        disp, comb, _, _ = top_k_gating(logits, moe_topk, capacity,
                                        norm_topk=norm_topk_prob)
        dispatched = jnp.einsum("tec,td->ecd", disp.astype(xt.dtype), xt)
        h1 = jnp.einsum("ecd,edf->ecf", dispatched, w1)
        if b1 is not None:
            h1 = h1 + b1[:, None, :]
        f2 = w1.shape[-1]
        if w2.shape[1] * 2 == f2:  # packed swiglu [E, D, 2F]
            g, u = jnp.split(h1, 2, -1)
            h = jax.nn.silu(g) * u
        else:
            h = jax.nn.gelu(h1)
        y = jnp.einsum("ecf,efd->ecd", h, w2)
        if b2 is not None:
            y = y + b2[:, None, :]
        out = jnp.einsum("tec,ecd->td", comb.astype(y.dtype), y)
        return out.reshape(shp)

    args = (x, gate_weight, ffn1_weight, ffn2_weight) + tuple(
        a for a in (ffn1_bias, ffn2_bias) if a is not None)
    return dispatch(fn, args, {}, name="fused_moe")


def _dropout_val(v, rate, key, mode):
    """Shared dropout-on-values helper (None key = inference/no-op)."""
    if key is None or rate == 0.0:
        return v
    keep = jax.random.bernoulli(key, 1.0 - rate, v.shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, v / (1.0 - rate), 0.0)
    return jnp.where(keep, v, 0.0)


def _layer_norm_val(v, scale, bias, eps):
    """Shared LN-on-values helper; statistics accumulate in fp32 like the
    canonical nn.functional.layer_norm."""
    v32 = v.astype(jnp.float32)
    mu = jnp.mean(v32, -1, keepdims=True)
    var = jnp.var(v32, -1, keepdims=True)
    out = ((v32 - mu) / jnp.sqrt(var + eps)).astype(v.dtype)
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return out


def _add_attn_mask(logits, mask):
    """bool mask = keep-where-True; numeric mask = additive (same convention
    as nn/functional/attention.py)."""
    if mask.dtype == jnp.bool_:
        return jnp.where(mask, logits, jnp.float32(-1e30))
    return logits + mask.astype(jnp.float32)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """dropout(x) + y in one op (reference:
    incubate/nn/functional/fused_dropout_add.py); XLA fuses the mask multiply
    into the add."""
    key = _random.next_key() if training and p > 0.0 else None

    def fn(a, b):
        return _dropout_val(a, p, key, mode) + b

    return dispatch(fn, (x, y), {}, name="fused_dropout_add")


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None, smooth=None,
                   act_method="gelu", compute_dtype="default",
                   quant_scale=-1, quant_round_type=0, quant_max_bound=0,
                   quant_min_bound=0, name=None):
    """bias-add + activation epilogue (reference:
    incubate/nn/functional/fused_bias_act.py). The int8/fp8 quant epilogue
    parameters are not implemented — pass them and you get a loud error, not
    silently-unquantized output."""
    if any(p is not None for p in (dequant_scales, shift, smooth)) \
            or quant_scale != -1:
        raise NotImplementedError(
            "fused_bias_act quantization epilogue (dequant_scales/shift/"
            "smooth/quant_scale) is not implemented; use paddle_tpu.nn.quant "
            "for quantized linears")
    acts = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu,
            "swiglu": None, "geglu": None}
    if act_method not in acts:
        raise ValueError(f"unsupported act_method {act_method!r}")

    def fn(xv, bv):
        if bv is not None:
            xv = xv + bv
        if act_method in ("swiglu", "geglu"):
            a, b = jnp.split(xv, 2, axis=-1)
            gate = jax.nn.silu(a) if act_method == "swiglu" else jax.nn.gelu(a)
            return gate * b
        return acts[act_method](xv)

    return dispatch(fn, (x, bias), {}, name="fused_bias_act")


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu", ln1_epsilon=1e-5,
                      ln2_epsilon=1e-5, pre_layer_norm=False, training=True,
                      mode="upscale_in_train", name=None):
    """Transformer FFN block in one op (reference:
    incubate/nn/functional/fused_transformer.py fused_feedforward):
    residual + LN( x + dropout2( linear2( dropout1( act( linear1(x) ) ) ) ) ),
    with pre-LN variant."""
    act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}[activation]
    key1 = _random.next_key() if training and dropout1_rate > 0 else None
    key2 = _random.next_key() if training and dropout2_rate > 0 else None

    def fn(xv, w1, w2, b1, b2, s1, bb1, s2, bb2):
        residual = xv
        h = xv
        if pre_layer_norm:
            h = _layer_norm_val(h, s1, bb1, ln1_epsilon)
        h = jnp.matmul(h, w1)
        if b1 is not None:
            h = h + b1
        h = _dropout_val(act(h), dropout1_rate, key1, mode)
        h = jnp.matmul(h, w2)
        if b2 is not None:
            h = h + b2
        out = residual + _dropout_val(h, dropout2_rate, key2, mode)
        if not pre_layer_norm:
            out = _layer_norm_val(out, s2, bb2, ln2_epsilon)
        return out

    return dispatch(fn, (x, linear1_weight, linear2_weight, linear1_bias,
                         linear2_bias, ln1_scale, ln1_bias, ln2_scale,
                         ln2_bias), {}, name="fused_feedforward")


def fused_multi_head_attention(x, qkv_weight, linear_weight, pre_layer_norm=False,
                               pre_ln_scale=None, pre_ln_bias=None,
                               ln_scale=None, ln_bias=None, pre_ln_epsilon=1e-5,
                               qkv_bias=None, linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, name=None):
    """Full MHA block in one op (reference: fused_transformer.py
    fused_multi_head_attention): optional pre-LN, fused QKV GEMM, SDPA,
    out-proj, dropout, residual, post-LN.

    qkv_weight: [3, H, D, hidden]; linear_weight: [hidden, hidden]."""
    key_attn = _random.next_key() if training and attn_dropout_rate > 0 \
        else None
    key_out = _random.next_key() if training and dropout_rate > 0 else None

    def fn(xv, wqkv, wo, pls, plb, lns, lnb, bqkv, bo, mask, cache):
        residual = xv
        h = _layer_norm_val(xv, pls, plb, pre_ln_epsilon) \
            if pre_layer_norm else xv
        three, H, D, hidden = wqkv.shape
        # wqkv [3, H, D, hidden]: contract the hidden dim of the input
        qkv = jnp.einsum("bsx,thdx->tbshd", h, wqkv)
        if bqkv is not None:
            qkv = qkv + bqkv.reshape(3, 1, 1, H, D)
        q, k, v = qkv[0], qkv[1], qkv[2]              # [B, S, H, D]
        new_cache = None
        if cache is not None:
            # cache [2, B, H, T, D]: append this call's K/V (reference
            # returns cache_kv_out alongside out)
            k_hist = jnp.moveaxis(cache[0], 2, 1)     # [B, T, H, D]
            v_hist = jnp.moveaxis(cache[1], 2, 1)
            k = jnp.concatenate([k_hist, k], axis=1)
            v = jnp.concatenate([v_hist, v], axis=1)
            new_cache = jnp.stack([jnp.moveaxis(k, 1, 2),
                                   jnp.moveaxis(v, 1, 2)])
        sc = 1.0 / math.sqrt(D)
        logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * sc
        if mask is not None:
            logits = _add_attn_mask(logits, mask)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        probs = _dropout_val(probs, attn_dropout_rate, key_attn, mode)
        ctx = jnp.einsum("bhst,bthd->bshd", probs, v)
        ctx = ctx.reshape(ctx.shape[0], ctx.shape[1], H * D)
        out = jnp.matmul(ctx, wo)
        if bo is not None:
            out = out + bo
        out = _dropout_val(out, dropout_rate, key_out, mode)
        if add_residual:
            out = residual + out
        if not pre_layer_norm:
            out = _layer_norm_val(out, lns, lnb, ln_epsilon)
        if cache is not None:
            return out, new_cache
        return out

    return dispatch(fn, (x, qkv_weight, linear_weight, pre_ln_scale,
                         pre_ln_bias, ln_scale, ln_bias, qkv_bias, linear_bias,
                         attn_mask, cache_kv), {},
                    name="fused_multi_head_attention")


def _kv_quant_scatter(pool, scales, wblk, slot, rows, quant, D,
                      end_rows):
    """Merge new token rows into a QUANTIZED block pool — the dense
    fallback's write rule, shared by the decode and append forms: the
    affected blocks dequantize, take the new rows, ZERO their dead tail
    (rows at or past ``end_rows`` — stale content of a reused freed
    block; attention always masks those positions, but an unmasked
    absmax would let a dirty block's garbage inflate the scale and
    crush the live rows' resolution), recompute their per-(block, head)
    absmax scale, and re-quantize; every untouched block keeps its
    exact int payload and scale (no silent re-rounding of blocks
    nothing wrote). ``wblk``/``slot``/``rows``/``end_rows`` are flat
    write coordinates (block index ``pool.shape[0]`` = out-of-range
    drop, the decode form's -1-table contract; ``end_rows[i]`` = live
    row COUNT of block ``wblk[i]`` after this write). O(pool) compute —
    acceptable on the CPU/tier-1 path this fallback serves; the TPU
    path is the in-VMEM Pallas variant.

    Returns ``(pool, scales)`` updated."""
    from ....ops.kernels.paged_attention import (
        kv_block_scale, kv_quantize, kv_unpack)

    nb, _, bs, _ = pool.shape
    written = jnp.zeros((nb + 1,), bool).at[wblk].set(True)[:nb]
    live_end = jnp.full((nb + 1,), bs, jnp.int32) \
        .at[wblk].set(end_rows.astype(jnp.int32), mode="drop")[:nb]
    pf = kv_unpack(pool, quant, D) * scales[..., None, None]
    pf = pf.at[wblk, :, slot].set(rows.astype(jnp.float32), mode="drop")
    dead = jnp.arange(bs)[None, None, :] >= live_end[:, None, None]
    pf = jnp.where(dead[..., None], jnp.float32(0.0), pf)
    new_s = kv_block_scale(pf, quant, axes=(2, 3))        # [NB, Hkv]
    pq = kv_quantize(pf, new_s[..., None, None], quant)
    pool = jnp.where(written[:, None, None, None], pq, pool)
    scales = jnp.where(written[:, None], new_s, scales)
    return pool, scales


def _kv_quant_gather(pool, scales, safe_tables, quant, D):
    """Per-sequence logical KV off a QUANTIZED pool: gather the table's
    blocks, dequantize with their per-(block, head) scales -> f32
    [B, MB, Hkv, bs, D] for the dense attention math."""
    from ....ops.kernels.paged_attention import kv_unpack
    return kv_unpack(pool[safe_tables], quant, D) * \
        scales[safe_tables][..., None, None]


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens_encoder,
                              seq_lens_decoder, seq_lens_this_time,
                              padding_offsets=None, cum_offsets=None,
                              cu_seqlens_q=None, cu_seqlens_k=None,
                              block_tables=None, pre_key_cache=None,
                              pre_value_cache=None, cache_k_quant_scales=None,
                              cache_v_quant_scales=None, max_seq_len=None,
                              block_size=None, use_neox_style=False,
                              cache_quant_type=None, name=None):
    """Paged-KV-cache decode attention (reference:
    incubate/nn/functional/block_multihead_attention.py, phi
    block_multi_head_attention_kernel.cu — the vLLM-style paged attention).

    Decode-step form: qkv [B, (Hq + 2*Hkv)*D] (one new token per sequence;
    Hq == Hkv is the MHA special case, Hq a multiple of Hkv is GQA);
    key_cache/value_cache [num_blocks, Hkv, block_size, D]; block_tables
    [B, max_blocks_per_seq] maps logical KV block i of each sequence to a
    physical cache block (-1 = unused); seq_lens_decoder [B] = tokens already
    cached. Returns (out [B, Hq*D], key_cache, value_cache) with the new
    token written into its block — functional cache update, TPU-style.

    On TPU (and unless FLAGS_use_paged_attention=0) this routes through the
    Pallas paged-attention decode kernel
    (:func:`paddle_tpu.ops.kernels.paged_attention.paged_attention_decode`):
    block-sparse reads straight off the physical pools via scalar-prefetched
    block tables, with the new-token write fused in-kernel. The dense path
    below (scatter + gather the whole padded horizon + einsum) is the
    reference semantics and the CPU/tier-1 fallback.

    Append-step form (the fused prefill+decode scheduler's mixed step):
    qkv [B, S, (Hq + 2*Hkv)*D] with ``seq_lens_this_time`` [B] = how many
    of the S rows are real for each sequence (0 = inactive slot). Sequence
    b's rows occupy positions [seq_lens_decoder[b], seq_lens_decoder[b] +
    seq_lens_this_time[b]); each row attends causally to the pooled
    history plus its own chunk prefix. Rows past seq_lens_this_time are
    padding: nothing is written for them and their outputs are garbage
    the caller ignores. Routes through
    :func:`~paddle_tpu.ops.kernels.paged_attention.paged_attention_append`
    on TPU; the dense scatter+gather+einsum below is the CPU fallback.

    Packed append form (the reference's own layout, ``qkv [token_num,
    ...]`` with ``cu_seqlens_q``; what the fused scheduler's mixed step
    hands a layer since it packs its granted rows on one axis,
    ``models/cache_layout.py``'s ``RowMap``): qkv ``[T, (Hq + 2*Hkv)*D]``
    with ``seq_lens_this_time`` [B] AND ``cu_seqlens_q`` [B], a sequence's
    first row (the exclusive running sum of ``seq_lens_this_time``,
    ``RowMap.start``): sequence b's rows are the ``seq_lens_this_time[b]``
    from ``cu_seqlens_q[b]`` on, in position order, sequences ascending
    and no two overlapping. ``max_seq_len`` (static, required in this
    form) is the most rows one sequence holds in a step (the chunk).
    Same semantics a row as the ``[B, S, ...]`` form, and the same
    returns with ``out [T, Hq*D]``: a row that holds no token comes back
    zero. On TPU the rows go to the kernel as they lie (its packed
    entry: no per-sequence view is built); the CPU fallback slices the
    ``[B, max_seq_len, ...]`` view out at ``cu_seqlens_q``, runs the
    dense form and takes the rows back.

    Quantized pools (``cache_quant_type="int8"|"int4"`` — the serving
    engine's ``kv_cache_dtype``; the reference signature's
    ``cache_k_quant_scales``/``cache_v_quant_scales`` carry the
    per-(physical block, kv head) fp32 scale arrays [num_blocks, Hkv]):
    both forms dequantize blocks on read and re-quantize every written
    block with a fresh absmax scale, returning the updated scale arrays
    after the pools — ``(out, key_cache, value_cache, k_scales,
    v_scales)``. On TPU the dequant/requant happens in VMEM inside the
    Pallas kernels; the dense fallback below does the same math at the
    XLA level (host-runnable, the tier-1 path). int4 packs two nibbles
    per pool byte along D (split-half layout, even head_dim here — the
    kernel itself also supports odd D with nibble padding).
    """
    if block_tables is None:
        raise ValueError("block_mha requires block_tables")
    quant = cache_quant_type
    if quant and (cache_k_quant_scales is None
                  or cache_v_quant_scales is None):
        raise ValueError("cache_quant_type needs cache_k_quant_scales and "
                         "cache_v_quant_scales ([num_blocks, Hkv] fp32)")
    if len(qkv.shape) == 3 or cu_seqlens_q is not None:
        if seq_lens_this_time is None:
            raise ValueError("append-step block_mha (3-D qkv, or packed "
                             "rows with cu_seqlens_q) requires "
                             "seq_lens_this_time (per-sequence q_lens)")
        return _block_mha_append(qkv, key_cache, value_cache,
                                 seq_lens_decoder, seq_lens_this_time,
                                 block_tables, cache_k_quant_scales,
                                 cache_v_quant_scales, quant,
                                 cu_seqlens_q, max_seq_len)
    def fn(qkv_v, kc, vc, lens, tables, *qargs):
        from ....ops.kernels.paged_attention import (
            current_paged_tp, paged_attention_decode,
            paged_attention_decode_tp, paged_attention_enabled)

        nb, Hkv, bs, Dp = kc.shape
        b = qkv_v.shape[0]
        max_blocks = tables.shape[1]
        if quant:
            ks, vs = (a.astype(jnp.float32) for a in qargs)
            D = _quant_head_dim(qkv_v.shape[1], Hkv, Dp, quant)
        else:
            ks = vs = None
            D = Dp
        Hq = qkv_v.shape[1] // D - 2 * Hkv
        q = qkv_v[:, :Hq * D].reshape(b, Hq, D)
        knew = qkv_v[:, Hq * D:(Hq + Hkv) * D].reshape(b, Hkv, D)
        vnew = qkv_v[:, (Hq + Hkv) * D:].reshape(b, Hkv, D)
        lens = lens.astype(jnp.int32)
        tables = tables.astype(jnp.int32)

        if paged_attention_enabled():
            tp = current_paged_tp()
            if tp is not None:
                # TP serving engine: a pallas_call cannot be GSPMD-
                # partitioned, so the kernel shard_maps over the tp axis
                # (kv-head shards; tables/lens/scales replicated along
                # their non-head dims)
                outs = paged_attention_decode_tp(
                    q, kc, vc, tables, lens, mesh=tp[0], axis=tp[1],
                    new_k=knew, new_v=vnew, k_scale=ks, v_scale=vs,
                    quant=quant)
            else:
                outs = paged_attention_decode(
                    q, kc, vc, tables, lens, new_k=knew, new_v=vnew,
                    k_scale=ks, v_scale=vs, quant=quant)
            if quant:
                out, kc, vc, ks, vs = outs
                return out.reshape(b, Hq * D), kc, vc, ks, vs
            out, kc, vc = outs
            return out.reshape(b, Hq * D), kc, vc

        # write the new token at position lens[i] of sequence i. A -1 table
        # entry (no block allocated) must not write AT ALL: clamping it to
        # block 0 and re-writing the old value is NOT a no-op when another
        # sequence genuinely writes block 0 in the same scatter — duplicate
        # indices make the last write win, clobbering the real token with
        # the stale value. Route invalid rows OUT OF BOUNDS and drop them.
        blk_idx = tables[jnp.arange(b), lens // bs]       # [B] physical block
        slot = lens % bs                                  # [B]
        wblk = jnp.where(blk_idx >= 0, blk_idx, nb)       # nb = out of range
        if quant:
            # quantized merge: dead tail past the new token zeroed,
            # fresh absmax scale per written block
            kc, ks = _kv_quant_scatter(kc, ks, wblk, slot, knew, quant,
                                       D, slot + 1)
            vc, vs = _kv_quant_scatter(vc, vs, wblk, slot, vnew, quant,
                                       D, slot + 1)
        else:
            kc = kc.at[wblk, :, slot].set(knew, mode="drop")
            vc = vc.at[wblk, :, slot].set(vnew, mode="drop")

        # gather each sequence's logical KV [B, max_blocks*bs, Hkv, D]
        safe_tables = jnp.maximum(tables, 0)
        if quant:
            kseq = _kv_quant_gather(kc, ks, safe_tables, quant, D)
            vseq = _kv_quant_gather(vc, vs, safe_tables, quant, D)
        else:
            kseq = kc[safe_tables]                        # [B, MB, Hkv, bs, D]
            vseq = vc[safe_tables]
        kseq = jnp.moveaxis(kseq, 3, 2).reshape(b, max_blocks * bs, Hkv, D)
        vseq = jnp.moveaxis(vseq, 3, 2).reshape(b, max_blocks * bs, Hkv, D)

        sc = 1.0 / math.sqrt(D)
        qg = q.reshape(b, Hkv, Hq // Hkv, D)              # GQA head groups
        logits = jnp.einsum("bhgd,bthd->bhgt", qg,
                            kseq).astype(jnp.float32) * sc
        t_idx = jnp.arange(max_blocks * bs)
        visible = t_idx[None, :] <= lens[:, None]         # include new token
        logits = jnp.where(visible[:, None, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(vseq.dtype)
        out = jnp.einsum("bhgt,bthd->bhgd", probs, vseq)
        if quant:
            return (out.astype(qkv_v.dtype).reshape(b, Hq * D),
                    kc, vc, ks, vs)
        return out.reshape(b, Hq * D), kc, vc

    args = (qkv, key_cache, value_cache, seq_lens_decoder, block_tables)
    if quant:
        args += (cache_k_quant_scales, cache_v_quant_scales)
        return dispatch(fn, args, {}, name="block_mha_decode_quant")
    return dispatch(fn, args, {}, name="block_multihead_attention")


def _quant_head_dim(qkv_width, Hkv, Dp, quant):
    """Head dim D of a quantized-pool call, from the qkv row width and
    the PACKED pool head dim Dp. int8 stores D bytes (D == Dp); int4
    packs two per byte, so D is 2*Dp — or 2*Dp - 1 for an odd head dim,
    disambiguated by which one divides the qkv width into a whole
    (GQA-consistent) head count. Odd-D models this can't disambiguate
    should call the Pallas kernel directly (serving models have even
    head dims)."""
    if quant == "int8":
        return Dp
    D = 2 * Dp
    if qkv_width % D == 0 and (qkv_width // D - 2 * Hkv) > 0 \
            and (qkv_width // D - 2 * Hkv) % Hkv == 0:
        return D
    return D - 1


def _block_mha_append(qkv, key_cache, value_cache, seq_lens, q_lens,
                      block_tables, k_scales=None, v_scales=None,
                      quant=None, start=None, width=None):
    """Append-step paged attention (see block_multihead_attention): S new
    positions per sequence against the block pools, causal within the
    chunk. Dense fallback = scatter the valid rows into their blocks
    (invalid rows route out of range and drop), gather each sequence's
    padded horizon, einsum with the per-row causal mask — the same
    reference semantics the decode form uses, extended along S.
    ``quant`` + scale arrays: quantized pools (dequant-on-read, window
    blocks re-quantized under fresh absmax scales; return grows the
    updated scale arrays). ``start`` [B]: the packed form, qkv ``[T, W]``
    with sequence b's rows from ``start[b]`` on and at most ``width`` of
    them (static)."""
    packed = start is not None
    if packed:
        if width is None or tuple(start.shape) != tuple(q_lens.shape):
            raise ValueError(
                "packed append-step block_mha needs max_seq_len (the most "
                "rows of a sequence) and cu_seqlens_q shaped as "
                f"seq_lens_this_time, got {width} and {tuple(start.shape)}")
        width = int(width)

    def fn(qkv_v, kc, vc, lens, qlens, tables, *qargs):
        from ....ops.kernels.paged_attention import (
            current_paged_tp, paged_attention_append,
            paged_attention_append_tp, paged_attention_enabled)

        T = qkv_v.shape[0]
        view = packed and not paged_attention_enabled()
        if packed:
            first, qargs = qargs[0].astype(jnp.int32), qargs[1:]
        if view:
            # the dense form below on the per-sequence view: ``width``
            # rows from each sequence's first (zeros past the axis' end)
            padded = jnp.concatenate(
                [qkv_v, jnp.zeros((width,) + qkv_v.shape[1:], qkv_v.dtype)])
            qkv_v = jax.vmap(lambda at: jax.lax.dynamic_slice_in_dim(
                padded, at, width))(first)
        nb, Hkv, bs, Dp = kc.shape
        max_blocks = tables.shape[1]
        if quant:
            ks, vs = (a.astype(jnp.float32) for a in qargs)
            D = _quant_head_dim(qkv_v.shape[-1], Hkv, Dp, quant)
        else:
            ks = vs = None
            D = Dp
        Hq = qkv_v.shape[-1] // D - 2 * Hkv
        # [B, S] rows, or the packed [T]
        lead = qkv_v.shape[:-1]
        q = qkv_v[..., :Hq * D].reshape(lead + (Hq, D))
        knew = qkv_v[..., Hq * D:(Hq + Hkv) * D].reshape(lead + (Hkv, D))
        vnew = qkv_v[..., (Hq + Hkv) * D:].reshape(lead + (Hkv, D))
        lens = lens.astype(jnp.int32)
        qlens = qlens.astype(jnp.int32)
        tables = tables.astype(jnp.int32)

        if paged_attention_enabled():
            tp = current_paged_tp()
            kw = dict(k_scale=ks, v_scale=vs, quant=quant)
            if packed:
                kw.update(start=first, width=width)
            if tp is not None:
                outs = paged_attention_append_tp(
                    q, kc, vc, tables, lens, qlens, knew, vnew,
                    mesh=tp[0], axis=tp[1], **kw)
            else:
                outs = paged_attention_append(
                    q, kc, vc, tables, lens, qlens, knew, vnew, **kw)
            return (outs[0].reshape(lead + (Hq * D,)),) + tuple(outs[1:])
        b, S = lead

        # scatter valid rows: row i of sequence b lands at absolute
        # position lens[b]+i when i < qlens[b]; padding / unallocated /
        # out-of-table rows route out of range and DROP (same contract as
        # the decode form — a clamped write could clobber a real block)
        i_idx = jnp.arange(S, dtype=jnp.int32)
        pos = lens[:, None] + i_idx[None, :]                  # [B, S]
        valid = i_idx[None, :] < qlens[:, None]
        blk_log = pos // bs
        phys = jnp.take_along_axis(
            tables, jnp.clip(blk_log, 0, max_blocks - 1), axis=1)
        wblk = jnp.where(valid & (phys >= 0) & (blk_log < max_blocks),
                         phys, nb)                            # nb = OOB
        slot = pos % bs
        wf, sf = wblk.reshape(-1), slot.reshape(-1)
        if quant:
            # live row count of each written block: the window's new end
            # (lens + q_lens) relative to the block start, clipped
            ends = jnp.clip((lens + qlens)[:, None] - blk_log * bs, 0, bs)
            ef = ends.reshape(-1)
            kc, ks = _kv_quant_scatter(kc, ks, wf, sf,
                                       knew.reshape(-1, Hkv, D), quant, D,
                                       ef)
            vc, vs = _kv_quant_scatter(vc, vs, wf, sf,
                                       vnew.reshape(-1, Hkv, D), quant, D,
                                       ef)
        else:
            kc = kc.at[wf, :, sf].set(knew.reshape(-1, Hkv, D),
                                      mode="drop")
            vc = vc.at[wf, :, sf].set(vnew.reshape(-1, Hkv, D),
                                      mode="drop")

        # gather each sequence's logical KV and attend with the per-row
        # causal mask: kv position t visible to chunk row i iff
        # t <= lens + i
        safe_tables = jnp.maximum(tables, 0)
        if quant:
            kseq = _kv_quant_gather(kc, ks, safe_tables, quant, D)
            vseq = _kv_quant_gather(vc, vs, safe_tables, quant, D)
        else:
            kseq = kc[safe_tables]                   # [B, MB, Hkv, bs, D]
            vseq = vc[safe_tables]
        kseq = jnp.moveaxis(kseq, 3, 2).reshape(b, max_blocks * bs, Hkv, D)
        vseq = jnp.moveaxis(vseq, 3, 2).reshape(b, max_blocks * bs, Hkv, D)
        sc = 1.0 / math.sqrt(D)
        qg = q.reshape(b, S, Hkv, Hq // Hkv, D)      # GQA head groups
        logits = jnp.einsum("bshgd,bthd->bhsgt", qg,
                            kseq).astype(jnp.float32) * sc
        t_idx = jnp.arange(max_blocks * bs)
        visible = t_idx[None, None, :] <= (lens[:, None]
                                           + i_idx[None, :])[:, :, None]
        logits = jnp.where(visible[:, None, :, None, :], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(vseq.dtype)
        out = jnp.einsum("bhsgt,bthd->bshgd", probs, vseq)
        if quant:
            out = out.astype(qkv_v.dtype)
        out = out.reshape(b, S, Hq * D)
        if view:
            # back to the packed rows: row t is sequence b's iff it lies in
            # [first[b], first[b] + qlens[b]); a row of nobody's is zero
            t = jnp.arange(T, dtype=jnp.int32)[:, None]
            held = (t >= first[None]) & (
                t < (first + jnp.minimum(qlens, S))[None])
            seq = jnp.argmax(held, axis=1).astype(jnp.int32)
            at = seq * S + (t[:, 0] - first[seq])
            out = jnp.where(jnp.any(held, axis=1)[:, None],
                            jnp.take(out.reshape(b * S, Hq * D), at, axis=0,
                                     mode="clip"), 0.0)
        return (out, kc, vc, ks, vs) if quant else (out, kc, vc)

    args = (qkv, key_cache, value_cache, seq_lens, q_lens, block_tables)
    if packed:
        args += (start,)
    if quant:
        args += (k_scales, v_scales)
        return dispatch(fn, args, {}, name="block_mha_append_quant")
    return dispatch(fn, args, {}, name="block_mha_append")


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """reference: incubate/nn/functional/fused_matmul_bias.py — one
    GEMM+bias-epilogue (XLA fuses the add into the dot)."""
    def fn(a, b, *bi):
        aa = jnp.swapaxes(a, -2, -1) if transpose_x else a
        bb = jnp.swapaxes(b, -2, -1) if transpose_y else b
        out = aa @ bb
        if bi:
            out = out + bi[0]
        return out
    args = (x, y) + ((bias,) if bias is not None else ())
    return dispatch(fn, args, {}, name="fused_matmul_bias")


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size,
                     name=None):
    """reference: incubate/nn/functional/blha_get_max_len.py — max
    encoder/decoder sequence lengths for block_multihead_attention setup."""
    def fn(enc, dec):
        return jnp.max(enc).reshape([1]), jnp.max(dec).reshape([1])
    return dispatch(fn, (seq_lens_encoder, seq_lens_decoder), {},
                    name="blha_get_max_len")


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                            linear_weights, linear_biases, ffn_ln_scales,
                            ffn_ln_biases, ffn1_weights, ffn1_biases,
                            ffn2_weights, ffn2_biases, pre_layer_norm=True,
                            epsilon=1e-5, cache_kvs=None, pre_caches=None,
                            rotary_embs=None, rotary_emb_dims=0, beam_offset=None,
                            seq_lens=None, time_step=None, attn_mask=None,
                            dropout_rate=0.0, activation="gelu", training=False,
                            mode="upscale_in_train", trans_qkvw=True,
                            ring_id=-1, name=None):
    """Whole-stack fused transformer (reference:
    incubate/nn/functional/fused_multi_transformer.py — the generation-path
    mega-op). Loops the per-layer fused blocks; each block is one XLA fusion
    region; KV caches append along seq when cache_kvs is given (decode step).

    Returns output, or (output, cache_kvs) when cache_kvs is not None."""
    from ....nn import functional as NF
    from ....nn.functional.attention import scaled_dot_product_attention

    num_layers = len(qkv_weights)
    out = x
    new_caches = []
    for i in range(num_layers):
        residual = out
        h = out
        if pre_layer_norm:
            h = NF.layer_norm(h, (h.shape[-1],), ln_scales[i], ln_biases[i],
                              epsilon)
        b, s, d = h.shape
        qkv_w = qkv_weights[i]
        if trans_qkvw:
            # (3, H, Dh, D) -> project: x @ W^T per slot
            def qkv_fn(hv, wv, bv):
                out3 = jnp.einsum("bsd,thkd->bsthk", hv, wv)
                return out3 + bv[None, None]
            qkv = dispatch(qkv_fn, (h, qkv_w, qkv_biases[i]), {},
                           name="fmt_qkv")
        else:
            def qkv_fn(hv, wv, bv):
                out3 = jnp.einsum("bsd,dthk->bsthk", hv, wv)
                return out3 + bv[None, None]
            qkv = dispatch(qkv_fn, (h, qkv_w, qkv_biases[i]), {},
                           name="fmt_qkv")
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        if rotary_embs is not None and rotary_emb_dims > 0:
            q, k, _ = fused_rotary_position_embedding(
                q, k, sin=rotary_embs[0], cos=rotary_embs[1])
        if cache_kvs is not None and cache_kvs[i] is not None:
            cache = cache_kvs[i]  # (2, B, H, S_cache, Dh) paddle layout
            def append_fn(cv, kv, vv):
                kq = jnp.swapaxes(kv, 1, 2)  # B,H,S,Dh
                vq = jnp.swapaxes(vv, 1, 2)
                nk = jnp.concatenate([cv[0], kq], axis=2)
                nv = jnp.concatenate([cv[1], vq], axis=2)
                return jnp.stack([nk, nv])
            new_cache = dispatch(append_fn, (cache, k, v), {},
                                 name="fmt_cache_append")
            new_caches.append(new_cache)
            def split_fn(cv):
                return (jnp.swapaxes(cv[0], 1, 2), jnp.swapaxes(cv[1], 1, 2))
            k, v = dispatch(split_fn, (new_cache,), {}, name="fmt_cache_read")
        attn = scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask,
            is_causal=(attn_mask is None and cache_kvs is None),
            dropout_p=0.0, training=training)
        attn = attn.reshape([b, s, d])
        attn = NF.linear(attn, linear_weights[i], linear_biases[i])
        if dropout_rate and training:
            attn = NF.dropout(attn, dropout_rate, training=training)
        out = residual + attn
        if not pre_layer_norm:
            out = NF.layer_norm(out, (d,), ln_scales[i], ln_biases[i], epsilon)

        residual = out
        h = out
        if pre_layer_norm:
            h = NF.layer_norm(h, (d,), ffn_ln_scales[i], ffn_ln_biases[i],
                              epsilon)
        h = NF.linear(h, ffn1_weights[i], ffn1_biases[i])
        h = getattr(NF, activation)(h)
        if dropout_rate and training:
            h = NF.dropout(h, dropout_rate, training=training)
        h = NF.linear(h, ffn2_weights[i], ffn2_biases[i])
        out = residual + h
        if not pre_layer_norm:
            out = NF.layer_norm(out, (d,), ffn_ln_scales[i], ffn_ln_biases[i],
                                epsilon)
    if cache_kvs is not None:
        return out, new_caches
    return out
