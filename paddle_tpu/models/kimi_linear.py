"""Kimi-Linear: a decoder that mixes Kimi Delta Attention (KDA: a gated
delta rule with a decay per key channel, a recurrent state a sequence)
with multi-head latent attention without positions (MLA: a compressed
key/value latent a token), over a dense SwiGLU in the first layer and
sparse experts with one shared expert in the rest.

Serving only: the layers run on the state kinds of
``models/cache_layout.py`` (a recurrent state and convolution tail a slot,
a paged latent pool) under :class:`paddle_tpu.inference.LLMEngine`; a
plain ``model(ids)`` builds those states for one call. Backward through
KDA and MLA is not written (ROADMAP Queue 2).

Layer equations (``benchmark/reference/kimi_linear_plain.py`` is their
plain float32 form, and ``benchmark/configs/kimi-linear-48b-a3b-ep4-d8
.json`` lists what ``config.json`` leaves to the family's public
implementation):

KDA, H heads of K = V: ``q~, k~, v~ = W x``; ``q, k, v = SiLU(conv4(.))``;
``q <- q/|q| K^-1/2``, ``k <- k/|k|``; ``g = -exp(A_log) softplus(W_fb
W_fa x + dt_bias)`` a channel, ``beta = sigmoid(W_b x)`` a head; the
recurrence of ``ops/kernels/kda.py``; ``y = W_o [RMSNorm_head(o) *
sigmoid(W_gb W_ga x)]``.

MLA: ``q = W_q x`` (H x (nope + pe)); ``[c; k_pe] = W_kva x``, ``c <-
RMSNorm(c)``; a token's cache entry is ``(c, k_pe)``; ``[k_nope_h; v_h] =
W_kvb,h c``; causal softmax of ``q_h . [k_nope_h; k_pe] / sqrt(nope +
pe)``; nothing is rotated. Served in the absorbed form: ``q_nope`` goes
through ``W_kvb``'s key half into the latent's width, the attention runs
against the latent pool with the latent itself as values
(``ops/kernels/latent_attention.py``), ``W_kvb``'s value half after.

Experts: ``ops/kernels/moe_dropless.py``; this model holds experts
``[expert_offset, expert_offset + num_experts)`` of
``num_experts_published`` and routes over all of them.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from ..nn import Layer, Linear, Embedding, RMSNorm, LayerList
from ..nn.initializer import Constant, Normal
from ..core.tensor import Tensor, dispatch
from ..ops.kernels import kda as _kda
from ..ops.kernels import latent_attention as _lat
from ..ops.kernels import moe_dropless as _moe
from . import cache_layout as CL

F32 = jnp.float32


@dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    #: MLA
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: KDA (``linear_attn_config``); layer ids count from 1
    #: the other layers are MLA
    kda_layers: tuple = ()
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    gate_low_rank: int = 128
    #: experts
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1024
    num_experts: int = 256               # held here
    num_experts_published: int = 256     # the router's width
    expert_offset: int = 0
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False

    def layer_kind(self, i):
        """"kda" or "mla" for layer ``i`` (from 0)."""
        return "kda" if (i + 1) in tuple(self.kda_layers) else "mla"


def _mm(x, w):
    """bf16 (or whatever the weights are) in, float32 accumulate, cast
    back: the MXU's native product."""
    return jnp.matmul(x, w, preferred_element_type=F32).astype(x.dtype)


def _mm32(x, w):
    return jnp.matmul(x, w, preferred_element_type=F32)


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(F32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _live_rows(q_lens, s):
    return jnp.arange(s, dtype=jnp.int32)[None, :] < \
        q_lens.astype(jnp.int32)[:, None]


class KimiDeltaAttention(Layer):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        h, H, K, r = c.hidden_size, c.linear_num_heads, c.linear_head_dim, \
            c.gate_low_rank
        self.H, self.K, self.eps = H, K, c.rms_norm_eps
        self.taps = c.short_conv_kernel_size
        lin = lambda i, o: Linear(i, o, bias_attr=False)  # noqa: E731
        self.q_proj, self.k_proj, self.v_proj = (lin(h, H * K)
                                                 for _ in range(3))
        for name in ("q_conv", "k_conv", "v_conv"):
            setattr(self, name, self.create_parameter(
                (self.taps, H * K), default_initializer=Normal(0.0, 0.02)))
        self.f_a_proj, self.f_b_proj = lin(h, r), lin(r, H * K)
        self.dt_bias = self.create_parameter(
            (H * K,), default_initializer=Constant(0.0))
        self.A_log = self.create_parameter(
            (H,), default_initializer=Constant(0.0))
        self.b_proj = lin(h, H)
        self.g_a_proj, self.g_b_proj = lin(h, r), lin(r, H * K)
        self.o_norm = RMSNorm(K, c.rms_norm_eps)
        self.o_proj = lin(H * K, h)

    def state_shapes(self, dtype):
        """What a slot holds: the float32 state of every head and the last
        ``taps - 1`` inputs of the three convolutions (q, k, v side by
        side)."""
        return {"S": ((self.H, self.K, self.K), np.float32),
                "conv": ((self.taps - 1, 3 * self.H * self.K), dtype)}

    def forward(self, x, cache):
        H, K, eps = self.H, self.K, self.eps
        rows = CL.packed(cache)

        def fn(x, S, tail, lens, q_lens, wq, wk, wv, cq, ck, cv, wfa, wfb,
               dtb, alog, wb, wga, wgb, on, wo):
            # every projection on x's own rows: [B, S] or, in a mixed
            # step, the packed [1, T]
            lead = x.shape[:2]
            qkv = jnp.concatenate([_mm(x, wq), _mm(x, wk), _mm(x, wv)], -1)
            g = -jnp.exp(alog.astype(F32))[:, None] * jax.nn.softplus(
                (_mm32(_mm(x, wfa), wfb) + dtb.astype(F32))
                .reshape(lead + (H, K)))
            beta = jax.nn.sigmoid(_mm32(x, wb))
            gate = jax.nn.sigmoid(_mm32(_mm(x, wga), wgb)) \
                .reshape(lead + (H, K))
            if rows is not None:
                # the per-slot view, around the convolution's tail and
                # the recurrence only
                qkv, g, beta = (rows.to_slots(a[0]) for a in (qkv, g, beta))
            b, s = qkv.shape[:2]
            fresh = (lens.astype(jnp.int32) == 0)
            S = jnp.where(fresh[:, None, None, None], 0.0, S)
            tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
            live = _live_rows(q_lens, s)
            y, tail = _kda.causal_conv(
                qkv, tail, jnp.concatenate([cq, ck, cv], -1), q_lens)
            y = jax.nn.silu(y).reshape(b, s, 3, H, K)
            q = _l2(y[:, :, 0]) * jnp.float32(K ** -0.5)
            k, v = _l2(y[:, :, 1]), y[:, :, 2]
            g = jnp.where(live[:, :, None, None], g, 0.0)
            beta = jnp.where(live[:, :, None], beta, 0.0)
            run = _kda.kda_recurrent if s == 1 else _kda.kda_chunk
            o, S = run(q, k, v, g, beta, S)
            if rows is not None:
                o = rows.from_slots(o)[None]
            o = (_rms(o, on, eps) * gate).astype(x.dtype)
            return _mm(o.reshape(lead + (H * K,)), wo), S, tail

        st = cache.state
        out, S, tail = dispatch(
            fn, (x, st["S"], st["conv"], cache.seq_lens, cache.q_lens,
                 self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                 self.q_conv, self.k_conv, self.v_conv,
                 self.f_a_proj.weight, self.f_b_proj.weight, self.dt_bias,
                 self.A_log, self.b_proj.weight, self.g_a_proj.weight,
                 self.g_b_proj.weight, self.o_norm.weight,
                 self.o_proj.weight), {}, name="kimi_kda")
        return out, CL.RecurrentCache({"S": S, "conv": tail}, cache.seq_lens,
                                      cache.q_lens, cache.row_budget, rows)


class KimiLatentAttention(Layer):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        h, H = c.hidden_size, c.num_attention_heads
        self.H, self.r = H, c.kv_lora_rank
        self.dn, self.dp, self.dv = c.qk_nope_head_dim, \
            c.qk_rope_head_dim, c.v_head_dim
        lin = lambda i, o: Linear(i, o, bias_attr=False)  # noqa: E731
        self.q_proj = lin(h, H * (self.dn + self.dp))
        self.kv_a_proj = lin(h, self.r + self.dp)
        self.kv_a_layernorm = RMSNorm(self.r, c.rms_norm_eps)
        self.kv_b_proj = lin(self.r, H * (self.dn + self.dv))
        self.o_proj = lin(H * self.dv, h)
        self.eps = c.rms_norm_eps

    @property
    def width(self):
        """Values a token costs in the pool: the latent and the shared
        position-free key part."""
        return self.r + self.dp

    def forward(self, x, cache):
        H, r, dn, dp, dv, eps = self.H, self.r, self.dn, self.dp, self.dv, \
            self.eps

        rows = CL.packed(cache)

        def fn(x, pool, tables, lens, q_lens, wq, wkva, nw, wkvb, wo):
            # projections on x's own rows ([B, S], or a mixed step's
            # packed [1, T]); the per-slot view around the pool only
            lead = x.shape[:2]
            q = _mm(x, wq).reshape(lead + (H, dn + dp))
            kv = _mm(x, wkva)
            entry = jnp.concatenate(
                [_rms(kv[..., :r], nw, eps).astype(x.dtype), kv[..., r:]], -1)
            wkvb = wkvb.reshape(r, H, dn + dv)
            # absorbed: q_nope through the key half into the latent's width
            q_abs = jnp.einsum("bshn,chn->bshc", q[..., :dn], wkvb[..., :dn],
                               preferred_element_type=F32)
            qc = (jnp.concatenate([q_abs, q[..., dn:].astype(F32)], -1) *
                  jnp.float32((dn + dp) ** -0.5)).astype(x.dtype)
            if rows is not None:
                entry, qc = rows.to_slots(entry[0]), rows.to_slots(qc[0])
            pool = _lat.latent_pool_write(pool, entry, tables, lens, q_lens)
            o = _lat.latent_attention_append(
                qc, pool, tables, lens, q_lens, r)
            if rows is not None:
                o = rows.from_slots(o)[None]
            o = jnp.einsum("bshc,chv->bshv", o, wkvb[..., dn:],
                           preferred_element_type=F32).astype(x.dtype)
            return _mm(o.reshape(lead + (H * dv,)), wo), pool

        out, pool = dispatch(
            fn, (x, cache.pool, cache.block_tables, cache.seq_lens,
                 cache.q_lens, self.q_proj.weight, self.kv_a_proj.weight,
                 self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                 self.o_proj.weight), {}, name="kimi_mla")
        return out, CL.LatentPagedCache(pool, cache.block_tables,
                                        cache.seq_lens, cache.q_lens,
                                        cache.row_budget, rows)


def _swiglu(x, wg, wu, wd):
    h = (jax.nn.silu(_mm32(x, wg)) * _mm32(x, wu)).astype(x.dtype)
    return _mm(h, wd)


class KimiMLP(Layer):
    def __init__(self, hidden, width):
        super().__init__()
        self.gate_proj = Linear(hidden, width, bias_attr=False)
        self.up_proj = Linear(hidden, width, bias_attr=False)
        self.down_proj = Linear(width, hidden, bias_attr=False)

    def forward(self, x, cache=None):
        return dispatch(_swiglu, (x, self.gate_proj.weight,
                                  self.up_proj.weight,
                                  self.down_proj.weight), {},
                        name="kimi_mlp")


class KimiExperts(Layer):
    """The held experts' weights, stacked: one leaf a projection."""

    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        e, h, f = c.num_experts, c.hidden_size, c.moe_intermediate_size
        init = Normal(0.0, 0.02)
        self.gate_proj = self.create_parameter((e, h, f),
                                               default_initializer=init)
        self.up_proj = self.create_parameter((e, h, f),
                                             default_initializer=init)
        self.down_proj = self.create_parameter((e, f, h),
                                               default_initializer=init)


class KimiRouter(Layer):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.weight = self.create_parameter(
            (c.hidden_size, c.num_experts_published),
            default_initializer=Normal(0.0, 0.02))
        self.e_score_correction_bias = self.create_parameter(
            (c.num_experts_published,), default_initializer=Constant(0.0))


class KimiSparseMoE(Layer):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.c = c
        self.gate = KimiRouter(c)
        self.experts = KimiExperts(c)
        self.shared_experts = KimiMLP(
            c.hidden_size, c.moe_intermediate_size * c.num_shared_experts)

    def forward(self, x, cache=None):
        c = self.c
        k = c.num_experts_per_token
        budget = getattr(cache, "row_budget", None)
        q_lens = getattr(cache, "q_lens", None)
        rmap = CL.packed(cache)

        def fn(x, q_lens, wr, bias, wg, wu, wd, sg, su, sd):
            b, s, h = x.shape
            n = b * s
            if rmap is not None:
                # a mixed step's packed rows: the first sum(q_lens) hold
                # a token
                live = rmap.live
            elif q_lens is None:
                live = jnp.ones((n,), bool)
            else:
                live = _live_rows(q_lens, s).reshape(n)
            xf = x.reshape(n, h)
            idx, w = _moe.route(xf, wr, bias, k, c.routed_scaling_factor,
                                c.moe_renormalize)
            rows = (budget or n) * min(k, c.num_experts)
            y, counts = _moe.held_expert_ffn(
                xf, idx, w, live, wg, wu, wd, c.expert_offset, rows)
            out = _swiglu(xf, sg, su, sd).astype(F32) + y
            return out.astype(x.dtype).reshape(b, s, h), counts

        sh = self.shared_experts
        out, counts = dispatch(
            fn, (x, q_lens, self.gate.weight,
                 self.gate.e_score_correction_bias, self.experts.gate_proj,
                 self.experts.up_proj, self.experts.down_proj,
                 sh.gate_proj.weight, sh.up_proj.weight,
                 sh.down_proj.weight), {}, name="kimi_moe")
        CL.count(counts._value if isinstance(counts, Tensor) else counts)
        return out


class KimiDecoderLayer(Layer):
    def __init__(self, c: KimiLinearConfig, layer_idx):
        super().__init__()
        self.kind = c.layer_kind(layer_idx)
        self.self_attn = KimiDeltaAttention(c) if self.kind == "kda" \
            else KimiLatentAttention(c)
        self.mlp = KimiMLP(c.hidden_size, c.intermediate_size) \
            if layer_idx < c.first_k_dense_replace else KimiSparseMoE(c)
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                c.rms_norm_eps)

    def forward(self, x, cache):
        a, new_cache = self.self_attn(self.input_layernorm(x), cache)
        x = x + a
        x = x + self.mlp(self.post_attention_layernorm(x), cache)
        return x, new_cache


class KimiLinearModel(Layer):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.config = c
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size)
        self.layers = LayerList([KimiDecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                position_offset=0):
        """``kv_caches``: one cache object a layer (``cache_layout``); the
        positions ride on them (``seq_lens``), so ``position_offset`` is
        not read: nothing in this model is rotated."""
        if kv_caches is None:
            raise ValueError(
                "KimiLinearModel runs on per-layer state: call the causal "
                "LM (it builds a one-call state) or pass kv_caches")
        x = self.embed_tokens(input_ids)
        new_caches = []
        for layer, cache in zip(self.layers, kv_caches):
            x, c = layer(x, cache)
            new_caches.append(c)
        return self.norm(x), new_caches


class KimiLinearForCausalLM(Layer):
    #: device-side counts of a step (``cache_layout.count``), booked into
    #: ``engine.stats`` under these names
    step_counter_names = _moe.COUNTERS

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.model = KimiLinearModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)

    @property
    def decoder(self):
        return self.model

    def cache_layout(self):
        """One state kind a layer, for the serving engine."""
        dt = self.model.embed_tokens.weight.dtype
        return [CL.Recurrent(layer.self_attn.state_shapes(np.dtype(dt)))
                if layer.kind == "kda"
                else CL.PagedLatent(layer.self_attn.width)
                for layer in self.model.layers]

    def _logits(self, hidden):
        return self.lm_head(hidden)

    def fresh_caches(self, batch, seq, block_size=64):
        """Per-layer state for ONE call over ``seq`` new positions from
        position 0 (the plain forward's; the engine builds its own)."""
        mb = -(-seq // block_size)
        tables = jnp.arange(batch * mb, dtype=jnp.int32).reshape(batch, mb)
        lens = jnp.zeros((batch,), jnp.int32)
        q_lens = jnp.full((batch,), seq, jnp.int32)
        dt = self.model.embed_tokens.weight.dtype
        zeros = lambda shape, dtype: jnp.zeros(shape, dtype)  # noqa: E731
        out = []
        for kind in self.cache_layout():
            a, b = kind.alloc(zeros, batch * mb, block_size, batch, dt)
            out.append(kind.cache(a, b, tables, lens, q_lens, None, None))
        return out

    def forward(self, input_ids, labels=None, attn_mask=None):
        if labels is not None:
            raise NotImplementedError(
                "training Kimi-Linear needs the backward of KDA and of "
                "latent attention, which are not written (ROADMAP Queue 2)")
        b, s = input_ids.shape[0], input_ids.shape[1]
        hidden, _ = self.model(input_ids,
                               kv_caches=self.fresh_caches(b, s))
        return self._logits(hidden)
