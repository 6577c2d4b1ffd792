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
recurrence of ``ops/kernels/kda.py`` (a step of several rows a slot: the
Pallas kernel ``ops/kernels/kda_chunk_walk.py`` over the live chunks);
``y = W_o [RMSNorm_head(o) * sigmoid(W_gb W_ga x)]``.

MLA (``models/latent_moe.py``'s layer, which the DeepSeek-V2 family
shares, with one query projection, no rotation and the plain scale): ``q =
W_q x`` (H x (nope + pe)); ``[c; k_pe] = W_kva x``, ``c <- RMSNorm(c)``; a
token's cache entry is ``(c, k_pe)``; ``[k_nope_h; v_h] = W_kvb,h c``;
causal softmax of ``q_h . [k_nope_h; k_pe] / sqrt(nope + pe)``; nothing is
rotated. Served in the absorbed form over the latent pool.

Experts (``latent_moe.SparseMoE`` over ``ops/kernels/moe_dropless.py``:
sigmoid scores, a selection bias, one routing group, renormalised
weights): this model holds experts ``[expert_offset, expert_offset +
num_experts)`` of ``num_experts_published`` and routes over all of them.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from ..nn import Layer, Linear, RMSNorm
from ..nn.initializer import Constant, Normal
from ..core.tensor import dispatch
from ..ops.kernels import kda as _kda
from ..ops.kernels import kda_chunk_walk as _walk
from ..profiler import scope
from . import cache_layout as CL
from .latent_moe import (F32, DecoderBlock, LatentAttention, SparseMoE,
                         StateCausalLM, StateDecoder, SwiGLU, live_rows, mm,
                         mm32, rms)


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


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


class KimiDeltaAttention(Layer):
    """``c``: any config with ``hidden_size``, ``linear_num_heads``,
    ``linear_head_dim``, ``short_conv_kernel_size``, ``gate_low_rank`` and
    ``rms_norm_eps``. ``beta_scale``: the write strength is ``beta_scale *
    sigmoid(W_b x)``; 2 lets ``I - beta k k^T`` take an eigenvalue in (-1,
    1) along ``k`` (a family that allows negative eigenvalues), 1 is this
    family's and traces nothing more."""

    def __init__(self, c: KimiLinearConfig, beta_scale=1.0):
        super().__init__()
        self.beta_scale = float(beta_scale)
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
        beta_scale = self.beta_scale
        rows = CL.packed(cache)

        def fn(x, S, tail, lens, q_lens, wq, wk, wv, cq, ck, cv, wfa, wfb,
               dtb, alog, wb, wga, wgb, on, wo):
            # every projection on x's own rows: [B, S] or, in a mixed
            # step, the packed [1, T]
            lead = x.shape[:2]
            with scope("qkv_proj"):
                qkv = jnp.concatenate([mm(x, wq), mm(x, wk), mm(x, wv)], -1)
            with scope("pt.gate"):
                g = -jnp.exp(alog.astype(F32))[:, None] * jax.nn.softplus(
                    (mm32(mm(x, wfa), wfb) + dtb.astype(F32))
                    .reshape(lead + (H, K)))
                beta = jax.nn.sigmoid(mm32(x, wb))
                if beta_scale != 1.0:
                    beta = beta * jnp.float32(beta_scale)
                gate = jax.nn.sigmoid(mm32(mm(x, wga), wgb)) \
                    .reshape(lead + (H, K))
            with scope("pt.view"):
                b, s = (S.shape[0], rows.width) if rows is not None \
                    else qkv.shape[:2]
                # the kernel walks a step's live chunks on the rows as
                # they lie, packed or per slot, and resets a fresh slot
                # and masks the dead rows itself
                walk = s > 1 and _walk.serves(K, K)
                fresh = (lens.astype(jnp.int32) == 0)
                if not walk:
                    S = jnp.where(fresh[:, None, None, None], 0.0, S)
                tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail),
                                 tail)
                live = None if walk else live_rows(q_lens, s)
            with scope("pt.conv"):
                cw = jnp.concatenate([cq, ck, cv], -1)
                if rows is not None:
                    y, tail = _kda.causal_conv_packed(qkv[0], tail, cw,
                                                      rows)
                    y = jax.nn.silu(y).reshape(-1, 3, H, K)
                    q = _l2(y[:, 0]) * jnp.float32(K ** -0.5)
                    k, v = _l2(y[:, 1]), y[:, 2]
                else:
                    y, tail = _kda.causal_conv(qkv, tail, cw, q_lens)
                    y = jax.nn.silu(y).reshape(b, s, 3, H, K)
                    q = _l2(y[:, :, 0]) * jnp.float32(K ** -0.5)
                    k, v = _l2(y[:, :, 1]), y[:, :, 2]
            if rows is not None:
                g, beta = g[0], beta[0]
            if walk:
                # a mixed step's packed rows go to the kernel as they are
                # and come back packed: no per-slot view of anything
                with scope("pt.core"):
                    o, S = _walk.kda_chunk_walk(q, k, v, g, beta, S, q_lens,
                                                lens, rows)
                    counts = _walk.grid_counts(
                        q_lens, q.shape[0] if rows is not None else b * s, s)
            else:
                # the XLA forms (a toy width's chunks, the one-row
                # recurrence) are per slot: the view [B, S, ...] around
                # them, of q, k, v, g, beta and of what comes back
                if rows is not None:
                    with scope("pt.view"):
                        q, k, v, g, beta = (rows.to_slots(a) for a in
                                            (q, k, v, g, beta))
                with scope("pt.gate"):
                    g = jnp.where(live[:, :, None, None], g, 0.0)
                    beta = jnp.where(live[:, :, None], beta, 0.0)
                with scope("pt.core"):
                    run = _kda.kda_recurrent if s == 1 else _kda.kda_chunk
                    o, S = run(q, k, v, g, beta, S)
                    counts = jnp.zeros((len(_walk.COUNTERS),), jnp.int32)
                if rows is not None:
                    with scope("pt.view"):
                        o = rows.from_slots(o)
            if rows is not None:
                o = o[None]
            with scope("pt.gate"):
                o = (rms(o, on, eps) * gate).astype(x.dtype)
            with scope("o_proj"):
                return mm(o.reshape(lead + (H * K,)), wo), S, tail, counts

        st = cache.state
        out, S, tail, counts = dispatch(
            fn, (x, st["S"], st["conv"], cache.seq_lens, cache.q_lens,
                 self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                 self.q_conv, self.k_conv, self.v_conv,
                 self.f_a_proj.weight, self.f_b_proj.weight, self.dt_bias,
                 self.A_log, self.b_proj.weight, self.g_a_proj.weight,
                 self.g_b_proj.weight, self.o_norm.weight,
                 self.o_proj.weight), {}, name="kimi_kda")
        CL.count(counts._value, at=len(StateCausalLM.step_counter_names))
        return out, CL.RecurrentCache({"S": S, "conv": tail}, cache.seq_lens,
                                      cache.q_lens, cache.row_budget, rows)


class KimiDecoderLayer(DecoderBlock):
    def __init__(self, c: KimiLinearConfig, layer_idx):
        kind = c.layer_kind(layer_idx)
        attn = KimiDeltaAttention(c) if kind == "kda" \
            else LatentAttention(
                c.hidden_size, c.num_attention_heads, c.kv_lora_rank,
                c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                c.rms_norm_eps)
        mlp = SwiGLU(c.hidden_size, c.intermediate_size) \
            if layer_idx < c.first_k_dense_replace else SparseMoE(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_published, c.expert_offset,
                c.num_experts_per_token, c.routed_scaling_factor,
                c.moe_intermediate_size * c.num_shared_experts,
                renormalize=c.moe_renormalize)
        super().__init__(attn, mlp, c.hidden_size, c.rms_norm_eps)
        self.kind = kind


class KimiLinearForCausalLM(StateCausalLM):
    #: the experts' counts, then the KDA kernel's grid
    step_counter_names = StateCausalLM.step_counter_names + _walk.COUNTERS

    def __init__(self, config: KimiLinearConfig):
        super().__init__(config, StateDecoder(config, [
            KimiDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)]))

    def cache_layout(self):
        """One state kind a layer, for the serving engine."""
        dt = self.model.embed_tokens.weight.dtype
        return [CL.Recurrent(layer.self_attn.state_shapes(np.dtype(dt)))
                if layer.kind == "kda"
                else CL.PagedLatent(layer.self_attn.width)
                for layer in self.model.layers]
