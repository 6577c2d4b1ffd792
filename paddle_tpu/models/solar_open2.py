"""Solar-Open2 (``model_type: "solar_open2"``, upstage/Solar-Open2-250B):
a decoder that interleaves Kimi Delta Attention (KDA: a gated delta rule
with a decay per key channel, a recurrent state a sequence) three to one
with GATED softmax attention over grouped K and V WITHOUT positions, over
sparse experts with one shared expert in every layer.

    block l:  h = x + Mix_l(RMSNorm(x));   y = h + MoE(RMSNorm(h))
    Mix_l = GatedGQA if l in ``gqa_layers`` else KDA        (no biases)

GatedGQA, Hq query heads on Hk key/value heads of width d (``head_dim`` is
a key of its own: ``hidden_size / heads`` is NOT the head size), query head
a reads key/value head ``a // (Hq / Hk)``: ``q, k, v = W u``; nothing is
rotated and nothing is normed; causal ``softmax(q . k / sqrt(d))``; ``out
= W_o [concat_a o^a * sigmoid(W_g u)]``, a gate a channel before ``W_o``
(arXiv:2505.06708). Served over paged K and V pools by the paged attention
kernels (``incubate.nn.functional.block_multihead_attention``: its append
form in a mixed step, its one-token form in a decode scan), as the llama
family's attention is.

KDA is :class:`~paddle_tpu.models.kimi_linear.KimiDeltaAttention` with
``beta = 2 sigmoid(W_b u)`` (``kda_allow_neg_eigval``: ``I - beta k k^T``
has the eigenvalue ``1 - beta`` in (-1, 1) along ``k``); the experts are
:class:`~paddle_tpu.models.latent_moe.SparseMoE` (sigmoid scores over all
the published experts, a selection bias, one group, renormalised
weights): this model holds experts ``[expert_offset, expert_offset +
n_routed_experts)`` of ``n_routed_experts_published`` and routes over all
of them.

Serving only, on :mod:`paddle_tpu.models.latent_moe`'s block, decoder and
causal LM, with TWO state kinds in one layout: ``cache_layout.PagedKV`` for
a GQA layer (K and V pools on the engine's block table) and
``cache_layout.Recurrent`` for a KDA layer. The plain float32 form is
``benchmark/reference/solar_open2_plain.py``; what ``config.json`` leaves
open is listed under ``assumed`` in ``benchmark/configs/solar-open2-250b-
ep8-d4.json``. The backward of neither mixer is written (ROADMAP Queue
2)."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax

from .. import ops
from ..nn import Layer, Linear
from ..core.tensor import dispatch
from ..ops.kernels import kda_chunk_walk as _walk
from ..profiler import scope
from . import cache_layout as CL
from .kimi_linear import KimiDeltaAttention
from .latent_moe import (F32, DecoderBlock, SparseMoE, StateCausalLM,
                         StateDecoder, mm32)
from .llama import PagedKVCache


@dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    #: gated GQA
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    #: the layers (from 0) whose mixer is gated GQA; the others are KDA
    gqa_layers: tuple = tuple(range(0, 48, 4))
    #: KDA (``linear_attn_config``)
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    gate_low_rank: int = 128
    #: ``kda_allow_neg_eigval``: beta = kda_beta_scale * sigmoid(.)
    kda_beta_scale: float = 2.0
    #: experts, in every layer
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320             # held here
    n_routed_experts_published: int = 320   # the router's width
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False

    def layer_kind(self, i):
        """"gqa" or "kda" for layer ``i`` (from 0)."""
        return "gqa" if i in tuple(self.gqa_layers) else "kda"


class GatedAttention(Layer):
    """Softmax attention of ``heads`` query heads over ``kv_heads`` K/V
    heads of ``head_dim``, no positions, a sigmoid gate a channel on the
    heads' outputs before ``o_proj``. ``x`` is ``[B, S, hidden]`` or a
    mixed step's packed ``[1, T, hidden]``: the projections, the gate and
    the paged attention all run on x's own rows (the attention op's
    packed form, with ``rows.start``), and no per-slot view is built."""

    def __init__(self, hidden, heads, kv_heads, head_dim):
        super().__init__()
        self.H, self.Hkv, self.D = heads, kv_heads, head_dim
        lin = lambda i, o: Linear(i, o, bias_attr=False)  # noqa: E731
        self.q_proj = lin(hidden, heads * head_dim)
        self.k_proj = lin(hidden, kv_heads * head_dim)
        self.v_proj = lin(hidden, kv_heads * head_dim)
        self.g_proj = lin(hidden, heads * head_dim)
        self.o_proj = lin(heads * head_dim, hidden)

    def forward(self, x, cache):
        from ..incubate.nn import functional as IF
        rows = CL.packed(cache)
        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        with scope("pt.view"):
            qkv = ops.concat([q, k, v], axis=-1)
            b, s, width = qkv.shape
        if rows is not None:
            # the packed append form: slot b's rows are the q_lens[b]
            # from rows.start[b] on
            with scope("pt.view"):
                qkv = ops.reshape(qkv, [s, width])
            with scope("pt.core"):
                o, kc, vc = IF.block_multihead_attention(
                    qkv, cache.k, cache.v, None, cache.seq_lens,
                    cache.q_lens, cu_seqlens_q=rows.start,
                    block_tables=cache.block_tables, max_seq_len=rows.width)
        elif s != 1:
            # the append form: S rows a slot, q_lens of them live
            with scope("pt.core"):
                o, kc, vc = IF.block_multihead_attention(
                    qkv, cache.k, cache.v, None, cache.seq_lens,
                    cache.q_lens, block_tables=cache.block_tables)
        else:
            with scope("pt.view"):
                qkv = ops.reshape(qkv, [b, width])
            with scope("pt.core"):
                o, kc, vc = IF.block_multihead_attention(
                    qkv, cache.k, cache.v, None, cache.seq_lens, None,
                    block_tables=cache.block_tables)
        with scope("pt.view"):
            o = ops.reshape(o, [b, s, self.H * self.D])

        def gated(o, x, wg):
            with scope("g_proj"):
                g = mm32(x, wg)
            with scope("pt.gate"):
                return (o.astype(F32) * jax.nn.sigmoid(g)).astype(x.dtype)

        o = dispatch(gated, (o, x, self.g_proj.weight), {},
                     name="gated_attention")
        return self.o_proj(o), PagedKVCache(
            kc, vc, cache.block_tables, cache.seq_lens, cache.q_lens,
            rows=rows, row_budget=cache.row_budget)


class SolarOpen2DecoderLayer(DecoderBlock):
    def __init__(self, c: SolarOpen2Config, layer_idx):
        kind = c.layer_kind(layer_idx)
        attn = GatedAttention(c.hidden_size, c.num_attention_heads,
                              c.num_key_value_heads, c.head_dim) \
            if kind == "gqa" else KimiDeltaAttention(
                c, beta_scale=c.kda_beta_scale)
        mlp = SparseMoE(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            c.n_routed_experts_published, c.expert_offset,
            c.num_experts_per_tok, c.routed_scaling_factor,
            c.moe_intermediate_size * c.n_shared_experts,
            renormalize=c.norm_topk_prob)
        super().__init__(attn, mlp, c.hidden_size, c.rms_norm_eps)
        self.kind = kind


class SolarOpen2ForCausalLM(StateCausalLM):
    #: the experts' counts, then the KDA kernel's grid
    step_counter_names = StateCausalLM.step_counter_names + _walk.COUNTERS

    def __init__(self, config: SolarOpen2Config):
        super().__init__(config, StateDecoder(config, [
            SolarOpen2DecoderLayer(config, i)
            for i in range(config.num_hidden_layers)]))

    def cache_layout(self):
        """One state kind a layer: K and V pools for a GQA layer, the
        recurrent state and the convolution's tail for a KDA layer."""
        c = self.config
        dt = np.dtype(self.model.embed_tokens.weight.dtype)
        return [CL.PagedKV(c.num_key_value_heads, c.head_dim,
                           q_heads=c.num_attention_heads)
                if layer.kind == "gqa"
                else CL.Recurrent(layer.self_attn.state_shapes(dt))
                for layer in self.model.layers]
