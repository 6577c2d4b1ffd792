"""DeepSeek-V2: a decoder whose every layer attends through a compressed
latent (MLA) with a compressed query and rotary positions scaled by YaRN,
over a dense SwiGLU in the first layer and, in the rest, routed experts
chosen by softmax scores among the best groups of experts, beside shared
experts.

Serving only, on ``models/latent_moe.py``'s layers (which the Kimi-Linear
family shares): every layer's state is a paged latent pool
(``cache_layout.PagedLatent``), so the engine's layout is latent only.

Layer equations (``benchmark/reference/deepseek_v2_plain.py`` is their
plain float32 form, and ``benchmark/configs/deepseek-v2-ep4-d5.json``
lists what ``config.json`` leaves to the family's public implementation):

Attention, H heads: ``c_q = RMSNorm(W_qa x)``, ``[q_nope_h; q_pe_h] =
W_qb,h c_q``; ``[c; k_pe] = W_kva x``, ``c <- RMSNorm(c)``; ``q_pe_h`` and
the one shared ``k_pe`` are rotated, pairs ``(2i, 2i + 1)`` by ``p f_i``
with YaRN's frequencies (``nn/rotary.py``); a token's cache entry is ``(c,
R_p k_pe)``; ``[k_nope_h; v_h] = W_kvb,h c``; causal softmax of ``q_h .
[k_nope_h; k_pe] (nope + pe)^-1/2 m^2``, ``m = yarn_mscale(factor,
mscale_all_dim)``; cos and sin carry ``yarn_mscale(factor, mscale) / m``.

Experts (layers from ``first_k_dense_replace`` on): ``s = softmax(W_r
x)`` over ALL the published experts in float32; ``n_group`` runs of
consecutive experts, a group scored by its best expert; the ``topk_group``
best groups keep their scores, the rest are 0; the ``num_experts_per_tok``
largest; ``w_i = routed_scaling_factor s_i`` (not renormalised, no
selection bias); ``y = E_shared(x) + sum over the selected experts HELD
here of w_i E_i(x)``, ``E_shared`` one SwiGLU of ``n_shared_experts x
moe_intermediate_size``. This model holds experts ``[expert_offset,
expert_offset + n_routed_experts)`` of ``n_routed_experts_published``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

from ..nn import rotary
from . import cache_layout as CL
from .latent_moe import (DecoderBlock, LatentAttention, SparseMoE,
                         StateCausalLM, StateDecoder, SwiGLU)


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    #: YaRN: ``factor`` 1 is plain rotary positions
    rope_scaling: dict = field(default_factory=lambda: dict(
        factor=40, original_max_position_embeddings=4096, beta_fast=32,
        beta_slow=1, mscale=0.707, mscale_all_dim=0.707))
    #: experts
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 160             # held here
    n_routed_experts_published: int = 160   # the router's width
    expert_offset: int = 0
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 163840
    tie_word_embeddings: bool = False


def _attention(c: DeepseekV2Config):
    rs = c.rope_scaling
    factor = float(rs["factor"])
    inv_freq = rotary.yarn_inv_freq(
        c.qk_rope_head_dim, c.rope_theta, factor,
        rs["original_max_position_embeddings"], rs["beta_fast"],
        rs["beta_slow"])
    all_dim = rotary.yarn_mscale(factor, rs["mscale_all_dim"])
    return LatentAttention(
        c.hidden_size, c.num_attention_heads, c.kv_lora_rank,
        c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
        c.rms_norm_eps, q_rank=c.q_lora_rank,
        rotary=functools.partial(
            rotary.rotate_pairs, inv_freq=inv_freq,
            magnitude=rotary.yarn_mscale(factor, rs["mscale"]) / all_dim),
        softmax_scale=(c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
        * all_dim * all_dim)


def _feed_forward(c: DeepseekV2Config, layer_idx):
    if layer_idx < c.first_k_dense_replace:
        return SwiGLU(c.hidden_size, c.intermediate_size)
    return SparseMoE(
        c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
        c.n_routed_experts_published, c.expert_offset,
        c.num_experts_per_tok, c.routed_scaling_factor,
        c.moe_intermediate_size * c.n_shared_experts, scoring="softmax",
        selection_bias=False, n_group=c.n_group, topk_group=c.topk_group,
        renormalize=False)


class DeepseekV2ForCausalLM(StateCausalLM):
    def __init__(self, config: DeepseekV2Config):
        c = config
        super().__init__(c, StateDecoder(c, [
            DecoderBlock(_attention(c), _feed_forward(c, i), c.hidden_size,
                         c.rms_norm_eps)
            for i in range(c.num_hidden_layers)]))

    def cache_layout(self):
        """A paged latent pool every layer, for the serving engine."""
        return [CL.PagedLatent(layer.self_attn.width)
                for layer in self.model.layers]
