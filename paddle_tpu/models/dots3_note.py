"""dots3-note (``model_type: "dots3_note"``, dots-studio/dots3-note-prev):
the language model of a decoder that interleaves, by ``layer_types``,
latent attention over the positions a LEARNED INDEXER selects with latent
attention of a second geometry over a sliding window, each under a sigmoid
gate a head, over a dense SwiGLU in the first layer and sparse experts
with one shared expert in the rest.

    block l:  h = x + Mix_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))
    Mix_l = SparseMLA if layer_types[l] == "full_attention" else WindowMLA

Both mixers are :class:`~paddle_tpu.models.latent_moe.LatentAttention`
(multi-head latent attention, absorbed, over a pool of ``(latent, rotated
shared key part)`` entries) with what this family adds passed by
constructor argument: the latents rescaled after their norms (``a_q =
(hidden / q_lora_rank)^1/2``, ``a_kv = (hidden / kv_lora_rank)^1/2``), a
gate a head before ``o_proj``, plain rotary positions with a theta a
mixer. SparseMLA (``num_attention_heads`` heads on the plain keys) also
takes an :class:`~paddle_tpu.models.latent_moe.Indexer`: ``index_n_heads``
index queries from the compressed query, one index key a token, a row
attends the ``index_topk`` positions of largest ``sum_j w_j ReLU(qI_j .
kI)`` (``ops/kernels/sparse_latent_attention.py``); while a context has
no more than ``index_topk`` positions that is every causal position.
WindowMLA (the ``swa_`` keys: other head count, ranks and key widths)
attends a row's last ``sliding_window_size`` positions, itself included.
The experts are :class:`~paddle_tpu.models.latent_moe.SparseMoE` as it is
(sigmoid scores over all the published experts, a selection bias, one
group, renormalised): this model holds experts ``[expert_offset,
expert_offset + n_routed_experts)`` of ``n_routed_experts_published``.

Serving only, with TWO state kinds in one layout
(``models/cache_layout.py``): ``IndexedLatent`` for a SparseMLA layer (a
latent pool and an index pool on the engine's one block table) and
``WindowedLatent`` for a WindowMLA layer (a ring of ``window_ring_rows``
entries a slot, on a table derived in the graph). The plain float32 form
is ``benchmark/reference/dots3_note_plain.py``; what ``config.json``
leaves open is listed under ``assumed`` in ``benchmark/configs/
dots3-note-prev-ep8-d5.json``. The vision tower, the audio encoder and the
MTP module of the published model are not described by its configuration
and are not here."""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from ..nn import rotary
from ..ops.kernels import sparse_latent_attention as _dsa
from . import cache_layout as CL
from .latent_moe import (DecoderBlock, Indexer, LatentAttention, SparseMoE,
                         StateCausalLM, StateDecoder, SwiGLU)

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclass
class Dots3NoteConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    num_hidden_layers: int = 46
    #: "full_attention" or "sliding_attention" a layer (from 0); a list
    #: longer than the depth is cut to it
    layer_types: tuple = tuple(
        FULL if i in (0, 1) or i % 4 == 1 else SLIDING for i in range(46))
    #: SparseMLA
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    #: WindowMLA
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    #: rows of a window layer's ring a slot: at least ``sliding_window_size
    #: - 1`` and the most rows the engine grants a slot a step (its chunk)
    window_ring_rows: int = 1024
    #: experts
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 256             # held here
    n_routed_experts_published: int = 256   # the router's width
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    tie_word_embeddings: bool = False

    def layer_kind(self, i):
        kind = tuple(self.layer_types)[i]
        if kind not in (FULL, SLIDING):
            raise ValueError(f"layer_types[{i}] = {kind!r} is not written")
        return kind


def _rotary(theta, dim):
    inv_freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    return functools.partial(rotary.rotate_pairs,
                             inv_freq=inv_freq.astype(np.float32))


def _attention(c: Dots3NoteConfig, kind):
    h = c.hidden_size
    if kind == SLIDING:
        return LatentAttention(
            h, c.swa_num_attention_heads, c.swa_kv_lora_rank,
            c.swa_qk_nope_head_dim, c.swa_qk_rope_head_dim, c.swa_v_head_dim,
            c.rms_norm_eps, q_rank=c.swa_q_lora_rank,
            rotary=_rotary(c.swa_rope_theta, c.swa_qk_rope_head_dim),
            rescale=((h / c.swa_q_lora_rank) ** 0.5,
                     (h / c.swa_kv_lora_rank) ** 0.5),
            head_gate=True, window=c.sliding_window_size)
    return LatentAttention(
        h, c.num_attention_heads, c.kv_lora_rank, c.qk_nope_head_dim,
        c.qk_rope_head_dim, c.v_head_dim, c.rms_norm_eps,
        q_rank=c.q_lora_rank,
        rotary=_rotary(c.rope_theta, c.qk_rope_head_dim),
        rescale=((h / c.q_lora_rank) ** 0.5, (h / c.kv_lora_rank) ** 0.5),
        head_gate=True,
        indexer=Indexer(h, c.q_lora_rank, c.index_n_heads, c.index_head_dim,
                        c.index_topk))


def _feed_forward(c: Dots3NoteConfig, layer_idx):
    if layer_idx < c.first_k_dense_replace:
        return SwiGLU(c.hidden_size, c.intermediate_size)
    return SparseMoE(
        c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
        c.n_routed_experts_published, c.expert_offset,
        c.num_experts_per_tok, c.routed_scaling_factor,
        c.moe_intermediate_size * c.n_shared_experts,
        renormalize=c.norm_topk_prob)


class Dots3NoteDecoderLayer(DecoderBlock):
    def __init__(self, c: Dots3NoteConfig, layer_idx):
        self.kind = c.layer_kind(layer_idx)
        super().__init__(_attention(c, self.kind),
                         _feed_forward(c, layer_idx), c.hidden_size,
                         c.rms_norm_eps)


class Dots3NoteForCausalLM(StateCausalLM):
    #: the experts' counts, then the indexed and windowed layers'
    step_counter_names = StateCausalLM.step_counter_names + _dsa.COUNTERS
    step_emit_ids = {**StateCausalLM.step_emit_ids, **_dsa.EMIT_IDS}

    def __init__(self, config: Dots3NoteConfig):
        super().__init__(config, StateDecoder(config, [
            Dots3NoteDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)]))

    def cache_layout(self, ring=None):
        """One state kind a layer: a latent pool with its index pool for a
        SparseMLA layer, a ring a slot for a WindowMLA layer (``ring``
        rows; None: the configuration's)."""
        c = self.config
        dt = np.dtype(self.model.embed_tokens.weight.dtype)
        ring = c.window_ring_rows if ring is None else int(ring)
        return [CL.IndexedLatent(layer.self_attn.width, c.index_head_dim)
                if layer.kind == FULL
                else CL.WindowedLatent(layer.self_attn.width,
                                       c.sliding_window_size, ring, dt)
                for layer in self.model.layers]

    def _fresh_layout(self, seq, block_size):
        # one call over ``seq`` new rows a slot: a ring that holds them all
        need = self.config.sliding_window_size - 1 + seq
        return self.cache_layout(ring=-(-need // block_size) * block_size)
