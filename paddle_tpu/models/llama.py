"""Llama-family decoder LM — the flagship/north-star model.

Reference analog: the reference trains Llama through PaddleNLP on top of fleet TP
layers + flash-attn + fused rope/rms kernels
(test/auto_parallel/hybrid_strategy/semi_auto_llama.py is the in-tree config).

TPU-first design decisions:
- bf16 weights + fp32 RMSNorm accumulation (MXU-native dtypes)
- attention through F.scaled_dot_product_attention → Pallas flash kernel on TPU
- rope applied in fp32 with precomputed cos/sin cache (fused by XLA)
- mesh sharding annotations live OUTSIDE the model (distributed.shard_llama applies
  GSPMD NamedShardings over a dp/tp mesh) so the same module runs 1-chip or pod.
"""
from __future__ import annotations

import math

import numpy as np
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..nn import Layer, Linear, Embedding, RMSNorm, LayerList
from ..nn import functional as F
from ..nn.functional.attention import flash_tp_context
from ..core.tensor import Tensor, dispatch, functional_mode
from ..jit.functional_call import stored_sharding
from .lora import active_lora
from .cache_layout import packed
from ..profiler import scope
from .. import ops


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    # PaddleNLP-style horizontal fusion: one QKV GEMM / one gate+up GEMM so
    # the layer input is read once per block instead of 3x/2x (HBM win)
    fuse_attention_qkv: bool = False
    fuse_swiglu: bool = False
    # per-decoder-layer activation recompute (reference: PaddleNLP llama
    # use_recompute → fleet recompute per block). Saves only each block's
    # input; XLA re-traces the block inside the backward.
    use_recompute: bool = False
    recompute_policy: str | None = None
    dtype: str = "float32"

    @staticmethod
    def llama2_7b(**over):
        return LlamaConfig(**{**dict(hidden_size=4096, intermediate_size=11008,
                                     num_hidden_layers=32, num_attention_heads=32),
                              **over})

    @staticmethod
    def tiny(**over):
        return LlamaConfig(**{**dict(vocab_size=1024, hidden_size=128,
                                     intermediate_size=352, num_hidden_layers=2,
                                     num_attention_heads=4, num_key_value_heads=4,
                                     max_position_embeddings=256), **over})


#: Megatron TP placement plan for the llama stack (weights are [in, out]
#: like nn.Linear): column-parallel shards the output dim, row-parallel the
#: input dim, the vocab embedding its vocab dim. THE canonical table — the
#: 7B scale proofs, the pod-topology worker, and the sharded-generate tests
#: all consume it (reference: fleet mp_layers Column/RowParallelLinear as
#: applied in test/auto_parallel/hybrid_strategy/semi_auto_llama.py).
LLAMA_TP_RULES = (
    ("embed_tokens.weight", ("mp", None)),
    ("q_proj.weight", (None, "mp")),
    ("k_proj.weight", (None, "mp")),
    ("v_proj.weight", (None, "mp")),
    ("o_proj.weight", ("mp", None)),
    ("gate_proj.weight", (None, "mp")),
    ("up_proj.weight", (None, "mp")),
    ("down_proj.weight", ("mp", None)),
    ("lm_head.weight", (None, "mp")),
)


def llama_tp_spec(name, axis="mp"):
    """PartitionSpec for parameter ``name`` under LLAMA_TP_RULES (norms and
    everything unlisted: replicated).

    Weight-only quantized deploy params are covered too: a
    ``*.quant_weight`` keeps its base linear's [in, out] placement (the
    int4 packed in-dim shards the same way — each packed row holds two
    adjacent input features), and ``*.weight_scale`` ([out]) shards iff the
    base rule shards the out dim — otherwise a quantized model would
    silently replicate under TP."""
    from jax.sharding import PartitionSpec

    def expand(spec):
        return PartitionSpec(*[axis if s == "mp" else s for s in spec])

    for pat, spec in LLAMA_TP_RULES:
        if name.endswith(pat):
            return expand(spec)
        stem = pat[:-len(".weight")] if pat.endswith(".weight") else None
        if stem is not None:
            if name.endswith(stem + ".quant_weight"):
                return expand(spec)
            if name.endswith(stem + ".weight_scale"):
                return expand(spec[1:]) if spec[1] == "mp" \
                    else PartitionSpec()
    return PartitionSpec()


def precompute_rope(head_dim, max_len, theta=10000.0):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)                      # [T, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)      # [T, D]
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(q, k, cos, sin, position_offset=0):
    """q,k: [B, S, H, D]; rotate-half formulation in fp32. position_offset is
    a scalar (shared offset), a [B] vector (per-slot positions for the
    continuous-batching decode step) or a [B, S] array of every row's own
    position (the mixed step's packed row axis)."""
    s = q.shape[1]
    if getattr(position_offset, "ndim", 0) == 2:
        cos_t = jnp.take(cos, position_offset, axis=0)[:, :, None, :]
        sin_t = jnp.take(sin, position_offset, axis=0)[:, :, None, :]
    elif getattr(position_offset, "ndim", 0) == 1:
        pos = position_offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        cos_t = jnp.take(cos, pos, axis=0)[:, :, None, :]   # [B, S, 1, D]
        sin_t = jnp.take(sin, pos, axis=0)[:, :, None, :]
    else:
        cos_t = jax.lax.dynamic_slice_in_dim(
            cos, position_offset, s, 0)[None, :, None, :]
        sin_t = jax.lax.dynamic_slice_in_dim(
            sin, position_offset, s, 0)[None, :, None, :]

    def rot(x):
        x32 = x.astype(jnp.float32)
        half = x.shape[-1] // 2
        x1, x2 = x32[..., :half], x32[..., half:]
        rotated = jnp.concatenate([-x2, x1], axis=-1)
        return (x32 * cos_t + rotated * sin_t).astype(x.dtype)
    return rot(q), rot(k)


class StaticKVCache:
    """Fixed-capacity per-layer KV cache for decoding: buffers preallocated
    at the FINAL sequence length and written in place with
    dynamic_update_slice. Together with a traced position offset, every
    decode step then has static shapes — ONE compiled program serves the
    whole generation instead of one per token per layer (the concat-grown
    tuple cache changes the k/v length every step)."""

    __slots__ = ("k", "v")

    def __init__(self, k, v):
        self.k, self.v = k, v


class SlotKVCache:
    """Static KV buffers with PER-SLOT lengths — the continuous-batching
    cache (:class:`paddle_tpu.inference.LLMEngine`): ``k``/``v`` are
    [B, capacity, H, D] slot buffers and ``lens`` [B] is how many tokens each
    slot has cached. A decode step writes slot b's new KV at position
    ``lens[b]`` and attends positions <= lens[b], so sequences of different
    lengths share ONE compiled step program. The engine, not the model,
    advances ``lens`` (only for slots that are active)."""

    __slots__ = ("k", "v", "lens")

    def __init__(self, k, v, lens):
        self.k, self.v, self.lens = k, v, lens


class PagedKVCache:
    """vLLM-style paged KV cache (reference:
    python/paddle/incubate/nn/functional/block_multihead_attention.py:1 —
    the phi block_multi_head_attention kernel's layout): physical pools
    ``k``/``v`` of shape [num_blocks, H, block_size, D], a per-sequence
    ``block_tables`` [B, max_blocks] mapping logical KV block -> physical
    block (-1 = unallocated), and ``seq_lens`` [B] tokens already cached.
    Decode steps attend through
    :func:`paddle_tpu.incubate.nn.functional.block_multihead_attention`.

    ``q_lens`` (the fused scheduler's mixed step): per-sequence count of
    REAL rows in an S>1 window — sequence b appends positions
    [seq_lens[b], seq_lens[b]+q_lens[b]) (a prefill chunk, one decode
    token, or 0 = idle slot; rows past q_lens are padding). Required for
    S>1; None keeps the one-token decode-step contract.

    ``quant`` + ``k_scale``/``v_scale`` (the engine's ``kv_cache_dtype``):
    the pools are int8/int4 QUANTIZED storage (int4 nibble-packed on the
    head dim) with per-(physical block, kv head) fp32 scale arrays
    [num_blocks, Hkv] riding alongside — the attention op dequantizes on
    read and returns updated scales with the pools.

    ``rows`` (a :class:`~paddle_tpu.models.cache_layout.RowMap`): the
    layer's ``x`` is the mixed step's packed ``[1, T, ...]`` and not
    ``[B, S, ...]``; the attention op takes the rows as they lie, with
    ``rows.start`` (its packed form).

    ``row_budget``: the dispatcher's static bound on the step's live rows
    over all slots, as the other kinds' cache objects carry it (None:
    every row may be live); an expert layer beside the attention sizes
    its grouped product by it."""

    __slots__ = ("k", "v", "block_tables", "seq_lens", "q_lens",
                 "k_scale", "v_scale", "quant", "rows", "row_budget")

    def __init__(self, k, v, block_tables, seq_lens, q_lens=None,
                 k_scale=None, v_scale=None, quant=None, rows=None,
                 row_budget=None):
        self.k, self.v = k, v
        self.block_tables, self.seq_lens = block_tables, seq_lens
        self.q_lens = q_lens
        self.k_scale, self.v_scale = k_scale, v_scale
        self.quant, self.rows = quant, rows
        self.row_budget = row_budget


class ChunkKVCache:
    """Dense slot buffers with per-slot APPEND windows — the fused
    prefill+decode scheduler's dense cache: ``k``/``v`` are [B, capacity,
    H, D] slot buffers, ``lens`` [B] tokens already cached, ``q_lens``
    [B] how many of the step's S rows are real for each slot. Row i of
    slot b writes position lens[b]+i when i < q_lens[b] (padding and
    past-capacity rows DROP — no dynamic-slice clamping that could slide
    back over live history) and attends causally to positions
    <= lens[b]+i. The engine advances ``lens`` by q_lens itself.
    ``rows``: as on :class:`PagedKVCache`."""

    __slots__ = ("k", "v", "lens", "q_lens", "rows")

    def __init__(self, k, v, lens, q_lens, rows=None):
        self.k, self.v, self.lens, self.q_lens = k, v, lens, q_lens
        self.rows = rows


def _window_causal_mask(s, T):
    """Additive mask builder for a per-slot decode/append window: row i of
    slot b sits at absolute position lens[b]+i and may see positions
    <= lens[b]+i (cached history plus its own window prefix). THE one copy
    — the SlotKVCache and ChunkKVCache attention branches both dispatch
    it, so the sentinel/dtype can never diverge between the legacy slot
    path and the fused mixed step."""
    def mask_fn(lens):
        rows = lens.astype(jnp.int32)[:, None, None, None] + \
            jnp.arange(s, dtype=jnp.int32)[None, None, :, None]
        valid = jnp.arange(T, dtype=jnp.int32)[None, None, None, :] <= rows
        return jnp.where(valid, jnp.float32(0), jnp.float32(-1e30))
    return mask_fn


def _filter_logits(logits, temp_val, top_k, top_p_val, use_top_p=True):
    """THE temperature/top-k/top-p filter pipeline (temperature scale, then
    top-k cut, then the nucleus mass cut on the renormalized distribution).
    Single source consumed by the sampler below — which the serving
    engine's COUPLED speculative acceptance (inference/llm_engine.py
    ``verify_window``) also samples through, so speculative exactness
    rides on drafts being tested against exactly the distribution
    tokens are drawn from."""
    logits = logits.astype(jnp.float32) / temp_val.astype(jnp.float32)
    V = logits.shape[-1]
    if top_k and 0 < int(top_k) < V:
        # one O(V * k) top_k serves BOTH cuts: after the top-k mask, the
        # surviving distribution lives entirely in this sorted-descending
        # slice, so the nucleus cutoff computes over k entries instead of a
        # full O(V log V) sort of the 32k-vocab logits every sampled step.
        # Caveat: with EXACT ties at the k-th value the strict `< kth` mask
        # keeps all tied entries but the slice normalizes over exactly k —
        # a measure-zero divergence for real logits, accepted for the
        # per-step sort elimination
        vals = jax.lax.top_k(logits, int(top_k))[0]       # [..., k] desc
        kth = vals[..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
        if use_top_p:
            probs = jax.nn.softmax(vals, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep the minimal prefix reaching top_p mass: a position
            # survives when the mass BEFORE it is still < top_p
            keep = (cum - probs) < top_p_val.astype(jnp.float32)
            cutoff = jnp.min(jnp.where(keep, vals, jnp.inf), axis=-1,
                             keepdims=True)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return logits
    if use_top_p:
        sorted_desc = -jnp.sort(-logits, axis=-1)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the minimal prefix reaching top_p mass: a position survives
        # when the mass BEFORE it is still < top_p
        keep = (cum - probs) < top_p_val.astype(jnp.float32)
        cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _sample_logits_device(logits, key, temp_val, top_k, top_p_val, greedy,
                          use_top_p):
    """In-graph sampling head: greedy / temperature / top-k / top-p, all
    computed on device from the framework RNG (reference surface: paddlenlp
    generation's TopKProcess/TopPProcess, executed host-side there).
    ``greedy``/``top_k``/``use_top_p`` are STATIC (they shape the program);
    ``temp_val``/``top_p_val`` are traced scalars, so a serving loop varying
    them never recompiles."""
    if greedy:
        return jnp.argmax(logits.astype(jnp.float32),
                          axis=-1).astype(jnp.int32)
    filtered = _filter_logits(logits, temp_val, top_k, top_p_val, use_top_p)
    return jax.random.categorical(key, filtered, axis=-1).astype(jnp.int32)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig, layer_idx=0):
        super().__init__()
        c = config
        #: position in the decoder stack — the batched multi-LoRA
        #: context (models/lora.py) gathers this layer's slice of the
        #: stacked adapter factors by it
        self.layer_idx = int(layer_idx)
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        self.fused = bool(getattr(c, "fuse_attention_qkv", False))
        if self.fused:
            self.qkv_proj = Linear(
                c.hidden_size,
                (self.num_heads + 2 * self.num_kv_heads) * self.head_dim,
                bias_attr=False)
        else:
            self.q_proj = Linear(c.hidden_size, self.num_heads * self.head_dim,
                                 bias_attr=False)
            self.k_proj = Linear(c.hidden_size,
                                 self.num_kv_heads * self.head_dim,
                                 bias_attr=False)
            self.v_proj = Linear(c.hidden_size,
                                 self.num_kv_heads * self.head_dim,
                                 bias_attr=False)
        self.o_proj = Linear(self.num_heads * self.head_dim, c.hidden_size,
                             bias_attr=False)
        self.config = c

    def forward(self, x, rope_cache, attn_mask=None, kv_cache=None, position_offset=0):
        b, s = x.shape[0], x.shape[1]
        lora = active_lora()
        #: a mixed step's packed row axis: x is [1, T, ...]. The paged
        #: pools' attention takes the rows as they lie; the dense chunk
        #: cache (``cache_impl="dense"``) wants the per-slot view [B, S,
        #: ...] (``views``)
        rows = packed(kv_cache)
        views = rows is not None and not isinstance(kv_cache, PagedKVCache)
        if self.fused:
            if lora is not None:
                raise ValueError(
                    "batched multi-LoRA targets the separate q/k/v "
                    "projections; fuse_attention_qkv is incompatible "
                    "with an armed adapter scope")
            qkv = self.qkv_proj(x)
            nq = self.num_heads * self.head_dim
            nkv = self.num_kv_heads * self.head_dim
            q = ops.reshape(qkv[:, :, :nq],
                            [b, s, self.num_heads, self.head_dim])
            k = ops.reshape(qkv[:, :, nq:nq + nkv],
                            [b, s, self.num_kv_heads, self.head_dim])
            v = ops.reshape(qkv[:, :, nq + nkv:],
                            [b, s, self.num_kv_heads, self.head_dim])
        else:
            qf, kf, vf = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            if lora is not None:
                # gathered per-slot adapter delta on top of each base
                # projection — slot 0 rows gather zeros (base tenant)
                qf = lora.apply("q_proj", self.layer_idx, x, qf)
                kf = lora.apply("k_proj", self.layer_idx, x, kf)
                vf = lora.apply("v_proj", self.layer_idx, x, vf)
            q = ops.reshape(qf, [b, s, self.num_heads, self.head_dim])
            k = ops.reshape(kf, [b, s, self.num_kv_heads, self.head_dim])
            v = ops.reshape(vf, [b, s, self.num_kv_heads, self.head_dim])

        def o_proj(t):
            if views:
                with scope("pt.view"):
                    t = dispatch(lambda y: rows.from_slots(y)[None], (t,),
                                 {}, name="rows_from_slots")
            out = self.o_proj(t)
            if lora is not None:
                out = lora.apply("o_proj", self.layer_idx, t, out)
            return out
        cos, sin = rope_cache
        with scope("pt.rope"):
            if isinstance(position_offset, Tensor):
                # traced offset (static-shape decode): the offset is a
                # dispatch ARGUMENT, so every step shares one compiled entry
                q, k = dispatch(
                    lambda qq, kk, off: apply_rope(qq, kk, cos, sin,
                                                   off.astype(jnp.int32)),
                    (q, k, position_offset), {}, name="rope_offset")
            else:
                q, k = dispatch(
                    lambda qq, kk: apply_rope(qq, kk, cos, sin,
                                              position_offset),
                    (q, k), {}, name="rope")
        if views:
            # what is above ran on the granted rows; o_proj takes the
            # attention's output back to them
            with scope("pt.view"):
                q, k, v = dispatch(
                    lambda *ts: tuple(rows.to_slots(t[0]) for t in ts),
                    (q, k, v), {}, name="rows_to_slots")
            b, s = q.shape[0], q.shape[1]
        if isinstance(kv_cache, PagedKVCache):
            # paged decode step (one new token/sequence) through the
            # block_multihead_attention op — the framework's own paged-KV
            # kernel as the generate() cache backend. GQA-capable: q keeps
            # num_heads, K/V the (possibly smaller) num_kv_heads.
            from ..incubate.nn import functional as IF
            H, Hkv, D = self.num_heads, self.num_kv_heads, self.head_dim
            kvq = kv_cache.quant
            qargs = dict(cache_k_quant_scales=kv_cache.k_scale,
                         cache_v_quant_scales=kv_cache.v_scale,
                         cache_quant_type=kvq) if kvq else {}
            if s != 1 or rows is not None:
                # fused mixed step: S rows per slot, q_lens of them real —
                # the APPEND form of the op (Pallas append kernel on TPU,
                # dense scatter+gather fallback on CPU); its PACKED form
                # where the step's rows come on one axis: slot b's are
                # the q_lens[b] from rows.start[b] on
                if kv_cache.q_lens is None:
                    raise ValueError(
                        "PagedKVCache with seq len > 1 is the fused "
                        "append step and needs per-slot q_lens")
                lead = [b, s] if rows is None else [s]
                if rows is not None:
                    qargs.update(cu_seqlens_q=rows.start,
                                 max_seq_len=rows.width)
                with scope("pt.view"):
                    qkv = ops.concat([ops.reshape(q, lead + [H * D]),
                                      ops.reshape(k, lead + [Hkv * D]),
                                      ops.reshape(v, lead + [Hkv * D])],
                                     axis=-1)
                with scope("pt.core"):
                    outs = IF.block_multihead_attention(
                        qkv, kv_cache.k, kv_cache.v, None,
                        kv_cache.seq_lens, kv_cache.q_lens,
                        block_tables=kv_cache.block_tables, **qargs)
                out, kc, vc = outs[:3]
                ks, vs = outs[3:] if kvq else (None, None)
                out = o_proj(ops.reshape(out, [b, s, H * D]))
                return out, PagedKVCache(
                    kc, vc, kv_cache.block_tables,
                    kv_cache.seq_lens + kv_cache.q_lens, kv_cache.q_lens,
                    k_scale=ks, v_scale=vs, quant=kvq)
            with scope("pt.view"):
                qkv = ops.concat([ops.reshape(q, [b, H * D]),
                                  ops.reshape(k, [b, Hkv * D]),
                                  ops.reshape(v, [b, Hkv * D])], axis=-1)
            with scope("pt.core"):
                outs = IF.block_multihead_attention(
                    qkv, kv_cache.k, kv_cache.v, None, kv_cache.seq_lens,
                    None, block_tables=kv_cache.block_tables, **qargs)
            out, kc, vc = outs[:3]
            ks, vs = outs[3:] if kvq else (None, None)
            out = o_proj(ops.reshape(out, [b, 1, H * D]))
            new_lens = kv_cache.seq_lens + 1
            return out, PagedKVCache(kc, vc, kv_cache.block_tables,
                                     new_lens, k_scale=ks, v_scale=vs,
                                     quant=kvq)
        if isinstance(kv_cache, ChunkKVCache):
            # fused mixed step, dense cache: write slot b's q_lens[b] real
            # rows at positions lens[b]+i via a DROP scatter (padding and
            # past-capacity rows vanish instead of dynamic-slice clamping
            # back over live history), causal mask against each row's own
            # absolute position — one compiled program serves any mix of
            # prefill chunks and decode tokens across slots.
            def chunk_write(kb, vb, kk, vv, lens, qlens):
                cap_t = kb.shape[1]
                lens = lens.astype(jnp.int32)
                i_idx = jnp.arange(s, dtype=jnp.int32)
                pos = lens[:, None] + i_idx[None, :]
                pos = jnp.where(i_idx[None, :] < qlens.astype(jnp.int32)
                                [:, None], pos, cap_t)      # OOB -> drop

                def upd(buf, new, p):
                    return buf.at[p].set(new.astype(buf.dtype),
                                         mode="drop")

                return (jax.vmap(upd)(kb, kk, pos),
                        jax.vmap(upd)(vb, vv, pos))

            with scope("pt.view"):
                k_buf, v_buf = dispatch(
                    chunk_write,
                    (kv_cache.k, kv_cache.v, k, v, kv_cache.lens,
                     kv_cache.q_lens), {}, name="chunk_kv_update")
                T = k_buf.shape[1]
                mask = dispatch(_window_causal_mask(s, T), (kv_cache.lens,),
                                {}, name="chunk_decode_mask")
            with scope("pt.core"):
                out = F.scaled_dot_product_attention(
                    q, k_buf, v_buf, attn_mask=mask, is_causal=False,
                    training=self.training)
            out = ops.reshape(out, [b, s, self.num_heads * self.head_dim])
            return o_proj(out), ChunkKVCache(
                k_buf, v_buf, kv_cache.lens, kv_cache.q_lens)
        if isinstance(kv_cache, SlotKVCache):
            # continuous-batching decode window (s=1 plain step, s=K a
            # speculative verify window): write slot b's s new positions at
            # its own length, rope at its own positions, causal mask against
            # its own prefix — one compiled program for ragged slots.
            def slot_step(kb, vb, kk, vv, lens):
                lens = lens.astype(jnp.int32)
                upd1 = jax.vmap(lambda buf, new, o:
                                jax.lax.dynamic_update_slice_in_dim(
                                    buf, new.astype(buf.dtype), o, 0))
                return upd1(kb, kk, lens), upd1(vb, vv, lens)

            with scope("pt.view"):
                k_buf, v_buf = dispatch(
                    slot_step, (kv_cache.k, kv_cache.v, k, v, kv_cache.lens),
                    {}, name="slot_kv_update")
                T = k_buf.shape[1]
                mask = dispatch(_window_causal_mask(s, T), (kv_cache.lens,),
                                {}, name="slot_decode_mask")
            with scope("pt.core"):
                out = F.scaled_dot_product_attention(
                    q, k_buf, v_buf, attn_mask=mask, is_causal=False,
                    training=self.training)
            out = ops.reshape(out, [b, s, self.num_heads * self.head_dim])
            return o_proj(out), SlotKVCache(k_buf, v_buf, kv_cache.lens)
        if isinstance(kv_cache, StaticKVCache):
            def upd(buf, new, off):
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, new.astype(buf.dtype), off.astype(jnp.int32), 1)

            with scope("pt.view"):
                k_buf = dispatch(upd, (kv_cache.k, k, position_offset), {},
                                 name="kv_update")
                v_buf = dispatch(upd, (kv_cache.v, v, position_offset), {},
                                 name="kv_update")
            T = k_buf.shape[1]

            def make_mask(off):
                # causal against the absolute position: query row q may see
                # cached/current positions <= off+q (for s=1 decode this is
                # the old "<= off" mask; for s>1 chunked prefill it keeps
                # causality WITHIN the chunk)
                rows = off.astype(jnp.int32) + jnp.arange(s, dtype=jnp.int32)
                valid = jnp.arange(T, dtype=jnp.int32)[None, None, None, :] \
                    <= rows[None, None, :, None]
                return jnp.where(valid, jnp.float32(0), jnp.float32(-1e30))

            with scope("pt.view"):
                mask = dispatch(make_mask, (position_offset,), {},
                                name="kv_decode_mask")
            with scope("pt.core"):
                out = F.scaled_dot_product_attention(
                    q, k_buf, v_buf, attn_mask=mask, is_causal=False,
                    training=self.training)
            out = ops.reshape(out, [b, s, self.num_heads * self.head_dim])
            return o_proj(out), StaticKVCache(k_buf, v_buf)
        if kv_cache is not None:
            k = ops.concat([kv_cache[0], k], axis=1)
            v = ops.concat([kv_cache[1], v], axis=1)
            kv_cache = (k, v)
        with flash_tp_context(self._heads_tp()), scope("pt.core"):
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=(attn_mask is None),
                training=self.training)
        out = ops.reshape(out, [b, s, self.num_heads * self.head_dim])
        out = o_proj(out)
        return (out, kv_cache) if kv_cache is not None else out

    def _heads_tp(self):
        """(mesh, axis) when this layer's heads are stored sharded over a
        mesh axis — a column-parallel q projection (``llama_tp_spec``) —
        else None. The flash kernel is a Mosaic call, which GSPMD cannot
        partition: under that layout it must shard_map over the axis."""
        from jax.sharding import NamedSharding
        weight = getattr(getattr(self, "q_proj", None), "weight", None)
        if weight is None:
            return None
        sharding = stored_sharding(weight)
        if not isinstance(sharding, NamedSharding):
            return None
        spec = tuple(sharding.spec)
        axis = spec[1] if len(spec) > 1 else None
        if axis is None or isinstance(axis, tuple):
            return None
        return sharding.mesh, axis


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig, layer_idx=0):
        super().__init__()
        c = config
        self.layer_idx = int(layer_idx)
        self.fused = bool(getattr(c, "fuse_swiglu", False))
        if self.fused:
            self.gate_up_proj = Linear(c.hidden_size, 2 * c.intermediate_size,
                                       bias_attr=False)
        else:
            self.gate_proj = Linear(c.hidden_size, c.intermediate_size,
                                    bias_attr=False)
            self.up_proj = Linear(c.hidden_size, c.intermediate_size,
                                  bias_attr=False)
        self.down_proj = Linear(c.intermediate_size, c.hidden_size, bias_attr=False)
        self._ff = c.intermediate_size

    def forward(self, x):
        lora = active_lora()
        if self.fused:
            if lora is not None:
                raise ValueError(
                    "batched multi-LoRA targets the separate gate/up "
                    "projections; fuse_swiglu is incompatible with an "
                    "armed adapter scope")
            gu = self.gate_up_proj(x)
            return self.down_proj(F.swiglu(gu[:, :, :self._ff],
                                           gu[:, :, self._ff:]))
        gate, up = self.gate_proj(x), self.up_proj(x)
        if lora is not None:
            gate = lora.apply("gate_proj", self.layer_idx, x, gate)
            up = lora.apply("up_proj", self.layer_idx, x, up)
        h = F.swiglu(gate, up)
        out = self.down_proj(h)
        if lora is not None:
            out = lora.apply("down_proj", self.layer_idx, h, out)
        return out


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig, layer_idx=0):
        super().__init__()
        self.self_attn = LlamaAttention(config, layer_idx=layer_idx)
        self.mlp = LlamaMLP(config, layer_idx=layer_idx)
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def forward(self, x, rope_cache, attn_mask=None, kv_cache=None,
                position_offset=0):
        if kv_cache is not None:
            attn_out, new_cache = self.self_attn(
                self.input_layernorm(x), rope_cache, attn_mask, kv_cache,
                position_offset)
            x = x + attn_out
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        x = x + self.self_attn(self.input_layernorm(x), rope_cache, attn_mask)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(config, layer_idx=i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = precompute_rope(head_dim, config.max_position_embeddings,
                                   config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                position_offset=0):
        x = self.embed_tokens(input_ids)
        rope = (self.rope_cos._value, self.rope_sin._value)
        if kv_caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, kv_caches):
                x, c = layer(x, rope, attn_mask, cache, position_offset)
                new_caches.append(c)
            return self.norm(x), new_caches
        remat = self.config.use_recompute and self.training
        if remat:
            from ..distributed.fleet.recompute import recompute
        for layer in self.layers:
            if remat:
                x = recompute(layer, x, rope, attn_mask,
                              checkpoint_policy=self.config.recompute_policy)
            else:
                x = layer(x, rope, attn_mask)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    @property
    def decoder(self):
        """The serving engine's seam (``models/cache_layout.py``): the
        module it calls with per-layer caches."""
        return self.llama

    def cache_layout(self):
        """One state kind a layer: K and V of ``(kv_heads, head_dim)``."""
        from .cache_layout import PagedKV
        c = self.config
        return [PagedKV(c.num_key_value_heads,
                        c.hidden_size // c.num_attention_heads)
                for _ in range(c.num_hidden_layers)]

    def _logits(self, hidden):
        if self.config.tie_word_embeddings:
            with scope("lm_head"):
                return ops.matmul(hidden, self.llama.embed_tokens.weight,
                                  transpose_y=True)
        return self.lm_head(hidden)

    def forward(self, input_ids, labels=None, attn_mask=None):
        hidden = self.llama(input_ids, attn_mask)
        logits = self._logits(hidden)
        if labels is None:
            return logits
        with scope("pt.loss"):
            loss = F.cross_entropy(
                # no fp32 pre-cast: cross_entropy's fused path accumulates
                # the lse in fp32 internally without copying the logits
                ops.reshape(logits, [-1, self.config.vocab_size]),
                ops.reshape(labels, [-1]), ignore_index=-100)
        return loss, logits

    def _gen_programs(self, B, prompt_len, limit, total, temperature, top_k,
                      top_p, eos_token_id, cache_impl, block_size):
        """Build (or fetch cached) the two compiled generation programs:

        - ``prefill``: embed -> all layers (causal flash) -> last-position
          logits + per-layer KV buffers, as ONE jitted program.
        - ``decode``: the ENTIRE decode loop as one jitted program — a
          ``lax.while_loop`` whose body is sample (on-device, from the
          framework RNG) -> one-token model step -> cache write. No logits
          ever travel to host; the only host transfer is the final token
          buffer. With TP/dp-sharded weights the same programs partition
          under GSPMD (single-controller SPMD decode).

        Reference analog: the fused-decode serving stack —
        incubate/nn/functional/masked_multihead_attention.py:1 (dense) /
        block_multihead_attention.py:1 (paged) under AnalysisPredictor
        (paddle/fluid/inference/api/analysis_predictor.h:101)."""
        from ..jit.functional_call import collect_state, bind_state

        c = self.config
        # temperature/top_p VALUES are traced decode args; only the program
        # STRUCTURE (greedy vs sampling, top-k width, nucleus on/off) keys
        # the compile cache — varying sampling params never recompiles
        greedy = float(temperature) <= 0.0
        use_top_p = bool(top_p) and float(top_p) < 1.0
        key = (B, prompt_len, limit, total, greedy, int(top_k), use_top_p,
               eos_token_id, cache_impl, int(block_size))
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        if key in cache:
            return cache[key]

        _, params, _, buffers = collect_state(self)
        state = params + buffers
        head_dim = c.hidden_size // c.num_attention_heads
        kvh = c.num_key_value_heads
        n_layers = c.num_hidden_layers
        paged = cache_impl == "paged"
        bs = int(block_size)
        mb = -(-total // bs)  # blocks per sequence

        dt = self.llama.embed_tokens.weight.dtype

        def prefill(state_vals, ids_v):
            empty = [(Tensor(jnp.zeros((B, 0, kvh, head_dim), dt)),
                      Tensor(jnp.zeros((B, 0, kvh, head_dim), dt)))
                     for _ in range(n_layers)]
            with functional_mode(), bind_state(state, state_vals):
                hidden, grown = self.llama(Tensor(ids_v), kv_caches=empty,
                                           position_offset=0)
                logits = self._logits(hidden[:, -1:])._value[:, 0]
            if paged:
                # scatter prompt KV into the block pools: logical block i of
                # sequence b lives at physical block b*mb + i
                k_bufs, v_bufs = [], []
                for k, v in grown:
                    def pool(t):
                        tv = t._value
                        pad = mb * bs - tv.shape[1]
                        tv = jnp.pad(tv, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        tv = tv.reshape(B, mb, bs, kvh, head_dim)
                        return jnp.moveaxis(tv, 2, 3).reshape(
                            B * mb, kvh, bs, head_dim)
                    k_bufs.append(pool(k))
                    v_bufs.append(pool(v))
            else:
                def to_static(t):
                    pad = total - t.shape[1]
                    return jnp.pad(t._value,
                                   ((0, 0), (0, pad), (0, 0), (0, 0)))
                k_bufs = [to_static(k) for k, _ in grown]
                v_bufs = [to_static(v) for _, v in grown]
            return logits, k_bufs, v_bufs

        tables = jnp.arange(B * mb, dtype=jnp.int32).reshape(B, mb)

        def decode(state_vals, k_bufs, v_bufs, logits0, rng_key, temp_val,
                   top_p_val):
            buf0 = jnp.zeros((B, limit), jnp.int32)
            finished0 = jnp.zeros((B,), bool)

            def cond(carry):
                i, _, _, _, _, finished, _ = carry
                cont = i < limit
                if eos_token_id is not None:
                    cont = jnp.logical_and(cont, ~jnp.all(finished))
                return cont

            def body(carry):
                i, logits, kb, vb, rkey, finished, buf = carry
                rkey, sub = jax.random.split(rkey)
                nxt = _sample_logits_device(logits, sub, temp_val,
                                            int(top_k), top_p_val, greedy,
                                            use_top_p)
                if eos_token_id is not None:
                    nxt = jnp.where(finished, jnp.int32(eos_token_id), nxt)
                    finished = finished | (nxt == eos_token_id)
                buf = jax.lax.dynamic_update_slice(buf, nxt[:, None],
                                                   (jnp.int32(0), i))
                off = jnp.int32(prompt_len) + i
                with functional_mode(), bind_state(state, state_vals):
                    if paged:
                        lens = jnp.full((B,), off, jnp.int32)
                        caches = [PagedKVCache(k, v, tables, lens)
                                  for k, v in zip(kb, vb)]
                    else:
                        caches = [StaticKVCache(k, v)
                                  for k, v in zip(kb, vb)]
                    last_h, new_caches = self.llama(
                        Tensor(nxt[:, None]), kv_caches=caches,
                        position_offset=Tensor(off))
                    logits = self._logits(last_h)._value[:, 0]
                kb = [getattr(cc.k, "_value", cc.k) for cc in new_caches]
                vb = [getattr(cc.v, "_value", cc.v) for cc in new_caches]
                return (i + 1, logits, kb, vb, rkey, finished, buf)

            i, _, _, _, _, _, buf = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), logits0, k_bufs, v_bufs, rng_key, finished0,
                 buf0))
            return buf, i

        # decode consumes the prefill-built caches exactly once — donate them
        # so the cache update is in-place (no 2x KV footprint on chip)
        entry = (jax.jit(prefill), jax.jit(decode, donate_argnums=(1, 2)))
        cache[key] = entry
        return entry

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, top_p=1.0, eos_token_id=None, cache_impl="static",
                 block_size=64):
        """Autoregressive decoding, fully compiled (reference surface:
        paddlenlp GenerationMixin.generate over the fused-decode inference
        stack; the reference keeps it out-of-tree, the flagship model here
        ships it in-core).

        Prefill is ONE compiled program (causal flash over the prompt);
        the whole decode loop is ONE more (on-device while_loop: sample ->
        one-token step -> cache write), so logits never round-trip to host
        and per-token cost is pure device compute. ``cache_impl="static"``
        holds dense fixed-capacity per-layer buffers (:class:`StaticKVCache`)
        written at a traced offset; ``cache_impl="paged"`` routes decode
        attention through the framework's
        ``block_multihead_attention`` paged-KV op (:class:`PagedKVCache`,
        ``block_size``-token blocks). temperature<=0 = greedy; top_k/top_p
        sampling draws from the framework RNG (``paddle.seed``-
        deterministic). Works with TP/dp-sharded weights on a mesh (the
        programs partition under GSPMD). Decoding is capped at
        ``max_position_embeddings`` (the rope table's end) with a warning.
        """
        from ..core import random as _random

        c = self.config
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(jnp.asarray(np.asarray(input_ids), jnp.int32))
        B, prompt_len = ids.shape[0], ids.shape[1]
        if prompt_len >= c.max_position_embeddings:
            raise ValueError(
                f"prompt length {prompt_len} >= max_position_embeddings "
                f"{c.max_position_embeddings}: no positions left to decode")
        if cache_impl not in ("static", "paged"):
            raise ValueError(f"unknown cache_impl {cache_impl!r}")
        limit = min(int(max_new_tokens),
                    c.max_position_embeddings - prompt_len)
        if limit < int(max_new_tokens):
            import warnings
            warnings.warn(
                f"generate: capping max_new_tokens {max_new_tokens} -> "
                f"{limit} (rope table ends at position "
                f"{c.max_position_embeddings})", RuntimeWarning,
                stacklevel=2)
        if limit <= 0:
            return Tensor(jnp.zeros((B, 0), jnp.int64))
        total = prompt_len + limit
        seed, counter = _random.default_generator.next_seed()
        rng_key = jax.random.fold_in(jax.random.PRNGKey(seed), counter)

        was_training = self.training
        self.eval()
        try:
            prefill, decode = self._gen_programs(
                B, prompt_len, limit, total, temperature, top_k, top_p,
                eos_token_id, cache_impl, block_size)
            from ..jit.functional_call import collect_state, read_values
            _, params, _, buffers = collect_state(self)
            state_vals = read_values(params + buffers)
            logits0, k_bufs, v_bufs = prefill(state_vals,
                                              ids._value.astype(jnp.int32))
            buf, n = decode(state_vals, k_bufs, v_bufs, logits0, rng_key,
                            jnp.float32(max(float(temperature), 1e-6)),
                            jnp.float32(top_p))
        finally:
            if was_training:
                self.train()
        n = int(np.asarray(n))
        out = np.asarray(buf)[:, :n]
        return Tensor(jnp.asarray(out, jnp.int64))

    def flops_per_token(self, seq_len):
        """Model FLOPs per token (fwd+bwd 3x fwd) for MFU accounting."""
        c = self.config
        d, L = c.hidden_size, c.num_hidden_layers
        ff = c.intermediate_size
        per_layer = (
            2 * d * d * (1 + 2 * c.num_key_value_heads / c.num_attention_heads + 1)
            + 2 * 2 * d * seq_len / 2  # attention scores+values (causal half)
            + 2 * 3 * d * ff
        )
        embed = 2 * d * c.vocab_size
        return 3 * (L * per_layer + embed)
