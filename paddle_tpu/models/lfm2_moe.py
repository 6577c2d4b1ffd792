"""LFM2-MoE (``model_type: "lfm2_moe"``, LiquidAI/LFM2-24B-A2B): a decoder
that interleaves GATED SHORT CONVOLUTIONS three to one with softmax
attention over grouped K and V whose queries and keys are normed a head and
rotated, over a dense SwiGLU in the leading ``num_dense_layers`` and sparse
experts WITHOUT a shared expert in the rest, under a head tied to the
embedding. No bias anywhere.

    block l:  h = x + Op_l(RMSNorm(x));   y = h + FF_l(RMSNorm(h))
    Op_l = GQA if ``layer_types[l] == "full_attention"`` else ShortConv

ShortConv: ``[B, C, z] = W_in u`` (hidden -> 3 x hidden, the thirds in
that order); ``s_t = B_t * z_t``; ``c_t = sum_j w[j] * s_{t-(L-1)+j}``, a
depthwise causal convolution of ``L = conv_L_cache`` taps with zeros before
a sequence's first row and NO activation; ``out_t = W_out (C_t * c_t)``.
What a slot keeps between steps is the last ``L - 1`` rows of ``s`` and
nothing else: ``cache_layout.Recurrent({"conv": [L - 1, hidden]})``, the
first recurrent kind whose whole state is a convolution's tail. ``s`` is
rounded to the model's dtype before the taps, so a row reads the same
``s_{t-1}`` from the tail as from its own chunk wherever a chunk ends.

GQA, Hq query heads on Hk key/value heads of ``head_dim`` d: ``q, k, v = W
u``; ``q, k <- RMSNorm`` over each head's d values (one learned ``[d]``
scale each); ``q, k <-`` rotary over all d values, rotate-half pairing,
``theta = rope_theta``, at the row's position (``RowMap.pos`` in a mixed
step, ``seq_lens + i`` else), all in float32; causal ``softmax(q . k /
sqrt(d)) v`` over the paged K and V pools by the attention op's three forms
(packed append, per-slot append, one-token), as
:class:`~paddle_tpu.models.solar_open2.GatedAttention` calls them; the pool
holds the ROTATED keys, so the kernels know no positions.

**A head of 64 on a pool of 128 lanes.** A pool ``[NB, 8, 64, 64]`` has a
minor axis of half a lane tile: the chip's compiler then lays it out with
the BLOCK axis minor, and every call of the paged kernels (which need the
row-major tiles) copies the whole pool into 128 padded lanes and back (1.08
GB of temporaries a call at this model's pool, compiled here for the v5e;
PERF.md section 6). So the layer packs ``pack = 2`` K/V heads side by side
in a pool row (:func:`lane_pack`): the pools are ``PagedKV(4, 128)``, 4 KiB
a token as published with no lane empty, a token's new K and V ``[8, 64]``
go in as the ``[4, 128]`` they already are in memory, and query head ``a``
is handed to the kernel as 128 values that are ZERO outside the half its
K/V head ``a // 4`` lies in (times ``sqrt(2)``, since the kernel scales by
``128^-1/2``): its scores against a pool row are then exactly ``q_a . k_(a
// 4) / 8``, and of the 128 values that come back the same half is its
output. The kernels' callers and every other family's programs are what
they were; the MXU multiplies by zeros in half its lanes, which a
memory-bound call does not feel. The one-token call is traced under
``paged_attention.decode_heads_a_step``: all four packed heads of a table
entry, and their 32 query rows, in ONE grid step (the stacked-heads form
PR 34 wrote for a group of one, at a group of eight), because at 128 slots
the one-head-a-step call is 16,384 grid steps whose fixed cost is all of
its time; the shapes alone cannot tell this call from the grouped calls of
the cells at a head size of 128, so the layer says it.

Experts (``latent_moe.SparseMoE`` with a shared width of 0): ``p =
sigmoid(W_r h)`` in float32 over all the published experts; the
``num_experts_per_tok`` largest of ``p + expert_bias`` (the bias selects,
it does not weigh); ``g_e = p_e / (sum_sel p + 1e-6) *
routed_scaling_factor``; ``out = sum_sel g_e SwiGLU_e(h)``. This model holds
experts ``[expert_offset, expert_offset + num_experts)`` of
``num_experts_published``.

Serving only, on :mod:`paddle_tpu.models.latent_moe`'s block, decoder and
causal LM. The plain float32 form is
``benchmark/reference/lfm2_moe_plain.py``; what ``config.json`` leaves open
is listed under ``assumed`` in ``benchmark/configs/lfm2-24b-a2b-pp4-d10
.json``. The backward of ShortConv is not written (ROADMAP Queue 2)."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax.numpy as jnp

from .. import ops
from ..nn import Layer, Linear, RMSNorm
from ..nn.initializer import Normal
from ..core.tensor import dispatch
from ..ops.kernels import kda as _kda
from ..ops.kernels import paged_attention as _paged
from ..profiler import scope
from . import cache_layout as CL
from .latent_moe import (F32, DecoderBlock, SparseMoE, StateCausalLM,
                         StateDecoder, SwiGLU, live_rows, mm, rms)
from .llama import PagedKVCache

#: what the two mixers count a step, after the experts' counters: live
#: rows through a conv layer, the slots' tails a conv call read that held
#: a live row and all it read; live rows through an attention layer, the
#: sum over them of the context each attends, itself included (what its
#: scores cost), and the sum over the slots with a live row of the context
#: the slot's last row attends (what a kernel has to read at least: a
#: slot's rows share its keys). Every one summed over the layers of its kind
COUNTERS = ("conv_rows", "conv_tails_live", "conv_tails_walked", "kv_rows",
            "kv_ctx_tokens", "kv_slot_tokens")
#: id on a step's ``pt:engine.emit`` span -> the counters it sums
EMIT_IDS = {"conv_rows": ("conv_rows",), "conv_tails": ("conv_tails_live",),
            "kv_rows": ("kv_rows",), "kv_ctx_tokens": ("kv_ctx_tokens",),
            "kv_slot_tokens": ("kv_slot_tokens",)}
#: the renormalised weights' epsilon, as the family's public code has it
RENORM_EPS = 1e-6


def _at(name):
    """Where a layer's run of :data:`COUNTERS` starts among the model's
    ``step_counter_names``."""
    return len(StateCausalLM.step_counter_names) + COUNTERS.index(name)


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    num_hidden_layers: int = 40
    #: "conv" or "full_attention" a layer, at least ``num_hidden_layers``
    layer_types: tuple = tuple(
        "full_attention" if i % 4 == 2 else "conv" for i in range(40))
    conv_L_cache: int = 3
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1000000.0
    #: experts, in the layers from ``num_dense_layers`` on
    num_dense_layers: int = 2
    moe_intermediate_size: int = 1536
    num_experts: int = 64                # held here
    num_experts_published: int = 64      # the router's width
    expert_offset: int = 0
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True

    @property
    def rms_norm_eps(self):
        """The name ``latent_moe.StateDecoder`` reads the final norm's
        epsilon under."""
        return self.norm_eps


def _linear(i, o):
    return Linear(i, o, bias_attr=False)


class Lfm2ShortConv(Layer):
    """The gated short convolution. ``x`` is ``[B, S, hidden]``, a mixed
    step's packed ``[1, T, hidden]`` (a slot's rows read its own tail and
    never a neighbour's rows: ``kda.causal_conv_packed``) or one row a slot
    of a decode scan. A slot at position 0 starts from a zero tail, so
    assigning or replaying a slot resets it in the graph."""

    def __init__(self, hidden, taps):
        super().__init__()
        self.hidden, self.taps = hidden, int(taps)
        self.in_proj = _linear(hidden, 3 * hidden)
        self.conv = self.create_parameter(
            (self.taps, hidden), default_initializer=Normal(0.0, 0.02))
        self.out_proj = _linear(hidden, hidden)

    def state_shapes(self, dtype):
        """What a slot holds: the last ``taps - 1`` rows of ``B * z``."""
        return {"conv": ((self.taps - 1, self.hidden), dtype)}

    def forward(self, x, cache):
        h = self.hidden
        rows = CL.packed(cache)

        def fn(x, tail, lens, q_lens, win, cw, wout):
            lead = x.shape[:2]
            with scope("in_proj"):
                bcz = mm(x, win)
            with scope("pt.conv"):
                fresh = lens.astype(jnp.int32) == 0
                tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail),
                                 tail)
                s = (bcz[..., :h].astype(F32) * bcz[..., 2 * h:].astype(F32)
                     ).astype(x.dtype)
                if rows is not None:
                    c, tail = _kda.causal_conv_packed(s[0], tail, cw, rows)
                    c, live = c[None], rows.live
                else:
                    c, tail = _kda.causal_conv(s, tail, cw, q_lens)
                    live = live_rows(q_lens, lead[1])
                y = (bcz[..., h:2 * h].astype(F32) * c).astype(x.dtype)
                held = q_lens.astype(jnp.int32) > 0
                counts = jnp.stack([
                    jnp.sum(live), jnp.sum(held),
                    jnp.int32(held.shape[0])]).astype(jnp.int32)
            with scope("out_proj"):
                return mm(y, wout), tail, counts

        out, tail, counts = dispatch(
            fn, (x, cache.state["conv"], cache.seq_lens, cache.q_lens,
                 self.in_proj.weight, self.conv, self.out_proj.weight), {},
            name="lfm2_short_conv")
        CL.count(counts._value, at=_at("conv_rows"))
        return out, CL.RecurrentCache({"conv": tail}, cache.seq_lens,
                                      cache.q_lens, cache.row_budget, rows)


#: values a vector register's row holds: what a pool's minor axis wants
LANES = 128


def lane_pack(kv_heads, head_dim):
    """How many K/V heads share a pool row so that its minor axis fills
    the chip's :data:`LANES`: the most that fit and divide ``kv_heads``
    (1: a head of 128 or more, the pool as every other family has it)."""
    return max(p for p in range(1, max(LANES // head_dim, 1) + 1)
               if kv_heads % p == 0)


def rotate_half(x, pos, inv_freq):
    """Rotary positions in the rotate-half pairing: value ``i`` of the
    first half turns with value ``i`` of the second by ``pos *
    inv_freq[i]``. ``x``: float32 ``[..., heads, d]``; ``pos``: the
    leading axes' positions."""
    angle = pos.astype(F32)[..., None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class Lfm2Attention(Layer):
    """Softmax attention of ``heads`` query heads over ``kv_heads`` K/V
    heads of ``head_dim``, q and k normed a head and rotated before the
    paged attention op. ``x`` as :class:`Lfm2ShortConv` takes it."""

    def __init__(self, hidden, heads, kv_heads, head_dim, eps, theta):
        super().__init__()
        self.H, self.Hkv, self.D, self.eps = heads, kv_heads, head_dim, eps
        #: K/V heads a pool row (module docstring), and which of a row's
        #: ``pack`` parts query head ``a`` reads: ``[heads, pack]`` one-hot
        self.pack = lane_pack(kv_heads, head_dim)
        part = (np.arange(heads) // (heads // kv_heads)) % self.pack
        self.part = part[:, None] == np.arange(self.pack)[None, :]
        self.inv_freq = (float(theta) ** (
            -np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
        ).astype(np.float32)
        self.q_proj = _linear(hidden, heads * head_dim)
        self.k_proj = _linear(hidden, kv_heads * head_dim)
        self.v_proj = _linear(hidden, kv_heads * head_dim)
        self.q_layernorm = RMSNorm(head_dim, eps)
        self.k_layernorm = RMSNorm(head_dim, eps)
        self.o_proj = _linear(heads * head_dim, hidden)

    def kind(self):
        """The layer's state kind: K and V pools whose rows hold ``pack``
        heads."""
        return CL.PagedKV(self.Hkv // self.pack, self.D * self.pack,
                          q_heads=self.H)

    def project(self, x, cache):
        """``[q; k; v]`` a row as the attention op takes them, ``[*lead,
        Hq pack d + 2 Hkv d]``: q and k normed a head and rotated at the
        row's position, q a head in the pool row's ``pack d`` lanes, zero
        outside its K/V head's part and times ``sqrt(pack)``; and the
        layer's counts of the step."""
        H, Hkv, D, eps, inv_freq = self.H, self.Hkv, self.D, self.eps, \
            self.inv_freq
        pack, part = self.pack, self.part
        rows = CL.packed(cache)

        def fn(x, lens, q_lens, wq, wk, wv, qn, kn):
            lead = x.shape[:2]
            with scope("qkv_proj"):
                q = mm(x, wq).reshape(lead + (H, D))
                k = mm(x, wk).reshape(lead + (Hkv, D))
                v = mm(x, wv)
            with scope("pt.qk_norm"):
                q, k = rms(q, qn, eps), rms(k, kn, eps)
            with scope("pt.rope"):
                if rows is not None:
                    pos, live = rows.pos[None], rows.live[None]
                else:
                    pos = lens.astype(jnp.int32)[:, None] + jnp.arange(
                        lead[1], dtype=jnp.int32)[None, :]
                    live = live_rows(q_lens, lead[1])
                q = rotate_half(q, pos, inv_freq)
                k = rotate_half(k, pos, inv_freq).astype(x.dtype)
            with scope("pt.view"):
                if pack > 1:
                    q = jnp.where(part[:, :, None], (
                        q * jnp.float32(pack ** 0.5))[..., None, :], 0.0)
                q = q.astype(x.dtype)
                qkv = jnp.concatenate(
                    [q.reshape(lead + (H * pack * D,)),
                     k.reshape(lead + (Hkv * D,)), v], -1)
                L, Q = lens.astype(jnp.int32), q_lens.astype(jnp.int32)
                counts = jnp.stack([
                    jnp.sum(live), jnp.sum(jnp.where(live, pos + 1, 0)),
                    jnp.sum(jnp.where(Q > 0, L + Q, 0))]).astype(jnp.int32)
            return qkv, counts

        return dispatch(
            fn, (x, cache.seq_lens, cache.q_lens, self.q_proj.weight,
                 self.k_proj.weight, self.v_proj.weight,
                 self.q_layernorm.weight, self.k_layernorm.weight), {},
            name="lfm2_qkv")

    def forward(self, x, cache):
        from ..incubate.nn import functional as IF
        H, D = self.H, self.D
        rows = CL.packed(cache)
        qkv, counts = self.project(x, cache)
        CL.count(counts._value, at=_at("kv_rows"))
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
            # every (packed) K/V head of a table entry in one grid step:
            # at 128 slots the one-head call's grid is all of its time
            with scope("pt.core"), _paged.decode_heads_a_step(
                    self.Hkv // self.pack):
                o, kc, vc = IF.block_multihead_attention(
                    qkv, cache.k, cache.v, None, cache.seq_lens, None,
                    block_tables=cache.block_tables)
        with scope("pt.view"):
            if self.pack > 1:
                # of a head's pack x d values, the part its K/V head lies in
                o = dispatch(lambda o: jnp.sum(jnp.where(
                    self.part[:, :, None],
                    o.reshape((b, s, H, self.pack, D)), 0), -2), (o,), {},
                    name="lfm2_head_part")
            o = ops.reshape(o, [b, s, H * D])
        return self.o_proj(o), PagedKVCache(
            kc, vc, cache.block_tables, cache.seq_lens, cache.q_lens,
            rows=rows, row_budget=cache.row_budget)


class Lfm2MoeDecoderLayer(DecoderBlock):
    def __init__(self, c: Lfm2MoeConfig, layer_idx):
        kind = c.layer_types[layer_idx]
        attn = Lfm2Attention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.norm_eps, c.rope_theta) \
            if kind == "full_attention" \
            else Lfm2ShortConv(c.hidden_size, c.conv_L_cache)
        mlp = SwiGLU(c.hidden_size, c.intermediate_size) \
            if layer_idx < c.num_dense_layers else SparseMoE(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_published, c.expert_offset,
                c.num_experts_per_tok, c.routed_scaling_factor, 0,
                renormalize=c.norm_topk_prob, renorm_eps=RENORM_EPS)
        super().__init__(attn, mlp, c.hidden_size, c.norm_eps)
        self.kind = kind


class Lfm2MoeForCausalLM(StateCausalLM):
    #: the experts' counts, then the two mixers'
    step_counter_names = StateCausalLM.step_counter_names + COUNTERS
    step_emit_ids = {**StateCausalLM.step_emit_ids, **EMIT_IDS}

    def __init__(self, config: Lfm2MoeConfig):
        if len(config.layer_types) < config.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(config.layer_types)} layers, the "
                f"depth is {config.num_hidden_layers}")
        super().__init__(config, StateDecoder(config, [
            Lfm2MoeDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)]),
            tied=config.tie_word_embeddings)

    def cache_layout(self):
        """One state kind a layer, in ``layer_types``' order: K and V
        pools for an attention layer (two heads a pool row at a head size
        of 64), the convolution's tail for a conv layer."""
        dt = np.dtype(self.model.embed_tokens.weight.dtype)
        return [layer.self_attn.kind() if layer.kind == "full_attention"
                else CL.Recurrent(layer.self_attn.state_shapes(dt))
                for layer in self.model.layers]
