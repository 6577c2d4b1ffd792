"""Brumby (``model_type: "brumby"``, manifestai/Brumby-14B-Base, retrained
from Qwen3-14B): a dense decoder whose every layer mixes tokens by POWER
RETENTION (arXiv:2507.04239) instead of softmax attention: attention whose
weight is the square of the score under a scalar gate a key/value head,
served in its recurrent form, so a layer keeps a fixed-size state a slot
and no K/V at all.

    block:  h = x + Ret(RMSNorm(x));  y = h + W_d(silu(W_g n) * (W_u n)),
            n = RMSNorm(h)                                   (no biases)
    Ret(u), Hq query heads on Hk key/value heads of width d, query head a
    reads key/value head a // (Hq / Hk):
      q = RoPE(RMSNorm_d(W_q u))   k = RoPE(RMSNorm_d(W_k u))   v = W_v u
      log g = logsigmoid(w_g . u)  in float32, one gate a key/value head
      o = power retention of degree 2 (``ops/kernels/power_retention.py``:
          the recurrent form, state S [Hk, D, d] and z [Hk, D] float32 a
          slot, D = d (d + 1) / 2), by ONE implementation in every step
          program and the plain forward: the Pallas kernel
          ``ops/kernels/power_retention_walk.py``, which reads and writes
          a live slot's state once a step (one-token form for a slot with
          one row, chunk form for one with more, nothing for one with
          none), interpreted on a CPU
      out = W_o concat_a o^a

``config.json`` carries Qwen3-14B's keys and none of the retention layer;
what it leaves open is written in ``benchmark/configs/brumby-14b-base-d8
.json`` under ``assumed`` and, plainly, in ``benchmark/reference/
brumby_plain.py`` (the attention form, float32).

Serving only, on :mod:`paddle_tpu.models.latent_moe`'s block, decoder and
causal LM and ONE state kind, ``cache_layout.Recurrent``: the first layout
without a paged layer (``LLMEngine`` allocates no pool for it). The
feed-forward, the norms and the rotary table are the llama family's. The
backward of the retention layer is not written (ROADMAP Queue 2)."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from ..nn import Layer, Linear, RMSNorm
from ..core.tensor import Tensor, dispatch
from ..ops.kernels import power_retention as _ret
from ..ops.kernels import power_retention_walk as _walk
from ..profiler import scope
from . import cache_layout as CL
from .latent_moe import (F32, DecoderBlock, StateCausalLM, StateDecoder,
                         SwiGLU, rms)
from .llama import apply_rope, precompute_rope


@dataclass
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = False
    #: the power of the score; 2 is the one that is written
    retention_degree: int = 2
    #: added to the normaliser ``phi(q) . z``
    retention_eps: float = 1e-6


class PowerRetention(Layer):
    def __init__(self, c: BrumbyConfig):
        super().__init__()
        h, d = c.hidden_size, c.head_dim
        self.Hq, self.Hk, self.d = c.num_attention_heads, \
            c.num_key_value_heads, d
        self.eps, self.ret_eps = c.rms_norm_eps, c.retention_eps
        lin = lambda i, o: Linear(i, o, bias_attr=False)  # noqa: E731
        self.q_proj = lin(h, self.Hq * d)
        self.k_proj = lin(h, self.Hk * d)
        self.v_proj = lin(h, self.Hk * d)
        self.o_proj = lin(self.Hq * d, h)
        #: one scalar gate a key/value head (not ``*_proj``: it is read
        #: with the norms under ``pt.gate``, not among the projections)
        self.gate = lin(h, self.Hk)
        self.q_norm, self.k_norm = RMSNorm(d, self.eps), RMSNorm(d, self.eps)

    def state_shapes(self):
        """What a slot holds: the float32 state and normaliser of every
        key/value head."""
        D = _ret.feature_dim(self.d)
        return {"S": ((self.Hk, D, self.d), np.float32),
                "z": ((self.Hk, D), np.float32)}

    def forward(self, x, cache, rope):
        Hq, Hk, d, eps, ret_eps = self.Hq, self.Hk, self.d, self.eps, \
            self.ret_eps
        rows = CL.packed(cache)
        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        with scope("pt.gate"):
            gate = self.gate(x)

        def fn(q, k, v, gate, S, z, lens, q_lens, qn, kn, cos, sin):
            lead = q.shape[:2]            # [B, S], or a mixed step's [1, T]
            S_in = S
            with scope("pt.gate"):
                log_g = jax.nn.log_sigmoid(gate.astype(F32))
                q = rms(q.reshape(lead + (Hq, d)), qn, eps)
                k = rms(k.reshape(lead + (Hk, d)), kn, eps)
            v = v.reshape(lead + (Hk, d))
            with scope("pt.rope"):
                lens = lens.astype(jnp.int32)
                pos = rows.pos[None] if rows is not None else (
                    lens[:, None]
                    + jnp.arange(lead[1], dtype=jnp.int32)[None, :])
                q, k = apply_rope(q, k, cos, sin, pos)
            if rows is None and lead[1] == 1:
                with scope("pt.core"):
                    live = q_lens > 0
                    o, S, z = _walk.retention_step(
                        q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], S, z, live,
                        lens, ret_eps)
                    o, counts = o[:, None], _ret.step_counts(live)
            else:
                with scope("pt.view"):
                    # ONE flat row axis: a mixed step's packed rows as
                    # they are; a plain [B, S] step's slots back to back
                    n = lead[0] * lead[1]
                    start = rows.start if rows is not None else \
                        jnp.arange(lead[0], dtype=jnp.int32) * lead[1]
                    flat = [a.reshape((n,) + a.shape[2:])
                            for a in (q, k, v, log_g)]
                with scope("pt.core"):
                    o, S, z = _walk.retention_walk(
                        *flat, S, z, start, q_lens, lens, ret_eps)
                    o, counts = o.reshape(lead + o.shape[1:]), \
                        _ret.walk_counts(q_lens)
            # the state leaves in the type the layout holds it in
            return o.astype(v.dtype).reshape(lead + (Hq * d,)), \
                S.astype(S_in.dtype), z.astype(S_in.dtype), counts

        st = cache.state
        cos, sin = rope
        o, S, z, counts = dispatch(
            fn, (q, k, v, gate, st["S"], st["z"], cache.seq_lens,
                 cache.q_lens, self.q_norm.weight, self.k_norm.weight,
                 cos, sin), {}, name="power_retention")
        CL.count(counts._value if isinstance(counts, Tensor) else counts)
        return self.o_proj(o), CL.RecurrentCache(
            {"S": S, "z": z}, cache.seq_lens, cache.q_lens,
            cache.row_budget, rows)


class BrumbyDecoderLayer(DecoderBlock):
    def __init__(self, c: BrumbyConfig):
        super().__init__(PowerRetention(c),
                         SwiGLU(c.hidden_size, c.intermediate_size),
                         c.hidden_size, c.rms_norm_eps)

    def forward(self, x, cache, rope):
        a, new_cache = self.self_attn(self.input_layernorm(x), cache, rope)
        x = x + a
        return x + self.mlp(self.post_attention_layernorm(x)), new_cache


class BrumbyDecoder(StateDecoder):
    """:class:`StateDecoder` with the llama family's rotary table, which
    every layer's q and k read at the rows' positions."""

    def __init__(self, c: BrumbyConfig):
        super().__init__(c, [BrumbyDecoderLayer(c)
                             for _ in range(c.num_hidden_layers)])
        cos, sin = precompute_rope(c.head_dim, c.max_position_embeddings,
                                   c.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                position_offset=0):
        if kv_caches is None:
            raise ValueError(
                f"{type(self).__name__} runs on per-layer state: call the "
                f"causal LM (it builds a one-call state) or pass kv_caches")
        rope = (self.rope_cos, self.rope_sin)
        x = self.embed_tokens(input_ids)
        new_caches = []
        for layer, cache in zip(self.layers, kv_caches):
            x, c = layer(x, cache, rope)
            new_caches.append(c)
        return self.norm(x), new_caches


class BrumbyForCausalLM(StateCausalLM):
    #: device-side counts of a step: the retention layers' own
    step_counter_names = _ret.COUNTERS
    step_emit_ids = _ret.EMIT_IDS

    def __init__(self, config: BrumbyConfig):
        c = config
        if c.retention_degree != 2:
            raise ValueError(
                f"retention_degree={c.retention_degree} is not written: "
                f"the state holds the symmetric degree-2 monomials "
                f"(ops/kernels/power_retention.py)")
        if c.num_attention_heads % c.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads={c.num_attention_heads} is not a "
                f"multiple of num_key_value_heads={c.num_key_value_heads}")
        if c.tie_word_embeddings:
            raise ValueError("tie_word_embeddings=True is not written "
                             "(the published head is untied)")
        super().__init__(c, BrumbyDecoder(c))

    def cache_layout(self):
        """One kind for every layer: a recurrent state a slot. No layer
        is paged."""
        return [CL.Recurrent(layer.self_attn.state_shapes())
                for layer in self.model.layers]
