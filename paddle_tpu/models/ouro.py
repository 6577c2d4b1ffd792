"""Ouro (``model_type: "ouro"``, arXiv 2510.25741): a looped decoder. ONE
stack of ``num_hidden_layers`` blocks runs ``total_ut_steps`` (R) times a
token with the same weights; every run of a layer keeps keys and values of
its own, and the final norm closes every run.

Layer l at loop step t, ``N1..N4`` four RMSNorms with scales of their own
(the plain float32 form is ``benchmark/reference/ouro_plain.py``):

    a = x + N2_l( Attn_l( N1_l(x) ; cache[t, l] ) )
    y = a + N4_l( down_l( silu(gate_l N3_l(a)) * up_l N3_l(a) ) )

    h = E[ids];  for t in 1..R:  h = Norm_f(Layer_{L-1}(... Layer_0(h)))
                                 g_t = sigmoid(w_g . h + b_g)
    logits = W_head h            (h after step R)

The attention, the feed-forward, the norm, the rotary table and the paged
cache object are the llama family's (``models/llama.py``), and so are the
kernels. What is this family's own:

- **State per layer APPLICATION**: ``cache_layout()`` names, a weight
  layer, :class:`~paddle_tpu.models.cache_layout.LoopedPagedKV` ("K and V,
  R times"): one K and one V pool a weight layer that hold R runs of
  blocks, the loop step part of a block's address.
- **The loop is a loop in the program**: :class:`OuroDecoder` runs the
  steps as ONE ``lax.scan`` around the L layers, the weights closed over,
  the pools in the carry and updated in place, so a step program holds
  every weight layer once and not R times.
- **The exit gate** is computed on the served path and leaves the step as
  counters (``step_counter_names``): ``loop_rows`` (live rows x R) and
  ``loop_exit_mass_t``, the exit distribution's mass at step t summed over
  live rows (``p_1 = g_1``, ``p_t = g_t prod_{s<t}(1 - g_s)``, ``p_R`` the
  remainder), counted and booked in 1/65536ths of a row
  (:data:`MASS_UNIT`; a reader divides).
  With the published ``early_exit_threshold`` of 1 the step served is
  always R; a lower threshold is a depth that differs by token inside one
  batched step and is refused by name.

Serving only: the backward through the looped stack is not written."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..nn import Layer, Linear, Embedding, RMSNorm, LayerList
from ..core.tensor import Tensor, functional_mode
from ..profiler import scope
from . import cache_layout as CL
from .cache_layout import _val
from .llama import LlamaAttention, LlamaMLP, PagedKVCache, precompute_rope

#: ``loop_exit_mass_t`` is counted in this many parts of a row
MASS_UNIT = 1 << 16


@dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    #: R: runs of the stack a token
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = False


class OuroDecoderLayer(Layer):
    """Sandwich norms: one before and one after the attention, one before
    and one after the feed-forward, the residual added after the second."""

    def __init__(self, config: OuroConfig, layer_idx=0):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.self_attn = LlamaAttention(config, layer_idx=layer_idx)
        self.mlp = LlamaMLP(config, layer_idx=layer_idx)
        self.input_layernorm = RMSNorm(h, eps)
        self.input_layernorm_2 = RMSNorm(h, eps)
        self.post_attention_layernorm = RMSNorm(h, eps)
        self.post_attention_layernorm_2 = RMSNorm(h, eps)

    def forward(self, x, rope_cache, kv_cache=None, position_offset=0):
        attn = self.self_attn(self.input_layernorm(x), rope_cache, None,
                              kv_cache, position_offset)
        new_cache = None
        if kv_cache is not None:
            attn, new_cache = attn
        x = x + self.input_layernorm_2(attn)
        x = x + self.post_attention_layernorm_2(
            self.mlp(self.post_attention_layernorm(x)))
        return x, new_cache


class OuroDecoder(Layer):
    """Embedding, then R runs of (the L layers, the final norm, the exit
    gate) as one ``lax.scan`` over the loop step. ``kv_caches``: one
    :class:`~paddle_tpu.models.llama.PagedKVCache` a WEIGHT layer, as
    :class:`~paddle_tpu.models.cache_layout.LoopedPagedKV` makes it; None
    runs the plain causal forward (every step attends its own rows)."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        c = self.config = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size)
        self.layers = LayerList([OuroDecoderLayer(c, layer_idx=i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.early_exit_gate = Linear(c.hidden_size, 1)
        cos, sin = precompute_rope(c.head_dim, c.max_position_embeddings,
                                   c.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        self.kind = CL.LoopedPagedKV(c.num_key_value_heads, c.head_dim,
                                     c.total_ut_steps)

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                position_offset=0):
        steps = self.config.total_ut_steps
        rope = (self.rope_cos._value, self.rope_sin._value)
        x = self.embed_tokens(input_ids)._value
        cached = kv_caches is not None
        pools = (tuple(_val(c.k) for c in kv_caches),
                 tuple(_val(c.v) for c in kv_caches)) if cached else ((), ())

        def step(carry, t):
            x, (ks, vs) = carry
            x, ks, vs = Tensor(x), list(ks), list(vs)
            with functional_mode():
                for i, layer in enumerate(self.layers):
                    cache = self.kind.at_step(kv_caches[i], t, ks[i],
                                              vs[i]) if cached else None
                    x, new = layer(x, rope, cache, position_offset)
                    if cached:
                        ks[i], vs[i] = _val(new.k), _val(new.v)
                x = self.norm(x)
                with scope("pt.exit"):
                    gate = jax.nn.sigmoid(
                        self.early_exit_gate(x)._value[..., 0]
                        .astype(jnp.float32))
            return (x._value, (tuple(ks), tuple(vs))), gate

        (x, (ks, vs)), gates = jax.lax.scan(
            step, (x, pools), jnp.arange(steps, dtype=jnp.int32))
        first = kv_caches[0] if cached else None
        with scope("pt.exit"):
            CL.count(_exit_counts(gates, _live_rows(first,
                                                    gates.shape[1:])))
        if not cached:
            return Tensor(x)
        return Tensor(x), [
            PagedKVCache(k, v, c.block_tables, c.seq_lens, c.q_lens,
                         rows=c.rows)
            for c, k, v in zip(kv_caches, ks, vs)]


def _live_rows(cache, shape):
    """Which rows of the step's ``shape`` hold a token: a mixed step's
    packed axis ``[1, T]`` by its RowMap, a one-token step's ``[B, 1]`` by
    the slots that are active, every row of a plain forward."""
    if cache is None:
        return jnp.ones(shape, bool)
    if cache.rows is not None:
        return cache.rows.live.reshape(shape)
    return (_val(cache.q_lens) > 0).reshape(shape)


def _exit_counts(gates, live):
    """``[loop_rows, loop_exit_mass_1 .. R]`` (int32) of one dispatch from
    the steps' gates ``[R, ...]``: a row's masses are floored to
    1/:data:`MASS_UNIT` and the last step takes the remainder, so they add
    up to :data:`MASS_UNIT` a live row exactly."""
    steps = gates.shape[0]
    stay, parts = jnp.ones_like(gates[0]), []
    for t in range(steps - 1):
        parts.append(jnp.floor(gates[t] * stay * MASS_UNIT)
                     .astype(jnp.int32))
        stay = stay * (1 - gates[t])
    parts.append(MASS_UNIT - sum(parts, jnp.zeros_like(live, jnp.int32)))
    n_live = jnp.sum(live, dtype=jnp.int32)
    return jnp.stack([n_live * steps] + [
        jnp.sum(jnp.where(live, p, 0), dtype=jnp.int32) for p in parts])


class OuroForCausalLM(Layer):
    """The looped decoder under an untied head, with the serving engine's
    seam (``models/cache_layout.py``)."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        c = self.config = config
        if c.early_exit_threshold < 1:
            raise ValueError(
                f"early_exit_threshold={c.early_exit_threshold} < 1 is an "
                f"adaptive exit: a depth that differs by token inside one "
                f"batched step, which the schedulers do not dispatch (they "
                f"assume one depth a dispatch); the program serves step "
                f"total_ut_steps={c.total_ut_steps} for every token")
        if c.head_dim * c.num_attention_heads != c.hidden_size:
            raise ValueError(
                f"head_dim={c.head_dim} x num_attention_heads="
                f"{c.num_attention_heads} is not hidden_size="
                f"{c.hidden_size}: the attention derives the one from the "
                f"others")
        if c.tie_word_embeddings:
            raise ValueError("tie_word_embeddings=True is not written for "
                             "the looped decoder (the published head is "
                             "untied)")
        self.model = OuroDecoder(c)
        self.lm_head = Linear(c.hidden_size, c.vocab_size, bias_attr=False)
        #: device-side counts of a step (``cache_layout.count``), booked
        #: into ``engine.stats`` under these names
        self.step_counter_names = ("loop_rows",) + tuple(
            f"loop_exit_mass_{t + 1}" for t in range(c.total_ut_steps))

    @property
    def decoder(self):
        return self.model

    def cache_layout(self):
        """One kind a WEIGHT layer: K and V, ``total_ut_steps`` times."""
        return [self.model.kind] * self.config.num_hidden_layers

    def _logits(self, hidden):
        return self.lm_head(hidden)

    def forward(self, input_ids, labels=None, attn_mask=None):
        if labels is not None:
            raise NotImplementedError(
                "training the looped decoder needs the backward pass "
                "through the looped stack under TrainStep with recompute, "
                "which is not written (ROADMAP Queue 2)")
        return self._logits(self.model(input_ids))
