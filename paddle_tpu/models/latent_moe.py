"""The layers that the latent-attention, sparse-expert decoder families
share (``models/kimi_linear.py``, ``models/deepseek_v2.py``,
``models/dots3_note.py``): multi-head
latent attention served in its absorbed form over a paged latent pool, a
SwiGLU feed-forward, the held share of a layer's routed experts with its
router and shared expert, the pre-norm block around them, and the decoder
and causal LM that run such blocks on per-layer state
(``models/cache_layout.py``). What a family does differently is a
constructor argument resolved in Python, so each family traces only the
operations of its own equations; nothing here asks which family it serves.

Latent attention, H heads: ``q_h = [q_nope_h; q_pe_h]`` from ``W_q x``, or
through a compressed query ``W_qb RMSNorm(W_qa x)`` (``q_rank``); ``[c;
k_pe] = W_kva x``, ``c <- RMSNorm(c)``; with ``rotary``, ``q_pe_h`` and
the one shared ``k_pe`` are rotated by the row's position in float32; a
token's cache entry is ``(c, k_pe)``, ROTATED, so the pool is all the
attention ever reads and the kernel knows no positions; ``[k_nope_h; v_h]
= W_kvb,h c``; causal softmax of ``q_h . [k_nope_h; k_pe] * scale``.
Absorbed: ``q_nope`` goes through ``W_kvb``'s key half into the latent's
width, the attention runs against the pool with the latent itself as
values (``ops/kernels/latent_attention.py``), ``W_kvb``'s value half
after. A row's position is ``RowMap.pos`` in a mixed step's packed form
and ``seq_lens + i`` in the per-slot forms (the one-token step is S = 1);
the pool's write and the kernel take the packed rows as they are, so a
mixed step's latent layer never builds the per-slot view. What one family
adds (latents rescaled after their norms, a gate a head, a window over a
ring a slot, a learned indexer that selects the positions a row attends:
:class:`Indexer`, ``ops/kernels/sparse_latent_attention.py``) is a
constructor argument of :class:`LatentAttention` too, and traces nothing
where it is not asked for.

Experts: ``ops/kernels/moe_dropless.py``; a layer holds experts
``[offset, offset + held)`` of ``published`` and routes over all of them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import ops
from ..nn import Layer, LayerNorm, Linear, Embedding, RMSNorm, LayerList
from ..nn.initializer import Constant, Normal
from ..core.tensor import dispatch
from ..ops.kernels import latent_attention as _lat
from ..ops.kernels import moe_dropless as _moe
from ..ops.kernels import sparse_latent_attention as _dsa
from ..profiler import scope
from . import cache_layout as CL

F32 = jnp.float32


def mm(x, w):
    """bf16 (or whatever the weights are) in, float32 accumulate, cast
    back: the MXU's native product."""
    return jnp.matmul(x, w, preferred_element_type=F32).astype(x.dtype)


def mm32(x, w):
    return jnp.matmul(x, w, preferred_element_type=F32)


def rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(F32)


def live_rows(q_lens, s):
    return jnp.arange(s, dtype=jnp.int32)[None, :] < \
        q_lens.astype(jnp.int32)[:, None]


def _linear(i, o):
    return Linear(i, o, bias_attr=False)


class Indexer(Layer):
    """A learned indexer's weights (``ops/kernels/
    sparse_latent_attention.py`` has its equations): ``heads`` index
    queries of ``dim`` from the layer's compressed query ``c_q`` (``wq_b``),
    one index key a token from the layer's input (``wk``, then a LayerNorm
    with scale and bias), a weight a head from the input
    (``weights_proj``); a row attends the ``top_k`` positions it scores
    highest. The first ``pe`` values of queries and keys are rotated by
    the layer's ``rotary``."""

    def __init__(self, hidden, q_rank, heads, dim, top_k, norm_eps=1e-6):
        super().__init__()
        self.heads, self.dim, self.top_k = heads, dim, int(top_k)
        self.norm_eps = norm_eps
        self.wq_b = _linear(q_rank, heads * dim)
        self.wk = _linear(hidden, dim)
        self.k_norm = LayerNorm(dim, norm_eps)
        self.weights_proj = _linear(hidden, heads)

    def leaves(self):
        return (self.wq_b.weight, self.wk.weight, self.k_norm.weight,
                self.k_norm.bias, self.weights_proj.weight)


def _layer_norm(x, w, b, eps):
    x = x.astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


class LatentAttention(Layer):
    """``q_rank``: the compressed query's width (None: one projection).
    ``rotary(x, pos)``: rotates the last axis of float32 ``x`` by the
    positions ``pos`` of its leading axes (None: nothing is rotated).
    ``softmax_scale``: None is ``(nope + pe)^-1/2``. ``rescale`` ``(a_q,
    a_kv)``: constants the compressed query and the latent are multiplied
    by after their norms (None: neither). ``head_gate``: ``g_proj``, a
    sigmoid gate a HEAD from the layer's input on the heads' outputs
    before ``o_proj``. ``window``: a row attends itself and the ``window -
    1`` positions before it (the cache is a
    ``cache_layout.WindowedLatent``'s ring). ``indexer`` (an
    :class:`Indexer`; needs ``q_rank``): a row attends the positions the
    indexer selects, and the cache carries an index pool
    (``cache_layout.IndexedLatent``); a table that cannot hold more than
    ``top_k`` positions is attended whole, which is then the same set.
    None of the four traces anything when it is not asked for."""

    def __init__(self, hidden, heads, kv_rank, nope, pe, v_dim, eps,
                 q_rank=None, rotary=None, softmax_scale=None, rescale=None,
                 head_gate=False, window=None, indexer=None):
        super().__init__()
        self.H, self.r, self.dn, self.dp, self.dv = heads, kv_rank, nope, \
            pe, v_dim
        self.eps, self.rotary = eps, rotary
        self.rescale = None if rescale is None else tuple(
            float(a) for a in rescale)
        self.window = None if window is None else int(window)
        if indexer is not None and (q_rank is None or window is not None):
            raise ValueError("an indexer reads the compressed query "
                             "(q_rank) and selects in a whole context (no "
                             "window)")
        self.scale = float(softmax_scale if softmax_scale is not None
                           else (nope + pe) ** -0.5)
        if q_rank is None:
            self.q_proj = _linear(hidden, heads * (nope + pe))
        else:
            self.q_a_proj = _linear(hidden, q_rank)
            self.q_a_layernorm = RMSNorm(q_rank, eps)
            self.q_b_proj = _linear(q_rank, heads * (nope + pe))
        self.kv_a_proj = _linear(hidden, kv_rank + pe)
        self.kv_a_layernorm = RMSNorm(kv_rank, eps)
        self.kv_b_proj = _linear(kv_rank, heads * (nope + v_dim))
        self.o_proj = _linear(heads * v_dim, hidden)
        if head_gate:
            self.g_proj = _linear(hidden, heads)
        if indexer is not None:
            self.indexer = indexer

    @property
    def width(self):
        """Values a token costs in the pool: the latent and the shared
        key part."""
        return self.r + self.dp

    def _query_leaves(self):
        if hasattr(self, "q_proj"):
            return (self.q_proj.weight,)
        return (self.q_a_proj.weight, self.q_a_layernorm.weight,
                self.q_b_proj.weight)

    def forward(self, x, cache):
        H, r, dn, dp, dv, eps = self.H, self.r, self.dn, self.dp, self.dv, \
            self.eps
        rotary, scale, rescale = self.rotary, self.scale, self.rescale
        rows = CL.packed(cache)
        window = self.window
        ix = getattr(self, "indexer", None)
        if (window is None) != (cache.window is None) or \
                (window is not None and window != cache.window):
            raise ValueError(f"a layer of window {window} was handed a cache "
                             f"of window {cache.window}")
        if (ix is None) != (cache.index_pool is None):
            raise ValueError("an indexed layer needs a cache with an index "
                             "pool, and no other layer takes one")

        def fn(x, pool, ipool, tables, lens, q_lens, base, wq, wkva, nw,
               wkvb, wo, wgate, wix):
            # everything on x's own rows: [B, S], or a mixed step's
            # packed [1, T], which no part of the layer leaves
            lead = x.shape[:2]
            with scope("q_proj"):
                if len(wq) == 1:
                    q = mm(x, wq[0])
                else:
                    cq = rms(mm(x, wq[0]), wq[1], eps)
                    if rescale is not None:
                        cq = cq * jnp.float32(rescale[0])
                    cq = cq.astype(x.dtype)
                    q = mm(cq, wq[2])
                q = q.reshape(lead + (H, dn + dp))
            with scope("kv_a_proj"):
                kv = mm(x, wkva)
                c = rms(kv[..., :r], nw, eps)
                if rescale is not None:
                    c = c * jnp.float32(rescale[1])
                c = c.astype(x.dtype)
                k_pe = kv[..., r:]
            if rotary is not None:
                with scope("pt.rope"):
                    pos = rows.pos[None] if rows is not None else (
                        lens.astype(jnp.int32)[:, None]
                        + jnp.arange(lead[1], dtype=jnp.int32)[None, :])
                    k_pe = rotary(k_pe, pos).astype(x.dtype)
            with scope("pt.view"):
                entry = jnp.concatenate([c, k_pe], -1)
            wkvb = wkvb.reshape(r, H, dn + dv)
            with scope("kv_b_proj"):
                # absorbed: q_nope through the key half into the latent's
                # width
                q_abs = jnp.einsum("bshn,chn->bshc", q[..., :dn],
                                   wkvb[..., :dn],
                                   preferred_element_type=F32)
            q_pe = q[..., dn:].astype(F32)
            if rotary is not None:
                with scope("pt.rope"):
                    q_pe = rotary(q_pe, pos)
            # a windowed layer's derived table starts at ``base``: the
            # pool's write and the kernel see positions less it
            lens_k, rows_k = lens, rows
            if base is not None:
                lens_k = lens.astype(jnp.int32) - base
                rows_k = None if rows is None else rows.shifted(base)
            with scope("pt.view"):
                qc = (jnp.concatenate([q_abs, q_pe], -1) *
                      jnp.float32(scale)).astype(x.dtype)
                if rows is not None:
                    # the packed rows as they are: the pool's write and
                    # the kernel read the row map themselves
                    entry, qc = entry[0], qc[0]
                pool = _lat.latent_pool_write(pool, entry, tables, lens_k,
                                              q_lens, rows_k)
            capacity = tables.shape[1] * pool.shape[1]
            if window is not None:
                granted = rows.width if rows is not None else lead[1]
                ring = (pool.shape[0] - 1) // tables.shape[0] * pool.shape[1]
                if window - 1 + granted > ring:
                    raise ValueError(
                        f"a ring of {ring} rows a slot cannot hold a window "
                        f"of {window} behind {granted} new rows a step: it "
                        f"needs {window - 1 + granted} "
                        f"(cache_layout.WindowedLatent)")
            if ix is not None:
                with scope("pt.index"):
                    wqi, wki, knw, knb, ww = wix
                    J, di = ix.heads, ix.dim
                    with scope("index_proj"):
                        qi = mm32(cq, wqi).reshape(lead + (J, di))
                        ki = mm32(x, wki)
                        wt = mm32(x, ww) * jnp.float32((J * di) ** -0.5)
                    ki = _layer_norm(ki, knw, knb, ix.norm_eps)
                    if rotary is not None:
                        with scope("pt.rope"):
                            qi = jnp.concatenate(
                                [rotary(qi[..., :dp], pos), qi[..., dp:]], -1)
                            ki = jnp.concatenate(
                                [rotary(ki[..., :dp], pos), ki[..., dp:]], -1)
                    qi, ki = qi.astype(x.dtype), ki.astype(x.dtype)
                    if rows is not None:
                        qi, ki, wt = qi[0], ki[0], wt[0]
                    else:
                        qi = qi.reshape((-1, J, di))
                        wt = wt.reshape((-1, J))
                    ipool = _lat.latent_pool_write(ipool, ki, tables, lens,
                                                   q_lens, rows)
            counts = None
            if ix is not None or window is not None:
                geo = _dsa.Rows(lead, lens, q_lens, rows)
                counts = _dsa.counts(
                    geo, None if ix is None else ix.top_k, window)
            if ix is not None and capacity > ix.top_k:
                with scope("pt.index"):
                    scores = _dsa.index_scores(qi, wt, ipool, tables, geo)
                with scope("pt.select"):
                    idx, ok = _dsa.select(scores, geo, ix.top_k)
                with scope("pt.core"), scope("pt.sparse"):
                    o = _dsa.sparse_attend(
                        qc.reshape((-1, H, r + dp)), pool, tables, geo, idx,
                        ok, r).reshape(qc.shape[:-1] + (r,))
            else:
                with scope("pt.core"):
                    o = _lat.latent_attention_append(
                        qc, pool, tables, lens_k, q_lens, r, rows_k,
                        window=window)
            if rows is not None:
                o = o[None]
            with scope("kv_b_proj"):
                o = jnp.einsum("bshc,chv->bshv", o, wkvb[..., dn:],
                               preferred_element_type=F32).astype(x.dtype)
            if wgate is not None:
                with scope("g_proj"):
                    g = mm32(x, wgate)
                with scope("pt.gate"):
                    o = (o.astype(F32) * jax.nn.sigmoid(g)[..., None]
                         ).astype(x.dtype)
            with scope("o_proj"):
                return mm(o.reshape(lead + (H * dv,)), wo), pool, ipool, \
                    counts

        gate = getattr(self, "g_proj", None)
        out, pool, ipool, counts = dispatch(
            fn, (x, cache.pool, cache.index_pool, cache.block_tables,
                 cache.seq_lens, cache.q_lens, cache.base,
                 self._query_leaves(), self.kv_a_proj.weight,
                 self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                 self.o_proj.weight, None if gate is None else gate.weight,
                 None if ix is None else ix.leaves()), {},
            name="latent_attention")
        if counts is not None:
            CL.count(CL._val(counts),
                     at=len(StateCausalLM.step_counter_names))
        return out, cache.with_pools(pool, ipool)


def swiglu(x, wg, wu, wd):
    h = (jax.nn.silu(mm32(x, wg)) * mm32(x, wu)).astype(x.dtype)
    return mm(h, wd)


class SwiGLU(Layer):
    def __init__(self, hidden, width):
        super().__init__()
        self.gate_proj = _linear(hidden, width)
        self.up_proj = _linear(hidden, width)
        self.down_proj = _linear(width, hidden)

    def forward(self, x, cache=None):
        return dispatch(swiglu, (x, self.gate_proj.weight,
                                  self.up_proj.weight,
                                  self.down_proj.weight), {}, name="swiglu")


class Experts(Layer):
    """The held experts' weights, stacked: one leaf a projection."""

    def __init__(self, held, hidden, width):
        super().__init__()
        init = Normal(0.0, 0.02)
        self.gate_proj = self.create_parameter((held, hidden, width),
                                               default_initializer=init)
        self.up_proj = self.create_parameter((held, hidden, width),
                                             default_initializer=init)
        self.down_proj = self.create_parameter((held, width, hidden),
                                               default_initializer=init)


class Router(Layer):
    def __init__(self, hidden, published, selection_bias):
        super().__init__()
        self.weight = self.create_parameter(
            (hidden, published), default_initializer=Normal(0.0, 0.02))
        if selection_bias:
            self.e_score_correction_bias = self.create_parameter(
                (published,), default_initializer=Constant(0.0))


class SparseMoE(Layer):
    """``held`` experts from ``offset`` of the ``published`` the router
    scores, ``top_k`` a row, beside one shared expert of ``shared_width``
    that every row takes (0: the family has NO shared expert; no leaf is
    made and nothing is traced for one). ``scoring``, ``selection_bias``,
    ``n_group`` / ``topk_group``, ``renormalize`` and ``renorm_eps``:
    :func:`moe_dropless.route`."""

    def __init__(self, hidden, width, held, published, offset, top_k,
                 scale, shared_width, scoring="sigmoid", selection_bias=True,
                 n_group=1, topk_group=1, renormalize=True, renorm_eps=0.0):
        super().__init__()
        self.held, self.offset, self.top_k, self.scale = held, offset, \
            top_k, scale
        self.routing = dict(renormalize=renormalize, scoring=scoring,
                            n_group=n_group, topk_group=topk_group)
        if renorm_eps:
            self.routing["renorm_eps"] = float(renorm_eps)
        self.gate = Router(hidden, published, selection_bias)
        self.experts = Experts(held, hidden, width)
        if shared_width:
            self.shared_experts = SwiGLU(hidden, shared_width)

    def forward(self, x, cache=None):
        k, held, offset, scale, routing = self.top_k, self.held, \
            self.offset, self.scale, self.routing
        budget = getattr(cache, "row_budget", None)
        q_lens = getattr(cache, "q_lens", None)
        rmap = CL.packed(cache)

        def fn(x, q_lens, wr, bias, wg, wu, wd, shared):
            b, s, h = x.shape
            n = b * s
            if rmap is not None:
                # a mixed step's packed rows: the first sum(q_lens) hold
                # a token
                live = rmap.live
            elif q_lens is None:
                live = jnp.ones((n,), bool)
            else:
                live = live_rows(q_lens, s).reshape(n)
            xf = x.reshape(n, h)
            with scope("pt.route"):
                idx, w = _moe.route(xf, wr, bias, k, scale, **routing)
            rows = (budget or n) * min(k, held)
            y, counts = _moe.held_expert_ffn(
                xf, idx, w, live, wg, wu, wd, offset, rows)
            if shared is not None:
                with scope("pt.shared"):
                    shared = swiglu(xf, *shared).astype(F32)
            with scope("pt.combine"):
                out = y if shared is None else shared + y
                return out.astype(x.dtype).reshape(b, s, h), counts

        sh = getattr(self, "shared_experts", None)
        out, counts = dispatch(
            fn, (x, q_lens, self.gate.weight,
                 getattr(self.gate, "e_score_correction_bias", None),
                 self.experts.gate_proj, self.experts.up_proj,
                 self.experts.down_proj,
                 None if sh is None else (
                     sh.gate_proj.weight, sh.up_proj.weight,
                     sh.down_proj.weight)), {},
            name="sparse_moe")
        CL.count(CL._val(counts))
        return out


class DecoderBlock(Layer):
    """Pre-norm: ``x + attn(norm(x))``, then ``x + mlp(norm(x))``. The
    attention returns its layer's new cache object."""

    def __init__(self, self_attn, mlp, hidden, eps):
        super().__init__()
        self.self_attn, self.mlp = self_attn, mlp
        self.input_layernorm = RMSNorm(hidden, eps)
        self.post_attention_layernorm = RMSNorm(hidden, eps)

    def forward(self, x, cache):
        a, new_cache = self.self_attn(self.input_layernorm(x), cache)
        x = x + a
        x = x + self.mlp(self.post_attention_layernorm(x), cache)
        return x, new_cache


class StateDecoder(Layer):
    """Embedding, the blocks, the final norm, on one cache object a layer
    (``cache_layout``). The positions ride on the caches (``seq_lens``,
    ``RowMap.pos``), so ``position_offset`` is not read."""

    def __init__(self, config, blocks):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = LayerList(blocks)
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                position_offset=0):
        if kv_caches is None:
            raise ValueError(
                f"{type(self).__name__} runs on per-layer state: call the "
                f"causal LM (it builds a one-call state) or pass kv_caches")
        x = self.embed_tokens(input_ids)
        new_caches = []
        for layer, cache in zip(self.layers, kv_caches):
            x, c = layer(x, cache)
            new_caches.append(c)
        return self.norm(x), new_caches


class StateCausalLM(Layer):
    """A :class:`StateDecoder` (``self.model``) under a head of its own
    (``tied``: under the embedding's matrix, and no ``lm_head`` leaf), as
    :class:`paddle_tpu.inference.LLMEngine` serves it: ``decoder``,
    ``cache_layout()`` (the family's), ``_logits``. A plain ``model(ids)``
    builds a one-call state. Serving only: the backward of latent
    attention is not written (ROADMAP Queue 2)."""
    #: device-side counts of a step (``cache_layout.count``), booked into
    #: ``engine.stats`` under these names, and the ids of a step's emit span
    step_counter_names = _moe.COUNTERS
    step_emit_ids = _moe.EMIT_IDS

    def __init__(self, config, decoder, tied=False):
        super().__init__()
        self.config = config
        self.model = decoder
        if not tied:
            self.lm_head = _linear(config.hidden_size, config.vocab_size)

    @property
    def decoder(self):
        return self.model

    def _logits(self, hidden):
        if not hasattr(self, "lm_head"):
            with scope("lm_head"):
                return ops.matmul(hidden, self.model.embed_tokens.weight,
                                  transpose_y=True)
        return self.lm_head(hidden)

    def _fresh_layout(self, seq, block_size):
        """The kinds of a one-call state over ``seq`` rows a slot."""
        return self.cache_layout()

    def fresh_caches(self, batch, seq, block_size=64):
        """Per-layer state for ONE call over ``seq`` new positions from
        position 0 (the plain forward's; the engine builds its own)."""
        mb = -(-seq // block_size)
        tables = jnp.arange(batch * mb, dtype=jnp.int32).reshape(batch, mb)
        lens = jnp.zeros((batch,), jnp.int32)
        q_lens = jnp.full((batch,), seq, jnp.int32)
        dt = self.model.embed_tokens.weight.dtype
        layout = CL.Layout(self._fresh_layout(seq, block_size))
        a, b = layout.alloc(jnp.zeros, batch * mb, block_size, batch, dt)
        return layout.caches(a, b, tables, lens, q_lens, None, None)

    def forward(self, input_ids, labels=None, attn_mask=None):
        if labels is not None:
            raise NotImplementedError(
                f"training {type(self).__name__} needs the backward of "
                f"its attention layers, which is not written (ROADMAP "
                f"Queue 2)")
        b, s = input_ids.shape[0], input_ids.shape[1]
        hidden, _ = self.model(input_ids,
                               kv_caches=self.fresh_caches(b, s))
        return self._logits(hidden)
