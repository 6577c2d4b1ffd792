"""Plain float32 reference of Brumby-14B-Base (``config.json`` of
``manifestai/Brumby-14B-Base``, ``model_type: "brumby"``; the layer is power
retention, arXiv:2507.04239 "Scaling Context Requires Rethinking
Attention"): a dense pre-norm decoder whose every layer mixes tokens by
attention with the SQUARE of the score as its weight, under a scalar gate a
key/value head. This file is the ATTENTION form: straight ``jax.numpy`` at
``highest`` matmul precision, no state, no feature map, no cache, no
batching tricks; queries go in blocks of positions so that 8,704 tokens x 40
heads fit. It imports nothing of the program and takes nothing the program
made: it makes its weights again from the seed, a layer at a time
(``harness.weights`` is the benchmark's own).

One sequence, rows at positions t = 0..T-1, eps ``rms_norm_eps``, no
biases anywhere:

    h = x + Ret(RMSNorm(x));   y = h + W_d(silu(W_g n) * (W_u n)),  n = RMSNorm(h)
    Ret(u), Hq query heads on Hk key/value heads of width d, query head a
    reads key/value head j = a // (Hq / Hk):
      q_t^a = RoPE_t(RMSNorm_d(W_q^a u_t))   k_t^j = RoPE_t(RMSNorm_d(W_k^j u_t))
      v_t^j = W_v^j u_t                      log g_t^j = logsigmoid(w_g^j . u_t)
      A_ts^a = (q_t^a . k_s^j / sqrt(d))^2 exp(G_t^j - G_s^j),  s <= t,
               G = cumsum(log g)
      o_t^a  = sum_s A_ts^a v_s^j / (sum_s A_ts^a + 1e-6)
      out_t  = W_o concat_a o_t^a
    logits = RMSNorm(y_L) W_head                     (untied head)

``config.json`` carries Qwen3-14B's keys (the model it was retrained from)
and not one key of the retention layer. What it leaves open is listed under
``assumed`` in the configuration's file, each one line below with a comment
that starts ``assumed``; the ground is the published description (the
manifestai release note of 2025-10 and the paper above). Linear weights are
stored [in, out].

``precision="int8"`` or ``"fp8"`` is the CONTROL, not a reference: every
matmul input (activations per row, weights per output column, queries, keys
and values per head, the attention weights per row) is rounded to 8 bits
with an absmax scale first, the nearest precision below the bfloat16 the
configuration states."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W
# the parts the llama family's reference already writes out plainly, and
# that are the same mathematics here: a product at ``highest`` precision
# (its inputs rounded to 8 bits under the control), RMSNorm, and
# rotate-half rotary positions
from benchmark.reference.dense_decoder import HI, LOW, _fq, _mm, _rms, _rope

LAYER_LEAVES = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                "self_attn.gate.weight", "self_attn.q_norm.weight",
                "self_attn.k_norm.weight",
                "mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight", "input_layernorm.weight",
                "post_attention_layernorm.weight")
#: assumed: the power of the score is 2
DEGREE = 2
#: assumed: the normaliser is the sum of the weights plus this
RET_EPS = 1e-6


def dims(cfg):
    h, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(h=h, nh=nh, nkv=int(cfg["num_key_value_heads"]),
                hd=int(cfg.get("head_dim") or h // nh),
                ff=int(cfg["intermediate_size"]), v=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def is_scale(name):
    """Norm scales, made as 1 + N(0, 0.1^2): the two of a block, the
    per-head ``q_norm`` and ``k_norm`` and the final norm (every leaf whose
    module's name holds ``norm``)."""
    return "norm" in name.rsplit(".", 2)[-2]


def layer_specs(cfg, layer):
    d = dims(cfg)
    shapes = ((d["h"], d["nh"] * d["hd"]), (d["h"], d["nkv"] * d["hd"]),
              (d["h"], d["nkv"] * d["hd"]), (d["nh"] * d["hd"], d["h"]),
              (d["h"], d["nkv"]), (d["hd"],), (d["hd"],),
              (d["h"], d["ff"]), (d["h"], d["ff"]), (d["ff"], d["h"]),
              (d["h"],), (d["h"],))
    return [(f"model.layers.{layer}.{leaf}", shape)
            for leaf, shape in zip(LAYER_LEAVES, shapes)]


def outer_specs(cfg):
    d = dims(cfg)
    return [("model.embed_tokens.weight", (d["v"], d["h"])),
            ("model.norm.weight", (d["h"],)),
            ("lm_head.weight", (d["h"], d["v"]))]


def specs(cfg):
    """[(name, shape)] of every leaf of the configuration."""
    out = outer_specs(cfg)[:1]
    for layer in range(dims(cfg)["layers"]):
        out += layer_specs(cfg, layer)
    return out + outer_specs(cfg)[1:]


def n_params(cfg):
    return sum(int(np.prod(s)) for _, s in specs(cfg))


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------

def retention(q, k, v, log_g, precision="f32", q_block=128):
    """Power retention of one sequence in its attention form. q: [T, nh,
    hd]; k, v: [T, nkv, hd]; log_g: [T, nkv] (<= 0). Queries go in blocks
    so that the weights of a long sequence fit.

    ``G_t - G_s`` is never formed from two long running sums: for a block
    of queries that starts at ``t0`` it is ``L_t + R_s``, ``L`` the running
    sum inside the block and ``R_s`` the sum from ``s + 1`` to ``t0 - 1``
    accumulated BACKWARDS from the block (minus ``L_s`` for a key inside
    it), so an exponent is exact to float32 where it is small, which is
    where its weight counts."""
    t, nh, hd = q.shape
    nkv = k.shape[1]
    if precision in LOW:
        q, k, v = (_fq(q, -1, precision), _fq(k, -1, precision),
                   _fq(v, 0, precision))
    # assumed: query head a reads key/value head a // (nh / nkv)
    qb = min(q_block, t)
    pad = (-t) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, qb, nkv, nh // nkv, hd)
    lp = jnp.pad(log_g, ((0, pad), (0, 0))).reshape(-1, qb, nkv)
    starts = jnp.arange(qp.shape[0], dtype=jnp.int32) * qb
    at = jnp.arange(t, dtype=jnp.int32)

    def block(args):
        qi, li, t0 = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HI) / hd ** 0.5
        L = jnp.cumsum(li, axis=0)                            # [qb, nkv]
        before = jnp.where((at < t0)[:, None], log_g, 0.0)
        back = jnp.cumsum(before[::-1], axis=0)[::-1] - before
        inside = (at >= t0) & (at < t0 + qb)
        L_key = jnp.take(L, jnp.clip(at - t0, 0, qb - 1), axis=0)
        R = jnp.where(inside[:, None], -L_key, back)          # [T, nkv]
        rows = t0 + jnp.arange(qb, dtype=jnp.int32)
        mask = at[None, :] <= rows[:, None]                   # [qb, T]
        expo = L.T[:, :, None] + R.T[:, None, :]              # [nkv, qb, T]
        decay = jnp.exp(jnp.where(mask[None], expo, -jnp.inf))
        # assumed: DEGREE 2, the square of the scaled score
        a = s ** DEGREE * decay[:, None]
        if precision in LOW:
            a = _fq(a, -1, precision)
        num = jnp.einsum("hgqk,khd->qhgd", a, v, precision=HI)
        # assumed: normalised by the sum of the weights plus RET_EPS
        den = jnp.moveaxis(jnp.sum(a, -1), 2, 0)
        return num / (den[..., None] + RET_EPS)

    out = jax.lax.map(block, (qp, lp, starts))
    return out.reshape(-1, nh, hd)[:t]


def _layer(x, lw, d, precision):
    """One block on one sequence. x: [T, h]; lw: the twelve leaves in
    LAYER_LEAVES order, float32."""
    wq, wk, wv, wo, wgate, qn, kn, wg, wu, wd, n1, n2 = lw
    t = x.shape[0]
    y = _rms(x, n1, d["eps"])
    # assumed: q_norm / k_norm, an RMSNorm over a head's width with a
    # scale of [head_dim] shared by the heads, BEFORE the rotation (kept
    # from Qwen3)
    q = _rms(_mm(y, wq, precision).reshape(t, d["nh"], d["hd"]), qn,
             d["eps"])
    k = _rms(_mm(y, wk, precision).reshape(t, d["nkv"], d["hd"]), kn,
             d["eps"])
    # assumed: rotate-half rotary positions at rope_theta on q and k (kept
    # from Qwen3)
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    v = _mm(y, wv, precision).reshape(t, d["nkv"], d["hd"])
    # assumed: one scalar gate a key/value head, log g = logsigmoid of a
    # bias-free Linear(hidden, nkv) of the layer's normed input, float32
    log_g = jax.nn.log_sigmoid(_mm(y, wgate, precision))
    a = retention(q, k, v, log_g, precision).reshape(t, d["nh"] * d["hd"])
    x = x + _mm(a, wo, precision)
    y = _rms(x, n2, d["eps"])
    ff = jax.nn.silu(_mm(y, wg, precision)) * _mm(y, wu, precision)
    return x + _mm(ff, wd, precision)


def _f32(arrays):
    return [a.astype(jnp.float32) for a in arrays]


def forward(params, ids, cfg, precision="f32"):
    """One sequence ``ids`` [T] through the whole model from ``params``
    ({name: array}). Returns logits [T, vocab]. The tests' form;
    ``served_logits`` is the same mathematics a layer at a time."""
    d = dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["model.embed_tokens.weight"], ids,
                     axis=0).astype(jnp.float32)
        for layer in range(d["layers"]):
            lw = _f32([params[f"model.layers.{layer}.{leaf}"]
                       for leaf in LAYER_LEAVES])
            x = _layer(x, lw, d, precision)
        x = _rms(x, params["model.norm.weight"].astype(jnp.float32),
                 d["eps"])
        return _mm(x, params["lm_head.weight"].astype(jnp.float32),
                   precision)


# ---------------------------------------------------------------------------
# serving: the served tokens' logits under the reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dkey", "precision"))
def _layer_rows(x, lw, dkey, precision):
    d = dict(dkey)
    return jax.lax.map(lambda xi: _layer(xi, _f32(lw), d, precision), x)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_rows(x, pos, norm_w, head_w, eps, precision):
    rows = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    rows = _rms(rows, norm_w.astype(jnp.float32), eps)
    return _mm(rows, head_w.astype(jnp.float32), precision)


def served_logits(seed, cfg, seqs, positions, precision="f32", device=None,
                  pad_to=512):
    """Teacher-force each of ``seqs`` (prompt and served tokens) through
    the reference's FULL forward, a layer at a time for all of them, and
    return for each the float32 logits at its ``positions`` as [m_i,
    vocab]. A sequence is padded at its end to a multiple of ``pad_to``
    (causal, so the padding reaches nothing before it; few distinct
    lengths, few programs)."""
    d = dims(cfg)
    dkey = tuple(sorted(d.items()))
    put = (lambda a: jax.device_put(a, device)) if device is not None \
        else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        emb, norm_w, head_w = [put(a) for a in W.make(
            seed, outer_specs(cfg), is_scale=is_scale)]
        xs = []
        for seq in seqs:
            ids = np.zeros((1, len(seq) + (-len(seq)) % pad_to), np.int32)
            ids[0, :len(seq)] = seq
            xs.append(jnp.take(emb, put(jnp.asarray(ids)), axis=0)
                      .astype(jnp.float32))
        del emb
        for layer in range(d["layers"]):
            lw = [put(a) for a in W.make(seed, layer_specs(cfg, layer),
                                         is_scale=is_scale)]
            xs = [_layer_rows(x, lw, dkey, precision) for x in xs]
        m = max(len(p) for p in positions)
        m += (-m) % 128           # few distinct widths, few programs
        out = []
        for x, pos in zip(xs, positions):
            padded = np.zeros((1, m), np.int32)
            padded[0, :len(pos)] = pos
            out.append(_head_rows(x, put(jnp.asarray(padded)), norm_w,
                                  head_w, d["eps"], precision)[0, :len(pos)])
        return out


def served_gaps(seed, cfg, requests, control=None, device=None, pad_to=512):
    """``requests``: [(prompt ids, served ids)]. For every served token the
    gap by which its reference logit lies below the reference's best at
    that position (0 where the served token IS the reference's choice).
    With ``control`` (a lower precision's name) also the same gap for the
    token it puts first there. Returns {"gaps": [n][m_i], "control_gaps":
    ... or None, "logit_std": float}."""
    seqs, positions = [], []
    for p, s in requests:
        seqs.append(np.concatenate([np.asarray(p), np.asarray(s)[:-1]]))
        # position len(p)-1+j predicts served token j
        positions.append(len(p) - 1 + np.arange(len(s)))
    logits = served_logits(seed, cfg, seqs, positions, "f32", device, pad_to)
    tops = [jnp.max(lg, -1) for lg in logits]
    gaps = [np.asarray(top - jnp.take_along_axis(
        lg, jnp.asarray(np.asarray(s), jnp.int32)[:, None], -1)[:, 0])
        for lg, top, (_, s) in zip(logits, tops, requests)]
    out = {"gaps": gaps, "control_gaps": None,
           "logit_std": float(jnp.std(logits[0][0]))}
    if control:
        low = served_logits(seed, cfg, seqs, positions, control, device,
                            pad_to)
        out["control_gaps"] = [
            np.asarray(top - jnp.take_along_axis(
                lg, jnp.argmax(lo, -1)[:, None], -1)[:, 0])
            for lg, top, lo in zip(logits, tops, low)]
    return out
