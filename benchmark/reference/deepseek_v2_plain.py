"""Plain float32 reference of the DeepSeek-V2 decoder (``config.json`` of
``deepseek-ai/DeepSeek-V2``): pre-norm blocks, every one with multi-head
latent attention (a compressed query, a compressed key/value latent,
rotary positions on a part of each head that all heads' keys share,
scaled by YaRN), over a dense SwiGLU in the first layer and routed experts
beside shared experts in the rest; a final RMSNorm and an untied head.
Straight ``jax.numpy`` at ``highest`` matmul precision: no kernels, no
cache, attention with per-head keys and values expanded from the latent
(NOT the absorbed form the program serves) in blocks of query rows, the
rotation written out from the formulas below, the experts a plain loop. It
imports nothing of the program and makes its weights again from the seed,
a layer at a time (an expert layer is 4 GB in float32).

The equations, one sequence, rows t = 0..T-1 at positions p = t, ``x`` the
normed input, H heads, ``dn`` / ``dp`` / ``dv`` = ``qk_nope_head_dim`` /
``qk_rope_head_dim`` / ``v_head_dim``:

Attention: ``c_q = RMSNorm(x W_qa)``, ``[q_nope_h; q_pe_h] = c_q W_qb,h``;
``[c; k_pe] = x W_kva``, ``c <- RMSNorm(c)``, ``[k_nope_h; v_h] = c
W_kvb,h``. ``q_pe_h <- R_p q_pe_h`` and ``k_pe <- R_p k_pe`` (one key part
for all heads): ``R_p`` turns the pair ``(2i, 2i + 1)`` by the angle ``p
f_i``, i = 0..dp/2-1. YaRN (``rope_scaling``): ``f_i = (1 - g_i) theta^(-2i
/dp) / factor + g_i theta^(-2i/dp)``, ``g_i = 1 - clip((i - lo) / (hi -
lo), 0, 1)``, ``lo = floor(n(beta_fast))``, ``hi = ceil(n(beta_slow))``
kept inside [0, dp - 1], ``n(b) = dp ln(original_max / (2 pi b)) / (2 ln
theta)``; ``m(s) = 0.1 s ln(factor) + 1``; cos and sin are multiplied by
``m(mscale) / m(mscale_all_dim)``. Causal ``softmax((q_nope_h . k_nope_h +
q_pe_h . k_pe) (dn + dp)^-1/2 m(mscale_all_dim)^2)``; ``y = concat_h(sum p
v_h) W_o``.

Experts (layers from ``first_k_dense_replace`` on): ``s = softmax(x W_r)``
over ALL the published experts; groups of ``published / n_group``
consecutive experts, ``g_j`` = the largest score in group j; the
``topk_group`` groups of largest ``g_j`` keep their scores, the others' are
0; the ``num_experts_per_tok`` largest of those; ``w_i =
routed_scaling_factor s_i`` (``norm_topk_prob`` false: not renormalised);
``y = E_shared(x) + sum over the selected experts HELD here of w_i
E_i(x)``, ``E(x) = (SiLU(x W_g) * x W_u) W_d``, ``E_shared`` one such of
width ``n_shared_experts x moe_intermediate_size``. This configuration
holds experts ``expert_offset .. expert_offset + n_routed_experts - 1`` of
``n_routed_experts_published``: what the absent ones would add is left
out, here as in the program. The auxiliary losses are training's.

Departures from the published description: none known in the mathematics;
what ``config.json`` does not state is listed under ``assumed`` in the
configuration's file. Linear weights are stored [in, out], the held
experts stacked [E, in, out].

``precision="int8"`` or ``"fp8"`` is the CONTROL, not a reference: every
matmul input is rounded to 8 bits with an absmax scale first (and the
attention's q, k, v), the nearest precision below the stated bfloat16."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W

HI = jax.lax.Precision.HIGHEST
LOW = ("int8", "fp8")

ATTN_LEAVES = ("q_a_proj.weight", "q_a_layernorm.weight", "q_b_proj.weight",
               "kv_a_proj.weight", "kv_a_layernorm.weight",
               "kv_b_proj.weight", "o_proj.weight")
DENSE_LEAVES = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")
MOE_LEAVES = ("gate.weight", "experts.gate_proj", "experts.up_proj",
              "experts.down_proj", "shared_experts.gate_proj.weight",
              "shared_experts.up_proj.weight",
              "shared_experts.down_proj.weight")
NORM_LEAVES = ("input_layernorm.weight", "post_attention_layernorm.weight")


def yarn(cfg):
    """(f [dp/2] float64, the multiplier of cos and sin, the multiplier
    of the softmax scale) from ``rope_scaling``, as written above."""
    rs, dp = cfg["rope_scaling"], int(cfg["qk_rope_head_dim"])
    theta, factor = float(cfg["rope_theta"]), float(rs["factor"])
    i = np.arange(dp // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dp)

    def n(b):
        return dp * math.log(rs["original_max_position_embeddings"]
                             / (2 * math.pi * b)) / (2 * math.log(theta))
    lo = max(math.floor(n(rs["beta_fast"])), 0)
    hi = min(math.ceil(n(rs["beta_slow"])), dp - 1)
    g = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)

    def m(s):
        return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0
    return ((1.0 - g) * plain / factor + g * plain,
            m(rs["mscale"]) / m(rs["mscale_all_dim"]),
            m(rs["mscale_all_dim"]) ** 2)


def dims(cfg):
    f, trig, scale = yarn(cfg)
    nope, pe = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        eps=float(cfg["rms_norm_eps"]), ff=int(cfg["intermediate_size"]),
        nh=int(cfg["num_attention_heads"]), rq=int(cfg["q_lora_rank"]),
        r=int(cfg["kv_lora_rank"]), dn=nope, dp=pe,
        dv=int(cfg["v_head_dim"]),
        freqs=tuple(float(a) for a in f), trig=trig,
        attn_scale=(nope + pe) ** -0.5 * scale,
        dense=int(cfg["first_k_dense_replace"]),
        mf=int(cfg["moe_intermediate_size"]),
        held=int(cfg["n_routed_experts"]),
        e_all=int(cfg["n_routed_experts_published"]),
        off=int(cfg["expert_offset"]),
        topk=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]),
        groups=int(cfg["n_group"]), keep=int(cfg["topk_group"]),
        scale=float(cfg["routed_scaling_factor"]))


def _dkey(d):
    return tuple(sorted(d.items()))


def is_scale(name):
    """Which leaves are norm scales (made as 1 + N(0, 0.1^2))."""
    return name.endswith("norm.weight")


def layer_specs(cfg, layer):
    d = dims(cfg)
    h, nh = d["h"], d["nh"]
    attn = list(zip(ATTN_LEAVES, (
        (h, d["rq"]), (d["rq"],), (d["rq"], nh * (d["dn"] + d["dp"])),
        (h, d["r"] + d["dp"]), (d["r"],),
        (d["r"], nh * (d["dn"] + d["dv"])), (nh * d["dv"], h))))
    if layer < d["dense"]:
        ff = list(zip(DENSE_LEAVES,
                      ((h, d["ff"]), (h, d["ff"]), (d["ff"], h))))
    else:
        e, f, sf = d["held"], d["mf"], d["mf"] * d["shared"]
        ff = list(zip(MOE_LEAVES, (
            (h, d["e_all"]), (e, h, f), (e, h, f), (e, f, h),
            (h, sf), (h, sf), (sf, h))))
    pre = f"model.layers.{layer}."
    return ([(pre + "self_attn." + n, s) for n, s in attn]
            + [(pre + "mlp." + n, s) for n, s in ff]
            + [(pre + n, (h,)) for n in NORM_LEAVES])


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

def _fq(x, axis, precision):
    top = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if precision == "int8":
        scale = jnp.where(top == 0, 1.0, top / 127.0)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    scale = jnp.where(top == 0, 1.0, top / 448.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _mm(x, w, precision):
    if precision in LOW:
        x, w = _fq(x, -1, precision), _fq(w, -2, precision)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate(x, pos, d):
    """``R_p`` on the last axis of ``x`` [T, ..., dp], row t at position
    ``pos[t]``: out[2i] = x[2i] cos - x[2i+1] sin, out[2i+1] = x[2i] sin +
    x[2i+1] cos, the angle ``pos f_i``."""
    angle = pos.astype(jnp.float32)[:, None] * jnp.asarray(d["freqs"],
                                                           jnp.float32)
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle) * d["trig"], jnp.sin(angle) * d["trig"]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * cos - odd * sin)
    return out.at[..., 1::2].set(even * sin + odd * cos)


def mla_heads(x, lw, d, precision, pos=None):
    """Per-head queries, keys and values: q, k [T, H, nope + pe] with the
    pe parts rotated, v [T, H, dv]. ``pos``: the rows' positions (None:
    0..T-1)."""
    wqa, qn, wqb, wkva, kn, wkvb, _ = lw
    t, nh, dn, dp, dv, r = x.shape[0], d["nh"], d["dn"], d["dp"], d["dv"], \
        d["r"]
    pos = jnp.arange(t, dtype=jnp.int32) if pos is None else pos
    cq = _rms(_mm(x, wqa, precision), qn, d["eps"])
    q = _mm(cq, wqb, precision).reshape(t, nh, dn + dp)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], pos, d)], -1)
    kva = _mm(x, wkva, precision)
    c = _rms(kva[:, :r], kn, d["eps"])
    kv = _mm(c, wkvb, precision).reshape(t, nh, dn + dv)
    k_pe = rotate(kva[:, r:], pos, d)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (t, nh, dp))], -1)
    return q, k, kv[..., dn:]


def _attention(q, k, v, scale, precision, q_block=256):
    """Causal softmax attention of one sequence with per-head keys and
    values; queries in blocks so a long sequence's scores fit."""
    t, nh, dk = q.shape
    if precision in LOW:
        q, k, v = (_fq(q, -1, precision), _fq(k, -1, precision),
                   _fq(v, 0, precision))
    qb = min(q_block, t)
    pad = (-t) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, nh, dk)
    starts = jnp.arange(qp.shape[0], dtype=jnp.int32) * qb

    def block(args):
        qi, start = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        rows = start + jnp.arange(qb, dtype=jnp.int32)
        mask = jnp.arange(t, dtype=jnp.int32)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if precision in LOW:
            p = _fq(p, -1, precision)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    return jax.lax.map(block, (qp, starts)).reshape(-1, nh, v.shape[-1])[:t]


def _mla(x, lw, d, precision):
    q, k, v = mla_heads(x, lw, d, precision)
    a = _attention(q, k, v, d["attn_scale"], precision)
    return _mm(a.reshape(x.shape[0], -1), lw[6], precision)


def _swiglu(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def route(x, wr, d):
    """idx [T, k] and weights [T, k] over ALL the published experts.
    Scores in full float32 whatever the control's precision: the control
    rounds what the experts compute, not which are chosen."""
    s = jax.nn.softmax(jnp.matmul(x, wr, precision=HI), axis=-1)
    t, e = s.shape
    size = e // d["groups"]
    g = jnp.max(s.reshape(t, d["groups"], size), axis=-1)
    # a group is kept if fewer than ``keep`` groups score above it (or
    # tie with it from a smaller index)
    j = jnp.arange(d["groups"])
    ahead = (g[:, None, :] > g[:, :, None]) | (
        (g[:, None, :] == g[:, :, None]) & (j[None, None, :] < j[None, :, None]))
    kept = jnp.sum(ahead, axis=-1) < d["keep"]
    limited = jnp.where(jnp.repeat(kept, size, axis=1), s, 0.0)
    _, idx = jax.lax.top_k(limited, d["topk"])
    return idx, jnp.take_along_axis(s, idx, axis=-1) * d["scale"]


def routed_part(x, idx, w, wg, wu, wd, offset, precision):
    """What the experts ``offset .. offset + E - 1`` add: a plain loop
    over them, each run on every row and weighted by the row's routing
    weight for it (0 where it was not selected)."""
    def one(y, ew):
        e, g, u, dn = ew
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        return y + we[:, None] * _swiglu(x, g, u, dn, precision), None
    ids = offset + jnp.arange(wg.shape[0], dtype=idx.dtype)
    return jax.lax.scan(one, jnp.zeros_like(x), (ids, wg, wu, wd))[0]


def _moe(x, lw, d, precision):
    wr, wg, wu, wd, sg, su, sd = lw
    idx, w = route(x, wr, d)
    return _swiglu(x, sg, su, sd, precision) + routed_part(
        x, idx, w, wg, wu, wd, d["off"], precision)


def _layer(x, lw, d, dense, precision):
    """One block on one sequence. x: [T, h]; lw: the layer's leaves in
    ``layer_specs`` order, float32; ``dense``: its feed-forward is the
    dense SwiGLU (else experts)."""
    n_attn = len(ATTN_LEAVES)
    attn, ff, (n1, n2) = lw[:n_attn], lw[n_attn:-2], lw[-2:]
    x = x + _mla(_rms(x, n1, d["eps"]), attn, d, precision)
    y = _rms(x, n2, d["eps"])
    if dense:
        return x + _swiglu(y, *ff, precision)
    return x + _moe(y, ff, d, precision)


def _f32(arrays):
    return [a.astype(jnp.float32) for a in arrays]


def forward_logits(params, ids, cfg, precision="f32"):
    """Logits [T, vocab] of one sequence from a dict of float32 leaves:
    the whole model at once, for the tests' sizes."""
    d = dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0)
        for layer in range(d["layers"]):
            lw = [params[n] for n, _ in layer_specs(cfg, layer)]
            x = _layer(x, lw, d, layer < d["dense"], precision)
        x = _rms(x, params["model.norm.weight"], d["eps"])
        return _mm(x, params["lm_head.weight"], precision)


# ---------------------------------------------------------------------------
# serving: the served tokens' logits under the reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dkey", "dense", "precision"))
def _layer_rows(x, lw, dkey, dense, precision):
    d = dict(dkey)
    return jax.lax.map(
        lambda xi: _layer(xi, _f32(lw), d, dense, precision), x)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_rows(x, pos, norm_w, head_w, eps, precision):
    rows = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    rows = _rms(rows, norm_w.astype(jnp.float32), eps)
    return _mm(rows, head_w.astype(jnp.float32), precision)


def served_logits(seed, cfg, seqs, positions, precision="f32", device=None,
                  pad_to=512):
    """Teacher-force each of ``seqs`` through the reference, a layer at a
    time for all of them, and return for each the float32 logits at its
    ``positions`` as [m_i, vocab]. A sequence is padded at its end to a
    multiple of ``pad_to`` (every layer is causal, so the padding reaches
    nothing before it)."""
    d = dims(cfg)
    dkey = _dkey(d)
    put = (lambda a: jax.device_put(a, device)) if device is not None \
        else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        emb, norm_w, head_w = [put(a) for a in
                               W.make(seed, outer_specs(cfg),
                                      is_scale=is_scale)]
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
            xs = [_layer_rows(x, lw, dkey, layer < d["dense"], precision)
                  for x in xs]
            del lw
        m = max(len(p) for p in positions)
        m += (-m) % 128
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
