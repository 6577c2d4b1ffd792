"""Plain float32 reference of the Kimi-Linear decoder (``config.json`` of
``moonshotai/Kimi-Linear-48B-A3B-Instruct``): pre-norm blocks whose
attention is either Kimi Delta Attention (KDA, a gated delta rule with a
decay per key channel) or multi-head latent attention without positions
(MLA), over a dense SwiGLU in the first layer and sparse experts with one
shared expert in the rest; a final RMSNorm and an untied head. Straight
``jax.numpy`` at ``highest`` matmul precision: no kernels, no cache, the
recurrence a ``lax.scan`` a token, attention with per-head keys and values
(NOT the absorbed form the program serves), the experts a plain loop. It
imports nothing of the program and makes its weights again from the seed,
a layer at a time (an expert layer is 2 GB in float32).

The equations, one sequence, rows t = 0..T-1, ``x`` the normed input:

KDA (H heads of K = V = ``linear_attn_config.head_dim``): ``q~, k~, v~ =
x W_q, x W_k, x W_v``; ``q, k, v = SiLU(conv(.))``, a causal depthwise
convolution of ``short_conv_kernel_size`` taps (zeros before the first
row, no bias); per head ``q <- q / sqrt(|q|^2 + 1e-6) * K^-1/2``, ``k <- k
/ sqrt(|k|^2 + 1e-6)``; ``g_t = -exp(A_log_h) softplus((x W_fa) W_fb +
dt_bias)`` a channel; ``beta_t = sigmoid(x W_b)`` a head; ``S' =
diag(exp(g_t)) S``, ``S <- S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t =
S^T q_t`` from ``S = 0``; ``y = [RMSNorm_head(o) * sigmoid((x W_ga)
W_gb)] W_o``.

MLA (H heads): ``q = x W_q`` (H x (nope + pe)); ``[c; k_pe] = x W_kva``,
``c <- RMSNorm(c)``; ``[k_nope_h; v_h] = c W_kvb,h``; ``k_h = [k_nope_h;
k_pe]``; causal ``softmax(q_h k_h / sqrt(nope + pe))``; ``y = concat_h(sum
p v_h) W_o``. Nothing is rotated (``mla_use_nope``).

Experts: ``s = sigmoid(x W_r)`` over ALL the published experts; the
``num_experts_per_token`` largest of ``s + b``; ``w_i = s_i / sum_sel s *
routed_scaling_factor``; ``y = E_shared(x) + sum over the selected experts
HELD here of w_i E_i(x)``. This configuration holds experts ``expert_offset
.. expert_offset + num_experts - 1`` of ``num_experts_published``: what the
absent ones would add is left out, here as in the program.

Departures from the published description: none known in the mathematics;
what ``config.json`` does not state is listed under ``assumed`` in the
configuration's file. Linear weights are stored [in, out], convolution
weights [taps, channels], the held experts stacked [E, in, out].

``precision="int8"`` or ``"fp8"`` is the CONTROL, not a reference: every
matmul input is rounded to 8 bits with an absmax scale first (and the
attention's q, k, v), the nearest precision below the stated bfloat16."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W

HI = jax.lax.Precision.HIGHEST
LOW = ("int8", "fp8")

KDA_LEAVES = ("q_proj.weight", "k_proj.weight", "v_proj.weight", "q_conv",
              "k_conv", "v_conv", "f_a_proj.weight", "f_b_proj.weight",
              "dt_bias", "A_log", "b_proj.weight", "g_a_proj.weight",
              "g_b_proj.weight", "o_norm.weight", "o_proj.weight")
MLA_LEAVES = ("q_proj.weight", "kv_a_proj.weight", "kv_a_layernorm.weight",
              "kv_b_proj.weight", "o_proj.weight")
DENSE_LEAVES = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")
MOE_LEAVES = ("gate.weight", "gate.e_score_correction_bias",
              "experts.gate_proj", "experts.up_proj", "experts.down_proj",
              "shared_experts.gate_proj.weight",
              "shared_experts.up_proj.weight",
              "shared_experts.down_proj.weight")
NORM_LEAVES = ("input_layernorm.weight", "post_attention_layernorm.weight")


def dims(cfg):
    lin = cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]), layers=layers,
        eps=float(cfg["rms_norm_eps"]), ff=int(cfg["intermediate_size"]),
        nh=int(cfg["num_attention_heads"]), r=int(cfg["kv_lora_rank"]),
        dn=int(cfg["qk_nope_head_dim"]), dp=int(cfg["qk_rope_head_dim"]),
        dv=int(cfg["v_head_dim"]),
        kda=tuple(i for i in lin["kda_layers"] if i <= layers),
        lh=int(lin["num_heads"]), lk=int(lin["head_dim"]),
        taps=int(lin["short_conv_kernel_size"]),
        rank=int(cfg["gate_low_rank"]),
        dense=int(cfg["first_k_dense_replace"]),
        mf=int(cfg["moe_intermediate_size"]),
        held=int(cfg["num_experts"]),
        e_all=int(cfg["num_experts_published"]),
        off=int(cfg["expert_offset"]),
        topk=int(cfg["num_experts_per_token"]),
        shared=int(cfg["num_shared_experts"]),
        scale=float(cfg["routed_scaling_factor"]),
        renorm=bool(cfg["moe_renormalize"]))


def _dkey(d):
    return tuple(sorted(d.items()))


def is_kda(d, layer):
    return (layer + 1) in d["kda"]


def is_scale(name):
    """Which leaves are norm scales (made as 1 + N(0, 0.1^2))."""
    return name.endswith("norm.weight")


def layer_specs(cfg, layer):
    d = dims(cfg)
    h, hk = d["h"], d["lh"] * d["lk"]
    if is_kda(d, layer):
        shapes = ((h, hk), (h, hk), (h, hk), (d["taps"], hk),
                  (d["taps"], hk), (d["taps"], hk), (h, d["rank"]),
                  (d["rank"], hk), (hk,), (d["lh"],), (h, d["lh"]),
                  (h, d["rank"]), (d["rank"], hk), (d["lk"],), (hk, h))
        attn = list(zip(KDA_LEAVES, shapes))
    else:
        nh = d["nh"]
        shapes = ((h, nh * (d["dn"] + d["dp"])), (h, d["r"] + d["dp"]),
                  (d["r"],), (d["r"], nh * (d["dn"] + d["dv"])),
                  (nh * d["dv"], h))
        attn = list(zip(MLA_LEAVES, shapes))
    if layer < d["dense"]:
        ff = list(zip(DENSE_LEAVES,
                      ((h, d["ff"]), (h, d["ff"]), (d["ff"], h))))
    else:
        e, f, sf = d["held"], d["mf"], d["mf"] * d["shared"]
        ff = list(zip(MOE_LEAVES, (
            (h, d["e_all"]), (d["e_all"],), (e, h, f), (e, h, f), (e, f, h),
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


def _conv(x, w):
    """Causal depthwise convolution. x: [T, D]; w: [taps, D]; tap
    ``taps - 1`` multiplies the current row, zeros before row 0."""
    taps, t = w.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(ext[j:j + t] * w[j] for j in range(taps))


def kda_scan(q, k, v, g, beta):
    """The recurrence, a row at a time from a zero state. q, k, g: [T, H,
    K]; v: [T, H, V]; beta: [T, H]. Returns o [T, H, V]."""
    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, :, None]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt,
                                           precision=HI))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HI)
    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


def _kda(x, lw, d, precision):
    (wq, wk, wv, cq, ck, cv, wfa, wfb, dtb, alog, wb, wga, wgb, on,
     wo) = lw
    t, H, K = x.shape[0], d["lh"], d["lk"]

    def branch(w, c):
        return jax.nn.silu(_conv(_mm(x, w, precision), c)).reshape(t, H, K)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = unit(branch(wq, cq)) * K ** -0.5
    k = unit(branch(wk, ck))
    v = branch(wv, cv)
    g = -jnp.exp(alog)[None, :, None] * jax.nn.softplus(
        (_mm(_mm(x, wfa, precision), wfb, precision) + dtb).reshape(t, H, K))
    beta = jax.nn.sigmoid(_mm(x, wb, precision))
    o = kda_scan(q, k, v, g, beta)
    gate = jax.nn.sigmoid(
        _mm(_mm(x, wga, precision), wgb, precision)).reshape(t, H, K)
    return _mm((_rms(o, on, d["eps"]) * gate).reshape(t, H * K), wo,
               precision)


def mla_heads(x, lw, d, precision):
    """Per-head queries, keys and values of the latent attention: q, k [T,
    H, nope + pe], v [T, H, dv]."""
    wq, wkva, nw, wkvb, _ = lw
    t, nh, dn, dp, dv, r = x.shape[0], d["nh"], d["dn"], d["dp"], d["dv"], \
        d["r"]
    q = _mm(x, wq, precision).reshape(t, nh, dn + dp)
    kva = _mm(x, wkva, precision)
    c = _rms(kva[:, :r], nw, d["eps"])
    kv = _mm(c, wkvb, precision).reshape(t, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kva[:, None, r:], (t, nh, dp))], -1)
    return q, k, kv[..., dn:]


def _attention(q, k, v, precision, q_block=512):
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
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / dk ** 0.5
        rows = start + jnp.arange(qb, dtype=jnp.int32)
        mask = jnp.arange(t, dtype=jnp.int32)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if precision in LOW:
            p = _fq(p, -1, precision)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    return jax.lax.map(block, (qp, starts)).reshape(-1, nh, v.shape[-1])[:t]


def _mla(x, lw, d, precision):
    q, k, v = mla_heads(x, lw, d, precision)
    a = _attention(q, k, v, precision)
    return _mm(a.reshape(x.shape[0], -1), lw[4], precision)


def _swiglu(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def route(x, wr, bias, d):
    """idx [T, k] and weights [T, k] over ALL the published experts.
    Scores in full float32 whatever the control's precision: the control
    rounds what the experts compute, not which are chosen."""
    s = jax.nn.sigmoid(jnp.matmul(x, wr, precision=HI))
    _, idx = jax.lax.top_k(s + bias, d["topk"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if d["renorm"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w * d["scale"]


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
    wr, bias, wg, wu, wd, sg, su, sd = lw
    idx, w = route(x, wr, bias, d)
    return _swiglu(x, sg, su, sd, precision) + routed_part(
        x, idx, w, wg, wu, wd, d["off"], precision)


def _layer(x, lw, d, kda, dense, precision):
    """One block on one sequence. x: [T, h]; lw: the layer's leaves in
    ``layer_specs`` order, float32; ``kda``: its attention is KDA (else
    MLA); ``dense``: its feed-forward is the dense SwiGLU (else experts)."""
    n_attn = len(KDA_LEAVES) if kda else len(MLA_LEAVES)
    attn, ff, (n1, n2) = lw[:n_attn], lw[n_attn:-2], lw[-2:]
    y = _rms(x, n1, d["eps"])
    x = x + (_kda if kda else _mla)(y, attn, d, precision)
    y = _rms(x, n2, d["eps"])
    if dense:
        return x + _swiglu(y, *ff, precision)
    return x + _moe(y, ff, d, precision)


def layer_kind(d, layer):
    """(kda, dense) of layer ``layer`` (from 0)."""
    return is_kda(d, layer), layer < d["dense"]


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
            x = _layer(x, lw, d, *layer_kind(d, layer), precision)
        x = _rms(x, params["model.norm.weight"], d["eps"])
        return _mm(x, params["lm_head.weight"], precision)


# ---------------------------------------------------------------------------
# serving: the served tokens' logits under the reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("dkey", "kda", "dense", "precision"))
def _layer_rows(x, lw, dkey, kda, dense, precision):
    d = dict(dkey)
    return jax.lax.map(
        lambda xi: _layer(xi, _f32(lw), d, kda, dense, precision), x)


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
            xs = [_layer_rows(x, lw, dkey, *layer_kind(d, layer), precision)
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
