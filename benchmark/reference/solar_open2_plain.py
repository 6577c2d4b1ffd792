"""Plain float32 reference of the Solar-Open2 decoder (``config.json`` of
``upstage/Solar-Open2-250B``): pre-norm blocks whose mixer is either Kimi
Delta Attention (KDA, a gated delta rule with a decay per key channel,
here with ``beta`` in (0, 2)) or GATED softmax attention over grouped keys
and values without positions, over sparse experts with one shared expert
in EVERY layer; a final RMSNorm and an untied head. Straight ``jax.numpy``
at ``highest`` matmul precision: no kernels, no cache, no batching, the
recurrence a ``lax.scan`` a token (not the chunk form), full causal
softmax in blocks of query positions, the experts a plain loop. It imports
nothing of the program and makes its weights again from the seed, a layer
at a time (an expert layer is 2.5 GB in float32).

The equations, one sequence, rows t = 0..T-1, ``x`` the normed input:

GatedGQA (Hq query heads on Hk key/value heads of ``head_dim`` d, query
head a reads key/value head ``a // (Hq / Hk)``): ``q, k, v = x W_q, x W_k,
x W_v``; nothing is rotated; causal ``softmax(q_a . k_j / sqrt(d))``; ``y =
[concat_a o_a * sigmoid(x W_g)] W_o``.

KDA (H heads of K = V = ``linear_attn_config.head_dim``): ``q~, k~, v~ =
x W_q, x W_k, x W_v``; ``q, k, v = SiLU(conv(.))``, a causal depthwise
convolution of ``short_conv_kernel_size`` taps (zeros before the first
row, no bias); per head ``q <- q / sqrt(|q|^2 + 1e-6) * K^-1/2``, ``k <- k
/ sqrt(|k|^2 + 1e-6)``; ``g_t = -exp(A_log_h) softplus((x W_fa) W_fb +
dt_bias)`` a channel; ``beta_t = 2 sigmoid(x W_b)`` a head; ``S' =
diag(exp(g_t)) S``, ``S <- S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t =
S^T q_t`` from ``S = 0``; ``y = [RMSNorm_head(o) * sigmoid((x W_ga)
W_gb)] W_o``.

Experts: ``s = sigmoid(x W_r)`` over ALL the published experts; the
``num_experts_per_tok`` largest of ``s + b``; ``w_i = s_i / sum_sel s *
routed_scaling_factor``; ``y = E_shared(x) + sum over the selected experts
HELD here of w_i E_i(x)``. This configuration holds experts ``expert_offset
.. expert_offset + n_routed_experts - 1`` of ``n_routed_experts_published``:
what the absent ones would add is left out, here as in the program.

Every line marked (+) computes something ``config.json`` does not state;
the configuration's file lists each under ``assumed`` with its ground.
Linear weights are stored [in, out], convolution weights [taps, channels],
the held experts stacked [E, in, out].

``precision`` other than ``"f32"`` is a CONTROL, not a reference.
``"int8"`` / ``"fp8"``: every matmul input is rounded to 8 bits with an
absmax scale first (and the attention's q, k, v), the nearest precision
below the stated bfloat16. Three more name ONE departure each, everything
else float32, to show that the cell's limits catch it: ``"bf16_state"``
(the KDA state rounded to bfloat16 after every token), ``"bf16_router"``
(the router's input, weights and scores in bfloat16), ``"no_gate"`` (the
GQA layer's output gate left out)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W

HI = jax.lax.Precision.HIGHEST
LOW = ("int8", "fp8")
#: the controls that name one departure each (module docstring)
DEPARTURES = ("bf16_state", "bf16_router", "no_gate")

KDA_LEAVES = ("q_proj.weight", "k_proj.weight", "v_proj.weight", "q_conv",
              "k_conv", "v_conv", "f_a_proj.weight", "f_b_proj.weight",
              "dt_bias", "A_log", "b_proj.weight", "g_a_proj.weight",
              "g_b_proj.weight", "o_norm.weight", "o_proj.weight")
GQA_LEAVES = ("q_proj.weight", "k_proj.weight", "v_proj.weight",
              "g_proj.weight", "o_proj.weight")
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
        eps=float(cfg["rms_norm_eps"]),
        nh=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        gqa=tuple(i for i in cfg["gqa_layers"] if i < layers),
        lh=int(lin["num_heads"]), lk=int(lin["head_dim"]),
        taps=int(lin["short_conv_kernel_size"]),
        rank=int(cfg["gate_low_rank"]),
        # (+) the family's public code multiplies beta by 2 where negative
        # eigenvalues are allowed
        beta_scale=2.0 if cfg["kda_allow_neg_eigval"] else 1.0,
        mf=int(cfg["moe_intermediate_size"]),
        held=int(cfg["n_routed_experts"]),
        e_all=int(cfg["n_routed_experts_published"]),
        off=int(cfg["expert_offset"]),
        topk=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]),
        scale=float(cfg["routed_scaling_factor"]),
        renorm=bool(cfg["norm_topk_prob"]))


def _dkey(d):
    return tuple(sorted(d.items()))


def is_kda(d, layer):
    return layer not in d["gqa"]


def is_scale(name):
    """Which leaves are norm scales (made as 1 + N(0, 0.1^2))."""
    return name.endswith("norm.weight")


def layer_specs(cfg, layer):
    d = dims(cfg)
    h, hk = d["h"], d["lh"] * d["lk"]
    if is_kda(d, layer):
        # (+) the decay's and the output gate's low rank is the head size
        shapes = ((h, hk), (h, hk), (h, hk), (d["taps"], hk),
                  (d["taps"], hk), (d["taps"], hk), (h, d["rank"]),
                  (d["rank"], hk), (hk,), (d["lh"],), (h, d["lh"]),
                  (h, d["rank"]), (d["rank"], hk), (d["lk"],), (hk, h))
        attn = list(zip(KDA_LEAVES, shapes))
    else:
        hq, hkv = d["nh"] * d["hd"], d["nkv"] * d["hd"]
        # (+) the gate is a channel wide: hidden -> heads x head_dim
        shapes = ((h, hq), (h, hkv), (h, hkv), (h, hq), (hq, h))
        attn = list(zip(GQA_LEAVES, shapes))
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


def kda_scan(q, k, v, g, beta, precision="f32"):
    """The recurrence, a row at a time from a zero state. q, k, g: [T, H,
    K]; v: [T, H, V]; beta: [T, H]. Returns o [T, H, V]."""
    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, :, None]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt,
                                           precision=HI))
        S = S + kt[:, :, None] * u[:, None, :]
        if precision == "bf16_state":
            # (a cast to bfloat16 and back is one XLA may take out as
            # excess precision, and on the chip it does)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
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
    beta = d["beta_scale"] * jax.nn.sigmoid(_mm(x, wb, precision))
    o = kda_scan(q, k, v, g, beta, precision)
    gate = jax.nn.sigmoid(
        _mm(_mm(x, wga, precision), wgb, precision)).reshape(t, H, K)
    return _mm((_rms(o, on, d["eps"]) * gate).reshape(t, H * K), wo,
               precision)


def _attention(q, k, v, precision, q_block=256):
    """Causal softmax attention of one sequence, query head a on key/value
    head ``a // (Hq / Hk)``. q: [T, Hq, d]; k, v: [T, Hk, d]. Queries in
    blocks so a long sequence's scores fit (25,344 keys x 64 heads x 256
    rows are 1.7 GB)."""
    t, nh, dk = q.shape
    nkv = k.shape[1]
    if precision in LOW:
        q, k, v = (_fq(q, -1, precision), _fq(k, -1, precision),
                   _fq(v, 0, precision))
    qb = min(q_block, t)
    pad = (-t) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
        .reshape(-1, qb, nkv, nh // nkv, dk)
    starts = jnp.arange(qp.shape[0], dtype=jnp.int32) * qb

    def block(args):
        qi, start = args
        # (+) no q/k norm, no positions: the plain scale of the head size
        s = jnp.einsum("qjgd,kjd->jgqk", qi, k, precision=HI) / dk ** 0.5
        rows = start + jnp.arange(qb, dtype=jnp.int32)
        mask = jnp.arange(t, dtype=jnp.int32)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        if precision in LOW:
            p = _fq(p, -1, precision)
        return jnp.einsum("jgqk,kjd->qjgd", p, v, precision=HI)

    return jax.lax.map(block, (qp, starts)).reshape(-1, nh, dk)[:t]


def _gqa(x, lw, d, precision):
    wq, wk, wv, wg, wo = lw
    t, nh, nkv, hd = x.shape[0], d["nh"], d["nkv"], d["hd"]
    q = _mm(x, wq, precision).reshape(t, nh, hd)
    k = _mm(x, wk, precision).reshape(t, nkv, hd)
    v = _mm(x, wv, precision).reshape(t, nkv, hd)
    a = _attention(q, k, v, precision).reshape(t, nh * hd)
    if precision != "no_gate":
        # (+) a sigmoid gate a channel, applied before W_o
        a = a * jax.nn.sigmoid(_mm(x, wg, precision))
    return _mm(a, wo, precision)


def _swiglu(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def route(x, wr, bias, d, precision="f32"):
    """idx [T, k] and weights [T, k] over ALL the published experts.
    Scores in full float32 whatever the 8-bit control's precision: it
    rounds what the experts compute, not which are chosen."""
    if precision == "bf16_router":
        bf = jnp.bfloat16
        s = jax.nn.sigmoid(jnp.matmul(x.astype(bf), wr.astype(bf))) \
            .astype(jnp.float32)
    else:
        # (+) sigmoid scores, a selection-only bias, one group
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
    idx, w = route(x, wr, bias, d, precision)
    return _swiglu(x, sg, su, sd, precision) + routed_part(
        x, idx, w, wg, wu, wd, d["off"], precision)


def _layer(x, lw, d, kda, precision):
    """One block on one sequence. x: [T, h]; lw: the layer's leaves in
    ``layer_specs`` order, float32; ``kda``: its mixer is KDA (else gated
    GQA)."""
    n_attn = len(KDA_LEAVES) if kda else len(GQA_LEAVES)
    attn, ff, (n1, n2) = lw[:n_attn], lw[n_attn:-2], lw[-2:]
    y = _rms(x, n1, d["eps"])
    x = x + (_kda if kda else _gqa)(y, attn, d, precision)
    y = _rms(x, n2, d["eps"])
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
            x = _layer(x, lw, d, is_kda(d, layer), precision)
        x = _rms(x, params["model.norm.weight"], d["eps"])
        return _mm(x, params["lm_head.weight"], precision)


# ---------------------------------------------------------------------------
# serving: the served tokens' logits under the reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dkey", "kda", "precision"))
def _layer_rows(x, lw, dkey, kda, precision):
    d = dict(dkey)
    return jax.lax.map(
        lambda xi: _layer(xi, _f32(lw), d, kda, precision), x)


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
            xs = [_layer_rows(x, lw, dkey, is_kda(d, layer), precision)
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
