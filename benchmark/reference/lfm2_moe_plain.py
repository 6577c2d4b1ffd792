"""Plain float32 reference of the LFM2-MoE decoder (``config.json`` of
``LiquidAI/LFM2-24B-A2B``, ``model_type: "lfm2_moe"``): pre-norm blocks
whose operator is, by ``layer_types``, either a GATED SHORT CONVOLUTION
("conv") or softmax attention over grouped keys and values with a norm a
head on q and k and rotary positions ("full_attention"); a dense SwiGLU in
the leading ``num_dense_layers`` and routed experts WITHOUT a shared expert
in the rest; a final RMSNorm (the family's ``embedding_norm``) and a head
TIED to the embedding. No bias anywhere. Straight ``jax.numpy`` at
``highest`` matmul precision: no kernels, no cache, no batching, the
convolution over the whole sequence at once, full causal softmax in blocks
of query rows, the experts a plain loop over every one of them. It imports
nothing of the program and makes its weights again from the seed, a layer
at a time (an expert layer is 2.4 GB in float32).

The equations, one sequence, rows t = 0..T-1 at positions p = t, ``u`` the
block's normed input, RMSNorm ``x / sqrt(mean(x^2) + norm_eps) * w``. Every
line marked (+) is NOT settled by a key of ``config.json`` and is listed
under ``assumed`` in the configuration's file.

ShortConv: ``[B, C, z] = u W_in``, hidden -> 3 x hidden, the thirds in
that order (+); ``s_t = B_t * z_t``; ``c_t = sum_{j=0..L-1} w[j] *
s_{t-(L-1)+j}`` a channel, ``L = conv_L_cache`` taps, ``s`` zero before row
0, no bias (``conv_bias`` false) and NO activation; ``out_t = (C_t * c_t)
W_out``.

GQA, Hq = ``num_attention_heads`` query heads on Hk =
``num_key_value_heads`` key/value heads of d = ``head_dim`` (+: hidden /
heads), query head a reads key/value head ``a // (Hq / Hk)``: ``q, k, v = u
W_q, u W_k, u W_v``; ``q_a <- RMSNorm(q_a)``, ``k_j <- RMSNorm(k_j)`` over
the head's d values, one learned [d] scale each; then rotary over all d
values in the rotate-half pairing (+): value i of the first half turns with
value i of the second by ``p theta^(-2i / d)``, ``theta =
rope_parameters.rope_theta``; causal ``softmax(q_a . k_j / sqrt(d)) v_j``;
``out = concat_a(o_a) W_o``.

Experts (layers from ``num_dense_layers`` on): ``p = sigmoid(n W_r)`` over
ALL the published experts in float32; the ``num_experts_per_tok`` largest
of ``p + expert_bias`` (``use_expert_bias``: the bias SELECTS and does not
weigh); ``g_e = p_e / (sum_chosen p + 1e-6) * routed_scaling_factor`` (+:
the 1e-6) (``norm_topk_prob``); ``y = sum over the chosen experts HELD here
of g_e E_e(n)``, ``E(x) = (SiLU(x W_g) * x W_u) W_d`` (+: ``hidden_act``
silu). This configuration holds experts ``expert_offset .. expert_offset +
num_experts - 1`` of ``num_experts_published`` (all 64 of 64 as shipped).

Linear weights are stored [in, out], the convolution's [taps, channels]
(tap ``taps - 1`` multiplies the current row), the held experts stacked [E,
in, out].

``precision`` other than ``"f32"`` is a CONTROL, not a reference.
``"int8"`` / ``"fp8"``: every matmul input is rounded to 8 bits with an
absmax scale first (and the attention's q, k, v, and the convolution's
``s``), the nearest precision below the stated bfloat16; it rounds what is
computed, not what is CHOSEN: the router's scores stay float32. Two more
name ONE departure each, everything else float32, to show that the
comparison catches it: ``"no_qk_norm"`` (q and k rotated as projected) and
``"bias_weighs"`` (the experts weighed by ``p + expert_bias``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W

HI = jax.lax.Precision.HIGHEST
LOW = ("int8", "fp8")
#: the controls that name one departure each (module docstring)
DEPARTURES = ("no_qk_norm", "bias_weighs")

CONV_LEAVES = ("in_proj.weight", "conv", "out_proj.weight")
GQA_LEAVES = ("q_proj.weight", "k_proj.weight", "v_proj.weight",
              "q_layernorm.weight", "k_layernorm.weight", "o_proj.weight")
DENSE_LEAVES = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")
MOE_LEAVES = ("gate.weight", "gate.e_score_correction_bias",
              "experts.gate_proj", "experts.up_proj", "experts.down_proj")
NORM_LEAVES = ("input_layernorm.weight", "post_attention_layernorm.weight")


def dims(cfg):
    layers = int(cfg["num_hidden_layers"])
    h, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(
        h=h, v=int(cfg["vocab_size"]), layers=layers,
        eps=float(cfg["norm_eps"]), nh=nh,
        nkv=int(cfg["num_key_value_heads"]),
        # (+) the head size is hidden / heads where no key states it
        hd=int(cfg.get("head_dim") or h // nh),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        kinds=tuple(cfg["layer_types"][:layers]),
        taps=int(cfg["conv_L_cache"]), dense=int(cfg["num_dense_layers"]),
        f=int(cfg["intermediate_size"]),
        mf=int(cfg["moe_intermediate_size"]),
        held=int(cfg["num_experts"]),
        e_all=int(cfg.get("num_experts_published", cfg["num_experts"])),
        off=int(cfg.get("expert_offset", 0)),
        topk=int(cfg["num_experts_per_tok"]),
        scale=float(cfg["routed_scaling_factor"]),
        renorm=bool(cfg["norm_topk_prob"]))


def _dkey(d):
    return tuple(sorted(d.items()))


def is_conv(d, layer):
    return d["kinds"][layer] != "full_attention"


def is_scale(name):
    """Which leaves are norm scales (made as 1 + N(0, 0.1^2)): the blocks'
    and the final norm, and the q and k norms a head."""
    return name.endswith("norm.weight")


def layer_specs(cfg, layer):
    d = dims(cfg)
    h = d["h"]
    if is_conv(d, layer):
        op = list(zip(CONV_LEAVES, ((h, 3 * h), (d["taps"], h), (h, h))))
    else:
        hq, hkv = d["nh"] * d["hd"], d["nkv"] * d["hd"]
        op = list(zip(GQA_LEAVES, ((h, hq), (h, hkv), (h, hkv), (d["hd"],),
                                   (d["hd"],), (hq, h))))
    if layer < d["dense"]:
        ff = list(zip(DENSE_LEAVES, ((h, d["f"]), (h, d["f"]), (d["f"], h))))
    else:
        e, f = d["held"], d["mf"]
        ff = list(zip(MOE_LEAVES, ((h, d["e_all"]), (d["e_all"],),
                                   (e, h, f), (e, h, f), (e, f, h))))
    pre = f"model.layers.{layer}."
    return ([(pre + "self_attn." + n, s) for n, s in op]
            + [(pre + "mlp." + n, s) for n, s in ff]
            + [(pre + n, (h,)) for n in NORM_LEAVES])


def outer_specs(cfg):
    """The embedding, which is the head too, and the final norm."""
    d = dims(cfg)
    return [("model.embed_tokens.weight", (d["v"], d["h"])),
            ("model.norm.weight", (d["h"],))]


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


def short_conv(u, lw, d, precision="f32"):
    """u: [T, h]. The whole sequence at once, zeros before row 0."""
    win, cw, wout = lw
    h, taps, t = d["h"], d["taps"], u.shape[0]
    bcz = _mm(u, win, precision)
    # (+) the thirds are B, C, z in that order
    s = bcz[:, :h] * bcz[:, 2 * h:]
    if precision in LOW:
        s = _fq(s, -1, precision)
    ext = jnp.concatenate([jnp.zeros((taps - 1, h), s.dtype), s])
    c = sum(ext[j:j + t] * cw[j] for j in range(taps))     # no activation
    return _mm(bcz[:, h:2 * h] * c, wout, precision)


def rotate_half(x, pos, theta):
    """x: [T, heads, d] at positions ``pos`` [T]. (+) the rotate-half
    pairing over the whole head: value i of the first half turns with
    value i of the second."""
    dk = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dk, 2, dtype=jnp.float32) / dk)
    angle = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :dk // 2], x[..., dk // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v, precision, q_block=256):
    """Causal softmax attention of one sequence, query head a on key/value
    head ``a // (Hq / Hk)``. q: [T, Hq, d]; k, v: [T, Hk, d]. Queries in
    blocks of rows."""
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
        s = jnp.einsum("qjgd,kjd->jgqk", qi, k, precision=HI) / dk ** 0.5
        rows = start + jnp.arange(qb, dtype=jnp.int32)
        mask = jnp.arange(t, dtype=jnp.int32)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        if precision in LOW:
            p = _fq(p, -1, precision)
        return jnp.einsum("jgqk,kjd->qjgd", p, v, precision=HI)

    return jax.lax.map(block, (qp, starts)).reshape(-1, nh, dk)[:t]


def qk_of(u, lw, d, precision="f32"):
    """The normed, rotated q [T, Hq, d] and k [T, Hk, d] of one sequence
    from position 0."""
    wq, wk, _, qn, kn, _ = lw
    t = u.shape[0]
    q = _mm(u, wq, precision).reshape(t, d["nh"], d["hd"])
    k = _mm(u, wk, precision).reshape(t, d["nkv"], d["hd"])
    if precision != "no_qk_norm":
        q, k = _rms(q, qn, d["eps"]), _rms(k, kn, d["eps"])
    pos = jnp.arange(t, dtype=jnp.int32)
    return rotate_half(q, pos, d["theta"]), rotate_half(k, pos, d["theta"])


def _gqa(u, lw, d, precision):
    t = u.shape[0]
    q, k = qk_of(u, lw, d, precision)
    v = _mm(u, lw[2], precision).reshape(t, d["nkv"], d["hd"])
    a = _attention(q, k, v, precision).reshape(t, d["nh"] * d["hd"])
    return _mm(a, lw[5], precision)


def _swiglu(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def route(x, wr, bias, d, precision="f32"):
    """idx [T, k] and weights [T, k] over ALL the published experts.
    Scores in full float32 whatever the 8-bit control's precision: it
    rounds what the experts compute, not which are chosen."""
    p = jax.nn.sigmoid(jnp.matmul(x, wr, precision=HI))
    _, idx = jax.lax.top_k(p + bias, d["topk"])
    # the bias selects; the weights are the scores themselves
    w = jnp.take_along_axis(
        p + bias if precision == "bias_weighs" else p, idx, axis=-1)
    if d["renorm"]:
        # (+) the family's public code adds 1e-6 to the sum
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
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
    wr, bias, wg, wu, wd = lw
    idx, w = route(x, wr, bias, d, precision)
    return routed_part(x, idx, w, wg, wu, wd, d["off"], precision)


def _layer(x, lw, d, layer, precision):
    """One block on one sequence. x: [T, h]; lw: the layer's leaves in
    ``layer_specs`` order, float32."""
    conv = is_conv(d, layer)
    n_op = len(CONV_LEAVES) if conv else len(GQA_LEAVES)
    op, ff, (n1, n2) = lw[:n_op], lw[n_op:-2], lw[-2:]
    y = _rms(x, n1, d["eps"])
    x = x + (short_conv if conv else _gqa)(y, op, d, precision)
    y = _rms(x, n2, d["eps"])
    if layer < d["dense"]:
        return x + _swiglu(y, *ff, precision)
    return x + _moe(y, ff, d, precision)


def _f32(arrays):
    return [a.astype(jnp.float32) for a in arrays]


def forward_logits(params, ids, cfg, precision="f32"):
    """Logits [T, vocab] of one sequence from a dict of float32 leaves:
    the whole model at once, for the tests' sizes."""
    d = dims(cfg)
    with jax.default_matmul_precision("highest"):
        emb = params["model.embed_tokens.weight"]
        x = jnp.take(emb, ids, axis=0)
        for layer in range(d["layers"]):
            lw = [params[n] for n, _ in layer_specs(cfg, layer)]
            x = _layer(x, lw, d, layer, precision)
        x = _rms(x, params["model.norm.weight"], d["eps"])
        # (+) the head is the embedding's matrix
        return _mm(x, emb.T, precision)


# ---------------------------------------------------------------------------
# serving: the served tokens' logits under the reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dkey", "layer", "precision"))
def _layer_rows(x, lw, dkey, layer, precision):
    d = dict(dkey)
    return jax.lax.map(
        lambda xi: _layer(xi, _f32(lw), d, layer, precision), x)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head_rows(x, pos, norm_w, emb, eps, precision):
    rows = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    rows = _rms(rows, norm_w.astype(jnp.float32), eps)
    return _mm(rows, emb.astype(jnp.float32).T, precision)


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
        emb, norm_w = [put(a) for a in W.make(seed, outer_specs(cfg),
                                              is_scale=is_scale)]
        xs = []
        for seq in seqs:
            ids = np.zeros((1, len(seq) + (-len(seq)) % pad_to), np.int32)
            ids[0, :len(seq)] = seq
            xs.append(jnp.take(emb, put(jnp.asarray(ids)), axis=0)
                      .astype(jnp.float32))
        for layer in range(d["layers"]):
            lw = [put(a) for a in W.make(seed, layer_specs(cfg, layer),
                                         is_scale=is_scale)]
            xs = [_layer_rows(x, lw, dkey, layer, precision) for x in xs]
            del lw
        m = max(len(p) for p in positions)
        m += (-m) % 128
        out = []
        for x, pos in zip(xs, positions):
            padded = np.zeros((1, m), np.int32)
            padded[0, :len(pos)] = pos
            out.append(_head_rows(x, put(jnp.asarray(padded)), norm_w, emb,
                                  d["eps"], precision)[0, :len(pos)])
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
