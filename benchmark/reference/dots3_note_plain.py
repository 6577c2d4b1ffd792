"""Plain float32 reference of the dots3-note-prev language model
(``config.json`` of ``dots-studio/dots3-note-prev``, ``model_type:
"dots3_note"``): pre-norm blocks whose mixer is, by ``layer_types``,
either latent attention over the positions a LEARNED INDEXER selects
("full_attention") or latent attention of a second geometry over a sliding
window ("sliding_attention"), each with a sigmoid gate a head on its
output; a dense SwiGLU in the first layer and routed experts beside one
shared expert in the rest; a final RMSNorm and an untied head. Straight
``jax.numpy`` at ``highest`` matmul precision: no kernels, no cache, no
batching; attention with per-head keys and values expanded from the latent
(NOT the absorbed form the program serves), a group of heads at a time in
blocks of query rows; the indexer scores EVERY causal pair in float32,
takes the exact top-k a row, and the attention masks everything outside
that set; the window is a mask; the experts a plain loop. It imports
nothing of the program and makes its weights again from the seed, a layer
at a time.

The equations, one sequence, rows t = 0..T-1 at positions p = t, ``u`` the
block's normed input. Every line marked (+) is NOT settled by a key of
``config.json`` and is listed under ``assumed`` in the configuration's
file.

SparseMLA (the ``full_attention`` layers), H = ``num_attention_heads``:
``c_q = a_q RMSNorm(u W_qa)``, ``[q_nope_h; q_pe_h] = c_q W_qb,h``; ``[c;
k_pe] = u W_kva``, ``c <- a_kv RMSNorm(c)``, ``[k_nope_h; v_h] = c
W_kvb,h``; ``a_q = (hidden_size / q_lora_rank)^1/2``, ``a_kv =
(hidden_size / kv_lora_rank)^1/2`` (+); ``q_pe_h`` and the one shared
``k_pe`` rotated by position, pairs ``(2i, 2i + 1)`` by ``p theta^(-2i /
dp)`` (+). Indexer, J = ``index_n_heads`` heads of ``index_head_dim``:
``qI_j = c_q W_qI,j`` (from the SAME ``c_q``) (+), ``kI = LayerNorm(u
W_kI)`` with scale and bias, eps 1e-6 (+), the first ``qk_rope_head_dim``
values of each rotated by position (+), ``w = u W_w J^-1/2
index_head_dim^-1/2``, ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])``
for s <= t in float32; ``S_t`` = the ``min(index_topk, t + 1)`` positions s
<= t of largest ``I[t, s]``, ties to the lower s. ``o_h[t] = sum_{s in
S_t} softmax_{s in S_t}((q_nope_h . k_nope_h[s] + q_pe_h . k_pe[s]) (dn +
dp)^-1/2) v_h[s]``; ``out = concat_h(g_h o_h) W_o``, ``g = sigmoid(u
W_g)`` one gate a HEAD (+).

WindowMLA (the ``sliding_attention`` layers): the same on the ``swa_``
keys, no indexer; row t attends ``t - sliding_window_size < s <= t``
(itself and the ``sliding_window_size - 1`` before it) (+).

Experts (layers from ``first_k_dense_replace`` on): ``s = sigmoid(n
W_r)`` over ALL the published experts in float32; the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` in one
group (+); ``w = s[chosen] / sum s[chosen] * routed_scaling_factor``; ``y
= E_shared(n) + sum over the chosen experts HELD here of w_e E_e(n)``,
``E(x) = (SiLU(x W_g) * x W_u) W_d``. This configuration holds experts
``expert_offset .. expert_offset + n_routed_experts - 1`` of
``n_routed_experts_published``: what the absent ones would add is left
out, here as in the program.

Left out, as in the program: the DeepSeek-V3.2 indexer's fp8 quantisation
and the Hadamard rotation before it (an orthogonal map of q and k alike,
which changes no dot product) (+); the vision tower, the audio encoder and
the MTP module, which the configuration does not describe. Linear weights
are stored [in, out], the held experts stacked [E, in, out].

``precision="int8"`` or ``"fp8"`` is the CONTROL, not a reference: every
matmul input is rounded to 8 bits with an absmax scale first (and the
attention's q, k, v), the nearest precision below the stated bfloat16. The
control rounds what is computed, not what is CHOSEN: the router's scores
and the indexer's scores stay float32."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W

HI = jax.lax.Precision.HIGHEST
LOW = ("int8", "fp8")
#: named departures from the mathematics, for the tests: each has to move
#: the logits by far more than the comparison's tolerance. ``no_gate``: the
#: heads' outputs ungated; ``no_rescale``: the latents as their norms
#: leave them; ``recent_topk``: a full layer attends its most recent
#: ``index_topk`` positions in place of the indexer's choice;
#: ``window_plus_one``: a window layer attends one position more
DEPARTURES = ("no_gate", "no_rescale", "recent_topk", "window_plus_one")
#: no departure but a question: ``bf16_index`` rounds the index queries and
#: keys to bfloat16, which is what the served pool and rows hold, and
#: leaves everything else float32: how much of a served gap is the CHOICE
#: of positions moving under rounding (PERF.md section 6, PR 45)
ROUNDED_INDEX = "bf16_index"

MLA_LEAVES = ("q_a_proj.weight", "q_a_layernorm.weight", "q_b_proj.weight",
              "kv_a_proj.weight", "kv_a_layernorm.weight",
              "kv_b_proj.weight", "o_proj.weight", "g_proj.weight")
INDEX_LEAVES = ("indexer.wq_b.weight", "indexer.wk.weight",
                "indexer.k_norm.weight", "indexer.k_norm.bias",
                "indexer.weights_proj.weight")
DENSE_LEAVES = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")
MOE_LEAVES = ("gate.weight", "gate.e_score_correction_bias",
              "experts.gate_proj", "experts.up_proj", "experts.down_proj",
              "shared_experts.gate_proj.weight",
              "shared_experts.up_proj.weight",
              "shared_experts.down_proj.weight")
NORM_LEAVES = ("input_layernorm.weight", "post_attention_layernorm.weight")
#: eps of the indexer's LayerNorm (+)
INDEX_NORM_EPS = 1e-6


def _geometry(cfg, pre):
    """One mixer's sizes from the keys with prefix ``pre`` ("" or
    "swa_")."""
    h = int(cfg["hidden_size"])
    nope = int(cfg[pre + "qk_nope_head_dim"])
    pe = int(cfg[pre + "qk_rope_head_dim"])
    rq, r = int(cfg[pre + "q_lora_rank"]), int(cfg[pre + "kv_lora_rank"])
    theta = float(cfg[pre + "rope_theta"])
    i = np.arange(pe // 2, dtype=np.float64)
    return dict(
        nh=int(cfg[pre + "num_attention_heads"]), rq=rq, r=r, dn=nope,
        dp=pe, dv=int(cfg[pre + "v_head_dim"]),
        # (+) plain rotary frequencies: rope_scaling is null
        freqs=tuple(float(f) for f in theta ** (-2.0 * i / pe)),
        # (+) apply_mla_qkv_lora_rescale: the latents are rescaled to the
        # hidden size's magnitude after their norms
        a_q=(h / rq) ** 0.5, a_kv=(h / r) ** 0.5,
        attn_scale=(nope + pe) ** -0.5)


def dims(cfg):
    layers = int(cfg["num_hidden_layers"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]), layers=layers,
        eps=float(cfg["rms_norm_eps"]), ff=int(cfg["intermediate_size"]),
        full=_dkey(_geometry(cfg, "")), swa=_dkey(_geometry(cfg, "swa_")),
        # layer_types as published, cut to the depth
        kinds=tuple(cfg["layer_types"][:layers]),
        window=int(cfg["sliding_window_size"]),
        ij=int(cfg["index_n_heads"]), di=int(cfg["index_head_dim"]),
        topk=int(cfg["index_topk"]),
        dense=int(cfg["first_k_dense_replace"]),
        mf=int(cfg["moe_intermediate_size"]),
        held=int(cfg["n_routed_experts"]),
        e_all=int(cfg["n_routed_experts_published"]),
        off=int(cfg["expert_offset"]),
        k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]),
        scale=float(cfg["routed_scaling_factor"]),
        renorm=bool(cfg["norm_topk_prob"]))


def _dkey(d):
    return tuple(sorted(d.items()))


def is_full(d, layer):
    return d["kinds"][layer] == "full_attention"


def is_scale(name):
    """Which leaves are norm scales (made as 1 + N(0, 0.1^2))."""
    return name.endswith("norm.weight")


def layer_specs(cfg, layer):
    d = dims(cfg)
    h = d["h"]
    full = is_full(d, layer)
    g = dict(d["full"] if full else d["swa"])
    nh = g["nh"]
    attn = list(zip(MLA_LEAVES, (
        (h, g["rq"]), (g["rq"],), (g["rq"], nh * (g["dn"] + g["dp"])),
        (h, g["r"] + g["dp"]), (g["r"],),
        (g["r"], nh * (g["dn"] + g["dv"])), (nh * g["dv"], h), (h, nh))))
    if full:
        attn += list(zip(INDEX_LEAVES, (
            (g["rq"], d["ij"] * d["di"]), (h, d["di"]), (d["di"],),
            (d["di"],), (h, d["ij"]))))
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


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rotate(x, freqs):
    """``R_p`` on the last axis of ``x`` [T, ..., dp], row t at position
    t: out[2i] = x[2i] cos - x[2i+1] sin, out[2i+1] = x[2i] sin + x[2i+1]
    cos, the angle ``t f_i`` (+: the interleaved pairing)."""
    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    angle = pos[:, None] * jnp.asarray(freqs, jnp.float32)
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * cos - odd * sin)
    return out.at[..., 1::2].set(even * sin + odd * cos)


def index_operands(u, cq, lw, d, g, rounded=False):
    """``(qI [T, J, di], kI [T, di], w [T, J])`` of one sequence, rotated.
    Float32 whatever the control's precision: the control rounds what is
    attended, not what is chosen. ``rounded``: ``qI`` and ``kI`` through
    bfloat16 (:data:`ROUNDED_INDEX`)."""
    wqi, wki, kn_w, kn_b, ww = lw
    t, j, di, dp = u.shape[0], d["ij"], d["di"], g["dp"]
    qi = jnp.matmul(cq, wqi, precision=HI).reshape(t, j, di)   # (+) from c_q
    ki = _layer_norm(jnp.matmul(u, wki, precision=HI), kn_w, kn_b,
                     INDEX_NORM_EPS)                            # (+)
    # (+) the first dp values of each rotated, the full layers' theta
    qi = jnp.concatenate([rotate(qi[..., :dp], g["freqs"]), qi[..., dp:]],
                         -1)
    ki = jnp.concatenate([rotate(ki[..., :dp], g["freqs"]), ki[..., dp:]],
                         -1)
    w = jnp.matmul(u, ww, precision=HI) * (j ** -0.5) * (di ** -0.5)
    if rounded:
        qi, ki = (a.astype(jnp.bfloat16).astype(jnp.float32)
                  for a in (qi, ki))
    return qi, ki, w


def index_scores(ops, rows):
    """``I[t, s]`` [len(rows), T] float32 for the query rows ``rows``,
    causal pairs only (the rest -inf)."""
    qi, ki, w = ops
    s = jnp.einsum("tjd,sd->tjs", qi[rows], ki, precision=HI)
    score = jnp.sum(w[rows][:, :, None] * jax.nn.relu(s), axis=1)
    causal = jnp.arange(ki.shape[0], dtype=jnp.int32)[None, :] \
        <= rows[:, None]
    return jnp.where(causal, score, -jnp.inf)


def selected(score, topk):
    """``(idx [rows, k], ok [rows, k])``: the ``min(topk, causal
    positions)`` largest of each row of ``score`` (-inf outside the causal
    pairs), ties to the lower position: ``jax.lax.top_k`` is exact and
    stable. ``ok`` is False for the entries a short row does not have."""
    vals, idx = jax.lax.top_k(score, min(int(topk), score.shape[1]))
    return idx, vals > -jnp.inf


def mask_of(idx, ok, t):
    """[rows, T] True at the selected positions."""
    rows = jnp.arange(idx.shape[0])[:, None]
    return jnp.zeros((idx.shape[0], t), bool).at[rows, idx].max(ok)


def _mixer(u, lw, d, full, precision, q_block=256, head_block=16):
    """One mixer on one sequence. ``u`` [T, h] normed; ``lw`` its leaves in
    ``layer_specs`` order."""
    g = dict(d["full"] if full else d["swa"])
    wqa, qn, wqb, wkva, kn, wkvb, wo, wg = lw[:len(MLA_LEAVES)]
    t, nh, dn, dp, dv, r = u.shape[0], g["nh"], g["dn"], g["dp"], g["dv"], \
        g["r"]
    a_q, a_kv = (1.0, 1.0) if precision == "no_rescale" else \
        (g["a_q"], g["a_kv"])
    window = d["window"] + (precision == "window_plus_one")
    cq = _rms(_mm(u, wqa, precision), qn, d["eps"]) * a_q          # (+)
    kva = _mm(u, wkva, precision)
    c = _rms(kva[:, :r], kn, d["eps"]) * a_kv                      # (+)
    k_pe = rotate(kva[:, r:], g["freqs"])
    qb = min(q_block, t)
    pad = (-t) % qb
    starts = jnp.arange((t + pad) // qb, dtype=jnp.int32) * qb
    pos = jnp.arange(t, dtype=jnp.int32)

    def block_rows(start):
        return jnp.minimum(start + jnp.arange(qb, dtype=jnp.int32), t - 1)

    if full and precision != "recent_topk":
        # S_t once a layer, a block of rows at a time: [blocks, qb, k]
        ops = index_operands(u, cq, lw[len(MLA_LEAVES):], d, g,
                             precision == ROUNDED_INDEX)
        sel = jax.lax.map(lambda start: selected(
            index_scores(ops, block_rows(start)), d["topk"]), starts)

    def allowed(i, start):
        """[qb, T] the pairs the rows of block ``i`` attend."""
        if full and precision != "recent_topk":
            return mask_of(sel[0][i], sel[1][i], t)
        rows = block_rows(start)
        # (+) itself and the window - 1 positions before it
        return (pos[None, :] <= rows[:, None]) & \
            (pos[None, :] > rows[:, None] - (d["topk"] if full else window))

    hb = min(head_block, nh)
    wqb = wqb.reshape(-1, nh, dn + dp)
    wkvb = wkvb.reshape(r, nh, dn + dv)
    outs = []
    for h0 in range(0, nh, hb):
        q = jnp.einsum("tc,chd->thd", *(
            (_fq(cq, -1, precision), _fq(wqb[:, h0:h0 + hb], 0, precision))
            if precision in LOW else (cq, wqb[:, h0:h0 + hb])), precision=HI)
        q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], g["freqs"])],
                            -1)
        kv = jnp.einsum("tc,chd->thd", *(
            (_fq(c, -1, precision), _fq(wkvb[:, h0:h0 + hb], 0, precision))
            if precision in LOW else (c, wkvb[:, h0:h0 + hb])), precision=HI)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (t, hb, dp))], -1)
        v = kv[..., dn:]
        if precision in LOW:
            q, k, v = (_fq(q, -1, precision), _fq(k, -1, precision),
                       _fq(v, 0, precision))
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, hb,
                                                            dn + dp)

        def block(args, k=k, v=v):
            qi, i, start = args
            s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) \
                * g["attn_scale"]
            p = jax.nn.softmax(
                jnp.where(allowed(i, start)[None], s, -jnp.inf), axis=-1)
            if precision in LOW:
                p = _fq(p, -1, precision)
            return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

        outs.append(jax.lax.map(
            block, (qp, jnp.arange(starts.shape[0]), starts))
                    .reshape(-1, hb, dv)[:t])
    o = jnp.concatenate(outs, axis=1)
    # (+) headwise gate: one sigmoid a head from the normed input, on the
    # head's output before W_o
    gate = jax.nn.sigmoid(_mm(u, wg, precision))
    if precision == "no_gate":
        gate = jnp.ones_like(gate)
    return _mm((o * gate[:, :, None]).reshape(t, nh * dv), wo, precision)


def _swiglu(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def route(x, wr, bias, d):
    """idx [T, k] and weights [T, k] over ALL the published experts.
    Scores in full float32 whatever the control's precision."""
    # (+) sigmoid scores, a selection-only bias, ONE group (the config has
    # no n_group / topk_group)
    s = jax.nn.sigmoid(jnp.matmul(x, wr, precision=HI))
    _, idx = jax.lax.top_k(s + bias, d["k"])
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


def _layer(x, lw, d, layer, precision):
    """One block on one sequence. x: [T, h]; lw: the layer's leaves in
    ``layer_specs`` order, float32."""
    full = is_full(d, layer)
    n_attn = len(MLA_LEAVES) + (len(INDEX_LEAVES) if full else 0)
    attn, ff, (n1, n2) = lw[:n_attn], lw[n_attn:-2], lw[-2:]
    x = x + _mixer(_rms(x, n1, d["eps"]), attn, d, full, precision)
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
        x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0)
        for layer in range(d["layers"]):
            lw = [params[n] for n, _ in layer_specs(cfg, layer)]
            x = _layer(x, lw, d, layer, precision)
        x = _rms(x, params["model.norm.weight"], d["eps"])
        return _mm(x, params["lm_head.weight"], precision)


def selected_sets(params, ids, cfg, layer):
    """The mask [T, T] of ``S_t`` in full layer ``layer`` of one sequence
    (the tests compare the program's choice with it)."""
    d = dims(cfg)
    g = dict(d["full"])
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0)
        for i in range(layer):
            lw = [params[n] for n, _ in layer_specs(cfg, i)]
            x = _layer(x, lw, d, i, "f32")
        lw = [params[n] for n, _ in layer_specs(cfg, layer)]
        u = _rms(x, lw[-2], d["eps"])
        cq = _rms(jnp.matmul(u, lw[0], precision=HI), lw[1], d["eps"]) \
            * g["a_q"]
        ilw = lw[len(MLA_LEAVES):len(MLA_LEAVES) + len(INDEX_LEAVES)]
        t = u.shape[0]
        idx, ok = selected(index_scores(
            index_operands(u, cq, ilw, d, g),
            jnp.arange(t, dtype=jnp.int32)), d["topk"])
        return mask_of(idx, ok, t)


# ---------------------------------------------------------------------------
# serving: the served tokens' logits under the reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dkey", "layer", "precision"))
def _layer_rows(x, lw, dkey, layer, precision):
    d = dict(dkey)
    return jax.lax.map(
        lambda xi: _layer(xi, _f32(lw), d, layer, precision), x)


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
            xs = [_layer_rows(x, lw, dkey, layer, precision) for x in xs]
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
