"""Plain float32 reference of the Ouro looped decoder (``config.json`` of
``ByteDance/Ouro-2.6B``, ``model_type: "ouro"``; arXiv 2510.25741): ONE
stack of ``num_hidden_layers`` blocks run ``total_ut_steps`` times a token
with the same weights, each pass with keys and values of its own.
Straight ``jax.numpy`` at ``highest`` matmul precision: no kernels, no
cache, no batching tricks. It imports nothing of the program and takes
nothing the program made: it makes its weights again from the seed, a
layer at a time (``harness.weights`` is the benchmark's own).

The equations, one sequence, rows at positions p = 0..T-1, hidden ``x``,
``N1..N4`` four RMSNorms a layer with scales of their own (eps
``rms_norm_eps``), layer l at loop step t:

    a = x + N2_l( Attn_l( N1_l(x) ) )                        (sandwich norms)
    y = a + N4_l( down_l( silu(gate_l N3_l(a)) * up_l N3_l(a) ) )

``Attn``: ``num_attention_heads`` heads of ``head_dim`` on
``num_key_value_heads`` K/V heads, no biases, rotate-half rotary positions
(pair ``(i, i + head_dim/2)`` turned by ``p theta^(-2i/head_dim)``) on q
and k, causal, softmax scale ``head_dim^-1/2``. The keys and values that
layer l attends at step t are those the SAME layer made at the SAME step t
for the earlier positions: with no cache that is simply the causal
attention of the step's own rows (a serving program keeps one cache a
(t, l) pair, index ``t x layers + l``, and never reads another step's).

    h = E[ids]
    for t in 1..R:   h = Norm_f( Layer_{L-1}( ... Layer_0(h) ... ) )
                     g_t = sigmoid(w_g . h + b_g)
    logits = h W_head                        (h after step R; untied head)

The final norm closes EVERY step and its output is the next step's input.
The exit distribution over steps is ``p_1 = g_1``, ``p_t = g_t prod_{s<t}
(1 - g_s)``, ``p_R`` the remainder; the step served is the first whose
cumulative mass reaches ``early_exit_threshold``: with the published
threshold of 1 that is always step R, so the served logits are step R's
(:func:`exit_distribution` gives the masses; they decide nothing here).

What ``config.json`` has no key for is listed under ``assumed`` in the
configuration's file, each one line below with a comment: the four norms a
layer and where they sit, the final norm inside the loop, one set of keys
and values a (step, layer), the gate a ``Linear(hidden, 1)`` with a bias
on the normed state, rotate-half pairs. Linear weights are stored
[in, out].

**A layer's weights are made again from the seed on each of its R visits**
(``served_logits`` walks the L layers R times and holds one layer at a
time: 51 M values are a few milliseconds of the device's random bits,
against 10 GB for the float32 stack kept whole).

``precision="int8"`` or ``"fp8"`` is the CONTROL, not a reference: every
matmul input (activations per row, weights per output column, queries,
keys and values per head) is rounded to 8 bits with an absmax scale
first, the nearest precision below the bfloat16 the configuration states."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W
# the parts the llama family's reference already writes out plainly, and
# that are the same mathematics here: a product at ``highest`` precision
# (its inputs rounded to 8 bits under the control), RMSNorm, rotate-half
# rotary positions (assumed: pair (i, i + head_dim/2) turned by
# p theta^(-2i/head_dim)), and causal attention of one sequence over the
# keys and values it is given (assumed: those THIS loop step made)
from benchmark.reference.dense_decoder import (HI, _attention, _mm, _rms,
                                               _rope)

LAYER_LEAVES = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                "mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight", "input_layernorm.weight",
                "input_layernorm_2.weight",
                "post_attention_layernorm.weight",
                "post_attention_layernorm_2.weight")


def dims(cfg):
    h, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(h=h, nh=nh, nkv=int(cfg["num_key_value_heads"]),
                hd=int(cfg.get("head_dim") or h // nh),
                ff=int(cfg["intermediate_size"]), v=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                steps=int(cfg["total_ut_steps"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def is_scale(name):
    """Norm scales, made as 1 + N(0, 0.1^2): the four of a layer and the
    final norm (every leaf whose module's name holds ``norm``)."""
    return "norm" in name.rsplit(".", 2)[-2]


def layer_specs(cfg, layer):
    d = dims(cfg)
    shapes = ((d["h"], d["nh"] * d["hd"]), (d["h"], d["nkv"] * d["hd"]),
              (d["h"], d["nkv"] * d["hd"]), (d["nh"] * d["hd"], d["h"]),
              (d["h"], d["ff"]), (d["h"], d["ff"]), (d["ff"], d["h"]),
              (d["h"],), (d["h"],), (d["h"],), (d["h"],))
    return [(f"model.layers.{layer}.{leaf}", shape)
            for leaf, shape in zip(LAYER_LEAVES, shapes)]


def outer_specs(cfg):
    d = dims(cfg)
    return [("model.embed_tokens.weight", (d["v"], d["h"])),
            ("model.norm.weight", (d["h"],)),
            ("model.early_exit_gate.weight", (d["h"], 1)),
            ("model.early_exit_gate.bias", (1,)),
            ("lm_head.weight", (d["h"], d["v"]))]


def specs(cfg):
    """[(name, shape)] of every leaf of the configuration: each weight
    layer ONCE, however often it runs."""
    out = outer_specs(cfg)[:1]
    for layer in range(dims(cfg)["layers"]):
        out += layer_specs(cfg, layer)
    return out + outer_specs(cfg)[1:]


def n_params(cfg):
    return sum(int(np.prod(s)) for _, s in specs(cfg))


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------

def _layer(x, lw, d, precision):
    """One block on one sequence at one loop step. x: [T, h]; lw: the
    eleven leaves in LAYER_LEAVES order, float32."""
    wq, wk, wv, wo, wg, wu, wd, n1, n2, n3, n4 = lw
    t = x.shape[0]
    y = _rms(x, n1, d["eps"])                       # N1: before attention
    q = _rope(_mm(y, wq, precision).reshape(t, d["nh"], d["hd"]), d["theta"])
    k = _rope(_mm(y, wk, precision).reshape(t, d["nkv"], d["hd"]),
              d["theta"])
    v = _mm(y, wv, precision).reshape(t, d["nkv"], d["hd"])
    a = _attention(q, k, v, precision).reshape(t, d["nh"] * d["hd"])
    # N2 (assumed): on the attention's output, before the residual adds it
    x = x + _rms(_mm(a, wo, precision), n2, d["eps"])
    y = _rms(x, n3, d["eps"])                       # N3: before the SwiGLU
    ff = jax.nn.silu(_mm(y, wg, precision)) * _mm(y, wu, precision)
    # N4 (assumed): on the feed-forward's output, before the residual
    return x + _rms(_mm(ff, wd, precision), n4, d["eps"])


def exit_gate(h, wg, bg):
    """g = sigmoid(w_g . h + b_g) on the normed state of a step (assumed:
    ``Linear(hidden, 1)`` with a bias). h: [..., hidden] -> [...]."""
    return jax.nn.sigmoid(jnp.matmul(h, wg, precision=HI)[..., 0] + bg[0])


def exit_distribution(gates):
    """``gates``: [R, ...] the steps' gates. Returns [R, ...]: p_1 = g_1,
    p_t = g_t prod_{s<t}(1 - g_s), p_R the remainder (they add up to 1)."""
    stay, out = jnp.ones_like(gates[0]), []
    for g in gates[:-1]:
        out.append(g * stay)
        stay = stay * (1 - g)
    return jnp.stack(out + [stay])


def _f32(arrays):
    return [a.astype(jnp.float32) for a in arrays]


def forward(params, ids, cfg, precision="f32"):
    """One sequence ``ids`` [T] through the whole model from ``params``
    ({name: array}). Returns (logits [T, vocab] of step R, exit masses
    [R, T]). The tests' form; ``served_logits`` is the same mathematics a
    layer at a time."""
    d = dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["model.embed_tokens.weight"], ids,
                     axis=0).astype(jnp.float32)
        gates = []
        for _ in range(d["steps"]):
            for layer in range(d["layers"]):
                lw = _f32([params[f"model.layers.{layer}.{leaf}"]
                           for leaf in LAYER_LEAVES])
                x = _layer(x, lw, d, precision)
            # assumed: the final norm closes every step, inside the loop
            x = _rms(x, params["model.norm.weight"].astype(jnp.float32),
                     d["eps"])
            gates.append(exit_gate(
                x, *_f32([params["model.early_exit_gate.weight"],
                          params["model.early_exit_gate.bias"]])))
        logits = _mm(x, params["lm_head.weight"].astype(jnp.float32),
                     precision)
        return logits, exit_distribution(jnp.stack(gates))


# ---------------------------------------------------------------------------
# serving: the served tokens' logits under the reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dkey", "precision"))
def _layer_rows(x, lw, dkey, precision):
    d = dict(dkey)
    return jax.lax.map(lambda xi: _layer(xi, _f32(lw), d, precision), x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm_rows(x, norm_w, eps):
    return _rms(x, norm_w.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head_rows(x, pos, head_w, precision):
    rows = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    return _mm(rows, head_w.astype(jnp.float32), precision)


def served_logits(seed, cfg, seqs, positions, precision="f32", device=None,
                  pad_to=128):
    """Teacher-force each of ``seqs`` (prompt and served tokens) through
    the reference, a layer at a time for all of them, R times over the
    stack, and return for each the float32 logits at its ``positions`` as
    [m_i, vocab]. A sequence is padded at its end to a multiple of
    ``pad_to`` (causal, so the padding reaches nothing before it)."""
    d = dims(cfg)
    dkey = tuple(sorted(d.items()))
    put = (lambda a: jax.device_put(a, device)) if device is not None \
        else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        emb, norm_w, _, _, head_w = [
            put(a) for a in W.make(seed, outer_specs(cfg),
                                   is_scale=is_scale)]
        xs = []
        for seq in seqs:
            ids = np.zeros((1, len(seq) + (-len(seq)) % pad_to), np.int32)
            ids[0, :len(seq)] = seq
            xs.append(jnp.take(emb, put(jnp.asarray(ids)), axis=0)
                      .astype(jnp.float32))
        del emb
        for _ in range(d["steps"]):
            for layer in range(d["layers"]):
                # made again on every visit: one layer held at a time
                lw = [put(a) for a in W.make(seed, layer_specs(cfg, layer),
                                             is_scale=is_scale)]
                xs = [_layer_rows(x, lw, dkey, precision) for x in xs]
            xs = [_norm_rows(x, norm_w, d["eps"]) for x in xs]
        m = max(len(p) for p in positions)
        m += (-m) % 128           # few distinct widths, few programs
        out = []
        for x, pos in zip(xs, positions):
            padded = np.zeros((1, m), np.int32)
            padded[0, :len(pos)] = pos
            out.append(_head_rows(x, put(jnp.asarray(padded)), head_w,
                                  precision)[0, :len(pos)])
        return out


def served_gaps(seed, cfg, requests, control=None, device=None, pad_to=128):
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
