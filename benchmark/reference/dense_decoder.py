"""Plain float32 reference of the dense decoder that the Mistral-7B-v0.3
configurations describe: pre-norm blocks of RMSNorm, grouped-query causal
attention with rotary positions (rotate-half, as the published
``modeling_mistral.py``), a SwiGLU feed-forward, a final RMSNorm and an
untied head. Straight ``jax.numpy`` at ``highest`` matmul precision: no
kernels, no cache, no batching tricks. It imports nothing of the program
and takes nothing the program made: it makes its weights again from the
seed, a layer at a time (``harness.weights`` is the benchmark's own).

Departures from the published description: none in the mathematics. There
is no sliding window (v0.3 has none). Linear weights are stored [in, out].

``precision="int8"`` or ``"fp8"`` is the CONTROL, not a reference: every
matmul input (activations per row, weights per output column, keys and
values per head) is rounded to 8 bits with an absmax scale first — the
nearest precision below the bfloat16 the configurations state."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights as W

HI = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                "mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight", "input_layernorm.weight",
                "post_attention_layernorm.weight")


def dims(cfg):
    h, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or h // nh)
    return dict(h=h, nh=nh, nkv=int(cfg["num_key_value_heads"]), hd=hd,
                ff=int(cfg["intermediate_size"]), v=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def layer_specs(cfg, layer):
    d = dims(cfg)
    shapes = ((d["h"], d["nh"] * d["hd"]), (d["h"], d["nkv"] * d["hd"]),
              (d["h"], d["nkv"] * d["hd"]), (d["nh"] * d["hd"], d["h"]),
              (d["h"], d["ff"]), (d["h"], d["ff"]), (d["ff"], d["h"]),
              (d["h"],), (d["h"],))
    return [(f"llama.layers.{layer}.{leaf}", shape)
            for leaf, shape in zip(LAYER_LEAVES, shapes)]


def outer_specs(cfg):
    d = dims(cfg)
    return [("llama.embed_tokens.weight", (d["v"], d["h"])),
            ("llama.norm.weight", (d["h"],)),
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

LOW = ("int8", "fp8")


def _fq(x, axis, precision):
    """Round to 8 bits with an absmax scale along ``axis`` — integers, or
    float8 e4m3 (3 bits of mantissa); the gradient passes straight
    through."""
    top = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if precision == "int8":
        scale = jnp.where(top == 0, 1.0, top / 127.0)
        q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    else:
        scale = jnp.where(top == 0, 1.0, top / 448.0)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision):
    if precision in LOW:
        x, w = _fq(x, -1, precision), _fq(w, 0, precision)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, hd]; position t rotates pair (i, i + hd/2) by
    t * theta^(-2i/hd)."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, precision, q_block=512):
    """Causal grouped-query attention of one sequence. q: [T, nh, hd],
    k, v: [T, nkv, hd]. Queries go in blocks so that the score matrix of
    a long sequence fits."""
    t, nh, hd = q.shape
    nkv = k.shape[1]
    if precision in LOW:
        q, k, v = (_fq(q, -1, precision), _fq(k, -1, precision),
                   _fq(v, 0, precision))
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    qb = min(q_block, t)
    pad = (-t) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, nh, hd)
    starts = jnp.arange(qp.shape[0], dtype=jnp.int32) * qb

    def block(args):
        qi, start = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / hd ** 0.5
        rows = start + jnp.arange(qb, dtype=jnp.int32)
        mask = jnp.arange(t, dtype=jnp.int32)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if precision in LOW:
            p = _fq(p, -1, precision)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, (qp, starts))
    return out.reshape(-1, nh, hd)[:t]


def _layer(x, lw, d, precision):
    """One block on one sequence. x: [T, h]; lw: the nine leaves in
    LAYER_LEAVES order, float32."""
    wq, wk, wv, wo, wg, wu, wd, n1, n2 = lw
    t = x.shape[0]
    y = _rms(x, n1, d["eps"])
    q = _rope(_mm(y, wq, precision).reshape(t, d["nh"], d["hd"]), d["theta"])
    k = _rope(_mm(y, wk, precision).reshape(t, d["nkv"], d["hd"]),
              d["theta"])
    v = _mm(y, wv, precision).reshape(t, d["nkv"], d["hd"])
    a = _attention(q, k, v, precision).reshape(t, d["nh"] * d["hd"])
    x = x + _mm(a, wo, precision)
    y = _rms(x, n2, d["eps"])
    ff = jax.nn.silu(_mm(y, wg, precision)) * _mm(y, wu, precision)
    return x + _mm(ff, wd, precision)


def _f32(arrays):
    return [a.astype(jnp.float32) for a in arrays]


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
    the reference, a layer at a time for all of them, and return for each
    the float32 logits at its ``positions`` as [m_i, vocab]. A sequence is
    padded at its end to a multiple of ``pad_to`` (causal, so the padding
    reaches nothing before it; few distinct lengths, few programs)."""
    d = dims(cfg)
    dkey = tuple(sorted(d.items()))
    put = (lambda a: jax.device_put(a, device)) if device is not None \
        else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        emb, norm_w, head_w = [put(a) for a in
                               W.make(seed, outer_specs(cfg))]
        xs = []
        for seq in seqs:
            ids = np.zeros((1, len(seq) + (-len(seq)) % pad_to), np.int32)
            ids[0, :len(seq)] = seq
            xs.append(jnp.take(emb, put(jnp.asarray(ids)), axis=0)
                      .astype(jnp.float32))
        del emb
        for layer in range(d["layers"]):
            lw = [put(a) for a in W.make(seed, layer_specs(cfg, layer))]
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


# ---------------------------------------------------------------------------
# training: loss, first gradient and parameter change of the first steps
# ---------------------------------------------------------------------------

def _forward_loss(params, ids, labels, cfg_key, precision):
    """Mean next-token cross-entropy over a batch, one row at a time (each
    recomputed in the backward pass, so one row's activations live at
    once)."""
    d = dict(cfg_key)

    @jax.checkpoint
    def row(ids_r, lbl_r):
        x = jnp.take(params["llama.embed_tokens.weight"], ids_r, axis=0)
        for layer in range(d["layers"]):
            lw = [params[f"llama.layers.{layer}.{leaf}"]
                  for leaf in LAYER_LEAVES]
            x = _layer(x, lw, d, precision)
        x = _rms(x, params["llama.norm.weight"], d["eps"])
        logits = _mm(x, params["lm_head.weight"], precision)
        logz = jax.nn.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, lbl_r[:, None], -1)[:, 0]
        return jnp.mean(logz - tgt)

    def body(acc, xs):
        return acc + row(*xs), None

    total, _ = jax.lax.scan(body, jnp.float32(0), (ids, labels))
    return total / ids.shape[0]


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _loss_and_grads(params, ids, labels, cfg_key, precision):
    return jax.value_and_grad(_forward_loss)(params, ids, labels, cfg_key,
                                             precision)


@functools.partial(jax.jit, static_argnames=("hyper",),
                   donate_argnums=(0, 1, 2))
def _adamw(params, m, v, grads, step, hyper):
    """AdamW (Loshchilov & Hutter), decay decoupled and applied to every
    leaf: p <- p - lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)."""
    lr, b1, b2, eps, wd = hyper
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    out_p, out_m, out_v = {}, {}, {}
    for name, p in params.items():
        g = grads[name]
        out_m[name] = b1 * m[name] + (1 - b1) * g
        out_v[name] = b2 * v[name] + (1 - b2) * g * g
        upd = (out_m[name] / bc1) / (jnp.sqrt(out_v[name] / bc2) + eps)
        out_p[name] = p - lr * (upd + wd * p)
    return out_p, out_m, out_v


def _norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def train_steps(seed, cfg, batches, hyper, precision="f32"):
    """Follow the first ``len(batches)`` steps from the seeded weights in
    float32. ``batches``: [(ids [B, S], labels [B, S])]; ``hyper``: (lr,
    beta1, beta2, eps, weight_decay). Returns the loss of each step, the
    norm of every leaf of the first gradient, and the norm of every leaf's
    change after the last step."""
    cfg_key = tuple(sorted(dims(cfg).items()))
    all_specs = specs(cfg)
    names = [n for n, _ in all_specs]
    with jax.default_matmul_precision("highest"):
        params = {n: a.astype(jnp.float32)
                  for n, a in zip(names, W.make(seed, all_specs))}
        m = {n: jnp.zeros_like(a) for n, a in params.items()}
        v = {n: jnp.zeros_like(a) for n, a in params.items()}
        losses, grad_norms = [], None
        for i, (ids, labels) in enumerate(batches):
            loss, grads = _loss_and_grads(
                params, jnp.asarray(ids, jnp.int32),
                jnp.asarray(labels, jnp.int32), cfg_key, precision)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = _norms(grads)
            params, m, v = _adamw(params, m, v, grads, jnp.float32(i + 1),
                                  tuple(float(h) for h in hyper))
            del grads
        del m, v
        delta = {}
        for (name, _), start in zip(all_specs, W.make(seed, all_specs)):
            delta[name] = params.pop(name) - start.astype(jnp.float32)
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": _norms(delta)}
