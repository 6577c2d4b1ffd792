"""Dropless routing over the experts a chip holds.

The layer routes every live row over ALL the published experts
(:func:`route`: sigmoid scores with a selection bias and renormalised
weights, or softmax scores as they are; optionally limited to the best
groups of experts; the ``top_k`` largest, scaled) and computes the part of
the result that its OWN experts give: experts
``[offset, offset + E)`` of the published count. Every assignment that
lands on a held expert is computed: the assignments are sorted by expert
and go through one grouped product (``grouped_expert_matmul.py``: a Pallas
kernel that reads each non-empty expert's weights once a projection, gate
and up in one call and down in another; ``jax.lax.ragged_dot`` for widths
that do not fill the lanes, a toy model's), so there is no capacity and
nothing is dropped; a dead row is not routed. What the absent experts
would have added is left out, and no code stands in for the chips that
hold them (``ops/kernels/moe.py`` is the capacity-routed GShard layer
with its exchange; no model of the benchmark uses it).

``rows`` is the static height of the grouped product: the caller's bound
on held assignments (live rows x min(top_k, E)), rounded up here to the
kernel's row tile (``grouped_expert_matmul.row_tile``: 16 to 64 rows by
the shapes, so that the last row tile is whole wherever the step's own
height allows it; 8 on the ``ragged_dot`` side, where XLA:TPU takes its
grouped-matmul kernel only for such a height and else multiplies every
row by every expert: read in the compiled HLO). The kernel runs the row
tiles that hold a held row and no other, so the rounding costs nothing
there. The counts that leave with the result say what was asked and what
was computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...profiler import scope
from . import grouped_expert_matmul as _gmm

HI = jax.lax.Precision.HIGHEST
#: what :func:`held_expert_ffn` counts, in order
COUNTERS = ("moe_assignments", "moe_assignments_held", "moe_rows_computed",
            "moe_expert_peak", "moe_assignments_dropped", "moe_rows_held",
            "moe_experts_nonempty", "moe_experts_held")
#: id on a step's ``pt:engine.emit`` span -> the counters whose sum of that
#: step it carries (what the host could not know at dispatch)
EMIT_IDS = {"held_rows": ("moe_assignments_held",),
            "experts_read": ("moe_experts_nonempty",),
            "experts_held": ("moe_experts_held",)}


def route(x, w_router, bias, top_k, scale, renormalize=True,
          scoring="sigmoid", n_group=1, topk_group=1, renorm_eps=0.0):
    """x: [N, h]; w_router: [h, E_all]; bias: [E_all], used to SELECT only,
    or None. Scores (``scoring``: "sigmoid", or "softmax" over the
    experts) and selection in float32. With ``n_group`` > 1 the experts
    are ``n_group`` runs of consecutive ids; a group scores what its best
    expert scores, the ``topk_group`` best groups keep their experts and
    the others' selection scores are 0 before the ``top_k`` are taken.
    The weights are the selected experts' scores, divided by their sum
    (plus ``renorm_eps``, where a family adds one) if ``renormalize``,
    times ``scale``. Returns (idx [N, k] int32, weights
    [N, k] float32)."""
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=HI)
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    sel = s if bias is None else s + bias.astype(jnp.float32)
    if n_group > 1:
        n, e = sel.shape
        best = jnp.max(sel.reshape(n, n_group, e // n_group), axis=-1)
        _, keep = jax.lax.top_k(best, topk_group)
        kept = jnp.any(keep[:, :, None] == jnp.arange(
            n_group, dtype=keep.dtype), axis=1)
        sel = jnp.where(jnp.repeat(kept, e // n_group, axis=1), sel, 0.0)
    _, idx = jax.lax.top_k(sel, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = w / (total + jnp.float32(renorm_eps) if renorm_eps else total)
    return idx.astype(jnp.int32), w * jnp.float32(scale)


def held_expert_ffn(x, idx, w, live, w_gate, w_up, w_down, offset, rows):
    """x: [N, h]; idx, w: [N, k] from :func:`route`; live: [N] bool;
    w_gate, w_up: [E, h, f]; w_down: [E, f, h], the held experts ``offset
    .. offset + E - 1``. Returns (y [N, h] float32, counts int32 in
    :data:`COUNTERS` order; ``moe_rows_held`` is the live rows with at
    least one assignment here: the rows an exchange would bring this
    chip; ``moe_experts_nonempty`` of the ``moe_experts_held`` got a row:
    the share of the held weights the product has to read)."""
    n, k = idx.shape
    e, h, f = w_gate.shape
    tm = _gmm.row_tile(rows, h, f, e, x.dtype)
    rows = int(min(-(-rows // tm) * tm, n * k))
    with scope("pt.dispatch"):
        local = idx - jnp.int32(offset)
        held = live[:, None] & (local >= 0) & (local < e)
        key = jnp.where(held, local, e).reshape(-1)       # e sorts last
        order = jnp.argsort(key, stable=True)[:rows]
        sizes = jnp.zeros((e,), jnp.int32).at[key].add(1, mode="drop")
        n_held = jnp.sum(sizes)
        token = order // k
        xs = jnp.take(x, token, axis=0)
    with scope("pt.experts"):
        out = _gmm.grouped_expert_ffn(xs, w_gate, w_up, w_down, sizes, tm)
    with scope("pt.combine"):
        valid = jnp.arange(rows, dtype=jnp.int32) < n_held
        ws = jnp.where(valid, jnp.take(w.reshape(-1), order), 0.0)
        y = jnp.zeros((n, x.shape[1]), jnp.float32).at[token].add(
            jnp.where(valid[:, None], out, 0.0) * ws[:, None])
    counts = jnp.stack([
        jnp.sum(live).astype(jnp.int32) * k, n_held,
        jnp.int32(rows), jnp.max(sizes),
        jnp.maximum(n_held - rows, 0),
        jnp.sum(jnp.any(held, axis=1)),
        jnp.sum(sizes > 0), jnp.int32(e)]).astype(jnp.int32)
    return y, counts
