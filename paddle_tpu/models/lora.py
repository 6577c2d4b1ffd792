"""Trace-time LoRA delta context — the MODEL-side half of batched
multi-LoRA serving (the serving-side store/cache/policy live in
:mod:`paddle_tpu.serving.adapters`; this module sits below the model so
``models/llama.py`` can consult it without importing the serving
package).

The engine arms :func:`lora_scope` around its traced model calls with a
pack of TRACED arrays — stacked per-target low-rank factors plus the
per-batch-row device slot vector — and each llama projection asks
:func:`active_lora` whether to add the gathered per-slot delta
``(x @ A[s, l]) @ B[s, l] * alpha[s]`` to its base output. With no scope
armed (the pack is None / the engine has no adapters) the model body
traces completely untouched, so base serving stays bit-identical to the
pre-adapter engine.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = ["LORA_TARGETS", "lora_target_dims", "lora_scope", "active_lora"]

#: the llama projections an adapter may target, with their (sub-layer,
#: attr) path inside a LlamaDecoderLayer — THE one copy of the table;
#: the store's shape validation, the device stacks, the model-side
#: delta application and apply_merged all consume it.
LORA_TARGETS = (
    ("q_proj", "self_attn"), ("k_proj", "self_attn"),
    ("v_proj", "self_attn"), ("o_proj", "self_attn"),
    ("gate_proj", "mlp"), ("up_proj", "mlp"), ("down_proj", "mlp"),
)


def lora_target_dims(config):
    """target -> (d_in, d_out) for a LlamaConfig."""
    hd = config.hidden_size // config.num_attention_heads
    d = config.hidden_size
    dq = config.num_attention_heads * hd
    dkv = config.num_key_value_heads * hd
    ff = config.intermediate_size
    return {"q_proj": (d, dq), "k_proj": (d, dkv), "v_proj": (d, dkv),
            "o_proj": (dq, d), "gate_proj": (d, ff), "up_proj": (d, ff),
            "down_proj": (ff, d)}


class _LoraState(threading.local):
    ctx = None


_STATE = _LoraState()


class _LoraApply:
    """The armed context: the traced stacks + per-batch-row device slots
    of ONE dispatch, applying the gathered delta on demand."""

    __slots__ = ("A", "B", "alpha", "slots", "rows")

    def __init__(self, pack):
        self.A = pack["A"]
        self.B = pack["B"]
        self.alpha = pack["alpha"]
        self.slots = pack["slots"]
        #: a mixed step's RowMap (models/cache_layout.py): the
        #: projections then run on the packed rows [1, T, d]
        self.rows = pack.get("rows")

    def apply(self, target, layer_idx, x, base):
        """``base + (x @ A[s, l]) @ B[s, l] * alpha[s]`` with ``s`` the
        per-row device slot — fp32 accumulation, cast back to the base
        dtype. ``x``/``base`` are framework Tensors [B, S, d_in/d_out];
        slot 0 gathers the all-zeros base row (delta exactly 0). On a
        mixed step's packed rows the delta is computed on the per-slot
        view (one adapter a slot) and gathered back."""
        import jax.numpy as jnp
        from ..core.tensor import dispatch

        A, Bm = self.A.get(target), self.B.get(target)
        if A is None or Bm is None:
            return base
        alpha, slots, rows = self.alpha, self.slots, self.rows
        li = int(layer_idx)

        def f(xv, bv):
            Ag = A[slots, li]                   # [B, d_in, r]
            Bg = Bm[slots, li]                  # [B, r, d_out]
            al = alpha[slots]                   # [B]
            if rows is not None:
                xv = rows.to_slots(xv[0])
            h = jnp.einsum("bsd,bdr->bsr", xv.astype(jnp.float32), Ag)
            d = jnp.einsum("bsr,bro->bso", h, Bg) * al[:, None, None]
            if rows is not None:
                d = rows.from_slots(d)[None]
            return bv + d.astype(bv.dtype)

        return dispatch(f, (x, base), {}, name=f"lora_{target}")


@contextlib.contextmanager
def lora_scope(pack):
    """Arm the LoRA delta for every llama projection dispatched inside —
    the engine wraps its traced model calls in this. ``pack`` is
    ``{"A": {target: [S, L, d_in, r]}, "B": {...}, "alpha": [S],
    "slots": [B]}`` of TRACED arrays (device slots per batch row; 0 =
    base). ``pack=None`` is inert: the model body traces untouched."""
    if pack is None:
        yield
        return
    prev = _STATE.ctx
    _STATE.ctx = _LoraApply(pack)
    try:
        yield
    finally:
        _STATE.ctx = prev


def active_lora():
    """The armed :class:`_LoraApply`, or None — the model-side hook
    (one attribute read on the untraced path)."""
    return _STATE.ctx
