"""Memory-residency model for train steps: what the backward saves.

The fit proofs (tests/test_7b_scale.py) decompose per-device residency into

1. state — exact, from the compiled program's ``argument_size_in_bytes``;
2. backward residuals — trace-level, from jax's ``saved_residuals`` (the
   only backend-independent view that SEES remat; the CPU backend's
   ``temp_size_in_bytes`` is remat-blind, measured in round 3);
3. in-segment transients — the remainder against the TPU compiler's
   ``peak_memory_in_bytes`` (not measured on the chip under the installed
   toolchain).

``saved_residuals`` is a PRIVATE jax API (jax._src.ad_checkpoint) — this
module is the single import site, with a loud failure naming the
dependency when a jax upgrade moves it.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def saved_residuals_compat(f, *args):
    """jax's saved_residuals, isolated behind one loud-failure import.

    Raises RuntimeError (not ImportError) with a clear message when the
    private API moves, so callers (the tests skip) can react
    instead of dying on an opaque AttributeError."""
    try:
        from jax._src.ad_checkpoint import saved_residuals
    except ImportError as e:  # pragma: no cover - jax upgrade path
        raise RuntimeError(
            "jax._src.ad_checkpoint.saved_residuals is gone in this jax "
            f"version ({jax.__version__}) — the residual-bytes memory model "
            "needs a replacement entry point (see "
            "paddle_tpu/utils/memory_model.py)") from e
    return saved_residuals(f, *args)


def residual_bytes(step, batch, dp_shards=1, seq_len=None):
    """Bytes the backward of a TrainStep saves between forward and backward
    (trace-level, backend-independent), EXCLUDING primal arguments (params —
    already counted in the compiled argument bytes).

    ``dp_shards``: degree of the data-parallel (ZeRO sharding) axis the
    batch is sharded over — batch-carrying residuals (leading dim B or B*S)
    are counted at 1/dp_shards per device; everything else fully replicated
    (conservative: layer boundaries are replicated under pure TP).

    ``seq_len`` non-None additionally ASSERTS no S x S residual survived
    (remat failure guard). Returns total bytes."""
    from ..jit.api import _make_loss_of, _split_leaves
    from ..jit.functional_call import read_values

    dyn, static_key, layout, treedef = _split_leaves(batch)
    # closed-over leaves must be concrete under this trace; batches are tiny
    dyn = [jnp.zeros(v.shape, v.dtype) if isinstance(v, jax.ShapeDtypeStruct)
           else v for v in dyn]
    loss_of_full = _make_loss_of(step.model, step.loss_fn, step.params,
                                 step.frozen, step.buffers, static_key,
                                 layout, treedef)
    frozen_vals = read_values(step.frozen)
    buf_vals = read_values(step.buffers)
    rng_key = jax.random.key(0)  # closed over: must be a real key array
    pv = read_values(step.params)
    batch_leading = set()
    for v in dyn:
        shape = getattr(v, "shape", ())
        if shape:
            batch_leading.add(shape[0])
            if len(shape) > 1:
                batch_leading.add(shape[0] * shape[1])

    def f(pv):
        loss, _bufs = loss_of_full(pv, frozen_vals, buf_vals, rng_key, dyn)
        return loss

    total = 0
    for aval, src in saved_residuals_compat(f, pv):
        if not getattr(aval, "shape", None):
            continue
        if "from the argument" in str(src):
            continue  # params: counted in compiled argument bytes
        shape = tuple(aval.shape)
        if seq_len is not None:
            assert not (seq_len in shape and shape.count(seq_len) >= 2), \
                f"S x S residual survived remat: {shape} ({src})"
        bytes_ = int(np.prod(shape)) * aval.dtype.itemsize
        if dp_shards > 1 and shape[0] in batch_leading:
            bytes_ //= dp_shards
        total += bytes_
    return total
