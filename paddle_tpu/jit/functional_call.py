"""Functional-state bridge: run stateful Layers under jax transforms.

The reference needs a whole subsystem to capture python programs into a graph
(SOT bytecode interception — python/paddle/jit/sot; AST transform — jit/dy2static).
Here capture is jax tracing: we temporarily rebind every Parameter/buffer `_value`
to a traced array and call the same eager code. One model definition, two engines —
the analog of the reference's dygraph/static duality without a second IR.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Sequence

import jax

from ..core.tensor import Tensor, functional_mode
from ..nn.layer_base import Layer


def collect_state(layers) -> tuple[list[str], list[Tensor], list[str], list[Tensor]]:
    """Gather (param_names, params, buffer_names, buffers) across layers, deduped."""
    # unwrap delegating model wrappers (DataParallel/_HybridShardedModel/
    # GroupShardedStage3 all proxy a real Layer behind `_model`)
    while not isinstance(layers, (Layer, list, tuple)) \
            and getattr(layers, "_model", None) is not None:
        layers = layers._model
    if isinstance(layers, Layer):
        layers = [layers]
    pnames, params, bnames, buffers = [], [], [], []
    seen = set()
    for li, layer in enumerate(layers):
        prefix = f"layer{li}." if len(layers) > 1 else ""
        for n, p in layer.named_parameters():
            if id(p) not in seen:
                seen.add(id(p))
                pnames.append(prefix + n)
                params.append(p)
        for n, b in layer.named_buffers():
            if b is not None and id(b) not in seen:
                seen.add(id(b))
                bnames.append(prefix + n)
                buffers.append(b)
    return pnames, params, bnames, buffers


class _Stored(threading.local):
    """Trace-time view of where bound tensors are STORED: while
    ``bind_state`` has a tensor's value swapped for a tracer, the tracer
    says nothing about placement, but the array it stands for had one."""

    def __init__(self):
        self.shardings = {}     # id(tensor) -> sharding of the stored array


_stored = _Stored()


@contextlib.contextmanager
def bind_state(tensors: Sequence[Tensor], values):
    """Temporarily swap each tensor's value (e.g. for traced arrays). The
    sharding of each swapped-out stored array stays readable through
    :func:`stored_sharding` for the duration."""
    saved = [t._value for t in tensors]
    seen = _stored.shardings
    prev = {}
    try:
        for t, v, s in zip(tensors, values, saved):
            t._value = v
            if not isinstance(s, jax.core.Tracer):
                prev[id(t)] = seen.get(id(t))
                seen[id(t)] = getattr(s, "sharding", None)
        yield
    finally:
        for t, s in zip(tensors, saved):
            t._value = s
        for key, old in prev.items():
            if old is None:
                seen.pop(key, None)
            else:
                seen[key] = old


def stored_sharding(tensor):
    """The sharding ``tensor``'s array is stored with: its value's own, or
    — inside a trace that bound it — the one recorded by ``bind_state``.
    Layers that route to a Pallas kernel read their weights' placement here:
    GSPMD cannot partition a Mosaic call, so under a sharded layout the
    kernel must shard_map over the mesh the weights live on."""
    value = tensor._value
    if isinstance(value, jax.core.Tracer):
        return _stored.shardings.get(id(tensor))
    return getattr(value, "sharding", None)


def read_values(tensors):
    return [t._value for t in tensors]
