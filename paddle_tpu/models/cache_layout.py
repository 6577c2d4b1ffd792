"""What a decoder keeps a layer between steps, as the serving engine sees
it: the model names a KIND a layer and the engine builds, donates, pins and
unpacks the per-layer state from that, knowing no family.

A model that :class:`paddle_tpu.inference.LLMEngine` serves has

- ``decoder``: the module called as ``decoder(ids, kv_caches=caches,
  position_offset=lens)`` -> ``(hidden, new_caches)``,
- ``cache_layout()``: one kind a layer, below,
- ``_logits(hidden)`` and ``config`` (``vocab_size``, ``hidden_size``,
  ``max_position_embeddings``),

and may declare ``step_counter_names``: device-side counts its layers
:func:`count` during a step, which leave the step program beside the
tokens and are booked into ``engine.stats`` at readout.

A kind makes the layer's state ``(a, b)`` (two pytrees of device arrays,
``b`` possibly None: the engine carries every layer's pair through its
programs as it carried K and V pools), the cache object the layer is
handed for one dispatch, and takes the pair back off the returned cache.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np
import jax.numpy as jnp

from ..core.tensor import Tensor


def _val(x):
    return x._value if isinstance(x, Tensor) else x


class PagedKV:
    """Paged K and V pools ``[n_blocks + 1, kv_heads, block, head_dim]``
    (:class:`paddle_tpu.models.llama.PagedKVCache`)."""
    kind = "paged_kv"
    paged = True

    def __init__(self, kv_heads, head_dim):
        self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)

    def bytes_per_token(self, itemsize):
        return 2 * self.kv_heads * self.head_dim * itemsize


class PagedLatent:
    """One paged pool ``[n_blocks + 1, block, width]`` of latent entries on
    the engine's block tables (:class:`LatentPagedCache`)."""
    kind = "paged_latent"
    paged = True

    def __init__(self, width):
        self.width = int(width)

    def bytes_per_token(self, itemsize):
        return self.width * itemsize

    def alloc(self, zeros, n_blocks, block_size, batch, dtype):
        return zeros((n_blocks + 1, block_size, self.width), dtype), None

    def cache(self, a, b, tables, lens, q_lens, active, row_budget):
        return LatentPagedCache(a, tables, lens, _q_lens(q_lens, active),
                                row_budget)

    def unpack(self, cache):
        return _val(cache.pool), None


class Recurrent:
    """A fixed-size state a slot: ``shapes`` = {name: (shape, dtype)} a
    slot, held as ``[max_batch, *shape]`` arrays
    (:class:`RecurrentCache`). Zero at position 0: the layer starts a slot
    whose ``lens`` is 0 from zeros, so assigning a slot resets it."""
    kind = "recurrent"
    paged = False

    def __init__(self, shapes):
        self.shapes = {k: (tuple(int(d) for d in s), np.dtype(dt))
                       for k, (s, dt) in shapes.items()}

    def bytes_per_slot(self):
        return sum(int(np.prod(s)) * dt.itemsize
                   for s, dt in self.shapes.values())

    def alloc(self, zeros, n_blocks, block_size, batch, dtype):
        return {k: zeros((batch,) + s, dt)
                for k, (s, dt) in self.shapes.items()}, None

    def cache(self, a, b, tables, lens, q_lens, active, row_budget):
        return RecurrentCache(a, lens, _q_lens(q_lens, active), row_budget)

    def unpack(self, cache):
        return {k: _val(v) for k, v in cache.state.items()}, None


def _q_lens(q_lens, active):
    """Live rows a slot: the mixed step's ``q_lens``; in a one-token step
    1 for an active slot and 0 for one that is not."""
    if q_lens is not None:
        return q_lens
    return jnp.asarray(active).astype(jnp.int32)


class LatentPagedCache:
    """A paged latent pool for one dispatch: ``pool`` [NB, block, width],
    ``block_tables`` [B, max_blocks], ``seq_lens`` [B] entries already
    held, ``q_lens`` [B] live rows of this step's S (0: the slot writes
    and attends nothing). ``row_budget``: the dispatcher's static bound on
    the step's live rows over all slots (None: every row may be live)."""
    __slots__ = ("pool", "block_tables", "seq_lens", "q_lens", "row_budget")

    def __init__(self, pool, block_tables, seq_lens, q_lens,
                 row_budget=None):
        self.pool, self.block_tables = pool, block_tables
        self.seq_lens, self.q_lens = seq_lens, q_lens
        self.row_budget = row_budget


class RecurrentCache:
    """A recurrent layer's per-slot state for one dispatch: ``state``
    {name: [B, ...]}, ``seq_lens`` [B] tokens already absorbed, ``q_lens``
    [B] live rows of this step (rows past them are identity updates)."""
    __slots__ = ("state", "seq_lens", "q_lens", "row_budget")

    def __init__(self, state, seq_lens, q_lens, row_budget=None):
        self.state, self.seq_lens, self.q_lens = state, seq_lens, q_lens
        self.row_budget = row_budget


# ---------------------------------------------------------------------------
# device-side counts of one step
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextlib.contextmanager
def collect_counts():
    """Collects what the layers :func:`count` while the body runs (one
    traced model call). Yields a list of int32 vectors; sum them."""
    prev = getattr(_TLS, "sink", None)
    _TLS.sink = sink = []
    try:
        yield sink
    finally:
        _TLS.sink = prev


def count(vec):
    """Add one layer's counts (an int32 vector in the order of the model's
    ``step_counter_names``) to the collecting dispatch, if there is one."""
    sink = getattr(_TLS, "sink", None)
    if sink is not None:
        sink.append(vec)
