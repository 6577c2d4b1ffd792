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

**The layouts the engine serves** (a layout is the list of kinds, one a
layer; ``LLMEngine._refuse_for_layout`` names what each cannot take):

1. every layer :class:`PagedKV` (the llama family; the one layout with
   the legacy scheduler, dense buffers, quantized and sharded pools,
   prefix caching, swap, speculation, LoRA);
2. :class:`PagedLatent` in every layer (DeepSeek-V2);
3. :class:`Recurrent` layers beside paged ones -- latent pools
   (Kimi-Linear) or K/V pools (a gated GQA layer among delta-rule layers):
   one block table a slot serves the paged layers, the recurrent layers
   hold ``[max_batch, ...]`` states, and a replayed slot's state is
   zeroed in the graph;
4. :class:`LoopedPagedKV` in every layer (a looped stack);
5. :class:`Recurrent` in every layer (no pool at all).

**What the decoder is handed in a mixed step.** A one-token step hands it
``ids[B, 1]``. A mixed step (the fused scheduler's prefill chunks and
decode tokens in one dispatch) hands it ``ids[1, T]``: ONE PACKED ROW AXIS
of the step's granted rows, slot-major, a slot's rows adjacent and in
position order, ``T`` the scheduler's static bound on them (a few rows of
padding at the end, not ``max_batch x chunk``). Every cache object of
that dispatch carries the same :class:`RowMap` as ``rows`` (None in a
one-token step) beside ``seq_lens`` / ``q_lens``. Embedding, norms,
residuals, projections, feed-forwards, routers and experts run on the
``T`` rows and never look at the map, except for a row's position
(``rows.pos``) and whether it holds a token (``rows.live``). A layer
takes the per-slot view ``[B, S, ...]`` only where its core is written
per slot, through the two gathers :meth:`RowMap.to_slots` and
:meth:`RowMap.from_slots`; rows of that view past ``q_lens[b]`` are
finite garbage nobody reads, as those kernels' contracts always said.
A core that reads ``(start, q_lens, seq_lens)`` itself takes the packed
rows as they are and builds no view: power retention's walk, the latent
pool's write and attention (``ops/kernels/latent_attention.py``), and
KDA's convolution and chunk walk (``kda.causal_conv_packed``,
``ops/kernels/kda_chunk_walk.py``), whose rows without a token come back
zero.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor


def _val(x):
    return x._value if isinstance(x, Tensor) else x


class PagedKV:
    """Paged K and V pools ``[n_blocks + 1, kv_heads, block, head_dim]``
    on the engine's block tables
    (:class:`paddle_tpu.models.llama.PagedKVCache`): THE general K/V kind,
    with the three methods every kind has, so a layer of a mixed layout
    holds K and V pools beside layers that hold a recurrent state or a
    latent pool. (A layout of this kind alone is also served by the
    engine's older all-K/V branch, which builds the same pools itself and
    alone carries their quantized, sharded, swapped and shared forms.)
    ``q_heads``: the query heads that read the ``kv_heads`` (None: the
    model's config says, ``num_attention_heads``); the append kernel's
    row tile follows the group, and so do the engine's tile counts."""
    kind = "paged_kv"
    paged = True

    def __init__(self, kv_heads, head_dim, q_heads=None):
        self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)
        self.q_heads = None if q_heads is None else int(q_heads)

    def bytes_per_token(self, itemsize):
        return 2 * self.kv_heads * self.head_dim * itemsize

    def alloc(self, zeros, n_blocks, block_size, batch, dtype):
        shape = (n_blocks + 1, self.kv_heads, block_size, self.head_dim)
        return zeros(shape, dtype), zeros(shape, dtype)

    def cache(self, a, b, tables, lens, q_lens, active, row_budget,
              rows=None):
        from .llama import PagedKVCache
        return PagedKVCache(a, b, tables, lens, _q_lens(q_lens, active),
                            rows=rows, row_budget=row_budget)

    def unpack(self, cache):
        return _val(cache.k), _val(cache.v)


class LoopedPagedKV(PagedKV):
    """K and V of ``(kv_heads, head_dim)``, ``loop_steps`` times: a WEIGHT
    layer that runs R times a token (a looped stack) and keeps keys and
    values of its own for each run. One K and one V pool a weight layer of
    ``[R x (n_blocks + 1), kv_heads, block, head_dim]`` on the engine's ONE
    block table a slot: the loop step is part of a block's address. Block
    ``p`` of the allocator is pool block ``t x (n_blocks + 1) + p`` at loop
    step ``t`` (:meth:`at_step`), so one block id holds R steps' worth of
    content and the allocator, the tables, preemption and replay know
    nothing of R. An unallocated entry (-1) stays -1 at every step: it
    reads block 0 masked and its write lands in the pool's last block,
    which is step R - 1's scratch block. The cache object is the llama
    family's :class:`~paddle_tpu.models.llama.PagedKVCache` (so are the
    kernels); in a one-token step its ``q_lens`` says which slots hold a
    live row. :class:`PagedKV` is the general kind and this one is it
    with what only a loop needs: R runs of blocks in ``alloc`` and in a
    token's cost, :meth:`at_step`, and a ``kind`` of its own, by which
    the engine refuses options for it with one reason."""
    kind = "paged_kv_looped"

    def __init__(self, kv_heads, head_dim, loop_steps):
        super().__init__(kv_heads, head_dim)
        self.loop_steps = int(loop_steps)

    def bytes_per_token(self, itemsize):
        return super().bytes_per_token(itemsize) * self.loop_steps

    def alloc(self, zeros, n_blocks, block_size, batch, dtype):
        shape = (self.loop_steps * (n_blocks + 1), self.kv_heads,
                 block_size, self.head_dim)
        return zeros(shape, dtype), zeros(shape, dtype)

    def at_step(self, cache, t, k=None, v=None):
        """``cache`` as loop step ``t`` (traced or not) sees it: the same
        pools (or ``k`` / ``v``, the pools as the steps before left
        them), the table moved to the step's run of blocks."""
        from .llama import PagedKVCache
        k = cache.k if k is None else k
        stride = _val(k).shape[0] // self.loop_steps
        tables = _val(cache.block_tables).astype(jnp.int32)
        tables = jnp.where(tables < 0, -1, tables + t * stride)
        return PagedKVCache(k, cache.v if v is None else v, tables,
                            cache.seq_lens, cache.q_lens, rows=cache.rows,
                            row_budget=cache.row_budget)


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

    def cache(self, a, b, tables, lens, q_lens, active, row_budget,
              rows=None):
        return LatentPagedCache(a, tables, lens, _q_lens(q_lens, active),
                                row_budget, rows)

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

    def cache(self, a, b, tables, lens, q_lens, active, row_budget,
              rows=None):
        return RecurrentCache(a, lens, _q_lens(q_lens, active), row_budget,
                              rows)

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
    the step's live rows over all slots (None: every row may be live).
    ``rows``: the :class:`RowMap` of a mixed step, whose ``x`` is the
    packed ``[1, T, ...]`` (None: ``x`` is ``[B, S, ...]``); the pool's
    write and the attention kernel are handed it with the packed rows,
    and read ``start``, ``slot``, ``pos`` and ``live`` off it."""
    __slots__ = ("pool", "block_tables", "seq_lens", "q_lens", "row_budget",
                 "rows")

    def __init__(self, pool, block_tables, seq_lens, q_lens,
                 row_budget=None, rows=None):
        self.pool, self.block_tables = pool, block_tables
        self.seq_lens, self.q_lens = seq_lens, q_lens
        self.row_budget, self.rows = row_budget, rows


class RecurrentCache:
    """A recurrent layer's per-slot state for one dispatch: ``state``
    {name: [B, ...]}, ``seq_lens`` [B] tokens already absorbed, ``q_lens``
    [B] live rows of this step (rows past them are identity updates).
    ``rows``: as on :class:`LatentPagedCache`."""
    __slots__ = ("state", "seq_lens", "q_lens", "row_budget", "rows")

    def __init__(self, state, seq_lens, q_lens, row_budget=None, rows=None):
        self.state, self.seq_lens, self.q_lens = state, seq_lens, q_lens
        self.row_budget, self.rows = row_budget, rows


# ---------------------------------------------------------------------------
# the packed row axis of a mixed step
# ---------------------------------------------------------------------------

#: what the chip wants of a bf16 operand's row count (a packed sublane
#: tile): the packed height is a multiple of it
ROW_TILE = 16


def packed_rows(max_step_tokens, max_batch, chunk, window=1):
    """The static height ``T`` of a mixed step's packed row axis: the
    fused scheduler's bound on the rows it grants one step, rounded up to
    :data:`ROW_TILE`. Decode tokens always land and the budget is what is
    left for prefill, the oldest ramping slot's guaranteed token included:
    ``max(max_step_tokens, max_batch)`` rows. A speculative engine
    (``window`` = its verify window > 1) grants a decode slot its committed
    token even when the drafts of the slots before it spent the budget:
    ``max_batch - 1`` rows more. Never more than the padded step's
    ``max_batch * chunk``, where the packed step is the padded step."""
    bound = max(int(max_step_tokens), int(max_batch))
    if window > 1:
        bound = int(max_step_tokens) + int(max_batch) - 1
    bound = min(bound, int(max_batch) * int(chunk))
    return -(-bound // ROW_TILE) * ROW_TILE


class RowMap:
    """Which slot and which of its rows each packed row of a mixed step
    is. Made in the graph from the step's effective ``q_lens`` (the
    capacity guard can take a slot out there, so the host cannot make it).
    With ``cu = cumsum(q_lens)``: slot ``b`` owns packed rows ``[cu[b] -
    q_lens[b], cu[b])``, in position order.

    ``slot`` [T] the row's slot, ``col`` [T] its index among the slot's
    rows, ``pos`` [T] its absolute position ``seq_lens[slot] + col``,
    ``live`` [T] whether it holds a token (``t < cu[-1]``; the rest are
    padding, at column and position 0 so that nothing computed on them
    indexes out of a table), ``start`` [B] a slot's first packed row,
    ``q_lens`` [B], ``width`` the per-slot view's S.

    **Who still calls** :meth:`to_slots` / :meth:`from_slots`: the paged
    K/V append's layers (``models/llama.py``'s attention, Solar's
    ``GatedAttention``: ``ops/kernels/paged_attention.py`` takes ``[B, S,
    H, D]``), ``models/lora.py``'s per-slot adapter gather, the engine's
    read-out and id packing, and the fallbacks that run a per-slot XLA
    form on packed rows (``latent_attention_append`` on a CPU,
    ``KimiDeltaAttention`` at a head width off the lanes,
    ``kda_chunk_walk`` for more packed rows than VMEM holds). The kernels
    of power retention, latent attention and KDA read ``start`` and
    ``q_lens`` and call neither."""
    __slots__ = ("slot", "col", "pos", "live", "start", "q_lens", "width")

    def __init__(self, q_lens, seq_lens, n_rows, width):
        q = q_lens.astype(jnp.int32)
        cu = jnp.cumsum(q)
        t = jnp.arange(int(n_rows), dtype=jnp.int32)
        self.q_lens, self.width = q, int(width)
        self.start = cu - q
        self.slot = jnp.minimum(
            jnp.sum(t[:, None] >= cu[None, :], axis=1, dtype=jnp.int32),
            q.shape[0] - 1)
        self.live = t < cu[-1]
        self.col = jnp.where(self.live, t - self.start[self.slot], 0)
        self.pos = jnp.where(
            self.live, seq_lens.astype(jnp.int32)[self.slot] + self.col, 0)

    def last(self):
        """[B] a slot's last live packed row (row 0 of the axis for a
        slot without rows: nobody reads what it gathers)."""
        return jnp.maximum(self.start + self.q_lens - 1, 0)

    def to_slots(self, x, width=None):
        """Packed ``x[T, ...]`` -> the per-slot view ``[B, width, ...]``
        (``width`` = S unless given): row ``i < q_lens[b]`` of slot ``b``
        lands at ``[b, i]``. One contiguous slice a slot, from its first
        packed row on, so a row past ``q_lens[b]`` holds whatever follows
        on the axis (the next slots' rows, the padding, then zeros)."""
        w = self.width if width is None else int(width)
        x = jnp.concatenate([x, jnp.zeros((w,) + x.shape[1:], x.dtype)])
        return jax.vmap(
            lambda at: jax.lax.dynamic_slice_in_dim(x, at, w))(self.start)

    def from_slots(self, y):
        """The per-slot view ``y[B, S, ...]`` -> packed ``[T, ...]``: the
        inverse of :meth:`to_slots` on live rows (a padding row reads
        some row of the last slot)."""
        s = y.shape[1]
        flat = y.reshape((y.shape[0] * s,) + y.shape[2:])
        return jnp.take(flat, self.slot * s + self.col, axis=0)


def packed(cache):
    """The :class:`RowMap` a cache object of a mixed step carries, or
    None (a one-token step, a plain forward, a cache class without one)."""
    return getattr(cache, "rows", None)


# ---------------------------------------------------------------------------
# device-side counts of one step
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextlib.contextmanager
def collect_counts():
    """Collects what the layers :func:`count` while the body runs (one
    traced model call). Yields a list of int32 vectors, of one length
    once the body has run; sum them."""
    prev = getattr(_TLS, "sink", None)
    _TLS.sink = sink = []
    try:
        yield sink
    finally:
        _TLS.sink = prev
        # a layer counts a run of the names: every vector to the widest
        width = max((v.shape[0] for v in sink), default=0)
        sink[:] = [v if v.shape[0] == width
                   else jnp.pad(v, (0, width - v.shape[0])) for v in sink]


def count(vec, at=0):
    """Add one layer's counts (an int32 vector in the order of the model's
    ``step_counter_names``, from name ``at`` on; the names past its end
    count 0) to the collecting dispatch, if there is one."""
    sink = getattr(_TLS, "sink", None)
    if sink is not None:
        sink.append(jnp.pad(vec, (at, 0)) if at else vec)
