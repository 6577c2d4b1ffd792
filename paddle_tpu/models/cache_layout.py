"""What a decoder keeps a layer between steps, as the serving engine sees
it: the model names a KIND a layer, and the engine asks the :class:`Layout`
of those kinds for everything it needs to know of a layer's state. It
knows no family, compares no kind's name and keeps no flag of its own.

A model that :class:`paddle_tpu.inference.LLMEngine` serves has

- ``decoder``: the module called as ``decoder(ids, kv_caches=caches,
  position_offset=lens)`` -> ``(hidden, new_caches)``,
- ``cache_layout()``: one kind a layer, below,
- ``_logits(hidden)`` and ``config`` (``vocab_size``, ``hidden_size``,
  ``max_position_embeddings``),

and may declare ``step_counter_names``: device-side counts its layers
:func:`count` during a step, which leave the step program beside the
tokens and are booked into ``engine.stats`` at readout (every name is
there at 0 from the engine's construction), and ``step_emit_ids``: id ->
the counters whose sum of one step rides on its ``pt:engine.emit`` span.
The module that owns the counters declares both (``COUNTERS``,
``EMIT_IDS``: ``ops/kernels/moe_dropless.py``, ``power_retention.py``,
``kda_chunk_walk.py``).

A kind makes the layer's state ``(a, b)`` (two pytrees of device arrays,
``b`` possibly None: the engine carries every layer's pair through its
programs as two lists), the cache object the layer is handed for one
dispatch, and takes the pair back off the returned cache. A paged kind
also says what a token costs and how many table entries one grid step of
its kernel walks; a kind that is not paged, what a slot costs.

**The layouts the engine serves** (:attr:`Layout.shape`; the table
:data:`REFUSALS` names what each cannot take):

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
5. :class:`Recurrent` in every layer (no pool at all);
6. :class:`IndexedLatent` layers (a latent pool AND an index pool on the
   one block table; a row attends the positions a learned indexer
   selects) beside :class:`WindowedLatent` layers of another width (a
   ring of the last positions a slot, on a table derived in the graph):
   a token costs the indexed layers' two pools alone, a slot the rings.

**What a new kind of state costs**, as the places it is written:

1. the kind's class here (``alloc`` / ``cache`` / ``unpack``, ``paged``,
   what a token or a slot costs, ``entries_per_step`` of a paged one);
2. its column in :data:`REFUSALS` where a layout with it reads a reason
   of its own, with its line in :attr:`Layout.shape`;
3. the model's ``cache_layout()``.

A model that composes the kinds that exist writes the third alone;
``inference/llm_engine.py`` is edited for none of the three. The sixth
layout confirmed the list (PR 45: two kinds, one column, one line of
:attr:`Layout.shape`, no edit to the engine) and added what it does not
say: a kind whose layer needs more of a dispatch than the cache object
carried also writes that into the cache class (:class:`LatentPagedCache`
gained ``index_pool``, ``window`` and ``base``, and :class:`RowMap`
``shifted``), and a kind that is not paged and not :class:`Recurrent`
(a ring a slot) is reset by nobody: it has to need no reset.

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
pool's write and attention (``ops/kernels/latent_attention.py``), the
learned indexer's scores, selection and gathered attention
(``ops/kernels/sparse_latent_attention.py``), KDA's convolution and
chunk walk (``kda.causal_conv_packed``,
``ops/kernels/kda_chunk_walk.py``), and the paged K/V append
(``ops/kernels/paged_attention.py``'s packed entry, through the
attention op's packed form), whose rows without a token come back zero.
No served layer builds the view any more: what is left on it are the
forms below, off the chip's path.
"""
from __future__ import annotations

import contextlib
import copy
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
    (:class:`paddle_tpu.models.llama.PagedKVCache`): THE K/V kind, of the
    llama family's layers and of a K/V layer beside recurrent or latent
    ones alike. The pools' format is this kind's: ``alloc`` is told the
    engine's ``kv_cache_dtype`` (``quant``) and the pools' sharding
    (``spec``: kv heads are axis 1). A quantized pool is ONE ``(payload,
    scale)`` bundle (int8, int4 nibble-packed on the head dim; a float32
    scale a (block, kv head); all zeros is the plain pool's cold state),
    so every step program, donation list and sharding pin carries the
    pair as one set of leaves, and ``cache`` / ``unpack`` are THE places
    that take it apart and bundle it again: no step body can forget the
    scales. ``q_heads``: the query heads that read the ``kv_heads``
    (None: the model's config says, ``num_attention_heads``); the append
    kernel's row tile follows the group."""
    kind = "paged_kv"
    paged = True
    #: runs of the layer a token, each with K and V of its own
    loop_steps = 1

    def __init__(self, kv_heads, head_dim, q_heads=None):
        self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)
        self.q_heads = None if q_heads is None else int(q_heads)
        self.quant = None

    def group(self, config):
        """Query heads a kv head."""
        return (self.q_heads or config.num_attention_heads) // self.kv_heads

    def entries_per_step(self, max_blocks, block_size):
        """Table entries one grid step of the attention kernels walks."""
        return 1

    def bytes_per_token(self, itemsize):
        return 2 * self.kv_heads * self.head_dim * itemsize * self.loop_steps

    def alloc(self, zeros, n_blocks, block_size, batch, dtype, quant=None,
              spec=None):
        from ..ops.kernels.paged_attention import kv_packed_dim
        self.quant = quant
        # +1: a trailing SCRATCH block the allocator never hands out, where
        # the kernels' fused write sends a -1 target (a freed slot's stale
        # lens over a wiped table row must not land on a real block)
        shape = (self.loop_steps * (n_blocks + 1), self.kv_heads, block_size,
                 kv_packed_dim(self.head_dim, quant))
        pin = () if spec is None else (spec,)

        def pool():
            if quant:
                return (zeros(shape, np.int8, *pin),
                        zeros(shape[:2], np.float32, *pin))
            return zeros(shape, dtype, *pin)
        return pool(), pool()

    def cache(self, a, b, tables, lens, q_lens, active, row_budget,
              rows=None):
        from .llama import PagedKVCache
        scales = {}
        if self.quant:
            (a, k_scale), (b, v_scale) = a, b
            scales = dict(k_scale=k_scale, v_scale=v_scale, quant=self.quant)
        return PagedKVCache(a, b, tables, lens, _q_lens(q_lens, active),
                            rows=rows, row_budget=row_budget, **scales)

    def unpack(self, cache):
        """Works for every cache class with ``k`` and ``v`` (the dense
        slot buffers' too, which have no scales: ``quant`` is then None)."""
        if self.quant:
            return ((_val(cache.k), _val(cache.k_scale)),
                    (_val(cache.v), _val(cache.v_scale)))
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
    with what only a loop needs: ``loop_steps`` (R runs of blocks in
    ``alloc`` and in a token's cost), :meth:`at_step`, and a ``kind`` of
    its own for the messages; a layout with it reads the ``looped`` column
    of :data:`REFUSALS`, one reason for every option."""
    kind = "paged_kv_looped"

    def __init__(self, kv_heads, head_dim, loop_steps):
        super().__init__(kv_heads, head_dim)
        self.loop_steps = int(loop_steps)

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

    def entries_per_step(self, max_blocks, block_size):
        """The latent kernel walks its table in wide entries."""
        from ..ops.kernels.latent_attention import entries_per_step
        return entries_per_step(max_blocks, block_size)

    def bytes_per_token(self, itemsize):
        return self.width * itemsize

    def alloc(self, zeros, n_blocks, block_size, batch, dtype, quant=None,
              spec=None):
        return zeros((n_blocks + 1, block_size, self.width), dtype), None

    def cache(self, a, b, tables, lens, q_lens, active, row_budget,
              rows=None):
        return LatentPagedCache(a, tables, lens, _q_lens(q_lens, active),
                                row_budget, rows)

    def unpack(self, cache):
        return _val(cache.pool), None


class IndexedLatent(PagedLatent):
    """A paged latent pool with an INDEX pool beside it on the same block
    tables: ``a`` the ``[n_blocks + 1, block, width]`` latents, ``b`` the
    ``[n_blocks + 1, block, index_width]`` index keys a learned indexer
    scores to choose the positions a row attends
    (``ops/kernels/sparse_latent_attention.py``). A token costs both."""
    kind = "paged_latent_indexed"

    def __init__(self, width, index_width):
        super().__init__(width)
        self.index_width = int(index_width)

    def entries_per_step(self, max_blocks, block_size):
        """The indexer scores its table a tile of entries at a time."""
        from ..ops.kernels.sparse_latent_attention import entries_per_step
        return entries_per_step(max_blocks, block_size)

    def bytes_per_token(self, itemsize):
        return (self.width + self.index_width) * itemsize

    def alloc(self, zeros, n_blocks, block_size, batch, dtype, quant=None,
              spec=None):
        return (zeros((n_blocks + 1, block_size, self.width), dtype),
                zeros((n_blocks + 1, block_size, self.index_width), dtype))

    def cache(self, a, b, tables, lens, q_lens, active, row_budget,
              rows=None):
        return LatentPagedCache(a, tables, lens, _q_lens(q_lens, active),
                                row_budget, rows, index_pool=b)

    def unpack(self, cache):
        return _val(cache.pool), _val(cache.index_pool)


class WindowedLatent:
    """Latent entries of a layer that attends a WINDOW of ``window``
    positions (a row, itself and the ``window - 1`` before it): state a
    SLOT, not a token. A ring of ``ring`` rows of ``width`` values a slot,
    held as a small pool of its own ``[max_batch * ring / block + 1,
    block, width]`` and addressed by ``position mod ring``: slot ``b``
    owns ring blocks ``[b R, (b + 1) R)``, ``R = ring / block``. The
    engine's block table is not read: the layer's table is DERIVED in the
    graph from ``seq_lens`` (:meth:`cache`): entry ``j`` is the logical
    block ``first + j``, ``first`` the block of the oldest position any
    row of the step attends, and maps to ring block ``b R + (first + j)
    mod R`` up to the block of the step's last row, else -1; the cache
    object carries ``base = first * block``, which the layer takes off
    the positions it hands the pool's write and the attention kernel
    (attention depends on differences of positions alone). ``ring >=
    window - 1 + rows`` for the most ``rows`` a slot is granted a step
    (the layer checks): the ring then holds every position some row
    attends, and whatever else a ring row holds reads as a position that
    is masked, outside the window or past the row: a replayed or newly
    assigned slot needs no reset."""
    kind = "windowed_latent"
    paged = False

    def __init__(self, width, window, ring, dtype):
        self.width, self.window, self.ring = int(width), int(window), \
            int(ring)
        self.dtype = np.dtype(dtype)

    def bytes_per_slot(self):
        return self.ring * self.width * self.dtype.itemsize

    def alloc(self, zeros, n_blocks, block_size, batch, dtype, quant=None,
              spec=None):
        if self.ring % block_size:
            raise ValueError(f"a ring of {self.ring} rows is not a whole "
                             f"number of blocks of {block_size}")
        return zeros((batch * (self.ring // block_size) + 1, block_size,
                      self.width), dtype), None

    def cache(self, a, b, tables, lens, q_lens, active, row_budget,
              rows=None):
        bs = a.shape[1]
        per = self.ring // bs
        q = _q_lens(q_lens, active).astype(jnp.int32)
        L = lens.astype(jnp.int32)
        first = jnp.maximum(L - np.int32(self.window - 1), 0) // np.int32(bs)
        last = (L + jnp.maximum(q, 1) - 1) // np.int32(bs)
        # entries: the ring's blocks and the one a window's ends share,
        # rounded up to the latent kernel's widest entry
        j = jnp.arange(-(-(per + 1) // 4) * 4, dtype=jnp.int32)[None, :]
        block = first[:, None] + j
        slot = jnp.arange(L.shape[0], dtype=jnp.int32)[:, None]
        derived = jnp.where(block <= last[:, None],
                            slot * np.int32(per) + block % np.int32(per), -1)
        return LatentPagedCache(a, derived, lens, q, row_budget, rows,
                                window=self.window,
                                base=first * np.int32(bs))

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

    def alloc(self, zeros, n_blocks, block_size, batch, dtype, quant=None,
              spec=None):
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


# ---------------------------------------------------------------------------
# the layout: what the engine asks
# ---------------------------------------------------------------------------

class Layout:
    """A model's kinds, one a layer, and every answer the serving engine
    needs of them: built once from ``model.cache_layout()`` and asked; the
    engine keeps no flag of its own. The kinds are copied: what ``alloc``
    tells a kind (a pool's format) is this engine's, not the model's."""

    def __init__(self, kinds):
        self.kinds = [copy.copy(k) for k in kinds]
        #: some layer keeps its state in pool blocks. False: NO pool is
        #: allocated, and the engine's allocator and block tables are an
        #: empty formality (``max_batch x ceil(capacity / block_size)``
        #: ids that back no memory), so the pool never runs dry, nothing
        #: is preempted for room and the pool's counters book nothing
        self.has_paged = any(k.paged for k in self.kinds)
        self.has_recurrent = any(isinstance(k, Recurrent)
                                 for k in self.kinds)
        #: some layer keeps an index pool beside its latents, or a ring a
        #: slot in place of blocks a token
        self.has_indexed = any(isinstance(k, (IndexedLatent, WindowedLatent))
                               for k in self.kinds)
        #: the kind of the layers that hold K and V pools, few or all
        #: (None: no layer does): the append kernel's tile counts are theirs
        self.kv = next((k for k in self.kinds if isinstance(k, PagedKV)),
                       None)
        #: runs of a weight layer a token under the slot's ONE block id:
        #: what a block, a token and a grid walk cost multiplies by it
        self.loop_steps = max(getattr(k, "loop_steps", 1)
                              for k in self.kinds)
        #: every layer holds plain K and V: the llama family's options
        self.plain_kv = self.loop_steps == 1 and all(
            isinstance(k, PagedKV) for k in self.kinds)

    def __iter__(self):
        return iter(self.kinds)

    def __len__(self):
        return len(self.kinds)

    def names(self):
        """The kinds a message names: those that are not plain K/V."""
        return sorted({k.kind for k in self.kinds} - {PagedKV.kind})

    def bytes_per_token(self, itemsize):
        """What one token costs the pools, over the paged layers."""
        return sum(k.bytes_per_token(itemsize) for k in self.kinds
                   if k.paged)

    def bytes_per_slot(self):
        """What one slot costs beside the pools, over the other layers."""
        return sum(k.bytes_per_slot() for k in self.kinds if not k.paged)

    def entries_per_step(self, max_blocks, block_size):
        """Table entries one grid step of the attention kernel walks, for
        ONE layer of each paged kind the layout has (the layers of a kind
        multiply a walk's counts alike)."""
        return [k.entries_per_step(max_blocks, block_size) for k in
                {k.kind: k for k in self.kinds if k.paged}.values()]

    # ---- one way to build, carry and unpack a layer's state -----------
    def alloc(self, zeros, n_blocks, block_size, batch, dtype, quant=None,
              spec=None):
        """Every layer's state pair, as two lists. ``quant`` / ``spec``:
        the K/V pools' format; a kind without one never reads them (it
        was refused both, :data:`REFUSALS`)."""
        pairs = [k.alloc(zeros, n_blocks, block_size, batch, dtype, quant,
                         spec) for k in self.kinds]
        return [a for a, _ in pairs], [b for _, b in pairs]

    def caches(self, kb, vb, tables, lens, q_lens, active, row_budget,
               rows=None):
        """Per-layer cache list of one traced dispatch (``q_lens`` None is
        the one-token step: a slot that is not ``active`` has no live
        row; ``rows``: a mixed step's RowMap, which every object carries)."""
        return [k.cache(a, b, tables, lens, q_lens, active, row_budget,
                        rows) for k, a, b in zip(self.kinds, kb, vb)]

    def unpack(self, new_caches):
        """The updated two lists off a model call's returned caches."""
        pairs = [k.unpack(c) for k, c in zip(self.kinds, new_caches)]
        return [a for a, _ in pairs], [b for _, b in pairs]

    # ---- what a layout cannot take -------------------------------------
    @property
    def shape(self):
        """The column of :data:`REFUSALS` this layout reads (None: plain
        K/V, nothing is refused). THE selection, written once."""
        if self.plain_kv:
            return None
        if self.loop_steps > 1:
            return "looped"
        if not self.has_paged:
            return "recurrent_only"
        if self.has_recurrent:
            return "beside"
        return "indexed_windowed" if self.has_indexed else "latent_only"

    def refuse(self, **options):
        """Raise ValueError for the first of ``options`` (name=value, in
        the table's order) that this layout cannot take, naming the
        mechanism, instead of serving a wrong token."""
        shape = self.shape
        if shape is None:
            return
        for name, (label, asked, reasons) in REFUSALS.items():
            if name not in options or not asked(options[name]):
                continue
            col = shape if shape in reasons else "beside"
            if col not in reasons:
                continue
            why = _LOOPED if (shape, col) == ("looped", "beside") \
                else reasons[col]
            if not isinstance(why, _Whole):
                why = _START[col] + why
            raise ValueError(why.format(
                option=label.format(options[name]), kinds=self.names(),
                loop_steps=self.loop_steps))


#: what a reason starts with, by the column it stands in (a reason that is
#: a :class:`_Whole` message starts with nothing)
_CANNOT = ("{option} cannot serve a model whose cache layout has {kinds} "
           "layers: ")
_START = {"beside": _CANNOT, "latent_only": _CANNOT,
          "indexed_windowed": _CANNOT,
          "recurrent_only": _CANNOT + (
              "a recurrent-only layout (no layer is paged: every layer "
              "keeps one fixed-size state a slot, and the engine allocates "
              "no pool) ")}
#: a looped layout's ONE reason for every option written for "one pool
#: block a block id", whichever option it is
_LOOPED = (
    "a looped layout keeps {loop_steps} runs of K/V under one block id "
    "(the loop step is part of a pool block's address, and the steps run "
    "as a loop inside the fused paged step programs); this option's code "
    "moves, copies, scales or shards ONE pool block a block id, and its "
    "form over all the runs is not written")


class _Whole(str):
    """A reason that is the whole message."""


_SHIPS = "{option} ships a request's list of K/V blocks; "


#: option -> (its name in the message, ``{}`` its value; whether a value
#: asks for the mechanism; the reason by :attr:`Layout.shape`), for every
#: option whose code assumes "a slot's state is a list of K/V blocks". A
#: shape with no column of its own reads ``beside`` (the state of a
#: recurrent layer beside a pool, of latents or of plain K and V alike, is
#: in no block), a looped layout :data:`_LOOPED` in its place; without
#: ``beside`` an option is refused for the shapes it lists alone.
#: ``latent_only``: the state IS a list of blocks, of ONE pool a layer,
#: which that option's code does not read yet. A new kind adds its column.
REFUSALS = {
    "scheduler": ("scheduler='legacy'", lambda v: v != "fused", {
        "beside": "legacy admission prefills a whole prompt through "
            "StaticKVCache slot buffers of K and V; a recurrent state or a "
            "latent pool advances only in the fused step programs "
            "(scheduler='fused')",
        "recurrent_only": "has no K and V for legacy admission's "
            "StaticKVCache slot buffers to hold; its state advances only in "
            "the fused step programs (scheduler='fused')"}),
    "cache_impl": ("cache_impl={!r}", lambda v: v != "paged", {
        "beside": "the dense slot buffers are [max_batch, capacity, kv_heads, "
            "head_dim] K and V arrays; latents live in a paged pool and a "
            "recurrent state is not a sequence of positions "
            "(cache_impl='paged')",
        "recurrent_only": "has no K and V to put in the dense [max_batch, "
            "capacity, kv_heads, head_dim] slot buffers: a recurrent state is "
            "not a sequence of positions (cache_impl='paged' names the fused "
            "step programs' state seam; it allocates nothing here)"}),
    "horizon": ("horizon > 1", lambda v: v and int(v) > 1, {
        "beside": "the horizon scan belongs to the legacy scheduler; use "
            "readout_stride"}),
    "kv_pool_blocks": ("kv_pool_blocks", lambda v: v is not None, {
        "recurrent_only": "has no pool to size: admission is bounded by "
            "max_batch slots and max_seq_len alone, and a slot's state costs "
            "the same whatever its length"}),
    "enable_prefix_cache": ("enable_prefix_cache", bool, {
        "beside": "a cached block holds its tokens' K/V, but a recurrent "
            "layer's state after a shared prefix is in no block: a hit would "
            "skip the rows that build it (prefix hashing assumes state is a "
            "list of blocks)",
        "latent_only": "the content store adopts, copies and spills a block "
            "as a (K, V) pair of pools a layer; a latent layer has one pool "
            "and no V, and that path is not written for it (ROADMAP Queue 2)",
        "indexed_windowed": "a cached block would have to hold a prefix's "
            "latents AND its index keys (two pools a layer on one table), "
            "and a windowed layer's ring a slot holds only the last "
            "positions, in no block: a hit would skip the rows that fill "
            "it; neither is written (ROADMAP Queue 2)",
        "recurrent_only": "has no blocks for the content store to hash, share "
            "or evict: the state after a shared prefix is one array a (slot, "
            "layer), and a hit would need it snapshotted at the prefix's end, "
            "which is not written"}),
    "kv_host_tier": ("kv_host_swap / kv_host_spill_bytes", bool, {
        "beside": "swap and spill copy a slot's list of pool blocks; its "
            "recurrent state and convolution tail are not blocks and would be "
            "lost (a preempted request replays from its first token instead)",
        "latent_only": "swap and spill gather a slot's blocks out of a (K, V) "
            "pair of pools a layer; a latent layer has one pool and no V, and "
            "that path is not written for it (a preempted request replays "
            "from its first token instead; ROADMAP Queue 2)",
        "indexed_windowed": "swap and spill gather a slot's blocks out of a "
            "(K, V) pair of pools a layer; here a block is a latent pool's "
            "and an index pool's, and a windowed layer's ring a slot is in "
            "no block and would be lost (a preempted request replays from "
            "its first token instead; ROADMAP Queue 2)",
        "recurrent_only": "has no pool blocks to swap out or spill: a slot's "
            "whole state is its recurrent state, whose copy to the host is "
            "not written, and with no pool to run dry nothing is preempted "
            "for room (a preempted request would replay from its first "
            "token)"}),
    "speculative_k": ("speculative_k > 1", lambda v: int(v or 1) > 1, {
        "beside": "a rejected draft rolls the slot's length back over rows "
            "already computed; a recurrent state that has absorbed them "
            "cannot be rolled back",
        "latent_only": "the verify grants are wired through PagedKVCache "
            "alone; a latent pool's rejected rows could be rolled back by its "
            "block table, but that path is not written",
        "indexed_windowed": "a rejected draft rolls the slot's length back "
            "over rows already computed; a windowed layer's ring has by then "
            "overwritten the positions one turn back, which a shorter "
            "length would attend again, and the verify grants are wired "
            "through PagedKVCache alone",
        "recurrent_only": "cannot roll a rejected draft back: the state has "
            "absorbed the draft's rows, and there is no block table whose "
            "length could forget them"}),
    "kv_cache_dtype": ("kv_cache_dtype", lambda v: v is not None, {
        "beside": "pool quantization keeps one scale per (block, kv head) of "
            "K and V pools; a latent pool and a float32 recurrent state have "
            "no such scales",
        "indexed_windowed": "pool quantization keeps one scale per (block, kv "
            "head) of K and V pools; a latent pool, an index pool and a ring "
            "of latents have no such scales",
        "recurrent_only": "has no K/V pool to quantize: a float32 recurrent "
            "state has no (block, kv head) scales"}),
    "adapter_store": ("adapter_store", lambda v: v is not None, {
        "beside": "batched LoRA adds its deltas to the llama family's q/k/v/o "
            "and gate/up/down projections by name",
        "recurrent_only": "is not the llama family's attention: batched LoRA "
            "adds its deltas inside that family's q/k/v/o and gate/up/down "
            "forwards by name, and a recurrent layer's projections do not "
            "read the adapter scope"}),
    "mesh": ("a tensor-parallel mesh", bool, {
        "beside": "kv heads are the shard dimension of K/V pools; a latent "
            "pool has one shared head and a recurrent state is held per slot "
            "(experts over chips with their exchange are not written)",
        "indexed_windowed": "kv heads are the shard dimension of K/V pools; a "
            "latent pool, its index pool and a windowed layer's ring have one "
            "shared head (experts over chips with their exchange are not "
            "written)",
        "recurrent_only": "has no K/V pools, whose kv heads are what the mesh "
            "shards: a recurrent state is held whole a slot, and its form "
            "sharded by head is not written"}),
    # the value is the caller's name: add_request(export_kv=True),
    # export_kv(), import_kv(), export_prefix_blocks(), ...
    "kv_shipping": ("{}", bool, {
        "beside": _Whole(_SHIPS + "a cache layout with {kinds} layers keeps "
            "state that is not in blocks of K and V (a recurrent state a "
            "slot, one latent pool a layer), so it cannot be exported or "
            "imported"),
        "indexed_windowed": _Whole(_SHIPS + "a cache layout with {kinds} "
            "layers keeps a latent pool with an index pool beside it and a "
            "ring of the last positions a slot, none of them a block of K "
            "and V, so it cannot be exported or imported"),
        "recurrent_only": _Whole(_SHIPS + "a recurrent-only layout ({kinds} "
            "layers, no layer paged) has no blocks at all: a request's state "
            "is one fixed-size array a (slot, layer), whose export and import "
            "are not written")}),
    "request_kind": ("{}", lambda v: v == "embed", dict.fromkeys(
        ("beside", "looped", "indexed_windowed"), _Whole(
            "kind='embed' pools the hidden rows of a K/V decoder's prefill; "
            "it is not wired for a cache layout with other state kinds (a "
            "latent pool, a recurrent state, a looped layout)"))),
}


class LatentPagedCache:
    """A paged latent pool for one dispatch: ``pool`` [NB, block, width],
    ``block_tables`` [B, max_blocks], ``seq_lens`` [B] entries already
    held, ``q_lens`` [B] live rows of this step's S (0: the slot writes
    and attends nothing). ``row_budget``: the dispatcher's static bound on
    the step's live rows over all slots (None: every row may be live).
    ``rows``: the :class:`RowMap` of a mixed step, whose ``x`` is the
    packed ``[1, T, ...]`` (None: ``x`` is ``[B, S, ...]``); the pool's
    write and the attention kernel are handed it with the packed rows,
    and read ``start``, ``slot``, ``pos`` and ``live`` off it.
    ``index_pool`` [NB, block, index width]: an indexed layer's index keys
    (:class:`IndexedLatent`). ``window`` / ``base`` [B]: a windowed
    layer's window and the position its derived table starts at
    (:class:`WindowedLatent`); ``seq_lens`` and ``rows.pos`` stay
    absolute."""
    __slots__ = ("pool", "block_tables", "seq_lens", "q_lens", "row_budget",
                 "rows", "index_pool", "window", "base")

    def __init__(self, pool, block_tables, seq_lens, q_lens,
                 row_budget=None, rows=None, index_pool=None, window=None,
                 base=None):
        self.pool, self.block_tables = pool, block_tables
        self.seq_lens, self.q_lens = seq_lens, q_lens
        self.row_budget, self.rows = row_budget, rows
        self.index_pool, self.window, self.base = index_pool, window, base

    def with_pools(self, pool, index_pool=None):
        """This dispatch's cache with the pools a layer leaves."""
        return LatentPagedCache(pool, self.block_tables, self.seq_lens,
                                self.q_lens, self.row_budget, self.rows,
                                index_pool, self.window, self.base)


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

    **Who still calls** :meth:`to_slots` / :meth:`from_slots`:
    ``models/lora.py``'s per-slot adapter gather, the engine's read-out
    and id packing, ``models/llama.py``'s attention over the DENSE chunk
    cache (``cache_impl="dense"``), and the fallbacks that run a per-slot
    XLA form on packed rows (``latent_attention_append``'s dense form on
    a CPU, ``KimiDeltaAttention`` at a head width off the lanes,
    ``kda_chunk_walk`` for more packed rows than VMEM holds). The kernels
    of power retention, latent attention, KDA and, since PR 46, the paged
    K/V append (``paged_attention_append``'s packed entry: ``start``
    scalar-prefetched beside ``seq_lens`` and ``q_lens``, slot ``b``'s
    rows read and written at ``start[b]`` of a head's resident block)
    read ``start`` and ``q_lens`` and call neither; the attention op's
    dense form on a CPU slices its own view out at ``cu_seqlens_q`` (the
    op layer imports nothing from ``models/``)."""
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

    def shifted(self, base):
        """This map with every live row's position less ``base[slot]``
        (a windowed layer's derived table starts there)."""
        out = copy.copy(self)
        out.pos = jnp.where(self.live, self.pos - base[self.slot], 0)
        return out

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
