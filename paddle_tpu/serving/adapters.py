"""Batched multi-LoRA adapters — the multi-tenant serving subsystem.

Reference analog: the reference's unified inference front-end serves many
fine-tunes of one base model through AnalysisPredictor instances (PAPER.md
§1, layer 6c); production LLM stacks do it batched (vLLM multi-LoRA /
Punica SGMV): requests carry an ``adapter_id``, and ONE compiled step
applies a gathered per-slot low-rank delta on top of the shared base
weights, so any mix of tenants rides one dispatch.

TPU-native shape — everything static so the engine's one-compiled-program
contract survives:

* :class:`AdapterStore` — the HOST registry. An adapter is a dict of
  per-target ``(A [L, d_in, r], B [L, r, d_out])`` low-rank factors for
  llama's q/k/v/o and gate/up/down projections plus a scalar ``alpha``.
  Ranks below the store's ``rank`` zero-pad (static shapes); adapter id
  0 is reserved for the base model and never registered.
* :class:`AdapterDeviceCache` — a FIXED number of device slots holding
  stacked ``[n_slots+1, L, d_in, r]`` / ``[n_slots+1, L, r, d_out]``
  buffers per target (row 0 is all-zeros = base). Admission ``acquire``s
  the request's adapter: resident → refcount bump (hit); absent → LRU
  swap-in from the host store (miss + swap, one jitted donated
  ``.at[row].set``); every slot pinned → the admission DEFERS (the
  request stays waiting), exactly like a dry KV pool. Retirement
  ``release``s; refcount-0 slots park in an LRU so a returning tenant
  hits without a swap. The allocator is pool-invariant-audited like the
  KV block allocator (``PADDLE_TPU_POOL_CHECKS=1``).
* :func:`lora_scope` — the trace-time context the engine arms around its
  model calls: :class:`paddle_tpu.models.llama.LlamaAttention` /
  ``LlamaMLP`` consult :func:`active_lora` and add the gathered delta
  ``(x @ A[s]) @ B[s] * alpha[s]`` (fp32 accumulation) to each base
  projection, where ``s`` is the per-batch-row device slot. With no
  scope armed the model body is UNTOUCHED — an engine with no adapters
  registered passes ``lora=None`` and traces the exact pre-adapter
  program, so base serving stays bit-identical.

Correctness bar: a tenant's greedy stream is token-exact vs an offline
reference whose weights were MERGED (``W + A @ B * alpha``,
:func:`apply_merged`) — and adapter identity survives preemption
re-prefill, supervised restart re-admission, and router failover, because
``adapter_id`` rides :class:`~paddle_tpu.inference.GenerationRequest`
through every one of those paths and the prefix cache chains its hashes
from a per-tenant root (no cross-tenant KV block sharing, ever).
"""
from __future__ import annotations

import os
import threading

import numpy as np

from ..analysis import lock_watchdog as _lockwatch
from ..models.lora import (LORA_TARGETS, active_lora, lora_scope,
                           lora_target_dims as _target_dims)

__all__ = ["AdapterStore", "AdapterDeviceCache", "LORA_TARGETS",
           "lora_scope", "active_lora", "apply_merged",
           "random_lora_weights"]


class AdapterStore:
    """Host-side adapter registry for ONE base-model geometry.

    ``rank`` is the store's static rank: every registered adapter's
    factors zero-pad up to it (the device stacks are shaped once).
    Adapters may target any subset of :data:`LORA_TARGETS`; untargeted
    projections stay zero (= base). Registration is allowed at any time
    — an engine picks a new adapter up at that request's admission (the
    jitted step retraces once when the FIRST adapter arrives, because
    the program gains the gather; never again after that).

    Thread-safe for the serving shape: registrations and engine-side
    reads hold one lock."""

    def __init__(self, config, rank=8):
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        self.config = config
        self.rank = int(rank)
        self.n_layers = int(config.num_hidden_layers)
        self.dims = _target_dims(config)
        # PADDLE_TPU_LOCK_CHECKS=1: acquisition edges feed the PTL004
        # lock-order watchdog (paddle_tpu.analysis.lock_watchdog)
        self._lock = _lockwatch.tracked(threading.Lock(),
                                        "AdapterStore._lock")
        #: adapter_id -> {"weights": {target: (A, B)}, "alpha": float}
        self._adapters = {}
        self._next_id = 1

    def __len__(self):
        with self._lock:
            return len(self._adapters)

    def ids(self):
        with self._lock:
            return sorted(self._adapters)

    def has(self, adapter_id):
        if adapter_id == 0:
            return True          # base model, always servable
        with self._lock:
            return adapter_id in self._adapters

    def register(self, weights, alpha=1.0, adapter_id=None):
        """Register one adapter; returns its id (> 0).

        ``weights``: dict target -> (A, B) with A ``[L, d_in, r]`` and B
        ``[L, r, d_out]`` (r <= the store rank; zero-padded up). A 2-D
        ``[d_in, r]`` factor broadcasts to every layer."""
        entry = {}
        for target, (A, B) in weights.items():
            if target not in self.dims:
                raise ValueError(
                    f"unknown LoRA target {target!r} (valid: "
                    f"{sorted(self.dims)})")
            d_in, d_out = self.dims[target]
            A = np.asarray(A, np.float32)
            B = np.asarray(B, np.float32)
            if A.ndim == 2:
                A = np.broadcast_to(A, (self.n_layers,) + A.shape)
            if B.ndim == 2:
                B = np.broadcast_to(B, (self.n_layers,) + B.shape)
            r = A.shape[-1]
            if r > self.rank:
                raise ValueError(
                    f"{target}: adapter rank {r} exceeds the store rank "
                    f"{self.rank} (the device stacks are shaped once)")
            if A.shape != (self.n_layers, d_in, r) or \
                    B.shape != (self.n_layers, r, d_out):
                raise ValueError(
                    f"{target}: expected A [L={self.n_layers}, {d_in}, r] "
                    f"and B [L, r, {d_out}], got {A.shape} / {B.shape}")
            if r < self.rank:           # zero-pad to the static rank
                A = np.concatenate(
                    [A, np.zeros((self.n_layers, d_in, self.rank - r),
                                 np.float32)], axis=-1)
                B = np.concatenate(
                    [B, np.zeros((self.n_layers, self.rank - r, d_out),
                                 np.float32)], axis=1)
            entry[target] = (np.ascontiguousarray(A),
                             np.ascontiguousarray(B))
        with self._lock:
            aid = self._next_id if adapter_id is None else int(adapter_id)
            if aid <= 0:
                raise ValueError("adapter_id 0 is reserved for the base "
                                 "model (ids must be > 0)")
            if aid in self._adapters:
                raise ValueError(f"duplicate adapter_id {aid}")
            self._next_id = max(self._next_id, aid) + 1
            self._adapters[aid] = {"weights": entry, "alpha": float(alpha)}
            return aid

    def get(self, adapter_id):
        with self._lock:
            return self._adapters[adapter_id]


class AdapterDeviceCache:
    """Fixed-size device cache of adapter slots over one AdapterStore.

    ``n_slots`` swappable slots; device row 0 is the always-resident
    all-zeros BASE row, so the stacked buffers have ``n_slots + 1``
    rows. ``acquire(adapter_id)`` returns the device ROW to gather in
    the fused step (0 for base), or None when every slot is pinned by a
    resident request (the caller defers admission). ``release`` drops
    one reference; a refcount-0 slot parks in an LRU (still loaded — a
    returning tenant hits without a swap) until a miss evicts it.

    ``make_zeros(shape, dtype)`` abstracts buffer creation so the engine
    can hand its mesh-aware allocator in (stacks are replicated under
    TP — the delta is computed replicated and added to the sharded base
    projection, which GSPMD reconciles)."""

    def __init__(self, store, n_slots=4, make_zeros=None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.store = store
        self.n_slots = int(n_slots)
        mk = make_zeros or (lambda shape, dt: np.zeros(shape, dt))
        L, r = store.n_layers, store.rank
        S = self.n_slots + 1
        #: stacked device factors, row 0 zeros (base)
        self.A = {t: mk((S, L, d_in, r), np.float32)
                  for t, (d_in, _) in store.dims.items()}
        self.B = {t: mk((S, L, r, d_out), np.float32)
                  for t, (_, d_out) in store.dims.items()}
        self.alpha = mk((S,), np.float32)
        # ---- host allocator state -----------------------------------
        import collections
        self._slot_of = {}                       # adapter_id -> slot (0-based)
        self._slot_aid = [None] * self.n_slots   # slot -> adapter_id
        self._ref = [0] * self.n_slots
        self._free = list(range(self.n_slots))
        self._lru = collections.OrderedDict()    # loaded refcount-0 slots
        self._set_fn = None
        self.stats = {"hits": 0, "misses": 0, "swaps": 0}
        #: zero factors for UNTARGETED projections, built once — a
        #: swap-in of a sparse adapter must not re-allocate full-size
        #: zero arrays for every projection it doesn't touch
        self._zeros = {
            t: (np.zeros((L, d_in, r), np.float32),
                np.zeros((L, r, d_out), np.float32))
            for t, (d_in, d_out) in store.dims.items()}
        self._debug = os.environ.get(
            "PADDLE_TPU_POOL_CHECKS", "0") not in ("", "0")

    # -- device upload --------------------------------------------------
    def _upload(self, slot, adapter):
        """Write one adapter's factors into device row ``slot + 1`` —
        one jitted donated program (row index traced: swapping a
        different slot never recompiles)."""
        import jax
        import jax.numpy as jnp

        if self._set_fn is None:
            def set_row(As, Bs, alpha, hostA, hostB, host_alpha, row):
                As = {t: a.at[row].set(hostA[t]) for t, a in As.items()}
                Bs = {t: b.at[row].set(hostB[t]) for t, b in Bs.items()}
                alpha = alpha.at[row].set(host_alpha)
                return As, Bs, alpha
            self._set_fn = jax.jit(set_row, donate_argnums=(0, 1, 2))
        w = adapter["weights"]
        hostA, hostB = {}, {}
        for t in self.store.dims:
            if t in w:
                hostA[t], hostB[t] = w[t]
            else:            # untargeted projection: shared zero delta
                hostA[t], hostB[t] = self._zeros[t]
        self.A, self.B, self.alpha = self._set_fn(
            self.A, self.B, self.alpha, hostA, hostB,
            jnp.float32(adapter["alpha"]), jnp.int32(slot + 1))

    # -- allocator ------------------------------------------------------
    def acquire(self, adapter_id):
        """Pin ``adapter_id`` resident; returns its device ROW (0 =
        base), or None when the cache is full of pinned slots (caller
        defers the admission until a release frees one)."""
        if adapter_id == 0:
            return 0
        slot = self._slot_of.get(adapter_id)
        if slot is not None:
            if self._ref[slot] == 0:
                self._lru.pop(slot, None)
            self._ref[slot] += 1
            self.stats["hits"] += 1
            self._check_invariants()
            return slot + 1
        # miss: free slot first, else evict the LRU-oldest loaded slot.
        # A full-of-pinned-slots cache defers WITHOUT counting a miss —
        # the caller retries every step, and one deferred admission must
        # not inflate the miss counter by its wait length.
        if self._free:
            slot = self._free.pop(0)
        elif self._lru:
            slot, _ = self._lru.popitem(last=False)
            del self._slot_of[self._slot_aid[slot]]
        else:
            return None                 # every slot pinned: defer
        self.stats["misses"] += 1
        self._upload(slot, self.store.get(adapter_id))
        self.stats["swaps"] += 1
        self._slot_of[adapter_id] = slot
        self._slot_aid[slot] = adapter_id
        self._ref[slot] = 1
        self._check_invariants()
        return slot + 1

    def release(self, adapter_id):
        if adapter_id == 0:
            return
        slot = self._slot_of.get(adapter_id)
        if slot is None:
            return
        self._ref[slot] = max(0, self._ref[slot] - 1)
        if self._ref[slot] == 0:
            self._lru[slot] = None      # loaded, evictable, probe-able
        self._check_invariants()

    def resident(self, adapter_id):
        """READ-ONLY: is ``adapter_id`` currently loaded (pinned or
        LRU-parked)? The replica router's adapter-affinity probe — dict
        reads only, safe from any thread."""
        return adapter_id == 0 or adapter_id in self._slot_of

    def occupancy(self):
        """Loaded fraction of the swappable slots (pinned + LRU)."""
        return len(self._slot_of) / self.n_slots

    def _check_invariants(self):
        """Debug audit (PADDLE_TPU_POOL_CHECKS=1, armed suite-wide by
        tests/conftest.py): every slot is exactly one of {free, LRU,
        pinned}, the id<->slot maps mirror, and LRU slots are loaded
        refcount-0."""
        if not self._debug:
            return
        free, lru = set(self._free), set(self._lru)
        pinned = {s for s in range(self.n_slots)
                  if self._ref[s] > 0}
        assert not (free & lru) and not (free & pinned) \
            and not (lru & pinned), "adapter slot in two pools"
        assert free | lru | pinned == set(range(self.n_slots)), \
            "adapter slot leak"
        for s in lru:
            assert self._ref[s] == 0 and self._slot_aid[s] is not None, \
                f"LRU slot {s} pinned or empty"
        for s in free:
            assert self._slot_aid[s] is None, f"free slot {s} still mapped"
        for aid, s in self._slot_of.items():
            assert self._slot_aid[s] == aid, "slot map drift"
        assert sum(v is not None for v in self._slot_aid) == \
            len(self._slot_of), "slot_aid / slot_of size drift"


# ---------------------------------------------------------------------------
# offline merged-weights reference
# ---------------------------------------------------------------------------

def apply_merged(model, store, adapter_id):
    """Merge adapter ``adapter_id`` INTO ``model``'s weights in place
    (``W += A[l] @ B[l] * alpha`` per target per layer) — the offline
    single-tenant reference the batched path must match token-exactly.
    Returns ``model``."""
    import jax.numpy as jnp

    entry = store.get(adapter_id)
    alpha = entry["alpha"]
    for target, sub in LORA_TARGETS:
        if target not in entry["weights"]:
            continue
        A, B = entry["weights"][target]
        for li, layer in enumerate(model.llama.layers):
            lin = getattr(getattr(layer, sub), target)
            delta = (A[li] @ B[li]) * alpha          # [d_in, d_out]
            w = lin.weight
            w._value = (w._value.astype(jnp.float32)
                        + jnp.asarray(delta)).astype(w.dtype)
    return model


def random_lora_weights(config, rank, seed=0, scale=0.02, targets=None):
    """Small random (A, B) factors for every (or the given) target —
    the test and example adapter generator. ``scale`` keeps the delta
    small enough that greedy decoding stays numerically stable while
    still changing the stream."""
    rng = np.random.default_rng(seed)
    dims = _target_dims(config)
    L = config.num_hidden_layers
    out = {}
    for t in (targets or dims):
        d_in, d_out = dims[t]
        out[t] = (
            rng.standard_normal((L, d_in, rank)).astype(np.float32) * scale,
            rng.standard_normal((L, rank, d_out)).astype(np.float32)
            * scale)
    return out
