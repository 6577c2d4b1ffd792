"""LLM serving engine — continuous batching over compiled decode steps.

Reference analog: the serving path the reference builds from
AnalysisPredictor (paddle/fluid/inference/api/analysis_predictor.h:101) plus
the fused decode kernels
(python/paddle/incubate/nn/functional/block_multihead_attention.py:1,
masked_multihead_attention.py:1) that PaddleNLP's serving stack drives with
dynamic request batching.

TPU-native design — everything is STATIC shapes so two compiled programs
serve the whole engine lifetime:

  * ``max_batch`` fixed slots; each slot owns a [capacity, H, D] region of
    the per-layer KV buffers and a traced length (``SlotKVCache``), so
    ragged sequences share one compiled decode step.
  * one **decode step** program: sample (per-slot temperature/top-p vectors,
    greedy-vs-sample selected per slot in-graph) -> one-token model step
    writing KV at each slot's own position -> next logits. Varying sampling
    params or slot occupancy never recompiles.
  * one **chunked-prefill** program per chunk size: admits a request by
    streaming its prompt through fixed-size chunks into its slot's KV region
    (dynamic_slice/update on the slot axis), returning last-position logits.
    Chunk padding is masked by causality and overwritten by later writes.
  * requests join and leave BETWEEN steps (continuous batching): a finished
    slot is freed at the step boundary and the next queued request admits
    into it while other slots keep decoding.

Logits stay on device between steps; the only per-step host transfer is the
[B] sampled-token vector that streaming callers need anyway.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import heapq
import os
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import lock_watchdog as _lockwatch
from ..core.tensor import Tensor, functional_mode
from ..models.cache_layout import (Layout, RowMap, collect_counts,
                                   packed_rows)
from ..models.llama import SlotKVCache, _sample_logits_device
from ..models.lora import lora_scope
from ..profiler import build, build_retraced, scope, span, watch_gc

__all__ = ["LLMEngine", "GenerationRequest", "RequestOutput", "PendingStep",
           "PoolCapacityError", "default_engine_stats"]


def default_engine_stats():
    """Fresh engine ``stats`` dict — THE one copy of the key schema.
    The serving layer reads these keys by name off ANY engine speaking
    the step protocol (LLMEngine, and protocol shims like
    serving/embedding.py's BertEmbedEngine), so every engine must carry
    the full set: a hand-copied dict would silently drift the next time
    a counter is added."""
    return {"steps": 0, "prefill_chunks": 0, "tokens_generated": 0,
            # dispatched step programs with a live row at temperature > 0:
            # the others ran sample_next's argmax alone (pick_tokens)
            "sampling_steps": 0,
            "spec_proposed_tokens": 0, "spec_accepted_tokens": 0,
            "preemptions": 0,
            "fused_steps": 0, "multi_steps": 0,
            "prefill_tokens": 0,
            "prefix_hit_tokens": 0, "prefix_cow_blocks": 0,
            "prefix_evicted_blocks": 0,
            "adapter_cache_hits": 0, "adapter_cache_misses": 0,
            "adapter_swaps": 0, "embed_requests": 0,
            # host KV tier: preemption swap (blocks/bytes each way, and
            # re-prefill tokens the restore avoided) + prefix spill
            # (LRU-evicted blocks demoted to host, spilled blocks
            # promoted back on a content-store hit)
            "kv_swap_out_blocks": 0, "kv_swap_in_blocks": 0,
            "kv_swap_out_bytes": 0, "kv_swap_in_bytes": 0,
            "kv_swap_saved_tokens": 0,
            "kv_spill_blocks": 0, "kv_promote_blocks": 0,
            # cross-replica KV shipping (disaggregated prefill/decode):
            # staged-entry exports out of this engine's pool and shipped
            # imports scattered back in — booked SEPARATELY from the
            # kv_swap_* preemption traffic, whose byte deltas are the
            # explain_tail preempt classifier's exclusive signal
            "kv_ship_out_blocks": 0, "kv_ship_in_blocks": 0,
            "kv_ship_out_bytes": 0, "kv_ship_in_bytes": 0,
            "decode_time_s": 0.0, "admit_time_s": 0.0,
            "schedule_time_s": 0.0,
            "dispatch_time_s": 0.0, "host_sync_time_s": 0.0,
            "emit_time_s": 0.0,
            # what a dispatched program computes against what was asked
            # of it: rows of the step programs (padding included; an
            # all-decode stride books the iterations it ran at readout),
            # and per paged dispatch the block-table entries the
            # attention grid walks and those of them that hold a live
            # token after the step (host lens mirror)
            "rows_computed": 0, "kv_grid_blocks": 0, "kv_live_blocks": 0,
            # per mixed paged dispatch: (row tile, table entry) pairs the
            # append kernel computes for this step's (lens, q_lens)
            # against every row tile x every entry of every slot (the
            # kernel module's own count, paged_attention.append_tile_steps)
            "attn_tile_steps": 0, "attn_tile_steps_grid": 0,
            # per paged dispatch: pool blocks that belong to a request
            # (neither free, nor cached, nor waiting out a write fence)
            # and the pool's blocks, so their ratio is the share of the
            # pool in use a step; and, booked at readout for the
            # all-decode dispatches (the iterations that ran through the
            # one-token decode attention): those iterations, their live
            # rows (a slot an iteration), and the tokens the live slots
            # held in them, the new one included
            "pool_blocks_used": 0, "pool_blocks_total": 0,
            "decode_ctx_tokens": 0, "decode_rows": 0,
            "decode_iterations": 0,
            # slots assigned into zeroed recurrent state (a layout with a
            # recurrent layer): admissions and preemption replays alike
            "state_resets": 0,
            # a (seconds, count) pair: takes a slot -> first prefill
            # grant dispatched (accepted -> takes a slot is telemetry's
            # queue_wait_s histogram, the server's)
            "slot_wait_time_s": 0.0, "first_grants": 0,
            # calls of a compiled program that found no entry in its
            # jit cache: their wall; a program's first is traced as
            # pt:engine.build
            "program_build_time_s": 0.0, "programs_built": 0,
            # the host wall of the engine's construction (the model seam,
            # the pools reserved whole, tables, allocator)
            "engine_init_time_s": 0.0,
            # the cyclic collector's pauses while this engine lived
            # (pt:host.gc, booked by profiler.watch_gc): their wall,
            # their count and the longest
            "gc_pause_time_s": 0.0, "gc_pauses": 0, "gc_pause_max_s": 0.0,
            # transfer-guard sanitizer (PADDLE_TPU_TRANSFER_CHECKS=1):
            # all-decode strides whose dispatch->readout window ran
            # under jax.transfer_guard("disallow") — each counted
            # readout is the stride's ONE permitted D2H sync
            "guarded_syncs": 0}

#: the engine's host phases — THE measurement points of a step: phase ->
#: (its ``pt:engine.*`` trace span, the stats wall sums it adds to).
#: ``sync`` is the host blocked on the token read, a WAIT; the rest is
#: host work.
_PHASES = {
    "admit": ("pt:engine.admit", ("admit_time_s",)),
    "schedule": ("pt:engine.schedule", ("schedule_time_s",)),
    "dispatch": ("pt:engine.dispatch", ("dispatch_time_s", "decode_time_s")),
    "sync": ("pt:engine.sync", ("host_sync_time_s", "decode_time_s")),
    "emit": ("pt:engine.emit", ("emit_time_s",)),
}

#: ``kind`` on a ``pt:engine.dispatch`` span and ``program`` on a
#: ``pt:engine.build`` span are indices into these
DISPATCH_KINDS = ("decode", "mixed", "spec")
PROGRAMS = ("step", "multi_step", "fused_step", "spec_step", "multi_spec",
            "prefill", "prefill_paged", "set_logits", "set_tokens",
            "set_len", "set_pooled", "cow", "kv_gather", "kv_scatter")

#: chain-hash seed for block 0 of every sequence (the "parent" of the
#: first block) — a fixed constant so equal first blocks collide
_ROOT_HASH = b"paddle-tpu-prefix-root"

#: smoothing of the per-request draft-acceptance EWMA that drives the
#: acceptance-adaptive verify-k grants (fused speculative scheduling):
#: high enough that a request whose drafts stop accepting sheds its
#: window within a few readouts, low enough that one unlucky window
#: doesn't collapse k for a stream that usually accepts
_SPEC_EWMA_ALPHA = 0.4

#: one RLock per MODEL object, shared by every engine built on it. The
#: compiled programs trace through ``bind_state``, which temporarily
#: swaps the model tensors' ``_value`` to tracers — so two engines on
#: the SAME model tracing from different threads (N replica servers of a
#: ReplicaRouter sharing weights) would leak each other's tracers.
#: step_begin (the only trace-capable engine entry point) serializes on
#: this lock; once every program is compiled the lock guards only the
#: sub-ms host-side dispatch, which the GIL serializes anyway.
_MODEL_DISPATCH_LOCKS = weakref.WeakKeyDictionary()
_LOCKS_GUARD = threading.Lock()

#: the open transfer-guard stride window, PER THREAD and shared by ALL
#: engines — jax.transfer_guard is thread-global config, so two engines
#: interleaved on one thread must share one window slot (per-engine
#: slots would nest contexts and unwind them out of LIFO order,
#: stranding the thread in "disallow")
_STRIDE_GUARD_TLS = threading.local()


def close_thread_stride_guard(finishing=None):
    """Close the CALLING thread's open transfer-guard stride window, if
    any — THE one copy of the close protocol, shared by every engine
    speaking the step protocol (LLMEngine, and shims like
    serving/embedding.py's BertEmbedEngine, whose readouts must not run
    inside another engine's disallow window). A window closed early —
    by a chained dispatch, a reset, or a DIFFERENT pending's finish —
    did not cover its stride, so the owner's ``guarded`` flag is
    revoked and its readout is not counted."""
    cm = getattr(_STRIDE_GUARD_TLS, "cm", None)
    if cm is None:
        return
    owner = getattr(_STRIDE_GUARD_TLS, "owner", None)
    if owner is not None and owner is not finishing:
        owner.guarded = False
    _STRIDE_GUARD_TLS.cm = None
    _STRIDE_GUARD_TLS.owner = None
    cm.__exit__(None, None, None)


def _model_dispatch_lock(model):
    with _LOCKS_GUARD:
        lock = _MODEL_DISPATCH_LOCKS.get(model)
        if lock is None:
            lock = _MODEL_DISPATCH_LOCKS[model] = threading.RLock()
        return lock


class PoolCapacityError(RuntimeError):
    """The head waiting request's prompt cannot prefill into the paged
    pool at all (kv_pool_blocks too small). A RuntimeError subclass so
    existing callers keep working; the serving layer catches exactly this
    type to reject the one doomed request instead of treating unrelated
    runtime errors (device/compile failures) as per-request problems."""


@dataclasses.dataclass
class GenerationRequest:
    request_id: int
    prompt_ids: np.ndarray           # [P] int32
    max_new_tokens: int = 64
    temperature: float = 0.0         # <=0 -> greedy
    top_p: float = 1.0
    eos_token_id: int | None = None
    #: latency-tier pin: cap the multi-step readout stride of every
    #: all-decode step this request is active in (None = the engine's
    #: ``readout_stride``; 1 = every step syncs the host, minimizing
    #: inter-token latency for THIS request at the whole batch's
    #: throughput cost — the effective stride is the min over slots)
    readout_stride: int | None = None
    #: the TENANT dimension (batched multi-LoRA,
    #: serving/adapters.py): 0 = the base model, > 0 = a registered
    #: adapter whose gathered low-rank delta rides this request's rows
    #: of every fused dispatch. Carried through preemption re-prefill,
    #: supervised-restart re-admission and router failover, and mixed
    #: into the prefix cache's hash-chain root so tenants never share
    #: KV blocks.
    adapter_id: int = 0
    #: the request's GRANT KIND in the fused token-budget walk:
    #: "generate" (prefill chunks, then one decode token per step) or
    #: "embed" (PREFILL-ONLY — no decode tokens, no sampling; the
    #: mean-pooled final hidden state returns on the prefill sync)
    kind: str = "generate"
    #: acceptance-adaptive speculation state (fused verify-k grants):
    #: EWMA of accepted/proposed drafts for THIS request, None until the
    #: first verify readout. Carried through preemption re-prefill and
    #: supervised-restart re-admission (the engine's rid-keyed
    #: ``_spec_ewma`` mirror survives ``reset()``) like the PR-8
    #: ``readout_stride`` pins, so a low-acceptance request does not
    #: reset to full-window speculation every time it moves.
    spec_ewma: float | None = None
    #: disaggregated serving (cross-replica KV shipping): stage this
    #: request's committed KV as an export entry when it finishes — the
    #: prefill replica's router hook then pops it via
    #: :meth:`LLMEngine.export_kv` and ships it to a decode replica.
    #: The staging runs at the finish site on the ENGINE thread, while
    #: the slot's blocks are still allocated (an external export call
    #: would race the retirement free).
    export_kv: bool = False
    #: distributed trace context (serving/types.TraceContext or its
    #: dict form) — opaque to the engine except for the recorder stamp
    #: at admission; preserved verbatim so one trace_id names this
    #: request across every replica/restart hop it takes
    trace_ctx: object | None = None


@dataclasses.dataclass
class RequestOutput:
    request_id: int
    token_ids: list
    finished: bool = False
    finish_reason: str | None = None
    #: prefill-only (kind="embed") result: the mean-pooled final hidden
    #: state [hidden_size] (fp32), None for generation requests
    embedding: np.ndarray | None = None


class _Slot:
    __slots__ = ("req", "generated", "prompt_len", "prefill_pos", "inflight",
                 "chain", "reg_blocks", "a_slot", "t_slot")

    def __init__(self, req, prompt_len, prefill_pos=None):
        self.req = req
        self.generated = []
        self.prompt_len = prompt_len
        #: device ROW of this request's adapter in the AdapterDeviceCache
        #: stacks (0 = the all-zeros base row) — the per-slot index the
        #: fused step gathers the LoRA delta by
        self.a_slot = 0
        #: prefix-cache chain state (paged + enable_prefix_cache): the
        #: rolling chain hash of each REGISTERED full block of this
        #: slot's committed token stream, and how many blocks have been
        #: registered in the content store so far. Admission seeds both
        #: from the probe's hit; prefill/decode extend them as blocks
        #: fill.
        self.chain = []
        self.reg_blocks = 0
        #: prompt tokens whose prefill has been DISPATCHED (== prompt_len
        #: once ramp-in completes; legacy admission prefills everything up
        #: front). The fused scheduler advances it one chunk grant at a
        #: time, so a partially-prefilled request stays RESIDENT in its
        #: slot between steps instead of blocking inside _admit.
        self.prefill_pos = prompt_len if prefill_pos is None else prefill_pos
        #: decode tokens dispatched but not yet step_finish()ed — the
        #: paged fused engine's host-side lens mirror (scheduled growth),
        #: what lets it allocate blocks for step N+1 before step N's
        #: readout and so pipeline at depth 2 on a full pool.
        self.inflight = 0
        #: perf_counter when the request took this slot, until its first
        #: prefill grant is dispatched (then None): the slot wait
        self.t_slot = None

    @property
    def ramping(self):
        return self.prefill_pos < self.prompt_len

    def sched_len(self):
        """Scheduled sequence length: what the device lens will be once
        every dispatched step lands (== current length when nothing is in
        flight)."""
        return self.prefill_pos + len(self.generated) + self.inflight


class PendingStep:
    """One in-flight decode step: device-array futures dispatched by
    :meth:`LLMEngine.step_begin`, host readout deferred to
    :meth:`LLMEngine.step_finish`.

    This split is what makes PIPELINED serving (``paddle_tpu.serving``)
    possible: a second ``step_begin()`` may be dispatched before the first
    ``step_finish()``, so JAX async dispatch overlaps step N+1's device
    compute with step N's device→host token transfer and host readout.
    ``slots`` snapshots the slot objects at dispatch time — a slot retired
    and reused between dispatch and finish fails the identity check at
    readout and its stale token column is dropped (it was decoded against
    the OLD request's state)."""

    __slots__ = ("toks", "was_active", "counts", "spec", "slots",
                 "pool_done", "sched", "step_id", "fenced", "t_dispatch",
                 "embed_done", "pooled", "verify", "offered", "guarded",
                 "rows", "ctr", "ctx0")

    def __init__(self, toks, was_active, counts, spec, slots, pool_done,
                 sched=None, fenced=None, embed_done=None, verify=None):
        self.toks = toks              # device [rows, B] (spec: [Kh,B,Ks])
        self.was_active = was_active  # device activity history
        self.counts = counts          # spec only: accepted counts [Kh, B]
        self.spec = spec
        self.slots = slots            # list[_Slot|None] snapshot at dispatch
        self.pool_done = pool_done    # outputs retired by the pool allocator
        #: fused scheduler: per-slot decode tokens SCHEDULED by this
        #: dispatch ({b: n}); step_finish pays them back off slot.inflight
        self.sched = sched or {}
        #: flight-recorder StepRecord id (None when no recorder is
        #: attached) — step_finish stamps every token it reads out with
        #: it, joining request timelines back to engine state
        self.step_id = None
        #: paged fused: physical blocks this dispatch may WRITE (the
        #: stride-aware in-flight fence) — step_finish drops the fence,
        #: releasing any block quarantined while this step was in flight
        self.fenced = fenced or []
        #: perf_counter at dispatch — step_finish amortizes per-token
        #: emit stamps over [t_dispatch, sync] so a k-step stride's
        #: token burst doesn't read as one giant inter-token gap
        self.t_dispatch = None
        #: [(slot_idx, _Slot), ...] embed requests whose FINAL prefill
        #: chunk this dispatch carries — step_finish reads their pooled
        #: hidden rows on the sync and retires them. ``pooled`` is THIS
        #: dispatch's pooled-accumulator output (not the engine's
        #: newest one: under pipelining the readout must not
        #: synchronize on younger in-flight steps).
        self.embed_done = embed_done or []
        #: rows each iteration of an early-exit stride computes: the
        #: readout books them times the iterations that ran (0: the
        #: dispatch's rows were known and booked when it was dispatched)
        self.rows = 0
        #: device vector of the model's step counters for this dispatch
        #: (None: the model declares none)
        self.ctr = None
        #: a paged all-decode dispatch: [B] tokens each active slot held
        #: when it was dispatched (0: not active), from which the readout
        #: books ``decode_ctx_tokens`` for the iterations that ran
        self.ctx0 = None
        self.pooled = None
        #: fused speculative dispatches: {slot: drafts granted} — the
        #: readout's acceptance accounting (EWMA + spec counters) and
        #: the paged BLOCK-TABLE ROLLBACK walk key off it
        self.verify = verify or {}
        #: fused speculative dispatches: device [windows, B] per-window
        #: OFFERED widths (1 + drafts after the in-graph clamps) — the
        #: exact proposal counts the acceptance accounting books
        #: against. None on legacy spec (its grant is never clamped).
        self.offered = None
        #: True when this dispatch armed the transfer-guard stride
        #: window (PADDLE_TPU_TRANSFER_CHECKS=1): its step_finish
        #: readout is the stride's ONE counted sync (stats
        #: ["guarded_syncs"])
        self.guarded = False


class LLMEngine:
    """Continuous-batching engine over a causal LM that names its decoder
    and its cache layout (``models/cache_layout.py``: ``model.decoder``,
    ``model.cache_layout()`` one state kind a layer, ``model._logits``);
    the engine knows no family. The llama family's layers are all paged
    (or dense) K/V (bf16/fp32 and WeightOnlyLinear-quantized weights;
    under a mesh the programs partition by GSPMD like ``generate()``);
    a layout with a paged latent pool or a recurrent state a slot is
    served by the fused scheduler over the paged allocator, and every
    option whose code assumes "state is a list of K/V blocks" refuses it
    at construction (``cache_layout.REFUSALS``). A layout with NO paged
    layer (every layer a recurrent state a slot) goes the same way with
    nothing to page: see ``cache_layout.Layout.has_paged``."""

    def __init__(self, model, max_batch=4, max_seq_len=None, chunk_size=64,
                 top_k=0, stream_callback=None, horizon=1, speculative_k=1,
                 lookup_ngram=3, mesh=None, cache_impl="dense",
                 block_size=64, kv_pool_blocks=None, scheduler="legacy",
                 max_step_tokens=None, enable_prefix_cache=False,
                 readout_stride=1, adapter_store=None,
                 adapter_cache_slots=4, kv_cache_dtype=None,
                 kv_host_swap=False, kv_host_spill_bytes=0,
                 sampling_seed=None):
        """``scheduler="fused"`` (Sarathi-style chunked-prefill+decode
        fusion): admission becomes slot ASSIGNMENT only — each engine step
        then processes, per slot, either one bounded prefill chunk (for
        ramping-in requests, ``_Slot.prefill_pos`` tracks progress) or one
        decode token, all in ONE jitted mixed-step dispatch, under the
        per-step token budget ``max_step_tokens`` (default ``chunk_size +
        max_batch - 1``: one full chunk plus a decode token for every
        other slot; decode tokens are always granted — the budget bounds
        prefill interference, which is what stalls inter-token latency).
        Steps with no ramping slot fall through to the plain decode scan
        (with ``horizon``), so steady-state decode cost is unchanged.
        ``scheduler="legacy"`` keeps admit-then-decode: the whole prompt
        prefills inside _admit as a serial chunk train while running
        decodes stall — still the best shape for offline drain-mode
        batches, and the parity reference for the fused path.

        ``mesh``: a jax Mesh for MULTI-PROCESS serving — engine buffers
        are created as global (replicated) arrays on it so the compiled
        programs can mix them with TP-sharded weights whose groups span
        processes; every process runs the same step() calls (SPMD) and
        reads the same replicated token vector.

        ``cache_impl="paged"`` (reference:
        incubate/nn/functional/block_multihead_attention.py:1): KV lives in
        a physical BLOCK POOL of ``kv_pool_blocks`` blocks of ``block_size``
        tokens, mapped per slot through block tables. Blocks allocate on
        demand as sequences grow and free at retirement, so engine HBM is
        bounded by the POOL (sum of actual lengths, block-rounded), not by
        slots x capacity — and the pool may be OVERSUBSCRIBED
        (kv_pool_blocks < max_batch * capacity/block_size): when it runs
        dry mid-decode, the most recently admitted slot is PREEMPTED back
        to the waiting queue (its tokens re-prefill on re-admission, so
        greedy output is unchanged).

        ``enable_prefix_cache`` (paged only — vLLM/SGLang-style automatic
        prefix caching): the host block allocator becomes a ref-counted,
        CONTENT-ADDRESSED store. Full blocks are keyed by a rolling hash
        chained over the whole prefix (equal prefixes collide on
        purpose), blocks freed at retirement park in an LRU "cached" pool
        instead of the free list, and admission probes the store for the
        longest cached prefix — hit blocks attach by pure table writes +
        refcount bumps, so the shared span costs ZERO prefill FLOPs.
        Shared (refcounted) blocks are never written; a slot that must
        append into content another request still references gets a
        private COPY first (copy-on-write — the partial tail block is
        always private). Greedy output is token-exact vs the uncached
        engine; the LRU evicts before any live slot is preempted.

        ``kv_cache_dtype`` ("int8" | "int4", paged only — QUANTIZED KV
        serving, the capacity lever): the physical K/V pools store
        int8 (or int4 nibble-packed on the head dim) with one fp32
        scale per (physical block, kv head) riding alongside, so the
        same HBM holds ~2x/~4x the resident blocks. The Pallas
        decode/append kernels dequantize blocks in VMEM during the
        online-softmax walk and re-quantize every fused write in VMEM
        (fresh per-head absmax scale computed in-kernel); the CPU dense
        fallback does the same math at the XLA level, so tier-1 stays
        host-runnable. Everything ABOVE the pool — block tables,
        allocator, prefix-cache content hashing (host-side over token
        ids), COW, the write fence, speculative rollback — operates on
        block indices and is quantization-oblivious; scale arrays shard
        kv-heads under a TP mesh exactly like the pools. ``None`` (the
        default) is bit-identical to the bf16 engine. Output tokens
        DRIFT from bf16 (that is the deal: ~2x/4x capacity for a
        quantization error of ~0.4%/~7% per KV read); tests/test_kv_quant.py
        tracks greedy drift explicitly.

        ``kv_host_swap`` (paged + fused only — the HOST KV TIER's
        preemption half): when pool pressure preempts a slot, its
        committed KV blocks are copied device→host asynchronously in
        the step_begin/step_finish gap instead of being discarded, and
        re-admission restores them host→device plus a one-token stitch
        — the preemption costs two overlapped copies, not a full
        re-prefill. Token-exact: the restored bytes are the bytes the
        pool held (quantized pools round-trip payload AND scale rows
        bit-exact), and the stitch position recomputes deterministically.

        ``kv_host_spill_bytes`` (paged + prefix cache only — the tier's
        eviction half): LRU-evicted prefix-cache blocks demote into a
        bounded host spill store of at most this many bytes instead of
        vanishing; a content-store probe that misses the device LRU but
        hits the spill PROMOTES the block back (one H2D copy) rather
        than recomputing the chunk. 0 (default) disables spilling.

        ``sampling_seed``: explicit base key for the per-(rid, position)
        fold_in sampling keys. The default (None) pulls a fresh seed
        from the global generator at the first step — fine for a single
        engine, but the generator's counter makes each engine's base key
        UNIQUE, so two replicas would sample different streams for the
        same rid. Disaggregated serving sets the SAME seed on every
        replica: a request migrated mid-stream (same rid, same
        positions) then re-samples token-exactly on the destination."""
        t_init = time.perf_counter()    # -> stats["engine_init_time_s"]
        from ..jit.functional_call import collect_state, read_values

        self.model = model
        #: serializes trace-capable dispatches across ALL engines built
        #: on this model object (replica servers sharing weights) — see
        #: _model_dispatch_lock. Wrapped for the lock-order watchdog
        #: when PADDLE_TPU_LOCK_CHECKS=1 (paddle_tpu.analysis, PTL004).
        self._dispatch_lock = _lockwatch.tracked(
            _model_dispatch_lock(model), "LLMEngine._dispatch_lock")
        # ---- runtime sanitizers (paddle_tpu.analysis) -----------------
        #: PADDLE_TPU_TRANSFER_CHECKS=1 (the test conftest's posture):
        #: every fused all-decode stride holds jax.transfer_guard
        #: ("disallow") from dispatch to readout on the stepping thread,
        #: proving PR 8's one-sync-per-stride contract as an assertion —
        #: a stray scalar pull in the window raises instead of costing
        #: p99. The documented readout increments stats["guarded_syncs"].
        self._transfer_checks = os.environ.get(
            "PADDLE_TPU_TRANSFER_CHECKS", "0") not in ("", "0")
        #: PADDLE_TPU_LOCK_CHECKS=1: pin the paged-pool allocator to the
        #: stepping thread — any allocator/quarantine/content-store
        #: mutation from another thread raises, naming the owner (the
        #: dynamic half of the PTL004 lock-discipline pass)
        self._lock_checks = _lockwatch.enabled()
        self._pool_owner = None
        c = model.config
        #: THE seam: the model's decoder, and the layout of its layers'
        #: state kinds, which answers whatever the engine asks of them
        self._decoder = model.decoder
        self._layout = Layout(model.cache_layout())
        #: device-side counts the model's layers make during a step; they
        #: leave the step program beside the tokens (booked at emit), and
        #: the ids some of them ride a step's emit span under
        self._step_counter_names = tuple(
            getattr(model, "step_counter_names", ()))
        self._step_emit_ids = dict(getattr(model, "step_emit_ids", {}))
        #: tensor-parallel serving (the multichip subsystem, serving/
        #: cluster.py): a mesh with a "tp" axis turns the engine's KV
        #: buffers into REAL NamedShardings — kv-heads shard across the
        #: axis (the paged pool's head dim / the dense buffers' head
        #: dim), block tables and the allocator stay host-global, and
        #: logits/lens/tokens stay replicated (the step's in-graph
        #: sample consumes replicated logits, so the vocab-sharded lm
        #: head all-gathers exactly once per step). Any other mesh keeps
        #: the legacy multi-process behavior: replicated global buffers.
        self._tp_size = int(mesh.shape["tp"]) if mesh is not None \
            and "tp" in tuple(mesh.axis_names) else 1
        self._tp_axis = "tp" if self._tp_size > 1 else None
        self._layout.refuse(
            scheduler=scheduler, cache_impl=cache_impl, horizon=horizon,
            kv_pool_blocks=kv_pool_blocks,
            enable_prefix_cache=enable_prefix_cache,
            kv_host_tier=kv_host_swap or kv_host_spill_bytes,
            speculative_k=speculative_k, kv_cache_dtype=kv_cache_dtype,
            adapter_store=adapter_store, mesh=self._tp_axis)
        self.B = int(max_batch)
        # decode horizon: tokens decoded per step() call as one compiled
        # lax.scan — amortizes the per-step host sync K-fold at the cost of
        # admitting/retiring requests only every K tokens
        self.horizon = max(1, int(horizon))
        # speculative verify windows (prompt-lookup drafting, NO reference
        # analog — the snapshot has no speculative decoding): each window
        # commits 1 sampled token plus up to speculative_k-1 drafted tokens
        # verified by ONE K-token model call. Drafting runs IN-GRAPH from a
        # device-side token history. Acceptance is COUPLED: a draft
        # survives iff it equals the token the engine would sample at
        # that position under its per-(rid, position) fold_in key, so a
        # speculative stream is TOKEN-IDENTICAL to the non-speculative
        # engine's — greedy and sampled alike — and restart/failover
        # resumption is exact in both modes. Under scheduler="legacy"
        # (dense only) windows run as a horizon scan; under
        # scheduler="fused" they are VERIFY grants in the token-budget
        # walk (any cache backend, mixing freely with prefill chunks,
        # plain decodes and embed prefills), with per-request
        # acceptance-adaptive draft counts and, for paged KV, zero-copy
        # block-table rollback of rejected tails.
        self.speculative_k = max(1, int(speculative_k))
        self.lookup_ngram = max(1, int(lookup_ngram))
        self.capacity = int(max_seq_len or c.max_position_embeddings)
        if self.capacity > c.max_position_embeddings:
            raise ValueError(
                f"max_seq_len {self.capacity} exceeds rope table "
                f"({c.max_position_embeddings})")
        self.chunk = int(chunk_size)
        self.top_k = int(top_k)
        self.stream_callback = stream_callback
        if scheduler not in ("legacy", "fused"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        self.scheduler = scheduler
        #: multi-step on-device decode (fused scheduler): ALL-DECODE
        #: steps run up to ``readout_stride`` decode iterations as ONE
        #: compiled loop with IN-GRAPH early exit (every slot hit
        #: eos/budget/capacity -> the loop stops on device), so the host
        #: round-trip tax amortizes k-fold in steady state while mixed
        #: (ramp-in) steps keep per-step scheduling. A request may pin a
        #: smaller stride (latency tier) — the effective stride of a
        #: step is the MIN over its active slots' pins.
        self.readout_stride = max(1, int(readout_stride))
        if self.readout_stride > 1:
            if scheduler != "fused":
                raise ValueError(
                    "readout_stride > 1 needs scheduler='fused' (the "
                    "legacy scheduler already amortizes host syncs with "
                    "`horizon`; the stride is the fused scheduler's "
                    "all-decode fast path)")
            if self.horizon > 1:
                raise ValueError(
                    "readout_stride generalizes `horizon` for the fused "
                    "scheduler's all-decode steps — set one, not both")

        model.eval()
        _, params, _, buffers = collect_state(model)
        self._state = params + buffers
        self._state_vals = read_values(self._state)

        # the K/V layers' heads: what the dense slot buffers and a
        # tensor-parallel mesh are sized by (the kinds size the pools)
        kv = self._layout.kv
        kvh, head_dim = (kv.kv_heads, kv.head_dim) if kv else (0, 0)
        dt = self._decoder.embed_tokens.weight.dtype
        # a prefill window is always a full `chunk` wide, so it must fit the
        # buffer (the final window slides BACK over already-written
        # positions instead of padding the time axis — see _admit)
        self.chunk = min(self.chunk, self.capacity)
        #: fused-scheduler per-step token cap: sum over slots of (prefill
        #: chunk grant | 1 decode token). Decode tokens always land; the
        #: budget throttles how much prefill may ride along per step.
        self.max_step_tokens = int(max_step_tokens) if max_step_tokens \
            else self.chunk + self.B - 1
        if self.max_step_tokens < 1:
            raise ValueError(f"max_step_tokens must be >= 1, got "
                             f"{self.max_step_tokens}")
        #: rows a mixed step computes: the height of its packed row axis
        #: (derived, models/cache_layout.py; never ``max_batch x chunk``
        #: unless the budget really grants that many)
        self.mixed_rows = packed_rows(self.max_step_tokens, self.B,
                                      self.chunk, self.speculative_k)
        self._mesh = mesh
        if kvh % self._tp_size:
            raise ValueError(
                f"num_key_value_heads {kvh} must divide by the tp "
                f"mesh axis ({self._tp_size}) — kv-heads are the "
                f"natural shard dim of the KV pools")
        if self.speculative_k > 1:
            # speculation is served by the fused scheduler's verify
            # grants (any cache backend) or the legacy dense scan; the
            # ONE remaining limitation is a tensor-parallel mesh
            if self._tp_axis is not None:
                raise ValueError(
                    "speculative_k > 1 under a tensor-parallel mesh is "
                    "the remaining speculation limitation: the verify "
                    "window's per-row lm-head gather has no TP wiring "
                    "yet — serve speculation single-chip, or drop to "
                    "speculative_k=1 on the TP replicas")
            if scheduler == "fused" and self.chunk < self.speculative_k:
                raise ValueError(
                    f"chunk_size {self.chunk} cannot carry a "
                    f"speculative_k={self.speculative_k} verify window "
                    f"(the fused mixed step's ids buffer is chunk "
                    f"tokens wide)")
        import ml_dtypes  # noqa: F401  (np.zeros understands bf16 via jnp)
        self._kvh = kvh
        self._head_dim = head_dim
        self._vocab = c.vocab_size
        self._np_dt = np.dtype(dt) if mesh is not None else dt
        if mesh is not None:
            from jax.sharding import PartitionSpec
            self._kv_spec = PartitionSpec(None, self._tp_axis) \
                if cache_impl == "paged" \
                else PartitionSpec(None, None, self._tp_axis)
        else:
            self._kv_spec = None
        self.cache_impl = cache_impl
        if enable_prefix_cache and cache_impl != "paged":
            raise ValueError("enable_prefix_cache needs cache_impl='paged' "
                             "(content-hashed block reuse lives in the "
                             "paged pool's table indirection; the dense "
                             "per-slot buffers have nothing to share)")
        self.prefix_cache = bool(enable_prefix_cache)
        if kv_cache_dtype is not None:
            if kv_cache_dtype not in ("int8", "int4"):
                raise ValueError(
                    f"unknown kv_cache_dtype {kv_cache_dtype!r} "
                    f"(supported: 'int8', 'int4', None)")
            if cache_impl != "paged":
                raise ValueError(
                    "kv_cache_dtype needs cache_impl='paged' — per-block "
                    "quantization scales live in the paged pool's block "
                    "granularity; the dense per-slot buffers have no "
                    "block to scale over")
        #: KV-pool quantization mode (None = bf16 pools, bit-identical
        #: to the pre-quantization engine)
        self.kv_quant = kv_cache_dtype
        # ---- host KV tier (DistServe/Splitwise-style memory tiering) --
        self.kv_host_swap = bool(kv_host_swap)
        self.kv_host_spill_bytes = int(kv_host_spill_bytes or 0)
        #: replica-independent sampling base key (None = pull one from
        #: the global generator at the first step) — SURVIVES reset()
        #: with the rest of the sampling-key contract
        self._sampling_seed = (int(sampling_seed)
                               if sampling_seed is not None else None)
        if self.kv_host_swap:
            if cache_impl != "paged":
                raise ValueError(
                    "kv_host_swap needs cache_impl='paged' — the host "
                    "tier swaps physical pool blocks; the dense per-slot "
                    "buffers have none")
            if scheduler != "fused":
                raise ValueError(
                    "kv_host_swap needs scheduler='fused' — re-admission "
                    "restores blocks and resumes the ramp at the stitch "
                    "position, which only the fused scheduler's "
                    "prefill_pos can express (legacy admission prefills "
                    "whole chunk trains)")
        if self.kv_host_spill_bytes:
            if cache_impl != "paged" or not enable_prefix_cache:
                raise ValueError(
                    "kv_host_spill_bytes needs cache_impl='paged' with "
                    "enable_prefix_cache=True — the spill store holds "
                    "LRU-EVICTED registered prefix blocks; without the "
                    "content store there is no eviction to spill")
        if cache_impl == "paged":
            if self.speculative_k > 1 and scheduler != "fused":
                raise ValueError(
                    "the legacy scheduler's speculative path is "
                    "dense-only — paged speculation rides the fused "
                    "scheduler's verify grants through the append-form "
                    "attention path (scheduler='fused')")
            self.block_size = int(block_size)
            if self.chunk % self.block_size:
                raise ValueError(f"chunk_size {self.chunk} must be a "
                                 f"multiple of block_size {self.block_size}")
            if self.capacity % self.chunk and self._layout.has_paged:
                raise ValueError(f"capacity {self.capacity} must be a "
                                 f"multiple of chunk_size {self.chunk} "
                                 f"under paged KV")
            self._max_blocks = -(-self.capacity // self.block_size)
            #: table entries a grid step walks, a paged kind of the layout
            self._grid_entries = self._layout.entries_per_step(
                self._max_blocks, self.block_size)
            full = self.B * self._max_blocks
            self.n_blocks = int(kv_pool_blocks or full)
            #: pool-invariant debug audit (satellite): on under
            #: PADDLE_TPU_POOL_CHECKS=1 (the test suite sets it) —
            #: asserts free + cached + live-refcounted == n_blocks and
            #: table/refcount consistency after every alloc/free.
            self._debug_pool = os.environ.get(
                "PADDLE_TPU_POOL_CHECKS", "0") not in ("", "0")
        # ---- batched multi-LoRA (serving/adapters.py) ----------------
        #: host AdapterStore of registered low-rank deltas; None = the
        #: multi-tenant machinery is entirely absent (every program
        #: traces the pre-adapter body — bit-identical serving). With a
        #: store attached but EMPTY, dispatches still pass lora=None, so
        #: base output stays bit-identical until the first registration
        #: (which retraces the step programs exactly once).
        self.adapter_store = adapter_store
        self._adapter_slots = int(adapter_cache_slots)
        #: lazily-built AdapterDeviceCache (stacked device factors +
        #: LRU slot allocator); reset() drops it with the other device
        #: buffers and the next adapter dispatch rebuilds + re-swaps
        self.adapter_cache = None
        if adapter_store is not None:
            if self.speculative_k > 1 and scheduler != "fused":
                raise ValueError(
                    "the legacy speculative scan is not adapter-aware — "
                    "batched multi-LoRA speculation rides the fused "
                    "scheduler's verify grants (scheduler='fused')")
            if getattr(c, "fuse_attention_qkv", False) or \
                    getattr(c, "fuse_swiglu", False):
                raise ValueError(
                    "batched multi-LoRA targets the separate q/k/v and "
                    "gate/up projections — build the serving model "
                    "without fuse_attention_qkv/fuse_swiglu")
        self._hidden = c.hidden_size
        # admission-order stamps: the paged allocator's preempt-newest
        # invariant AND the fused scheduler's oldest-first budget walk
        self._admit_order = [0] * self.B
        self._admit_seq = 0
        self._init_device_state()

        # host-side slot table / queues
        self.slots: list[_Slot | None] = [None] * self.B
        self.waiting: collections.deque[GenerationRequest] = \
            collections.deque()
        self.finished_outputs: dict[int, RequestOutput] = {}
        self._next_id = 0
        #: tokens a preempted request committed before eviction, stitched
        #: back in front of its post-readmission stream at finish
        self._preempted_prefix = {}
        self._rng_key = None
        self._step_fn = None
        self._prefill_fn = None
        self._set_logits_fn = None
        self._set_pooled_fn = None
        #: outstanding step_begin() dispatches not yet step_finish()ed —
        #: the paged engine must stay at depth 1 (its host block allocator
        #: needs post-step lens before the next dispatch)
        self._inflight = 0
        #: optional FlightRecorder (profiler.flight_recorder): when
        #: attached and enabled, step_begin/step_finish emit one
        #: StepRecord per step and stamp every emitted token with its
        #: step id. None (the default) costs one attribute check per step.
        self.flight_recorder = None
        #: optional FaultInjector (serving.faults): scripted chaos
        #: schedules fire at the step_begin/step_finish hooks. None (the
        #: default) costs one attribute check per step.
        self.fault_injector = None
        self._rec_ctx = None       # per-step_begin wall-split anchors
        self._rec_preempted = []   # rids parked by _preempt_slot this step
        self._phase = None    # (name, entered at, span, ids) — see _to
        #: compiled multi-step decode programs, keyed by stride K (one
        #: program per distinct effective stride; survives reset())
        self._multi_fns = {}
        self._multi_step_factory = None
        #: compiled multi-window SPECULATIVE decode programs, keyed by
        #: stride (windows per dispatch); survives reset() like
        #: _multi_fns
        self._multi_spec_fns = {}
        self._multi_spec_factory = None
        #: rid -> draft-acceptance EWMA — the acceptance-adaptive
        #: verify-k state, SURVIVES reset() (like the rid counter and
        #: the sampling base key) so a supervised restart's re-admitted
        #: request resumes speculation at its learned window, not at the
        #: optimistic default. Entries drop at request finish.
        self._spec_ewma = {}
        #: seconds the CURRENT token's emit stamp should be backdated by
        #: (step_finish amortizes a k-row readout over the dispatch→sync
        #: window; 0.0 outside a readout walk and for 1-row steps) — the
        #: serving layer reads it inside its stream callback
        self.emit_backdate_s = 0.0
        self.stats = dict(default_engine_stats(),
                          **dict.fromkeys(self._step_counter_names, 0))
        watch_gc(self)
        self.stats["engine_init_time_s"] = time.perf_counter() - t_init

    # ------------------------------------------------------------------
    # device state (built at __init__, REBUILT by reset())
    # ------------------------------------------------------------------
    def _make_zeros(self, shape, dtype, spec=None):
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(self._mesh, spec or PartitionSpec())
            shard = np.zeros(sharding.shard_shape(tuple(shape)), dtype)
            return jax.make_array_from_callback(
                shape, sharding, lambda idx: shard)
        return jnp.zeros(shape, dtype)

    def _init_device_state(self):
        """(Re)build every device-side buffer and the host allocator /
        content-store state from scratch. Called by ``__init__`` and by
        :meth:`reset` — after a crash the old buffers may be donated-away
        or mid-flight, so recovery rebuilds rather than trusts them. The
        compiled programs survive (same shapes, same shardings)."""
        L = len(self._layout)
        if self.cache_impl == "paged":
            # one state pair a layer, built by its kind: pools on the
            # allocator's blocks (quantized and sharded as the K/V kind
            # does it), a recurrent state a slot
            self._k, self._v = self._layout.alloc(
                self._make_zeros, self.n_blocks, self.block_size, self.B,
                self._np_dt, self.kv_quant, self._kv_spec)
            self._tables = np.full((self.B, self._max_blocks), -1, np.int32)
            #: min-heap of free physical blocks: allocation always pops
            #: the SMALLEST free index, so physical layout is a pure
            #: function of the request/retirement sequence — repeated
            #: runs produce identical tables (the old list popped LIFO
            #: from the tail, making layout depend on retirement history
            #: and trace diffs noisy). list(range(n)) is already a heap.
            self._free_blocks = list(range(self.n_blocks))
            self._slot_blocks = [[] for _ in range(self.B)]
            #: per-block live reference count (prefix-cache sharing makes
            #: >1 possible; without it the count is only ever 0/1)
            self._block_ref = [0] * self.n_blocks
            # ---- content-addressed store (enable_prefix_cache) -------
            #: chain_hash -> phys for every REGISTERED full block; the
            #: hash chains over the whole prefix, so equal prefixes
            #: collide on purpose and the probe walk is one dict get per
            #: block
            self._store = {}
            self._block_hash = {}    # phys -> chain hash (registered)
            self._block_parent = {}  # phys -> parent chain hash
            self._block_tokens = {}  # phys -> block token ids (bytes)
            self._children = {}      # parent hash -> [phys, ...]
            #: refcount-0 registered blocks, oldest-freed first — the
            #: "cached" pool between live and free. Allocation evicts
            #: from HERE (oldest first) before any live slot is
            #: preempted.
            self._lru = collections.OrderedDict()
            # ---- stride-aware in-flight write fence ------------------
            #: phys -> number of IN-FLIGHT dispatches that may still
            #: write the block (stamped at step_begin over each active
            #: slot's committed-len..scheduled-stride span, dropped at
            #: that step's step_finish). The allocation ladder must
            #: never hand a fenced block to a new owner: a freed block
            #: still under fence parks in ``_quarantine`` instead of
            #: the free heap — this is what makes eviction/preemption
            #: safe while dispatches pipeline at depth > 1.
            self._write_fence = {}
            #: refcount-0 UNREGISTERED blocks whose fence has not
            #: cleared yet — released to the free heap by the
            #: step_finish that drops their last fence
            self._quarantine = set()
            # ---- host KV tier (kv_host_swap / kv_host_spill_bytes) ---
            #: rid -> swap entry (tokens covered, host block copies,
            #: tenant) for requests whose committed KV was demoted to
            #: host RAM at preemption. Entries drop at re-admission
            #: (consumed), at any terminal finish (_finish_tokens), and
            #: at reset() — a supervised restart re-prefills instead.
            self._swap_store = {}
            #: rid -> cumulative STITCH wall (s) of shipped-entry
            #: restores (the migration's last phase, timed where it
            #: actually runs — the decode replica's mixed step). The
            #: router folds it into its per-migration phase breakdown;
            #: entries drop with the rid's swap entry lifecycle.
            self._stitch_s = {}
            #: swap/spill entries whose device→host copies were issued
            #: but not yet materialized to numpy — drained in the
            #: step_begin/step_finish gap (the copy overlaps the step's
            #: device work) or on first use, whichever comes first
            self._swap_pending = []
            #: chain_hash -> spilled-block entry: the bounded host store
            #: LRU-evicted REGISTERED prefix blocks demote into (oldest
            #: spilled first out when the byte budget fills)
            self._spill = collections.OrderedDict()
            self._spill_bytes = 0
            # ---- cross-replica KV shipping (serving/kv_transport) ----
            #: rid -> staged EXPORT entry (tokens + tenant + per-layer
            #: block stacks + chain hashes), written by the engine
            #: thread at an export_kv-flagged request's finish site and
            #: popped by the router thread via export_kv(). Bounded:
            #: oldest entries drop when a router never collects.
            self._export_store = collections.OrderedDict()
            self._export_cap = 2 * self.B
            #: shipped PREFIX-block entries awaiting the engine thread
            #: (pull-on-miss imports land here from the router thread —
            #: a GIL-atomic list append — and drain into _spill at the
            #: top of the next step, before admission probes run)
            self._spill_inbox = []
        else:
            shape = (self.B, self.capacity, self._kvh, self._head_dim)
            self._k = [self._make_zeros(shape, self._np_dt, self._kv_spec)
                       for _ in range(L)]
            self._v = [self._make_zeros(shape, self._np_dt, self._kv_spec)
                       for _ in range(L)]
        self._logits = self._make_zeros((self.B, self._vocab), np.float32)
        self._lens = self._make_zeros((self.B,), np.int32)
        # device-side committed-token history (speculative mode): the
        # in-graph prompt-lookup draft reads it, decode windows append
        self._tokens = self._make_zeros((self.B, self.capacity), np.int32) \
            if self.speculative_k > 1 else None
        #: per-slot mean-pool accumulator for PREFILL-ONLY (embed)
        #: requests: each fused mixed step adds the sum of its granted
        #: prefill rows' final hidden states; the finishing readout
        #: divides by the prompt length. Zeroed per slot at admission.
        self._pooled = self._make_zeros((self.B, self._hidden), np.float32)
        #: the adapter device cache dies with the other device buffers
        #: (a crashed dispatch may have consumed its stacks through
        #: donation) — the next adapter dispatch rebuilds and re-swaps
        self.adapter_cache = None
        #: pool bytes incl. scale arrays, cached once per (re)build — the
        #: flight recorder stamps it on every StepRecord
        self._kv_nbytes = self.kv_pool_nbytes()

    def reset(self):
        """Tear the engine down to EMPTY and re-arm it — the supervised
        server's crash-recovery hook (``AsyncLLMServer(supervise=...)``).

        Every slot, waiting request, finished output, preemption stitch
        and (paged) pool/table/content-store binding drops; the device
        buffers are rebuilt from zeros (a crashed dispatch may have
        consumed the old ones through buffer donation, so they cannot be
        trusted or even touched) — on a quantized engine that includes
        the per-block scale arrays, rebuilt alongside their pools (zero
        scales over zero payloads dequantize to the same cold state). What SURVIVES: the compiled programs
        (identical shapes/shardings — a restart costs no recompile), the
        request-id counter (rids stay unique across restarts), the
        engine's cumulative ``stats``, the rid-keyed draft-acceptance
        EWMA mirror (a re-admitted speculative request resumes at its
        learned verify window), and the sampling base key — token ``p``
        of request ``r`` samples from ``fold_in(fold_in(key, r), p)``,
        so a re-admitted request's sampled stream continues exactly
        where the crash cut it. ``_check_pool_invariants`` holds
        trivially after a reset."""
        self._close_stride_guard()
        self._pool_owner = None
        self.slots = [None] * self.B
        self.waiting.clear()
        self.finished_outputs.clear()
        self._preempted_prefix.clear()
        self._inflight = 0
        self._admit_order = [0] * self.B
        self._rec_ctx = None
        self._rec_preempted = []
        self._init_device_state()
        if self.cache_impl == "paged":
            self._check_pool_invariants()
        return self

    # ------------------------------------------------------------------
    # the measurement points: host phases and program builds
    # ------------------------------------------------------------------
    def _to(self, phase, **ids):
        """Leave the engine's current host phase and enter ``phase`` (None:
        none) — THE measurement point of step_begin/step_finish: the one
        clock read at a boundary closes the phase left (its wall goes to
        its ``stats`` sums, its ``pt:engine.*`` span ends) and opens the
        one entered (``ids`` ride on its span, with ``pc_ns``, the
        boundary on the ``perf_counter`` clock in ns, which lays every
        perf_counter-stamped StepRecord and request event onto a
        profile's clock). Returns the boundary's ``perf_counter()``.
        Phases never nest; step_begin and step_finish each leave the
        last one they entered, on the thread that entered it."""
        now = time.perf_counter()
        if self._phase is not None:
            name, t0, ann, _ = self._phase
            for key in _PHASES[name][1]:
                self.stats[key] += now - t0
            ann.__exit__(None, None, None)
        if phase is None:
            self._phase = None
        else:
            if ids:
                ids["pc_ns"] = int(now * 1e9)
            ann = span(_PHASES[phase][0], **ids)
            ann.__enter__()
            self._phase = (phase, now, ann, ids)
        return now

    def _dispatch_ids(self, kind, rows, live_tokens, granted,
                      prefill_rows=0, live_tiles=None):
        """What rides on a ``pt:engine.dispatch`` span. ``step_id`` is the
        StepRecord's where a recorder is attached, else this dispatch's
        index among the engine's own, so flight-recorder timelines and
        the profile join by id. The step's shape: ``prefill_rows`` and
        ``decode_rows`` of its ``live_tokens``, and ``ctx_tokens``, the
        tokens the ``granted`` slots (a mask) hold before it (the host's
        lens mirror, so call this BEFORE the mirrors grow): what a
        kernel-alone run needs to stand at the traced steps' own
        ``(seq_lens, q_lens)``. ``live_tiles``: a mixed paged step's
        ``attn_tile_steps``."""
        rec = self._rec()
        ids = dict(
            step_id=(rec.next_step_id() if rec is not None
                     else self.stats["steps"] + self._inflight),
            kind=DISPATCH_KINDS.index(kind), rows=int(rows),
            live_tokens=int(live_tokens), prefill_rows=int(prefill_rows),
            decode_rows=int(live_tokens) - int(prefill_rows),
            ctx_tokens=sum(s.sched_len() for s, g in zip(self.slots, granted)
                           if g and s is not None))
        if live_tiles is not None:
            ids["live_tiles"] = int(live_tiles)
        return ids

    @property
    def _loop_steps(self):
        return self._layout.loop_steps

    def _attn_tile_steps(self, q_lens):
        """``(run, grid)`` row-tile steps of the append kernel for a
        mixed paged step granting ``q_lens``, per kv head and K/V layer
        (they multiply both alike; a layout's other layers run no such
        kernel): the kernel module's own count over the host's lens
        mirror, so call this BEFORE the mirrors grow. The group (query
        heads a kv head) is the K/V kind's to say. The step's rows are
        packed (``RowMap``: a slot's first row is the grants before it),
        and the kernel's tiles lie on that axis. The host's grants, as
        every count here: a slot the step's own capacity guard takes out
        in the graph (pipelined over-dispatch; the read-out's margin
        retires a slot before it fires) is still counted, and the slots
        after it as if it held its rows."""
        from ..ops.kernels.paged_attention import append_tile_steps
        lens = [0 if s is None else s.sched_len() for s in self.slots]
        q_lens = np.asarray(q_lens, np.int64)
        return append_tile_steps(
            lens, q_lens, self._layout.kv.group(self.model.config),
            self.chunk, self.block_size, self._tables.shape[1],
            np.cumsum(q_lens) - q_lens)

    def _book_kv_grid(self, iterations):
        """One paged dispatch's attention grid against what it holds: the
        walk visits every table entry of every slot, ``iterations`` times
        (a stride's decode iterations; 1 for a mixed step) — the append
        kernel's too: it skips the work of a dead entry, not the entry
        (one grid step for all of its kv heads); an entry is live when it
        holds a token once the dispatch has landed (host lens mirror, so
        call this after the mirrors grew). The counts are of ONE paged
        layer of each paged kind the layout has: the layers of a kind
        multiply both counts alike, and so do heads in the decode kernel,
        and a recurrent layer beside them walks no table and adds
        nothing. A kind whose kernel walks its table in wide entries
        says how many of them a grid step takes: both counts are in its
        grid steps, one live when it holds a live entry. A looped layout
        walks the table once a loop step: both counts times R. Beside
        them, the pool's blocks in use at this dispatch."""
        if not self._layout.has_paged:
            return      # no pool, no attention grid: nothing to book
        bs = self.block_size
        iterations *= self._layout.loop_steps
        for n in self._grid_entries:
            live = sum(-(-s.sched_len() // (bs * n))
                       for s in self.slots if s is not None)
            grid = self._tables.size // n
            self.stats["kv_grid_blocks"] += iterations * grid
            self.stats["kv_live_blocks"] += iterations * min(live, grid)
        self.stats["pool_blocks_used"] += self.n_blocks - len(
            self._free_blocks) - len(self._lru) - len(self._quarantine)
        self.stats["pool_blocks_total"] += self.n_blocks

    def _program(self, name, fn):
        """``fn`` (a jitted program) with its builds booked. A build is
        a call that found no compiled entry in ``fn``'s own jit cache —
        it traced and compiled, or loaded from the persistent cache —
        and jit itself says so: its cache grew over the call. The wall
        of such a call goes to ``program_build_time_s``. The first call
        of a program is a build before it starts, so it runs under a
        ``pt:engine.build`` span; a later retrace (a new argument
        structure, e.g. the first LoRA batch) is known only once the
        call is back and is booked without one. Either way the build
        leaves a record in ``profiler.builds()`` (its wall split into
        trace, lowering and compile-or-load) that names the step it
        rode on; a retrace's also goes to the log."""
        pid, first = PROGRAMS.index(name), True

        def step_id():
            return self._phase[3].get("step_id") if self._phase else None

        def call(*args, **kw):
            nonlocal first
            size, t0 = fn._cache_size(), time.perf_counter()
            if first:
                first = False
                with build("pt:engine.build", name, step_id(), program=pid):
                    out = fn(*args, **kw)
                wall = time.perf_counter() - t0
            else:
                out = fn(*args, **kw)
                if fn._cache_size() == size:
                    return out
                wall = time.perf_counter() - t0
                build_retraced("pt:engine.build", name, t0, wall, step_id())
            self.stats["program_build_time_s"] += wall
            self.stats["programs_built"] += 1
            return out
        return call

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _programs(self):
        if self._step_fn is not None:
            return
        model = self.model
        decoder = self._decoder
        layout = self._layout
        n_ctr = len(self._step_counter_names)
        ctr_zero = jnp.zeros((n_ctr,), jnp.int32)
        #: the scheduler's bound on a mixed step's live rows over all
        #: slots (decode tokens always land, prefill takes the rest):
        #: what an expert layer sizes its grouped product by
        row_budget = max(self.max_step_tokens, self.B)
        #: the packed height of a mixed step's row axis: that bound,
        #: rounded to the chip's row tile (``cache_layout.packed_rows``)
        T = self.mixed_rows
        state = self._state
        B, cap, chunk = self.B, self.capacity, self.chunk
        top_k = self.top_k

        if self._tp_axis is not None:
            # TP sharding pins: KV buffer outputs keep the kv-head shard
            # (so donation round-trips in place and GSPMD never resolves
            # a step to a resharded layout), everything the HOST reads
            # (tokens, carried logits, lens) pins replicated — the
            # vocab-sharded lm head all-gathers into the logits exactly
            # once per step, and np.asarray readouts see full replicas.
            from jax.sharding import NamedSharding, PartitionSpec as _P
            _kv_sh = NamedSharding(
                self._mesh,
                _P(None, self._tp_axis) if self.cache_impl == "paged"
                else _P(None, None, self._tp_axis))
            _rep_sh = NamedSharding(self._mesh, _P())

            def _pin_kv(bufs):
                # tree_map: a quantized pool entry is a (payload, scale)
                # TUPLE — the paged P(None, tp) spec pins both (axis 1 is
                # kv heads on the 4-D payload and the 2-D scale alike)
                return jax.tree_util.tree_map(
                    lambda b: jax.lax.with_sharding_constraint(b, _kv_sh),
                    list(bufs))

            def _pin_rep(x):
                return jax.lax.with_sharding_constraint(x, _rep_sh)
        else:
            def _pin_kv(bufs):
                return bufs

            def _pin_rep(x):
                return x

        kvq = self.kv_quant

        K = self.horizon

        def pick_tokens(logits, temps, draw):
            """THE greedy/sampled select of ``sample_next`` and
            ``row_sample``, gated on what the step's ``temps`` describe:
            ``logits`` [B, ..., V], ``temps`` [B], ``draw()`` -> the
            filtered categorical's tokens [B, ...]. A step with a row at
            ``temperature > 0`` runs argmax, ``draw()`` and the per-row
            select; an all-greedy step (free slots carry 0.0) runs the
            argmax alone — no sort over the vocabulary, no random bits.
            The ``lax.cond`` sits outside every ``vmap`` (under one it
            would lower to a select that runs both sides), and its
            predicate is the program's own input: one compiled program
            either way."""
            with scope("pt.sample"):
                def greedy():
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32)

                def sampled():
                    cold = (temps <= 0.0).reshape(
                        temps.shape + (1,) * (logits.ndim - 2))
                    return jnp.where(cold, greedy(), draw())
                return jax.lax.cond(jnp.any(temps > 0.0), sampled, greedy)

        def sample_next(logits, key, temps, top_ps, rids, lens):
            """THE sample-from-carried-logits prologue: greedy rows argmax,
            sampling rows the filtered categorical, per-slot select. One
            copy consumed by one_step, the spec verify windows, AND the
            fused mixed step (the carried-logits fix once had to be
            applied in several copies of this code). An all-greedy step
            runs the argmax alone (``pick_tokens``).

            Sampling keys derive as ``fold_in(fold_in(key, rid), pos)``
            instead of advancing one global split stream: the token
            sampled at position ``pos`` of request ``rid`` is a pure
            function of (engine base key, rid, position), so batch
            composition, pool-pressure preemption replay, and supervised
            engine RESTART (the fault-tolerance layer's token-exact
            resumption) cannot change a sampled stream. Greedy rows never
            consult the key, and EVERY path — including the speculative
            verify windows, whose coupled acceptance rule re-derives the
            same per-position keys instead of advancing a shared stream —
            leaves ``key`` untouched across steps, so resumption is
            token-exact in sampled mode too (docs/architecture.md)."""
            def draw():
                keys = jax.vmap(lambda r, p: jax.random.fold_in(
                    jax.random.fold_in(key, r), p))(rids, lens)
                return jax.vmap(
                    lambda k, row, t, tp: _sample_logits_device(
                        row, k, jnp.maximum(t, 1e-6), top_k, tp, False,
                        True)
                )(keys, logits, temps, top_ps)
            return pick_tokens(logits, temps, draw)

        def one_step(k_bufs, v_bufs, logits, lens, active, rng, state_vals,
                     temps, top_ps, eos_ids, rids, tables, lora=None):
            """sample from current logits -> one-token model step.
            ``tables`` selects the cache backend at TRACE time: None ->
            dense SlotKVCache slot buffers; a [B, MB] array -> PagedKVCache
            block pool (ONE body serves both engines). ``lora`` (batched
            multi-LoRA): the traced adapter stacks + per-slot device
            rows — the scope adds the gathered delta to every llama
            projection; None traces the exact pre-adapter body."""
            nxt = sample_next(logits, rng, temps, top_ps, rids, lens)
            # inactive slots decode garbage; pin them to token 0
            nxt = jnp.where(active, nxt, 0)
            with functional_mode(), _bind(state, state_vals), \
                    lora_scope(lora):
                if tables is None:
                    caches = [SlotKVCache(k, v, lens)
                              for k, v in zip(k_bufs, v_bufs)]
                else:
                    caches = layout.caches(k_bufs, v_bufs, tables, lens,
                                           None, active, B)
                with collect_counts() as counted:
                    hidden, new_caches = decoder(
                        Tensor(nxt[:, None]), kv_caches=caches,
                        position_offset=Tensor(lens))
                new_logits = model._logits(hidden)._value[:, 0] \
                    .astype(jnp.float32)
            # an INACTIVE row's carried logits must survive the remaining
            # scan iterations — a slot deactivated non-terminally (pool
            # budget clamp) samples from them next step
            kb, vb = layout.unpack(new_caches)
            with scope("pt.readout"):
                new_logits = jnp.where(active[:, None], new_logits, logits)
                new_lens = jnp.where(active, lens + 1, lens)
                finished = active & (nxt == eos_ids)
                return (nxt, new_logits, kb, vb, new_lens, finished, rng,
                        sum(counted, ctr_zero) if n_ctr else None)

        def step(state_vals, k_bufs, v_bufs, logits, lens, active, rng,
                 temps, top_ps, eos_ids, budgets, rids, tables=None,
                 lora=None):
            """`horizon` decode iterations as ONE compiled lax.scan — the
            host sync amortizes over K
            tokens per slot. A slot that hits eos, capacity, or its
            remaining budget mid-horizon deactivates in-graph; the host
            reads the per-iteration (tokens, active) history to attribute
            outputs. ``tables`` (paged mode) is a traced input — the host
            allocator mutates it between steps without recompiling."""
            def body(carry, _):
                kb, vb, logits, lens, act, emitted, rng, ctr = carry
                nxt, logits, kb, vb, lens, finished, rng, c1 = one_step(
                    kb, vb, logits, lens, act, rng, state_vals, temps,
                    top_ps, eos_ids, rids, tables, lora)
                with scope("pt.readout"):
                    emitted = emitted + act.astype(jnp.int32)
                    act_next = act & ~finished & (lens < cap - 1) & \
                        (emitted < budgets)
                    if n_ctr:
                        ctr = ctr + c1
                return (kb, vb, logits, lens, act_next, emitted, rng,
                        ctr), (nxt, act)

            emitted0 = jnp.zeros_like(lens)
            ctr0 = jnp.zeros((n_ctr,), jnp.int32) if n_ctr else None
            (k_bufs, v_bufs, logits, lens, active, _, rng, ctr), \
                (toks, was_active) = jax.lax.scan(
                    body,
                    (k_bufs, v_bufs, logits, lens, active, emitted0, rng,
                     ctr0),
                    None, length=K)
            return (_pin_rep(toks), _pin_rep(was_active), _pin_rep(logits),
                    _pin_kv(k_bufs), _pin_kv(v_bufs), _pin_rep(lens), rng,
                    ctr)

        def make_multi_step(Kms):
            """Build the ``readout_stride=Kms`` MULTI-STEP decode
            program: up to Kms one_step iterations as ONE dispatch, as a
            ``lax.while_loop`` that EARLY-EXITS IN-GRAPH the moment no
            slot is active (every slot hit eos / its budget / capacity)
            — unlike the horizon scan, a batch that finishes 1 step into
            a 4-step stride pays 1 step of device compute, not 4. Token
            and activity rows land in [Kms, B] buffers (rows past the
            exit stay zero/inactive, which the shared readout walk
            already skips), so step_finish drains the whole stride in
            the same single [rows, B] device→host sync."""
            def multi_step(state_vals, k_bufs, v_bufs, logits, lens,
                           active, rng, temps, top_ps, eos_ids, budgets,
                           rids, tables=None, lora=None):
                nL = len(k_bufs)

                def cond(carry):
                    i = carry[0]
                    act = carry[5]
                    return (i < Kms) & jnp.any(act)

                def body(carry):
                    i, kb, vb, lg, ln, act, emitted, toks, wa, ctr = carry
                    nxt, lg, kb, vb, ln, finished, _, c1 = one_step(
                        kb, vb, lg, ln, act, rng, state_vals, temps,
                        top_ps, eos_ids, rids, tables, lora)
                    with scope("pt.readout"):
                        if n_ctr:
                            ctr = ctr + c1
                        toks = jax.lax.dynamic_update_slice(
                            toks, nxt[None], (i, jnp.int32(0)))
                        wa = jax.lax.dynamic_update_slice(
                            wa, act[None], (i, jnp.int32(0)))
                        emitted = emitted + act.astype(jnp.int32)
                        act = act & ~finished & (ln < cap - 1) & \
                            (emitted < budgets)
                    return (i + 1, kb, vb, lg, ln, act, emitted, toks, wa,
                            ctr)

                carry = (jnp.int32(0), list(k_bufs), list(v_bufs), logits,
                         lens, jnp.asarray(active),
                         jnp.zeros_like(lens),
                         jnp.zeros((Kms, B), jnp.int32),
                         jnp.zeros((Kms, B), bool),
                         jnp.zeros((n_ctr,), jnp.int32) if n_ctr else None)
                (_, k_out, v_out, logits, lens, _, _, toks, wa, ctr) = \
                    jax.lax.while_loop(cond, body, carry)
                assert len(k_out) == nL
                return (_pin_rep(toks), _pin_rep(wa), _pin_rep(logits),
                        _pin_kv(k_out), _pin_kv(v_out), _pin_rep(lens),
                        rng, ctr)
            return multi_step

        self._multi_step_factory = make_multi_step

        Kspec = self.speculative_k
        ngram = self.lookup_ngram

        def row_sample(logits_rows, key, temps, top_ps, rids, poss):
            """Per-(row, position) COUPLED sampler: the token the engine
            would commit at each position — greedy rows argmax, sampled
            rows the filtered categorical under the per-(rid, position)
            fold_in key, i.e. EXACTLY the key ``sample_next`` would use
            when the stream reaches that position one token at a time.
            ``logits_rows`` [B, R, V], ``poss`` [B, R] -> [B, R] int32.
            The verify rule is built on this coupling: a draft is
            accepted iff it EQUALS this token, so a speculative stream
            is token-identical to the non-spec engine's — greedy AND
            sampled — and restart/failover resumption needs no
            acceptance-randomness replay (there is none). An all-greedy
            step runs the argmax alone (``pick_tokens``)."""
            def per_slot(k_rid, rows, t, tp, ps):
                return jax.vmap(lambda p, row: _sample_logits_device(
                    row, jax.random.fold_in(k_rid, p),
                    jnp.maximum(t, 1e-6), top_k, tp, False, True))(ps, rows)

            def draw():
                k_rids = jax.vmap(
                    lambda r: jax.random.fold_in(key, r))(rids)
                return jax.vmap(per_slot)(k_rids, logits_rows, temps,
                                          top_ps, poss)
            return pick_tokens(logits_rows, temps, draw)

        def verify_window(logits_win, draft, lens, q_eff, key, temps,
                          top_ps, rids, active):
            """Coupled acceptance over ONE verify window. ``logits_win``
            [B, Kw, V] are the model's logits over the window rows
            (row j = the distribution for position lens+1+j given the
            window prefix), ``draft`` [B, Kw-1] the prompt-lookup
            proposals, ``q_eff`` the per-slot granted window width (1 +
            drafts; rows past it are padding and never accept). Draft j
            survives iff it equals the COUPLED sample at its position
            and every earlier draft did. Returns ``(counts, n_acc,
            next_logits)``: committed tokens per slot (1 + accepted
            drafts), accepted-draft counts, and the carried logits at
            the last accepted row — the distribution the NEXT committed
            token samples from, which by the coupling is exactly the
            non-spec engine's carried-logits state."""
            Kd = draft.shape[1]
            poss = lens[:, None] + 1 + \
                jnp.arange(Kd, dtype=jnp.int32)[None, :]
            targets = row_sample(logits_win[:, :Kd], key, temps, top_ps,
                                 rids, poss)
            acc = (targets == draft) & \
                (jnp.arange(Kd)[None, :] < (q_eff - 1)[:, None])
            n_acc = jnp.cumprod(acc.astype(jnp.int32), axis=1) \
                .sum(axis=1).astype(jnp.int32)
            counts = jnp.where(active, 1 + n_acc, 0)
            next_logits = jnp.take_along_axis(
                logits_win, n_acc[:, None, None], axis=1)[:, 0]
            return counts, n_acc, next_logits

        def spec_step(state_vals, k_bufs, v_bufs, logits, lens, active, rng,
                      temps, top_ps, eos_ids, budgets, rids, tokens_buf):
            """`horizon` speculative verify windows as ONE compiled scan.
            Each window: in-graph prompt-lookup draft from the device token
            history -> commit one sampled token + verify the Kspec-1 drafts
            with ONE Kspec-token model call (verify_window: COUPLED
            acceptance, so greedy and sampled streams are both token-exact
            vs plain decode and the key never advances). KV written past
            the accepted prefix is stale but unreferenced (lens-based
            masks) and is overwritten by the next window."""
            def body(carry, _):
                kb, vb, logits, lens, act, emitted, rng, tbuf = carry
                draft = _lookup_draft(tbuf, lens, Kspec - 1, ngram)
                committed = sample_next(logits, rng, temps, top_ps, rids,
                                        lens)
                committed = jnp.where(act, committed, 0)
                window = jnp.concatenate([committed[:, None], draft],
                                         axis=1)
                with functional_mode(), _bind(state, state_vals):
                    caches = [SlotKVCache(k, v, lens)
                              for k, v in zip(kb, vb)]
                    hidden, new_caches = decoder(
                        Tensor(window), kv_caches=caches,
                        position_offset=Tensor(lens))
                    logits_all = model._logits(hidden)._value \
                        .astype(jnp.float32)                # [B, K, V]
                kb = [cc.k._value if isinstance(cc.k, Tensor) else cc.k
                      for cc in new_caches]
                vb = [cc.v._value if isinstance(cc.v, Tensor) else cc.v
                      for cc in new_caches]
                counts, _, new_logits = verify_window(
                    logits_all, draft, lens,
                    jnp.where(act, Kspec, 0), rng, temps, top_ps, rids,
                    act)
                new_logits = jnp.where(act[:, None], new_logits, logits)
                new_lens = lens + counts
                tbuf = _write_window(tbuf, window, lens)
                emitted = emitted + counts
                kidx = jnp.arange(Kspec)[None, :]
                in_window = kidx < counts[:, None]
                eos_hit = jnp.any(
                    in_window & (window == eos_ids[:, None]), axis=1)
                act_next = act & ~eos_hit & \
                    (new_lens < cap - Kspec) & (emitted < budgets)
                return (kb, vb, new_logits, new_lens, act_next, emitted,
                        rng, tbuf), (window, counts, act)

            emitted0 = jnp.zeros_like(lens)
            (k_bufs, v_bufs, logits, lens, active, _, rng, tokens_buf), \
                (toks, counts, was_active) = jax.lax.scan(
                    body,
                    (k_bufs, v_bufs, logits, lens, active, emitted0, rng,
                     tokens_buf),
                    None, length=K)
            return (_pin_rep(toks), _pin_rep(counts), _pin_rep(was_active),
                    _pin_rep(logits), _pin_kv(k_bufs), _pin_kv(v_bufs),
                    _pin_rep(lens), rng, tokens_buf)

        def make_multi_spec(Kms):
            """Build the fused ALL-DECODE speculative program for stride
            ``Kms``: up to Kms verify windows per slot as ONE dispatch,
            as a ``lax.while_loop`` with the multi-step path's IN-GRAPH
            EARLY EXIT (every slot hit eos / budget / capacity / its
            covered blocks -> the loop stops on device). Each window
            runs through the APPEND-form attention path (q_lens = the
            granted 1 + k drafts per slot, shrunk in-graph to the
            per-slot ``row_caps`` coverage budget), verifies with the
            coupled rule, and rolls rejected tokens back via lens.
            Token/count/activity rows land in [Kms, B, Kspec] /
            [Kms, B] buffers — the same layout the legacy verify scan
            hands step_finish, so ONE spec readout serves both."""
            Kd = Kspec - 1

            def multi_spec(state_vals, k_bufs, v_bufs, logits, lens,
                           active, rng, temps, top_ps, eos_ids, budgets,
                           rids, spec_qs, row_caps, tokens_buf,
                           tables=None, lora=None):
                nL = len(k_bufs)

                def cond(carry):
                    return (carry[0] < Kms) & jnp.any(carry[5])

                def body(carry):
                    (i, kb, vb, lg, ln, act, emitted, tbuf, toks, cnts,
                     wa, qs) = carry
                    # pipelined over-dispatch guard: a slot whose
                    # PREVIOUS (still in-flight) dispatch grew it to the
                    # capacity margin deactivates before its window (or
                    # its token-history write) could cross the buffer
                    act = act & (ln + Kspec <= cap)
                    draft = _lookup_draft(tbuf, ln, Kd, ngram)
                    committed = sample_next(lg, rng, temps, top_ps, rids,
                                            ln)
                    committed = jnp.where(act, committed, 0)
                    window = jnp.concatenate([committed[:, None], draft],
                                             axis=1)
                    # per-slot window width: the granted 1 + k drafts,
                    # shrunk in-graph to the covered-block / capacity
                    # row budget (pool pressure narrows windows before
                    # anyone is preempted)
                    q_eff = jnp.clip(jnp.minimum(row_caps, cap) - ln, 0,
                                     spec_qs)
                    q_eff = jnp.where(act, q_eff, 0)
                    act = act & (q_eff >= 1)
                    q_eff = jnp.where(act, q_eff, 0)
                    with functional_mode(), _bind(state, state_vals), \
                            lora_scope(lora):
                        if tables is None:
                            from ..models.llama import ChunkKVCache
                            caches = [ChunkKVCache(k, v, ln, q_eff)
                                      for k, v in zip(kb, vb)]
                        else:
                            caches = layout.caches(
                                kb, vb, tables, ln, q_eff, None, row_budget)
                        hidden, new_caches = decoder(
                            Tensor(window), kv_caches=caches,
                            position_offset=Tensor(ln))
                        logits_win = model._logits(hidden)._value \
                            .astype(jnp.float32)          # [B, Kspec, V]
                    kb, vb = layout.unpack(new_caches)
                    counts, _, new_lg = verify_window(
                        logits_win, draft, ln, q_eff, rng, temps, top_ps,
                        rids, act)
                    new_lg = jnp.where(act[:, None], new_lg, lg)
                    new_ln = ln + counts
                    tb_new = _write_window(tbuf, window, ln)
                    tbuf = jnp.where(act[:, None], tb_new, tbuf)
                    toks = jax.lax.dynamic_update_slice(
                        toks, window[None], (i, jnp.int32(0),
                                             jnp.int32(0)))
                    cnts = jax.lax.dynamic_update_slice(
                        cnts, counts[None], (i, jnp.int32(0)))
                    wa = jax.lax.dynamic_update_slice(
                        wa, act[None], (i, jnp.int32(0)))
                    qs = jax.lax.dynamic_update_slice(
                        qs, q_eff[None], (i, jnp.int32(0)))
                    emitted = emitted + counts
                    kidx = jnp.arange(Kspec)[None, :]
                    in_win = kidx < counts[:, None]
                    eos_hit = jnp.any(
                        in_win & (window == eos_ids[:, None]), axis=1)
                    act = act & ~eos_hit & (new_ln < cap - Kspec) & \
                        (emitted < budgets)
                    return (i + 1, kb, vb, new_lg, new_ln, act, emitted,
                            tbuf, toks, cnts, wa, qs)

                carry = (jnp.int32(0), list(k_bufs), list(v_bufs), logits,
                         lens, jnp.asarray(active), jnp.zeros_like(lens),
                         tokens_buf,
                         jnp.zeros((Kms, B, Kspec), jnp.int32),
                         jnp.zeros((Kms, B), jnp.int32),
                         jnp.zeros((Kms, B), bool),
                         jnp.zeros((Kms, B), jnp.int32))
                (_, k_out, v_out, logits, lens, _, _, tokens_buf, toks,
                 cnts, wa, qs) = jax.lax.while_loop(cond, body, carry)
                assert len(k_out) == nL
                return (_pin_rep(toks), _pin_rep(cnts), _pin_rep(wa),
                        _pin_rep(logits), _pin_kv(k_out), _pin_kv(v_out),
                        _pin_rep(lens), rng, tokens_buf, _pin_rep(qs))
            return multi_spec

        self._multi_spec_factory = make_multi_spec

        def fused_step(state_vals, k_bufs, v_bufs, logits, lens, rng, ids,
                       q_lens, is_decode, active, temps, top_ps, rids,
                       tables=None, lora=None, is_embed=None, pooled=None,
                       tokens_buf=None, spec_ks=None):
            """ONE mixed prefill+decode dispatch (the fused scheduler's
            step): slot b is granted rows [0, q_lens[b]) of the host's
            ``ids[B, chunk]`` — either a prefill chunk (host-provided
            prompt rows) or one decode token (row 0, sampled IN-GRAPH
            from the carried logits, so no extra host round-trip vs the
            plain step). The decoder is NOT handed ``[B, chunk]`` rows:
            once the capacity guard has fixed the effective grants, the
            granted rows are packed slot-major onto ONE row axis of the
            static height ``T = packed_rows(max_step_tokens, max_batch,
            chunk)`` (``cache_layout.RowMap``, made here in the graph),
            ``ids`` is gathered to ``[1, T]`` and every cache object
            carries the map; the layers take the per-slot ``[B, chunk]``
            view only around their attention and recurrent cores. Every
            row sits at its own absolute position (``lens[slot] + col``);
            the ``T - sum(q_lens)`` padding rows write nothing and their
            outputs are never read. A slot's last live packed row feeds
            the head. ``tables`` selects the cache
            backend at trace time exactly like ``step``; ``lora`` arms
            the per-slot adapter delta exactly like ``one_step``.

            ``pooled``/``is_embed`` (prefill-only grant kind): when an
            EMBED slot is resident, its granted prefill rows' final
            hidden states accumulate into its ``pooled`` row — the
            mean-pool numerator the finishing readout divides by the
            prompt length. Passed as None on generate-only dispatches,
            so the no-embed program is untouched.

            ``tokens_buf``/``spec_ks`` (VERIFY grant kind — the fused
            speculative engine): a decode slot with ``spec_ks[b] = k >
            0`` was granted a k-draft verify window (``q_lens[b] = k+1``)
            — row 0 is its committed sample, rows 1..k the in-graph
            prompt-lookup drafts read from the device token history, the
            whole window runs through the SAME append-form attention as
            a prefill chunk, and the coupled ``verify_window`` rule
            commits the matching prefix (rejected tokens roll back via
            lens; their KV rows are stale-but-unreferenced). Passed as
            None on non-speculative engines, so the spec-free program —
            and ``speculative_k=1`` serving — is bit-identical."""
            nxt = sample_next(logits, rng, temps, top_ps, rids, lens)
            with scope("pt.pack"):
                # capacity guard for pipelined over-dispatch: a window that
                # would cross the buffer end deactivates in-graph
                active = active & (lens + q_lens <= cap)
                dec = active & is_decode
                if spec_ks is None:
                    nxt = jnp.where(dec, nxt, 0)
                    q_eff = jnp.where(active, q_lens, 0)
                    row0 = jnp.arange(chunk, dtype=jnp.int32)[None, :] == 0
                    ids = jnp.where(dec[:, None] & row0, nxt[:, None], ids)
                else:
                    # verify windows must fit the token-history write below;
                    # a clamped-out verify slot goes fully inactive (its
                    # rows must not scatter) — in practice the readout's
                    # capacity margin retires slots before this fires
                    dec = dec & (lens + Kspec <= cap)
                    active = active & (~is_decode | dec)
                    nxt = jnp.where(dec, nxt, 0)
                    q_eff = jnp.where(active, q_lens, 0)
                    draft = _lookup_draft(tokens_buf, lens, Kspec - 1, ngram)
                    window = jnp.concatenate([nxt[:, None], draft], axis=1)
                    wcols = jnp.arange(chunk, dtype=jnp.int32)[None, :] < Kspec
                    padded_win = jnp.zeros_like(ids) \
                        .at[:, :Kspec].set(window)
                    ids = jnp.where(dec[:, None] & wcols, padded_win, ids)
                    tb_new = _write_window(tokens_buf, window, lens)
                    tokens_buf = jnp.where(dec[:, None], tb_new, tokens_buf)
                # the packed row axis: made HERE, after the guard above may
                # have taken a slot out in the graph
                rows = RowMap(q_eff, lens, T, chunk)
            with functional_mode(), _bind(state, state_vals), \
                    lora_scope(lora and dict(lora, rows=rows)):
                if tables is None:
                    from ..models.llama import ChunkKVCache
                    caches = [ChunkKVCache(k, v, lens, q_eff, rows)
                              for k, v in zip(k_bufs, v_bufs)]
                else:
                    caches = layout.caches(k_bufs, v_bufs, tables, lens,
                                           q_eff, None, row_budget, rows)
                with scope("pt.pack"):
                    packed_ids = Tensor(rows.from_slots(ids)[None])
                    packed_pos = Tensor(rows.pos[None])
                with collect_counts() as counted:
                    hidden, new_caches = decoder(
                        packed_ids, kv_caches=caches,
                        position_offset=packed_pos)
                hidden = hidden._value[0]                       # [T, H]
                # per-slot LAST LIVE row: a prefill chunk's next-token
                # logits / the decode token's next logits — one gather,
                # then the lm head over [B, 1, H] only (never every
                # row: the head over them would dominate)
                with scope("pt.pack"):
                    last_rows = Tensor(hidden[rows.last()][:, None])
                new_logits = model._logits(last_rows)._value[:, 0] \
                    .astype(jnp.float32)
                if spec_ks is not None:
                    # verify slots need PER-ROW logits over the window
                    # (not just the last live row): the head runs over
                    # [B, Kspec, H] — bounded by the window width
                    logits_win = model._logits(Tensor(
                        rows.to_slots(hidden, Kspec)))._value \
                        .astype(jnp.float32)
            if pooled is not None:
                # masked sum of this dispatch's real prefill rows for
                # embed slots only, fp32 — one tiny [B,T]x[T,H]
                # contraction riding the mixed step
                emb_mask = ((rows.slot[None, :]
                             == jnp.arange(B, dtype=jnp.int32)[:, None])
                            & rows.live[None, :]
                            & (is_embed & ~is_decode)[:, None])
                pooled = pooled + jnp.einsum(
                    "bt,th->bh", emb_mask.astype(jnp.float32),
                    hidden.astype(jnp.float32))
            kb, vb = layout.unpack(new_caches)
            if spec_ks is None:
                with scope("pt.readout"):
                    new_logits = jnp.where(active[:, None], new_logits,
                                           logits)
                    new_lens = lens + q_eff
                    # [1, B] token/activity rows: the readout walk in
                    # step_finish is shared with the scan-based steps
                    # (K==1)
                    return (_pin_rep(nxt[None]), _pin_rep(dec[None]),
                            _pin_rep(new_logits), _pin_kv(kb), _pin_kv(vb),
                            _pin_rep(new_lens), rng, pooled,
                            sum(counted, ctr_zero) if n_ctr else None)
            counts, _, spec_logits = verify_window(
                logits_win, draft, lens, q_eff, rng, temps, top_ps,
                rids, dec)
            new_logits = jnp.where(dec[:, None], spec_logits, new_logits)
            new_logits = jnp.where(active[:, None], new_logits, logits)
            # rejected drafts ROLL BACK here: a verify slot's lens grow
            # by its committed count, not its granted window — the
            # written-past-committed KV rows are stale but unreferenced
            # (lens-based masks) and the next window overwrites them
            new_lens = lens + jnp.where(dec, counts, q_eff)
            # [1, B, Kspec] window layout + [1, B] counts: the spec
            # readout flatten in step_finish is shared with the legacy
            # verify scan (one window here). The offered widths ride
            # along so the acceptance accounting books exact proposals.
            return (_pin_rep(window[None]), _pin_rep(counts[None]),
                    _pin_rep(dec[None]), _pin_rep(new_logits),
                    _pin_kv(kb), _pin_kv(vb), _pin_rep(new_lens), rng,
                    pooled, tokens_buf, _pin_rep(q_eff[None]))

        def prefill_chunk(state_vals, k_bufs, v_bufs, ids, slot, off, last,
                          lora=None):
            """Run chunk `ids` [1, chunk] of one prompt through the model
            against slot `slot`'s KV region starting at position `off`;
            returns updated buffers + the logits at in-chunk row `last`.
            ``lora``: the single-sequence adapter pack (slots vector of
            length 1) — prefill KV must carry the tenant's deltas."""
            from ..models.llama import StaticKVCache

            z = jnp.int32(0)
            k_slot = [jax.lax.dynamic_slice(
                k, (slot, z, z, z), (1,) + k.shape[1:]) for k in k_bufs]
            v_slot = [jax.lax.dynamic_slice(
                v, (slot, z, z, z), (1,) + v.shape[1:]) for v in v_bufs]
            with functional_mode(), _bind(state, state_vals), \
                    lora_scope(lora):
                caches = [StaticKVCache(k, v)
                          for k, v in zip(k_slot, v_slot)]
                hidden, new_caches = decoder(
                    Tensor(ids), kv_caches=caches,
                    position_offset=Tensor(off))
                row = jax.lax.dynamic_slice(
                    hidden._value, (z, last, z), (1, 1, hidden.shape[-1]))
                logits_row = model._logits(Tensor(row))._value[0, 0] \
                    .astype(jnp.float32)
            k_out = [jax.lax.dynamic_update_slice(
                kb, (cc.k._value if isinstance(cc.k, Tensor) else cc.k
                     ).astype(kb.dtype), (slot, z, z, z))
                for kb, cc in zip(k_bufs, new_caches)]
            v_out = [jax.lax.dynamic_update_slice(
                vb, (cc.v._value if isinstance(cc.v, Tensor) else cc.v
                     ).astype(vb.dtype), (slot, z, z, z))
                for vb, cc in zip(v_bufs, new_caches)]
            return _pin_kv(k_out), _pin_kv(v_out), _pin_rep(logits_row)

        def set_logits(logits, row, slot):
            return jax.lax.dynamic_update_slice(
                logits, row[None].astype(logits.dtype), (slot, jnp.int32(0)))

        if self.cache_impl == "paged":
            from ..models.llama import PagedKVCache, StaticKVCache
            bs_blk = self.block_size
            MB = self._max_blocks

            head_d = self._head_dim

            def prefill_chunk_paged(state_vals, k_pools, v_pools, ids,
                                    table_row, off, last, lora=None):
                """Paged chunked prefill: gather the slot's logical KV from
                its blocks, run the chunk like the dense path, scatter the
                chunk's new KV back into the (block-aligned) blocks.
                Quantized pools gather DEQUANTIZED (f32) and scatter back
                re-quantized: each written block is whole-chunk content,
                so its fresh per-head absmax scale needs no merge with
                old rows."""
                from ..ops.kernels.paged_attention import (
                    kv_block_scale, kv_quantize, kv_unpack)
                z = jnp.int32(0)
                safe = jnp.maximum(table_row, 0)

                def gather(p):
                    if kvq:
                        blks = kv_unpack(p[0][safe], kvq, head_d) * \
                            p[1][safe][..., None, None]
                    else:
                        blks = p[safe]
                    return jnp.moveaxis(blks, 2, 1).reshape(
                        1, MB * bs_blk, blks.shape[1], blks.shape[3])

                k_slot = [gather(p) for p in k_pools]
                v_slot = [gather(p) for p in v_pools]
                with functional_mode(), _bind(state, state_vals), \
                        lora_scope(lora):
                    caches = [StaticKVCache(k, v)
                              for k, v in zip(k_slot, v_slot)]
                    hidden, new_caches = decoder(
                        Tensor(ids), kv_caches=caches,
                        position_offset=Tensor(off))
                    row = jax.lax.dynamic_slice(
                        hidden._value, (z, last, z),
                        (1, 1, hidden.shape[-1]))
                    logits_row = model._logits(Tensor(row))._value[0, 0] \
                        .astype(jnp.float32)

                def scatter(pool, cc_val):
                    # chunk rows [off, off+chunk) -> chunk//bs_blk blocks,
                    # as ONE batched scatter (the old per-logical-block
                    # Python loop traced O(chunk/block_size) sequential
                    # dynamic_update_slice ops per prompt chunk)
                    new_rows = jax.lax.dynamic_slice(
                        cc_val, (z, off, z, z),
                        (1, chunk) + cc_val.shape[2:])[0]   # [chunk, H, D]
                    nblk = chunk // bs_blk
                    h, d = new_rows.shape[1], new_rows.shape[2]
                    blks = jnp.swapaxes(
                        new_rows.reshape(nblk, bs_blk, h, d), 1, 2)
                    phys = jax.lax.dynamic_slice(
                        table_row, (off // bs_blk,), (nblk,))
                    if kvq:
                        payload, scales = pool
                        blks = blks.astype(jnp.float32)
                        # zero the chunk's PADDING rows (chunk index >
                        # last): their token-id-0 KV must not ride the
                        # absmax scale — and the stored bytes then match
                        # what the fused append path writes for the same
                        # prefix (it never writes padding rows at all)
                        ridx = jnp.arange(nblk)[:, None] * bs_blk + \
                            jnp.arange(bs_blk)[None, :]    # [nblk, bs]
                        dead = (ridx > last)[:, None, :, None]
                        blks = jnp.where(dead, jnp.float32(0.0), blks)
                        s_new = kv_block_scale(blks, kvq,
                                               axes=(2, 3))  # [nblk, H]
                        return (payload.at[phys].set(
                                    kv_quantize(blks, s_new[..., None,
                                                            None], kvq)),
                                scales.at[phys].set(s_new))
                    return pool.at[phys].set(blks.astype(pool.dtype))

                k_out = [scatter(p, (cc.k._value if isinstance(cc.k, Tensor)
                                     else cc.k))
                         for p, cc in zip(k_pools, new_caches)]
                v_out = [scatter(p, (cc.v._value if isinstance(cc.v, Tensor)
                                     else cc.v))
                         for p, cc in zip(v_pools, new_caches)]
                return _pin_kv(k_out), _pin_kv(v_out), _pin_rep(logits_row)

            self._prefill_paged_fn = self._program(
                "prefill_paged", jax.jit(prefill_chunk_paged,
                                         donate_argnums=(1, 2)))

            def cow_copy(k_pools, v_pools, src, dst):
                """Copy-on-write block duplication: clone physical block
                ``src`` into ``dst`` across every layer's K/V pool. One
                jitted program, src/dst traced — no recompile per copy.
                Block-index ops only, so under TP each shard clones its
                own kv-head slice — no cross-shard traffic. tree_map
                clones a quantized pool's payload AND its per-block
                scale row in one rule (scale[src] is block src's row —
                the clone is bit-exact, so COW never re-rounds)."""
                def cp(p):
                    return p.at[dst].set(p[src])
                return (_pin_kv(jax.tree_util.tree_map(cp, list(k_pools))),
                        _pin_kv(jax.tree_util.tree_map(cp, list(v_pools))))

            self._cow_fn = self._program(
                "cow", jax.jit(cow_copy, donate_argnums=(0, 1)))

            def kv_gather_blocks(k_pools, v_pools, idx):
                """Host-tier STAGING gather: physical blocks ``idx`` out
                of every layer's K/V pool as fresh arrays the host can
                then copy down (swap-out / spill). tree_map's one rule
                carries a quantized pool's payload AND its per-block
                scale rows, so int8/int4 content round-trips bit-exact.
                Reads only — and its input is the engine's NEWEST pool
                futures, so it is sequenced after every already-
                dispatched write (the committed content has landed by
                construction) and before any later owner's writes
                (program order over the shared pool buffers — the same
                argument _cow_tail documents)."""
                def g(p):
                    return p[idx]
                return (jax.tree_util.tree_map(g, list(k_pools)),
                        jax.tree_util.tree_map(g, list(v_pools)))

            self._kv_gather_fn = self._program(
                "kv_gather", jax.jit(kv_gather_blocks))

            def kv_scatter_blocks(k_pools, v_pools, idx, k_vals, v_vals):
                """Host-tier restore scatter (swap-in / spill promote):
                write staged host block copies back into pool blocks
                ``idx``. The destinations are freshly allocated private
                blocks — the write fence guarantees no in-flight
                dispatch targets them (fenced blocks never reach the
                free heap), so the restore cannot race a pipelined
                writer."""
                def s(p, vals):
                    return p.at[idx].set(vals.astype(p.dtype))
                return (_pin_kv(jax.tree_util.tree_map(
                            s, list(k_pools), list(k_vals))),
                        _pin_kv(jax.tree_util.tree_map(
                            s, list(v_pools), list(v_vals))))

            self._kv_scatter_fn = self._program(
                "kv_scatter", jax.jit(kv_scatter_blocks,
                                      donate_argnums=(0, 1)))

        def set_tokens(tokens_buf, row, slot):
            return jax.lax.dynamic_update_slice(
                tokens_buf, row[None].astype(jnp.int32),
                (slot, jnp.int32(0)))

        def set_len(lens, slot, val):
            return jax.lax.dynamic_update_slice(lens, val[None], (slot,))

        def set_pooled_zero(pooled, slot):
            z = jnp.zeros((1, pooled.shape[1]), pooled.dtype)
            return jax.lax.dynamic_update_slice(pooled, z,
                                                (slot, jnp.int32(0)))

        # NOT donated: an in-flight PendingStep may still hold this very
        # array as its pooled output (step_finish reads it after the
        # sync) — the zero-row update copies a tiny [B, H] buffer
        prog = self._program
        self._set_pooled_fn = prog("set_pooled", jax.jit(set_pooled_zero))
        self._step_fn = prog("step", jax.jit(step, donate_argnums=(1, 2, 3)))
        # the paged step IS the unified step with `tables` bound — one
        # traced body serves both cache backends
        self._step_paged_fn = self._step_fn
        # same trick for the fused mixed step: one traced body, the
        # `tables` arg selects dense ChunkKVCache vs PagedKVCache
        self._fused_fn = prog("fused_step", jax.jit(
            fused_step, donate_argnums=(1, 2, 3)))
        self._spec_fn = prog("spec_step", jax.jit(
            spec_step, donate_argnums=(1, 2, 3, 12)))
        self._prefill_fn = prog("prefill", jax.jit(
            prefill_chunk, donate_argnums=(1, 2)))
        self._set_logits_fn = prog("set_logits", jax.jit(
            set_logits, donate_argnums=(0,)))
        self._set_tokens_fn = prog("set_tokens", jax.jit(
            set_tokens, donate_argnums=(0,)))
        self._set_len_fn = prog("set_len", jax.jit(
            set_len, donate_argnums=(0,)))

    def _multi_fn(self, stride):
        """The compiled multi-step decode program for ``stride`` — one
        program per distinct effective stride (engine stride plus any
        smaller per-request pins actually seen), cached for the engine's
        lifetime (reset() keeps them: same shapes, same shardings)."""
        fn = self._multi_fns.get(stride)
        if fn is None:
            self._programs()
            fn = self._multi_fns[stride] = self._program(
                "multi_step", jax.jit(self._multi_step_factory(stride),
                                      donate_argnums=(1, 2, 3)))
        return fn

    def _multi_spec_fn(self, stride):
        """The compiled multi-window SPECULATIVE decode program for
        ``stride`` windows per dispatch — cached per distinct stride for
        the engine's lifetime, exactly like :meth:`_multi_fn`."""
        fn = self._multi_spec_fns.get(stride)
        if fn is None:
            self._programs()
            fn = self._multi_spec_fns[stride] = self._program(
                "multi_spec", jax.jit(self._multi_spec_factory(stride),
                                      donate_argnums=(1, 2, 3, 14)))
        return fn

    # ------------------------------------------------------------------
    # acceptance-adaptive verify-k (fused speculative scheduling)
    # ------------------------------------------------------------------
    def _spec_k_for(self, slot):
        """Draft count of ``slot``'s next verify grant: its acceptance
        EWMA scaled into [1, speculative_k - 1] (optimistic full window
        until the first readout teaches otherwise). A low-acceptance
        request keeps proposing ONE draft — never zero, so the EWMA can
        recover when the stream turns repetitive again — instead of
        burning the step budget on windows that roll back."""
        ewma = slot.req.spec_ewma
        if ewma is None:
            ewma = self._spec_ewma.get(slot.req.request_id, 1.0)
        kd = self.speculative_k - 1
        return max(1, min(kd, int(round(ewma * kd + 0.25))))

    def _update_spec_ewma(self, slot, proposed, accepted):
        """Fold one readout's accepted/proposed draft counts into the
        request's acceptance EWMA (request field + the engine's
        rid-keyed mirror, which survives reset() for restart
        resumption)."""
        if proposed <= 0:
            return
        rate = accepted / proposed
        prev = slot.req.spec_ewma
        if prev is None:
            prev = self._spec_ewma.get(slot.req.request_id, rate)
        ewma = (1.0 - _SPEC_EWMA_ALPHA) * prev + _SPEC_EWMA_ALPHA * rate
        slot.req.spec_ewma = ewma
        self._spec_ewma[slot.req.request_id] = ewma

    def spec_ewma_for(self, request_id):
        """READ-ONLY: the persisted draft-acceptance EWMA of
        ``request_id`` (None = never speculated) — what the replica
        router forwards on failover so the survivor's verify grants
        start at the learned window instead of the optimistic
        default."""
        return self._spec_ewma.get(request_id)

    def _effective_stride(self):
        """The readout stride the NEXT all-decode dispatch should run:
        the engine's ``readout_stride`` capped by every active slot's
        per-request pin (a latency-tier request pinning 1 drags the
        whole batch to per-step readout while it is resident — the
        documented tradeoff), and by ``horizon`` for engines that use
        the legacy scan amortization instead."""
        if self.scheduler != "fused" or self.readout_stride <= 1:
            return self.horizon
        pins = [s.req.readout_stride for s in self.slots
                if s is not None and s.req.readout_stride is not None]
        return max(1, min([self.readout_stride] + pins))

    # ------------------------------------------------------------------
    # batched multi-LoRA (tenant) plumbing — serving/adapters.py
    # ------------------------------------------------------------------
    def _lora_armed(self):
        return self.adapter_store is not None and \
            len(self.adapter_store) > 0

    def _ensure_adapter_cache(self):
        if self.adapter_cache is None:
            from ..serving.adapters import AdapterDeviceCache
            self.adapter_cache = AdapterDeviceCache(
                self.adapter_store, n_slots=self._adapter_slots,
                make_zeros=self._make_zeros)
        return self.adapter_cache

    def _lora_pack(self, rows):
        """The traced LoRA arguments of one dispatch: the device stacks
        plus the per-batch-row adapter slot vector ``rows`` ([B] int32;
        0 = base). None while no adapter is registered — the step
        programs then trace the exact pre-adapter body (bit-identical
        base serving); the first registered adapter flips the signature
        and retraces once."""
        if not self._lora_armed():
            return None
        cache = self._ensure_adapter_cache()
        return {"A": cache.A, "B": cache.B, "alpha": cache.alpha,
                "slots": np.asarray(rows, np.int32)}

    def _slot_adapter_rows(self):
        return np.array([s.a_slot if s is not None else 0
                         for s in self.slots], np.int32)

    def _acquire_adapter(self, req):
        """Pin ``req``'s adapter resident in the device cache; returns
        its device row (0 = base), or None when every cache slot is
        pinned by resident requests — the admission then DEFERS exactly
        like a dry KV pool (a retirement releases a slot)."""
        aid = getattr(req, "adapter_id", 0)
        if not aid:
            return 0
        cache = self._ensure_adapter_cache()
        before = dict(cache.stats)
        row = cache.acquire(aid)
        self.stats["adapter_cache_hits"] += \
            cache.stats["hits"] - before["hits"]
        self.stats["adapter_cache_misses"] += \
            cache.stats["misses"] - before["misses"]
        self.stats["adapter_swaps"] += \
            cache.stats["swaps"] - before["swaps"]
        return row

    def _release_adapter(self, adapter_id):
        if adapter_id and self.adapter_cache is not None:
            self.adapter_cache.release(adapter_id)

    def adapter_resident(self, adapter_id):
        """READ-ONLY: could a request for ``adapter_id`` admit without a
        swap right now? The replica router's adapter-affinity probe
        (dict reads only — safe from any thread)."""
        if not adapter_id:
            return True
        return self.adapter_cache is not None and \
            self.adapter_cache.resident(adapter_id)

    @staticmethod
    def _tenant_root(adapter_id):
        """The prefix-cache hash-chain ROOT of one tenant: adapter id 0
        keeps the historical root (base-tenant hashes are unchanged);
        any other id mixes into the seed, so two tenants' chains over
        the SAME prompt never collide — different fine-tunes produce
        different KV for identical tokens, and a shared block would
        silently serve tenant A's KV to tenant B."""
        if not adapter_id:
            return _ROOT_HASH
        return _ROOT_HASH + b"/tenant=" + str(int(adapter_id)).encode()

    # ------------------------------------------------------------------
    # request lifecycle
    # ------------------------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens=64, temperature=0.0,
                    top_p=1.0, eos_token_id=None, request_id=None,
                    committed_tokens=None, readout_stride=None,
                    adapter_id=0, kind="generate", spec_ewma=None,
                    export_kv=False, trace_ctx=None):
        """``readout_stride``: per-request latency-tier pin — cap the
        multi-step decode stride of every all-decode step this request
        is active in (1 = sync the host every step; None = the engine
        default; ignored unless the engine runs ``readout_stride > 1``).

        ``committed_tokens``: tokens ALREADY generated for this request
        in a previous life (supervised-restart / failover re-admission).
        They join the prompt for prefill — exactly the pool-pressure
        preemption stitch — so the engine's stream CONTINUES: only new
        tokens hit the stream callback, the returned output prepends the
        committed ones, and ``max_new_tokens`` counts only NEW tokens.
        Token-exactness rides the per-(rid, position) fold_in sampling
        keys: position ``len(prompt)+len(committed)`` samples the same
        token it would have in the uninterrupted run.

        ``adapter_id``: the request's TENANT — a registered id in the
        engine's adapter store (0 = base model). ``kind="embed"`` makes
        the request PREFILL-ONLY (fused scheduler required): no decode
        tokens, no sampling; the finished RequestOutput carries the
        mean-pooled final hidden state in ``embedding``.

        ``export_kv``: stage the request's committed KV as a staged
        export entry at its finish (disaggregated serving — see
        :meth:`export_kv`)."""
        self._layout.refuse(
            kv_shipping=export_kv and "add_request(export_kv=True)",
            request_kind=kind)
        ids = np.asarray(
            prompt_ids.numpy() if hasattr(prompt_ids, "numpy")
            else prompt_ids, dtype=np.int32).reshape(-1)
        if len(ids) == 0:
            raise ValueError("empty prompt")
        if readout_stride is not None and int(readout_stride) < 1:
            raise ValueError(f"readout_stride must be >= 1, got "
                             f"{readout_stride}")
        adapter_id = int(adapter_id or 0)
        if adapter_id:
            if self.adapter_store is None:
                raise ValueError(
                    f"adapter_id {adapter_id} on an engine without an "
                    f"adapter_store (LLMEngine(adapter_store=...))")
            if not self.adapter_store.has(adapter_id):
                raise ValueError(f"unknown adapter_id {adapter_id} (not "
                                 f"registered in the adapter store)")
        if kind not in ("generate", "embed"):
            raise ValueError(f"unknown request kind {kind!r}")
        if kind == "embed":
            if self.scheduler != "fused":
                raise ValueError(
                    "embedding (prefill-only) requests need "
                    "scheduler='fused' — the prefill-only grant kind "
                    "lives in the fused token-budget walk")
            max_new_tokens = 0
            # no decode headroom needed: an embed prompt may run to
            # capacity - 1 (the +1 in the fused pool arithmetic covers
            # the last granted position)
            if len(ids) > self.capacity - 1:
                raise ValueError(
                    f"embedding prompt of {len(ids)} tokens exceeds the "
                    f"engine capacity ({self.capacity} - 1)")
            self.stats["embed_requests"] += 1
        committed = [int(t) for t in committed_tokens] \
            if committed_tokens else []
        if committed:
            ids = np.concatenate(
                [ids, np.asarray(committed, np.int32)])
        if kind != "embed" and \
                len(ids) >= self.capacity - self.speculative_k:
            raise ValueError(f"prompt of {len(ids)} tokens leaves no room "
                             f"to generate (engine capacity "
                             f"{self.capacity})")
        rid = self._next_id if request_id is None else request_id
        if request_id is not None and (
                rid in self.finished_outputs
                or any(r.request_id == rid for r in self.waiting)
                or any(s is not None and s.req.request_id == rid
                       for s in self.slots)):
            raise ValueError(f"duplicate request_id {rid!r}")
        self._next_id = max(self._next_id, rid) + 1
        if committed:
            # the preemption stitch: _finish_tokens pops this and
            # prepends it to whatever the slot generates from here on
            self._preempted_prefix[rid] = \
                self._preempted_prefix.pop(rid, []) + committed
        self.waiting.append(GenerationRequest(
            rid, ids, int(max_new_tokens), float(temperature), float(top_p),
            eos_token_id,
            readout_stride=(int(readout_stride)
                            if readout_stride is not None else None),
            adapter_id=adapter_id, kind=kind,
            # acceptance-adaptive verify-k seed: an explicit carry-over
            # (router failover) wins; else the engine's rid-keyed mirror
            # (supervised restart / preemption re-admission under the
            # same rid) — fresh requests start at the optimistic default
            spec_ewma=(float(spec_ewma) if spec_ewma is not None
                       else self._spec_ewma.get(rid)),
            export_kv=bool(export_kv), trace_ctx=trace_ctx))
        if trace_ctx is not None:
            rec = self._rec()
            if rec is not None:
                # direct-engine admissions stamp the timeline here; the
                # server's submit() already stamped its own recorder
                # (set_trace_ctx is idempotent for the same context)
                rec.set_trace_ctx(rid, trace_ctx if isinstance(
                    trace_ctx, dict) else trace_ctx.to_dict())
        return rid

    def has_unfinished(self):
        return bool(self.waiting) or any(s is not None for s in self.slots)

    def cancel(self, request_id, reason="cancelled"):
        """Cancel a waiting or running request. Returns the partial
        RequestOutput (finish_reason ``reason``, default 'cancelled' —
        the serving layer passes 'deadline' for expiries), or None if the
        id is unknown/already finished. A cancelled running slot frees
        immediately (slot and, under paged KV, its pool blocks); its KV
        region is simply reused by the next admission."""
        for i, req in enumerate(self.waiting):
            if req.request_id == request_id:
                del self.waiting[i]
                out = RequestOutput(
                    request_id, self._finish_tokens(req, []), True,
                    reason)
                self.finished_outputs[request_id] = out
                return out
        for b, slot in enumerate(self.slots):
            if slot is not None and slot.req.request_id == request_id:
                out = RequestOutput(
                    request_id,
                    self._finish_tokens(slot.req, slot.generated), True,
                    reason)
                self.finished_outputs[request_id] = out
                self._free_slot(b)
                return out
        return None

    # ------------------------------------------------------------------
    # paged-pool allocator (host side; tables are a traced step input)
    # ------------------------------------------------------------------
    def _n_allocatable(self):
        """Blocks a new allocation may claim: strictly free ones plus the
        LRU-cached pool (refcount-0 registered content, evictable). Pool
        pressure consumes BOTH before any live slot is preempted."""
        return len(self._free_blocks) + len(self._lru)

    def _pop_block(self):
        """One writable physical block: the smallest FREE index first
        (order-stable layout), else evict the oldest LRU-cached block —
        its content identity unregisters and the block is plain free."""
        if self._free_blocks:
            return heapq.heappop(self._free_blocks)
        phys, _ = self._lru.popitem(last=False)
        if self.kv_host_spill_bytes:
            # demote the evicted content to the host spill store BEFORE
            # its identity unregisters — a later probe promotes it back
            # instead of recomputing the chunk
            self._spill_block(phys)
        self._unregister(phys)
        self.stats["prefix_evicted_blocks"] += 1
        return phys

    def _alloc_blocks(self, slot_idx, n):
        """Grow slot `slot_idx` by `n` PRIVATE physical blocks (refcount
        1, content unregistered). False = pool dry (free + cached both
        exhausted)."""
        # owner check FIRST: the capacity probe itself reads allocator
        # state racily, so an off-thread attempt must be flagged even
        # when it would have failed the capacity check anyway
        self._assert_pool_owner("_alloc_blocks")
        if self._n_allocatable() < n:
            return False
        blocks = self._slot_blocks[slot_idx]
        for _ in range(n):
            phys = self._pop_block()
            self._block_ref[phys] = 1
            self._tables[slot_idx, len(blocks)] = phys
            blocks.append(phys)
        self._check_pool_invariants()
        return True

    def _release_block(self, phys):
        """Drop one reference. At refcount 0 the FENCE is authoritative:
        a block still under an in-flight write fence parks in quarantine
        — never in a pool the allocation ladder hands out from — until
        the dispatch that may still write it lands (``_unfence`` then
        routes it to the LRU if registered, the free heap otherwise).
        Registered blocks CAN be fenced: a mixed-step prefill grant
        publishes its just-filled blocks at dispatch time
        (``_register_upto``), so the grant's own write fence and the
        registration overlap until that step's finish. An unfenced
        registered block parks straight in the LRU cached pool (content
        stays probe-able); anything else returns to the free heap."""
        self._assert_pool_owner("_release_block")
        self._block_ref[phys] -= 1
        if self._block_ref[phys] > 0:
            return
        if self._write_fence.get(phys):
            self._quarantine.add(phys)
        else:
            self._park_free_block(phys)

    def _park_free_block(self, phys):
        """Route an unfenced refcount-0 block to the pool its
        registration state earns — THE one copy of the rule, shared by
        direct release and the quarantine drain: LRU cached pool if its
        content is published (probe-able), free heap otherwise."""
        if phys in self._block_hash:
            self._lru[phys] = None
        else:
            heapq.heappush(self._free_blocks, phys)

    # ---- stride-aware in-flight write fence ---------------------------
    def _fence_blocks(self, b, lo, hi, fenced):
        """Fence every block of slot ``b`` covering positions [lo, hi]:
        the dispatch being built may write them, so until its
        step_finish they must not be handed to a new owner. Fencing is
        CONSERVATIVE — ``lo`` is the slot's committed length (not its
        scheduled one), so even a dispatch whose predecessor early-exits
        in-graph below its scheduled growth (pool-budget clamp) writes
        only fenced blocks."""
        bs = self.block_size
        blocks = self._slot_blocks[b]
        for blk in range(lo // bs, min(hi // bs + 1, len(blocks))):
            phys = blocks[blk]
            self._write_fence[phys] = self._write_fence.get(phys, 0) + 1
            fenced.append(phys)

    def _unfence(self, fenced):
        """Drop one fence per listed block (its dispatch's device work —
        including every KV write — provably landed: the token sync
        completed). A quarantined block whose last fence drops leaves
        quarantine for the pool its registration state earns: the LRU
        cached pool if its content is published (probe-able again), the
        free heap otherwise."""
        self._assert_pool_owner("_unfence")
        for phys in fenced:
            n = self._write_fence.get(phys, 0) - 1
            if n > 0:
                self._write_fence[phys] = n
            else:
                self._write_fence.pop(phys, None)
                if phys in self._quarantine:
                    self._quarantine.discard(phys)
                    self._park_free_block(phys)
        if fenced:
            self._check_pool_invariants()

    # ---- content-addressed store (enable_prefix_cache) ---------------
    def _chain_hash(self, parent, tokens):
        """Rolling prefix hash of one full block: blake2b over the parent
        chain hash + the block's token ids. Chaining makes equal PREFIXES
        (not merely equal blocks) collide on purpose, and the digest is
        deterministic across runs so traces diff cleanly."""
        return hashlib.blake2b(
            parent + np.asarray(tokens, np.int32).tobytes(),
            digest_size=16).digest()

    def _register_block(self, phys, chain_hash, parent, tokens):
        """Publish a FULL private block's content identity. First writer
        wins: if the store already has this chain hash (another block
        with identical prefix content), ours stays unregistered and will
        free normally — one canonical block per content."""
        if chain_hash in self._store or phys in self._block_hash:
            return
        self._assert_pool_owner("_register_block")
        self._store[chain_hash] = phys
        self._block_hash[phys] = chain_hash
        self._block_parent[phys] = parent
        self._block_tokens[phys] = np.asarray(tokens, np.int32).tobytes()
        self._children.setdefault(parent, []).append(phys)

    def _unregister(self, phys):
        self._assert_pool_owner("_unregister")
        h = self._block_hash.pop(phys, None)
        if h is None:
            return
        self._store.pop(h, None)
        parent = self._block_parent.pop(phys)
        kids = self._children.get(parent)
        if kids is not None:
            kids.remove(phys)
            if not kids:
                del self._children[parent]
        self._block_tokens.pop(phys, None)

    def _slot_token_range(self, slot, lo, hi):
        """Token ids at positions [lo, hi) of ``slot``'s committed stream
        (prompt, then generated)."""
        P = slot.prompt_len
        if hi <= P:
            return slot.req.prompt_ids[lo:hi]
        gen = np.asarray(slot.generated, np.int32)
        if lo >= P:
            return gen[lo - P:hi - P]
        return np.concatenate([slot.req.prompt_ids[lo:], gen[:hi - P]])

    def _register_upto(self, slot_idx, slot, upto_pos):
        """Register every newly FULL block of ``slot``'s committed stream
        [0, upto_pos) in the content store, extending its hash chain.
        Shared/hit blocks were registered by their first writer and are
        skipped via ``reg_blocks``; the COW tail registers here once the
        slot's own appends fill it."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        blocks = self._slot_blocks[slot_idx]
        n_full = min(upto_pos // bs, len(blocks))
        while slot.reg_blocks < n_full:
            i = slot.reg_blocks
            toks = self._slot_token_range(slot, i * bs, (i + 1) * bs)
            parent = slot.chain[i - 1] if i else \
                self._tenant_root(slot.req.adapter_id)
            h = self._chain_hash(parent, toks)
            slot.chain.append(h)
            self._register_block(blocks[i], h, parent, toks)
            slot.reg_blocks += 1

    def _probe_prefix(self, slot_idx, token_ids, chunk_granular=False,
                      adapter_id=0, no_cow=False):
        """Find the longest cached prefix of ``token_ids`` and attach it
        to slot ``slot_idx``: pure table writes + refcount bumps, zero
        prefill FLOPs for the hit span. The hit is capped at P-1 tokens —
        at least the final prompt position always recomputes so admission
        still produces the last-position logits the sampler needs.

        ``chunk_granular`` (legacy scheduler): the hit rounds DOWN to a
        whole number of prefill chunks, because legacy chunk windows
        scatter whole chunk spans and must never scatter into a shared
        block. The fused scheduler drop-scatters exact positions, so it
        keeps block granularity and additionally extends the hit to
        TOKEN granularity through a copy-on-write tail.

        Returns ``(hit_tokens, chain)`` where ``chain`` is the list of
        chain hashes of the full-block hits."""
        P = len(token_ids)
        bs = self.block_size
        max_full = (P - 1) // bs
        if chunk_granular:
            max_full = ((P - 1) // self.chunk) * (self.chunk // bs)
        # the chain seeds at the TENANT root: two adapters' chains over
        # the same prompt diverge from block 0, so no probe can ever
        # attach another tenant's KV
        found, parent = [], self._tenant_root(adapter_id)
        for k in range(min(max_full, self._max_blocks)):
            h = self._chain_hash(parent, token_ids[k * bs:(k + 1) * bs])
            phys = self._store.get(h)
            if phys is None and self.kv_host_spill_bytes:
                # device miss, host-tier hit: promote the spilled block
                # back into the pool (re-registered) so the walk treats
                # it like any cached hit
                phys = self._promote_spilled(h)
            if phys is None:
                break
            # CLAIM the block the moment it is found — not in a second
            # pass. A LATER iteration's spill promotion allocates
            # (_pop_block), and the LRU eviction inside it would
            # happily hand out a refcount-0 block this walk already
            # found, overwriting content we are about to attach. A
            # registered block may also sit in QUARANTINE instead of
            # the LRU (released while its publishing grant's dispatch
            # was still in flight); attaching it is safe — the
            # in-flight write IS the registered content and precedes
            # any reader dispatch in program order — but it must leave
            # quarantine or its unfence would free a live block.
            if self._block_ref[phys] == 0:
                self._lru.pop(phys, None)
                self._quarantine.discard(phys)
            self._block_ref[phys] += 1
            found.append((h, phys))
            parent = h
        if chunk_granular:
            # the hit boundary must be a chunk-window boundary: roll the
            # claim back on the trimmed tail (registered blocks re-park
            # in the LRU, probe-able again)
            per = self.chunk // bs
            keep = (len(found) // per) * per
            for h, phys in found[keep:]:
                self._release_block(phys)
            found = found[:keep]
        blocks = self._slot_blocks[slot_idx]
        chain = []
        for k, (h, phys) in enumerate(found):
            self._tables[slot_idx, k] = phys
            blocks.append(phys)
            chain.append(h)
        hit = len(found) * bs
        if not chunk_granular and not no_cow:
            # no_cow (swap-in re-admission): the hit must stay
            # BLOCK-aligned — the restore attaches whole host block
            # copies after it, which a token-granular COW tail would
            # misalign (and the swap entry covers that span anyway)
            hit += self._cow_tail(slot_idx, token_ids, hit, chain,
                                  adapter_id=adapter_id)
        self._check_pool_invariants()
        return hit, chain

    def prefix_chain_hashes(self, token_ids, adapter_id=0):
        """Per-full-block rolling chain hashes of ``token_ids`` — the
        router's affinity precompute. Content-only (no engine state
        read), so one computation serves every replica with the same
        ``block_size`` AND tenant (the chain seeds at the tenant root).
        Empty when the prefix cache is off."""
        if self.cache_impl != "paged" or not self.prefix_cache:
            return []
        ids = np.asarray(token_ids, np.int32).reshape(-1)
        bs = self.block_size
        parent, out = self._tenant_root(adapter_id), []
        for k in range(min((len(ids) - 1) // bs, self._max_blocks)):
            parent = self._chain_hash(parent, ids[k * bs:(k + 1) * bs])
            out.append(parent)
        return out

    def probe_prefix_len(self, token_ids, chain_hashes=None, adapter_id=0):
        """READ-ONLY affinity probe: how many leading tokens of
        ``token_ids`` the content store could serve right now (full
        cached blocks only — no COW extension, no refcount bumps, no
        table writes). The replica router calls this from ITS thread to
        score placements; the walk is dict membership tests only, which
        the GIL makes atomic per op — a store mutating concurrently can
        make the answer stale, never wrong-shaped, and the real attach
        re-probes under the engine thread. Hashing is TP-oblivious: the
        store keys on token content, not on shard layout. Pass
        ``chain_hashes`` (from :meth:`prefix_chain_hashes`) to skip
        re-hashing the prompt per probe. Returns 0 when the prefix
        cache is off."""
        if self.cache_impl != "paged" or not self.prefix_cache:
            return 0
        if chain_hashes is None:
            chain_hashes = self.prefix_chain_hashes(token_ids,
                                                    adapter_id=adapter_id)
        hit = 0
        for h in chain_hashes[:self._max_blocks]:
            # the host spill store counts: a spilled block is one H2D
            # promote away from serving, far cheaper than the recompute
            # the affinity score is steering around
            if h not in self._store and h not in self._spill:
                break
            hit += self.block_size
        return hit

    def _cow_tail(self, slot_idx, token_ids, hit, chain, adapter_id=0):
        """Token-granular hit extension (copy-on-write): if a cached full
        block CONTINUES the hit chain and its leading tokens match the
        remaining prompt, the slot needs exactly that block's prefix —
        but must then append its own tokens into it, and the source is
        content other requests may still reference. So the source block
        is cloned device-side into a fresh PRIVATE block (the partial
        tail is always private) and the matched span's prefill is
        skipped too. Returns the extra tokens hit (0 = no match / pool
        dry)."""
        P = len(token_ids)
        bs = self.block_size
        cap = min(bs - 1, P - 1 - hit)
        if cap <= 0:
            return 0
        parent = chain[-1] if chain else self._tenant_root(adapter_id)
        rem = np.asarray(token_ids[hit:hit + cap], np.int32)
        best, best_t = None, 0
        for phys in self._children.get(parent, ()):
            cand = np.frombuffer(self._block_tokens[phys],
                                 np.int32)[:len(rem)]
            t = int(np.cumprod(cand == rem).sum())
            if t > best_t:
                best, best_t = phys, t
        if best is None or not self._alloc_blocks(slot_idx, 1):
            return 0
        dst = self._slot_blocks[slot_idx][-1]
        # the copy dispatches NOW: even if allocating dst just evicted
        # `best` from the store, its device content is only overwritten
        # by LATER dispatches — program order over the shared pool
        # buffers makes the clone read the original bytes
        self._k, self._v = self._cow_fn(self._k, self._v,
                                        np.int32(best), np.int32(dst))
        self.stats["prefix_cow_blocks"] += 1
        return best_t

    # ---- host KV tier (kv_host_swap / kv_host_spill_bytes) ------------
    # The fence-tracked swap API: every device<->host KV-pool copy in
    # the engine goes through the four functions below (the PTL006
    # checker in paddle_tpu.analysis enforces exactly that). Copies are
    # ASYNC — the gather/scatter dispatches here, the transfer overlaps
    # the step's device work in the step_begin/step_finish gap, and
    # step_finish (or a consumer that needs the bytes sooner)
    # materializes them.

    def _pad_block_idx(self, blocks):
        """Block-index vector padded to the next power-of-two length
        with the trailing SCRATCH block (index n_blocks — never handed
        out, routinely garbage-written by the kernels), so the compiled
        gather/scatter programs retrace O(log max_blocks) times total
        instead of once per distinct block count."""
        n = len(blocks)
        m = 1 << max(n - 1, 0).bit_length()
        idx = np.full((max(m, 1),), self.n_blocks, np.int32)
        idx[:n] = blocks
        return idx

    def _swap_out_slot(self, b, slot):
        """Demote slot ``b``'s committed KV to host RAM at preemption
        (the tier's swap-out half). The gather's input is the newest
        pool futures, so in-flight pipelined writers need no special
        handling: their writes land at positions >= the committed
        length, and the gather is sequenced after them by data flow —
        the fence/quarantine then keeps the released blocks from being
        handed to a new owner while those writers are still outstanding,
        exactly as for any other release."""
        req = slot.req
        kv_len = slot.prefill_pos + len(slot.generated)
        if kv_len <= 0 or req.kind == "embed":
            # an embed slot's pooled accumulator cannot survive a skip
            # of its prefill span (same reason embeds never probe the
            # prefix cache) — let it re-prefill
            return
        nb = (kv_len - 1) // self.block_size + 1
        blocks = self._slot_blocks[b][:nb]
        if len(blocks) < nb:
            return
        k_host, v_host = self._kv_gather_fn(self._k, self._v,
                                            self._pad_block_idx(blocks))
        for leaf in jax.tree_util.tree_leaves([k_host, v_host]):
            try:
                leaf.copy_to_host_async()
            except AttributeError:      # CPU fallback: a buffer move
                pass
        done = np.concatenate([req.prompt_ids,
                               np.asarray(slot.generated, np.int32)])
        entry = {"tokens": done[:kv_len], "adapter_id": req.adapter_id,
                 "n_blocks": nb, "k": k_host, "v": v_host, "ready": False,
                 "nbytes": nb * self.kv_bytes_per_block()}
        # a re-preempted request's newest committed state wins
        self._swap_store[req.request_id] = entry
        self._swap_pending.append(entry)
        self.stats["kv_swap_out_blocks"] += nb
        self.stats["kv_swap_out_bytes"] += entry["nbytes"]

    def _drain_swap_writes(self):
        """Materialize every pending device→host tier copy into plain
        numpy and drop the device references — called in the
        step_begin/step_finish gap's finish side (the transfer already
        overlapped the step's device work) and lazily by any consumer
        that needs an entry sooner."""
        if not self._swap_pending:
            return
        for entry in self._swap_pending:
            nb = entry["n_blocks"]
            entry["k"] = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:nb], entry["k"])
            entry["v"] = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:nb], entry["v"])
            entry["ready"] = True
        self._swap_pending.clear()

    def _try_swap_restores(self):
        """The swap-in half, run at the top of every MIXED step (the
        restore fires exactly where the prefill grants it replaces
        would have been scheduled): every ramping slot with a live swap
        entry restores as many of its host-resident blocks as the pool
        can cover right now — async H2D scatter into private blocks,
        ``prefill_pos``/lens jump to the stitch. The entry SURVIVES a
        dry pool (restores retry as retirements free blocks — the whole
        point of demoting instead of discarding) and partial restores
        stay BLOCK-ALIGNED so the remainder can restore later; it is
        consumed when the stitch reaches ``T-1`` (the final position
        recomputes — deterministically identical KV — so the last
        prefill grant still produces the sampler's logits), and dropped
        when it can no longer apply (tenant/token drift, the ramp
        passed it by re-prefilling, or a misaligned budget-clamped
        grant boundary)."""
        # gate on the STORE, not kv_host_swap: shipped entries (a peer's
        # import_kv) restore through this same path on engines that never
        # enabled local preempt-swap
        if self.cache_impl != "paged" or not self._swap_store:
            return
        bs = self.block_size
        for b, slot in enumerate(self.slots):
            if slot is None:
                continue
            rid = slot.req.request_id
            entry = self._swap_store.get(rid)
            if entry is None:
                continue
            req = slot.req
            T = len(entry["tokens"])
            pos = slot.prefill_pos
            # the token-prefix compare is O(T): run it ONCE per
            # (entry, resident request) — both arrays are immutable, so
            # the cached verdict holds for every dry-pool retry
            if entry.get("validated") != rid:
                if entry["adapter_id"] != req.adapter_id or \
                        T > slot.prompt_len or \
                        not np.array_equal(entry["tokens"],
                                           req.prompt_ids[:T]):
                    del self._swap_store[rid]
                    continue
                entry["validated"] = rid
            if (not slot.ramping) or slot.generated or pos >= T - 1 or \
                    req.kind == "embed":
                del self._swap_store[rid]
                continue
            if pos % bs:
                # a budget-clamped grant left the ramp mid-block: keep
                # the entry (a later aligned position may restore; the
                # finish/preempt paths clean it up regardless)
                continue
            target = T - 1               # the stitch cap: T-1 recomputes
            first_blk = pos // bs
            n_restore = target // bs + 1 - first_blk
            t0 = time.perf_counter()
            # blocks the slot already owns past the stitch count toward
            # the restore span (a budget-clamped grant may have grabbed
            # coverage it never filled) — only the shortfall allocates
            have = max(len(self._slot_blocks[b]) - first_blk, 0)
            got = min(n_restore, have + self._n_allocatable())
            need = first_blk + got - len(self._slot_blocks[b])
            if got <= 0 or (need > 0 and
                            not self._alloc_blocks(b, need)):
                continue                 # pool dry NOW — retry next step
            self._drain_swap_writes()    # the entry may still be staging
            dst = self._slot_blocks[b][first_blk:first_blk + got]
            idx = self._pad_block_idx(dst)
            m = len(idx)

            def staged(x):
                rows = x[first_blk:first_blk + got]
                if m > got:
                    pad = np.zeros((m - got,) + rows.shape[1:],
                                   rows.dtype)
                    rows = np.concatenate([rows, pad])
                return rows

            self._k, self._v = self._kv_scatter_fn(
                self._k, self._v, idx,
                jax.tree_util.tree_map(staged, entry["k"]),
                jax.tree_util.tree_map(staged, entry["v"]))
            covered = (first_blk + got) * bs
            # a partial restore stops at a BLOCK boundary (the remainder
            # restores or re-prefills later); a full one stitches at T-1
            stitch = target if covered > target else covered
            slot.prefill_pos = stitch
            self._lens = self._set_len_fn(self._lens, np.int32(b),
                                          np.int32(stitch))
            if stitch >= target:
                del self._swap_store[rid]
            shipped = bool(entry.get("shipped"))
            if shipped:
                # cross-replica ships book their OWN counters so the
                # StepRecord swap-byte deltas stay the preempt_swap
                # classifier's exclusive signal (see _spill_block note)
                self.stats["kv_ship_in_blocks"] += got
                self.stats["kv_ship_in_bytes"] += got * \
                    self.kv_bytes_per_block()
            else:
                self.stats["kv_swap_in_blocks"] += got
                self.stats["kv_swap_in_bytes"] += got * \
                    self.kv_bytes_per_block()
            self.stats["kv_swap_saved_tokens"] += max(stitch - pos, 0)
            restore_s = time.perf_counter() - t0
            if shipped:
                # the migration's STITCH phase wall (alloc + H2D scatter
                # + lens jump), keyed by rid for the router's migration
                # phase breakdown (ReplicaRouter reads it after the
                # decode leg resolves; bounded by _swap_store churn)
                self._stitch_s[rid] = \
                    self._stitch_s.get(rid, 0.0) + restore_s
            rec = self._rec()
            if rec is not None:
                rec.req_event(rid,
                              "kv_shipped_in" if shipped else "swapped_in",
                              step_id=rec.next_step_id(),
                              value=max(stitch - pos, 0))
                if shipped:
                    # a dedicated stitch span so the merged cross-replica
                    # trace shows the restore wall as its own sub-span
                    rec.req_event(rid, "kv_stitch",
                                  step_id=rec.next_step_id(),
                                  value=round(restore_s, 6))

    def _spill_block(self, phys):
        """Demote an LRU-evicted registered block's content to the
        bounded host spill store (the tier's eviction half), keyed by
        its chain hash so a later content-store probe can promote it
        back instead of recomputing the chunk. Called BEFORE
        ``_unregister`` strips the block's identity; the byte budget
        evicts the oldest spilled entries first."""
        h = self._block_hash.get(phys)
        per = self.kv_bytes_per_block()
        if h is None or h in self._spill or per > self.kv_host_spill_bytes:
            return
        k_host, v_host = self._kv_gather_fn(self._k, self._v,
                                            self._pad_block_idx([phys]))
        for leaf in jax.tree_util.tree_leaves([k_host, v_host]):
            try:
                leaf.copy_to_host_async()
            except AttributeError:
                pass
        while self._spill_bytes + per > self.kv_host_spill_bytes \
                and self._spill:
            _, old = self._spill.popitem(last=False)
            self._spill_bytes -= old["nbytes"]
        entry = {"parent": self._block_parent[phys],
                 "tokens": self._block_tokens[phys],
                 "n_blocks": 1, "k": k_host, "v": v_host, "ready": False,
                 "nbytes": per}
        self._spill[h] = entry
        self._spill_bytes += per
        self._swap_pending.append(entry)
        # spill traffic books on its OWN counters (kv_spill_blocks /
        # kv_host_spill_blocks), never on kv_swap_out_bytes: the
        # StepRecord swap-byte deltas are the preempt_swap-vs-reprefill
        # classifier's signal, and spill bytes riding them would label a
        # swap-off preemption step "preempt_swap" whenever an unrelated
        # eviction landed on it
        self.stats["kv_spill_blocks"] += 1

    def _promote_spilled(self, h):
        """Promote a spilled block back into the device pool: claim a
        writable block, scatter the host copy in, RE-REGISTER the
        content identity, and park it refcount-0 in the LRU — the
        probe's normal attach path then bumps it live, so promotion is
        invisible to everything above the content store. Returns the
        physical block, or None (spill miss / pool dry)."""
        entry = self._spill.get(h)
        if entry is None or not self._n_allocatable():
            return None
        self._drain_swap_writes()
        del self._spill[h]
        self._spill_bytes -= entry["nbytes"]
        phys = self._pop_block()
        idx = self._pad_block_idx([phys])

        def staged(x):
            if len(idx) > 1:
                pad = np.zeros((len(idx) - 1,) + x.shape[1:], x.dtype)
                return np.concatenate([x[:1], pad])
            return x[:1]

        self._k, self._v = self._kv_scatter_fn(
            self._k, self._v, idx,
            jax.tree_util.tree_map(staged, entry["k"]),
            jax.tree_util.tree_map(staged, entry["v"]))
        self._register_block(phys, h, entry["parent"],
                             np.frombuffer(entry["tokens"], np.int32))
        self._lru[phys] = None
        # promote traffic books on kv_promote_blocks only — see the
        # matching note in _spill_block (swap-byte deltas stay the
        # preemption classifier's exclusive signal)
        self.stats["kv_promote_blocks"] += 1
        return phys

    def swap_resident_rids(self):
        """Request ids whose committed KV currently lives in the HOST
        tier (preempted + swapped out — awaiting re-admission, or
        re-admitted and mid-restore) — a READ-ONLY probe the replica
        router uses to know which of a replica's requests can resume
        from their streamed tokens without recompute on failover."""
        if self.cache_impl != "paged":
            return ()
        return tuple(self._swap_store)

    # ---- cross-replica KV shipping (disaggregated prefill/decode) -----
    # The staged-entry format is the PR-13 swap entry plus identity
    # (rid, chain hashes) and pool-geometry fields, so export/import
    # reuse the same gather/scatter programs and the same stitch-at-T-1
    # re-admission. serving/kv_transport.py serializes exactly these
    # dicts to bytes-on-wire.

    def _export_slot_kv(self, b, slot):
        """Stage slot ``b``'s committed KV as a SHIPPABLE export entry —
        runs on the engine thread at the finish site of an
        ``export_kv``-flagged request, while the slot's blocks are still
        allocated. Same gather + async D2H staging as ``_swap_out_slot``;
        the entry carries identity (rid, tenant, tokens, chain hashes)
        and pool geometry so the destination can validate before it
        scatters. Materialization is deferred to :meth:`export_kv` (the
        copy overlaps whatever the device is doing next)."""
        req = slot.req
        kv_len = slot.prefill_pos + len(slot.generated)
        if kv_len <= 0 or req.kind == "embed":
            return
        nb = (kv_len - 1) // self.block_size + 1
        blocks = self._slot_blocks[b][:nb]
        if len(blocks) < nb:
            return
        k_host, v_host = self._kv_gather_fn(self._k, self._v,
                                            self._pad_block_idx(blocks))
        for leaf in jax.tree_util.tree_leaves([k_host, v_host]):
            try:
                leaf.copy_to_host_async()
            except AttributeError:      # CPU fallback: a buffer move
                pass
        done = np.concatenate([req.prompt_ids,
                               np.asarray(slot.generated, np.int32)])
        entry = {"rid": req.request_id, "tokens": done[:kv_len],
                 "adapter_id": req.adapter_id, "n_blocks": nb,
                 "block_size": self.block_size, "kv_quant": self.kv_quant,
                 # chain hashes of the FULL blocks: the destination's
                 # content-store identity (its _register_upto recomputes
                 # and must agree) and the pull-on-miss address space
                 "chain": self.prefix_chain_hashes(
                     done[:kv_len], adapter_id=req.adapter_id),
                 "k": k_host, "v": v_host, "ready": False,
                 "nbytes": nb * self.kv_bytes_per_block()}
        self._export_store[req.request_id] = entry
        while len(self._export_store) > self._export_cap:
            self._export_store.popitem(last=False)
        self.stats["kv_ship_out_blocks"] += nb
        self.stats["kv_ship_out_bytes"] += entry["nbytes"]

    def export_kv(self, request_id):
        """Pop + materialize the staged export entry for ``request_id``
        (an ``export_kv``-flagged request that finished on this engine).
        Callable from ANY thread — the pop is a GIL-atomic dict op and
        materialization only reads already-gathered host-bound staging
        arrays, never the pool. Returns the plain-numpy staged entry
        (serializable by ``serving.kv_transport``), or None."""
        self._layout.refuse(kv_shipping="export_kv()")
        if self.cache_impl != "paged":
            return None
        entry = self._export_store.pop(request_id, None)
        if entry is None:
            return None
        if not entry["ready"]:
            nb = entry["n_blocks"]
            entry["k"] = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:nb], entry["k"])
            entry["v"] = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:nb], entry["v"])
            entry["ready"] = True
        return entry

    def import_kv(self, entry):
        """Stage a SHIPPED entry for restore into this engine: validate
        pool-geometry compatibility, then seed the swap store under the
        entry's rid — the existing ``_try_swap_restores`` (engine
        thread) does the allocation, the fenced scatter, the one-token
        stitch and the identity validation (rid + tenant + token
        prefix) when the re-admitted request's slot next schedules.
        Callable from ANY thread (one GIL-atomic dict write). Returns
        True when staged, False on a compatibility reject — the router
        falls back to plain re-prefill."""
        self._layout.refuse(kv_shipping="import_kv()")
        if self.cache_impl != "paged" or self.scheduler != "fused":
            return False
        if not entry.get("ready") or entry.get("n_blocks", 0) <= 0:
            return False
        if int(entry.get("block_size", -1)) != self.block_size or \
                entry.get("kv_quant") != self.kv_quant:
            return False
        pool_leaves = jax.tree_util.tree_leaves([self._k, self._v])
        ent_leaves = jax.tree_util.tree_leaves(
            [entry["k"], entry["v"]])
        if len(ent_leaves) != len(pool_leaves):
            return False
        for p, e in zip(pool_leaves, ent_leaves):
            if tuple(e.shape[1:]) != tuple(p.shape[1:]) or \
                    np.dtype(e.dtype) != np.dtype(p.dtype):
                return False
        rid = entry["rid"]
        self._swap_store[rid] = {
            "tokens": np.asarray(entry["tokens"], np.int32),
            "adapter_id": int(entry["adapter_id"]),
            "n_blocks": int(entry["n_blocks"]),
            "k": entry["k"], "v": entry["v"], "ready": True,
            "nbytes": int(entry["n_blocks"]) * self.kv_bytes_per_block(),
            # shipped entries book kv_ship_in_* at restore, never the
            # kv_swap_* counters (the preempt classifier's signal)
            "shipped": True}
        return True

    def export_prefix_blocks(self, chain_hashes):
        """Pull-on-miss PEER export: package the registered prefix
        blocks for ``chain_hashes`` (device content store, or this
        engine's own spill store) as shippable single-block entries.
        READ-ONLY and callable from the router thread: the gather reads
        immutable pool array values through the dispatch lock, and the
        hash→phys mapping is re-checked AFTER materialization — a block
        evicted and reused mid-gather fails the re-check and is dropped
        (an eviction re-registered under the SAME hash is harmless by
        content addressing). Returns entries for the servable prefix
        only, stopping at the first miss."""
        self._layout.refuse(kv_shipping="export_prefix_blocks()")
        out = []
        if self.cache_impl != "paged" or not self.prefix_cache:
            return out
        per = self.kv_bytes_per_block()
        for h in chain_hashes:
            phys = self._store.get(h)
            if phys is None:
                spilled = self._spill.get(h) \
                    if self.kv_host_spill_bytes else None
                if spilled is not None and spilled.get("ready"):
                    out.append({"hash": h, "parent": spilled["parent"],
                                "tokens": spilled["tokens"],
                                "n_blocks": 1,
                                "block_size": self.block_size,
                                "kv_quant": self.kv_quant,
                                "k": spilled["k"], "v": spilled["v"],
                                "ready": True,
                                "nbytes": spilled["nbytes"]})
                    continue
                break
            parent = self._block_parent.get(phys)
            tokens = self._block_tokens.get(phys)
            if parent is None or tokens is None:
                break
            with self._dispatch_lock:
                k_host, v_host = self._kv_gather_fn(
                    self._k, self._v, self._pad_block_idx([phys]))
            k_host = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:1], k_host)
            v_host = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:1], v_host)
            if self._store.get(h) != phys or \
                    self._block_hash.get(phys) != h:
                break       # evicted/reused mid-gather: stop the span
            out.append({"hash": h, "parent": parent, "tokens": tokens,
                        "n_blocks": 1, "block_size": self.block_size,
                        "kv_quant": self.kv_quant,
                        "k": k_host, "v": v_host, "ready": True,
                        "nbytes": per})
        if out:
            self.stats["kv_ship_out_blocks"] += len(out)
            self.stats["kv_ship_out_bytes"] += \
                sum(e["nbytes"] for e in out)
        return out

    def import_prefix_blocks(self, entries):
        """Queue shipped prefix-block entries (a peer's
        :meth:`export_prefix_blocks`) for this engine's spill store.
        Callable from ANY thread — entries land in a GIL-atomic inbox
        and the engine thread drains them (validated + budget-bounded)
        at the top of its next step, BEFORE admission probes run, so a
        request submitted right after the import hits them. Requires an
        armed spill store (``kv_host_spill_bytes > 0``); entries are
        dropped otherwise. Returns the number queued."""
        self._layout.refuse(kv_shipping="import_prefix_blocks()")
        if self.cache_impl != "paged" or not self.prefix_cache or \
                not self.kv_host_spill_bytes:
            return 0
        n = 0
        for e in entries:
            if not e.get("ready") or \
                    int(e.get("block_size", -1)) != self.block_size or \
                    e.get("kv_quant") != self.kv_quant:
                continue
            self._spill_inbox.append(e)
            n += 1
        return n

    def _drain_spill_inbox(self):
        """Engine-thread half of pull-on-miss: move shipped prefix
        blocks from the inbox into the bounded spill store (hash
        re-derived from parent + tokens, so a corrupt or miskeyed entry
        can never register under a hash it doesn't hash to). The
        existing probe → ``_promote_spilled`` path then serves them
        exactly like locally spilled content."""
        if not self._spill_inbox:
            return
        inbox, self._spill_inbox = self._spill_inbox, []
        pool_leaves = jax.tree_util.tree_leaves([self._k, self._v])
        got_blocks = got_bytes = 0
        for e in inbox:
            tokens = np.frombuffer(e["tokens"], np.int32) \
                if isinstance(e["tokens"], bytes) \
                else np.asarray(e["tokens"], np.int32)
            h = self._chain_hash(e["parent"], tokens)
            if e.get("hash") is not None and e["hash"] != h:
                continue
            if h in self._store or h in self._spill:
                continue
            ent_leaves = jax.tree_util.tree_leaves([e["k"], e["v"]])
            if len(ent_leaves) != len(pool_leaves) or any(
                    tuple(x.shape[1:]) != tuple(p.shape[1:])
                    or np.dtype(x.dtype) != np.dtype(p.dtype)
                    for x, p in zip(ent_leaves, pool_leaves)):
                continue
            per = self.kv_bytes_per_block()
            if per > self.kv_host_spill_bytes:
                continue
            while self._spill_bytes + per > self.kv_host_spill_bytes \
                    and self._spill:
                _, old = self._spill.popitem(last=False)
                self._spill_bytes -= old["nbytes"]
            self._spill[h] = {"parent": e["parent"],
                              "tokens": tokens.tobytes(),
                              "n_blocks": 1, "k": e["k"], "v": e["v"],
                              "ready": True, "nbytes": per}
            self._spill_bytes += per
            got_blocks += 1
            got_bytes += per
        if got_blocks:
            self.stats["kv_ship_in_blocks"] += got_blocks
            self.stats["kv_ship_in_bytes"] += got_bytes

    def _check_pool_invariants(self):
        """Debug-only allocator audit (PADDLE_TPU_POOL_CHECKS=1; the test
        conftest enables it suite-wide): every physical block sits in
        exactly ONE of {free heap, LRU cached, live-refcounted}, their
        sizes sum to n_blocks (no leaks), refcounts equal table
        references, table rows mirror _slot_blocks, and the trailing
        scratch block never enters circulation."""
        if not self._debug_pool:
            return
        # the audit READS allocator state wholesale — from a non-owning
        # thread that races the very invariants it checks, but only
        # while a dispatch is actually in flight (tests legitimately
        # audit a quiesced engine from the main thread after stop())
        if self._inflight > 0:
            self._assert_pool_owner("_check_pool_invariants")
        free = set(self._free_blocks)
        cached = set(self._lru)
        quarantined = set(self._quarantine)
        live = [p for blocks in self._slot_blocks for p in blocks]
        live_set = set(live)
        assert len(free) == len(self._free_blocks), "free heap duplicates"
        pools = (free, cached, live_set, quarantined)
        for i, a in enumerate(pools):
            for bset in pools[i + 1:]:
                assert not (a & bset), "block in two pools"
        assert free | cached | live_set | quarantined == \
            set(range(self.n_blocks)), (
            f"pool leak: free({len(free)}) + cached({len(cached)}) + "
            f"live({len(live_set)}) + quarantined({len(quarantined)}) "
            f"!= n_blocks({self.n_blocks})")
        for phys in quarantined:
            assert self._write_fence.get(phys), \
                f"unfenced block {phys} stuck in quarantine"
        for phys in list(cached) + list(free):
            # the fence is authoritative at release: a fenced block must
            # never sit in a pool the allocation ladder hands out from
            # (_pop_block pops the free heap / evicts the LRU with no
            # fence check)
            assert not self._write_fence.get(phys), \
                f"fenced block {phys} in an allocatable pool"
        refs = collections.Counter(live)
        for phys in range(self.n_blocks):
            assert self._block_ref[phys] == refs.get(phys, 0), (
                f"block {phys}: refcount {self._block_ref[phys]} != "
                f"{refs.get(phys, 0)} table references")
        for b in range(self.B):
            blocks = self._slot_blocks[b]
            row = self._tables[b]
            assert list(row[:len(blocks)]) == blocks, f"table row {b} drift"
            assert all(x == -1 for x in row[len(blocks):]), \
                f"table row {b} stale tail"
        for phys in cached:
            assert phys in self._block_hash, \
                f"unregistered block {phys} in the cached LRU"

    def _ensure_blocks(self, slot_idx, upto_pos):
        """Blocks covering positions [0, upto_pos]. False = pool dry."""
        need = upto_pos // self.block_size + 1
        have = len(self._slot_blocks[slot_idx])
        return need <= have or self._alloc_blocks(slot_idx, need - have)

    def prefill_blocks_needed(self, prompt_len):
        """Pool blocks the prefill of a ``prompt_len``-token prompt must
        cover. THE one copy of this arithmetic — admission, the
        too-small-pool check, the self-preempt recoverability guard, and
        the serving layer's synchronous validation all call it. Legacy
        admission writes whole chunk windows (chunk-rounded, block-
        quantized); the fused scheduler drop-scatters exact token
        positions, so it needs the prompt's own blocks plus the one the
        FIRST decode token grows into (position prompt_len) — without
        that +1 a block-aligned prompt that exactly fills the pool would
        admit, ramp fully, then silently retire 'preempted_pool' with
        zero tokens where the legacy path raises the loud too-small-pool
        error."""
        if self.scheduler == "fused":
            return -(-(prompt_len + 1) // self.block_size)
        pad_end = min(-(-prompt_len // self.chunk) * self.chunk,
                      self.capacity)
        return -(-pad_end // self.block_size)

    def _kernel_tp_ctx(self):
        """Trace-time TP routing for the Pallas paged kernels: while
        active, ``block_multihead_attention``'s TPU fast path shard_maps
        the decode/append kernels over the tp axis (each shard reads its
        own kv-head slice of the pools; block tables and seq_lens ride
        in replicated). Only trace time matters — the wrapped dispatches
        are already-compiled calls afterwards — and the context is inert
        without a tp mesh (or on CPU, where the dense fallback under
        GSPMD partitions itself)."""
        import contextlib
        if self._tp_axis is None or self.cache_impl != "paged":
            return contextlib.nullcontext()
        from ..ops.kernels.paged_attention import paged_tp_context
        return paged_tp_context(self._mesh, self._tp_axis)

    def tp_degree(self):
        """Size of the engine's tensor-parallel mesh axis (1 = single
        chip)."""
        return self._tp_size

    # ------------------------------------------------------------------
    # KV-pool capacity accounting (quantized serving)
    # ------------------------------------------------------------------
    def kv_pool_nbytes(self):
        """Total (global) device bytes of the paged K/V pools INCLUDING
        the quantization scale arrays; 0 on dense engines. Summed off
        the real buffers' shapes, so the capacity acceptance (an int8
        pool fits >= 1.9x, int4 >= 3.5x the bf16 block count at equal
        HBM bytes) is asserted against what is actually allocated, not
        a side formula."""
        if self.cache_impl != "paged":
            return 0
        # the POOLS: a layout's per-slot recurrent state is not in blocks
        pools = [(k, v) for kind, k, v in zip(self._layout, self._k, self._v)
                 if kind.paged]
        return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(pools))

    def kv_bytes_per_block(self):
        """Device bytes ONE pool block costs across all layers (K + V
        payload plus its per-head scales) — what an equal-byte pool
        sizing divides a HBM budget by."""
        if self.cache_impl != "paged":
            return 0
        return self.kv_pool_nbytes() // (self.n_blocks + 1)

    def kv_pool_effective_blocks(self):
        """Pool capacity in BF16-EQUIVALENT blocks: how many unquantized
        blocks the pool's HBM bytes would have held — n_blocks on an
        unquantized pool, ~2x/~4x n_blocks under int8/int4 (minus the
        scale overhead). The ``kv_pool_effective_blocks`` Prometheus
        gauge samples this: capacity dashboards read one number that is
        comparable across pool dtypes."""
        if self.cache_impl != "paged" or not self._layout.has_paged:
            return 0
        if not self.kv_quant:
            return self.n_blocks
        unquant = self.block_size * self._layout.bytes_per_token(
            np.dtype(self._np_dt).itemsize)
        return int(self.n_blocks * unquant
                   // max(self.kv_bytes_per_block(), 1))

    def max_pipeline_depth(self):
        """How many step_begin() dispatches may be in flight at once.

        The depth contract (mirrored as a table in
        docs/architecture.md):

        * **fused, dense**: 3 — every grant decision reads the
          scheduler's own lens mirror (``_Slot.sched_len`` counts
          in-flight growth), finish/preemption detection tolerates
          up-to-(depth-1)-steps-stale host state (a slot that finished
          in flight keeps dispatching until its first readout; later
          pendings drop its column via the slot-identity check), and
          the in-graph guards bound over-decode.
        * **fused, paged, full pool** (>= max_batch * blocks-per-slot):
          3 — allocation cannot fail in steady state. One TRANSIENT
          exception: a slot retiring while later dispatches still fence
          its blocks parks them in quarantine for up to depth-1
          step_finishes, so a boundary-crossing slot (or a fresh
          admission) in that window can find the heap short and take
          the ladder's partial-coverage clamp — or, worst case, a
          preemption, which stays token-exact (re-prefill + the
          per-(rid, position) sampling keys) and self-heals as the
          fences drain.
        * **fused, paged, oversubscribed**: 2 — the stride-aware
          in-flight WRITE FENCE makes mid-flight eviction memory-safe
          (a victim's blocks quarantine until the dispatches that may
          still write them land, so they are never handed to a new
          owner early), but every stale step a preemption decision
          lags costs re-prefill churn, so the contract caps the lag at
          one dispatch.
        * **fused speculative** (``speculative_k > 1``): 2 — the
          verify-grant lens mirror overestimates in-flight growth by
          every rejected tail (the device rolls back, the host learns
          at readout), so each extra stale dispatch over-fences and
          over-allocates a full window per slot; the contract caps the
          lag at one dispatch, which the rollback/quarantine machinery
          is proven against.
        * **legacy dense / speculative**: 2 (the original in-graph-
          guard contract — host request state is one step stale at the
          chained dispatch).
        * **legacy paged**: 1 — legacy slots have no in-flight lens
          mirror; the block allocator and the admission prefill train
          need each step's post-readout lens."""
        if self.scheduler == "fused":
            if self.speculative_k > 1:
                return 2
            if self.cache_impl != "paged" or \
                    self.n_blocks >= self.B * self._max_blocks:
                return 3
            return 2
        if self.cache_impl == "paged":
            return 1
        return 2

    def _release_slot_blocks(self, slot_idx):
        """Release every block slot ``slot_idx`` references and wipe its
        table row — shared by retirement (_free_slot) and the pool-dry
        admission rollback. Releases the DEEPEST block first: the LRU
        then evicts leaves before their chain parents (evicting a prefix
        head first would orphan every descendant still cached under
        it)."""
        for phys in reversed(self._slot_blocks[slot_idx]):
            self._release_block(phys)
        self._slot_blocks[slot_idx] = []
        self._tables[slot_idx, :] = -1
        self._check_pool_invariants()

    def _free_slot(self, slot_idx):
        slot = self.slots[slot_idx]
        if self.cache_impl == "paged":
            self._release_slot_blocks(slot_idx)
        if slot is not None:
            # drop this request's pin on its adapter's device slot (a
            # refcount-0 slot parks in the adapter LRU, still loaded —
            # the tenant's next request hits without a swap)
            self._release_adapter(getattr(slot.req, "adapter_id", 0))
        self.slots[slot_idx] = None

    def _preempt_newest(self, exclude=None, newer_than=None, retired=None):
        """Pool pressure: evict the most recently admitted active slot back
        to the FRONT of the waiting queue (its committed tokens join the
        prompt, so re-prefill reproduces the identical greedy state) and
        free its blocks. ``newer_than`` restricts candidates to slots
        admitted AFTER that order stamp — a requester may only evict slots
        newer than itself, or the preempt-newest invariant inverts (a new
        arrival evicting an older, further-along request, then thrashing
        as the roles swap every re-admission). Returns the evicted slot
        index or None."""
        candidates = [b for b, s in enumerate(self.slots)
                      if s is not None and b != exclude
                      and (newer_than is None
                           or self._admit_order[b] > newer_than)]
        if not candidates:
            return None
        b = max(candidates, key=lambda i: self._admit_order[i])
        self._preempt_slot(b, retired=retired)
        return b

    def _retire_pool_edge(self, b, retired=None):
        """Retire slot ``b`` at the pool edge with the distinct
        'preempted_pool' reason (not 'capacity' — that is the engine's
        sequence-length cap). THE one copy of the retire block — the
        recoverability guard, the legacy coverage loop's sole-slot case,
        and the fused scheduler's coverage all call it."""
        slot = self.slots[b]
        out = RequestOutput(
            slot.req.request_id,
            self._finish_tokens(slot.req, slot.generated), True,
            "preempted_pool")
        self.finished_outputs[slot.req.request_id] = out
        if retired is not None:
            retired.append(out)
        self._free_slot(b)
        return out

    def _preempt_slot(self, b, retired=None):
        """Evict slot ``b`` back to the FRONT of the waiting queue: its
        committed tokens join the prompt so re-prefill reproduces the
        identical greedy state, and its blocks free for older slots.

        Recoverability guard: chunk-rounded re-prefill can need MORE
        blocks than the slot currently holds, so a grown prompt may no
        longer fit the pool AT ALL — parking it would stall the FIFO and
        end in the loud too-small-pool error, losing its stream. Such a
        slot retires gracefully instead (finish_reason 'preempted_pool',
        appended to ``retired`` so step_finish returns it)."""
        slot = self.slots[b]
        req = slot.req
        done = np.concatenate([req.prompt_ids,
                               np.asarray(slot.generated, np.int32)])
        if self.prefill_blocks_needed(len(done)) > self.n_blocks:
            self._retire_pool_edge(b, retired)
            return
        if self.kv_host_swap:
            # demote the committed KV to host RAM BEFORE the blocks
            # release — re-admission then restores it (one H2D copy +
            # a one-token stitch) instead of re-prefilling the stream.
            # Unconditional on purpose: with the prefix cache on, the
            # registered full blocks often survive in the LRU/spill
            # store too, but only the swap entry covers the PARTIAL
            # tail block and content eviction races — the gather is one
            # async dispatch whose copy hides under the next step.
            self._swap_out_slot(b, slot)
        prefix = self._preempted_prefix.get(req.request_id, [])
        self._preempted_prefix[req.request_id] = \
            list(prefix) + list(slot.generated)
        self.waiting.appendleft(GenerationRequest(
            req.request_id, done,
            req.max_new_tokens - len(slot.generated),
            req.temperature, req.top_p, req.eos_token_id,
            readout_stride=req.readout_stride,
            adapter_id=req.adapter_id, kind=req.kind,
            spec_ewma=req.spec_ewma, export_kv=req.export_kv))
        self._free_slot(b)
        self.stats["preemptions"] += 1
        if self._rec() is not None:
            self._rec_preempted.append(req.request_id)

    def _finish_tokens(self, req, generated):
        """Full output stream incl. tokens committed before a preemption.
        Called exactly once per TERMINAL output, so it also drops the
        request's persisted acceptance-EWMA entry (kept across
        preemption and restart, dead weight after the finish)."""
        prefix = self._preempted_prefix.pop(req.request_id, [])
        self._spec_ewma.pop(req.request_id, None)
        if self.cache_impl == "paged":
            # a terminal output's host-tier swap entry is dead weight
            # (and a rid-reuse hazard) — drop it with the stitch state
            self._swap_store.pop(req.request_id, None)
        return list(prefix) + list(generated)

    def _admit(self, slot_idx, req, a_slot=0):
        """Chunked prefill of `req` into slot `slot_idx`. Dispatches are
        ASYNC (no host read), so chunk programs pipeline on device; the
        admit_time_s stat records only the host-side enqueue cost — the
        device-side prefill compute lands inside the next decode read.
        Paged mode returns False when the pool can't cover the prompt.
        ``a_slot``: the request's adapter device row (already acquired
        by the caller) — prefill KV must carry the adapter's deltas."""
        self._programs()
        P = len(req.prompt_ids)
        paged = self.cache_impl == "paged"
        # single-sequence prefill: the LoRA gather sees a batch of one
        lora1 = self._lora_pack(np.array([a_slot], np.int32))
        hit, chain = 0, []
        if paged:
            if self.prefix_cache:
                # longest cached prefix, CHUNK-granular here: legacy
                # prefill scatters whole chunk windows and must never
                # scatter into a shared block, so the hit boundary must
                # be a window boundary
                hit, chain = self._probe_prefix(slot_idx, req.prompt_ids,
                                                chunk_granular=True,
                                                adapter_id=req.adapter_id)
            # prefill writes whole chunks: cover round_up(P, chunk), then
            # release the over-allocation down to the prompt's own blocks
            # (chunk is a block multiple, so blocks-needed * block_size
            # IS the padded end position)
            pad_end = self.prefill_blocks_needed(P) * self.block_size
            if not self._ensure_blocks(slot_idx, pad_end - 1):
                # pool dry — roll the acquired hit back (the request
                # requeues; its shared refs must not pin cached blocks)
                self._release_slot_blocks(slot_idx)
                return False
        off = hit
        logits_row = None
        # ONE zero-padded prompt buffer per admit, sliced per window (the
        # old loop re-allocated a chunk-sized np.zeros and re-copied the
        # table row for EVERY chunk — pure host overhead on the admission
        # path), and ONE table-row copy: the row doesn't change during the
        # loop (blocks were allocated above).
        padded = np.zeros((max(-(-P // self.chunk) * self.chunk,
                               self.chunk),), np.int32)
        padded[:P] = req.prompt_ids
        table_row = self._tables[slot_idx].copy() if paged else None
        # legacy admission prefills BEFORE the step dispatches: its chunk
        # spans stamp the id the upcoming dispatch will take, so request
        # time still joins back to a StepRecord
        rec = self._rec()
        if hit:
            self.stats["prefix_hit_tokens"] += hit
            if rec is not None:
                rec.req_event(req.request_id, "cached_prefix",
                              step_id=rec.next_step_id(), value=hit)
        while off < P:
            take = min(self.chunk, P - off)
            if paged:
                # chunk windows stay block-aligned (off is a multiple of
                # chunk; capacity % chunk == 0), no slide-back needed
                win = off
            else:
                # JAX dynamic slices CLAMP out-of-range starts, so a window
                # that would cross the buffer end slides BACK instead:
                # positions [win, off) are recomputed (producing identical
                # KV) and the new tokens land exactly at [off, off+take)
                win = min(off, self.capacity - self.chunk)
            chunk_ids = padded[win:win + self.chunk][None]
            if paged:
                self._k, self._v, logits_row = self._prefill_paged_fn(
                    self._state_vals, self._k, self._v, chunk_ids,
                    table_row, np.int32(win),
                    np.int32(off + take - 1 - win), lora=lora1)
            else:
                self._k, self._v, logits_row = self._prefill_fn(
                    self._state_vals, self._k, self._v, chunk_ids,
                    np.int32(slot_idx), np.int32(win),
                    np.int32(off + take - 1 - win), lora=lora1)
            off += take
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_tokens"] += take
            if rec is not None:
                rec.req_event(req.request_id, "prefill",
                              step_id=rec.next_step_id(), value=take)
        if paged:
            # drop the chunk-padding over-allocation: keep only the blocks
            # the prompt actually occupies (+ the one decode grows into).
            # Popped blocks are always the fresh private tail of the
            # allocation (hit blocks sit below keep), so release just
            # returns them to the free heap.
            keep = P // self.block_size + 1
            blocks = self._slot_blocks[slot_idx]
            while len(blocks) > keep:
                phys = blocks.pop()
                self._tables[slot_idx, len(blocks)] = -1
                self._release_block(phys)
        self._admit_order[slot_idx] = self._admit_seq
        self._admit_seq += 1
        self._logits = self._set_logits_fn(self._logits, logits_row,
                                           np.int32(slot_idx))
        self._lens = self._set_len_fn(self._lens, np.int32(slot_idx),
                                      np.int32(P))
        if self._tokens is not None:
            # token history for in-graph drafting: the prompt, zero-padded
            row = np.zeros((self.capacity,), np.int32)
            row[:P] = req.prompt_ids
            self._tokens = self._set_tokens_fn(
                self._tokens, row, np.int32(slot_idx))
        slot = _Slot(req, P)
        slot.chain = chain
        slot.reg_blocks = len(chain)
        slot.a_slot = a_slot
        self.slots[slot_idx] = slot
        if paged:
            # the whole prompt is prefilled: publish its full blocks'
            # content (hit blocks are already registered and skip)
            self._register_upto(slot_idx, slot, P)
            self._check_pool_invariants()
        # the whole prompt was granted here: no wait in the slot
        self.stats["first_grants"] += 1

    def _admit_fused(self, slot_idx, req, a_slot=0, t_slot=None):
        """Fused-scheduler admission: slot ASSIGNMENT plus (prefix cache
        on) the content-store probe — hit blocks attach by table writes
        and refcount bumps, the optional COW tail costs one block clone,
        and ``prefill_pos`` starts AT the hit boundary so the step
        scheduler grants zero prefill for the shared span. No prefill
        dispatch, no other block allocation (both happen chunk-by-chunk
        inside the step scheduler); admission stays O(hit blocks) and
        never stalls running decodes. ``t_slot``: when the request took
        the slot (the admit phase's entry)."""
        self._programs()
        hit, chain = 0, []
        # swap-store gate, not kv_host_swap: a SHIPPED entry (import_kv)
        # must suppress the prefix probe the same way a local swap does,
        # even on engines with preempt-swap off
        swapped = self.cache_impl == "paged" and req.kind != "embed" and \
            req.request_id in self._swap_store
        if self.prefix_cache and req.kind != "embed":
            # embed requests never PROBE: a hit would skip the shared
            # span's hidden-state computation and corrupt the mean pool.
            # They still REGISTER their filled blocks (the KV content is
            # a pure function of tenant + tokens), so a later generate
            # request of the same tenant hits them.
            hit, chain = self._probe_prefix(slot_idx, req.prompt_ids,
                                            adapter_id=req.adapter_id,
                                            no_cow=swapped)
        probe_hit = hit
        # a live swap entry restores LAZILY in the scheduler
        # (_try_swap_restores, the next mixed step): the pool is often
        # dry at the exact re-admission moment, and consuming the entry
        # then would forfeit the restore a retirement one step later
        # could have paid for — admission just seeds the stitch at the
        # probe hit
        self._lens = self._set_len_fn(self._lens, np.int32(slot_idx),
                                      np.int32(hit))
        if self._layout.has_recurrent:
            # a recurrent layer starts a slot whose length is 0 from
            # zeros in-graph (cache_layout.Recurrent): setting the length
            # IS the reset, and costs the host nothing more in this phase
            self.stats["state_resets"] += 1
        if self._tokens is not None:
            # speculative fused engine: seed the device token history
            # with the WHOLE prompt (host-known even for a prefix-cache
            # hit span) so prompt-lookup drafts can match into it
            row = np.zeros((self.capacity,), np.int32)
            row[:len(req.prompt_ids)] = req.prompt_ids
            self._tokens = self._set_tokens_fn(self._tokens, row,
                                               np.int32(slot_idx))
        if req.kind == "embed":
            # fresh mean-pool accumulator for this slot's new occupant
            self._pooled = self._set_pooled_fn(self._pooled,
                                               np.int32(slot_idx))
        slot = _Slot(req, len(req.prompt_ids), prefill_pos=hit)
        slot.chain = chain
        slot.reg_blocks = len(chain)
        slot.a_slot = a_slot
        slot.t_slot = t_slot
        self.slots[slot_idx] = slot
        if probe_hit:
            # only the CONTENT-STORE hit counts as a prefix hit — the
            # swap-restored span is booked on the kv_swap_* stats
            self.stats["prefix_hit_tokens"] += probe_hit
            rec = self._rec()
            if rec is not None:
                rec.req_event(req.request_id, "cached_prefix",
                              step_id=rec.next_step_id(), value=probe_hit)
        self._admit_order[slot_idx] = self._admit_seq
        self._admit_seq += 1

    def _admit_waiting(self):
        fused = self.scheduler == "fused"
        for b in range(self.B):
            if not self.waiting:
                break
            if self.slots[b] is None:
                req = self.waiting[0]
                room = self.capacity - len(req.prompt_ids) - \
                    self.speculative_k
                if req.max_new_tokens > room:
                    import warnings
                    warnings.warn(
                        f"request {req.request_id}: capping max_new_tokens "
                        f"{req.max_new_tokens} -> {room} (engine capacity "
                        f"{self.capacity})", RuntimeWarning, stacklevel=3)
                    req.max_new_tokens = room
                if fused and self.cache_impl == "paged":
                    need = self.prefill_blocks_needed(len(req.prompt_ids))
                    if need > self.n_blocks:
                        # can NEVER ramp in: leave it at the head;
                        # step_begin raises the loud too-small-pool error
                        break
                    # admission-defer PROGRESS GUARANTEE (the fused-ramp
                    # livelock fix): a ramping slot must never be
                    # admitted while the pool cannot cover its ramp AND
                    # the outstanding ramp demand of already-resident
                    # ramping slots — otherwise two ramps over a pool
                    # barely larger than one prompt trade blocks through
                    # the preempt ladder forever (preempt newest →
                    # re-admit → re-grab → preempt), burning prefill
                    # FLOPs without either finishing (the 2-slot ×
                    # 4-block-prompt × 4-block-pool thrash PR 12
                    # surfaced). Deferring costs nothing: the resident
                    # ramp can always finish alone, and its retirement
                    # re-opens admission.
                    ramp_deficit = sum(
                        max(self.prefill_blocks_needed(s.prompt_len)
                            - len(self._slot_blocks[i]), 0)
                        for i, s in enumerate(self.slots)
                        if s is not None and s.ramping)
                    if ramp_deficit and \
                            need + ramp_deficit > self._n_allocatable():
                        break
                a_slot = self._acquire_adapter(req)
                if a_slot is None:
                    # every adapter cache slot is pinned by resident
                    # requests: defer (a retirement releases one) —
                    # exactly the dry-pool admission shape
                    break
                self.waiting.popleft()
                t_slot = self._to("admit")
                admitted = self._admit_fused(b, req, a_slot, t_slot) \
                    if fused else self._admit(b, req, a_slot)
                self._to("schedule")
                if admitted is False:
                    # paged pool dry: requeue and wait for a retirement
                    self.waiting.appendleft(req)
                    self._release_adapter(req.adapter_id)
                    break

    # ------------------------------------------------------------------
    # the engine loop
    # ------------------------------------------------------------------
    def step(self):
        """Admit waiting requests into free slots, run ONE decode step for
        all active slots, retire finished requests. Returns the list of
        RequestOutput finished by this step."""
        pending = self.step_begin()
        if pending is None:
            return []
        return self.step_finish(pending)

    def _rec(self):
        """The attached FlightRecorder when it is recording, else None —
        the one-attribute-check gate every hook goes through."""
        r = self.flight_recorder
        return r if (r is not None and r.enabled) else None

    def _record_dispatch(self, pending, kind, grants, scheduled, budget,
                         dispatch_s, readout_stride=1):
        """Emit this dispatch's StepRecord (recorder attached and armed
        by step_begin) and stamp ``pending`` with its step id. The
        admit/schedule splits are the engine's own stats deltas since
        step_begin's entry — what its phases (:meth:`_to`) booked — so
        the record can't drift from what the engine measured."""
        rec, ctx = self._rec(), self._rec_ctx
        if rec is None or ctx is None:
            return
        t0, admit0, sched0, hits0, swaps0, kvin0, kvout0, shin0, shout0 = ctx
        self._to("schedule")     # books the open schedule phase so far
        admit_s = self.stats["admit_time_s"] - admit0
        paged = self.cache_impl == "paged"
        preempted = tuple(self._rec_preempted) + tuple(
            o.request_id for o in pending.pool_done)
        pending.step_id = rec.begin_step(
            scheduler=self.scheduler, kind=kind, grants=grants,
            tokens_scheduled=scheduled, token_budget=budget,
            queue_depth=len(self.waiting),
            free_blocks=len(self._free_blocks) if paged else None,
            total_blocks=self.n_blocks if paged else None,
            pipeline_inflight=self._inflight,
            preemptions=preempted, admit_s=admit_s,
            schedule_s=self.stats["schedule_time_s"] - sched0,
            dispatch_s=dispatch_s, t_begin=t0,
            prefix_hit_tokens=(self.stats["prefix_hit_tokens"] - hits0
                               if self.prefix_cache else None),
            cached_blocks=len(self._lru) if self.prefix_cache else None,
            readout_stride=readout_stride,
            # quantized-KV capacity facts: pool bytes (payload + scales)
            # and the pool storage dtype — what joins a preemption-churn
            # tail back to "the pool was simply small"
            kv_pool_bytes=self._kv_nbytes if paged else None,
            kv_cache_dtype=(self.kv_quant or str(np.dtype(self._np_dt)))
            if paged else None,
            # preemption-SWAP traffic THIS step moved (swap-out at its
            # preemptions, swap-in restores at its scheduling) plus the
            # spill store's point-in-time size — the swap-byte deltas
            # are the exclusive signal splitting the explain_tail
            # preemption cause into swap vs re-prefill (spill/promote
            # traffic books elsewhere on purpose)
            kv_swap_in_bytes=(self.stats["kv_swap_in_bytes"] - kvin0)
            if paged else None,
            kv_swap_out_bytes=(self.stats["kv_swap_out_bytes"] - kvout0)
            if paged else None,
            kv_host_spill_blocks=len(self._spill) if paged else None,
            # cross-replica ship traffic THIS step (a shipped restore's
            # stitch grant rides a mixed step) — the explain_tail
            # "kv_ship" cause's signal, booked apart from swap bytes
            kv_ship_in_bytes=(self.stats["kv_ship_in_bytes"] - shin0)
            if paged else None,
            kv_ship_out_bytes=(self.stats["kv_ship_out_bytes"] - shout0)
            if paged else None,
            # per-slot TENANT ids + this step's adapter swap-ins (the
            # explain_tail "adapter_swap" cause reads them back)
            adapter_slots=tuple(
                (b, s.req.adapter_id) for b, s in enumerate(self.slots)
                if s is not None and s.req.adapter_id),
            adapter_swaps=self.stats["adapter_swaps"] - swaps0)
        self._rec_ctx = None

    # ---- runtime sanitizers (paddle_tpu.analysis) ---------------------
    def _open_stride_guard(self, pending):
        """Arm the one-sync-per-stride contract for an all-decode
        multi-step dispatch: until the guard closes (step_finish, or the
        next chained step_begin under pipelining), ANY implicit device
        transfer on the stepping thread raises — the PR-8 headline claim
        as a runtime assertion. Explicit
        transfers (jax.device_put / device_get) stay allowed, which is
        exactly the allowlist semantics the documented readout needs."""
        if not self._transfer_checks or \
                getattr(_STRIDE_GUARD_TLS, "cm", None) is not None:
            return
        cm = jax.transfer_guard("disallow")
        cm.__enter__()
        _STRIDE_GUARD_TLS.cm = cm
        _STRIDE_GUARD_TLS.owner = pending
        pending.guarded = True

    @property
    def _stride_guard(self):
        """The CALLING thread's open stride-guard context (None when no
        window is open on this thread) — introspection for tests. The
        slot is shared by all engines on the thread (see
        _STRIDE_GUARD_TLS)."""
        return getattr(_STRIDE_GUARD_TLS, "cm", None)

    def _close_stride_guard(self, finishing=None):
        """Close the CALLING thread's open window, if any (whichever
        engine opened it — one slot per thread; see
        :func:`close_thread_stride_guard`). A jax transfer guard is
        thread-local: another thread's window cannot be closed from
        here — and need not be, since it constrains only that thread;
        it heals when that thread next enters any engine (or is inert
        forever if the thread died with it)."""
        close_thread_stride_guard(finishing)

    def _note_pool_owner(self):
        if self._lock_checks:
            self._pool_owner = threading.get_ident()

    def _assert_pool_owner(self, what):
        """PADDLE_TPU_LOCK_CHECKS=1: the paged-pool allocator, content
        store and quarantine are engine-stepping-thread state (PTL004)
        — there is deliberately no lock on them, so a mutation from any
        other thread is a race. The owner is whichever thread ran the
        last step_begin; reset() clears the pin."""
        if not self._lock_checks or self._pool_owner is None:
            return
        me = threading.get_ident()
        if me != self._pool_owner:
            raise AssertionError(
                f"{what} on thread {me}, but the paged pool is owned by "
                f"engine-stepping thread {self._pool_owner} "
                f"(allocator/quarantine/content-store mutations are "
                f"engine-thread-only; route this through the serve loop "
                f"or take a step-protocol entry point)")

    def step_begin(self):
        """Admit waiting requests into free slots and DISPATCH one decode
        step for all active slots WITHOUT reading anything back. Returns a
        :class:`PendingStep` for :meth:`step_finish`, or None when there is
        nothing to run. Serialized per MODEL object (admission prefill,
        COW clones and the step dispatch all may TRACE through the shared
        model's bind_state — concurrent replica engines on one model must
        not interleave traces).

        Pipelining contract (dense and speculative engines): a second
        ``step_begin()`` may be called before the first ``step_finish()``
        — the chained dispatch consumes the first step's device futures,
        so the device runs ahead of the host by one step. Host request
        state is one step stale at the chained dispatch; that is safe
        because (a) the in-graph guards (eos, budget, capacity) deactivate
        slots from DEVICE state, (b) a slot the host retires between
        dispatch and finish fails the PendingStep identity check and its
        stale tokens are dropped, and (c) over-decode past a budget is
        bounded by one horizon and truncated by the host readout. The
        PAGED engine allocates pool blocks from host lens before each
        dispatch, so it must run depth 1 (finish before the next begin —
        enforced)."""
        # a chained (pipelined) dispatch re-opens host->device traffic:
        # the previous stride's strict window ends here, not at its
        # step_finish
        self._close_stride_guard()
        fi = self.fault_injector
        if fi is not None:
            # the chaos hook fires OUTSIDE the model dispatch lock: an
            # injected hang must wedge only THIS engine's loop, never
            # sibling replicas tracing through the same model object
            fi.on_step_begin(self)
        with self._dispatch_lock:
            try:
                return self._step_begin_impl()
            finally:
                self._to(None)

    def _step_begin_impl(self):
        from ..core import random as _random

        t_begin = self._to("schedule")
        self._note_pool_owner()
        if self.cache_impl == "paged" and self._spill_inbox:
            # pull-on-miss arrivals land BEFORE admission so a request
            # submitted right after the import probes into them
            self._drain_spill_inbox()
        if self.cache_impl == "paged" and \
                self._inflight >= self.max_pipeline_depth():
            raise RuntimeError(
                "paged engine cannot pipeline step_begin() calls this "
                "deep: its block allocator needs the previous step's "
                "lens (step_finish the outstanding PendingStep first; "
                "see max_pipeline_depth())")
        if self._rec() is not None:
            # wall-split anchors for this step's record: entry time,
            # admit-stat baseline (scheduling = wall - admit - dispatch),
            # prefix-hit + adapter-swap baselines (the record carries
            # this step's deltas)
            self._rec_ctx = (t_begin,
                             self.stats["admit_time_s"],
                             self.stats["schedule_time_s"],
                             self.stats["prefix_hit_tokens"],
                             self.stats["adapter_swaps"],
                             self.stats["kv_swap_in_bytes"],
                             self.stats["kv_swap_out_bytes"],
                             self.stats["kv_ship_in_bytes"],
                             self.stats["kv_ship_out_bytes"])
            self._rec_preempted = []
        self._admit_waiting()
        if not any(s is not None for s in self.slots):
            if self.waiting and self.cache_impl == "paged":
                # nothing running AND the head request couldn't admit: the
                # pool simply cannot hold its prompt — fail loudly rather
                # than letting generate() spin forever
                req = self.waiting[0]
                P = len(req.prompt_ids)
                need = self.prefill_blocks_needed(P)
                if need > self.n_blocks:
                    raise PoolCapacityError(
                        f"request {req.request_id}: prefilling its "
                        f"{P}-token prompt needs {need} KV blocks but the "
                        f"pool has {self.n_blocks} total (kv_pool_blocks "
                        f"too small)")
            return None
        self._programs()
        if self._rng_key is None:
            if self._sampling_seed is not None:
                # replica-independent base key (disaggregated serving):
                # every engine built with the same sampling_seed derives
                # identical per-(rid, position) fold_in keys, so a
                # migrated sampled stream continues token-exactly
                key = jax.random.PRNGKey(self._sampling_seed)
            else:
                seed, counter = _random.default_generator.next_seed()
                key = jax.random.fold_in(jax.random.PRNGKey(seed), counter)
            if self._mesh is not None:
                # multi-process: the key must be a GLOBAL replicated array
                # (every process derives the identical value from the seed)
                from jax.sharding import NamedSharding, PartitionSpec
                # ptlint: disable=PTL001 -- one-time rng seed pull at the
                # FIRST step only (self._rng_key is None exactly once per
                # reset), never in the per-stride dispatch->readout window
                data = np.asarray(jax.random.key_data(key))
                glob = jax.make_array_from_callback(
                    data.shape,
                    NamedSharding(self._mesh, PartitionSpec()),
                    lambda idx: data[idx])
                key = jax.random.wrap_key_data(glob)
            self._rng_key = key
        spec = self.speculative_k > 1
        pool_budget, pool_done = {}, []
        if self.scheduler == "fused" and \
                any(s is not None and s.ramping for s in self.slots):
            # at least one slot is ramping in: ONE fused mixed dispatch
            # covers its prefill chunk AND every decode slot's token
            # (or, speculative engine, its verify window). All-decode
            # steps fall through to the plain scan below (horizon
            # amortization intact in steady state).
            return self._begin_mixed_step(pool_done)
        if spec and self.scheduler == "fused":
            # fused SPECULATIVE all-decode: every slot runs verify
            # windows through the multi-window program (readout_stride
            # composes — a stride step is `stride` windows with the
            # same in-graph early exit)
            return self._begin_spec_decode(pool_done)
        # ALL-DECODE fast path: with readout_stride > 1 the fused
        # scheduler runs up to `stride` decode iterations as one
        # multi-step dispatch (in-graph early exit); the token-budget
        # walk degenerates to ONE decode grant of `stride` tokens per
        # slot, and block coverage below is pre-granted for the whole
        # stride. Legacy engines keep stride == horizon (the scan).
        stride = self._effective_stride()
        if self.cache_impl == "paged":
            # block coverage for the stride's growth (last written
            # position is cur + stride - 1); pool pressure first grabs
            # whatever blocks remain free (partial coverage + a budget
            # clamp beats eviction), then evicts the newest slots, and
            # only retires at the pool edge when a slot can't even write
            # one more token
            order = sorted((b for b, s in enumerate(self.slots)
                            if s is not None),
                           key=lambda i: self._admit_order[i])
            for b in order:
                if self.slots[b] is None:
                    continue  # evicted below while ensuring an older slot
                slot = self.slots[b]
                if slot.req.kind == "embed":
                    # fully-ramped embed slot awaiting its pooled
                    # readout: no decode growth, no block coverage
                    continue
                # sched_len counts in-flight growth too: under the fused
                # scheduler's pipelining the host allocates for step N+1
                # before step N's readout (legacy engines run depth 1
                # here, where sched_len == current length)
                cur = slot.sched_len()
                last_pos = min(cur + stride - 1, self.capacity - 1)
                while not self._ensure_blocks(b, last_pos):
                    avail = self._n_allocatable()
                    if avail:
                        self._alloc_blocks(b, avail)
                    covered = len(self._slot_blocks[b]) * self.block_size
                    if covered > cur:
                        pool_budget[b] = covered - cur
                        break
                    victim = self._preempt_newest(
                        exclude=b, newer_than=self._admit_order[b],
                        retired=pool_done)
                    if victim is None:
                        # no NEWER victim: this slot is the newest active.
                        # If OLDER slots are still running, self-preempt —
                        # park the request back on the waiting queue (its
                        # re-prefill path reproduces the identical greedy
                        # state; _preempt_slot's recoverability guard
                        # retires it instead when the grown prompt has
                        # outgrown the pool) and let it resume once an
                        # older slot retires and frees blocks. Only the
                        # SOLE active slot must retire outright (parking
                        # it would readmit into the same dry pool and
                        # spin) — with the distinct 'preempted_pool'
                        # reason, not 'capacity' (the engine's
                        # sequence-length cap).
                        if any(s is not None and i != b
                               for i, s in enumerate(self.slots)):
                            self._preempt_slot(b, retired=pool_done)
                            break
                        self._retire_pool_edge(b, pool_done)
                        break

        # embed slots never DECODE: one fully ramped but unread (its
        # pooled readout rides an earlier in-flight dispatch) sits
        # inactive in an all-decode step
        active = np.array([s is not None and s.req.kind != "embed"
                           for s in self.slots])
        if not active.any():
            if pool_done:
                pending = PendingStep(None, None, None, spec,
                                      list(self.slots), pool_done)
                # no dispatch, but preemptions/retirements happened —
                # record the drain so the causal chain has no hole
                self._record_dispatch(pending, "drain", (), 0,
                                      self.B * self.horizon, 0.0)
                return pending
            return None
        temps, top_ps, eos_ids, rids, budgets = \
            self._slot_sampling_arrays()
        for b, cap_left in pool_budget.items():
            budgets[b] = min(budgets[b], cap_left)

        # the stride-aware in-flight write fence (paged fused): every
        # block this dispatch may write — from each slot's COMMITTED
        # length through its scheduled stride — is fenced until
        # step_finish, so a mid-flight eviction can never hand one to a
        # new owner (see _fence_blocks / _release_block)
        fenced = []
        if self.cache_impl == "paged" and self.scheduler == "fused":
            for b, slot in enumerate(self.slots):
                if slot is None or not active[b]:
                    continue
                lo = slot.prefill_pos + len(slot.generated)
                hi = min(slot.sched_len() + stride - 1, self.capacity - 1)
                self._fence_blocks(b, lo, hi, fenced)

        # multi-step all-decode (readout_stride): one compiled k-step
        # loop with in-graph early exit — the host sync amortizes over
        # up to `stride` tokens per slot. Pinned latency-tier requests
        # (effective stride 1), horizon engines and legacy engines keep
        # the scan path — a readout_stride=1 engine is bit-identical to
        # the pre-stride engine by construction.
        use_multi = self.readout_stride > 1 and stride > 1

        # gathered per-slot adapter rows (None while no adapter is
        # registered — the dispatch then traces the pre-adapter body)
        lora = self._lora_pack(self._slot_adapter_rows())

        # the decode clock starts HERE: pool-allocator scans and host array
        # construction above must not masquerade as device decode time in
        # the stats' wall split. All arms DISPATCH
        # only — no host read; JAX async dispatch returns futures and the
        # transfer blocks in step_finish().
        k_iter = stride if use_multi else self.horizon
        rows = self.B * (self.speculative_k if spec else 1)
        t0 = self._to("dispatch", **self._dispatch_ids(
            "spec" if spec else "decode", rows * k_iter,
            int(active.sum()) * k_iter, active))
        counts = ctr_dev = None
        if use_multi:
            fn = self._multi_fn(stride)
            if self.cache_impl == "paged":
                with self._kernel_tp_ctx():
                    (toks, was_active, self._logits, self._k, self._v,
                     self._lens, self._rng_key, ctr_dev) = fn(
                        self._state_vals, self._k, self._v, self._logits,
                        self._lens, active, self._rng_key, temps, top_ps,
                        eos_ids, budgets, rids, self._tables.copy(),
                        lora=lora)
            else:
                (toks, was_active, self._logits, self._k, self._v,
                 self._lens, self._rng_key, ctr_dev) = fn(
                    self._state_vals, self._k, self._v, self._logits,
                    self._lens, active, self._rng_key, temps, top_ps,
                    eos_ids, budgets, rids, lora=lora)
            self.stats["multi_steps"] += 1
        elif self.cache_impl == "paged":
            with self._kernel_tp_ctx():
                (toks, was_active, self._logits, self._k, self._v,
                 self._lens, self._rng_key, ctr_dev) = self._step_paged_fn(
                    self._state_vals, self._k, self._v, self._logits,
                    self._lens, active, self._rng_key, temps, top_ps,
                    eos_ids, budgets, rids, self._tables.copy(),
                    lora=lora)
        elif spec:
            (toks, counts, was_active, self._logits, self._k, self._v,
             self._lens, self._rng_key, self._tokens) = self._spec_fn(
                self._state_vals, self._k, self._v, self._logits,
                self._lens, active, self._rng_key,
                temps, top_ps, eos_ids, budgets, rids, self._tokens)
        else:
            (toks, was_active, self._logits, self._k, self._v, self._lens,
             self._rng_key, ctr_dev) = self._step_fn(
                self._state_vals, self._k, self._v, self._logits,
                self._lens, active, self._rng_key,
                temps, top_ps, eos_ids, budgets, rids, lora=lora)
        dt = self._to("schedule") - t0
        self._inflight += 1
        if not use_multi:
            # the scan runs its whole horizon whatever deactivates
            self.stats["rows_computed"] += rows * k_iter
        ctx0 = None
        if self.cache_impl == "paged" and not spec:
            ctx0 = np.array([s.sched_len() if s is not None and active[b]
                             else 0 for b, s in enumerate(self.slots)])
        sched = {}
        if self.scheduler == "fused":
            # host lens mirror for the paged pipeline: a surviving slot
            # grows exactly `stride` tokens per dispatch (every in-graph
            # early-deactivation — eos, budget, capacity — also retires
            # the slot at readout, so the mirror never undershoots a
            # live slot; an early EXIT below the stride only ever
            # accompanies such a deactivation)
            for b, slot in enumerate(self.slots):
                if slot is not None and active[b]:
                    slot.inflight += stride
                    sched[b] = stride
        pending = PendingStep(
            toks, was_active, counts, spec, list(self.slots), pool_done,
            sched=sched, fenced=fenced,
            # legacy verify scan: full-width windows per active slot —
            # the shared readout's acceptance accounting reads this
            verify=({int(b): self.speculative_k - 1
                     for b in np.nonzero(active)[0]
                     if self.slots[b] is not None} if spec else None))
        pending.t_dispatch = t0
        pending.ctr = ctr_dev
        pending.ctx0 = ctx0
        pending.rows = rows if use_multi else 0
        if self.cache_impl == "paged":
            self._book_kv_grid(k_iter)
        if use_multi:
            # all-decode stride dispatched: arm the strict
            # dispatch->readout window (no-op unless
            # PADDLE_TPU_TRANSFER_CHECKS=1)
            self._open_stride_guard(pending)
        if self._rec() is not None:
            # ONE decode grant per slot covering the whole stride (spec:
            # stride verify windows of up to Kspec each)
            per_slot = stride * (self.speculative_k if spec else 1)
            grants = tuple(
                (b, s.req.request_id, "decode", per_slot)
                for b, s in enumerate(self.slots)
                if s is not None and active[b])
            self._record_dispatch(
                pending, "spec" if spec else "decode", grants,
                sum(g[3] for g in grants), self.B * per_slot, dt,
                readout_stride=per_slot)
        return pending

    def _slot_sampling_arrays(self, budgets=True):
        """Per-slot traced sampling inputs of one dispatch — THE one
        copy of the array construction (temps, top_ps, eos_ids, rids,
        and optionally remaining budgets) shared by the all-decode,
        speculative and mixed dispatch builders, so a new per-request
        field can never silently desynchronize one path. Called once a
        dispatched program, so ``sampling_steps`` is counted here: the
        host's copy of the predicate ``pick_tokens`` reads on the device."""
        temps = np.array([s.req.temperature if s else 0.0
                          for s in self.slots], np.float32)
        self.stats["sampling_steps"] += bool((temps > 0.0).any())
        top_ps = np.array([s.req.top_p if s else 1.0
                           for s in self.slots], np.float32)
        eos_ids = np.array([(s.req.eos_token_id if s and
                             s.req.eos_token_id is not None else -1)
                            for s in self.slots], np.int32)
        # per-slot request ids ride into the dispatch: sampling keys are
        # fold_in(fold_in(base, rid), position) — see sample_next
        rids = np.array([s.req.request_id if s else 0
                         for s in self.slots], np.int32)
        if not budgets:
            return temps, top_ps, eos_ids, rids
        buds = np.array([(s.req.max_new_tokens - len(s.generated))
                         if s else 0 for s in self.slots], np.int32)
        return temps, top_ps, eos_ids, rids, buds

    # ------------------------------------------------------------------
    # fused scheduler: speculative all-decode dispatch (verify windows)
    # ------------------------------------------------------------------
    def _begin_spec_decode(self, pool_done):
        """ALL-DECODE dispatch of the fused SPECULATIVE engine: every
        active generate slot gets one VERIFY grant — 1 committed token
        plus its acceptance-adaptive draft count per window — run as
        ``stride`` windows in one compiled while_loop with in-graph
        early exit (the multi-step composition), through the append-form
        attention path. Rejected drafts roll back in-graph (lens) and,
        for paged slots, by host block-table truncation at readout.
        Pool pressure SHRINKS windows (per-slot ``row_caps``) before
        anyone is preempted — only a slot that cannot even write its
        committed token walks the preempt ladder."""
        stride = self._effective_stride()
        Kw = self.speculative_k
        paged = self.cache_impl == "paged"
        spec_qs = np.zeros((self.B,), np.int32)
        row_caps = np.full((self.B,), self.capacity, np.int32)
        order = sorted((b for b, s in enumerate(self.slots)
                        if s is not None),
                       key=lambda i: self._admit_order[i])
        for b in order:
            slot = self.slots[b]
            if slot is None or slot.req.kind == "embed":
                continue
            cur = slot.sched_len()
            if cur >= self.capacity:
                continue  # pipelined overshoot; readout retires it
            kd = self._spec_k_for(slot)
            if paged:
                want_hi = min(cur + stride * (1 + kd) - 1,
                              self.capacity - 1)
                if not self._ensure_blocks(b, want_hi):
                    avail = self._n_allocatable()
                    if avail:
                        self._alloc_blocks(b, avail)
                    covered = len(self._slot_blocks[b]) * self.block_size
                    if covered <= cur:
                        # cannot even write the committed token: the
                        # ordinary coverage ladder (preempt newer /
                        # park / retire at the pool edge)
                        if not self._ensure_pos_covered(b, cur,
                                                        pool_done):
                            continue
                        covered = len(self._slot_blocks[b]) * \
                            self.block_size
                    row_caps[b] = min(int(row_caps[b]), covered)
            spec_qs[b] = 1 + kd
        active = np.array([spec_qs[b] > 0 and self.slots[b] is not None
                           for b in range(self.B)])
        if not active.any():
            if pool_done:
                pending = PendingStep(None, None, None, True,
                                      list(self.slots), pool_done)
                self._record_dispatch(pending, "drain", (), 0,
                                      self.B * Kw * stride, 0.0)
                return pending
            return None
        temps, top_ps, eos_ids, rids, budgets = \
            self._slot_sampling_arrays()
        lora = self._lora_pack(self._slot_adapter_rows())
        # stride-aware write fence over every position this dispatch's
        # windows may write (committed length .. the scheduled stride of
        # full windows, clamped by coverage) — _fence_blocks clamps to
        # the blocks the slot actually holds
        fenced = []
        if paged:
            for b in np.nonzero(active)[0]:
                slot = self.slots[b]
                lo = slot.prefill_pos + len(slot.generated)
                hi = min(slot.sched_len() + stride * int(spec_qs[b]) - 1,
                         self.capacity - 1)
                self._fence_blocks(int(b), lo, hi, fenced)

        t0 = self._to("dispatch", **self._dispatch_ids(
            "spec", self.B * Kw * stride, int(spec_qs.sum()) * stride,
            active))
        fn = self._multi_spec_fn(stride)
        if paged:
            with self._kernel_tp_ctx():
                (toks, counts, was_active, self._logits, self._k,
                 self._v, self._lens, self._rng_key, self._tokens,
                 offered) = fn(
                    self._state_vals, self._k, self._v, self._logits,
                    self._lens, active, self._rng_key, temps, top_ps,
                    eos_ids, budgets, rids, spec_qs, row_caps,
                    self._tokens, tables=self._tables.copy(), lora=lora)
        else:
            (toks, counts, was_active, self._logits, self._k, self._v,
             self._lens, self._rng_key, self._tokens, offered) = fn(
                self._state_vals, self._k, self._v, self._logits,
                self._lens, active, self._rng_key, temps, top_ps,
                eos_ids, budgets, rids, spec_qs, row_caps, self._tokens,
                lora=lora)
        dt = self._to("schedule") - t0
        self.stats["fused_steps"] += 1
        if stride > 1:
            self.stats["multi_steps"] += 1
        self._inflight += 1
        sched, verify = {}, {}
        for b in np.nonzero(active)[0]:
            slot = self.slots[b]
            if slot is not None:
                # mirror the WORST-CASE growth (full acceptance every
                # window); the readout pays the whole grant back and
                # the committed count lands in slot.generated, so the
                # overestimate lives only while the dispatch is in
                # flight (the depth-2 contract)
                n = stride * int(spec_qs[b])
                slot.inflight += n
                sched[int(b)] = n
                verify[int(b)] = int(spec_qs[b]) - 1
        pending = PendingStep(toks, was_active, counts, True,
                              list(self.slots), pool_done, sched=sched,
                              fenced=fenced, verify=verify)
        pending.t_dispatch = t0
        pending.offered = offered
        pending.rows = self.B * Kw
        if paged:
            self._book_kv_grid(stride)
        if stride > 1:
            # speculative all-decode stride: same one-sync-per-stride
            # window as the dense multi-step path
            self._open_stride_guard(pending)
        if self._rec() is not None:
            grants = tuple(
                (int(b), self.slots[b].req.request_id, "verify",
                 stride * int(spec_qs[b]))
                for b in np.nonzero(active)[0]
                if self.slots[b] is not None)
            self._record_dispatch(
                pending, "spec", grants, sum(g[3] for g in grants),
                self.B * Kw * stride, dt, readout_stride=Kw * stride)
        return pending

    # ------------------------------------------------------------------
    # fused scheduler: the mixed prefill+decode step
    # ------------------------------------------------------------------
    def _ensure_pos_covered(self, b, pos, retired):
        """Cover decode position ``pos`` for slot ``b``, preempting NEWER
        slots under pool pressure (the horizon-1 mirror of the legacy
        coverage loop). Returns False when slot ``b`` itself had to be
        preempted (parked) or retired at the pool edge."""
        while not self._ensure_blocks(b, pos):
            victim = self._preempt_newest(
                exclude=b, newer_than=self._admit_order[b], retired=retired)
            if victim is not None:
                continue
            if any(s is not None and i != b
                   for i, s in enumerate(self.slots)):
                self._preempt_slot(b, retired=retired)
            else:
                # sole active slot at the pool edge: parking it would
                # readmit into the same dry pool and spin
                self._retire_pool_edge(b, retired)
            return False
        return True

    def _schedule_mixed(self, pool_done):
        """One token-budget scheduling pass: per slot, either one decode
        token (always granted — the budget bounds prefill interference,
        not decode progress), a VERIFY grant (speculative engine: the
        committed token is always granted, its acceptance-adaptive
        draft count rides the budget and shrinks first under budget or
        pool pressure), or a prefill chunk grant of up to ``min(chunk,
        remaining prompt, budget left)`` tokens, walked in admission
        order so older requests ramp first. Paged slots allocate their
        blocks HERE (the allocator moved into the unified scheduler); a
        ramping slot that can't cover its grant shrinks it to the
        blocks it could grab and otherwise waits for a retirement."""
        B, S = self.B, self.chunk
        paged = self.cache_impl == "paged"
        spec = self.speculative_k > 1
        ids = np.zeros((B, S), np.int32)
        q_lens = np.zeros((B,), np.int32)
        spec_ks = np.zeros((B,), np.int32) if spec else None
        is_dec = np.zeros((B,), bool)
        active = np.zeros((B,), bool)
        sched = {}
        budget = self.max_step_tokens
        order = sorted((b for b, s in enumerate(self.slots)
                        if s is not None),
                       key=lambda i: self._admit_order[i])
        for b in order:                      # decode slots first
            slot = self.slots[b]
            if slot is None or slot.ramping:
                continue
            if slot.req.kind == "embed":
                # prefill-only: a fully-ramped embed slot gets NO decode
                # grant — it just awaits its pooled readout (the
                # dispatch that carried its final chunk is in flight)
                continue
            cur = slot.sched_len()
            if cur >= self.capacity:
                continue  # pipelined overshoot; readout retires it
            if paged and not self._ensure_pos_covered(b, cur, pool_done):
                continue
            q = 1
            if spec:
                # verify grant: 1 committed token (always) + adaptive
                # drafts, shrunk by the remaining budget and by the
                # blocks the pool could actually cover — drafts are the
                # first thing pool/budget pressure takes away
                kd = min(self._spec_k_for(slot), max(budget - 1, 0))
                if paged and kd > 0 and \
                        not self._ensure_blocks(b, cur + kd):
                    avail = self._n_allocatable()
                    if avail:
                        self._alloc_blocks(b, avail)
                    covered = len(self._slot_blocks[b]) * self.block_size
                    kd = max(0, min(kd, covered - cur - 1))
                spec_ks[b] = kd
                q = 1 + kd
            q_lens[b] = q
            is_dec[b] = True
            active[b] = True
            sched[b] = q
            budget -= q
        first_ramp = True
        for b in order:                      # then prefill grants
            slot = self.slots[b]
            if slot is None or not slot.ramping:
                continue
            # progress guarantee: even when decode tokens alone exhaust
            # the budget (max_step_tokens < live decode slots), the
            # OLDEST ramping slot still gets one token — otherwise a
            # pathological budget starves ramp-in behind long decodes
            grant_cap = budget if budget > 0 else (1 if first_ramp else 0)
            if grant_cap <= 0:
                continue
            pos = slot.prefill_pos
            take = min(S, slot.prompt_len - pos, grant_cap)
            if paged and take > 0 and \
                    not self._ensure_blocks(b, pos + take - 1):
                avail = self._n_allocatable()
                if avail:
                    self._alloc_blocks(b, avail)
                covered = len(self._slot_blocks[b]) * self.block_size
                take = min(take, covered - pos)
            if take <= 0:
                continue
            # the guaranteed token is spent only on a grant that LANDED —
            # a pool-blocked oldest ramp must not eat it while a younger
            # ramping slot with covered blocks could make progress
            first_ramp = False
            ids[b, :take] = slot.req.prompt_ids[pos:pos + take]
            q_lens[b] = take
            active[b] = True
            budget -= take
        return ids, q_lens, is_dec, active, sched, spec_ks

    def _begin_mixed_step(self, pool_done):
        """Schedule and DISPATCH one fused mixed step (>= 1 slot is
        ramping): the whole ramp-in costs one dispatch per engine step
        instead of O(prompt_len / chunk) serial admission dispatches with
        every decode slot stalled behind them."""
        # host-tier swap-ins fire HERE, displacing the prefill grants
        # they make redundant (prefill_pos jumps to the stitch before
        # the budget walk sees the slot)
        self._try_swap_restores()
        for _ in range(self.B + 1):
            ids, q_lens, is_dec, active, sched, spec_ks = \
                self._schedule_mixed(pool_done)
            if active.any():
                break
            # nothing schedulable: every assigned slot is ramping into a
            # dry pool — park the newest (frees blocks for an older ramp;
            # _preempt_slot's recoverability guard retires hopeless ones)
            if self._preempt_newest(retired=pool_done) is None:
                break
        if not active.any():
            if pool_done:
                pending = PendingStep(None, None, None, False,
                                      list(self.slots), pool_done)
                self._record_dispatch(pending, "drain", (), 0,
                                      self.max_step_tokens, 0.0)
                return pending
            return None
        temps, top_ps, _, rids = self._slot_sampling_arrays(budgets=False)
        lora = self._lora_pack(self._slot_adapter_rows())
        # prefill-only plumbing: pass the pooled accumulator (and the
        # embed-slot mask) only while an embed request is RESIDENT, so
        # generate-only serving keeps the untouched no-embed program
        embed_rows = [b for b, s in enumerate(self.slots)
                      if s is not None and s.req.kind == "embed"]
        is_embed = pooled_arg = None
        if embed_rows:
            is_embed = np.zeros((self.B,), bool)
            is_embed[embed_rows] = True
            pooled_arg = self._pooled

        # in-flight write fence over this mixed dispatch's spans: the
        # decode token / verify window per decode slot, the granted
        # chunk span per ramping slot (see _fence_blocks)
        fenced = []
        if self.cache_impl == "paged":
            for b in np.nonzero(active)[0]:
                slot = self.slots[b]
                lo = slot.prefill_pos + len(slot.generated)
                hi = slot.sched_len() + int(q_lens[b]) - 1 if is_dec[b] \
                    else slot.prefill_pos + int(q_lens[b]) - 1
                self._fence_blocks(int(b), lo, min(hi, self.capacity - 1),
                                   fenced)

        spec = self.speculative_k > 1
        spec_args = dict(tokens_buf=self._tokens, spec_ks=spec_ks) \
            if spec else {}
        counts_dev = None
        # the append kernel's tile count is of the layers that hold K/V
        # pools, few or all (a looped layout's too: loop steps multiply
        # both counts alike)
        tiles = self._attn_tile_steps(q_lens) \
            if self.cache_impl == "paged" and self._layout.kv is not None \
            else None
        t0 = self._to("dispatch", **self._dispatch_ids(
            "mixed", self.mixed_rows, int(q_lens.sum()), q_lens > 0,
            prefill_rows=int(q_lens[~is_dec].sum()),
            live_tiles=tiles and tiles[0]))
        if self.cache_impl == "paged":
            with self._kernel_tp_ctx():
                ret = self._fused_fn(
                    self._state_vals, self._k, self._v, self._logits,
                    self._lens, self._rng_key, ids, q_lens, is_dec,
                    active, temps, top_ps, rids, self._tables.copy(),
                    lora=lora, is_embed=is_embed, pooled=pooled_arg,
                    **spec_args)
        else:
            ret = self._fused_fn(
                self._state_vals, self._k, self._v, self._logits,
                self._lens, self._rng_key, ids, q_lens, is_dec, active,
                temps, top_ps, rids,
                lora=lora, is_embed=is_embed, pooled=pooled_arg,
                **spec_args)
        offered = ctr_dev = None
        if spec:
            # spec layout: [1, B, Kw] window tokens + [1, B] counts —
            # the readout flatten shared with the legacy verify scan
            (toks, counts_dev, was_active, self._logits, self._k,
             self._v, self._lens, self._rng_key, pooled_out,
             self._tokens, offered) = ret
        else:
            (toks, was_active, self._logits, self._k, self._v,
             self._lens, self._rng_key, pooled_out, ctr_dev) = ret
        if pooled_out is not None:
            self._pooled = pooled_out
        dt = self._to("schedule") - t0
        self.stats["fused_steps"] += 1
        # every row of the packed axis is computed, granted or padding
        self.stats["rows_computed"] += self.mixed_rows
        # host mirrors of the scheduled growth (dispatch-time, so the
        # next step — possibly dispatched before this one's readout —
        # schedules from the post-step state)
        embed_done = []
        verify = {}
        for b in np.nonzero(active)[0]:
            slot = self.slots[b]
            if is_dec[b]:
                slot.inflight += int(q_lens[b])
                if spec:
                    verify[int(b)] = int(spec_ks[b])
            else:
                if slot.t_slot is not None:
                    # its first prefill grant: the wait in the slot ends
                    # with this dispatch
                    self.stats["slot_wait_time_s"] += t0 - slot.t_slot
                    self.stats["first_grants"] += 1
                    slot.t_slot = None
                slot.prefill_pos += int(q_lens[b])
                self.stats["prefill_chunks"] += 1
                self.stats["prefill_tokens"] += int(q_lens[b])
                if self.prefix_cache:
                    # blocks this grant fills are prompt content — publish
                    # them now so a same-prefix request admitted next step
                    # already hits (device reads happen in later
                    # dispatches, after this grant's write lands)
                    self._register_upto(int(b), slot, slot.prefill_pos)
                if slot.req.kind == "embed" and not slot.ramping:
                    # this dispatch carries the embed request's FINAL
                    # chunk: its pooled row is complete once the step's
                    # device work lands — step_finish reads + retires
                    embed_done.append((int(b), slot))
        self._inflight += 1
        if self.cache_impl == "paged":
            self._book_kv_grid(1)
            if tiles is not None:
                self.stats["attn_tile_steps"] += tiles[0]
                self.stats["attn_tile_steps_grid"] += tiles[1]
        pending = PendingStep(toks, was_active, counts_dev, spec,
                              list(self.slots), pool_done, sched=sched,
                              fenced=fenced, embed_done=embed_done,
                              verify=verify)
        pending.t_dispatch = t0
        pending.ctr = ctr_dev
        pending.pooled = pooled_out
        pending.offered = offered
        rec = self._rec()
        if rec is not None:
            grants = tuple(
                (int(b), self.slots[b].req.request_id,
                 ("verify" if spec else "decode") if is_dec[b]
                 else ("embed" if self.slots[b].req.kind == "embed"
                       else "prefill"), int(q_lens[b]))
                for b in np.nonzero(active)[0] if self.slots[b] is not None)
            self._record_dispatch(pending, "mixed", grants,
                                  sum(g[3] for g in grants),
                                  self.max_step_tokens, dt,
                                  readout_stride=(self.speculative_k
                                                  if spec else 1))
            for _, rid, gkind, n in grants:
                if gkind in ("prefill", "embed"):
                    rec.req_event(rid, "prefill",
                                  step_id=pending.step_id, value=n)
        return pending

    def step_finish(self, pending):
        """Block on ``pending``'s device→host token transfer, attribute the
        tokens to the slots captured at dispatch time, retire finished
        requests. Returns the list of RequestOutput finished by this
        step. Tokens of a slot whose occupant changed since dispatch
        (retired, cancelled, preempted — possibly already reused) are
        dropped: they were decoded for the old occupant's state."""
        # the strict stride window ends HERE: the readout below is the
        # stride's one permitted sync. Close before the chaos hook — an
        # injected crash must not leak a thread-local disallow context.
        self._close_stride_guard(finishing=pending)
        fi = self.fault_injector
        if fi is not None:
            fi.on_step_finish(self)
        try:
            return self._step_finish_impl(pending)
        finally:
            self._to(None)

    def _step_finish_impl(self, pending):
        spec = pending.spec
        rec = self._rec()
        sid = pending.step_id
        if pending.toks is None:
            if rec is not None and sid is not None:
                rec.finish_step(sid, 0.0, 0.0, tuple(
                    o.request_id for o in pending.pool_done))
            return list(pending.pool_done)
        self._inflight -= 1
        # pay the dispatch's scheduled decode growth back off the
        # host-side lens mirror (fused scheduler; {} otherwise)
        for b, n in pending.sched.items():
            slot = pending.slots[b]
            if slot is not None and self.slots[b] is slot:
                slot.inflight = max(0, slot.inflight - n)
        # the id its dispatch span carried: the recorder's, else the
        # count of steps finished before it
        ids = {"step_id": sid if sid is not None else self.stats["steps"]}
        t0 = self._to("sync", **ids)
        if spec:
            toks3 = np.asarray(pending.toks)          # [Kh, B, Kspec]
            counts_np = np.asarray(pending.counts)    # [Kh, B]
            wa_np = np.asarray(pending.was_active)    # [Kh, B]
            # per-window OFFERED widths (fused paths; None on the
            # legacy scan whose grant is never clamped in-graph)
            offered_np = np.asarray(pending.offered) \
                if pending.offered is not None else None
            Kh, B_, Ks = toks3.shape
            # flatten windows into the [rows, B] stream the readout walks;
            # a window row i is live for slot b iff i < counts (acceptance
            # truncates windows, so the stream has per-window gaps — the
            # readout SKIPS dead rows instead of stopping at them)
            toks_np = toks3.transpose(0, 2, 1).reshape(Kh * Ks, B_)
            act_np = ((np.arange(Ks)[None, :, None] <
                       counts_np[:, None, :]) &
                      wa_np[:, None, :]).reshape(Kh * Ks, B_)
        else:
            toks_np = np.asarray(pending.toks)       # [K, B] — THE transfer
            act_np = np.asarray(pending.was_active)  # [K, B]
        # the model's step counters left the program beside the tokens
        ctr_np = np.asarray(pending.ctr) if pending.ctr is not None else None
        dt = self._to(None) - t0
        self.stats["steps"] += 1
        if pending.guarded:
            # THE stride's one documented D2H sync just happened — the
            # transfer-guard window it closed proved nothing else
            # synced between dispatch and here
            self.stats["guarded_syncs"] += 1
        # the device work (every KV write included) provably landed —
        # the token sync completed — so this dispatch's write fences
        # drop now, BEFORE the readout walk can retire slots and free
        # (possibly quarantined) blocks
        if pending.fenced:
            self._unfence(pending.fenced)
        if self.cache_impl == "paged" and self._swap_pending:
            # host-tier copies issued in the step_begin/step_finish gap
            # overlapped this step's device work — settle them to numpy
            self._drain_swap_writes()

        # batched-readout stamp amortization: a k-row stride drains k
        # device steps in this ONE sync, but those tokens were produced
        # at k distinct device step boundaries spread over the
        # dispatch→sync window — so each row's emit stamp is backdated
        # by the boundaries still ahead of it, and histograms /
        # explain_tail see honest inter-token gaps instead of k-1 zeros
        # and one stride-wide spike. The window divides over the
        # boundaries the device actually RAN — iterations with any
        # activity (an early-exited stride spent its whole window on
        # the rows that executed), and for the spec engine a verify
        # WINDOW is one boundary: its Ks rows commit together, so they
        # share a stamp rather than being spread across gaps that never
        # existed. emit_backdate_s publishes the per-row backdate to
        # the serving layer's stream callback.
        n_exec = 0
        per_row = 0.0
        if spec:
            # flattened row k belongs to verify window k // Ks; wa_np
            # [Kh, B] (from the readout prep above) says which windows
            # the device actually ran
            row_boundary = np.arange(toks_np.shape[0]) // \
                self.speculative_k
            n_exec = int(wa_np.any(axis=1).sum())
        else:
            row_boundary = np.arange(toks_np.shape[0])
            n_exec = int(act_np.any(axis=1).sum())
        # an early-exit stride's rows, now that the iterations it ran are
        # known (at least the one every dispatch runs)
        self.stats["rows_computed"] += pending.rows * max(n_exec, 1)
        if pending.ctx0 is not None:
            # iteration k attends a live slot's tokens at dispatch, the k
            # it decoded since, and the new one
            k = np.arange(act_np.shape[0])[:, None]
            self.stats["decode_ctx_tokens"] += int(
                ((pending.ctx0[None, :] + k + 1) * act_np).sum())
            self.stats["decode_rows"] += int(act_np.sum())
            self.stats["decode_iterations"] += n_exec
        if ctr_np is not None:
            booked = dict(zip(self._step_counter_names,
                              (int(v) for v in ctr_np)))
            for name, v in booked.items():
                self.stats[name] += v
            # what the host could not know at dispatch, summed over the
            # layers
            for eid, names in self._step_emit_ids.items():
                ids[eid] = sum(booked[name] for name in names)
        now_pc = t0 = self._to("emit", **ids)
        if toks_np.shape[0] > 1 and pending.t_dispatch is not None \
                and n_exec > 1:
            per_row = max(now_pc - pending.t_dispatch, 0.0) / n_exec
        done = list(pending.pool_done)
        spec_acc_total = spec_rej_total = 0
        for b, slot in enumerate(pending.slots):
            if slot is None or self.slots[b] is not slot:
                # empty at dispatch, or retired/preempted/cancelled (and
                # possibly reused) since: stale column, skip
                continue
            finish_reason = None
            n_read = 0
            for k in range(toks_np.shape[0]):
                if not act_np[k, b]:
                    if spec:
                        # rejected tail of a verify window: later windows
                        # may still hold live tokens
                        continue
                    # deactivated in-graph before this iteration (eos or
                    # capacity hit at an earlier k): nothing more to read
                    break
                tok = int(toks_np[k, b])
                slot.generated.append(tok)
                n_read += 1
                self.stats["tokens_generated"] += 1
                self.emit_backdate_s = \
                    max(n_exec - 1 - int(row_boundary[k]), 0) * per_row
                if rec is not None and sid is not None:
                    # THE token→step join: this token's timeline span
                    # carries the id of the StepRecord that produced it
                    # (stamped at its amortized device step boundary)
                    rec.on_token(slot.req.request_id, sid,
                                 t=now_pc - self.emit_backdate_s)
                if self.stream_callback is not None:
                    self.stream_callback(slot.req.request_id, tok)
                    if self.slots[b] is not slot:
                        # the callback cancelled this request re-entrantly;
                        # stop reading its window and keep the 'cancelled'
                        # output it recorded
                        break
                if slot.req.eos_token_id is not None and \
                        tok == slot.req.eos_token_id:
                    finish_reason = "eos"
                elif len(slot.generated) >= slot.req.max_new_tokens:
                    finish_reason = "length"
                elif slot.prompt_len + len(slot.generated) >= \
                        self.capacity - self.speculative_k:
                    # margin of K: a verify window writes K positions, and
                    # JAX dynamic updates would clamp past the buffer end
                    finish_reason = "capacity"
                if finish_reason:
                    break
            if spec and n_read > 0:
                # drafts that actually landed in an output (row 0 of each
                # window is the committed sample, not a draft). Window
                # width == speculative_k for the legacy scan AND the
                # fused verify grants, so the flattened-row arithmetic
                # is shared.
                Ks = self.speculative_k
                n_committed = sum(
                    1 for k in range(toks_np.shape[0])
                    if act_np[k, b] and k % Ks == 0)
                accepted = max(n_read - n_committed, 0)
                # acceptance accounting: proposed = drafts the device
                # actually OFFERED this slot — per-window offered widths
                # read back from the fused programs (the in-graph
                # row_caps/capacity clamp can shrink a window below its
                # grant, and booking the full grant would bias the
                # EWMA/acceptance rate low exactly under pool pressure);
                # the legacy scan never clamps, so its grant IS exact
                if offered_np is not None:
                    proposed = int(np.maximum(
                        offered_np[:, b] - 1, 0)[wa_np[:, b]].sum())
                else:
                    kd = pending.verify.get(b, Ks - 1) if pending.verify \
                        else Ks - 1
                    proposed = int(wa_np[:, b].sum()) * kd
                self.stats["spec_proposed_tokens"] += proposed
                self.stats["spec_accepted_tokens"] += accepted
                spec_acc_total += accepted
                spec_rej_total += max(proposed - accepted, 0)
                if self.slots[b] is slot:
                    # the re-entrant-cancel guard: a stream callback may
                    # have cancelled this request mid-readout — its
                    # _finish_tokens already dropped the persisted EWMA
                    # entry, and updating it here would resurrect a dead
                    # rid's state (leak + stale seed on rid reuse)
                    self._update_spec_ewma(slot, proposed, accepted)
            if self.slots[b] is not slot:
                continue  # cancelled mid-window; don't record a finish
            if self.prefix_cache and n_read > 0:
                # decode-filled blocks register too (multi-turn reuse: a
                # follow-up prompt carrying this conversation's history
                # hits them) — content is the COMMITTED stream only
                self._register_upto(b, slot,
                                    slot.prefill_pos + len(slot.generated))
            if finish_reason:
                if slot.req.export_kv and self.cache_impl == "paged":
                    # stage the committed KV for cross-replica shipping
                    # WHILE the blocks are still allocated — export_kv()
                    # (router thread) pops the staged entry afterwards
                    self._export_slot_kv(b, slot)
                out = RequestOutput(
                    slot.req.request_id,
                    self._finish_tokens(slot.req, slot.generated), True,
                    finish_reason)
                self.finished_outputs[slot.req.request_id] = out
                done.append(out)
                # slot (and its KV blocks) freed; next step admits into it
                self._free_slot(b)
        # BLOCK-TABLE ROLLBACK (paged verify grants): blocks granted for
        # drafts the device rejected are orphaned — release them with NO
        # copy. Blocks still fenced by a younger in-flight dispatch
        # (depth 2: it may carry an in-flight writer) route through the
        # quarantine machinery instead of the free heap, so they are
        # never handed to a new owner early. The keep line is the slot's
        # sched_len — still counting YOUNGER dispatches' scheduled
        # growth, so nothing any in-flight writer may touch is released.
        if self.cache_impl == "paged" and pending.verify:
            bs = self.block_size
            for b in pending.verify:
                slot = pending.slots[b]
                if slot is None or self.slots[b] is not slot:
                    continue  # retired/preempted; blocks already freed
                keep = slot.sched_len() // bs + 1
                blocks = self._slot_blocks[b]
                while len(blocks) > keep:
                    phys = blocks.pop()
                    self._tables[b, len(blocks)] = -1
                    self._release_block(phys)
            self._check_pool_invariants()
        # prefill-only (embed) completions: this dispatch carried each
        # one's FINAL chunk, so ITS pooled output (pending.pooled — not
        # the engine's newest buffer, which belongs to younger in-flight
        # dispatches the readout must not synchronize on) holds the
        # complete rows. One [H] device read per finishing embed
        # request, divided by the prompt length = the mean pool.
        for b, slot in pending.embed_done:
            if self.slots[b] is not slot:
                continue      # cancelled/preempted since dispatch
            vec = np.asarray(pending.pooled[b], np.float32) \
                / max(slot.prompt_len, 1)
            out = RequestOutput(slot.req.request_id, [], True, "embed",
                                embedding=vec)
            self.finished_outputs[slot.req.request_id] = out
            done.append(out)
            self._free_slot(b)
        self.emit_backdate_s = 0.0
        d_emit = self._to(None) - t0
        if rec is not None and sid is not None:
            rec.finish_step(sid, dt, d_emit,
                            tuple(out.request_id for out in done),
                            spec_accepted=spec_acc_total,
                            spec_rejected=spec_rej_total)
        return done

    def generate(self, prompts, **sampling):
        """Drain-mode convenience: submit all prompts, run steps until every
        request finishes, return outputs in submission order. Pops its
        outputs from `finished_outputs` — long-running step()-driven servers
        should likewise consume step()'s return list and delete (or pop)
        entries they read, or the dict grows without bound."""
        rids = [self.add_request(p, **sampling) for p in prompts]
        while self.has_unfinished():
            self.step()
        return [self.finished_outputs.pop(r) for r in rids]

    def reset_stats(self):
        for key in self.stats:
            self.stats[key] = 0.0 if key.endswith("_s") else 0


def _bind(state, values):
    from ..jit.functional_call import bind_state
    return bind_state(state, values)


def _lookup_draft(tokens_buf, lens, k_draft, ngram):
    """In-graph prompt-lookup drafting: for each row, match the committed
    history's final `ngram` tokens against the history itself (most recent
    match wins) and propose the `k_draft` tokens that followed it. Falls
    back to repeating the last token — a bad draft only wastes the verify
    window, never changes output."""
    cap = tokens_buf.shape[1]
    idx = jnp.arange(cap)

    def per_row(buf, L):
        tail_start = jnp.maximum(L - ngram, 0)
        tail = jax.lax.dynamic_slice(buf, (tail_start,), (ngram,))
        eq = jnp.ones((cap,), bool)
        for j in range(ngram):
            # buf[i + j] == tail[j] for every window position i
            eq = eq & (jnp.roll(buf, -j) == tail[j])
        m = eq & (idx < (L - ngram))  # exclude the tail's own position
        has = jnp.any(m)
        i_star = cap - 1 - jnp.argmax(jnp.flip(m))  # most recent match
        start = jnp.where(has, i_star + ngram, 0)
        cont = jax.lax.dynamic_slice(buf, (start,), (k_draft,))
        last = buf[jnp.maximum(L - 1, 0)]
        pos = start + jnp.arange(k_draft)
        return jnp.where(has & (pos < L), cont, last).astype(jnp.int32)

    return jax.vmap(per_row)(tokens_buf, lens.astype(jnp.int32))


def _write_window(tokens_buf, window, lens):
    """Append a verify window's tokens to each row's history at its own
    length (rejected-tail positions are overwritten by later windows)."""
    def per_row(buf, w, L):
        return jax.lax.dynamic_update_slice(buf, w, (L,))

    return jax.vmap(per_row)(tokens_buf, window.astype(jnp.int32),
                             lens.astype(jnp.int32))


# NOTE: the old module-level `_spec_accept` (rejection sampling against
# the processed distribution, with residual masking carried across
# windows) was REPLACED by the in-_programs `verify_window` coupled
# rule: a draft is accepted iff it equals the token the engine would
# sample at that position under its per-(rid, position) fold_in key.
# Acceptance probability for a delta proposal is identical (p(draft)),
# but the committed stream is now TOKEN-IDENTICAL to the non-spec
# engine's in sampled mode too — no residual state to lose across a
# window boundary, a preemption, or a supervised restart — and the
# top-k/top-p "nucleus may shift by one token" approximation is gone.
