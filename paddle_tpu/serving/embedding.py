"""Embed-only encoder engine — non-generative serving through the same
:class:`~paddle_tpu.serving.AsyncLLMServer` front-end.

Reference analog: the reference's AnalysisPredictor front-end serves
classification/embedding models through the same predictor surface as
generative ones (PAPER.md §1, layer 6c). Here the llama engine already
serves prefill-only embedding requests INSIDE its fused token-budget walk
(``LLMEngine.add_request(kind="embed")``); this module is the second
half of the scenario-diversity story: a bidirectional ENCODER (bert) has
no KV cache and no decode loop at all, so it gets its own minimal engine
speaking the ``step_begin``/``step_finish`` protocol — one compiled
full-sequence forward per batch, masked mean-pool of the final hidden
states, everything else (admission queue, backpressure, deadlines,
telemetry, supervision) inherited from the server unchanged.

Static shapes: one ``[max_batch, max_seq_len]`` program serves every
batch composition (shorter prompts pad, the attention mask hides the
padding, and the pooled mean divides by the true lengths).
"""
from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from ..inference.llm_engine import (RequestOutput,
                                    close_thread_stride_guard,
                                    default_engine_stats)

__all__ = ["BertEmbedEngine"]


@dataclasses.dataclass
class _EmbedRequest:
    request_id: int
    prompt_ids: np.ndarray
    adapter_id: int = 0
    kind: str = "embed"
    max_new_tokens: int = 0
    temperature: float = 0.0
    top_p: float = 1.0
    eos_token_id: int | None = None
    readout_stride: int | None = None


class _BSlot:
    __slots__ = ("req",)

    def __init__(self, req):
        self.req = req


class _EmbedPending:
    __slots__ = ("out", "batch", "t_dispatch")

    def __init__(self, out, batch, t_dispatch):
        self.out = out          # device [B, H] pooled rows
        self.batch = batch      # [(row, _BSlot), ...]
        self.t_dispatch = t_dispatch


class BertEmbedEngine:
    """Prefill-only serving engine over a bert encoder
    (:class:`~paddle_tpu.models.bert.BertModel` or
    ``BertForMaskedLM``). Speaks the slice of the LLMEngine protocol
    :class:`~paddle_tpu.serving.AsyncLLMServer` drives — submit through
    ``server.submit_embed(...)``; every result carries the masked
    mean-pooled final hidden state."""

    #: the server routes every submission through submit_embed and
    #: rejects generation kinds up front
    embed_only = True

    def __init__(self, model, max_batch=8, max_seq_len=None):
        bert = getattr(model, "bert", model)
        self.model = model
        self._bert = bert
        c = bert.config
        model.eval()
        self.B = int(max_batch)
        self.capacity = int(max_seq_len or c.max_position_embeddings)
        if self.capacity > c.max_position_embeddings:
            raise ValueError(
                f"max_seq_len {self.capacity} exceeds the position table "
                f"({c.max_position_embeddings})")
        # the LLMEngine surface the server reads
        self.speculative_k = 1
        self.cache_impl = "dense"
        self.scheduler = "fused"
        self.prefix_cache = False
        self.readout_stride = 1
        self.horizon = 1
        self.stream_callback = None
        self.flight_recorder = None
        self.fault_injector = None
        self.waiting = collections.deque()
        self.slots = [None] * self.B
        self.finished_outputs = {}
        self._next_id = 0
        self._inflight = 0
        self._cancelled = set()
        self._fn = None
        self._state = None
        self._state_vals = None
        # the serving layer reads stats keys by name — share LLMEngine's
        # schema so a future counter can never silently drift
        self.stats = default_engine_stats()

    # -- protocol surface ----------------------------------------------
    def max_pipeline_depth(self):
        return 1     # one batch in flight; the sync IS the result

    def tp_degree(self):
        return 1

    def prefill_blocks_needed(self, prompt_len):
        return 0     # no paged pool

    def probe_prefix_len(self, token_ids, chain_hashes=None, adapter_id=0):
        return 0

    def prefix_chain_hashes(self, token_ids, adapter_id=0):
        return []

    def reset(self):
        """Supervised-restart hook: drop every resident/waiting request
        binding (the server re-admits from its own snapshot)."""
        self.waiting.clear()
        self.slots = [None] * self.B
        self.finished_outputs.clear()
        self._cancelled.clear()
        self._inflight = 0
        return self

    def add_request(self, prompt_ids, request_id=None, adapter_id=0,
                    kind="embed", **_ignored):
        ids = np.asarray(
            prompt_ids.numpy() if hasattr(prompt_ids, "numpy")
            else prompt_ids, dtype=np.int32).reshape(-1)
        if len(ids) == 0:
            raise ValueError("empty prompt")
        if len(ids) > self.capacity:
            raise ValueError(f"prompt of {len(ids)} tokens exceeds the "
                             f"encoder capacity {self.capacity}")
        if kind != "embed":
            raise ValueError("BertEmbedEngine serves embedding requests "
                             "only (kind='embed')")
        if adapter_id:
            raise ValueError("BertEmbedEngine has no adapter store")
        rid = self._next_id if request_id is None else request_id
        self._next_id = max(self._next_id, rid) + 1
        self.waiting.append(_EmbedRequest(rid, ids))
        self.stats["embed_requests"] += 1
        return rid

    def has_unfinished(self):
        return bool(self.waiting) or any(s is not None for s in self.slots)

    def cancel(self, request_id, reason="cancelled"):
        for i, req in enumerate(self.waiting):
            if req.request_id == request_id:
                del self.waiting[i]
                out = RequestOutput(request_id, [], True, reason)
                self.finished_outputs[request_id] = out
                return out
        for b, slot in enumerate(self.slots):
            if slot is not None and slot.req.request_id == request_id:
                # the batch is already on device; drop the row at readout
                self._cancelled.add(request_id)
                self.slots[b] = None
                out = RequestOutput(request_id, [], True, reason)
                self.finished_outputs[request_id] = out
                return out
        return None

    # -- compiled program ----------------------------------------------
    def _programs(self):
        if self._fn is not None:
            return
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor, functional_mode
        from ..jit.functional_call import (bind_state, collect_state,
                                           read_values)

        _, params, _, buffers = collect_state(self.model)
        state = params + buffers
        self._state = state
        self._state_vals = read_values(state)
        bert = self._bert

        def embed(state_vals, ids, mask):
            with functional_mode(), bind_state(state, state_vals):
                seq, _ = bert(Tensor(ids), None, Tensor(mask))
                seqv = seq._value.astype(jnp.float32)
            m = mask.astype(jnp.float32)[:, :, None]
            return (seqv * m).sum(axis=1) / jnp.maximum(m.sum(axis=1), 1.0)

        self._fn = jax.jit(embed)

    # -- the step protocol ---------------------------------------------
    def step_begin(self):
        # step-protocol contract: close the calling thread's open
        # transfer-guard stride window (another engine interleaved on
        # this thread may have armed it — this dispatch legitimately
        # re-opens host->device traffic)
        close_thread_stride_guard()
        if self._inflight:
            return None          # depth 1: the sync IS the result
        if not self.waiting:
            return None
        t0 = time.perf_counter()
        self._programs()
        batch = []
        ids = np.zeros((self.B, self.capacity), np.int32)
        mask = np.zeros((self.B, self.capacity), np.int32)
        row = 0
        while self.waiting and row < self.B:
            req = self.waiting.popleft()
            P = len(req.prompt_ids)
            ids[row, :P] = req.prompt_ids
            mask[row, :P] = 1
            slot = _BSlot(req)
            self.slots[row] = slot
            batch.append((row, slot))
            self.stats["prefill_tokens"] += P
            self.stats["prefill_chunks"] += 1
            row += 1
        self.stats["admit_time_s"] += time.perf_counter() - t0
        t0 = time.perf_counter()
        out = self._fn(self._state_vals, ids, mask)
        dt = time.perf_counter() - t0
        self.stats["dispatch_time_s"] += dt
        self.stats["decode_time_s"] += dt
        self.stats["fused_steps"] += 1
        self._inflight += 1
        return _EmbedPending(out, batch, t0)

    def step_finish(self, pending):
        # as in LLMEngine.step_finish: the readout below must not run
        # inside another engine's disallow window on this thread
        close_thread_stride_guard()
        t0 = time.perf_counter()
        rows = np.asarray(pending.out, np.float32)   # THE sync
        dt = time.perf_counter() - t0
        self.stats["host_sync_time_s"] += dt
        self.stats["decode_time_s"] += dt
        self.stats["steps"] += 1
        self._inflight -= 1
        done = []
        t0 = time.perf_counter()
        for row, slot in pending.batch:
            rid = slot.req.request_id
            if self.slots[row] is not slot or rid in self._cancelled:
                self._cancelled.discard(rid)
                continue         # cancelled mid-flight: row dropped
            out = RequestOutput(rid, [], True, "embed",
                                embedding=rows[row])
            self.finished_outputs[rid] = out
            done.append(out)
            self.slots[row] = None
        self.stats["emit_time_s"] += time.perf_counter() - t0
        return done

    def reset_stats(self):
        for key in self.stats:
            self.stats[key] = 0.0 if key.endswith("_s") else 0
