"""AsyncLLMServer — the production-shaped serving loop over LLMEngine.

Reference analog: the reference's real server is AnalysisPredictor driven
by PaddleNLP's serving stack (SURVEY §1 layer 6c) — request queue in
front, predictor loop behind, per-request streaming out. This module is
that shape on the TPU-native engine, built around the one property a
synchronous ``engine.step()`` loop never exploits: **JAX async dispatch**.

The engine thread runs a PIPELINED loop::

    dispatch step N+1  ──►  device works on N+1
    sync step N's [B] token vector (device→host)   ← overlapped with N+1
    emit tokens / retire / admit (prefill dispatches are async too)

so the host-side readout + request bookkeeping of step N hides under the
device compute of step N+1 (``LLMEngine.step_begin``/``step_finish``;
buffers are donated between steps, the only per-step transfer stays the
sampled-token vector). The paged engine's host block allocator needs each
step's lens before the next dispatch, so it runs the same loop at depth 1.

On top of the loop sit the two serving layers the engine itself does not
provide:

* **request lifecycle** — bounded admission queue with backpressure
  (:class:`~paddle_tpu.serving.scheduler.AdmissionQueue`), per-request
  streaming iterators (:class:`~paddle_tpu.serving.types.RequestHandle`),
  cancellation, and per-request deadlines that free the slot / pool
  blocks at the next step boundary.
* **per-stage telemetry**
  (:class:`~paddle_tpu.profiler.serving_telemetry.ServingTelemetry`) —
  every second of engine-thread wall time lands in a named stage
  (queue_admit / prefill_dispatch / schedule / decode_dispatch /
  host_sync / emit / idle / other), plus TTFT, inter-token, e2e and
  queue-wait histograms, exported as a JSON snapshot and a
  Prometheus-style text dump.
"""
from __future__ import annotations

import collections
import os
import threading
import time

import numpy as np

from ..analysis import lock_watchdog as _lockwatch
from ..inference.llm_engine import PoolCapacityError
from ..profiler.serving_telemetry import ServingTelemetry
from .scheduler import AdmissionQueue
from .types import (RequestHandle, RequestState, ServeRequest, ServeResult,
                    ServerClosed, TraceContext)

__all__ = ["AsyncLLMServer"]


class AsyncLLMServer:
    """Async serving facade over one :class:`LLMEngine`.

    The server OWNS the engine once started: all engine calls happen on
    the background engine thread; callers interact only through
    :meth:`submit` handles. ``pipeline_depth`` None = auto (2 for the
    dense/speculative engines, 1 for paged — see module docstring).

    Usage::

        server = AsyncLLMServer(engine, max_queue_size=64)
        server.start()
        handle = server.submit(prompt_ids, max_new_tokens=64,
                               deadline_s=30.0)
        for tok in handle:          # streams as the engine decodes
            ...
        result = handle.result()    # ServeResult(finish_reason=...)
        server.stop()
    """

    def __init__(self, engine, max_queue_size=64, pipeline_depth=None,
                 poll_interval_s=0.005, telemetry=None,
                 flight_recorder=None, replica=None, supervise=None,
                 step_timeout_s=None, fault_injector=None,
                 shed_deadlines=False, metrics_store=None, slos=None,
                 pathology_detectors=None, metrics_interval_s=0.05,
                 slo_interval_s=0.25, black_box=None,
                 trace_context=True):
        """``flight_recorder``: a
        :class:`~paddle_tpu.profiler.flight_recorder.FlightRecorder`
        instance (or ``True`` for a default-sized one) to attach to the
        engine for the server's lifetime — per-step StepRecords,
        per-request span timelines, chrome-trace export and
        ``explain_tail``. None (the default) records nothing and costs
        one attribute check per step.

        ``replica``: this server's index in a multi-replica cluster
        (:class:`~paddle_tpu.serving.cluster.ReplicaRouter`). Stamped as
        a ``replica`` label on every Prometheus metric line and as the
        process lane of chrome-trace exports, so N replicas' scrapes and
        merged traces never collide. None = single-server (unlabeled).

        ``supervise``: a :class:`~paddle_tpu.serving.RestartPolicy` arms
        SUPERVISED recovery — a serving-loop crash snapshots every
        in-flight request (prompt + tokens already streamed), resets the
        engine (pool/allocator/prefix-store rebuilt, invariants clean),
        and re-admits each one as prompt⊕streamed-tokens so its stream
        CONTINUES token-exactly (greedy always; sampled via the per-
        (request, position) fold_in sampling keys). Restarts are bounded
        with exponential backoff; an exhausted policy fails every waiter
        with ``finish_reason="server_error"`` carrying the partial
        tokens, exactly like the unsupervised (None, default) path.

        ``step_timeout_s``: arms the WATCHDOG — the loop stamps a
        heartbeat every pass (one monotonic read); a watchdog thread
        flips the ``server_healthy`` gauge to 0 (and :meth:`health` to
        ``"hung"``) once the heartbeat goes stale by more than this, and
        interrupts the stuck step where possible (today: an attached
        FaultInjector's interruptible hang; a genuinely wedged device
        call cannot be cancelled — the router fails over around it).
        Set it ABOVE the worst-case legitimate step (first-step compiles
        included) or a cold start reads as a hang. None (default): no
        watchdog thread; :meth:`health` still answers from the
        heartbeat's age when asked.

        ``fault_injector``: a
        :class:`~paddle_tpu.serving.FaultInjector` scripted chaos
        schedule, attached to the engine for the server's lifetime
        (deterministic crash/hang/queue-full tests — never used in
        production serving).

        ``shed_deadlines``: deadline-aware load shedding (OFF by
        default — behavior is bit-identical when False). When on, a
        request whose ``deadline_s`` budget is already below the
        telemetry-estimated queue wait + time-to-first-token is finished
        with ``finish_reason="deadline"`` at submit/admission, BEFORE
        its prefill burns FLOPs a doomed stream can never repay.

        ``metrics_store``: a
        :class:`~paddle_tpu.profiler.metrics_store.MetricsStore` (or
        ``True`` for a default-sized one) — the serve loop feeds every
        gauge and counter into it as monotonic-stamped time series
        (throttled to ``metrics_interval_s``) and the token hot path
        appends per-tenant latency samples, giving windowed
        rate/mean/quantile queries over time. None (the default) costs
        a single detached-attribute check per site — same budget as
        the flight recorder.

        ``slos``: a list of :class:`~paddle_tpu.profiler.slo.SLO`
        objectives — arms the SLO engine (evaluated from the store
        every ``slo_interval_s`` on the loop, and on demand via
        :meth:`slo_report`), maintaining the multi-window burn-rate
        alerts and the ``slo_burn_rate{slo=...}`` /
        ``slo_breached{slo=...}`` gauges. Implies a metrics store.

        ``pathology_detectors``: live pathology detectors subscribed
        to the flight recorder's completed StepRecords (ramp-thrash,
        host-sync regression, spec-acceptance collapse, adapter-swap
        storm, swap-stall — ``explain_tail``'s taxonomy as streaming
        alerts). None (default) arms the standard set when BOTH a
        metrics store and a flight recorder are attached; an explicit
        list overrides; ``False`` disables.

        ``black_box``: a
        :class:`~paddle_tpu.profiler.black_box.BlackBox` (or a
        directory path string, or ``True`` for the default
        ``./debug_bundles``) — arms AUTOMATIC postmortem bundle dumps:
        crash→restart, watchdog hang verdict, and metrics-store alert
        RAISE (edge-triggered per alert instance) each write one
        bounded debug bundle (flight-recorder ring tail, metrics
        series tails, alert log, engine/pool snapshot, worst tail
        gaps). Manual dumps via :meth:`dump_debug_bundle` work with or
        without an armed instance. None (default): no automatic dumps,
        zero hot-path cost."""
        if pipeline_depth is not None and pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, "
                             f"got {pipeline_depth}")
        self.replica = replica
        if flight_recorder is True:
            from ..profiler.flight_recorder import FlightRecorder
            flight_recorder = FlightRecorder(replica=replica)
        if flight_recorder is not None and replica is not None \
                and flight_recorder.replica is None:
            flight_recorder.replica = replica
        self.flight_recorder = flight_recorder
        self.engine = engine
        # the engine knows its own safe depth (see
        # LLMEngine.max_pipeline_depth's contract table): 3 for fused
        # engines (dense, and paged on a full pool — the scheduler
        # mirrors device lens, and the in-flight write fence makes
        # eviction safe), 2 for fused oversubscribed paged and the
        # legacy dense/spec engines, 1 for legacy paged. The DEFAULT
        # stays 2 — the pre-stride contract — so deeper pipelining is
        # an explicit opt-in (pipeline_depth=3); the loop keeps up to
        # depth dispatches in flight before blocking on the oldest sync.
        self.pipeline_depth = min(int(pipeline_depth or 2),
                                  engine.max_pipeline_depth())
        self.poll_interval_s = float(poll_interval_s)
        self.telemetry = telemetry or ServingTelemetry(replica=replica)
        if replica is not None and self.telemetry.replica is None:
            self.telemetry.replica = replica
        self._queue = AdmissionQueue(max_queue_size)
        self._handles: dict[int, RequestHandle] = {}
        # PADDLE_TPU_LOCK_CHECKS=1: acquisition edges feed the PTL004
        # lock-order watchdog (paddle_tpu.analysis.lock_watchdog)
        self._hlock = _lockwatch.tracked(threading.Lock(),
                                         "AsyncLLMServer._hlock")
        self._next_id = 0
        # last engine-stat values the kv_ship telemetry counters have
        # absorbed (see _update_gauges — ship bookings come from two
        # threads, so step-window deltas would miss some)
        self._ship_seen: dict[str, int] = {}
        self._work_evt = threading.Event()
        self._thread = None
        self._accepting = False
        self._stopping = False
        self._crashed = None
        self._saved_callback = None
        self._saved_recorder = None
        # ---- fault tolerance (supervise / watchdog / chaos) ----------
        self.supervise = supervise
        self.step_timeout_s = (float(step_timeout_s)
                               if step_timeout_s is not None else None)
        self.fault_injector = fault_injector
        self.shed_deadlines = bool(shed_deadlines)
        # ---- SLO sensor layer (metrics store / SLOs / detectors) -----
        if metrics_store is True or (slos and not metrics_store):
            from ..profiler.metrics_store import MetricsStore
            metrics_store = MetricsStore()
        # normalize falsy (False, mirroring pathology_detectors=False)
        # to the detached None off-path — `False is not None` would
        # otherwise sail past every off-path check into store calls
        self.metrics_store = metrics_store or None
        self.metrics_interval_s = float(metrics_interval_s)
        self.slo_interval_s = float(slo_interval_s)
        self.slo_engine = None
        if slos:
            from ..profiler.slo import SLOEngine
            self.slo_engine = SLOEngine(slos, self.metrics_store,
                                        telemetry=self.telemetry)
        if pathology_detectors is None and self.metrics_store is not None \
                and self.flight_recorder is not None:
            from ..profiler.slo import default_detectors
            pathology_detectors = default_detectors(self.metrics_store,
                                                    self.telemetry)
        self.pathology_detectors = list(pathology_detectors or ())
        self._ms_last_t = 0.0       # metrics-store feed throttle
        self._slo_last_t = 0.0      # SLO evaluation throttle
        # ---- postmortem black box ------------------------------------
        if black_box:
            from ..profiler.black_box import BlackBox
            if black_box is True:
                black_box = BlackBox()
            elif isinstance(black_box, (str, os.PathLike)):
                black_box = BlackBox(out_dir=black_box)
        self.black_box = black_box or None
        #: mint a TraceContext per submitted request (False exists for
        #: an on/off overhead A/B; caller-supplied contexts
        #: are honored either way)
        self.trace_context = bool(trace_context)
        #: alert instances whose RAISE already triggered a bundle —
        #: (kind, labels, raised_t) identities, so a long-burning alert
        #: dumps once at its raise edge, not once per feed pass
        self._bb_alerts_seen: set = set()
        #: restarts consumed this lifetime (reset by start())
        self.restarts = 0
        self._heartbeat = None      # time.monotonic() of the last loop pass
        self._hung = False          # watchdog verdict (loop pass clears it)
        self._recovering = False    # True between a crash and its re-arm
        self._saved_injector = None
        self._wd_stop = threading.Event()
        self._wd_thread = None

    # -- lifecycle -------------------------------------------------------
    def start(self):
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._saved_callback = self.engine.stream_callback
        self.engine.stream_callback = self._on_token
        if self.flight_recorder is not None:
            self._saved_recorder = self.engine.flight_recorder
            self.engine.flight_recorder = self.flight_recorder
        if self.fault_injector is not None:
            self._saved_injector = self.engine.fault_injector
            self.engine.fault_injector = self.fault_injector
            self.fault_injector._telemetry = self.telemetry
        if self.pathology_detectors and self.flight_recorder is not None:
            for d in self.pathology_detectors:
                # a fresh lifetime evaluates a fresh window: no
                # StepRecords (or active alerts) from a previous run
                d.reset()
                self.flight_recorder.subscribe(d.on_step)
        self._accepting = True
        self._stopping = False
        self._crashed = None  # a restarted server starts clean
        self.restarts = 0
        self._heartbeat = None
        self._hung = False
        self._recovering = False
        self.telemetry.reset()
        self.telemetry.set_gauge(
            "engine_init_time_s",
            self.engine.stats.get("engine_init_time_s", 0.0))
        self._thread = threading.Thread(target=self._loop,
                                        name="paddle-tpu-serving",
                                        daemon=True)
        self._thread.start()
        if self.step_timeout_s is not None:
            self._wd_stop.clear()
            self._wd_thread = threading.Thread(
                target=self._watchdog_loop, name="paddle-tpu-watchdog",
                daemon=True)
            self._wd_thread.start()
        return self

    def stop(self, drain=True, timeout=None):
        """Stop the engine thread. ``drain=True`` serves every accepted
        request to completion first; ``drain=False`` cancels everything
        outstanding.

        A join that times out raises :exc:`TimeoutError` WITHOUT
        detaching anything — the engine thread still owns the engine
        (it may be inside a long compile, an injected hang, or a
        supervised restart's backoff); a second ``stop()`` keeps
        waiting. A supervised restart already in progress when stop()
        lands is allowed to COMPLETE: with ``drain=True`` the resumed
        requests then serve out token-exactly before the loop exits,
        with ``drain=False`` they are cancelled at the first post-
        recovery sweep."""
        if self._thread is None:
            return
        self._accepting = False
        if not drain:
            with self._hlock:
                handles = list(self._handles.values())
            for h in handles:
                h.cancel_requested = True
        self._stopping = True
        self._wake()
        self._thread.join(timeout)
        if self._thread.is_alive():
            # join timed out: the engine thread still owns the engine —
            # do NOT detach it (a restart would race two threads over one
            # engine); the caller can stop() again with a longer timeout
            raise TimeoutError(
                f"serving loop did not stop within {timeout}s (it may be "
                f"inside a long compile); still draining — call stop() "
                f"again to keep waiting")
        self._thread = None
        if self._wd_thread is not None:
            self._wd_stop.set()
            self._wd_thread.join()
            self._wd_thread = None
        self.engine.stream_callback = self._saved_callback
        if self.flight_recorder is not None:
            if self.pathology_detectors:
                for d in self.pathology_detectors:
                    self.flight_recorder.unsubscribe(d.on_step)
            self.engine.flight_recorder = self._saved_recorder
        if self.fault_injector is not None:
            self.engine.fault_injector = self._saved_injector
        if self._crashed is not None:
            raise RuntimeError(
                f"serving loop crashed: {self._crashed}") from self._crashed

    def health(self):
        """Point-in-time health probe — answerable from ANY thread, even
        (especially) while the serve loop is wedged. States:

        * ``"running"`` — loop thread alive and heartbeating: healthy.
        * ``"hung"`` — thread alive but the heartbeat is stale past
          ``step_timeout_s`` (watchdog verdict, or computed right here
          when no watchdog thread runs): the loop is stuck inside a
          step. The replica router fails over on this state while the
          thread still lives.
        * ``"restarting"`` — a supervised recovery is between crash and
          re-arm (backoff/reset/re-admission). The router places nothing
          here but does NOT evict: the resumption is about to happen.
        * ``"crashed"`` — terminal (no policy, or restarts exhausted).
        * ``"stopped"`` — not started, or stopped.

        Only ``"running"`` is healthy."""
        now = time.monotonic()
        thread = self._thread
        alive = thread is not None and thread.is_alive()
        hb = self._heartbeat
        age = (now - hb) if hb is not None else None
        if self._crashed is not None:
            state = "crashed"
        elif not alive:
            state = "stopped"
        elif self._recovering:
            state = "restarting"
        elif self._hung or (self.step_timeout_s is not None
                            and age is not None
                            and age > self.step_timeout_s):
            state = "hung"
        else:
            state = "running"
        return {"state": state, "healthy": state == "running",
                "heartbeat_age_s": age, "restarts": self.restarts,
                "thread_alive": alive}

    def evict_request(self, request_id, reason="evicted"):
        """Force-finish one request from ANY thread, without the engine
        thread's help — the router's hung-replica failover hook. The
        handle detaches immediately (no further tokens can reach it) and
        finishes with ``finish_reason=reason`` carrying every token
        emitted so far. The engine is NOT touched: if the wedged loop
        later revives, the zombie slot decodes to a finish whose output
        is dropped (its handle is gone) and frees its pool blocks
        normally. Returns the detached handle, or None if unknown/done."""
        with self._hlock:
            h = self._handles.pop(request_id, None)
        if h is None or h.done:
            return None
        self._queue.remove(h)
        self._finish_handle(h, h.full_stream(), reason)
        return h

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=exc == (None, None, None))
        return False

    def _wake(self):
        self._work_evt.set()

    # -- submission ------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=64, temperature=0.0,
               top_p=1.0, eos_token_id=None, deadline_s=None, block=True,
               timeout=None, routing=None, resume_tokens=None,
               readout_stride=None, adapter_id=0,
               kind="generate", spec_ewma=None, request_id=None,
               export_kv=False, trace_ctx=None) -> RequestHandle:
        """Submit one generation request; returns its streaming
        :class:`RequestHandle`.

        Backpressure: when the admission queue is at capacity, blocks
        (``block=True``, up to ``timeout`` seconds) or raises
        :class:`ServerQueueFull` immediately. Validation errors (empty or
        over-capacity prompt) raise ValueError synchronously.
        ``deadline_s`` is a relative budget: once exceeded, the request is
        cancelled wherever it is (queued or mid-decode) with
        finish_reason ``"deadline"`` and its slot / pool blocks free at
        the next step boundary.

        ``routing``: opaque metadata dict (a routing key, or the
        ReplicaRouter's placement record). Surfaced verbatim on
        ``ServeResult.routing`` and stamped into the request's trace
        timeline as a ``"routed"`` span, so placement decisions are
        per-request observable (``explain_tail`` carries them on tail
        entries).

        ``resume_tokens``: tokens this request already streamed on a
        PREVIOUS server (the router's ``resume_inflight`` failover):
        admission prefills prompt⊕resume_tokens so the stream continues
        token-exactly — only new tokens stream out of the handle, the
        terminal result carries the full sequence, and they count
        against ``max_new_tokens`` (the ORIGINAL total budget).

        ``readout_stride``: latency-tier pin for multi-step decode —
        ``readout_stride=1`` forces every all-decode step this request
        is resident in to sync the host per step (minimum inter-token
        latency for this stream, at the whole batch's throughput cost).
        None (default) inherits the engine's stride.

        ``adapter_id``: the request's TENANT (batched multi-LoRA) — a
        registered id in the engine's adapter store, 0 = base model.
        ``kind="embed"`` marks the request prefill-only (use
        :meth:`submit_embed`).

        ``spec_ewma``: carried draft-acceptance EWMA for a speculative
        engine's acceptance-adaptive verify-k (the router forwards the
        dead replica's learned value on failover — see
        ``LLMEngine.spec_ewma_for``). None lets the engine learn from
        scratch; inert on non-speculative engines.

        ``request_id``: explicit id override (disaggregated serving: a
        request migrated from a prefill replica must keep ITS id on the
        decode replica — the engine's swap-store restore validates by
        rid, and the per-(rid, position) sampling keys make the sampled
        continuation token-exact only under the same rid). Rejects ids
        this server already tracks; ``_next_id`` stays monotonic past it.

        ``export_kv``: stage this request's committed KV as a shippable
        export entry when it finishes (the router's prefill leg) — see
        ``LLMEngine.export_kv``.

        ``trace_ctx``: the request's distributed
        :class:`~paddle_tpu.serving.types.TraceContext` (or its dict
        form) — supplied by the router (which minted it at fleet entry
        and hop-increments it across ship/failover/retry
        resubmissions); MINTED HERE when absent, so every request has
        one. Stamped on the recorder timeline, carried on the
        ``GenerationRequest``, surfaced on ``ServeResult.trace_ctx``."""
        if self._crashed is not None:
            raise ServerClosed(
                f"serving loop crashed: {self._crashed}") from self._crashed
        if not self._accepting:
            raise ServerClosed("server is not accepting requests")
        eng = self.engine
        ids = np.asarray(
            prompt_ids.numpy() if hasattr(prompt_ids, "numpy")
            else prompt_ids, dtype=np.int32).reshape(-1)
        resume = [int(t) for t in resume_tokens] if resume_tokens else None
        total = len(ids) + len(resume or [])
        # fail fast on the submitter's thread, mirroring add_request's
        # checks (the engine would only see the prompt much later) —
        # tenant/kind first, because the capacity bound depends on the
        # kind (an embed prompt needs NO decode headroom)
        if len(ids) == 0:
            raise ValueError("empty prompt")
        adapter_id = int(adapter_id or 0)
        if adapter_id:
            store = getattr(eng, "adapter_store", None)
            if store is None:
                raise ValueError(
                    f"adapter_id {adapter_id} on an engine without an "
                    f"adapter_store")
            if not store.has(adapter_id):
                raise ValueError(f"unknown adapter_id {adapter_id}")
        if kind not in ("generate", "embed"):
            raise ValueError(f"unknown request kind {kind!r}")
        embed_only = getattr(eng, "embed_only", False)
        if kind == "embed":
            if not embed_only and getattr(eng, "scheduler", "") != "fused":
                raise ValueError(
                    "embedding requests need a fused-scheduler engine "
                    "(or an embed-only encoder engine)")
            max_new_tokens = 0
            cap = eng.capacity if embed_only else eng.capacity - 1
            if total > cap:
                raise ValueError(
                    f"embedding prompt of {total} tokens exceeds the "
                    f"engine capacity ({cap})")
        else:
            if embed_only:
                raise ValueError("this server wraps an embed-only "
                                 "encoder engine — use submit_embed()")
            if total >= eng.capacity - eng.speculative_k:
                raise ValueError(
                    f"prompt of {total} tokens leaves no room to "
                    f"generate (engine capacity {eng.capacity})")
        if eng.cache_impl == "paged" and \
                eng.prefill_blocks_needed(total) > eng.n_blocks:
            raise ValueError(
                f"prompt of {total} tokens cannot prefill into the "
                f"{eng.n_blocks}-block pool")
        with self._hlock:
            if request_id is not None:
                rid = int(request_id)
                if rid in self._handles:
                    raise ValueError(
                        f"request_id {rid} is already tracked by this "
                        f"server")
                self._next_id = max(self._next_id, rid + 1)
            else:
                rid = self._next_id
                self._next_id += 1
        now = time.monotonic()
        if readout_stride is not None and int(readout_stride) < 1:
            raise ValueError(f"readout_stride must be >= 1, got "
                             f"{readout_stride}")
        # the trace context propagation rule: accept the caller's (the
        # router hop-increments across resubmissions), mint at this
        # entry point otherwise — every request has exactly one trace_id
        # from its very first hop
        tc = TraceContext.coerce(trace_ctx)
        if tc is None and self.trace_context:
            tc = TraceContext.mint("submit")
        req = ServeRequest(
            rid, ids, int(max_new_tokens), float(temperature), float(top_p),
            eos_token_id,
            deadline=(now + float(deadline_s)
                      if deadline_s is not None else None),
            submitted_at=now,
            routing=dict(routing) if routing is not None else None,
            resume_tokens=resume,
            readout_stride=(int(readout_stride)
                            if readout_stride is not None else None),
            adapter_id=adapter_id, kind=kind,
            spec_ewma=(float(spec_ewma) if spec_ewma is not None
                       else None),
            export_kv=bool(export_kv), trace_ctx=tc)
        handle = RequestHandle(self, req)
        if kind == "embed":
            self.telemetry.inc("embed_requests")
        rec = self.flight_recorder
        if self.shed_deadlines and deadline_s is not None:
            est = self._admission_estimate_s()
            if float(deadline_s) < est:
                # doomed before its prefill would even start: shed NOW,
                # before it burns FLOPs a dead stream can never repay.
                # Counters stay reconcilable with the admission-side
                # shed (which routes through _finish_handle): every
                # submitted request finishes exactly once.
                self.telemetry.inc("requests_submitted")
                self.telemetry.inc("requests_shed_deadline")
                self.telemetry.inc("requests_finished")
                # per-tenant + store accounting like every other finish
                # path: a tenant whose traffic is being shed must show
                # it in ITS e2e series, not vanish from the report
                shed_e2e = time.monotonic() - now
                self.telemetry.observe("e2e_s", shed_e2e,
                                       tenant=adapter_id)
                if self.metrics_store is not None:
                    self.metrics_store.observe("e2e_s", shed_e2e,
                                               tenant=adapter_id)
                if rec is not None:
                    rec.req_event(rid, "queued")
                    rec.set_trace_ctx(rid, tc)
                    rec.req_event(rid, "finish", value="deadline")
                handle._finish(ServeResult(
                    rid, list(resume or []), "deadline", True,
                    e2e_s=0.0, routing=req.routing, trace_ctx=tc))
                return handle
        with self._hlock:
            self._handles[rid] = handle
        if rec is not None:
            # BEFORE the put: once the handle is in the queue the engine
            # thread may admit it (and emit "admitted"/token events)
            # concurrently — "queued" must already be the timeline head
            rec.req_event(rid, "queued")
            rec.set_trace_ctx(rid, tc)
            if req.routing is not None:
                rec.req_event(rid, "routed", value=dict(req.routing))
        try:
            fi = self.fault_injector
            if fi is not None:
                # injected queue_full bursts ride the SAME rejection
                # bookkeeping as a genuinely full queue
                fi.on_submit(self)
            # the RE-ADMISSION grant: a failover resume (tokens already
            # streamed on a previous replica — possibly restored from
            # its host KV tier) jumps the queue; its consumer is already
            # mid-stream, so queueing it behind fresh arrivals converts
            # a swap-sized stall into a whole queue wait
            self._queue.put(handle, block=block, timeout=timeout,
                            front=resume is not None)
        except Exception:
            with self._hlock:
                self._handles.pop(rid, None)
            self.telemetry.inc("requests_rejected_queue_full")
            if rec is not None:   # terminal: the timeline must not leak
                rec.req_event(rid, "finish", value="rejected_queue_full")
            raise
        if self._stopping or self._crashed is not None:
            # TOCTOU with stop(): the loop may have taken its final exit
            # look at the queue before our put landed — undo (unless the
            # loop already picked the handle up, in which case it's safe)
            if self._queue.remove(handle):
                with self._hlock:
                    self._handles.pop(rid, None)
                if rec is not None:
                    rec.req_event(rid, "finish", value="server_stopped")
                raise ServerClosed("server stopped while submitting")
        self.telemetry.inc("requests_submitted")
        self._wake()
        return handle

    def submit_embed(self, prompt_ids, adapter_id=0, deadline_s=None,
                     block=True, timeout=None,
                     routing=None) -> RequestHandle:
        """Submit one PREFILL-ONLY embedding request: no decode tokens,
        no sampling — the prompt's prefill chunks batch into the same
        fused mixed steps as generation traffic, and the terminal
        :class:`ServeResult` carries the mean-pooled final hidden state
        in ``embedding`` (handed back on the prefill sync). Works on a
        fused-scheduler :class:`~paddle_tpu.inference.LLMEngine` (llama
        pooling, optionally per-tenant via ``adapter_id``) and on an
        embed-only encoder engine
        (:class:`~paddle_tpu.serving.embedding.BertEmbedEngine`)."""
        return self.submit(prompt_ids, adapter_id=adapter_id,
                           deadline_s=deadline_s, block=block,
                           timeout=timeout, routing=routing, kind="embed")

    def num_outstanding(self):
        with self._hlock:
            return len(self._handles)

    # -- engine thread ---------------------------------------------------
    def _loop(self):
        """The engine thread's outer SUPERVISOR: run the serve loop; on a
        crash, either recover (``supervise=RestartPolicy``: snapshot
        in-flight requests, reset the engine, re-admit each as
        prompt⊕streamed-tokens and keep serving — token-exact via the
        per-(rid, position) sampling keys) or fail terminally (every
        waiter gets ``finish_reason="server_error"`` carrying its partial
        tokens)."""
        while True:
            try:
                self._serve_loop()
                # clean exit: a stopped replica must not keep scraping
                # as healthy (health() already answers "stopped")
                self.telemetry.set_gauge("server_healthy", 0.0)
                return
            except BaseException as e:
                if not self._recover(e):
                    return

    def _serve_loop(self):
        # the in-flight dispatch window, oldest first: up to
        # pipeline_depth step_begin()s run ahead of the oldest sync
        # (depth 2 reproduces the pre-deque loop's exact call sequence:
        # begin, begin, finish | begin, finish | ...)
        pending = collections.deque()
        while True:
            # one loop iteration: in a profile the parent of every other
            # pt:server.* span and, through them, of the engine's; in the
            # attribution its self time, the loop's own glue between the
            # stages below, is "other"
            with self.telemetry.stage("other", "pass"):
                if self._serve_pass(pending):
                    return

    def _serve_pass(self, pending):
        """One iteration of the serve loop over the in-flight window
        ``pending``; True when the loop is to end."""
        tel = self.telemetry
        # the watchdog heartbeat: ONE monotonic read per pass (the
        # whole supervision-off/on overhead budget rides on this
        # line staying this cheap)
        self._heartbeat = time.monotonic()
        self._hung = False
        # "other" covers the loop's own bookkeeping (cancel/
        # deadline sweeps, finish routing, gauge sampling) so the
        # attribution explains the busy wall to >= 0.9, not ~0.7
        with tel.stage("other"):
            self._sweep_cancels_and_deadlines()
            self._update_gauges()
        with tel.stage("queue_admit"):
            self._feed_engine()
            self._mark_admission_stalls()
        # THE pipelined-dispatch move: fill the in-flight window
        # before blocking on the oldest step's token transfer
        while len(pending) < self.pipeline_depth:
            try:
                nxt = self._begin_step()
            except PoolCapacityError as e:
                # exactly the head-request-can-never-admit signal
                # (its prompt outgrew the paged pool): fail THAT
                # request, not the server. Any other error (device,
                # compile) falls to the supervisor.
                self._fail_head_waiting(e)
                break
            if nxt is None:
                break
            pending.append(nxt)
        if not pending:
            if self._stopping and not self.num_outstanding() \
                    and len(self._queue) == 0:
                return True
            with tel.stage("idle"):
                self._work_evt.wait(self.poll_interval_s)
                self._work_evt.clear()
            return False
        self._finish_step(pending.popleft())
        return False

    def _recover(self, exc):
        """Crash handler. Returns True when the serve loop should
        re-enter (supervised restart armed and within budget), False when
        the crash is terminal (every waiter failed attributably)."""
        tel = self.telemetry
        tel.set_gauge("server_healthy", 0.0)
        rec = self.flight_recorder
        pol = self.supervise
        # postmortem black box: capture the crash-time state BEFORE any
        # recovery path resets the engine (the bundle is the last look
        # at what the loop died holding)
        self._black_box_dump("crash", detail=str(exc))
        if pol is None or self.restarts >= pol.max_restarts:
            # terminal: fail every waiter, don't hang them — each result
            # carries the tokens its stream already received (resume
            # prefix from a previous replica included). ORDER matters:
            # _crashed/_accepting flip BEFORE the atomic snapshot+clear,
            # so a racing submit() either sees the flags and raises
            # ServerClosed or lands in the snapshot and gets failed —
            # never a handle nobody will ever finish.
            self._crashed = exc
            self._accepting = False  # submit() must not feed a dead loop
            with self._hlock:
                handles = list(self._handles.values())
                self._handles.clear()
            self._queue.drain()
            for h in handles:
                if h.done:
                    continue
                if rec is not None:
                    rec.req_event(h.request_id, "crashed", value=str(exc))
                h._finish(ServeResult(
                    h.request_id, h.full_stream(),
                    f"server_error: {exc}", True,
                    routing=h.request.routing,
                    trace_ctx=h.request.trace_ctx))
            return False
        # ---- supervised restart --------------------------------------
        with self._hlock:
            handles = [h for h in self._handles.values() if not h.done]
        self._recovering = True
        self.restarts += 1
        tel.inc("engine_restarts")
        resident = [h for h in handles
                    if h.state in (RequestState.PENDING,
                                   RequestState.RUNNING)]
        if rec is not None:
            for h in resident:
                rec.req_event(h.request_id, "crashed", value=str(exc))
        # a crash LOOP must not spin the engine thread
        time.sleep(self.supervise.delay(self.restarts))
        try:
            self.engine.reset()
        except BaseException as reset_exc:  # engine unrecoverable
            self._recovering = False
            self.supervise = None   # force the terminal path
            return self._recover(reset_exc)
        # re-admit every engine-resident request as prompt⊕streamed so
        # its stream CONTINUES (oldest first — the original admission
        # order, so slot/pool layout replays deterministically)
        for h in sorted(resident, key=lambda h: h.request.request_id):
            committed = h.full_stream()
            if self._readmit(h, committed):
                tel.inc("requests_resumed")
                if rec is not None:
                    rec.req_event(h.request_id, "resumed",
                                  value=len(committed))
        self._recovering = False
        self._wake()
        return True

    def _readmit(self, handle, committed):
        """Hand one request to the engine as prompt⊕``committed``
        (tokens it already streamed in a previous life — a supervised
        restart's snapshot, or a failover resume prefix; empty for a
        fresh admission). THE one copy of the re-admission edge cases:
        a stream that already emitted its eos token finishes ``"eos"``
        right here (re-prefilling it would decode PAST the eos — the
        crash merely beat the finished output's routing), an exhausted
        budget finishes ``"length"``, and an engine validation error
        finishes ``"rejected"`` on the `requests_rejected_validation`
        counter. Returns True when the request entered the engine."""
        req = handle.request
        eng = self.engine
        eos = req.eos_token_id
        if committed and eos is not None and committed[-1] == eos:
            self._finish_handle(handle, committed, "eos")
            return False
        remaining = req.max_new_tokens - len(committed)
        if committed and remaining <= 0:
            self._finish_handle(handle, committed, "length")
            return False
        if committed and len(req.prompt_ids) + len(committed) >= \
                eng.capacity - eng.speculative_k:
            # the stream GREW to the engine's buffer edge before the
            # crash/failover: the uninterrupted run would have retired
            # it "capacity" — re-prefilling would only trip add_request
            # validation and mislabel a complete stream as rejected
            self._finish_handle(handle, committed, "capacity")
            return False
        try:
            self.engine.add_request(
                req.prompt_ids, max_new_tokens=remaining,
                temperature=req.temperature, top_p=req.top_p,
                eos_token_id=eos, request_id=req.request_id,
                committed_tokens=committed or None,
                readout_stride=req.readout_stride,
                adapter_id=req.adapter_id, kind=req.kind,
                spec_ewma=req.spec_ewma,
                export_kv=getattr(req, "export_kv", False),
                trace_ctx=req.trace_ctx)
        except ValueError as e:
            # the rejection must be visible in telemetry, not just on
            # the handle — a silent validation drop looks like a lost
            # request to a dashboard
            self.telemetry.inc("requests_rejected_validation")
            self._finish_handle(handle, committed, f"rejected: {e}")
            return False
        handle.state = RequestState.PENDING
        return True

    def _watchdog_loop(self):
        """Stale-heartbeat monitor (armed by ``step_timeout_s``). Flips
        the ``server_healthy`` gauge and the :meth:`health` verdict to
        hung, and interrupts the stuck step where the runtime allows it —
        today that means an attached FaultInjector's interruptible hang
        (the scripted stand-in for a cancellable device call); a
        genuinely wedged dispatch cannot be cancelled from outside, the
        router fails over around it instead."""
        period = min(self.step_timeout_s / 4.0, 0.05)
        while not self._wd_stop.wait(period):
            hb = self._heartbeat
            thread = self._thread
            if (hb is None or self._recovering or self._crashed is not None
                    or thread is None or not thread.is_alive()):
                continue
            if time.monotonic() - hb > self.step_timeout_s \
                    and not self._hung:
                self._hung = True
                self.telemetry.set_gauge("server_healthy", 0.0)
                # the hang VERDICT edge (the loop pass clears _hung, so
                # a re-wedged loop re-triggers) — dump the black box
                # from THIS thread: the wedged loop can't
                self._black_box_dump(
                    "hang",
                    detail=f"heartbeat stale > {self.step_timeout_s}s")
                fi = self.fault_injector
                if fi is not None and fi.hanging:
                    fi.interrupt()

    def _fail_head_waiting(self, err):
        eng = self.engine
        if not eng.waiting:
            raise err  # not a head-of-queue admission failure: re-raise
        req = eng.waiting.popleft()
        # a preemption-grown request may have committed (and streamed)
        # tokens before being parked: _finish_tokens stitches them in AND
        # pops the engine's _preempted_prefix entry (leak otherwise)
        tokens = eng._finish_tokens(req, [])
        with self._hlock:
            h = self._handles.get(req.request_id)
        if h is not None:
            self._finish_handle(h, tokens, f"rejected: {err}")

    def _begin_step(self):
        """engine.step_begin() with its wall split into the prefill
        (admission) dispatch, the decode dispatch, and the host scheduling
        remainder — read back from the engine's own stage stats so the
        attribution can't drift from what the engine measured."""
        eng, tel = self.engine, self.telemetry
        s_admit = eng.stats["admit_time_s"]
        s_disp = eng.stats["dispatch_time_s"]
        s_pre = eng.stats["preemptions"]
        s_ptok = eng.stats["prefill_tokens"]
        s_multi = eng.stats["multi_steps"]
        s_pfx = {k: eng.stats[k] for k in ("sampling_steps",
                                           "prefix_hit_tokens",
                                           "prefix_cow_blocks",
                                           "prefix_evicted_blocks",
                                           "adapter_cache_hits",
                                           "adapter_cache_misses",
                                           "adapter_swaps",
                                           "kv_swap_out_blocks",
                                           "kv_swap_in_blocks",
                                           "kv_swap_saved_tokens",
                                           "kv_spill_blocks",
                                           "kv_promote_blocks")}
        with tel.stage("schedule", "begin") as booked:
            pending = eng.step_begin()
            d_admit = eng.stats["admit_time_s"] - s_admit
            d_disp = eng.stats["dispatch_time_s"] - s_disp
            booked[0] += d_admit + d_disp
        d_ptok = eng.stats["prefill_tokens"] - s_ptok
        tel.add_stage("prefill_dispatch", d_admit)
        tel.add_stage("decode_dispatch", d_disp)
        if d_ptok:
            tel.inc("prefill_tokens", d_ptok)
        for key, before in s_pfx.items():
            # prefix-cache activity (hits at admission, COW clones, LRU
            # evictions), adapter-cache activity (hit/miss/swap at
            # admission) AND a dispatch with a sampling row all happen
            # inside step_begin — the deltas land on the matching
            # telemetry counters
            if eng.stats[key] > before:
                tel.inc(key, eng.stats[key] - before)
        if eng.stats["preemptions"] > s_pre:
            # pool-pressure preemptions happen inside step_begin's
            # allocator loop — this is where the delta is visible
            tel.inc("preemptions", eng.stats["preemptions"] - s_pre)
        if eng.stats["multi_steps"] > s_multi:
            tel.inc("multi_steps", eng.stats["multi_steps"] - s_multi)
        if d_admit > 0.0:
            self._note_admissions()
        return pending

    def _finish_step(self, pending):
        """engine.step_finish() with its wall split into the device→host
        token sync and the readout/emit remainder, then the routing of
        what finished (the remainder's "other")."""
        eng, tel = self.engine, self.telemetry
        s_sync = eng.stats["host_sync_time_s"]
        s_emit = eng.stats["emit_time_s"]
        # speculative acceptance accounting lands at READOUT (this is
        # where the engine learns which drafts committed)
        s_spec = {k: eng.stats[k] for k in ("spec_proposed_tokens",
                                            "spec_accepted_tokens")}
        with tel.stage("other", "finish") as booked:
            done = eng.step_finish(pending)
            # the step's device buffers go HERE, before anyone hears of
            # its results: freeing them lets other threads run, and a
            # client woken first would find the engine still mid-step
            del pending
            d_sync = eng.stats["host_sync_time_s"] - s_sync
            d_emit = eng.stats["emit_time_s"] - s_emit
            booked[0] += d_sync + d_emit
            tel.add_stage("host_sync", d_sync)
            tel.add_stage("emit", d_emit)
            tel.inc("engine_steps")
            for key, before in s_spec.items():
                if eng.stats[key] > before:
                    tel.inc(key, eng.stats[key] - before)
            if done:
                self._handle_done(done)

    def _admission_estimate_s(self):
        """Telemetry-estimated latency a fresh submission pays before its
        first token: observed mean queue wait + mean TTFT. 0.0 on a cold
        server (no observations yet) — deadline shedding never fires
        before the estimator has data, so a cold start sheds nothing."""
        tel = self.telemetry
        return tel.queue_wait_s.mean + tel.ttft_s.mean

    def _feed_engine(self):
        """Move queued requests into the engine's waiting deque — only as
        many as could plausibly admit (engine backlog stays ≤ max_batch)
        so queue-wait is measured HERE and cancellation of queued
        requests never has to dig through engine state."""
        eng, tel = self.engine, self.telemetry
        while len(eng.waiting) < eng.B:
            handle = self._queue.pop()
            if handle is None:
                return
            if handle.done:          # cancelled/expired while queued
                continue
            req = handle.request
            resume = list(req.resume_tokens or [])
            if self.shed_deadlines and req.deadline is not None:
                # admission-side shed: the queue wait is already paid,
                # so the bar is the remaining budget vs estimated TTFT
                if req.deadline - time.monotonic() < tel.ttft_s.mean:
                    tel.inc("requests_shed_deadline")
                    self._finish_handle(handle, resume, "deadline")
                    continue
            self._readmit(handle, resume)

    def _update_gauges(self):
        """Sample the point-in-time engine state into the telemetry
        gauges — the Prometheus view of what the flight recorder stamps
        per step. One pass is a handful of O(B) reads; it runs every
        loop iteration so the gauges stay fresh even while idle."""
        eng, tel = self.engine, self.telemetry
        # the loop is provably passing right now — that IS healthy (a
        # watchdog hang verdict or a crash flips it to 0 from outside)
        tel.set_gauge("server_healthy", 1.0)
        tel.set_gauge("queue_depth", len(self._queue))
        tel.set_gauge("engine_waiting", len(eng.waiting))
        tel.set_gauge("running_slots",
                      sum(1 for s in eng.slots if s is not None))
        tel.set_gauge("pipeline_inflight", eng._inflight)
        if eng.cache_impl == "paged":
            free = len(eng._free_blocks)
            tel.set_gauge("kv_pool_free_blocks", free)
            tel.set_gauge("kv_pool_occupancy",
                          1.0 - free / max(eng.n_blocks, 1))
            tel.set_gauge("kv_pool_effective_blocks",
                          eng.kv_pool_effective_blocks())
            # host KV tier traffic (0 with the tier off — the gauges
            # sample the cumulative engine stats, so one scrape shows
            # whether preemptions are converting into copies)
            tel.set_gauge("kv_swap_in_bytes",
                          eng.stats.get("kv_swap_in_bytes", 0))
            tel.set_gauge("kv_swap_out_bytes",
                          eng.stats.get("kv_swap_out_bytes", 0))
            tel.set_gauge("kv_host_spill_blocks",
                          len(getattr(eng, "_spill", ())))
            # the spill store's bound is set in BYTES (kv_host_spill_bytes
            # engine arg) — report occupancy in the bound's own unit too
            tel.set_gauge("kv_host_spill_bytes",
                          getattr(eng, "_spill_bytes", 0))
            # cross-replica ship counters book from BOTH the engine
            # thread (finish-site export, restore import) and the router
            # thread (pull-on-miss peer export) — delta-sync them here,
            # outside any step window, so no booking site is missed
            for key in ("kv_ship_out_blocks", "kv_ship_in_blocks",
                        "kv_ship_out_bytes", "kv_ship_in_bytes"):
                cur = eng.stats.get(key, 0)
                d = cur - self._ship_seen.get(key, 0)
                if d > 0:
                    tel.inc(key, d)
                    self._ship_seen[key] = cur
            if eng.prefix_cache:
                tel.set_gauge("prefix_cached_blocks", len(eng._lru))
                hit = eng.stats["prefix_hit_tokens"]
                pre = eng.stats["prefill_tokens"]
                tel.set_gauge("prefix_cache_hit_rate",
                              hit / (hit + pre) if hit + pre else 0.0)
        cache = getattr(eng, "adapter_cache", None)
        if cache is not None:
            tel.set_gauge("adapter_cache_occupancy", cache.occupancy())
        prop = eng.stats.get("spec_proposed_tokens", 0)
        if prop:
            tel.set_gauge("spec_acceptance_rate",
                          eng.stats["spec_accepted_tokens"] / prop)
        rec = self.flight_recorder
        if rec is not None and rec.enabled:
            last = rec.last_record()
            if last is not None:
                tel.set_gauge("token_budget_utilization",
                              last.budget_utilization)
        # the serve loop provably sampled the gauges this pass: stamp
        # it — gauge_last_sample_age_s ages from HERE (the watchdog's
        # out-of-loop writes deliberately do not refresh it)
        tel.mark_gauge_sample()
        # SLO sensor layer: the off path is this one attribute check
        if self.metrics_store is not None:
            self._feed_sensors()

    def _feed_sensors(self):
        """Feed EVERY gauge and cumulative counter into the metrics
        store as time series (counters stay cumulative — windowed
        ``store.rate()`` turns the deltas into tokens/s,
        preemptions/s, ...) and run the throttled SLO evaluation.
        Called once per loop pass (only with a store attached); both
        halves are interval-gated so a hot loop costs two monotonic
        reads per pass, not a store write per gauge."""
        now = time.monotonic()
        store = self.metrics_store
        if now - self._ms_last_t >= self.metrics_interval_s:
            self._ms_last_t = now
            for name, v in self.telemetry.get_gauges().items():
                if name != "gauge_last_sample_age_s":
                    # the staleness gauge is computed at READ time —
                    # storing the feed-time value would record the
                    # sensor's own cadence, not the loop's health
                    store.observe(name, v, t=now)
            for name, v in self.telemetry.get_counters().items():
                store.observe(name, v, t=now)
        if self.slo_engine is not None \
                and now - self._slo_last_t >= self.slo_interval_s:
            self._slo_last_t = now
            self.slo_engine.evaluate(now=now)
        if self.black_box is not None:
            # alert RAISE edges (burn-rate alerts from the SLO engine,
            # pathology detectors' raises): each alert INSTANCE —
            # identified by (kind, labels, raised_t) — dumps exactly one
            # bundle, at the first feed pass that sees it active
            for a in store.alerts(active_only=True):
                key = (a.kind,
                       tuple(sorted((str(k), str(v))
                                    for k, v in a.labels.items())),
                       round(a.raised_t, 6))
                if key not in self._bb_alerts_seen:
                    self._bb_alerts_seen.add(key)
                    self._black_box_dump(
                        "burn_alert", detail=f"{a.kind}: {a.message}")

    def _black_box_dump(self, reason, detail=None):
        """Best-effort AUTOMATIC bundle dump (crash / hang / alert
        edges). Never raises into the serving loop or the watchdog —
        postmortem capture must not be able to make the incident
        worse. No-op without an armed ``black_box``."""
        bb = self.black_box
        if bb is None:
            return None
        try:
            return bb.dump(reason, server=self, detail=detail)
        except Exception:
            return None

    def dump_debug_bundle(self, path, reason="manual", detail=None):
        """Write one bounded postmortem debug bundle for THIS server to
        ``path`` (JSON: flight-recorder ring tail + worst tail gaps,
        metrics-store series tails + alert log, engine config/pool/
        kv-tier snapshot, health/restart state, injected-fault record).
        Works from ANY thread, with or without an armed ``black_box``
        (manual dumps don't dedup or rotate). Read it back with
        ``python -m paddle_tpu.profiler.bundle <path>``."""
        from ..profiler.black_box import collect_bundle, write_bundle
        return write_bundle(
            collect_bundle(server=self, reason=reason, detail=detail),
            path)

    def slo_report(self):
        """Point-in-time SLO/sensor report — answerable from ANY
        thread: per-SLO burn-rate evaluations (fresh, not the loop's
        last throttled pass), the store's alert log, each pathology
        detector's active flag, and the per-tenant latency snapshot.
        ``text`` carries the human rendering. Works (degenerately) with
        no store attached — empty slos/alerts, but tenant latency
        still reports."""
        from ..profiler.slo import format_slo_report
        store = self.metrics_store
        out = {
            "replica": self.replica,
            "slos": (self.slo_engine.evaluate()
                     if self.slo_engine is not None else []),
            "alerts": ([a.to_dict() for a in store.alerts()]
                       if store is not None else []),
            "pathologies": {d.kind: d.active
                            for d in self.pathology_detectors},
            "tenant_latency": self.telemetry.tenant_latency_snapshot(),
            "gauge_last_sample_age_s":
                self.telemetry.get_gauges()["gauge_last_sample_age_s"],
        }
        out["text"] = format_slo_report(out)
        return out

    def _note_admissions(self):
        """Mark handles whose request just entered an engine slot as
        RUNNING and record their queue wait (submit → slot admission)
        plus the admission stall (first-free-slot → slot admission)."""
        now = time.monotonic()
        with self._hlock:
            handles = dict(self._handles)
        for slot in self.engine.slots:
            if slot is None:
                continue
            h = handles.get(slot.req.request_id)
            if h is not None and h.state is RequestState.PENDING:
                h.state = RequestState.RUNNING
                h.admitted_at = now
                wait = now - h.request.submitted_at
                if self.flight_recorder is not None:
                    self.flight_recorder.req_event(
                        slot.req.request_id, "admitted")
                self.telemetry.inc("requests_admitted")
                self.telemetry.observe("queue_wait_s", wait,
                                       tenant=h.request.adapter_id)
                if self.metrics_store is not None:
                    self.metrics_store.observe(
                        "queue_wait_s", wait, t=now,
                        tenant=h.request.adapter_id)
                self.telemetry.observe(
                    "admission_stall_s",
                    max(now - h.stall_mark, 0.0)
                    if h.stall_mark is not None else 0.0)

    def _mark_admission_stalls(self):
        """Stamp the moment a FREE slot exists for a request that could
        take it; _note_admissions turns the stamp into the
        admission_stall_s observation. Only as many of the OLDEST pending
        requests as there are free slots carry a stamp — the rest are
        waiting on CAPACITY, not on admission, and their marks clear (a
        stamped-then-refilled slot must not convert a capacity wait into
        a reported stall). Under the legacy scheduler the stall covers
        whole admission prefill trains and step horizons; the fused
        scheduler admits on the next loop pass (~0)."""
        eng = self.engine
        free = sum(1 for s in eng.slots if s is None)
        now = time.monotonic()
        with self._hlock:
            handles = list(self._handles.values())
        pending = sorted((h for h in handles
                          if h.state is RequestState.PENDING),
                         key=lambda h: h.request.submitted_at)
        # legacy paged admission also needs POOL blocks for the whole
        # prompt — a free slot over a dry pool is still a capacity wait,
        # not an admission stall (fused admission allocates lazily, so a
        # free slot alone is admissible there). The fused scheduler's
        # admission-defer progress guarantee is mirrored the same way:
        # while a resident slot is still RAMPING, a prompt the pool
        # cannot cover waits on capacity, not on admission.
        paged = eng.cache_impl == "paged"
        legacy_paged = paged and eng.scheduler != "fused"
        fused_ramping = paged and not legacy_paged and any(
            s is not None and s.ramping for s in eng.slots)
        # the fused defer also counts the resident ramps' OUTSTANDING
        # block demand (the engine's exact predicate) — mirroring only
        # the new prompt's need would stamp deferred requests as
        # admission stalls, the precise misclassification this mark
        # discipline exists to avoid
        ramp_deficit = sum(
            max(eng.prefill_blocks_needed(s.prompt_len)
                - len(eng._slot_blocks[i]), 0)
            for i, s in enumerate(eng.slots)
            if s is not None and s.ramping) if fused_ramping else 0
        for i, h in enumerate(pending):
            admissible = i < free and (
                not (legacy_paged or fused_ramping)
                or eng.prefill_blocks_needed(len(h.request.prompt_ids))
                + ramp_deficit <= eng._n_allocatable())
            if admissible:
                if h.stall_mark is None:
                    h.stall_mark = now
            else:
                h.stall_mark = None

    def _sweep_cancels_and_deadlines(self):
        """Apply caller cancellations and expire deadlines. A running
        request's slot (and paged pool blocks) frees RIGHT HERE —
        before the next dispatch — so capacity returns to the pool
        immediately, not after the stream drains."""
        eng = self.engine
        now = time.monotonic()
        with self._hlock:
            items = list(self._handles.items())
        for rid, h in items:
            if h.done:
                continue
            expired = h.request.deadline is not None \
                and now > h.request.deadline
            if not h.cancel_requested and not expired:
                continue
            reason = "cancelled" if h.cancel_requested else "deadline"
            # a still-queued handle has generated nothing HERE, but a
            # failover resume carries its previous replica's tokens
            tokens = list(h.request.resume_tokens or [])
            if h.state is RequestState.QUEUED:
                self._queue.remove(h)
            else:
                out = eng.cancel(rid, reason=reason)
                if out is not None:
                    eng.finished_outputs.pop(rid, None)
                    tokens = out.token_ids
            self.telemetry.inc("requests_expired" if reason == "deadline"
                               else "requests_cancelled")
            self._finish_handle(h, tokens, reason)

    def _on_token(self, rid, tok):
        """Engine stream callback (fires inside step_finish's readout):
        route the token to its handle and record TTFT / inter-token.
        The stamp is BACKDATED by the engine's ``emit_backdate_s`` — a
        k-step batched readout drains k tokens in one sync, but each
        was produced at its own device step boundary, so histograms see
        k amortized gaps instead of k-1 zeros and one stride-wide
        spike. Clamped monotonic per handle (pipelined strides can
        backdate into the previous readout's window)."""
        with self._hlock:
            h = self._handles.get(rid)
        if h is None:
            return
        now = time.monotonic() - self.engine.emit_backdate_s
        if h.last_token_at is not None and now < h.last_token_at:
            now = h.last_token_at
        tenant = h.request.adapter_id
        store = self.metrics_store
        if h.first_token_at is None:
            ttft = max(now - h.request.submitted_at, 0.0)
            self.telemetry.observe("ttft_s", ttft, tenant=tenant)
            if store is not None:
                store.observe("ttft_s", ttft, t=now, tenant=tenant)
        elif h.last_token_at is not None:
            gap = now - h.last_token_at
            self.telemetry.observe("inter_token_s", gap, tenant=tenant)
            if store is not None:
                store.observe("inter_token_s", gap, t=now, tenant=tenant)
        self.telemetry.inc("tokens_emitted")
        self.telemetry.inc_tenant(tenant)
        h._emit(tok, t=now)

    def _handle_done(self, outputs):
        for out in outputs:
            self.engine.finished_outputs.pop(out.request_id, None)
            with self._hlock:
                h = self._handles.get(out.request_id)
            if h is None:
                continue
            emb = getattr(out, "embedding", None)
            if emb is not None:
                # per-tenant accounting: an embed request's processed
                # tokens are its pooled prompt positions
                self.telemetry.inc_tenant(h.request.adapter_id,
                                          len(h.request.prompt_ids))
            self._finish_handle(h, out.token_ids, out.finish_reason,
                                embedding=emb)

    def _finish_handle(self, handle, token_ids, reason, embedding=None):
        now = time.monotonic()
        req = handle.request
        trace = None
        rec = self.flight_recorder
        if rec is not None and rec.enabled:
            rec.req_event(handle.request_id, "finish", value=reason)
            trace = rec.request_trace(handle.request_id)
        result = ServeResult(
            handle.request_id, list(token_ids), reason, True,
            ttft_s=(handle.first_token_at - req.submitted_at
                    if handle.first_token_at is not None else None),
            e2e_s=now - req.submitted_at,
            queue_wait_s=(handle.admitted_at - req.submitted_at
                          if handle.admitted_at is not None else None),
            trace=trace, routing=req.routing, embedding=embedding,
            trace_ctx=req.trace_ctx)
        self.telemetry.inc("requests_finished")
        self.telemetry.observe("e2e_s", result.e2e_s,
                               tenant=req.adapter_id)
        if self.metrics_store is not None:
            self.metrics_store.observe("e2e_s", result.e2e_s, t=now,
                                       tenant=req.adapter_id)
        with self._hlock:
            self._handles.pop(handle.request_id, None)
        handle._finish(result)
