"""Multichip serving — the two-level cluster subsystem.

Reference analog: the reference's fleet/auto_parallel orchestration layer
(PAPER.md §1, layer 6a) over its AnalysisPredictor serving front-end
(layer 6c): capacity scales with CHIPS, not with engine slots. Two
independent levels compose:

* **Level 1 — tensor parallelism** (:func:`tp_engine`): one
  :class:`~paddle_tpu.inference.LLMEngine` whose weights AND paged KV
  pools shard across a ``("tp",)`` mesh axis. kv-heads are the natural
  shard dim — the Pallas paged-attention grid is ``(batch, kv_head,
  max_blocks)``, so each shard keeps its own physical pool slice and the
  per-shard kernel is byte-identical to the single-chip one at
  ``Hkv/ntp`` heads (``paged_attention_decode_tp`` /
  ``paged_attention_append_tp`` shard_map it; the CPU dense fallback
  partitions under GSPMD). Block tables, the allocator, and the prefix
  cache's content hashing stay HOST-GLOBAL and TP-oblivious; the
  vocab-sharded lm head all-gathers into the replicated carried logits
  exactly once per step. Greedy output is token-exact vs the single-chip
  engine.
* **Level 2 — data parallelism** (:class:`ReplicaRouter`): N
  :class:`~paddle_tpu.serving.AsyncLLMServer` replicas (each possibly a
  TP engine) behind one router that places every request by a score
  combining **load** (queue depth + running slots + KV-pool occupancy,
  read from each replica's existing Prometheus gauges) and **prefix
  affinity** (a read-only probe of each replica's content-hash store for
  the longest cached prefix of the incoming prompt — the replica that
  already holds the system prompt serves it with zero prefill FLOPs for
  the shared span). Placement falls back to least-loaded when nothing
  hits. Failover: a dead replica's QUEUED requests (nothing streamed
  yet) resubmit transparently to survivors; its IN-FLIGHT requests
  (tokens already streamed) fail with the attributable
  ``finish_reason="replica_lost"``. :meth:`ReplicaRouter.drain` removes
  a replica gracefully (migrate queued, finish running, stop).

Everything is testable end-to-end on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the
``tests/conftest.py`` virtual-mesh pattern).

Scoring formula (documented contract, see docs/architecture.md)::

    score(replica) = affinity_weight * hit_tokens / prompt_len
                   - load_weight * ((queue_depth + engine_waiting
                                     + running_slots) / max_batch
                                    + kv_pool_occupancy)

highest score wins; ties break toward the lower replica index.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time

import numpy as np

from ..analysis import lock_watchdog as _lockwatch
from .types import (ServeResult, ServerClosed, ServerQueueFull,
                    TraceContext)

__all__ = ["ReplicaRouter", "RouterHandle", "tp_serving_mesh",
           "shard_model_tp", "tp_engine", "FLEET_TAIL_CAUSES"]

#: every cause :meth:`ReplicaRouter.explain_tail` can name BEYOND the
#: per-replica :data:`~paddle_tpu.profiler.flight_recorder.TAIL_CAUSES`
#: taxonomy: a cross-replica boundary gap is either the migration
#: itself (``kv_ship:{phase}``, phase the dominant entry of
#: ``kv_transport.MIGRATION_PHASES`` — kept in lockstep by test +
#: PTL008) or a failover resubmission's re-prefill window. STRICT
#: registry, like TAIL_CAUSES/ALERT_KINDS.
FLEET_TAIL_CAUSES = ("failover_resubmit", "kv_ship:serialize",
                     "kv_ship:transport", "kv_ship:import",
                     "kv_ship:place", "kv_ship:stitch")


# ---------------------------------------------------------------------------
# Level 1 — tensor-parallel engine construction
# ---------------------------------------------------------------------------

def tp_serving_mesh(tp=None, devices=None):
    """A ``("tp",)`` jax Mesh over ``tp`` devices (default: all local
    devices). The axis NAME is the contract: ``LLMEngine(mesh=...)``
    shards its KV buffers iff the mesh carries a ``"tp"`` axis of size
    > 1 (any other mesh keeps the legacy replicated-buffer behavior)."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if tp is not None:
        if len(devices) < tp:
            raise ValueError(f"need {tp} devices for tp={tp}, have "
                             f"{len(devices)}")
        devices = devices[:tp]
    return Mesh(np.asarray(devices), ("tp",))


def shard_model_tp(model, mesh, axis="tp"):
    """Lay the llama stack's weights out TP-sharded on ``mesh`` in place
    (Megatron placement via :func:`~paddle_tpu.models.llama.llama_tp_spec`:
    column-parallel q/k/v/gate/up + lm_head, row-parallel o/down, vocab-
    sharded embedding; norms replicated). Multi-process safe: every
    process must hold identical host values (same seed / same checkpoint)
    and contributes its addressable shards."""
    import jax
    from jax.sharding import NamedSharding

    from ..models.llama import llama_tp_spec

    for name, p in model.named_parameters():
        host = np.asarray(p._value)
        sharding = NamedSharding(mesh, llama_tp_spec(name, axis=axis))
        p._value = jax.make_array_from_callback(
            host.shape, sharding, lambda idx, h=host: h[idx])
    return model


def tp_engine(model, tp=None, mesh=None, devices=None, shard_weights=True,
              **engine_kw):
    """Build a tensor-parallel serving engine: shard ``model``'s weights
    over a ``("tp",)`` mesh (built from ``tp``/``devices`` unless a
    ``mesh`` is passed) and return ``LLMEngine(model, mesh=mesh, ...)``
    whose KV pools shard along kv-heads on the same axis. Token-exact
    greedy parity with the single-chip engine is the contract
    (tests/test_cluster.py asserts it for dense AND paged, prefix cache
    on and off). Engine kwargs pass through — including
    ``kv_cache_dtype="int8"|"int4"`` (quantized KV pools): the
    per-(block, head) scale arrays shard kv-heads with the pools and
    per-head absmax quantization is shard-local, so TP quantized
    serving stays token-exact vs single-chip quantized
    (tests/test_kv_quant.py::TestComposition::test_tp_mesh_exact)."""
    from ..inference import LLMEngine

    if mesh is None:
        mesh = tp_serving_mesh(tp, devices)
    if "tp" not in tuple(mesh.axis_names):
        raise ValueError(f"tp_engine needs a mesh with a 'tp' axis, got "
                         f"{tuple(mesh.axis_names)}")
    if shard_weights:
        shard_model_tp(model, mesh)
    return LLMEngine(model, mesh=mesh, **engine_kw)


# ---------------------------------------------------------------------------
# Level 2 — the data-parallel replica router
# ---------------------------------------------------------------------------

class RouterHandle:
    """Caller-side view of one routed request.

    Wraps the current replica-local
    :class:`~paddle_tpu.serving.RequestHandle` and survives failover: a
    queued request whose replica dies is transparently re-attached to a
    survivor (``resubmits`` counts the hops); a request that had already
    streamed tokens finishes with ``finish_reason="replica_lost"``.
    Iterate for the token stream, :meth:`result` for the terminal
    :class:`~paddle_tpu.serving.ServeResult` (its ``routing`` dict names
    the replica and the placement score that won)."""

    def __init__(self, router, prompt_ids, kwargs, routing_key=None):
        self._router = router
        self.prompt_ids = prompt_ids
        self._kwargs = kwargs
        self.routing_key = routing_key
        self._cond = threading.Condition()
        self._inner = None           # current replica-local RequestHandle
        self._replica = None
        self._final: ServeResult | None = None
        self._streamed = []          # tokens handed to the caller
        #: disaggregated serving state: {"budget": original
        #: max_new_tokens, "done": ship completed} on a request the
        #: router split into a prefill leg + decode leg; None otherwise
        self._disagg = None
        #: tokens committed on a finished prefill leg that the caller
        #: had NOT yet consumed when the ship migrated the stream — the
        #: decode replica treats them as resume prefix (never re-emits),
        #: so the router delivers them from here first
        self._carry = collections.deque()
        self._migrating = False      # drain: a cancel that must resubmit
        self.resubmits = 0
        #: failover-retry pacing: when every survivor's queue is full, a
        #: resubmission parks back in the outstanding set and retries on
        #: monitor ticks — with capped exponential backoff — until the
        #: router's retry window closes
        self._retry_since = None
        self._last_try = None
        self._retry_delay = router.poll_interval_s
        #: tokens the caller already consumed at failover time — the
        #: resume_inflight resubmission's continuation point
        self._resume_tokens = None

    @property
    def replica(self):
        """Index of the replica currently serving this request."""
        return self._replica

    @property
    def done(self):
        return self._final is not None

    @property
    def routing(self):
        """The routing/placement dict stamped on the current submission
        (also surfaced on the terminal ``ServeResult.routing``)."""
        inner = self._inner
        return inner.request.routing if inner is not None else None

    # -- router side -----------------------------------------------------
    def _attach(self, replica_idx, inner):
        with self._cond:
            self._inner = inner
            self._replica = replica_idx
            self._migrating = False
            self._cond.notify_all()

    def _finish(self, result):
        with self._cond:
            self._final = result
            self._cond.notify_all()

    # -- caller side -----------------------------------------------------
    def _pop_token(self):
        """Pop one streamed token AND record it in ``_streamed`` under
        the same lock — _resolve snapshots (pending deque, streamed
        list) under that lock too, so a crash result can never count a
        token in both."""
        if self._carry:
            # migrated-leg tokens the decode replica will never re-emit
            # (they ride resume_tokens): deliver them before the new
            # inner's stream
            try:
                tok = self._carry.popleft()
            except IndexError:
                tok = None
            if tok is not None:
                self._streamed.append(tok)
                return tok
        inner = self._inner
        if inner is None:
            return None
        with inner._cond:
            if inner._tokens:
                tok = inner._tokens.popleft()
                self._streamed.append(tok)
                return tok
        return None

    def tokens(self, timeout=None):
        """Generator over the token stream (across failover re-attach),
        with an optional per-token timeout."""
        while True:
            deadline = None if timeout is None \
                else time.monotonic() + timeout
            while True:
                tok = self._pop_token()
                if tok is not None:
                    break
                if self._final is not None:
                    # re-pop: a token emitted between the miss above and
                    # the final landing must still be delivered
                    tok = self._pop_token()
                    if tok is None:
                        return
                    break
                inner = self._inner
                if inner is not None and inner.done:
                    # nudge the router — the waiting client drives the
                    # resolve latency, the monitor is only the backstop
                    self._router._resolve(self)
                    with self._cond:
                        if self._final is None:
                            self._cond.wait(0.02)
                elif inner is not None:
                    # the streaming hot path waits on the INNER handle's
                    # condition — _emit notifies it, so token delivery is
                    # notification-driven like a plain server handle (the
                    # bounded wait only exists to notice a failover
                    # re-attach swapping _inner out from under us)
                    with inner._cond:
                        if not inner._tokens and not inner.done:
                            inner._cond.wait(0.05)
                else:
                    with self._cond:
                        if self._final is None:
                            self._cond.wait(0.02)
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        f"routed request: no token within {timeout}s")
            yield tok

    def __iter__(self):
        return self.tokens()

    def result(self, timeout=None) -> ServeResult:
        """Block for the terminal result (post-failover if any)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._cond:
                if self._final is not None:
                    return self._final
                inner = self._inner
            if inner is not None and inner.done:
                self._router._resolve(self)
                continue
            if inner is not None:
                try:
                    inner.result(timeout=0.05)
                    continue   # inner done: loop resolves it
                except TimeoutError:
                    pass
            else:
                with self._cond:
                    self._cond.wait(0.05)
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"routed request not finished within {timeout}s")

    def cancel(self):
        inner = self._inner
        if inner is not None:
            inner.cancel()


class ReplicaRouter:
    """Load- and prefix-affinity-aware placement over N
    :class:`~paddle_tpu.serving.AsyncLLMServer` replicas.

    ``policy``: ``"affinity"`` (the default — affinity score on top of
    least-loaded), ``"least_loaded"`` (ignore affinity), or ``"random"``
    (the control arm of a comparison). ``submit(..., replica=i)`` pins a request
    explicitly (ops / tests). The router owns replica lifecycle when
    started through it: :meth:`start` starts un-started replicas plus the
    failover monitor, :meth:`stop` drains and stops everything.

    Failover contract: when a replica is LOST — its serving loop
    crashed terminally, or its :meth:`~AsyncLLMServer.health` probe
    reports ``"hung"`` (heartbeat stale past ``step_timeout_s``; the
    thread may still be alive) — every request it had QUEUED (nothing
    streamed yet) is resubmitted to a survivor and completes there
    (re-prefill reproduces the identical stream); every request already
    STREAMING fails with ``finish_reason="replica_lost"`` carrying the
    tokens streamed so far — or, with ``resume_inflight=True``,
    resubmits with ``resume_tokens`` and CONTINUES on the survivor
    (token-exactly for greedy; a sampled tail re-samples under the
    survivor's keys). A replica mid-supervised-restart (``"restarting"``) takes
    no new placements but keeps its residents: the resumption is about
    to happen locally. Nothing is silently dropped."""

    def __init__(self, replicas, affinity_weight=2.0, load_weight=1.0,
                 policy="affinity", poll_interval_s=0.01,
                 failover_retry_s=10.0, max_retry_backoff_s=0.5,
                 resume_inflight=False, seed=0,
                 adapter_affinity_weight=1.0, metrics_store=None,
                 metrics_interval_s=0.05, roles=None, transport=None,
                 pull_on_miss=False):
        if not replicas:
            raise ValueError("ReplicaRouter needs at least one replica")
        if policy not in ("affinity", "least_loaded", "random"):
            raise ValueError(f"unknown routing policy {policy!r}")
        self.replicas = list(replicas)
        #: DISAGGREGATED prefill/decode serving (DistServe/Splitwise):
        #: ``roles={"prefill": [...], "decode": [...]}`` (replica
        #: indices). New generate prompts place on PREFILL replicas as a
        #: one-token leg with ``export_kv`` staging; on leg finish the
        #: router ships the staged entry to a DECODE replica (import +
        #: stitch re-admission, jumping the queue like a failover
        #: resume) and the stream continues there — token-exactly for
        #: greedy, and for sampled when the replicas share a
        #: ``sampling_seed``. Any ship/validation failure falls back to
        #: plain re-prefill on the decode side: shipping is an
        #: optimization, never a correctness dependency.
        if roles is not None:
            n = len(self.replicas)
            roles = {k: sorted(int(i) for i in v)
                     for k, v in roles.items()}
            for k in ("prefill", "decode"):
                if not roles.get(k):
                    raise ValueError(f"roles needs a non-empty {k!r} "
                                     f"replica list")
                if any(i < 0 or i >= n for i in roles[k]):
                    raise ValueError(f"roles[{k!r}] has an out-of-range "
                                     f"replica index (have {n})")
            # a migrated request keeps its rid across replicas (the
            # engine's restore + sampling keys validate by rid) — give
            # each replica a disjoint id base so a prefill-assigned rid
            # can never collide with a decode replica's own. 2**26
            # spacing: rids must stay int32-safe (the per-(rid,
            # position) sampling keys fold_in the rid), so 67M ids per
            # replica for up to 31 replicas
            if len(self.replicas) > 31:
                raise ValueError("disaggregated roles support at most "
                                 "31 replicas (int32 rid bases)")
            for i, srv in enumerate(self.replicas):
                srv._next_id = max(srv._next_id, i * (1 << 26))
        self.roles = roles
        #: staged-entry mover (serving.kv_transport): defaults to the
        #: in-process loopback, which still round-trips real serialized
        #: bytes. pull_on_miss additionally lets a replica whose prefix
        #: probe missed fetch the cached span from the peer that
        #: probe_prefix_len says can serve it, instead of recomputing.
        if transport is None and (roles is not None or pull_on_miss):
            from .kv_transport import InProcessTransport
            transport = InProcessTransport()
        self.transport = transport
        self.pull_on_miss = bool(pull_on_miss)
        #: end-to-end migration latency (leg finish → decode-side
        #: re-admission granted), observed per successful ship
        from ..profiler.serving_telemetry import LatencyHistogram
        self.migration_latency = LatencyHistogram()
        #: the same latency DECOMPOSED per kv_transport.MIGRATION_PHASES
        #: name — serialize/transport/import timed inside the
        #: transport's ship(), place around the decode-side placement,
        #: stitch read back from the destination engine's fenced
        #: restore. One histogram per phase; snapshot() surfaces them
        #: next to migration_latency.
        self.migration_phases = {}
        #: per-migration records (trace_id, rid, src→dst, perf_counter
        #: t0/t1, phase seconds, wire bytes) — bounded; feeds the merged
        #: trace's router lane and explain_tail's boundary-gap
        #: attribution
        self._migrations = collections.deque(maxlen=256)
        self.affinity_weight = float(affinity_weight)
        #: adapter-affinity bonus (multi-tenant serving): a replica
        #: whose adapter device cache already HOLDS the request's
        #: adapter serves it without a swap-in, so placement prefers it
        #: — scored as a flat bonus on top of the prefix/load formula
        #: (the swap cost is per-admission, not per-token)
        self.adapter_affinity_weight = float(adapter_affinity_weight)
        self.load_weight = float(load_weight)
        self.policy = policy
        self.poll_interval_s = float(poll_interval_s)
        #: how long a failover resubmission keeps retrying when every
        #: survivor's queue is full before the request fails as
        #: replica_lost — transient backpressure must not drop requests.
        #: Retries pace with CAPPED EXPONENTIAL BACKOFF: the delay
        #: doubles from poll_interval_s up to max_retry_backoff_s, so a
        #: long backpressure window costs O(log) placement passes, not a
        #: hot retry loop per parked handle.
        self.failover_retry_s = float(failover_retry_s)
        self.max_retry_backoff_s = float(max_retry_backoff_s)
        #: upgrade the failover contract for IN-FLIGHT requests: instead
        #: of failing with ``replica_lost``, resubmit them to a survivor
        #: with ``resume_tokens`` = everything the caller has consumed,
        #: so the stream CONTINUES — token-exactly for GREEDY requests
        #: (deterministic decode off the identical prefix). A SAMPLED
        #: stream continues from the consumed prefix but re-samples its
        #: tail under the survivor's own keys (fresh rid + fresh base
        #: key): distribution-correct, not bit-exact — unlike
        #: same-server supervised restart, which IS sampled-exact
        #: (same engine base key, same rid, per-position fold_in).
        #: Opt-in: resumption recomputes the undelivered tokens, which
        #: costs survivor FLOPs a latency-critical cluster may prefer to
        #: spend on fresh traffic.
        self.resume_inflight = bool(resume_inflight)
        #: optional router-level metrics store: the monitor loop feeds
        #: its own view (outstanding placements per replica, failover
        #: counters) as replica-labeled time series — the fleet-side
        #: half of the sensor layer (True = default-sized store)
        if metrics_store is True:
            from ..profiler.metrics_store import MetricsStore
            metrics_store = MetricsStore()
        # falsy (False) normalizes to the detached None off-path
        self.metrics_store = metrics_store or None
        #: monitor-side feed throttle (same discipline as the server's
        #: _feed_sensors): the monitor ticks every poll_interval_s
        #: (10ms default) but the store samples at this cadence
        self.metrics_interval_s = float(metrics_interval_s)
        self._ms_last_t = 0.0
        self._rng = np.random.default_rng(seed)
        # PADDLE_TPU_LOCK_CHECKS=1: acquisition edges feed the PTL004
        # lock-order watchdog (paddle_tpu.analysis.lock_watchdog)
        self._lock = _lockwatch.tracked(threading.Lock(),
                                        "ReplicaRouter._lock")
        self._outstanding: set[RouterHandle] = set()
        #: outstanding placements per replica, counted by the ROUTER at
        #: placement time — the load gauges are sampled by each replica's
        #: serve loop and lag a burst of submissions, so a salvo would
        #: otherwise pile onto whichever replica scored best a
        #: millisecond ago. The score uses max(gauges, this).
        self._live_per = [0] * len(self.replicas)
        self._draining: set[int] = set()
        self._stop_evt = threading.Event()
        self._monitor = None
        self.stats = {"submitted": 0, "affinity_routed": 0,
                      "adapter_routed": 0,
                      "resubmitted": 0, "replica_lost": 0,
                      "resumed": 0, "evicted_hung": 0,
                      #: failover resubmissions whose request was
                      #: SWAP-RESIDENT on the lost replica (its KV lived
                      #: in that host's RAM tier, awaiting re-admission)
                      #: — every streamed token is already with the
                      #: caller, so resumption is exact and the host
                      #: copy is simply abandoned with the replica
                      "swap_resident_failover": 0,
                      #: disaggregated serving: prefill legs whose KV
                      #: shipped to a decode replica (stitch-only
                      #: re-admission), legs that fell back to plain
                      #: re-prefill (ship/import/validation failure),
                      #: host-resident KV abandoned by a hung-/dead-
                      #: replica failover (swap-resident or mid-ship —
                      #: transfer work the fleet paid and lost), and
                      #: prefix blocks fetched from peers on a probe
                      #: miss instead of recomputed
                      "kv_shipped": 0, "kv_ship_fallback": 0,
                      "kv_ship_abandoned": 0, "pull_on_miss_blocks": 0,
                      "placements": [0] * len(self.replicas)}

    # -- lifecycle -------------------------------------------------------
    def start(self):
        for srv in self.replicas:
            if srv._thread is None:
                srv.start()
        self._stop_evt.clear()
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="paddle-tpu-router",
                                         daemon=True)
        self._monitor.start()
        return self

    def stop(self, drain=True, timeout=None):
        """Stop the monitor and every replica. A replica whose stop
        fails — a crashed loop re-raising, or a TimeoutError from a
        join still inside a long compile — is collected, not fatal, so
        one bad replica can't wedge cluster shutdown. Returns the
        ``[(replica_idx, exception), ...]`` list."""
        self._stop_evt.set()
        if self._monitor is not None:
            self._monitor.join(timeout)
            self._monitor = None
        errors = []
        for i, srv in enumerate(self.replicas):
            try:
                srv.stop(drain=drain, timeout=timeout)
            except Exception as e:   # noqa: BLE001 — collect, keep going
                errors.append((i, e))
        return errors

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=exc == (None, None, None))
        return False

    def alive(self, idx):
        srv = self.replicas[idx]
        return (srv._thread is not None and srv._thread.is_alive()
                and srv._crashed is None and srv._accepting)

    def healthy(self, idx):
        """Placement eligibility: thread-level liveness AND the health
        protocol's verdict. A ``"hung"`` replica (thread alive, heartbeat
        stale past its ``step_timeout_s``) takes no new placements and
        its residents fail over; a ``"restarting"`` one (supervised
        recovery between crash and re-arm) takes no new placements but
        its residents stay PUT — the resumption is about to happen."""
        if not self.alive(idx):
            return False
        try:
            return self.replicas[idx].health()["state"] == "running"
        except Exception:   # routing heuristic: never let it fail
            return True

    # -- placement -------------------------------------------------------
    def _score(self, idx, ids, hashes=None, adapter_id=0):
        """(score, affinity_tokens, adapter_hit) of placing ``ids`` on
        replica ``idx`` — the documented formula (module docstring) plus
        the ADAPTER-affinity bonus: a replica whose adapter cache
        already holds ``adapter_id`` serves without a swap-in.
        ``hashes``: precomputed chain hashes (the hash chain depends on
        token content + tenant only, so one computation serves every
        same-block_size replica)."""
        srv = self.replicas[idx]
        aff = 0
        adapter_hit = False
        if self.policy == "affinity":
            try:
                aff = int(srv.engine.probe_prefix_len(
                    ids, chain_hashes=hashes, adapter_id=adapter_id))
            except Exception:   # routing heuristic: never let it fail
                aff = 0
            if adapter_id:
                try:
                    adapter_hit = bool(
                        srv.engine.adapter_resident(adapter_id))
                except Exception:
                    adapter_hit = False
        g = srv.telemetry.get_gauges()
        load = (g.get("queue_depth", 0.0) + g.get("engine_waiting", 0.0)
                + g.get("running_slots", 0.0)) / max(srv.engine.B, 1)
        # the router's own outstanding count covers the gauge lag window
        # (submissions placed this millisecond that no loop pass has
        # sampled yet); max() rather than + because settled placements
        # appear in both views
        load = max(load, self._live_per[idx] / max(srv.engine.B, 1))
        # pool pressure counts only UNAVAILABLE blocks: the raw occupancy
        # gauge treats LRU-cached (evictable) prefix blocks as occupied,
        # which would permanently penalize exactly the warm replica the
        # affinity term is trying to prefer
        pool = g.get("kv_pool_occupancy", 0.0)
        cached = g.get("prefix_cached_blocks", 0.0)
        n_blocks = getattr(srv.engine, "n_blocks", 0)
        if n_blocks:
            pool = max(0.0, pool - cached / n_blocks)
        score = self.affinity_weight * (aff / max(len(ids), 1)) \
            + self.adapter_affinity_weight * float(adapter_hit) \
            - self.load_weight * (load + pool)
        return score, aff, adapter_hit

    def _role_for(self, handle):
        """Which role set a submission places into, or None (no
        disaggregation). A split request's DECODE leg (ship done — it
        carries a resume prefix) goes to decode replicas; everything
        else — fresh prompts, prefill legs retrying after a failed
        replica, embeds — is prefill-heavy work and goes to prefill
        replicas."""
        if self.roles is None:
            return None
        d = handle._disagg
        return "decode" if (d is not None and d.get("shipping")) \
            else "prefill"

    def _rank(self, ids, pin=None, adapter_id=0, role=None):
        """Candidate replicas best-first as (idx, score, aff_tokens,
        adapter_hit). ``role``: restrict candidates to that role set
        (disaggregated serving) — degrading gracefully to EVERY healthy
        replica when the whole role set is down, so losing the last
        prefill replica converts prompts to mixed placement instead of
        request loss."""
        #: prompt hash chain per (block_size, tenant) — computed at most
        #: once per submission, shared by same-geometry replicas' probes
        hash_cache = {}

        def hashes_for(idx):
            eng = self.replicas[idx].engine
            if self.policy != "affinity" or \
                    getattr(eng, "prefix_cache", False) is False:
                return None
            bs = eng.block_size
            key = (bs, adapter_id)
            if key not in hash_cache:
                hash_cache[key] = eng.prefix_chain_hashes(
                    ids, adapter_id=adapter_id)
            return hash_cache[key]

        if pin is not None:
            score, aff, ahit = self._score(pin, ids, hashes_for(pin),
                                           adapter_id)
            return [(pin, score, aff, ahit)]
        cand = [i for i in range(len(self.replicas))
                if self.healthy(i) and i not in self._draining]
        if role is not None and self.roles is not None:
            in_role = [i for i in cand if i in self.roles[role]]
            cand = in_role or cand
        if not cand:
            return []
        if self.policy == "random":
            order = [int(i) for i in self._rng.permutation(cand)]
            return [(i, 0.0, 0, False) for i in order]
        scored = [(i,) + self._score(i, ids, hashes_for(i), adapter_id)
                  for i in cand]
        scored.sort(key=lambda t: (-t[1], t[0]))
        return scored

    # -- submission ------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=64, temperature=0.0,
               top_p=1.0, eos_token_id=None, deadline_s=None,
               routing_key=None, replica=None, block=True,
               timeout=None, readout_stride=None, adapter_id=0,
               kind="generate") -> RouterHandle:
        """Place and submit one request; returns its
        :class:`RouterHandle`. ``routing_key`` is an opaque caller tag
        that rides the placement dict into ``ServeResult.routing`` and
        the request's trace spans. ``replica`` pins placement (skips
        scoring). ``readout_stride`` is the per-request latency-tier
        pin, forwarded to whichever replica serves (and re-serves, on
        failover) the request. Backpressure: a replica whose queue is
        full is skipped for the next-best; with every queue full,
        blocks (``block=True``, up to ``timeout``) or raises
        :class:`~paddle_tpu.serving.ServerQueueFull`."""
        ids = np.asarray(
            prompt_ids.numpy() if hasattr(prompt_ids, "numpy")
            else prompt_ids, dtype=np.int32).reshape(-1)
        kwargs = dict(max_new_tokens=max_new_tokens,
                      temperature=temperature, top_p=top_p,
                      eos_token_id=eos_token_id, deadline_s=deadline_s,
                      readout_stride=readout_stride,
                      adapter_id=adapter_id, kind=kind,
                      # fleet-entry trace mint: rides _kwargs so EVERY
                      # resubmission hop (ship / failover / queue retry)
                      # carries the same trace_id; the hop-bump sites
                      # replace it with child contexts
                      trace_ctx=TraceContext.mint("router"))
        handle = RouterHandle(self, ids, kwargs, routing_key)
        if self.roles is not None and kind == "generate" and \
                int(max_new_tokens) > 1:
            # disaggregated split: submit a ONE-token prefill leg with
            # export staging; the leg's finish hook ships the KV and
            # resubmits the remaining budget on a decode replica (an
            # eos on the very first token just finishes normally). A
            # budget of 1 is pure prefill already — no split.
            handle._disagg = {"budget": int(max_new_tokens)}
            kwargs["max_new_tokens"] = 1
            kwargs["export_kv"] = True
        deadline = None if timeout is None else time.monotonic() + timeout
        delay = self.poll_interval_s
        while True:
            err = self._try_place(handle, ids, pin=replica)
            if err is None:
                return handle
            # a validation rejection is the caller's bug, not transient
            # backpressure — surface it synchronously like a plain
            # server's submit() would, never retry it
            if not block or isinstance(err, (ServerClosed, ValueError)):
                raise err
            if deadline is not None and time.monotonic() > deadline:
                raise err
            # capped exponential backoff: sustained backpressure must
            # not melt into a hot scoring/placement spin per submitter
            time.sleep(delay)
            delay = min(delay * 2.0, self.max_retry_backoff_s)

    def _try_place(self, handle, ids, pin=None, resubmit=False):
        """One placement pass over the ranked candidates. Returns None
        on success, else the error to surface (queue-full everywhere /
        no replica alive). Scoring (affinity probes hash the whole
        prompt per replica) runs OUTSIDE the router lock — scores are an
        advisory heuristic over point-in-time reads, so concurrent
        submitters may score stale-ish state but must not serialize on
        each other's hash walks; the lock guards only the actual
        placement bookkeeping."""
        adapter_id = int(handle._kwargs.get("adapter_id") or 0)
        ranked = self._rank(ids, pin=pin, adapter_id=adapter_id,
                            role=self._role_for(handle))
        if self.pull_on_miss and ranked and \
                handle._kwargs.get("kind", "generate") == "generate":
            # BEFORE the submit: the fetched span must be in the target's
            # spill inbox before its engine thread runs this request's
            # admission probe (the inbox drains at the top of the next
            # step, ahead of admission)
            self._pull_prefix(ranked[0], ids, adapter_id)
        with self._lock:
            last_err = None
            for idx, score, aff, ahit in ranked:
                srv = self.replicas[idx]
                routing = {"replica": idx, "policy": self.policy,
                           "score": round(float(score), 4),
                           "affinity_tokens": int(aff),
                           # the handle's counter increments only once
                           # this placement SUCCEEDS — stamp what this
                           # submission will be, not what the last was
                           "resubmits": handle.resubmits
                           + (1 if resubmit else 0)}
                if adapter_id:
                    routing["adapter_id"] = adapter_id
                    routing["adapter_resident"] = bool(ahit)
                if handle.routing_key is not None:
                    routing["routing_key"] = handle.routing_key
                try:
                    inner = srv.submit(ids, routing=routing, block=False,
                                       resume_tokens=handle._resume_tokens
                                       or None, **handle._kwargs)
                except (ServerQueueFull, ServerClosed, ValueError) as e:
                    # ValueError: this replica's validation rejected the
                    # prompt (e.g. prompt⊕resume at ITS capacity edge) —
                    # a differently-sized survivor may still take it; an
                    # uncaught raise here would kill the monitor thread
                    # mid-failover
                    last_err = e
                    continue
                handle._attach(idx, inner)
                self._outstanding.add(handle)
                self._live_per[idx] += 1
                self.stats["placements"][idx] += 1
                if not resubmit:
                    self.stats["submitted"] += 1
                    if aff > 0:
                        self.stats["affinity_routed"] += 1
                    if adapter_id and ahit:
                        self.stats["adapter_routed"] += 1
                return None
            return last_err or ServerClosed("no replica alive")

    def _pull_prefix(self, top, ids, adapter_id):
        """Pull-on-miss: when the chosen replica's prefix probe (device
        content store + its own spill store) covers LESS of this prompt
        than some peer could serve, fetch the missing span's blocks
        from that peer over the transport instead of recomputing them.
        Entirely best-effort and read-only on the peer: a block evicted
        mid-gather just truncates the span, and the target re-derives
        every chain hash before registering, so a bad fetch can never
        corrupt the content store. Requires the target to run an armed
        spill store (``kv_host_spill_bytes > 0``) — the fetched blocks
        land there and the existing probe → promote path serves them."""
        idx, _, aff, _ = top
        eng = self.replicas[idx].engine
        if self.transport is None or \
                not getattr(eng, "prefix_cache", False) or \
                not getattr(eng, "kv_host_spill_bytes", 0):
            return
        bs = eng.block_size
        try:
            hashes = eng.prefix_chain_hashes(ids, adapter_id=adapter_id)
        except Exception:
            return
        have = int(aff) // bs
        if have >= len(hashes):
            return
        want = hashes[have:]
        best_peer, best_len = None, 0
        for j, srv in enumerate(self.replicas):
            if j == idx or not self.healthy(j):
                continue
            peng = srv.engine
            if getattr(peng, "block_size", None) != bs or \
                    getattr(peng, "kv_quant", None) != eng.kv_quant:
                continue
            try:
                plen = int(peng.probe_prefix_len(
                    ids, chain_hashes=hashes, adapter_id=adapter_id))
            except Exception:
                continue
            if plen // bs > have and plen > best_len:
                best_peer, best_len = peng, plen
        if best_peer is None:
            return
        try:
            entries = best_peer.export_prefix_blocks(want)
            if not entries:
                return
            n, _ = self.transport.ship_prefix_blocks(entries, eng)
        except Exception:
            return
        if n:
            with self._lock:
                self.stats["pull_on_miss_blocks"] += n

    def num_outstanding(self):
        with self._lock:
            return len(self._outstanding)

    # -- failover / resolution -------------------------------------------
    def _bump_trace(self, handle, via):
        """Advance the handle's trace context one hop: same trace_id,
        hop+1, parented on the previous hop's span — called once per
        resubmission EPISODE (ship, failover, first queue-full park),
        never per retry tick, so hop counts hops, not backoff spins."""
        tc = TraceContext.coerce(handle._kwargs.get("trace_ctx"))
        if tc is not None:
            handle._kwargs["trace_ctx"] = tc.child(via)

    def _done_with(self, handle):
        """Drop a handle from the outstanding set + the per-replica
        placement count (CALLER HOLDS self._lock)."""
        if handle in self._outstanding:
            self._outstanding.discard(handle)
            if handle._replica is not None:
                self._live_per[handle._replica] -= 1

    def _monitor_loop(self):
        while not self._stop_evt.wait(self.poll_interval_s):
            with self._lock:
                handles = list(self._outstanding)
            for rh in handles:
                inner = rh._inner
                if inner is not None and inner.done:
                    self._resolve(rh)
            self._failover_hung()
            if self.metrics_store is not None:
                self._feed_metrics_store()

    def _feed_metrics_store(self):
        """Feed the router's own counters + per-replica placement view
        into the router-level metrics store (interval-throttled — the
        monitor ticks far faster than a useful sampling cadence)."""
        store = self.metrics_store
        t = time.monotonic()
        if t - self._ms_last_t < self.metrics_interval_s:
            return
        self._ms_last_t = t
        with self._lock:
            live = list(self._live_per)
            stats = dict(self.stats)
        store.observe("router_outstanding", sum(live), t=t)
        for i, n in enumerate(live):
            store.observe("router_replica_outstanding", n, t=t,
                          replica=i)
        for key in ("submitted", "resubmitted", "replica_lost",
                    "resumed", "evicted_hung"):
            store.observe(f"router_{key}", stats[key], t=t)

    def _failover_hung(self):
        """Health-probe failover: a replica whose :meth:`AsyncLLMServer
        .health` verdict is ``"hung"`` (heartbeat stale past its
        ``step_timeout_s`` — the loop thread is ALIVE but stuck inside a
        step) gets its resident requests evicted and failed over NOW,
        without waiting for the thread to die. ``evict_request`` detaches
        each handle from the wedged server (a later revival decodes into
        dropped outputs, never into a double delivery), and the normal
        resolve path converts the eviction into resubmission — with
        ``resume_inflight``, stream continuation (greedy-exact)."""
        for idx, srv in enumerate(self.replicas):
            try:
                hung = srv.health()["state"] == "hung"
            except Exception:
                hung = False
            if not hung:
                continue
            # swap-resident awareness: requests whose KV the wedged
            # replica had demoted to ITS host tier are not in any slot,
            # but they are exactly as resumable as running ones — the
            # committed tokens all streamed before the preemption that
            # swapped them out. The probe is read-only dict access on
            # the (stuck, not racing) engine thread's state.
            try:
                swap_rids = set(srv.engine.swap_resident_rids())
            except Exception:
                swap_rids = set()
            with self._lock:
                mine = [rh for rh in self._outstanding
                        if rh._replica == idx and not rh.done]
            for rh in mine:
                inner = rh._inner
                if inner is not None and not inner.done:
                    if srv.evict_request(inner.request_id,
                                         reason="replica_lost") is not None:
                        with self._lock:
                            self.stats["evicted_hung"] += 1
                            if inner.request_id in swap_rids:
                                self.stats["swap_resident_failover"] += 1
                                # the wedged replica's host-resident KV
                                # copy is abandoned with it — transfer
                                # work the fleet paid and lost
                                self.stats["kv_ship_abandoned"] += 1
                self._resolve(rh)

    def _resolve(self, handle):
        """Turn a finished replica-local result into the routed
        request's fate: final result, or failover resubmission.
        Idempotent AND race-safe: the monitor and any number of waiting
        callers may resolve concurrently — membership in the
        outstanding set (removed atomically under the router lock) is
        the gate, so exactly one caller acts."""
        inner = handle._inner
        if inner is None or not inner.done or handle.done:
            return
        res = inner.result_obj
        reason = res.finish_reason or ""
        #: "lost" covers both shapes of replica loss: a terminal serve-
        #: loop crash (server_error) and a hung-replica eviction
        #: (replica_lost via evict_request) — either way the replica
        #: cannot finish this request
        lost = reason.startswith("server_error") or \
            reason == "replica_lost"
        migrating = handle._migrating and reason == "cancelled"
        streamed = inner.first_token_at is not None
        d = handle._disagg
        if d is not None and not lost and not migrating and \
                reason == "length" and not d.get("placed"):
            # the PREFILL-COMPLETE hook: the leg hit its one-token
            # budget with the real budget unspent — ship the staged KV
            # and continue on a decode replica
            self._ship_and_resubmit(handle, inner, res)
            return
        if d is not None and lost and not d.get("placed") and \
                not d.get("abandoned"):
            # the prefill leg's replica died with the staged/committed
            # KV still on it (mid-ship): the transfer work is lost —
            # make it visible before the plain failover path re-prefills
            d["abandoned"] = True
            with self._lock:
                self.stats["kv_ship_abandoned"] += 1
        # in-flight resumption (opt-in): resubmit with resume_tokens =
        # everything the caller consumed, so the stream continues
        # token-exactly on a survivor instead of failing replica_lost
        resume_stream = lost and streamed and self.resume_inflight
        # a drain-migration that raced its cancel against the first
        # token must NOT resubmit (the caller may already have consumed
        # tokens a fresh greedy stream would repeat) — the cancel stands
        resubmit = (lost and not streamed) or resume_stream or \
            (migrating and not streamed and not handle._streamed)
        now = time.monotonic()
        if resubmit and handle._last_try is not None and \
                now - handle._last_try < handle._retry_delay:
            # pacing: a queue-full retry parked the handle; wait out its
            # current backoff delay instead of hot-spinning the
            # placement pass from every blocked caller
            return
        with self._lock:
            if handle not in self._outstanding:
                return          # another caller won the resolve
            self._done_with(handle)
            if resubmit:
                handle._replica = None   # no live placement while parked
            if lost and streamed and not resume_stream:
                self.stats["replica_lost"] += 1
        if not lost and not migrating:
            handle._finish(res)
            return
        if not resubmit:
            if lost:
                # in-flight: tokens already left the building — fail
                # attributably, carrying everything streamed so far
                # (handed-out tokens plus any still in the deque —
                # snapshot under the same lock _pop_token records with,
                # so no token lands in both lists)
                with inner._cond:
                    pending = list(inner._tokens)
                    emitted = list(handle._streamed)
                handle._finish(ServeResult(
                    res.request_id, emitted + pending,
                    "replica_lost", True, routing=inner.request.routing,
                    trace_ctx=res.trace_ctx or inner.request.trace_ctx))
            else:
                handle._finish(res)
            return
        if resume_stream:
            # freeze the dead stream: clear the undelivered deque under
            # the pop lock so a racing caller can't consume a token the
            # survivor is about to recompute, then resume from exactly
            # what the caller HAS seen
            with inner._cond:
                inner._tokens.clear()
                handle._resume_tokens = list(handle._streamed)
        if resubmit:
            # carry the dead replica's learned draft-acceptance EWMA to
            # the survivor (speculative engines): the resumed stream's
            # verify-k grants start at the adapted window, like the
            # readout_stride pin rides _kwargs. Host-dict read off the
            # dead server's engine — safe from this thread, best-effort.
            try:
                ewma = inner._server.engine.spec_ewma_for(
                    inner.request_id)
            except Exception:
                ewma = None
            if ewma is not None:
                handle._kwargs["spec_ewma"] = ewma
        # resubmit to a survivor (placement excludes the dead/hung/
        # draining replica via healthy()/draining checks)
        if handle._retry_since is None:
            # first attempt of this failover episode — parked queue-full
            # retries keep the already-bumped context
            self._bump_trace(handle, "failover")
        handle._last_try = now
        err = self._try_place(handle, handle.prompt_ids, resubmit=True)
        if err is None:
            handle.resubmits += 1
            handle._retry_since = None
            handle._retry_delay = self.poll_interval_s
            with self._lock:
                self.stats["resubmitted"] += 1
                if resume_stream:
                    self.stats["resumed"] += 1
            return
        if isinstance(err, ServerQueueFull) and not self._stop_evt.is_set():
            # transient backpressure on the survivors: park the handle
            # back in the outstanding set — the monitor's next tick
            # retries, the delay doubling up to max_retry_backoff_s —
            # until the failover window closes. Dropping it NOW would
            # convert a momentarily full queue into request loss.
            if handle._retry_since is None:
                handle._retry_since = now
                self._bump_trace(handle, "queue_retry")
            if now - handle._retry_since < self.failover_retry_s:
                handle._retry_delay = min(handle._retry_delay * 2.0,
                                          self.max_retry_backoff_s)
                with self._lock:
                    self._outstanding.add(handle)
                return
        with self._lock:
            self.stats["replica_lost"] += 1
        handle._finish(ServeResult(
            res.request_id,
            # a lost replica's terminal result already carries the full
            # emitted stream (resume prefix included); a failed drain
            # migration only ever handed out what the caller consumed
            list(res.token_ids) if lost else list(handle._streamed),
            "replica_lost", True, routing=inner.request.routing,
            trace_ctx=res.trace_ctx or inner.request.trace_ctx))

    def _ship_and_resubmit(self, handle, inner, res):
        """The prefill-complete hook (disaggregated serving): export
        the finished leg's staged KV, ship it over the transport to the
        best decode replica, and resubmit the remaining budget there
        under the SAME rid with the leg's tokens as resume prefix — the
        decode engine's swap-store restore re-admits with the one-token
        stitch (``AdmissionQueue.put(front=...)`` grant, like a
        failover resume), so the migrated request pays ZERO re-prefill
        tokens. ANY failure — export raced the store cap, transport or
        pool-geometry reject, validation, queue full on the shipped-to
        replica — falls back to plain resume resubmission (re-prefill
        on the decode side, token-identical stream). Re-entrant: a
        queue-full park retries from the monitor with the staged entry
        cached on the handle, paced by the failover backoff."""
        now = time.monotonic()
        if handle._last_try is not None and \
                now - handle._last_try < handle._retry_delay:
            return                   # parked: wait out the backoff
        with self._lock:
            if handle not in self._outstanding:
                return               # another caller won the resolve
            self._done_with(handle)
            handle._replica = None
        t0 = time.perf_counter()
        d = handle._disagg
        if not d.get("shipping"):
            # first ship attempt of this migration (parked retries keep
            # the already-bumped context): the decode leg is hop+1
            self._bump_trace(handle, "kv_ship")
        d["shipping"] = True         # role flips to "decode" from here
        src = inner._server
        src_idx = next((i for i, s in enumerate(self.replicas)
                        if s is src), None)
        rid = inner.request_id
        # freeze the leg's stream: undelivered tokens move to the
        # router-level carry (the decode replica treats the WHOLE leg
        # stream as resume prefix and never re-emits it)
        with inner._cond:
            pending = list(inner._tokens)
            inner._tokens.clear()
        handle._carry.extend(pending)
        leg_tokens = [int(t) for t in res.token_ids]
        handle._resume_tokens = leg_tokens
        handle._kwargs["max_new_tokens"] = d["budget"]
        handle._kwargs["export_kv"] = False
        # the rid is the migration's identity: the decode engine's
        # restore validates by it, and the shared-sampling_seed
        # per-(rid, position) keys make a SAMPLED continuation
        # token-exact only under the same rid
        handle._kwargs["request_id"] = rid
        if "entry" not in d:
            try:
                te0 = time.perf_counter()
                d["entry"] = src.engine.export_kv(rid)
                # the source-side export is part of the migration's
                # serialize cost (gathering the KV into the staged
                # entry) — folded into the serialize phase below so the
                # phase sub-spans account for the latency window
                d["export_s"] = time.perf_counter() - te0
            except Exception:
                d["entry"] = None
        entry = d["entry"]
        full_ids = np.concatenate(
            [np.asarray(handle.prompt_ids, np.int32),
             np.asarray(leg_tokens, np.int32)])
        adapter_id = int(handle._kwargs.get("adapter_id") or 0)
        ranked = self._rank(full_ids, adapter_id=adapter_id,
                            role="decode")
        shipped = False
        err = ServerClosed("no replica alive")
        phases, nbytes, dst_idx = {}, 0, None
        for idx, _score, _aff, _ahit in ranked:
            dst = self.replicas[idx]
            shipped = False
            phases, nbytes, dst_idx = {}, 0, idx
            if entry is not None and self.transport is not None:
                try:
                    # the transport times its own phases (serialize/
                    # transport/import) and returns them per call, so
                    # concurrent ships can't clobber each other
                    nbytes, tphases = self.transport.ship(
                        entry, dst.engine)
                    shipped = True
                    phases = dict(tphases or {})
                    if "serialize" in phases:
                        phases["serialize"] += d.get("export_s", 0.0)
                except Exception:
                    shipped = False
            tp0 = time.perf_counter()
            err = self._try_place(handle, handle.prompt_ids, pin=idx,
                                  resubmit=True)
            if err is None:
                if shipped:
                    phases["place"] = time.perf_counter() - tp0
                break
            if shipped:
                # placement failed AFTER the import landed: pop the
                # orphaned staged entry (GIL-atomic) so it cannot
                # linger under a rid this replica never admits
                try:
                    dst.engine._swap_store.pop(rid, None)
                except Exception:
                    pass
                shipped = False
        if err is None:
            d["placed"] = True
            handle.resubmits += 1
            handle._retry_since = None
            handle._retry_delay = self.poll_interval_s
            handle._last_try = None
            t1 = time.perf_counter()
            self.migration_latency.observe(t1 - t0)
            if shipped:
                for p, v in phases.items():
                    self._observe_phase(p, v)
                tc = TraceContext.coerce(
                    handle._kwargs.get("trace_ctx"))
                with self._lock:
                    # stitch is timed DESTINATION-side (the fenced
                    # restore at re-admission, after this returns) —
                    # _finalize_migrations reads it back off the decode
                    # engine before anyone consumes the record
                    self._migrations.append({
                        "trace_id": tc.trace_id if tc else None,
                        "rid": rid, "src": src_idx,
                        "dst": dst_idx, "t0": t0, "t1": t1,
                        "phases": phases, "bytes": int(nbytes)})
            with self._lock:
                self.stats["resubmitted"] += 1
                if shipped:
                    self.stats["kv_shipped"] += 1
                else:
                    self.stats["kv_ship_fallback"] += 1
            return
        if isinstance(err, ServerQueueFull) and \
                not self._stop_evt.is_set():
            # transient decode-side backpressure: park and retry from
            # the monitor, exactly like a failover resubmission
            if handle._retry_since is None:
                handle._retry_since = now
                self._bump_trace(handle, "queue_retry")
            if now - handle._retry_since < self.failover_retry_s:
                handle._last_try = now
                handle._retry_delay = min(handle._retry_delay * 2.0,
                                          self.max_retry_backoff_s)
                with self._lock:
                    self._outstanding.add(handle)
                return
        # terminal: the retry window closed or no replica can take it
        with self._lock:
            self.stats["replica_lost"] += 1
            self.stats["kv_ship_fallback"] += 1
        handle._finish(ServeResult(
            res.request_id, list(res.token_ids), "replica_lost", True,
            routing=inner.request.routing,
            trace_ctx=res.trace_ctx or inner.request.trace_ctx))

    # -- migration phase bookkeeping -------------------------------------
    def _observe_phase(self, phase, seconds):
        """Book one migration phase observation (histograms created on
        first use, keyed by kv_transport.MIGRATION_PHASES names)."""
        from ..profiler.serving_telemetry import LatencyHistogram
        h = self.migration_phases.get(phase)
        if h is None:
            h = self.migration_phases[phase] = LatencyHistogram()
        h.observe(seconds)

    def _finalize_migrations(self):
        """Fill in each migration record's destination-side ``stitch``
        wall — timed by the decode engine's fenced restore AFTER the
        ship returned, so it's read back lazily here — and book it,
        once, into the phase histograms. Returns the records, oldest
        first."""
        with self._lock:
            migs = list(self._migrations)
        for m in migs:
            if "stitch" not in m["phases"] and m["dst"] is not None:
                eng = self.replicas[m["dst"]].engine
                s = getattr(eng, "_stitch_s", {}).get(m["rid"])
                if s is not None:
                    m["phases"]["stitch"] = s
                    self._observe_phase("stitch", s)
        return migs

    # -- drain -----------------------------------------------------------
    def drain(self, idx, timeout=30.0):
        """Gracefully remove replica ``idx``: stop placing new work on
        it, migrate its queued (nothing-streamed) requests to survivors,
        let its running requests finish, then stop it. The replica stays
        in ``replicas`` (stopped) so indices remain stable."""
        with self._lock:
            self._draining.add(idx)
            srv = self.replicas[idx]
            mine = [rh for rh in self._outstanding
                    if rh._replica == idx and not rh.done]
        for rh in mine:
            inner = rh._inner
            if inner is not None and inner.first_token_at is None:
                rh._migrating = True
                inner.cancel()
        deadline = time.monotonic() + timeout
        while any(rh._migrating and not rh.done for rh in mine):
            if time.monotonic() > deadline:
                raise TimeoutError(f"drain({idx}): migrations incomplete "
                                   f"after {timeout}s")
            for rh in mine:
                inner = rh._inner
                if rh._migrating and inner is not None and inner.done:
                    self._resolve(rh)
            time.sleep(self.poll_interval_s)
        srv.stop(drain=True, timeout=max(deadline - time.monotonic(), 0.1))

    # -- observability ---------------------------------------------------
    def snapshot(self):
        """JSON-ready cluster view: router stats + each replica's
        telemetry snapshot (keyed by replica index)."""
        with self._lock:
            out = {"policy": self.policy,
                   "stats": {k: (list(v) if isinstance(v, list) else v)
                             for k, v in self.stats.items()},
                   "draining": sorted(self._draining)}
        if self.roles is not None:
            out["roles"] = {k: list(v) for k, v in self.roles.items()}
        migs = self._finalize_migrations()
        out["migration_latency"] = self.migration_latency.snapshot()
        out["migration_phases"] = {
            p: h.snapshot()
            for p, h in sorted(self.migration_phases.items())}
        out["migrations_recorded"] = len(migs)
        if self.transport is not None:
            out["transport"] = {
                "ship_count": getattr(self.transport, "ship_count", 0),
                "ship_bytes": getattr(self.transport, "ship_bytes", 0),
                "fail_count": getattr(self.transport, "fail_count", 0)}
        out["replicas"] = {}
        for i, srv in enumerate(self.replicas):
            eng = srv.engine
            try:
                swap_resident = len(eng.swap_resident_rids())
            except Exception:
                swap_resident = 0
            out["replicas"][i] = {
                "alive": self.alive(i),
                "tp_degree": eng.tp_degree(),
                # host KV tier view: requests parked in this replica's
                # host RAM (resumable without recompute) and its spill
                # store's current size — the failover/capacity facts a
                # fleet controller reads per replica
                "kv_tier": {
                    "swap_resident": swap_resident,
                    "spill_blocks": len(getattr(eng, "_spill", ())),
                    # the spill store is BYTE-bounded (kv_host_spill_bytes
                    # engine arg): report occupancy in the bound's unit
                    "spill_bytes": getattr(eng, "_spill_bytes", 0),
                    "swap_out_bytes": eng.stats.get("kv_swap_out_bytes",
                                                    0),
                    "swap_in_bytes": eng.stats.get("kv_swap_in_bytes", 0),
                    "ship_out_bytes": eng.stats.get("kv_ship_out_bytes",
                                                    0),
                    "ship_in_bytes": eng.stats.get("kv_ship_in_bytes", 0),
                },
                "telemetry": srv.telemetry.snapshot()}
        return out

    def slo_report(self):
        """FLEET-level SLO/sensor report — the one view that answers
        "is tenant 3's p99 TTFT isolated while tenant 0 floods the
        queue, and on which replica?":

        * ``replicas`` — each replica's own :meth:`AsyncLLMServer
          .slo_report` (per-replica burn rates, alerts, pathologies);
        * ``fleet.slos`` — every SLO (union across replicas, by name)
          re-evaluated over the windowed latency samples CONCATENATED
          across the replica stores — a fleet burn rate, not an
          average of per-replica ones;
        * ``fleet.tenant_latency`` — per-tenant histograms merged
          BUCKET-WISE across replicas (exact at bucket resolution —
          per-replica p99s cannot be recombined);
        * ``fleet.alerts`` / ``fleet.pathologies`` — each replica's
          alert log and active detectors, replica-labeled;
        * ``router`` — the router-level store's snapshot (replica-
          labeled placement series) when one is attached.

        ``text`` is the human rendering."""
        from ..profiler.serving_telemetry import ServingTelemetry
        from ..profiler.slo import evaluate_slo, format_fleet_report
        replicas = {}
        merged = {}                  # tenant -> {family: LatencyHistogram}
        slos_by_name = {}
        stores = []
        alerts = []
        pathologies = {}
        for i, srv in enumerate(self.replicas):
            rep = srv.slo_report()
            replicas[i] = rep
            if srv.metrics_store is not None:
                stores.append(srv.metrics_store)
            if srv.slo_engine is not None:
                for s in srv.slo_engine.slos:
                    slos_by_name.setdefault(s.name, s)
            for t, fams in srv.telemetry.tenant_latency_hists().items():
                tgt = merged.setdefault(t, {})
                for n, h in fams.items():
                    if n in tgt:
                        tgt[n].merge(h)
                    else:
                        tgt[n] = h   # already a copy
            for a in rep["alerts"]:
                alerts.append({**a, "replica": i})
            for kind, active in rep["pathologies"].items():
                if active:
                    pathologies.setdefault(kind, []).append(i)
        now = time.monotonic()
        fleet_slos = []
        for s in slos_by_name.values():
            fast, slow = [], []
            truncated = False
            for store in stores:
                sl, fa, tr = store.windowed_values(
                    s.series_name, s.window_s,
                    fast_window_s=s.fast_window, now=now,
                    labels=s.series_labels)
                slow.extend(sl)
                fast.extend(fa)
                truncated = truncated or tr
            fleet_slos.append(evaluate_slo(s, fast, slow,
                                           window_truncated=truncated))
        out = {
            "replicas": replicas,
            "fleet": {
                "slos": fleet_slos,
                "tenant_latency":
                    ServingTelemetry.render_tenant_latency(merged),
                "alerts": alerts,
                "pathologies": pathologies,
            },
        }
        if self.metrics_store is not None:
            out["router"] = self.metrics_store.snapshot(max_samples=16)
        out["text"] = format_fleet_report(out)
        return out

    def prometheus_text(self):
        """One VALID Prometheus exposition across replicas: same-name
        series merge into one metric family (a single ``# TYPE`` line,
        then every replica's labeled samples) — naive concatenation
        would repeat TYPE lines per replica, which strict parsers
        reject. Each replica's telemetry must carry its own ``replica``
        label (``AsyncLLMServer(replica=i)``) or the merged samples
        would collide."""
        families = {}            # metric name -> (type_line, [samples])
        order = []
        for srv in self.replicas:
            current = None
            for line in srv.telemetry.prometheus_text().splitlines():
                if line.startswith("# TYPE "):
                    name = line.split()[2]
                    if name not in families:
                        families[name] = (line, [])
                        order.append(name)
                    current = name
                elif line:
                    families[current][1].append(line)
        out = []
        for name in order:
            type_line, samples = families[name]
            out.append(type_line)
            out.extend(samples)
        return "\n".join(out) + "\n"

    def export_merged_trace(self, path):
        """Merge every recorder-equipped replica's chrome trace into one
        Perfetto-loadable timeline — one process lane group per replica
        (rides :func:`paddle_tpu.profiler.merge_profile`, the same
        cross-rank merge training traces use) — then STITCH it:

        * every request whose spans landed on more than one (pid, tid)
          lane — a shipped decode leg, a failover resubmission — gets
          Perfetto FLOW events (``"ph":"s"`` → ``"ph":"f"``, matched on
          name+cat+id under
          :data:`~paddle_tpu.profiler.flight_recorder.FLOW_EVENT_NAME`)
          chaining its lanes in time order, so Perfetto renders the
          migrated request as ONE connected arrow-linked chain across
          replica pids;
        * each recorded migration renders its router-side phase spans
          (``kv_ship:serialize/transport/import/place``, timed where
          they ran) on a dedicated ``router:migrations`` process lane —
          the destination engine's ``kv_stitch`` span completes the
          decomposition on the decode replica's own lane.

        All replicas share this process's perf_counter clock, so
        cross-replica ordering is real — no alignment applied."""
        import tempfile

        from ..profiler import merge_profile
        from ..profiler.flight_recorder import FLOW_EVENT_NAME
        from .kv_transport import MIGRATION_PHASES

        with tempfile.TemporaryDirectory(
                prefix="paddle_tpu_cluster_trace_") as tmpd:
            files = []
            for i, srv in enumerate(self.replicas):
                rec = srv.flight_recorder
                if rec is None:
                    continue
                files.append(rec.export_chrome_trace(
                    os.path.join(tmpd, f"replica{i}.json")))
            if not files:
                raise RuntimeError(
                    "no replica has a flight recorder attached "
                    "(AsyncLLMServer(flight_recorder=True))")
            # same process, same perf_counter clock: keep it (align
            # would destroy cross-replica simultaneity)
            merge_profile(files, path, align_start=False)
        with open(path) as f:
            doc = json.load(f)
        events = doc["traceEvents"]
        # -- flow stitching: request lanes grouped by trace_id ----------
        lanes = {}       # trace_id -> {(pid, tid): (min_ts, max_end)}
        for ev in events:
            if ev.get("ph") != "X" or ev.get("cat") != "request":
                continue
            trace_id = (ev.get("args") or {}).get("trace_id")
            if trace_id is None:
                continue
            key = (ev["pid"], ev["tid"])
            lane = lanes.setdefault(trace_id, {})
            lo, hi = lane.get(key, (float("inf"), float("-inf")))
            lane[key] = (min(lo, ev["ts"]),
                         max(hi, ev["ts"] + ev.get("dur", 0.0)))
        flow_id = 0
        for trace_id in sorted(lanes):
            lane = lanes[trace_id]
            if len(lane) < 2:
                continue
            ordered = sorted(lane.items(), key=lambda kv: kv[1][0])
            for (ka, (_lo_a, hi_a)), (kb, (lo_b, _hi_b)) in zip(
                    ordered, ordered[1:]):
                flow_id += 1
                common = {"cat": "trace", "name": FLOW_EVENT_NAME,
                          "id": flow_id,
                          "args": {"trace_id": trace_id}}
                events.append({"ph": "s", "pid": ka[0], "tid": ka[1],
                               # the arrow leaves the earlier lane's
                               # last span and lands on the later
                               # lane's first — clamped so s <= f even
                               # when the lanes overlap in time
                               "ts": min(hi_a, lo_b), **common})
                events.append({"ph": "f", "bp": "e", "pid": kb[0],
                               "tid": kb[1], "ts": lo_b, **common})
        # -- the router's migration phase lane --------------------------
        migs = self._finalize_migrations()
        if migs:
            rpid = len(files)       # one past the last replica rank
            events.append({"ph": "M", "pid": rpid, "tid": 0,
                           "name": "process_name",
                           "args": {"name": "router:migrations"}})
            for m in migs:
                tid = 100 + int(m["rid"] or 0)
                events.append({"ph": "M", "pid": rpid, "tid": tid,
                               "name": "thread_name",
                               "args": {"name": f"migration rid "
                                                f"{m['rid']}"}})
                ts = m["t0"] * 1e6
                for p in MIGRATION_PHASES:
                    v = m["phases"].get(p)
                    if v is None or p == "stitch":
                        continue    # stitch renders on the decode lane
                    dur = max(v * 1e6, 1.0)
                    events.append({
                        "ph": "X", "cat": "migration", "pid": rpid,
                        "tid": tid, "name": f"kv_ship:{p}", "ts": ts,
                        "dur": dur,
                        "args": {"trace_id": m["trace_id"],
                                 "request_id": m["rid"],
                                 "src": m["src"], "dst": m["dst"],
                                 "bytes": m["bytes"]}})
                    ts += dur
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def explain_tail(self, quantile=0.99, top=None):
        """The FLEET-level slow-token explainer: join every replica
        recorder's request timelines by ``trace_id`` into one
        END-TO-END token stream per request — across KV ships,
        failovers, and restarts — and classify the worst inter-token
        gaps. A gap that stayed inside one replica gets that replica
        recorder's own :data:`~paddle_tpu.profiler.flight_recorder
        .TAIL_CAUSES` verdict (via ``classify_token_gap``); a gap
        spanning a replica boundary is attributed to the migration
        itself (``kv_ship:{phase}``, phase = the recorded migration's
        dominant phase — :data:`FLEET_TAIL_CAUSES`) when one covers it,
        else to the failover resubmission's re-prefill window
        (``failover_resubmit``). Entries carry ``trace_id``,
        ``request_id``/``replica`` of the LATER token, ``gap_s``,
        ``step_id``, ``cause``, and the migration's phase seconds when
        the cause is a ship phase."""
        migs = self._finalize_migrations()
        by_trace = {}
        for m in migs:
            if m["trace_id"] is not None:
                by_trace.setdefault(m["trace_id"], []).append(m)
        streams = {}
        for i, srv in enumerate(self.replicas):
            rec = srv.flight_recorder
            if rec is None:
                continue
            for rid, tl in rec.timelines().items():
                tc = tl.get("trace_ctx")
                key = tc["trace_id"] if tc else (i, rid)
                st = streams.setdefault(
                    key, {"trace_id": tc["trace_id"] if tc else None,
                          "tokens": [], "crashes": []})
                for ev in tl["events"]:
                    if ev["kind"] == "token":
                        st["tokens"].append(
                            (ev["t"], i, rid, ev["step_id"]))
                    elif ev["kind"] == "crashed":
                        st["crashes"].append(ev["t"])
        gaps = []
        for key, st in streams.items():
            toks = sorted(st["tokens"])
            for (t0, i0, _r0, _s0), (t1, i1, r1, s1) in zip(
                    toks, toks[1:]):
                gaps.append((t1 - t0, t0, t1, i0, i1, r1, s1, key, st))
        if not gaps:
            return []
        ordered = sorted(g[0] for g in gaps)
        thresh = ordered[min(int(quantile * len(ordered)),
                             len(ordered) - 1)]
        tail = sorted((g for g in gaps if g[0] >= thresh),
                      key=lambda g: -g[0])
        if top is not None:
            tail = tail[:top]
        out = []
        for gap, t0, t1, i0, i1, rid, sid, key, st in tail:
            entry = {"request_id": rid, "replica": i1,
                     "gap_s": round(gap, 6), "step_id": sid}
            if st["trace_id"] is not None:
                entry["trace_id"] = st["trace_id"]
            if i0 != i1:
                # the stream moved replicas inside this gap: either the
                # recorded migration explains it phase-by-phase, or it
                # was a failover's re-prefill window
                mig = next((m for m in by_trace.get(st["trace_id"], ())
                            if t0 <= m["t1"] and m["t0"] <= t1), None)
                if mig is not None and mig["phases"]:
                    phases = mig["phases"]
                    dom = max(phases, key=phases.get)
                    entry["cause"] = f"kv_ship:{dom}"
                    entry["migration"] = {
                        "src": mig["src"], "dst": mig["dst"],
                        "bytes": mig["bytes"],
                        "phases": {p: round(v, 6)
                                   for p, v in sorted(phases.items())}}
                else:
                    entry["cause"] = "failover_resubmit"
            elif any(t0 < ct <= t1 for ct in st["crashes"]):
                entry["cause"] = "restart_recovery"
            else:
                rec = self.replicas[i1].flight_recorder
                cause, _step = rec.classify_token_gap(rid, sid, gap)
                entry["cause"] = cause
            out.append(entry)
        return out

    def dump_debug_bundle(self, out_dir, reason="manual", detail=None):
        """Fleet postmortem under ``out_dir``: one black-box debug
        bundle PER replica (``replica{i}.json``), the merged stitched
        cross-replica trace (``merged_trace.json``, when any replica
        has a recorder), and the router's own view (``router.json``:
        snapshot + fleet explain_tail). Returns the path dict."""
        from ..profiler.black_box import collect_bundle, write_bundle
        os.makedirs(out_dir, exist_ok=True)
        paths = {"replicas": []}
        for i, srv in enumerate(self.replicas):
            p = os.path.join(out_dir, f"replica{i}.json")
            paths["replicas"].append(write_bundle(
                collect_bundle(server=srv, reason=reason,
                               detail=detail), p))
        if any(srv.flight_recorder is not None
               for srv in self.replicas):
            paths["trace"] = self.export_merged_trace(
                os.path.join(out_dir, "merged_trace.json"))
        rp = os.path.join(out_dir, "router.json")
        with open(rp, "w") as f:
            json.dump({"schema": "paddle_tpu.router_postmortem/v1",
                       "snapshot": self.snapshot(),
                       "explain_tail": self.explain_tail(0.0, top=16)},
                      f, sort_keys=True, indent=1, default=str)
        paths["router"] = rp
        return paths
