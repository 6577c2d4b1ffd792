"""Fault-tolerant serving (paddle_tpu/serving/faults.py + the
AsyncLLMServer supervision layer) — deterministic fault injection,
supervised engine restart with token-exact resumption, watchdog hang
detection, and deadline-aware load shedding.

The acceptance bars from the ISSUE:

* chaos matrix: an injected mid-stream engine crash with ``supervise=``
  on leaves every in-flight request's FINAL token sequence identical to
  an uninjected run — dense AND paged, prefix cache on and off — with
  <= the configured restarts consumed and ``_check_pool_invariants``
  clean after recovery (``test_crash_recovery_token_exact``).
* a hung-step injection flips ``server_healthy`` within
  ``step_timeout_s`` (+ one watchdog period) while the loop thread is
  still alive, and ``engine_restarts`` / ``requests_resumed`` are
  visible in the Prometheus export with ``crashed``/``resumed`` spans
  in the chrome trace (``test_hang_flips_health``,
  ``test_restart_counters_and_trace_spans``).

Engines are module-scoped (compilation dominates CPU wall); a recovered
engine is clean by construction (reset() rebuilds pools + allocator),
and ``_fresh`` asserts each test starts drained.
"""
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (AsyncLLMServer, FaultInjector,
                                InjectedFault, RestartPolicy,
                                ServerQueueFull)

V = 96
@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


ENGINE_CONFIGS = {
    "dense": dict(),
    "paged": dict(cache_impl="paged", block_size=8, scheduler="fused"),
    "paged_prefix": dict(cache_impl="paged", block_size=8,
                         scheduler="fused", enable_prefix_cache=True),
    # fused speculative serving (PR 10): a crash can land mid-verify-
    # window — recovery must still be token-exact, the rid-keyed
    # acceptance-EWMA mirror survives reset(), and the paged rollback/
    # fence machinery must leave the pool invariant-clean
    "fused_spec": dict(cache_impl="paged", block_size=8,
                       scheduler="fused", speculative_k=3),
}


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("chunk_size", 16)
    return LLMEngine(model, **kw)


@pytest.fixture(scope="module")
def engines(tiny_model):
    return {name: _engine(tiny_model, **kw)
            for name, kw in ENGINE_CONFIGS.items()}


def _fresh(eng):
    assert all(s is None for s in eng.slots)
    assert not eng.waiting
    eng.finished_outputs.clear()
    eng.reset_stats()
    return eng


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, size=(n,)).astype(np.int32) for n in sizes]


# ---------------------------------------------------------------------------
# the FaultInjector itself
# ---------------------------------------------------------------------------

def test_injector_schedule_consumed_once(engines):
    """Scripted actions fire at the scripted step, exactly once, and
    land in .fired — the determinism the chaos tests stand on."""
    eng = _fresh(engines["dense"])
    fi = FaultInjector().crash_at_step(2)
    eng.fault_injector = fi
    try:
        with pytest.raises(InjectedFault):
            eng.generate(_prompts(0, (5,)), max_new_tokens=8)
    finally:
        eng.fault_injector = None
        # the crashed generate left a slot resident — clean it up
        eng.reset()
    assert fi.fired == [("raise", 2, "injected fault")]
    assert fi.step == 2


def test_injected_queue_full_burst(engines):
    """queue_full_burst rides the SAME rejection bookkeeping as a real
    full queue: ServerQueueFull to the caller, the rejection counter,
    and no handle leak."""
    eng = _fresh(engines["dense"])
    fi = FaultInjector().queue_full_burst(2)
    server = AsyncLLMServer(eng, max_queue_size=8, fault_injector=fi)
    p = _prompts(1, (6,))[0]
    with server:
        for _ in range(2):
            with pytest.raises(ServerQueueFull, match="injected"):
                server.submit(p, max_new_tokens=4, block=False)
        h = server.submit(p, max_new_tokens=4)   # burst consumed
        assert h.result(timeout=120).finish_reason == "length"
    snap = server.telemetry.snapshot()
    assert snap["counters"]["requests_rejected_queue_full"] == 2
    assert snap["counters"]["faults_injected"] == 2
    assert server.num_outstanding() == 0
    assert [f[0] for f in fi.fired] == ["queue_full", "queue_full"]


# ---------------------------------------------------------------------------
# supervised restart — THE chaos acceptance matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", list(ENGINE_CONFIGS))
def test_crash_recovery_token_exact(engines, config):
    """Mid-stream engine crash under supervise=: every in-flight
    request's final token sequence is identical to an uninjected run,
    <= max_restarts consumed, pool invariants clean after recovery."""
    eng = _fresh(engines[config])
    prompts = _prompts(3, (9, 5, 17))
    want = [o.token_ids for o in eng.generate(prompts, max_new_tokens=8)]
    _fresh(eng)

    fi = FaultInjector().crash_at_step(4)
    server = AsyncLLMServer(
        eng, max_queue_size=8, fault_injector=fi, flight_recorder=True,
        supervise=RestartPolicy(max_restarts=2, backoff_s=0.01))
    with server:
        handles = [server.submit(p, max_new_tokens=8) for p in prompts]
        results = [h.result(timeout=240) for h in handles]
    assert [r.token_ids for r in results] == want
    assert all(r.finish_reason == "length" for r in results)
    assert fi.fired and fi.fired[0][0] == "raise"
    assert 1 <= server.restarts <= 2
    snap = server.telemetry.snapshot()
    assert snap["counters"]["engine_restarts"] == server.restarts
    assert snap["counters"]["requests_resumed"] >= 1
    if eng.cache_impl == "paged":
        eng._check_pool_invariants()


def test_crash_recovery_sampled_exact(engines):
    """SAMPLED (temperature > 0) streams also resume token-exactly:
    token p of request r samples from fold_in(fold_in(base, r), p), so a
    restart replays the identical per-position keys. Since PR 10 that
    includes SPECULATIVE engines — the coupled acceptance rule has no
    per-window key advance, so a crash mid-verify-window resumes
    sampled-exact too (PR 7 documented spec as greedy-exact only; the
    speculative sampled variant lives in tests/test_fused_spec.py's
    chaos test, the greedy one in this file's matrix via the
    fused_spec config). Same engine (same lazily-derived base key),
    fresh server per run (rids restart at 0)."""
    eng = _fresh(engines["dense"])
    prompts = _prompts(5, (9, 5))

    def run(fi):
        server = AsyncLLMServer(
            eng, fault_injector=fi,
            supervise=RestartPolicy(max_restarts=2, backoff_s=0.01))
        with server:
            hs = [server.submit(p, max_new_tokens=8, temperature=0.8,
                                top_p=0.9) for p in prompts]
            return [h.result(timeout=240).token_ids for h in hs]

    want = run(FaultInjector())
    got = run(FaultInjector().crash_at_step(3))
    assert got == want
    _fresh(eng)


@pytest.mark.slow
def test_crash_at_readout_phase(engines):
    """phase="finish" crashes at the step_finish (readout) side — after
    a dispatch landed, with a pending step in flight on the dense
    depth-2 pipeline — and recovery is still token-exact. Slow lane:
    the tier-1 chaos matrix already covers begin-phase recovery on
    every engine config under the wall budget."""
    eng = _fresh(engines["dense"])
    prompts = _prompts(6, (7, 11))
    want = [o.token_ids for o in eng.generate(prompts, max_new_tokens=6)]
    _fresh(eng)
    fi = FaultInjector().crash_at_step(2, phase="finish")
    server = AsyncLLMServer(
        eng, fault_injector=fi,
        supervise=RestartPolicy(max_restarts=1, backoff_s=0.01))
    with server:
        hs = [server.submit(p, max_new_tokens=6) for p in prompts]
        assert [h.result(timeout=240).token_ids for h in hs] == want
    assert server.restarts == 1


def test_fail_request_poison_pill(engines):
    """fail_request(rid): the loop crashes when that request occupies a
    slot at dispatch; supervision brings EVERYONE back token-exactly
    (the poisoned request is a schedule trigger, not a casualty)."""
    eng = _fresh(engines["paged"])
    prompts = _prompts(7, (6, 12))
    want = [o.token_ids for o in eng.generate(prompts, max_new_tokens=6)]
    _fresh(eng)
    fi = FaultInjector().fail_request(1)
    server = AsyncLLMServer(
        eng, fault_injector=fi,
        supervise=RestartPolicy(max_restarts=1, backoff_s=0.01))
    with server:
        hs = [server.submit(p, max_new_tokens=6) for p in prompts]
        assert [h.result(timeout=240).token_ids for h in hs] == want
    assert [f[0] for f in fi.fired] == ["fail_request"]
    eng._check_pool_invariants()


def test_restarts_exhausted_fails_attributably(engines):
    """A crash LOOP consumes the policy then fails terminally: every
    waiter gets finish_reason="server_error" CARRYING its partial
    tokens, submit() raises ServerClosed, stop() re-raises the crash."""
    eng = _fresh(engines["dense"])
    fi = FaultInjector()
    # the injector's step counter runs ON across restarts (engine state
    # resets, the schedule does not) — each life crashes 3 steps in
    for step in (3, 6, 9):
        fi.crash_at_step(step)
    server = AsyncLLMServer(
        eng, fault_injector=fi,
        supervise=RestartPolicy(max_restarts=2, backoff_s=0.01))
    try:
        server.start()
        h = server.submit(_prompts(8, (6,))[0], max_new_tokens=30)
        res = h.result(timeout=240)
        assert res.finish_reason.startswith("server_error")
        assert len(res.token_ids) >= 1          # partial stream carried
        assert res.token_ids == h.emitted
        assert server.restarts == 2
        assert len(fi.fired) == 3
        assert server.health()["state"] == "crashed"
        assert server.telemetry.get_gauges()["server_healthy"] == 0.0
        with pytest.raises(Exception, match="crashed"):
            server.submit(_prompts(8, (5,))[0])
        with pytest.raises(RuntimeError, match="injected fault"):
            server.stop()
    finally:
        eng.fault_injector = None
        eng.reset()   # leave the module-scoped engine clean


def test_unsupervised_crash_unchanged(engines):
    """No supervise= (the default): a crash still fails every waiter
    with server_error — the pre-existing contract, now carrying the
    partial tokens."""
    eng = _fresh(engines["dense"])
    fi = FaultInjector().crash_at_step(3)
    server = AsyncLLMServer(eng, fault_injector=fi)
    try:
        server.start()
        h = server.submit(_prompts(9, (6,))[0], max_new_tokens=30)
        res = h.result(timeout=240)
        assert res.finish_reason.startswith("server_error")
        assert len(res.token_ids) >= 1
        assert server.restarts == 0
        with pytest.raises(RuntimeError, match="injected fault"):
            server.stop()
    finally:
        eng.fault_injector = None
        eng.reset()


def test_restart_counters_and_trace_spans(engines, tmp_path):
    """engine_restarts / requests_resumed / faults_injected appear in
    the Prometheus export; crashed/resumed spans land in the request
    timeline, the chrome trace, and explain_tail's restart_recovery
    cause."""
    eng = _fresh(engines["paged_prefix"])
    fi = FaultInjector().crash_at_step(4)
    server = AsyncLLMServer(
        eng, fault_injector=fi, flight_recorder=True,
        supervise=RestartPolicy(max_restarts=1, backoff_s=0.01))
    with server:
        hs = [server.submit(p, max_new_tokens=8)
              for p in _prompts(10, (9, 5))]
        results = [h.result(timeout=240) for h in hs]
    text = server.telemetry.prometheus_text()
    assert "paddle_tpu_serving_engine_restarts_total 1" in text
    assert "paddle_tpu_serving_requests_resumed_total" in text
    assert "paddle_tpu_serving_faults_injected_total 1" in text
    assert "# TYPE paddle_tpu_serving_server_healthy gauge" in text
    # crashed -> resumed spans on the resumed requests' timelines
    kinds = [e["kind"] for r in results for e in r.trace["events"]]
    assert "crashed" in kinds and "resumed" in kinds
    # trace identity survives the restart VERBATIM: same trace_id, hop
    # still 0 (re-admission resumes the same hop — it is not a new one)
    tls = server.flight_recorder.timelines()
    for r in results:
        assert r.trace_ctx is not None and r.trace_ctx.hop == 0
        tc = tls[r.request_id].get("trace_ctx")
        assert tc is not None
        assert tc["trace_id"] == r.trace_ctx.trace_id
    # the chrome trace carries them too
    raw = server.flight_recorder.export_chrome_trace(
        str(tmp_path / "chaos_trace.json"))
    with open(raw) as f:
        names = {e.get("name") for e in json.load(f)["traceEvents"]}
    assert "crashed" in names and "resumed" in names
    # the recovery gap is attributed, not mislabeled as a dispatch stall
    tail = server.flight_recorder.explain_tail(0.0)
    assert any(e["cause"] == "restart_recovery" for e in tail)
    eng._check_pool_invariants()


# ---------------------------------------------------------------------------
# watchdog — hang detection
# ---------------------------------------------------------------------------

def test_hang_flips_health_and_watchdog_interrupts(engines):
    """An injected interruptible hang: health() flips to "hung" and the
    server_healthy gauge to 0 within step_timeout_s + one watchdog
    period, the watchdog interrupts the hang (the cancellable-device-
    call stand-in), and serving completes token-exactly afterwards."""
    eng = _fresh(engines["dense"])
    prompts = _prompts(11, (7,))
    want = [o.token_ids for o in eng.generate(prompts, max_new_tokens=10)]
    _fresh(eng)
    fi = FaultInjector().hang_at_step(4, seconds=60.0, interruptible=True)
    server = AsyncLLMServer(eng, fault_injector=fi, step_timeout_s=0.3)
    with server:
        h = server.submit(prompts[0], max_new_tokens=10)
        deadline = time.monotonic() + 30.0
        flipped_at = None
        while time.monotonic() < deadline:
            st = server.health()
            if st["state"] == "hung":
                flipped_at = st["heartbeat_age_s"]
                break
            time.sleep(0.01)
        assert flipped_at is not None, "health never flipped to hung"
        # flipped as soon as the heartbeat went stale (one poll of slack)
        assert flipped_at <= 0.3 + 0.2
        assert server._thread.is_alive()     # hung, NOT dead
        # the watchdog ends the interruptible hang: the stream finishes
        res = h.result(timeout=240)
        assert res.token_ids == want[0]
        assert server.health()["state"] == "running"
        assert server.telemetry.get_gauges()["server_healthy"] == 1.0
    assert fi.fired == [("hang", 4, 60.0)]


def test_health_states(engines):
    """The health() protocol: stopped -> running -> stopped, gauge 0 on
    a never-started server AND after a clean stop (a decommissioned
    replica must not keep scraping healthy)."""
    eng = _fresh(engines["dense"])
    server = AsyncLLMServer(eng)
    assert server.health()["state"] == "stopped"
    assert not server.health()["healthy"]
    assert server.telemetry.get_gauges()["server_healthy"] == 0.0
    server.start()
    h = server.submit(_prompts(12, (5,))[0], max_new_tokens=4)
    h.result(timeout=120)
    st = server.health()
    assert st["state"] == "running" and st["healthy"]
    assert st["thread_alive"] and st["restarts"] == 0
    assert server.telemetry.get_gauges()["server_healthy"] == 1.0
    server.stop()
    assert server.health()["state"] == "stopped"
    assert server.telemetry.get_gauges()["server_healthy"] == 0.0


def test_resume_already_at_eos_finishes_without_decode(engines):
    """A resume whose committed tail already ends with the request's
    eos token finishes "eos" at re-admission instead of re-prefilling
    and decoding PAST the eos (the crash/failover merely beat the
    finished output's routing)."""
    eng = _fresh(engines["dense"])
    server = AsyncLLMServer(eng)
    with server:
        p = _prompts(17, (6,))[0]
        steps_before = eng.stats["steps"]
        h = server.submit(p, max_new_tokens=8, eos_token_id=42,
                          resume_tokens=[7, 9, 42])
        res = h.result(timeout=120)
        assert res.finish_reason == "eos"
        assert res.token_ids == [7, 9, 42]
        assert list(h) == []              # nothing new streamed
        # and the engine never decoded for it
        assert eng.stats["steps"] == steps_before
        # a resume NOT at eos still serves the remaining budget
        h2 = server.submit(p, max_new_tokens=4, eos_token_id=None,
                           resume_tokens=[7, 9])
        res2 = h2.result(timeout=120)
        assert res2.finish_reason == "length"
        assert res2.token_ids[:2] == [7, 9]
        assert len(res2.token_ids) == 4   # 2 resumed + 2 new


# ---------------------------------------------------------------------------
# stop(timeout=) semantics (satellite)
# ---------------------------------------------------------------------------

def test_stop_timeout_then_second_stop(engines):
    """stop(timeout=) that expires raises TimeoutError WITHOUT detaching
    the engine; a second stop() keeps waiting and completes the drain.
    (server.py documents this; this is the missing coverage.)"""
    eng = _fresh(engines["dense"])
    prompts = _prompts(13, (6,))
    want = [o.token_ids for o in eng.generate(prompts, max_new_tokens=8)]
    _fresh(eng)
    fi = FaultInjector().hang_at_step(2, seconds=1.0, interruptible=False)
    server = AsyncLLMServer(eng, fault_injector=fi)
    server.start()
    h = server.submit(prompts[0], max_new_tokens=8)
    with pytest.raises(TimeoutError, match="call stop\\(\\) again"):
        server.stop(timeout=0.1)     # lands inside the 1s hard hang
    # the engine thread still owns the engine and keeps draining
    assert server._thread is not None and server._thread.is_alive()
    server.stop(timeout=120)         # second stop: waits it out
    assert server._thread is None
    assert h.result(timeout=5).token_ids == want[0]


def test_stop_during_supervised_restart(engines):
    """stop(drain=True) landing while a supervised restart is mid-
    backoff lets the recovery COMPLETE: the resumed requests serve out
    token-exactly before the loop exits."""
    eng = _fresh(engines["dense"])
    prompts = _prompts(14, (8, 5))
    want = [o.token_ids for o in eng.generate(prompts, max_new_tokens=8)]
    _fresh(eng)
    fi = FaultInjector().crash_at_step(3)
    server = AsyncLLMServer(
        eng, fault_injector=fi,
        supervise=RestartPolicy(max_restarts=1, backoff_s=0.5))
    server.start()
    hs = [server.submit(p, max_new_tokens=8) for p in prompts]
    # wait for the crash to land, then stop DURING the 0.5s backoff
    deadline = time.monotonic() + 30.0
    while not fi.fired and time.monotonic() < deadline:
        time.sleep(0.005)
    assert fi.fired
    server.stop(drain=True, timeout=240)
    assert [h.result(timeout=5).token_ids for h in hs] == want
    assert server.restarts == 1


# ---------------------------------------------------------------------------
# deadline-aware load shedding (satellite)
# ---------------------------------------------------------------------------

def test_deadline_shedding_flag_gated(engines):
    """shed_deadlines=True rejects a request whose deadline budget is
    below the telemetry-estimated queue wait + TTFT with
    finish_reason="deadline" BEFORE any prefill; the default (False)
    keeps today's behavior bit-identically (expiry via the sweep)."""
    eng = _fresh(engines["dense"])
    p = _prompts(15, (7,))[0]
    # --- default OFF: a doomed deadline goes the normal expiry path ---
    server = AsyncLLMServer(eng)
    with server:
        warm = server.submit(p, max_new_tokens=6)
        warm.result(timeout=120)         # telemetry now has estimates
        h = server.submit(p, max_new_tokens=6, deadline_s=1e-6)
        res = h.result(timeout=120)
    assert res.finish_reason == "deadline"
    snap = server.telemetry.snapshot()
    assert snap["counters"]["requests_shed_deadline"] == 0
    assert snap["counters"]["requests_expired"] >= 1
    _fresh(eng)
    # --- ON: shed at submit, before burning prefill FLOPs -------------
    server = AsyncLLMServer(eng, shed_deadlines=True, flight_recorder=True)
    with server:
        warm = server.submit(p, max_new_tokens=6)
        warm.result(timeout=120)
        prefill_before = server.telemetry.counters["prefill_tokens"]
        h = server.submit(p, max_new_tokens=6, deadline_s=1e-6)
        res = h.result(timeout=5)        # immediate — never queued
        assert res.finish_reason == "deadline"
        assert res.token_ids == []
        assert list(h) == []
        # a comfortable deadline is untouched by the shedder
        ok = server.submit(p, max_new_tokens=6, deadline_s=120.0)
        assert ok.result(timeout=120).finish_reason == "length"
    snap = server.telemetry.snapshot()
    assert snap["counters"]["requests_shed_deadline"] == 1
    # the shed request burned ZERO prefill tokens
    assert snap["counters"]["prefill_tokens"] == prefill_before + len(p)
    # and on a COLD server the estimator has no data -> nothing sheds
    _fresh(eng)
    server = AsyncLLMServer(eng, shed_deadlines=True)
    with server:
        h = server.submit(p, max_new_tokens=4, deadline_s=30.0)
        assert h.result(timeout=120).finish_reason == "length"
    assert server.telemetry.counters["requests_shed_deadline"] == 0


# ---------------------------------------------------------------------------
# validation-rejection telemetry (satellite)
# ---------------------------------------------------------------------------

def test_feed_engine_rejection_counted(engines):
    """A ValueError out of engine admission is no longer telemetry-
    silent: requests_rejected_validation increments and the handle
    finishes attributably."""
    eng = _fresh(engines["dense"])
    server = AsyncLLMServer(eng)
    orig = eng.add_request
    calls = {"n": 0}

    def flaky(*a, **kw):
        if calls["n"] == 0:
            calls["n"] += 1
            raise ValueError("synthetic validation failure")
        return orig(*a, **kw)

    eng.add_request = flaky
    try:
        with server:
            h = server.submit(_prompts(16, (6,))[0], max_new_tokens=4)
            res = h.result(timeout=120)
    finally:
        eng.add_request = orig
    assert res.finish_reason == "rejected: synthetic validation failure"
    assert server.telemetry.counters["requests_rejected_validation"] == 1
