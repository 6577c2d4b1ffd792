"""SLO sensor layer — burn-rate math, live pathology detectors
(synthetic fire + quiescent), gauge staleness, the server's
slo_report, per-tenant latency histograms and fleet aggregation.

The math/detector halves are PURE HOST (synthetic StepRecords, no jax
dispatch). The serve-backed tests reuse one tiny module-scoped model
like tests/test_serving.py.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler.flight_recorder import FlightRecorder, StepRecord
from paddle_tpu.profiler.metrics_store import MetricsStore
from paddle_tpu.profiler.serving_telemetry import ServingTelemetry
from paddle_tpu.profiler.slo import (SLO, AdapterSwapStormDetector,
                                     HostSyncRegressionDetector,
                                     RampThrashDetector, SLOEngine,
                                     SpecCollapseDetector,
                                     SwapStallDetector, default_detectors,
                                     evaluate_slo, format_slo_report)
from paddle_tpu.serving import AsyncLLMServer, FaultInjector, ReplicaRouter


# ---------------------------------------------------------------------------
# SLO declaration + burn-rate math (pure host)
# ---------------------------------------------------------------------------

def test_slo_metric_parsing_and_validation():
    s = SLO("a", "ttft_p99", target_s=0.2, window_s=60.0)
    assert s.metric_base == "ttft" and s.objective == 0.99
    assert s.series_name == "ttft_s"
    assert s.fast_window == pytest.approx(5.0)      # window/12
    assert s.series_labels is None                  # all traffic
    t = SLO("b", "e2e_p90", target_s=1.0, tenant=3, fast_window_s=2.0)
    assert t.objective == 0.90 and t.fast_window == 2.0
    assert t.series_labels == {"tenant": "3"}
    with pytest.raises(ValueError, match="metric"):
        SLO("x", "ttfp_p99", target_s=1.0)
    with pytest.raises(ValueError, match="metric"):
        SLO("x", "ttft_p999", target_s=1.0)
    with pytest.raises(ValueError, match="target_s"):
        SLO("x", "ttft_p99", target_s=0.0)
    # the name becomes a Prometheus label value: exposition-breaking
    # characters are rejected at declaration, not at scrape time
    with pytest.raises(ValueError, match="label value"):
        SLO('victim "a"', "ttft_p99", target_s=1.0)
    with pytest.raises(ValueError, match="label value"):
        SLO("", "ttft_p99", target_s=1.0)


def test_burn_rate_multiwindow_semantics():
    slo = SLO("v", "ttft_p99", target_s=0.1, window_s=60.0,
              fast_window_s=5.0, burn_threshold=6.0)
    good, bad = [0.05] * 94, [0.5] * 6
    # all good: nothing burns, objective met
    r = evaluate_slo(slo, good[:10], good)
    assert r["burn_rate_fast"] == 0.0 and not r["burning"]
    assert not r["breached"] and r["measured_s"] == pytest.approx(0.05)
    # 6% bad everywhere: burn = 0.06/0.01 = 6 >= threshold BOTH windows
    r = evaluate_slo(slo, bad + good[:94], bad + good)
    assert r["burn_rate_slow"] == pytest.approx(6.0)
    assert r["burning"] and r["breached"]
    # fast-only burn (a blip): no alert — the slow window gates it
    r = evaluate_slo(slo, bad, good)
    assert r["burn_rate_fast"] == pytest.approx(100.0)
    assert r["burn_rate_slow"] == 0.0 and not r["burning"]
    # slow-only burn (stale incident, fast window recovered): clears
    r = evaluate_slo(slo, good[:10], bad + good)
    assert not r["burning"]
    # empty windows: burn 0, not breached (no evidence)
    r = evaluate_slo(slo, [], [])
    assert r["burn_rate_fast"] == 0.0 and not r["breached"]


def test_slo_engine_gauges_and_alert_lifecycle():
    store = MetricsStore()
    tel = ServingTelemetry()
    slo = SLO("victim", "ttft_p99", tenant=0, target_s=0.1,
              window_s=10.0, fast_window_s=2.0, burn_threshold=2.0)
    eng = SLOEngine([slo], store, telemetry=tel)
    now = 1000.0
    # tenant-scoped: tenant 1's bad samples must NOT burn tenant 0's SLO
    for i in range(20):
        store.observe("ttft_s", 0.01, t=now - 1.0 + i * 0.01, tenant=0)
        store.observe("ttft_s", 9.99, t=now - 1.0 + i * 0.01, tenant=1)
    (r,) = eng.evaluate(now=now)
    assert not r["burning"] and r["samples_slow"] == 20
    assert tel.snapshot()["labeled_gauges"]["slo_breached"]["victim"] == 0.0
    assert store.alerts(kind="slo_burn") == []
    # tenant 0 goes bad: both windows burn, alert raises, gauges flip
    for i in range(20):
        store.observe("ttft_s", 5.0, t=now + i * 0.01, tenant=0)
    (r,) = eng.evaluate(now=now + 0.2)
    assert r["burning"] and r["breached"]
    lab = tel.snapshot()["labeled_gauges"]
    assert lab["slo_breached"]["victim"] == 1.0
    assert lab["slo_burn_rate"]["victim"] >= 2.0
    (alert,) = store.alerts(kind="slo_burn", active_only=True)
    assert alert.labels == {"slo": "victim"}
    # recovery: bad samples age out of the fast window -> alert clears
    (r,) = eng.evaluate(now=now + 100.0)
    assert not r["burning"]
    assert store.alerts(kind="slo_burn", active_only=True) == []
    assert tel.snapshot()["labeled_gauges"]["slo_breached"]["victim"] == 0.0
    # the human rendering mentions the objective
    txt = format_slo_report({"slos": [r], "alerts": [], "pathologies": {}})
    assert "victim" in txt and "ttft_p99" in txt


def test_slo_engine_surfaces_window_truncation():
    """A high-rate series that wraps its ring INSIDE the slow window
    must say so — otherwise the slow window silently collapses into
    the fast one and the multi-window semantics are a lie."""
    store = MetricsStore(capacity=8)
    slo = SLO("hot", "inter_token_p99", target_s=1.0, window_s=60.0,
              fast_window_s=1.0)
    eng = SLOEngine([slo], store)
    now = 1000.0
    for i in range(50):                  # ring wraps (8 retained)
        store.observe("inter_token_s", 0.01, t=now - 5.0 + i * 0.1)
    (r,) = eng.evaluate(now=now)
    assert r["window_truncated"] is True
    # same data, window smaller than the retained span: honest
    slo2 = SLO("cool", "inter_token_p99", target_s=1.0, window_s=0.5)
    (r2,) = SLOEngine([slo2], store).evaluate(now=now)
    assert r2["window_truncated"] is False


def test_detector_reset_clears_alert_and_window():
    """reset() (called by server.start()) drops the step window AND
    clears an alert left active by a previous run — no cross-run
    windows, no immortal pathology gauges."""
    det, store, tel = _armed(RampThrashDetector)
    for _ in range(8):
        det.on_step(_rec(grants=PREFILL, preemptions=(7,)))
    assert det.active
    det.reset()
    assert not det.active and len(det._recs) == 0
    assert store.alerts(kind="ramp_thrash", active_only=True) == []
    assert _pathology_gauge(tel, "ramp_thrash") == 0.0
    # the cleared alert stays in the log (post-hoc answerable)
    assert len(store.alerts(kind="ramp_thrash")) == 1


def test_slo_engine_add_and_type_checks():
    store = MetricsStore()
    eng = SLOEngine([], store)
    eng.add(SLO("late", "e2e_p50", target_s=1.0))
    assert [r["slo"] for r in eng.evaluate()] == ["late"]
    with pytest.raises(TypeError):
        SLOEngine([object()], store)
    with pytest.raises(TypeError):
        eng.add("not an slo")


# ---------------------------------------------------------------------------
# live pathology detectors (synthetic StepRecords, timing-deterministic)
# ---------------------------------------------------------------------------

_SEQ = [0]


def _rec(*, grants=(), preemptions=(), sync_s=0.0, wall_s=0.05, stride=1,
         spec=(0, 0), adapter_swaps=0, swap_in=None, swap_out=None):
    i = _SEQ[0] = _SEQ[0] + 1
    r = StepRecord(i, 100.0 + i, "fused", "mixed", tuple(grants),
                   sum(g[3] for g in grants), 32, 0, None, None, 1,
                   tuple(preemptions), 0.0, 0.0, 0.01,
                   readout_stride=stride, adapter_swaps=adapter_swaps,
                   kv_swap_in_bytes=swap_in, kv_swap_out_bytes=swap_out)
    r.t_finish = r.t_begin + wall_s
    r.sync_s = sync_s
    r.spec_accepted, r.spec_rejected = spec
    return r


def _armed(det_cls, **kw):
    store = MetricsStore()
    tel = ServingTelemetry()
    return det_cls(store, tel, **kw), store, tel


def _pathology_gauge(tel, kind):
    return tel.snapshot()["labeled_gauges"]["pathology_active"].get(kind)


PREFILL = ((0, 1, "prefill", 16),)
DECODE = ((0, 1, "decode", 1), (1, 2, "decode", 1))


def test_ramp_thrash_fires_and_clears():
    det, store, tel = _armed(RampThrashDetector)
    # the scripted ramp-thrash shape: prefill-only steps, preemptions,
    # not one committed decode token (the PR-13 livelock signature)
    for _ in range(8):
        det.on_step(_rec(grants=PREFILL, preemptions=(7,)))
    assert det.active and det.fired == 1
    (alert,) = store.alerts(kind="ramp_thrash", active_only=True)
    assert alert.data["decode_tokens"] == 0
    assert alert.data["preemptions"] >= 3
    assert _pathology_gauge(tel, "ramp_thrash") == 1.0
    # decode progress returns: the window drains of thrash -> clears
    for _ in range(40):
        det.on_step(_rec(grants=DECODE))
    assert not det.active
    assert store.alerts(kind="ramp_thrash", active_only=True) == []
    assert _pathology_gauge(tel, "ramp_thrash") == 0.0


def test_ramp_thrash_quiescent_on_healthy_preemptions():
    # preemptions WITH decode progress are normal pool churn, not thrash
    det, store, _ = _armed(RampThrashDetector)
    for _ in range(20):
        det.on_step(_rec(grants=PREFILL + DECODE, preemptions=(7,)))
    assert not det.active and store.alerts() == []


def test_host_sync_regression_fires_stride1_only():
    det, store, _ = _armed(HostSyncRegressionDetector)
    # stride-4 amortized readouts with huge sync share: by DESIGN, no fire
    for _ in range(20):
        det.on_step(_rec(grants=DECODE, sync_s=0.09, wall_s=0.1, stride=4))
    assert not det.active
    # the same share on stride-1 steps IS the regression
    for _ in range(20):
        det.on_step(_rec(grants=DECODE, sync_s=0.09, wall_s=0.1))
    assert det.active
    (alert,) = store.alerts(kind="host_sync_regression", active_only=True)
    assert alert.data["sync_share"] > 0.5


def test_host_sync_quiescent_under_budget():
    det, store, _ = _armed(HostSyncRegressionDetector)
    for _ in range(20):
        det.on_step(_rec(grants=DECODE, sync_s=0.01, wall_s=0.1))
    assert not det.active and store.alerts() == []


def test_spec_collapse_fires_and_quiescent():
    det, store, _ = _armed(SpecCollapseDetector)
    for _ in range(8):
        det.on_step(_rec(grants=DECODE, spec=(1, 9)))   # 10% acceptance
    assert det.active
    (alert,) = store.alerts(kind="spec_acceptance_collapse",
                            active_only=True)
    assert alert.data["acceptance_rate"] < 0.2
    det2, store2, _ = _armed(SpecCollapseDetector)
    for _ in range(8):
        det2.on_step(_rec(grants=DECODE, spec=(9, 1)))  # healthy
    assert not det2.active and store2.alerts() == []
    # non-spec steps (0/0) never divide by zero nor fire
    det3, store3, _ = _armed(SpecCollapseDetector)
    for _ in range(8):
        det3.on_step(_rec(grants=DECODE))
    assert not det3.active


def test_adapter_swap_storm_fires_and_quiescent():
    det, store, _ = _armed(AdapterSwapStormDetector)
    for _ in range(10):
        det.on_step(_rec(grants=DECODE, adapter_swaps=1))
    assert det.active
    (alert,) = store.alerts(kind="adapter_swap_storm", active_only=True)
    assert alert.data["swaps_per_step"] >= 0.5
    det2, store2, _ = _armed(AdapterSwapStormDetector)
    recs = [_rec(grants=DECODE, adapter_swaps=1 if i == 0 else 0)
            for i in range(10)]
    for r in recs:
        det2.on_step(r)                     # one cold swap-in: normal
    assert not det2.active and store2.alerts() == []


def test_swap_stall_fires_and_quiescent():
    det, store, _ = _armed(SwapStallDetector)
    for i in range(12):
        det.on_step(_rec(grants=DECODE,
                         swap_out=4096 if i % 2 else None))
    assert det.active
    (alert,) = store.alerts(kind="swap_stall", active_only=True)
    assert alert.data["swap_bytes"] > 0
    det2, store2, _ = _armed(SwapStallDetector)
    for i in range(12):
        det2.on_step(_rec(grants=DECODE,
                          swap_in=4096 if i == 0 else None))
    assert not det2.active and store2.alerts() == []


def test_detectors_subscribe_to_recorder_scripted_shape():
    """The scripted ramp-thrash shape THROUGH the recorder: detectors
    ride FlightRecorder.subscribe and see completed StepRecords —
    the tier-1 proof the smoke acceptance names."""
    rec = FlightRecorder(capacity=64)
    store = MetricsStore()
    dets = default_detectors(store)
    assert {d.kind for d in dets} == {
        "ramp_thrash", "host_sync_regression",
        "spec_acceptance_collapse", "adapter_swap_storm", "swap_stall"}
    for d in dets:
        rec.subscribe(d.on_step)
    for _ in range(8):
        sid = rec.begin_step(
            scheduler="fused", kind="mixed", grants=PREFILL,
            tokens_scheduled=16, token_budget=32, queue_depth=3,
            free_blocks=0, total_blocks=8, pipeline_inflight=1,
            preemptions=(5,), admit_s=0.0, schedule_s=0.0,
            dispatch_s=0.01, t_begin=100.0)
        rec.finish_step(sid, 0.001, 0.0)
    (thrash,) = [d for d in dets if d.kind == "ramp_thrash"]
    assert thrash.active, "scripted ramp-thrash shape did not fire"
    assert store.alerts(kind="ramp_thrash", active_only=True)
    # the other four stay quiet on this shape
    assert not any(d.active for d in dets if d is not thrash)
    # unsubscribe detaches: further steps change nothing
    for d in dets:
        rec.unsubscribe(d.on_step)
    n = len(store.alerts())
    sid = rec.begin_step(
        scheduler="fused", kind="mixed", grants=PREFILL,
        tokens_scheduled=16, token_budget=32, queue_depth=3,
        free_blocks=0, total_blocks=8, pipeline_inflight=1,
        preemptions=(5,), admit_s=0.0, schedule_s=0.0,
        dispatch_s=0.01, t_begin=200.0)
    rec.finish_step(sid, 0.001, 0.0)
    assert len(store.alerts()) == n


def test_raising_subscriber_cannot_crash_finish_step():
    rec = FlightRecorder(capacity=8)
    seen = []

    def bad(r):
        raise RuntimeError("detector bug")

    rec.subscribe(bad)
    rec.subscribe(seen.append)
    sid = rec.begin_step(
        scheduler="fused", kind="decode", grants=DECODE,
        tokens_scheduled=2, token_budget=32, queue_depth=0,
        free_blocks=None, total_blocks=None, pipeline_inflight=1,
        preemptions=(), admit_s=0.0, schedule_s=0.0, dispatch_s=0.01,
        t_begin=100.0)
    rec.finish_step(sid, 0.0, 0.0)          # must not raise
    assert len(seen) == 1 and seen[0].step_id == sid


# ---------------------------------------------------------------------------
# gauge staleness (satellite): stamps + gauge_last_sample_age_s
# ---------------------------------------------------------------------------

def test_gauge_sample_age_computed_at_read_time():
    tel = ServingTelemetry()
    # before any loop pass: age reads as uptime, not a fresh 0
    assert tel.get_gauges()["gauge_last_sample_age_s"] >= 0.0
    tel.mark_gauge_sample()
    assert tel.get_gauges()["gauge_last_sample_age_s"] < 0.05
    time.sleep(0.06)
    age = tel.get_gauges()["gauge_last_sample_age_s"]
    assert age >= 0.05
    # an out-of-loop writer (the watchdog's server_healthy flip) does
    # NOT refresh the sampling mark — only mark_gauge_sample does
    tel.set_gauge("server_healthy", 0.0)
    assert tel.get_gauges()["gauge_last_sample_age_s"] >= age
    # per-gauge write stamps surface in the snapshot
    snap = tel.snapshot()
    assert snap["gauge_ages"]["server_healthy"] < 0.05
    assert snap["gauges"]["gauge_last_sample_age_s"] >= age
    # and the age is a real exposition family
    assert ("# TYPE paddle_tpu_serving_gauge_last_sample_age_s gauge"
            in tel.prometheus_text())
    # reset clears the stamps
    tel.reset()
    assert tel.snapshot()["gauge_ages"] == {}


# ---------------------------------------------------------------------------
# serve-backed tests (tiny model)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("chunk_size", 16)
    return LLMEngine(model, scheduler="fused", **kw)


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 96, size=(n,)).astype(np.int32) for n in sizes]


def test_serve_feeds_store_and_reports(tiny_model):
    """End-to-end: the loop feeds gauges/counters as time series, the
    token path feeds per-tenant latency, slo_report carries the lot,
    and NO pathology detector false-positives on a healthy serve (the
    quiescent half of the detector acceptance)."""
    eng = _engine(tiny_model)
    srv = AsyncLLMServer(
        eng, max_queue_size=16, flight_recorder=True, metrics_store=True,
        slos=[SLO("all_ttft", "ttft_p99", target_s=60.0, window_s=30.0)],
        metrics_interval_s=0.0, slo_interval_s=0.01)
    assert len(srv.pathology_detectors) == 5    # default set armed
    with srv:
        hs = [srv.submit(p, max_new_tokens=6)
              for p in _prompts(1, (7, 12, 5, 9))]
        outs = [h.result(timeout=300) for h in hs]
    assert all(len(o.token_ids) == 6 for o in outs)
    store = srv.metrics_store
    # gauge + counter series landed with monotonic growth on counters
    assert store.series("queue_depth") is not None
    toks = store.series("tokens_emitted")
    assert toks is not None and toks.last()[1] == 24
    vals = toks.values()
    assert vals == sorted(vals)                 # cumulative
    # latency series are tenant-labeled (tenant 0 = base)
    assert len(store.values("ttft_s", labels={"tenant": "0"})) == 4
    rep = srv.slo_report()
    (r,) = rep["slos"]
    assert r["slo"] == "all_ttft" and r["samples_slow"] == 4
    # quiescent: a healthy serve fires NO pathology alert
    assert all(not on for on in rep["pathologies"].values())
    assert [a for a in rep["alerts"] if a["kind"] != "slo_burn"] == []
    assert isinstance(rep["text"], str) and "all_ttft" in rep["text"]
    assert rep["gauge_last_sample_age_s"] >= 0.0
    # per-tenant latency snapshot mirrors the global families
    assert set(rep["tenant_latency"]["0"]) == {
        "ttft", "inter_token", "e2e", "queue_wait"}
    assert rep["tenant_latency"]["0"]["ttft"]["count"] == 4


def test_per_tenant_histograms_split_the_traffic(tiny_model):
    """Two tenants through one engine: each tenant's histograms count
    ITS requests only, the prometheus exposition carries tenant-labeled
    series under the global family header, and a tenant-scoped SLO
    reads only that tenant's samples."""
    from paddle_tpu.serving import AdapterStore, random_lora_weights

    cfg = tiny_model.config
    store = AdapterStore(cfg, rank=4)
    aid = store.register(random_lora_weights(cfg, rank=4, seed=3,
                                             scale=0.05), alpha=1.0)
    eng = _engine(tiny_model, adapter_store=store, adapter_cache_slots=2)
    srv = AsyncLLMServer(eng, max_queue_size=16, metrics_store=True,
                         metrics_interval_s=0.0)
    with srv:
        hs = [srv.submit(p, max_new_tokens=4)
              for p in _prompts(2, (6, 8))]
        ha = [srv.submit(p, max_new_tokens=4, adapter_id=aid)
              for p in _prompts(3, (7,))]
        for h in hs + ha:
            h.result(timeout=300)
    snap = srv.telemetry.snapshot()
    tl = snap["tenant_latency"]
    assert tl["0"]["ttft"]["count"] == 2
    assert tl[str(aid)]["ttft"]["count"] == 1
    assert tl["0"]["e2e"]["count"] == 2
    # global histogram still blends everything
    assert snap["latency"]["ttft"]["count"] == 3
    text = srv.telemetry.prometheus_text()
    assert f'paddle_tpu_serving_ttft_seconds_count{{tenant="{aid}"}} 1' \
        in text
    # exactly ONE TYPE header per family despite the tenant series
    assert text.count("# TYPE paddle_tpu_serving_ttft_seconds "
                      "histogram") == 1
    # tenant-scoped store reads split too
    ms = srv.metrics_store
    assert len(ms.values("ttft_s", labels={"tenant": str(aid)})) == 1
    assert len(ms.values("ttft_s", labels={"tenant": "0"})) == 2


def test_per_tenant_observe_strictness():
    tel = ServingTelemetry()
    tel.observe("ttft_s", 0.1, tenant=2)            # fine
    with pytest.raises(KeyError, match="per-tenant"):
        tel.observe("admission_stall_s", 0.1, tenant=2)
    with pytest.raises(KeyError, match="unknown labeled gauge"):
        tel.set_labeled_gauge("slo_burn_rates", "x", 1.0)
    # histogram merge guards mismatched bounds
    from paddle_tpu.profiler.serving_telemetry import LatencyHistogram
    a, b = LatencyHistogram(), LatencyHistogram(bounds=(0.1, 1.0))
    with pytest.raises(ValueError, match="bounds"):
        a.merge(b)
    a2 = LatencyHistogram()
    a.observe(0.05)
    a2.observe(0.5)
    a.merge(a2)
    assert a.count == 2 and a.maximum == 0.5


def test_metrics_store_off_path_is_detached(tiny_model):
    """metrics_store=None wires NOTHING — the off path the overhead
    budget rides on is the single detached-attribute check (the rest of
    the serving suite exercises actual serving without a store)."""
    eng = _engine(tiny_model)
    srv = AsyncLLMServer(eng, max_queue_size=8)
    assert srv.metrics_store is None and srv.slo_engine is None
    assert srv.pathology_detectors == []
    # False (the pathology_detectors=False convention) is the same
    # detached off-path, not a crash in the first loop pass
    srv_f = AsyncLLMServer(eng, max_queue_size=8, metrics_store=False)
    assert srv_f.metrics_store is None
    rep = srv.slo_report()                  # degrades, never raises
    assert rep["slos"] == [] and rep["alerts"] == []
    assert rep["tenant_latency"] == {}
    # slos=... implies a store even when none was passed
    srv2 = AsyncLLMServer(eng, max_queue_size=8,
                          slos=[SLO("x", "ttft_p99", target_s=1.0)])
    assert srv2.metrics_store is not None
    assert srv2.slo_engine.store is srv2.metrics_store
    # a recorder WITHOUT a store arms no detectors (and vice versa)
    srv3 = AsyncLLMServer(eng, max_queue_size=8, flight_recorder=True)
    assert srv3.pathology_detectors == []
    srv4 = AsyncLLMServer(eng, max_queue_size=8, metrics_store=True)
    assert srv4.pathology_detectors == []


def test_hung_server_gauge_age_grows(tiny_model):
    """The satellite's acceptance: a HUNG serve loop exposes stale
    gauges — gauge_last_sample_age_s must GROW past step_timeout_s
    while the watchdog's hung flip (server_healthy=0) is visible in
    the same scrape."""
    eng = _engine(tiny_model)
    fi = FaultInjector().hang_at_step(3, seconds=60.0, interruptible=True)
    srv = AsyncLLMServer(eng, max_queue_size=8, fault_injector=fi,
                         step_timeout_s=0.3)
    with srv:
        h = srv.submit(_prompts(5, (7,))[0], max_new_tokens=8)
        # wait for the health verdict AND the watchdog's gauge flip
        # (the watchdog thread ticks on its own period, a beat after
        # the heartbeat-age computation already answers "hung")
        deadline = time.monotonic() + 30.0
        g1 = None
        while time.monotonic() < deadline:
            g = srv.telemetry.get_gauges()
            if srv.health()["state"] == "hung" \
                    and g["server_healthy"] == 0.0:
                g1 = g
                break
            time.sleep(0.01)
        assert g1 is not None, "hung state + gauge flip never observed"
        assert g1["gauge_last_sample_age_s"] > 0.3
        time.sleep(0.15)
        g2 = srv.telemetry.get_gauges()
        assert g2["gauge_last_sample_age_s"] > g1["gauge_last_sample_age_s"]
        # the exposition carries the same growing number
        text = srv.telemetry.prometheus_text()
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith(
                       "paddle_tpu_serving_gauge_last_sample_age_s")]
        assert float(line.split()[-1]) > 0.3
        h.result(timeout=240)                   # watchdog interrupts
    # healthy loop passes drive the age back under the poll interval
    assert fi.fired == [("hang", 3, 60.0)]


def test_router_fleet_slo_report(tiny_model):
    """Fleet aggregation: per-replica reports, tenant histograms merged
    BUCKET-WISE, fleet SLOs evaluated over samples concatenated across
    replica stores, and the router-level store's placement series."""
    slo = [SLO("fleet_ttft", "ttft_p99", target_s=120.0, window_s=60.0)]
    srvs = [AsyncLLMServer(_engine(tiny_model), max_queue_size=8,
                           replica=i, metrics_store=True, slos=list(slo),
                           metrics_interval_s=0.0)
            for i in range(2)]
    router = ReplicaRouter(srvs, policy="least_loaded",
                           metrics_store=True)
    with router:
        hs = [router.submit(p, max_new_tokens=3, replica=i % 2)
              for i, p in enumerate(_prompts(6, (6, 9)))]
        for h in hs:
            h.result(timeout=300)
        rep = router.slo_report()
    assert set(rep["replicas"]) == {0, 1}
    per_rep = [rep["replicas"][i]["tenant_latency"]["0"]["ttft"]["count"]
               for i in (0, 1)]
    assert per_rep == [1, 1]
    fleet = rep["fleet"]
    assert fleet["tenant_latency"]["0"]["ttft"]["count"] == 2
    (fr,) = fleet["slos"]
    assert fr["slo"] == "fleet_ttft" and fr["samples_slow"] == 2
    assert not fr["burning"]
    assert fleet["pathologies"] == {}
    # router-level store fed the placement series
    names = {s["name"] for s in rep["router"]["series"]}
    assert "router_outstanding" in names
    assert "router_replica_outstanding" in names
    assert "fleet" in rep["text"]
