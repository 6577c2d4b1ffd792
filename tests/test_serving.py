"""paddle_tpu.serving — async server over the LLM engine.

Coverage the ISSUE asks for, all CPU-fast: streaming order (pipelined
dispatch stays token-exact vs the engine's own generate()), cancellation
frees paged pool blocks, deadline expiry (queued AND running), admission
backpressure on a full queue, and the telemetry snapshot/prometheus
schema. Dense (pipeline depth 2), paged (depth 1) and speculative
engines all serve through the same loop. Engines are module-scoped
fixtures — program compilation dominates CPU wall, and a drained engine
is reusable — and the long soak variant is marked ``slow`` so tier-1
wall time is unaffected."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler.serving_telemetry import (GAUGES, LatencyHistogram,
                                                   ServingTelemetry, STAGES)
from paddle_tpu.serving import (AdmissionQueue, AsyncLLMServer,
                                ServerQueueFull)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, cache_impl="dense", **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("chunk_size", 16)
    if cache_impl == "paged":
        kw.setdefault("block_size", 8)
    return LLMEngine(model, cache_impl=cache_impl, **kw)


@pytest.fixture(scope="module")
def dense_eng(tiny_model):
    return _engine(tiny_model)


@pytest.fixture(scope="module")
def paged_eng(tiny_model):
    return _engine(tiny_model, "paged")


@pytest.fixture(scope="module")
def paged_b1_eng(tiny_model):
    return _engine(tiny_model, "paged", max_batch=1, horizon=1)


def _fresh(eng):
    """Reusing a module-scoped engine: verify the previous test drained
    it, then clear bookkeeping."""
    assert all(s is None for s in eng.slots)
    assert not eng.waiting
    eng.finished_outputs.clear()
    eng.reset_stats()
    return eng


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 96, size=(n,)).astype(np.int32) for n in sizes]


# ---------------------------------------------------------------------------
# streaming exactness — pipelined serve == engine.generate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_impl", ["dense", "paged"])
def test_streaming_order_matches_generate(request, cache_impl):
    """Tokens stream per request, in order, and the full streams equal
    the plain engine's generate() outputs — for the DENSE engine this
    exercises pipeline depth 2 (step N+1 dispatched before step N's
    sync), for PAGED depth 1."""
    eng = _fresh(request.getfixturevalue(
        "dense_eng" if cache_impl == "dense" else "paged_eng"))
    prompts = _prompts(1, (5, 11, 3, 8))
    ref = [o.token_ids for o in eng.generate(prompts, max_new_tokens=6)]
    server = AsyncLLMServer(eng, max_queue_size=8)
    assert server.pipeline_depth == (1 if cache_impl == "paged" else 2)
    with server:
        handles = [server.submit(p, max_new_tokens=6) for p in prompts]
        streams = [list(h.tokens(timeout=120)) for h in handles]
        results = [h.result(timeout=120) for h in handles]
    assert streams == ref
    for r, tokens in zip(results, ref):
        assert r.token_ids == tokens
        assert r.finish_reason == "length"
        assert r.ttft_s is not None and r.e2e_s >= r.ttft_s
    snap = server.telemetry.snapshot()
    assert snap["counters"]["requests_finished"] == 4
    assert snap["counters"]["tokens_emitted"] == 24


def test_speculative_engine_serves_exact(tiny_model, dense_eng):
    """The spec engine (in-graph prompt-lookup windows) streams through
    the same pipelined loop, greedy-token-exact vs plain decode."""
    # repetitive prompt = the workload where drafts actually accept
    base = _prompts(2, (6,))[0]
    p = np.tile(base, 5)[:28]
    (ref,) = _fresh(dense_eng).generate([p], max_new_tokens=8)
    eng = _engine(tiny_model, max_batch=1, speculative_k=3, horizon=2)
    with AsyncLLMServer(eng) as server:
        h = server.submit(p, max_new_tokens=8)
        assert list(h.tokens(timeout=120)) == ref.token_ids
        assert h.result().finish_reason == "length"


def test_mid_stream_submission(dense_eng):
    """A request submitted while another decodes joins via continuous
    batching without perturbing the first stream."""
    eng = _fresh(dense_eng)
    p1, p2 = _prompts(3, (9, 4))
    ref1 = [o.token_ids for o in eng.generate([p1], max_new_tokens=10)]
    ref2 = [o.token_ids for o in eng.generate([p2], max_new_tokens=5)]
    with AsyncLLMServer(eng) as server:
        h1 = server.submit(p1, max_new_tokens=10)
        it1 = h1.tokens(timeout=120)
        first = [next(it1) for _ in range(2)]
        h2 = server.submit(p2, max_new_tokens=5)
        rest = list(it1)
        assert first + rest == ref1[0]
        assert list(h2.tokens(timeout=120)) == ref2[0]


# ---------------------------------------------------------------------------
# lifecycle: cancellation, deadlines, backpressure
# ---------------------------------------------------------------------------

def test_cancellation_frees_pool_blocks(paged_b1_eng):
    """Cancelling a running request on the PAGED engine frees its slot
    and returns every pool block at the next step boundary."""
    eng = _fresh(paged_b1_eng)
    total = eng.n_blocks
    with AsyncLLMServer(eng) as server:
        h = server.submit(_prompts(4, (12,))[0], max_new_tokens=40)
        it = h.tokens(timeout=120)
        got = [next(it)]          # running for sure
        h.cancel()
        got += list(it)           # drains buffered tokens, then ends
        res = h.result(timeout=120)
        assert res.finish_reason == "cancelled"
        assert res.token_ids[:len(got)] == got
        assert len(res.token_ids) < 40
        # blocks freed at the cancel sweep, well before drain completes
        deadline = time.monotonic() + 30
        while len(eng._free_blocks) != total:
            assert time.monotonic() < deadline, "pool blocks leaked"
            time.sleep(0.01)
        assert all(s is None for s in eng.slots)
    assert server.telemetry.counters["requests_cancelled"] == 1


@pytest.mark.parametrize("cache_impl", ["dense", "paged"])
def test_deadline_expiry_frees_slot(request, tiny_model, cache_impl):
    """A running request whose deadline passes finishes with reason
    'deadline', its slot (and pool blocks) free immediately, and a
    queued request with an already-hopeless deadline expires without
    ever being admitted."""
    if cache_impl == "paged":
        eng = _fresh(request.getfixturevalue("paged_b1_eng"))
    else:
        eng = _engine(tiny_model, max_batch=1, horizon=1)
    server = AsyncLLMServer(eng)
    # pace emission at ~10ms/token so the deadline deterministically
    # lands mid-stream on any machine, warm or cold jit cache
    orig_on_token = server._on_token
    server._on_token = lambda rid, tok: (time.sleep(0.01),
                                         orig_on_token(rid, tok))
    with server:
        h = server.submit(_prompts(5, (10,))[0], max_new_tokens=50,
                          deadline_s=0.25)
        # second request waits behind the first, and its own deadline
        # expires while queued (the first holds the only slot longer)
        h2 = server.submit(_prompts(5, (6,))[0], max_new_tokens=4,
                           deadline_s=0.05)
        r = h.result(timeout=120)
        r2 = h2.result(timeout=120)
    assert r.finish_reason == "deadline"
    assert 0 < len(r.token_ids) < 50
    assert r2.finish_reason == "deadline"
    assert r2.token_ids == [] and r2.queue_wait_s is None
    if cache_impl == "paged":
        assert len(eng._free_blocks) == eng.n_blocks
    assert all(s is None for s in eng.slots)
    assert server.telemetry.counters["requests_expired"] == 2


def test_backpressure_full_queue(tiny_model):
    """With the engine thread not draining, a bounded queue rejects
    (block=False) or times out (block=True) — and counts rejections."""
    eng = _engine(tiny_model)  # programs never compile: loop not started
    server = AsyncLLMServer(eng, max_queue_size=2)
    # deterministic: accept submissions without starting the drain thread
    server._accepting = True
    p = _prompts(6, (5,))[0]
    server.submit(p, max_new_tokens=4)
    server.submit(p, max_new_tokens=4)
    with pytest.raises(ServerQueueFull):
        server.submit(p, max_new_tokens=4, block=False)
    t0 = time.monotonic()
    with pytest.raises(ServerQueueFull):
        server.submit(p, max_new_tokens=4, timeout=0.05)
    assert time.monotonic() - t0 >= 0.04
    assert server.telemetry.counters["requests_rejected_queue_full"] == 2
    # backpressure RELEASES: free a slot and the blocked submit lands
    server._queue.pop()
    h = server.submit(p, max_new_tokens=4, timeout=1.0)
    assert h is not None


def test_submit_validation_is_synchronous(tiny_model):
    eng = _engine(tiny_model, "paged", kv_pool_blocks=2)  # never compiles
    server = AsyncLLMServer(eng)
    server._accepting = True
    with pytest.raises(ValueError, match="empty"):
        server.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="capacity"):
        server.submit(np.ones((70,), np.int32))
    with pytest.raises(ValueError, match="pool"):
        server.submit(np.ones((30,), np.int32))  # 4 blocks > pool of 2


def test_admission_queue_fifo_and_remove():
    q = AdmissionQueue(max_size=3)
    q.put("a"), q.put("b"), q.put("c")
    with pytest.raises(ServerQueueFull):
        q.put("d", block=False)
    assert q.remove("b") is True and q.remove("zz") is False
    q.put("d", block=False)  # space from the removal
    assert [q.pop(), q.pop(), q.pop(), q.pop()] == ["a", "c", "d", None]


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_latency_histogram_quantiles_and_prometheus():
    h = LatencyHistogram(bounds=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.005, 0.005, 0.05, 2.0):
        h.observe(v)
    assert h.count == 5 and h.maximum == 2.0
    assert h.quantile(0.5) == 0.01      # bucket upper bound
    assert h.quantile(1.0) == 2.0       # overflow bucket -> observed max
    lines = h.prometheus_lines("x_seconds")
    assert 'x_seconds_bucket{le="+Inf"} 5' in lines
    assert any(line.startswith("x_seconds_sum") for line in lines)


def test_a_steps_buffers_are_freed_before_its_results_are_routed(dense_eng):
    """Results are routed (handles finished, clients woken) only once the
    finished step's PendingStep is gone: freeing its device buffers lets
    other threads run, and a client woken before that finds the engine
    still mid-step (an audit of a live replica's pool then races it)."""
    import gc
    eng = _fresh(dense_eng)
    server = AsyncLLMServer(eng, max_queue_size=16)
    last, alive_at_routing = [], []
    step_finish, handle_done = eng.step_finish, server._handle_done

    def spy_finish(pending):
        last[:] = [id(pending), type(pending)]
        return step_finish(pending)

    def spy_done(done):
        # with a later step in flight (the loop's own name for the last
        # begun step is then that one). No step begins between a finish
        # and its routing, so an object of that type at that address is
        # the finished step itself
        if eng._inflight:
            alive_at_routing.append(any(
                id(o) == last[0] for o in gc.get_objects()
                if type(o) is last[1]))
        return handle_done(done)
    eng.step_finish, server._handle_done = spy_finish, spy_done
    try:
        with server:
            for h in [server.submit(p, max_new_tokens=5)
                      for p in _prompts(3, (7, 12, 5))]:
                h.result(timeout=240)
    finally:
        eng.step_finish = step_finish
    assert alive_at_routing and not any(alive_at_routing)


def test_telemetry_snapshot_schema_and_attribution(dense_eng):
    """The snapshot carries every named stage, the latency histograms,
    and an attribution that explains (nearly) all of a busy serve
    window."""
    eng = _fresh(dense_eng)
    prompts = _prompts(7, (7, 12, 5, 9, 6, 10))
    server = AsyncLLMServer(eng, max_queue_size=16)
    with server:
        t0 = time.perf_counter()
        handles = [server.submit(p, max_new_tokens=8) for p in prompts]
        for h in handles:
            h.result(timeout=240)
        wall = time.perf_counter() - t0
    snap = server.telemetry.snapshot(wall_s=wall)
    for key in ("uptime_s", "counters", "gauges", "stages_s", "latency",
                "attribution", "prefill_token_share"):
        assert key in snap, key
    assert set(STAGES) <= set(snap["stages_s"])
    assert set(GAUGES) <= set(snap["gauges"])
    # a drained server's point-in-time gauges read empty
    assert snap["gauges"]["queue_depth"] == 0
    assert snap["gauges"]["running_slots"] == 0
    for hist in ("ttft", "inter_token", "e2e", "queue_wait",
                 "admission_stall"):
        assert snap["latency"][hist]["count"] >= 1 \
            or hist in ("inter_token", "admission_stall")
        assert {"p50_s", "p90_s", "p99_s", "mean_s"} <= set(
            snap["latency"][hist])
    # legacy engine, whole prompts prefilled at admission: the share of
    # prefill work is visible and sane
    assert snap["counters"]["prefill_tokens"] == \
        sum(len(p) for p in prompts)
    assert 0.0 < snap["prefill_token_share"] < 1.0
    # requests outnumber slots: someone waited for a freed slot, so the
    # stall histogram observed admissions (fused keeps the VALUES ~0;
    # existence + counting is the schema contract here)
    assert snap["latency"]["admission_stall"]["count"] >= 1
    att = snap["attribution"]
    assert 0.0 < att["attributed_share"] <= 1.0
    # a busy window must be explained by the named stages: every piece of
    # the loop body lands in a stage, so >= 0.9 must hold deterministically
    assert att["attributed_share"] >= 0.9, att
    assert snap["counters"]["requests_finished"] == len(prompts)
    text = server.telemetry.prometheus_text()
    assert "# TYPE paddle_tpu_serving_requests_finished_total counter" \
        in text
    assert 'paddle_tpu_serving_stage_seconds_total{stage="host_sync"}' \
        in text
    assert "paddle_tpu_serving_ttft_seconds_bucket" in text
    assert "paddle_tpu_serving_admission_stall_seconds_bucket" in text
    assert "# TYPE paddle_tpu_serving_prefill_token_share gauge" in text
    assert "paddle_tpu_serving_prefill_tokens_total" in text
    for g in GAUGES:
        assert f"# TYPE paddle_tpu_serving_{g} gauge" in text, g


def test_telemetry_strict_names_and_register():
    """A typo'd stage/counter/gauge name must raise instead of silently
    forking the attribution into a phantom key; register() is the
    explicit extension escape hatch and survives reset()."""
    tel = ServingTelemetry()
    with pytest.raises(KeyError, match="unknown telemetry stage"):
        tel.add_stage("prefil_dispatch", 0.1)        # the typo scenario
    with pytest.raises(KeyError, match="unknown telemetry counter"):
        tel.inc("request_finished")                  # singular typo
    with pytest.raises(KeyError, match="unknown telemetry gauge"):
        tel.set_gauge("queue_dept", 3)
    # the prefix-cache names are declared (not phantom-forked) ...
    tel.inc("prefix_hit_tokens", 5)
    tel.inc("prefix_cow_blocks")
    tel.inc("prefix_evicted_blocks")
    tel.set_gauge("prefix_cached_blocks", 4)
    tel.set_gauge("prefix_cache_hit_rate", 0.5)
    # ... as is the multi-step decode dispatch counter
    tel.inc("multi_steps", 3)
    assert tel.snapshot()["counters"]["multi_steps"] == 3
    # ... and a typo'd variant still raises instead of forking
    with pytest.raises(KeyError, match="unknown telemetry counter"):
        tel.inc("prefix_hit_token")
    with pytest.raises(KeyError, match="unknown telemetry gauge"):
        tel.set_gauge("prefix_cache_hitrate", 0.5)
    # the speculative-serving names are declared (not phantom-forked) ...
    tel.inc("spec_proposed_tokens", 8)
    tel.inc("spec_accepted_tokens", 5)
    tel.set_gauge("spec_acceptance_rate", 5 / 8)
    assert tel.snapshot()["counters"]["spec_proposed_tokens"] == 8
    # ... and typo'd variants still raise instead of forking
    with pytest.raises(KeyError, match="unknown telemetry counter"):
        tel.inc("spec_proposed_token")
    with pytest.raises(KeyError, match="unknown telemetry gauge"):
        tel.set_gauge("spec_acceptence_rate", 0.5)
    # the fault-tolerance names are declared (not phantom-forked) ...
    tel.inc("requests_rejected_validation")
    tel.inc("requests_shed_deadline")
    tel.inc("requests_resumed")
    tel.inc("engine_restarts")
    tel.inc("faults_injected")
    tel.set_gauge("server_healthy", 1.0)
    # ... and their typos still raise
    with pytest.raises(KeyError, match="unknown telemetry counter"):
        tel.inc("request_rejected_validation")
    with pytest.raises(KeyError, match="unknown telemetry gauge"):
        tel.set_gauge("server_health", 1.0)
    # the multi-tenant names are declared (not phantom-forked) ...
    tel.inc("adapter_cache_hits", 2)
    tel.inc("adapter_cache_misses")
    tel.inc("adapter_swaps")
    tel.inc("embed_requests")
    tel.set_gauge("adapter_cache_occupancy", 0.5)
    # ... their typos still raise ...
    with pytest.raises(KeyError, match="unknown telemetry counter"):
        tel.inc("adapter_cache_hit")
    with pytest.raises(KeyError, match="unknown telemetry counter"):
        tel.inc("adapter_swap")
    with pytest.raises(KeyError, match="unknown telemetry gauge"):
        tel.set_gauge("adapter_cache_occupency", 0.5)
    # ... and the per-TENANT token counters are data-keyed (dynamic
    # tenant ids), surviving snapshot + exposition round trips
    tel.inc_tenant(0, 3)
    tel.inc_tenant(7, 5)
    snap_mt = tel.snapshot()
    assert snap_mt["counters"]["adapter_cache_hits"] == 2
    assert snap_mt["tenant_tokens"] == {"0": 3, "7": 5}
    assert 'tenant_tokens_total{tenant="7"} 5' in tel.prometheus_text()
    with pytest.raises(ValueError, match="register kind"):
        tel.register("histogram", "x")
    tel.register("stage", "custom_stage")
    tel.register("counter", "custom_total")
    tel.register("gauge", "custom_gauge")
    tel.add_stage("custom_stage", 0.5)
    tel.inc("custom_total", 2)
    tel.set_gauge("custom_gauge", 7)
    snap = tel.snapshot()
    assert snap["stages_s"]["custom_stage"] == 0.5
    assert snap["counters"]["custom_total"] == 2
    assert snap["gauges"]["custom_gauge"] == 7.0
    tel.reset()                                      # registration sticks
    tel.inc("custom_total")
    assert tel.counters["custom_total"] == 1
    assert tel.stage_s["custom_stage"] == 0.0
    text = tel.prometheus_text()
    assert "paddle_tpu_serving_custom_total_total 1" in text
    assert "# TYPE paddle_tpu_serving_custom_gauge gauge" in text


def test_engine_stage_stats_accumulate(dense_eng):
    """The engine's split stage stats (dispatch / host_sync / emit) are
    populated by the begin/finish path and reset cleanly."""
    eng = _fresh(dense_eng)
    eng.generate(_prompts(8, (6,)), max_new_tokens=4)
    assert eng.stats["dispatch_time_s"] > 0
    assert eng.stats["host_sync_time_s"] > 0
    assert eng.stats["emit_time_s"] > 0
    assert eng.stats["decode_time_s"] >= (
        eng.stats["dispatch_time_s"] + eng.stats["host_sync_time_s"]) * 0.99
    eng.reset_stats()
    assert eng.stats["dispatch_time_s"] == 0.0


def test_paged_engine_rejects_pipelined_begin(paged_eng):
    """Depth-1 contract: the paged engine refuses a second step_begin()
    while one step is in flight (its allocator needs post-step lens)."""
    eng = _fresh(paged_eng)
    eng.add_request(_prompts(9, (6,))[0], max_new_tokens=4)
    pending = eng.step_begin()
    assert pending is not None
    with pytest.raises(RuntimeError, match="pipeline"):
        eng.step_begin()
    eng.step_finish(pending)
    while eng.has_unfinished():
        eng.step()


# ---------------------------------------------------------------------------
# soak (excluded from tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_soak_churn(tiny_model):
    """Longer churn: 24 mixed requests through 2 slots with sprinkled
    cancels and deadlines; every handle reaches a terminal state, greedy
    survivors stay exact, no pool-block leaks."""
    sizes = [5 + (i * 7) % 19 for i in range(24)]
    prompts = _prompts(10, sizes)
    ref = {i: o.token_ids for i, o in enumerate(
        _engine(tiny_model, "paged").generate(prompts, max_new_tokens=10))}
    eng = _engine(tiny_model, "paged")
    with AsyncLLMServer(eng, max_queue_size=32) as server:
        handles = {}
        for i, p in enumerate(prompts):
            kw = {}
            if i % 11 == 3:
                kw["deadline_s"] = 0.02
            handles[i] = server.submit(p, max_new_tokens=10, **kw)
            if i % 7 == 5:
                handles[i].cancel()
        results = {i: h.result(timeout=600) for i, h in handles.items()}
    for i, r in results.items():
        assert r.finished
        if r.finish_reason == "length":
            assert r.token_ids == ref[i]
        else:
            assert r.finish_reason in ("cancelled", "deadline")
    assert len(eng._free_blocks) == eng.n_blocks
