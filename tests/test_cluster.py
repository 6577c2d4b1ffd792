"""Multichip serving (paddle_tpu/serving/cluster.py) — TP engine parity,
TP kernel shard_map parity, and the ReplicaRouter's placement / failover
/ drain contracts. All tier-1 tests run on the conftest `tp_mesh` (4
virtual CPU devices, tiny shapes); the 8-device big-mesh variant is
gated ``slow``.

The acceptance bars from the ISSUE:

* TP engine (tp=4, CPU) is TOKEN-EXACT greedy-parity with the
  single-chip engine for dense AND paged cache impls, prefix cache on
  and off (``test_tp_engine_greedy_parity``).
* Router failover converts a dead replica's queued requests into
  resubmission (identical tokens on a survivor), in-flight ones into
  ``finish_reason="replica_lost"``, and the survivors' pool invariants
  hold (``test_router_failover_mid_stream``; PADDLE_TPU_POOL_CHECKS is
  armed suite-wide by conftest).
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (AsyncLLMServer, FaultInjector,
                                ReplicaRouter, RestartPolicy)
from paddle_tpu.serving.cluster import shard_model_tp, tp_engine

V = 96


def _build_model():
    paddle.seed(7)
    cfg = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def ref_model():
    return _build_model()


@pytest.fixture(scope="module")
def tp_model(tp_mesh):
    """Same weights as ref_model (same seed), laid out TP-sharded."""
    return shard_model_tp(_build_model(), tp_mesh)


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, size=(n,)).astype(np.int32) for n in sizes]


ENGINE_CONFIGS = {
    "dense_legacy": dict(),
    "dense_fused": dict(scheduler="fused"),
    "paged": dict(cache_impl="paged", block_size=8, scheduler="fused"),
    "paged_prefix": dict(cache_impl="paged", block_size=8,
                         scheduler="fused", enable_prefix_cache=True),
}

# the ISSUE's tier-1 acceptance matrix is dense AND paged, prefix cache
# on and off — dense×fused adds a 4th engine-compile pair for a scheduler
# the paged configs already exercise at TP, so it rides the slow lane
# (tier-1 wall budget)
_CONFIG_PARAMS = [
    # tier-1 wall budget: dense_fused (PR 6) and the plain paged cell
    # (PR 14 — subsumed by paged_prefix, the richer composition) ride
    # the slow lane
    pytest.param(name, marks=[pytest.mark.slow]
                 if name in ("dense_fused", "paged") else [])
    for name in ENGINE_CONFIGS
]


# ---------------------------------------------------------------------------
# Level 1 — the TP engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", _CONFIG_PARAMS)
def test_tp_engine_greedy_parity(tp_mesh, tp_model, ref_model, config):
    """tp=4 virtual devices, CPU: token-exact greedy parity vs the
    single-chip engine — dense and paged, prefix cache off and on. The
    KV buffers must be REALLY sharded (not replicated) for the test to
    mean anything."""
    kw = dict(ENGINE_CONFIGS[config])
    prompts = _prompts(3, (9, 5, 17))
    ref = LLMEngine(ref_model, max_batch=2, max_seq_len=64, chunk_size=16,
                    **kw)
    want = [o.token_ids for o in ref.generate(prompts, max_new_tokens=8)]

    eng = LLMEngine(tp_model, max_batch=2, max_seq_len=64, chunk_size=16,
                    mesh=tp_mesh, **kw)
    assert eng.tp_degree() == 4
    # the pools genuinely shard on the kv-head dim: each shard holds
    # kvh / 4 heads
    spec = eng._k[0].sharding.spec
    head_dim = 1 if kw.get("cache_impl") == "paged" else 2
    assert spec[head_dim] == "tp", spec
    shard_shape = next(iter(eng._k[0].addressable_shards)).data.shape
    assert shard_shape[head_dim] == eng._k[0].shape[head_dim] // 4
    got = [o.token_ids for o in eng.generate(prompts, max_new_tokens=8)]
    assert got == want


@pytest.mark.slow   # tier-1 wall budget (PR 14): TP parity stays
# tier-1 via the engine-level matrix above, and TP-through-server is
# exercised by __graft_entry__ dryrun's serve=engine_tp leg
def test_tp_engine_serves_through_async_server(tp_mesh, tp_model,
                                               ref_model):
    """The TP paged engine behind AsyncLLMServer streams the identical
    tokens the single-chip engine generates (prefill + fused mixed steps
    + the pipelined serve loop, all with sharded pools)."""
    prompts = _prompts(11, (21, 6))
    ref = LLMEngine(ref_model, max_batch=2, max_seq_len=64, chunk_size=16,
                    cache_impl="paged", block_size=8, scheduler="fused")
    want = [o.token_ids for o in ref.generate(prompts, max_new_tokens=6)]

    eng = LLMEngine(tp_model, max_batch=2, max_seq_len=64, chunk_size=16,
                    cache_impl="paged", block_size=8, scheduler="fused",
                    mesh=tp_mesh)
    server = AsyncLLMServer(eng, max_queue_size=4)
    server.start()
    try:
        handles = [server.submit(p, max_new_tokens=6) for p in prompts]
        results = [h.result(timeout=300) for h in handles]
    finally:
        server.stop()
    assert [r.token_ids for r in results] == want


def test_tp_engine_rejects_indivisible_kv_heads(tp_mesh):
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=96,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    with pytest.raises(ValueError, match="num_key_value_heads"):
        LLMEngine(m, max_batch=1, max_seq_len=32, mesh=tp_mesh)


# ---------------------------------------------------------------------------
# TP kernels — shard_map'd Pallas decode/append (interpret mode)
# ---------------------------------------------------------------------------

def _kernel_inputs(rng, B=2, Hq=8, Hkv=4, D=16, BS=8, MB=4, NB=9):
    q = rng.standard_normal((B, Hq, D)).astype(np.float32)
    kp = rng.standard_normal((NB, Hkv, BS, D)).astype(np.float32)
    vp = rng.standard_normal((NB, Hkv, BS, D)).astype(np.float32)
    tables = np.array([[0, 1, 2, -1], [3, 4, -1, -1]], np.int32)
    lens = np.array([19, 10], np.int32)
    return q, kp, vp, tables, lens


def test_tp_kernel_decode_parity(tp_mesh, rng):
    """The shard_map'd decode kernel (kv-heads over "tp") matches the
    unsharded kernel bit-for-bit in interpret mode — fused new-token
    write included (per-shard pools round-trip through the aliased
    outputs)."""
    from paddle_tpu.ops.kernels.paged_attention import (
        paged_attention_decode, paged_attention_decode_tp)
    q, kp, vp, tables, lens = _kernel_inputs(rng)
    nk = rng.standard_normal((2, 4, 16)).astype(np.float32)
    nv = rng.standard_normal((2, 4, 16)).astype(np.float32)
    ref = paged_attention_decode(q, kp.copy(), vp.copy(), tables, lens,
                                 new_k=nk, new_v=nv)
    got = paged_attention_decode_tp(q, kp.copy(), vp.copy(), tables, lens,
                                    tp_mesh, new_k=nk, new_v=nv)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                   rtol=1e-5, atol=1e-5)
    # read-only form too (no fused write)
    ref_o = paged_attention_decode(q, kp, vp, tables, lens)
    got_o = paged_attention_decode_tp(q, kp, vp, tables, lens, tp_mesh)
    np.testing.assert_allclose(np.asarray(ref_o), np.asarray(got_o),
                               rtol=1e-5, atol=1e-5)


def test_tp_kernel_append_parity(tp_mesh, rng):
    """Append (mixed prefill+decode) kernel under shard_map: q_lens
    mixing full chunks, partial chunks and an idle (0) slot."""
    from paddle_tpu.ops.kernels.paged_attention import (
        paged_attention_append, paged_attention_append_tp)
    q, kp, vp, tables, lens = _kernel_inputs(rng)
    S = 4
    qa = rng.standard_normal((2, S, 8, 16)).astype(np.float32)
    nk = rng.standard_normal((2, S, 4, 16)).astype(np.float32)
    nv = rng.standard_normal((2, S, 4, 16)).astype(np.float32)
    for qlens in ([4, 2], [1, 0]):
        qlens = np.asarray(qlens, np.int32)
        ref = paged_attention_append(qa, kp.copy(), vp.copy(), tables,
                                     lens, qlens, nk, nv)
        got = paged_attention_append_tp(qa, kp.copy(), vp.copy(), tables,
                                        lens, qlens, nk, nv, tp_mesh)
        # padding rows (>= q_lens) hold garbage in BOTH paths: compare
        # only the valid region of the attention output, pools fully
        valid = np.arange(S)[None, :] < qlens[:, None]
        np.testing.assert_allclose(
            np.asarray(ref[0])[valid], np.asarray(got[0])[valid],
            rtol=1e-5, atol=1e-5)
        for r, g in zip(ref[1:], got[1:]):
            np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                       rtol=1e-5, atol=1e-5)


def test_tp_kernel_append_follows_q_lens(tp_mesh, rng):
    """The mixed step in miniature under shard_map — a full chunk, a
    decode row, an idle slot with a wiped table row — at a shape with two
    row tiles a kv head (96 positions x 4 q heads, tiles of 192): each
    shard's one-head kernel walks the tiles and blocks the unsharded
    four-head kernel does."""
    from paddle_tpu.ops.kernels.paged_attention import (
        paged_attention_append, paged_attention_append_tp)
    B, S, Hq, Hkv, D, BS, MB = 3, 96, 16, 4, 16, 8, 18
    lens = np.array([40, 100, 9], np.int32)
    qlens = np.array([S, 1, 0], np.int32)
    tables = np.full((B, MB), -1, np.int32)
    tables[0, :17] = np.arange(17)
    tables[1, :13] = 17 + np.arange(13)
    NB = 31                                  # 30 real blocks + the scratch
    kp = rng.standard_normal((NB, Hkv, BS, D)).astype(np.float32)
    vp = rng.standard_normal((NB, Hkv, BS, D)).astype(np.float32)
    qa = rng.standard_normal((B, S, Hq, D)).astype(np.float32)
    nk = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    nv = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    ref = paged_attention_append(qa, kp.copy(), vp.copy(), tables, lens,
                                 qlens, nk, nv)
    got = paged_attention_append_tp(qa, kp.copy(), vp.copy(), tables, lens,
                                    qlens, nk, nv, tp_mesh)
    valid = np.arange(S)[None, :] < qlens[:, None]
    np.testing.assert_allclose(np.asarray(ref[0])[valid],
                               np.asarray(got[0])[valid],
                               rtol=1e-5, atol=1e-5)
    for r, g in zip(ref[1:], got[1:]):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    # the chunk landed: positions 40..135 of slot 0 hold new_k
    blk, row = tables[0, 40 // BS], 40 % BS
    np.testing.assert_array_equal(np.asarray(got[1])[blk, :, row], nk[0, 0])


def test_tp_kernel_packed_append_is_the_unsharded_one(tp_mesh, rng):
    """The packed entry (a mixed step's rows on one axis, ``start``
    replicated beside the lens) under shard_map: a decode row, a partial
    chunk that starts off a 16-row tile and an idle slot; each shard's
    kernel hands back its heads of the unsharded kernel's rows, pools and
    zeros, bit for bit."""
    from paddle_tpu.ops.kernels.paged_attention import (
        paged_attention_append, paged_attention_append_tp)
    _, kp, vp, _, _ = _kernel_inputs(rng, NB=13)
    tables = np.array([[0, 1, 2, -1], [3, 4, 5, -1], [6, 7, -1, -1]],
                      np.int32)
    lens = np.array([19, 10, 3], np.int32)
    qlens = np.array([1, 7, 0], np.int32)
    T, S = 16, 8
    start = (np.cumsum(qlens) - qlens).astype(np.int32)
    qa = rng.standard_normal((T, 8, 16)).astype(np.float32)
    nk = rng.standard_normal((T, 4, 16)).astype(np.float32)
    nv = rng.standard_normal((T, 4, 16)).astype(np.float32)
    ref = paged_attention_append(qa, kp.copy(), vp.copy(), tables, lens,
                                 qlens, nk, nv, start=start, width=S)
    got = paged_attention_append_tp(qa, kp.copy(), vp.copy(), tables, lens,
                                    qlens, nk, nv, tp_mesh, start=start,
                                    width=S)
    assert np.asarray(ref[0])[:8].any() and not np.asarray(ref[0])[8:].any()
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


# ---------------------------------------------------------------------------
# Level 2 — the ReplicaRouter
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def router_model():
    return _build_model()


@pytest.fixture(scope="module")
def router_ref_eng(router_model):
    """ONE parity-reference engine for all router tests (compiles once;
    a drained engine is reusable — the test_serving idiom)."""
    return LLMEngine(router_model, max_batch=2, max_seq_len=64,
                     chunk_size=16)


def _ref_tokens(ref_eng, prompts, n):
    assert all(s is None for s in ref_eng.slots) and not ref_eng.waiting
    outs = ref_eng.generate(prompts, max_new_tokens=n)
    return [o.token_ids for o in outs]


def _replica(model, i, fault_injector=None, **kw):
    srv_kw = {k: kw.pop(k) for k in ("step_timeout_s", "supervise")
              if k in kw}
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("chunk_size", 16)
    eng = LLMEngine(model, cache_impl="paged", block_size=8,
                    scheduler="fused", enable_prefix_cache=True, **kw)
    return AsyncLLMServer(eng, max_queue_size=8, replica=i,
                          flight_recorder=True,
                          fault_injector=fault_injector, **srv_kw)


def _shared_prompts(seed, sys_len, tail_sizes):
    rng = np.random.default_rng(seed)
    sysp = rng.integers(1, V, size=(sys_len,)).astype(np.int32)
    return [np.concatenate([sysp,
                            rng.integers(1, V, size=(n,)).astype(np.int32)])
            for n in tail_sizes]


def _throttle(engine, dt=0.01):
    """Slow an engine's readout so scheduling races in the tests become
    deterministic (a queued request must still be queued when the test
    acts on it)."""
    orig = engine.step_finish
    engine.step_finish = lambda p: (time.sleep(dt), orig(p))[1]


def test_probe_prefix_len_read_only(router_model):
    """The router's affinity probe reports the cached prefix without
    touching allocator state (no refcount bumps, no table writes)."""
    eng = LLMEngine(router_model, max_batch=2, max_seq_len=64,
                    chunk_size=16, cache_impl="paged", block_size=8,
                    scheduler="fused", enable_prefix_cache=True)
    prompts = _shared_prompts(5, 24, (5,))
    eng.generate(prompts, max_new_tokens=4)
    before = (list(eng._free_blocks), list(eng._block_ref))
    hit = eng.probe_prefix_len(prompts[0])
    # the 29-token prompt registered its 3 full blocks (8 each)
    assert hit == 24
    assert eng.probe_prefix_len(prompts[0][:17]) == 16
    # the router's precomputed-hash form answers identically (one hash
    # walk per submission, membership tests per replica)
    hashes = eng.prefix_chain_hashes(prompts[0])
    assert len(hashes) == 3
    assert eng.probe_prefix_len(prompts[0], chain_hashes=hashes) == 24
    # a foreign prompt misses
    assert eng.probe_prefix_len(np.arange(1, 40, dtype=np.int32)) == 0
    after = (list(eng._free_blocks), list(eng._block_ref))
    assert before == after
    eng._check_pool_invariants()
    # dense / cache-off engines answer 0 (router falls back to load)
    dense = LLMEngine(router_model, max_batch=1, max_seq_len=64,
                      chunk_size=16)
    assert dense.probe_prefix_len(prompts[0]) == 0


def test_router_affinity_placement(router_model, router_ref_eng):
    """A request sharing a cached system prompt routes to the replica
    that holds it; the placement decision is observable on
    ServeResult.routing (replica, score, affinity_tokens, routing_key)
    and in the request's trace."""
    prompts = _shared_prompts(0, 24, (5, 7, 3))
    want = _ref_tokens(router_ref_eng, prompts, 6)

    router = ReplicaRouter([_replica(router_model, 0),
                            _replica(router_model, 1)])
    router.start()
    try:
        r0 = router.submit(prompts[0], max_new_tokens=6).result(timeout=300)
        seeded = r0.routing["replica"]
        assert r0.routing["affinity_tokens"] == 0  # cold cluster
        r1 = router.submit(prompts[1], max_new_tokens=6,
                           routing_key="tenantA").result(timeout=300)
        assert r1.routing["replica"] == seeded
        assert r1.routing["affinity_tokens"] == 24
        assert r1.routing["routing_key"] == "tenantA"
        assert r1.routing["policy"] == "affinity"
        # trace carries the placement as a "routed" span
        kinds = [e["kind"] for e in r1.trace["events"]]
        assert "routed" in kinds
        # token-exactness through the router
        assert [r0.token_ids, r1.token_ids] == want[:2]
        # streaming iteration through the RouterHandle
        h2 = router.submit(prompts[2], max_new_tokens=6)
        assert list(h2) == want[2]
        assert router.stats["affinity_routed"] >= 1
    finally:
        router.stop()


@pytest.mark.slow
def test_router_least_loaded_spreads(router_model):
    """Without affinity signal, placement balances by the load gauges:
    two concurrent requests on two single-slot replicas land on
    DIFFERENT replicas."""
    srv0 = _replica(router_model, 0, max_batch=1)
    srv1 = _replica(router_model, 1, max_batch=1)
    router = ReplicaRouter([srv0, srv1], policy="least_loaded")
    router.start()
    try:
        _throttle(srv0.engine)
        _throttle(srv1.engine)
        prompts = _prompts(9, (9, 9))
        h0 = router.submit(prompts[0], max_new_tokens=12)
        # let the gauges see replica 0 busy before placing the second
        time.sleep(0.15)
        h1 = router.submit(prompts[1], max_new_tokens=12)
        h0.result(timeout=300), h1.result(timeout=300)
        assert {h0.replica, h1.replica} == {0, 1}
        assert router.stats["placements"] == [1, 1]
    finally:
        router.stop()


def test_router_failover_mid_stream(router_model, router_ref_eng):
    """Kill a replica mid-stream under load (a scripted
    FaultInjector.kill(), not ad-hoc thread murder): its QUEUED requests
    complete on the survivor with the exact tokens a healthy serve
    produces, its IN-FLIGHT request fails with
    finish_reason="replica_lost" (carrying the tokens streamed so far),
    and the survivor's pool invariants hold (PADDLE_TPU_POOL_CHECKS is
    armed suite-wide)."""
    prompts = _shared_prompts(1, 16, (5, 7, 3))
    want = _ref_tokens(router_ref_eng, prompts, 6)

    fi0 = FaultInjector()
    srv0 = _replica(router_model, 0, fault_injector=fi0, max_batch=1)
    srv1 = _replica(router_model, 1)
    router = ReplicaRouter([srv0, srv1])
    router.start()
    try:
        _throttle(srv0.engine)  # keep the victim streaming slowly
        # in-flight on the doomed replica, queued behind its sole slot
        h_live = router.submit(prompts[0], max_new_tokens=30, replica=0)
        h_q1 = router.submit(prompts[1], max_new_tokens=6, replica=0)
        h_q2 = router.submit(prompts[2], max_new_tokens=6, replica=0)
        stream = iter(h_live)
        first = next(stream)          # it is genuinely mid-stream

        fi0.kill("injected replica death")

        lost = h_live.result(timeout=300)
        assert lost.finish_reason == "replica_lost"
        assert lost.token_ids[0] == first
        assert lost.routing["replica"] == 0
        assert lost.trace_ctx is not None and lost.trace_ctx.hop == 0
        # queued requests converted to RESUBMISSION, not loss
        for h, tokens in ((h_q1, want[1]), (h_q2, want[2])):
            res = h.result(timeout=300)
            assert res.finish_reason in ("length", "eos")
            assert res.token_ids == tokens
            assert h.replica == 1
            assert h.resubmits == 1
            assert res.routing["resubmits"] == 1
            # the trace identity survives the failover with exactly one
            # hop bump, attributed to the failover resubmission
            assert res.trace_ctx is not None
            assert res.trace_ctx.hop == 1
            assert res.trace_ctx.via == "failover"
        assert router.stats["replica_lost"] == 1
        assert router.stats["resubmitted"] == 2
        srv1.engine._check_pool_invariants()
        assert not router.alive(0) and router.alive(1)
        # replica-label satellite, on the servers already running here:
        # the survivor's Prometheus lines carry its replica label (so a
        # cluster scrape aggregates instead of colliding) and its
        # snapshot/explain_tail carry the placement record
        text = srv1.telemetry.prometheus_text()
        assert 'replica="1"' in text
        assert 'stage="idle",replica="1"' in text
        assert srv1.telemetry.snapshot()["replica"] == 1
        tail = srv1.flight_recorder.explain_tail(0.0)
        assert tail and all(e["routing"]["resubmits"] == 1 for e in tail)
    finally:
        errors = router.stop()
    # the dead replica's crash surfaces at stop, attributably
    assert [i for i, _ in errors] == [0]
    assert "injected replica death" in str(errors[0][1])


@pytest.mark.slow
def test_router_hung_replica_failover_resume(router_model,
                                             router_ref_eng):
    """Health-probe failover: a replica wedged INSIDE a step (thread
    ALIVE, heartbeat stale past step_timeout_s) flips health() to
    "hung"; the router evicts its residents without waiting for the
    thread to die, and — with resume_inflight=True — the stream
    CONTINUES token-exactly on the survivor from what the caller
    already consumed. Slow lane: the wedge must outlive failover wall
    (seconds) by construction; the tier-1 watchdog/hang coverage lives
    in tests/test_faults.py."""
    prompts = _shared_prompts(3, 16, (5,))
    want = _ref_tokens(router_ref_eng, prompts, 10)
    fi0 = FaultInjector()
    srv0 = _replica(router_model, 0, fault_injector=fi0,
                    step_timeout_s=0.5)
    srv1 = _replica(router_model, 1)
    # warm the compile caches BEFORE arming the tight step_timeout_s —
    # a cold first-step compile would read as a hang
    for srv in (srv0, srv1):
        srv.engine.generate([prompts[0]], max_new_tokens=2)
        srv.engine.reset()
    router = ReplicaRouter([srv0, srv1], resume_inflight=True)
    router.start()
    try:
        h = router.submit(prompts[0], max_new_tokens=10, replica=0)
        first = next(iter(h))
        # long enough that failover (~0.5s stale + resume serve) runs
        # to completion while the victim is still wedged; short enough
        # that the teardown stop() isn't parked long once it ends
        fi0.hang_at_step(5, seconds=3.5, interruptible=False)
        res = h.result(timeout=300)
        # the wedged replica was failed over AROUND, not waited out
        assert res.finish_reason in ("length", "eos")
        assert res.token_ids == want[0]
        assert res.token_ids[0] == first
        assert h.replica == 1 and h.resubmits == 1
        assert router.stats["evicted_hung"] >= 1
        assert router.stats["resumed"] >= 1
        # the thread is still alive — this was a HEALTH failover
        assert router.alive(0) and not router.healthy(0)
        assert srv0.health()["state"] == "hung"
        # the gauge flips on the next watchdog tick (<= timeout/4 after
        # the heartbeat goes stale) — the router's health() age check
        # can legitimately beat it by one tick
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                srv0.telemetry.get_gauges()["server_healthy"] != 0.0:
            time.sleep(0.01)
        assert srv0.telemetry.get_gauges()["server_healthy"] == 0.0
        srv1.engine._check_pool_invariants()
    finally:
        router.stop(timeout=120)


@pytest.mark.slow
def test_router_supervised_replica_recovers_in_place(router_model,
                                                     router_ref_eng):
    """A SUPERVISED replica's crash is not a failover event: health
    reports "restarting" (no new placements, residents stay), the
    restart resumes every stream in place, and the router's
    resubmission machinery never fires. Slow lane: single-server
    supervised recovery is tier-1-covered in tests/test_faults.py;
    this adds the through-the-router angle."""
    prompts = _shared_prompts(9, 16, (5, 7))
    want = _ref_tokens(router_ref_eng, prompts, 6)
    fi0 = FaultInjector().crash_at_step(3)
    srv0 = _replica(router_model, 0, fault_injector=fi0,
                    supervise=RestartPolicy(max_restarts=1,
                                            backoff_s=0.01))
    srv1 = _replica(router_model, 1)
    router = ReplicaRouter([srv0, srv1])
    router.start()
    try:
        hs = [router.submit(p, max_new_tokens=6, replica=0)
              for p in prompts]
        results = [h.result(timeout=300) for h in hs]
        assert [r.token_ids for r in results] == want
        assert all(h.replica == 0 and h.resubmits == 0 for h in hs)
        assert srv0.restarts == 1
        assert router.stats["resubmitted"] == 0
        assert router.stats["replica_lost"] == 0
        srv0.engine._check_pool_invariants()
    finally:
        router.stop()


@pytest.mark.slow
def test_chaos_soak_three_replicas(router_model, router_ref_eng):
    """The scripted-chaos soak the ISSUE asks for: a seeded random
    fault schedule (crashes + sub-watchdog hangs) over 3 supervised
    replicas under mixed load. Every stream either finishes
    TOKEN-EXACTLY (in-place restart or resume_inflight failover) or
    fails attributably; pool invariants hold everywhere
    (PADDLE_TPU_POOL_CHECKS armed suite-wide)."""
    rng = np.random.default_rng(42)
    prompts = _shared_prompts(10, 24, tuple(3 + i % 9 for i in range(18)))
    want = _ref_tokens(router_ref_eng, prompts, 8)
    fis = [FaultInjector() for _ in range(3)]
    replicas = [_replica(router_model, i, fault_injector=fis[i],
                         supervise=RestartPolicy(max_restarts=3,
                                                 backoff_s=0.01),
                         step_timeout_s=5.0)
                for i in range(3)]
    for srv in replicas:   # compile before the watchdog arms
        srv.engine.generate([prompts[0][:8]], max_new_tokens=2)
        srv.engine.reset()
    # the scripted "random" schedule: deterministic under the seed, so
    # a failure replays exactly
    for fi in fis:
        for step in sorted(int(s) for s in rng.integers(2, 40, size=3)):
            if rng.random() < 0.5:
                fi.crash_at_step(step)
            else:
                fi.hang_at_step(step, seconds=0.2)
    router = ReplicaRouter(replicas, resume_inflight=True)
    router.start()
    try:
        handles = [router.submit(p, max_new_tokens=8) for p in prompts]
        results = [h.result(timeout=600) for h in handles]
        exact = 0
        for r, tokens in zip(results, want):
            if r.finish_reason in ("length", "eos"):
                assert r.token_ids == tokens
                exact += 1
            else:   # attributable, never silent
                assert r.finish_reason in ("replica_lost",), r
        assert exact >= len(prompts) - 2    # chaos, not carnage
        assert sum(len(fi.fired) for fi in fis) >= 3
        for srv in replicas:
            if srv._crashed is None:
                srv.engine._check_pool_invariants()
    finally:
        router.stop(timeout=120)


def test_router_drain_migrates_queued(router_model, router_ref_eng):
    """drain(): the replica stops taking new work, queued requests
    migrate to survivors, running ones finish in place."""
    prompts = _shared_prompts(2, 16, (5, 7))
    want = _ref_tokens(router_ref_eng, prompts, 6)

    srv0 = _replica(router_model, 0, max_batch=1)
    srv1 = _replica(router_model, 1)
    router = ReplicaRouter([srv0, srv1])
    router.start()
    try:
        _throttle(srv0.engine)
        h_run = router.submit(prompts[0], max_new_tokens=25, replica=0)
        h_q = router.submit(prompts[1], max_new_tokens=6, replica=0)
        next(iter(h_run))             # running and streaming
        router.drain(0, timeout=120)
        run_res = h_run.result(timeout=300)
        assert run_res.finish_reason in ("length", "eos")
        assert len(run_res.token_ids) == 25      # finished in place
        q_res = h_q.result(timeout=300)
        assert q_res.token_ids == want[1]
        assert q_res.routing["replica"] == 1     # migrated
        assert not router.alive(0) and router.alive(1)
        # a drained replica receives no new placements
        h_new = router.submit(prompts[0], max_new_tokens=4)
        assert h_new.result(timeout=300).routing["replica"] == 1
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# multi-replica observability (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_replica_labels_and_merged_trace(tmp_path, router_model):
    """Replica-labeled Prometheus lines don't collide across replicas,
    snapshots carry the index, explain_tail entries carry the routing
    record, and the merged chrome trace lands one process lane group per
    replica."""
    import json

    prompts = _shared_prompts(4, 16, (5, 7))
    router = ReplicaRouter([_replica(router_model, 0),
                            _replica(router_model, 1)])
    router.start()
    try:
        hs = [router.submit(p, max_new_tokens=6, replica=i % 2,
                            routing_key=f"t{i}")
              for i, p in enumerate(prompts)]
        for h in hs:
            h.result(timeout=300)
        text = router.prometheus_text()
        assert 'replica="0"' in text and 'replica="1"' in text
        # valid exposition: ONE TYPE line per metric family, every
        # replica's labeled samples grouped under it (strict parsers
        # reject repeated TYPE lines / split families)
        fam = "paddle_tpu_serving_requests_finished_total"
        assert text.count(f"# TYPE {fam}") == 1
        assert text.count(f'{fam}{{replica="0"}}') == 1
        assert text.count(f'{fam}{{replica="1"}}') == 1
        assert 'stage="idle",replica="0"' in text
        snap = router.snapshot()
        assert snap["replicas"][0]["telemetry"]["replica"] == 0
        # explain_tail carries the placement record on tail entries
        tail = router.replicas[0].flight_recorder.explain_tail(0.0)
        assert tail and all(e["routing"]["replica"] == 0 for e in tail)
        merged = router.export_merged_trace(
            str(tmp_path / "cluster_trace.json"))
        events = json.load(open(merged))["traceEvents"]
        names = {e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
        assert {"rank0:replica0", "rank1:replica1"} <= names
        pids = {e["pid"] for e in events}
        assert pids == {0, 1}
    finally:
        router.stop()


def test_routing_metadata_plain_server(router_model):
    """The routing satellite works WITHOUT the router: submit(...,
    routing=...) surfaces on ServeResult and in the trace on a plain
    AsyncLLMServer."""
    eng = LLMEngine(router_model, max_batch=1, max_seq_len=64,
                    chunk_size=16)
    server = AsyncLLMServer(eng, max_queue_size=4, flight_recorder=True)
    server.start()
    try:
        h = server.submit(np.arange(1, 8, dtype=np.int32),
                          max_new_tokens=4,
                          routing={"routing_key": "abc", "shard": 3})
        res = h.result(timeout=300)
        assert res.routing == {"routing_key": "abc", "shard": 3}
        routed = [e for e in res.trace["events"] if e["kind"] == "routed"]
        assert routed and routed[0]["value"]["shard"] == 3
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# big mesh / soak (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tp8_engine_parity():
    """Full 8-device TP parity (the MULTICHIP dryrun's serve=engine_tp(8)
    shape, single process)."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    paddle.seed(7)
    cfg = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=8,
                      num_key_value_heads=8, max_position_embeddings=128)
    ref_m = LlamaForCausalLM(cfg)
    ref_m.eval()
    paddle.seed(7)
    tp_m = LlamaForCausalLM(cfg)
    tp_m.eval()
    prompts = _prompts(3, (9, 5))
    ref = LLMEngine(ref_m, max_batch=2, max_seq_len=64, chunk_size=16,
                    cache_impl="paged", block_size=8, scheduler="fused")
    want = [o.token_ids for o in ref.generate(prompts, max_new_tokens=8)]
    mesh = Mesh(np.asarray(devs[:8]), ("tp",))
    eng = tp_engine(tp_m, mesh=mesh, max_batch=2, max_seq_len=64,
                    chunk_size=16, cache_impl="paged", block_size=8,
                    scheduler="fused")
    assert eng.tp_degree() == 8
    got = [o.token_ids for o in eng.generate(prompts, max_new_tokens=8)]
    assert got == want


@pytest.mark.slow
def test_failover_retries_through_full_survivor_queue(router_model,
                                                      router_ref_eng):
    """A survivor whose admission queue is momentarily FULL must not
    convert a failover resubmission into request loss — the router parks
    the handle and retries on monitor ticks until the queue frees
    (failover_retry_s window)."""
    prompts = _shared_prompts(6, 16, (5, 7, 3, 4))
    want = _ref_tokens(router_ref_eng, prompts, 4)
    fi0 = FaultInjector()
    srv0 = _replica(router_model, 0, fault_injector=fi0, max_batch=1)
    srv1 = AsyncLLMServer(
        LLMEngine(router_model, max_batch=1, max_seq_len=64,
                  chunk_size=16, cache_impl="paged", block_size=8,
                  scheduler="fused", enable_prefix_cache=True),
        max_queue_size=1, replica=1)
    router = ReplicaRouter([srv0, srv1], failover_retry_s=60.0)
    router.start()
    try:
        _throttle(srv0.engine)
        _throttle(srv1.engine)
        # survivor: one running (slot), one in engine.waiting, one
        # FILLING its single admission-queue slot
        s_run = router.submit(prompts[0], max_new_tokens=25, replica=1)
        next(iter(s_run))
        s_w = router.submit(prompts[1], max_new_tokens=4, replica=1)
        s_q = router.submit(prompts[2], max_new_tokens=4, replica=1)
        # victim: one queued request, then crash
        h_q = router.submit(prompts[3], max_new_tokens=4, replica=0)

        fi0.kill("injected replica death")
        res = h_q.result(timeout=300)
        assert res.finish_reason in ("length", "eos")
        assert res.token_ids == want[3]
        assert h_q.replica == 1 and h_q.resubmits == 1
        for h, tokens in ((s_w, want[1]), (s_q, want[2])):
            assert h.result(timeout=300).token_ids == tokens
        s_run.result(timeout=300)
    finally:
        router.stop()


@pytest.mark.slow
def test_router_soak_under_churn(router_model):
    """Sustained mixed load across 3 replicas with a mid-run drain:
    every request finishes (complete or attributably migrated), pool
    invariants hold everywhere."""
    prompts = _shared_prompts(8, 24, tuple(3 + i % 9 for i in range(24)))
    replicas = [_replica(router_model, i) for i in range(3)]
    router = ReplicaRouter(replicas)
    router.start()
    try:
        handles = [router.submit(p, max_new_tokens=8) for p in prompts[:16]]
        router.drain(0, timeout=300)
        handles += [router.submit(p, max_new_tokens=8)
                    for p in prompts[16:]]
        results = [h.result(timeout=600) for h in handles]
        assert all(r.finish_reason in ("length", "eos", "cancelled")
                   for r in results)
        for srv in replicas[1:]:
            srv.engine._check_pool_invariants()
    finally:
        router.stop()
