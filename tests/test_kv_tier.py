"""KV tiering — async host-RAM swap for preempted slots + a bounded
spill store for LRU-evicted prefix blocks.

The acceptance bars from the ISSUE:

* a preempt/re-admit cycle through the host tier is TOKEN-EXACT vs the
  untiered engine, greedy AND sampled, on bf16 and on int8/int4
  quantized pools (the (payload, scale) pairs round-trip bit-exact);
* re-prefill work measurably drops: the restore books
  ``kv_swap_saved_tokens`` and the tiered run dispatches fewer prefill
  tokens than the untiered one under identical pool pressure;
* spilled prefix blocks PROMOTE back on a content-store hit instead of
  recomputing, under tenant-keyed hashing (no cross-tenant promotion);
* the fused-scheduler ramp livelock (2 slots x 4-block prompts x
  4-block pool — ROADMAP item 1) COMPLETES under the admission-defer
  progress guarantee instead of thrashing;
* tiering x existing features: supervised restart / FaultInjector
  chaos with swapped-out slots stays token-exact (the host tier dies
  with the crash — recovery re-prefills), and the router counts
  swap-resident requests on hung-replica failover.

Engine-heavy cases ride the ``slow`` lane per the tier-1 wall-budget
policy (int4 round-trip, restart chaos, hung-replica failover); the
tier-1 core keeps the swap/spill/livelock correctness bars with engines shared as hard as the seeding allows.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (AsyncLLMServer, FaultInjector,
                                RestartPolicy)
from paddle_tpu.serving.scheduler import AdmissionQueue

V = 96
CFG = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=128)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, V, size=(n,)).astype(np.int32)
            for n in (25, 27)]


def _kw(**over):
    kw = dict(max_batch=2, max_seq_len=64, chunk_size=16,
              cache_impl="paged", block_size=8, scheduler="fused")
    kw.update(over)
    return kw


def _toks(eng, prompts, n=10, **sampling):
    return [o.token_ids for o in eng.generate(prompts, max_new_tokens=n,
                                              **sampling)]


# ---------------------------------------------------------------------------
# constructor contract
# ---------------------------------------------------------------------------

def test_tier_constructor_validation(tiny_model):
    with pytest.raises(ValueError, match="cache_impl='paged'"):
        LLMEngine(tiny_model, max_batch=2, max_seq_len=64,
                  scheduler="fused", kv_host_swap=True)
    with pytest.raises(ValueError, match="scheduler='fused'"):
        LLMEngine(tiny_model, **_kw(scheduler="legacy",
                                    kv_host_swap=True))
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        LLMEngine(tiny_model, **_kw(kv_host_spill_bytes=1 << 20))


# ---------------------------------------------------------------------------
# preemption swap: token-exactness (greedy + sampled) + the re-prefill win
# ---------------------------------------------------------------------------

def test_swap_cycle_token_exact_and_reprefill_avoided(tiny_model, prompts):
    """THE swap acceptance, in one three-engine pass: pool pressure
    preempts through the host tier and the restored streams are
    token-identical to the full-pool engine — greedy AND sampled (the
    per-(rid, position) fold_in keys make the stitch sample the exact
    continuation; engines are seeded alike so their base keys match) —
    while the tiered run dispatches measurably fewer prefill tokens
    than the untiered oversubscribed engine, and the pool drains
    clean."""
    paddle.seed(321)
    full = LLMEngine(tiny_model, **_kw())
    greedy_ref = _toks(full, prompts)
    sampled_ref = _toks(full, prompts, temperature=0.8, top_p=0.9)

    plain = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8))
    assert _toks(plain, prompts) == greedy_ref
    assert plain.stats["preemptions"] >= 1      # pressure is real

    paddle.seed(321)
    tier = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                       kv_host_swap=True))
    assert _toks(tier, prompts) == greedy_ref
    assert _toks(tier, prompts, temperature=0.8, top_p=0.9) == sampled_ref

    assert tier.stats["preemptions"] >= 1
    assert tier.stats["kv_swap_out_blocks"] >= 1
    assert tier.stats["kv_swap_in_blocks"] >= 1
    assert tier.stats["kv_swap_out_bytes"] > 0
    assert tier.stats["kv_swap_saved_tokens"] >= 1
    assert len(tier._free_blocks) == 8          # nothing leaked
    assert not tier._swap_store                 # entries consumed/dropped
    tier._check_pool_invariants()

    # the tier's whole point: restored spans are prefill work NOT done.
    # Compare the greedy batch only (plain ran one batch, tier ran two)
    total_prompt = sum(len(p) for p in prompts)
    re_plain = plain.stats["prefill_tokens"] - total_prompt
    re_tier = (tier.stats["prefill_tokens"] // 2) - total_prompt
    assert re_plain > 0                         # pressure caused re-prefill
    assert re_tier < re_plain


@pytest.mark.parametrize("dtype", ["int8"])
def test_quantized_pool_swap_round_trip(tiny_model, prompts, dtype):
    """Quantized pools swap token-exactly: the (payload, scale) pytree
    pairs ride the host tier intact, so a restored block dequantizes to
    the same values the untiered quantized engine would read. (int4
    twin in the slow lane.)"""
    plain = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                        kv_cache_dtype=dtype))
    ref = _toks(plain, prompts)
    assert plain.stats["preemptions"] >= 1
    tier = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                       kv_cache_dtype=dtype,
                                       kv_host_swap=True))
    assert _toks(tier, prompts) == ref
    assert tier.stats["kv_swap_in_blocks"] >= 1


@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["int4"])
def test_quantized_pool_swap_round_trip_slow(tiny_model, prompts, dtype):
    plain = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                        kv_cache_dtype=dtype))
    ref = _toks(plain, prompts)
    tier = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                       kv_cache_dtype=dtype,
                                       kv_host_swap=True))
    assert _toks(tier, prompts) == ref
    assert tier.stats["kv_swap_in_blocks"] >= 1


def test_swap_resident_window_and_entry_cleanup(tiny_model, prompts):
    """Between the preempting step and the re-admitting one the request
    is SWAP-RESIDENT (the router's failover probe sees it); terminal
    finishes — including cancellation — drop any leftover entry."""
    tier = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                       kv_host_swap=True))
    rids = [tier.add_request(p, max_new_tokens=10) for p in prompts]
    seen = set()
    while tier.has_unfinished():
        tier.step()
        seen.update(tier.swap_resident_rids())
    assert seen & set(rids)                     # the window was observable
    assert not tier._swap_store
    for r in rids:
        tier.finished_outputs.pop(r)
    # cancel path drops the entry too (same engine, fresh rid)
    rid = tier.add_request(prompts[0], max_new_tokens=4)
    tier._swap_store[rid] = {"tokens": np.zeros(1, np.int32),
                             "adapter_id": 0, "n_blocks": 1,
                             "k": [], "v": [], "ready": True,
                             "nbytes": 0}
    tier.cancel(rid)
    assert rid not in tier._swap_store


# ---------------------------------------------------------------------------
# ramp-livelock regression (PR 12's finding)
# ---------------------------------------------------------------------------

def test_ramp_livelock_shape_completes(tiny_model):
    """THE thrash shape: 2 slots x 4-block prompts x 4-block pool. The
    admission-defer progress guarantee must serialize the ramps — the
    workload completes with ZERO preemptions and full-pool token
    parity instead of preempt/re-admit thrashing."""
    rng = np.random.default_rng(3)
    ps = [rng.integers(1, V, size=(26,)).astype(np.int32)
          for _ in range(2)]
    kw = dict(max_batch=2, max_seq_len=32, chunk_size=8,
              cache_impl="paged", block_size=8, scheduler="fused")
    full = LLMEngine(tiny_model, **kw)
    ref = [o.token_ids for o in full.generate(ps, max_new_tokens=5)]
    sub = LLMEngine(tiny_model, kv_pool_blocks=4, **kw)
    t0 = time.perf_counter()
    outs = sub.generate(ps, max_new_tokens=5)
    assert time.perf_counter() - t0 < 60
    assert [o.token_ids for o in outs] == ref
    assert [o.finish_reason for o in outs] == ["length", "length"]
    assert sub.stats["preemptions"] == 0
    # a bounded step count is the no-thrash proof: the old ladder burned
    # a preempt/re-admit cycle per step without either ramp finishing
    assert sub.stats["steps"] <= 40


# ---------------------------------------------------------------------------
# prefix spill store
# ---------------------------------------------------------------------------

def test_prefix_spill_promotion_tenant_keyed(tiny_model, prompts):
    """An LRU-evicted prefix block demotes to the host spill store; the
    same prompt's re-admission PROMOTES it back (prefix hit, no
    recompute) instead of paying the chunk again. Spill entries key on
    the TENANT-rooted chain hash: another tenant's probe of the same
    token stream misses both the device store and the spill."""
    rng = np.random.default_rng(5)
    eng = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                      enable_prefix_cache=True,
                                      kv_host_spill_bytes=4 << 20))
    p0 = prompts[0]
    eng.generate([p0], max_new_tokens=4)
    hits_before = eng.stats["prefix_hit_tokens"]
    # churn two fresh prompts through the pool: p0's registered blocks
    # evict from the LRU and spill to host
    churn = [rng.integers(1, V, size=(27,)).astype(np.int32)
             for _ in range(2)]
    eng.generate(churn, max_new_tokens=8)
    assert eng.stats["kv_spill_blocks"] >= 1
    assert len(eng._spill) >= 1
    # same tenant: the spilled span counts as servable (router probe);
    # a different tenant's chain diverges from block 0 — no hit, device
    # or spilled
    assert eng.probe_prefix_len(p0, adapter_id=0) >= eng.block_size
    assert eng.probe_prefix_len(p0, adapter_id=1) == 0
    eng.generate([p0], max_new_tokens=4)
    assert eng.stats["kv_promote_blocks"] >= 1
    assert eng.stats["prefix_hit_tokens"] > hits_before
    # spill/promote traffic books on its OWN counters, never on the
    # kv_swap_*_bytes deltas (those are the preempt_swap-vs-reprefill
    # classifier's exclusive signal — swap is OFF on this engine)
    assert eng.stats["kv_swap_in_bytes"] == 0
    assert eng.stats["kv_swap_out_bytes"] == 0
    eng._check_pool_invariants()


def test_spill_byte_budget_bounds_store(tiny_model, prompts):
    """The spill store is BYTE-bounded: a budget of ~1 block holds at
    most one entry (oldest out); shrinking the budget below one block
    stops spilling entirely (same engine — the bound is read per
    eviction)."""
    rng = np.random.default_rng(8)
    churn = [rng.integers(1, V, size=(27,)).astype(np.int32)
             for _ in range(2)]
    probe = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8))  # no compile
    per = probe.kv_bytes_per_block()
    del probe
    one = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                      enable_prefix_cache=True,
                                      kv_host_spill_bytes=per))
    one.generate([prompts[0]], max_new_tokens=4)
    one.generate(churn, max_new_tokens=8)
    assert one.stats["kv_spill_blocks"] >= 1
    assert len(one._spill) == 1
    assert one._spill_bytes <= per
    # a budget below one block cannot hold any entry — no new spills
    one.kv_host_spill_bytes = max(per // 2, 1)
    spilled = one.stats["kv_spill_blocks"]
    one.generate([prompts[1]], max_new_tokens=8)
    assert one.stats["kv_spill_blocks"] == spilled


# ---------------------------------------------------------------------------
# observability plumbing
# ---------------------------------------------------------------------------

def test_steprecord_and_gauges_carry_tier_traffic(tiny_model, prompts):
    """StepRecords on the preempting/restoring steps carry the swap
    byte deltas (what splits the explain_tail preemption cause), and
    the server samples the tier gauges + counters."""
    from paddle_tpu.profiler.flight_recorder import FlightRecorder
    eng = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                      kv_host_swap=True))
    server = AsyncLLMServer(eng, flight_recorder=FlightRecorder())
    server.start()
    try:
        handles = [server.submit(p, max_new_tokens=10) for p in prompts]
        for h in handles:
            h.result(timeout=300)
    finally:
        server.stop()
    recs = server.flight_recorder.records()
    assert any((r.kv_swap_out_bytes or 0) > 0 for r in recs)
    assert any((r.kv_swap_in_bytes or 0) > 0 for r in recs)
    assert all(r.kv_host_spill_blocks is not None for r in recs)
    d = recs[-1].to_dict()
    for key in ("kv_swap_in_bytes", "kv_swap_out_bytes",
                "kv_host_spill_blocks"):
        assert key in d
    g = server.telemetry.get_gauges()
    assert g["kv_swap_out_bytes"] > 0
    assert g["kv_swap_in_bytes"] > 0
    assert g["kv_host_spill_blocks"] == 0       # spill off on this engine
    c = server.telemetry.counters
    assert c["kv_swap_out_blocks"] >= 1
    assert c["kv_swap_in_blocks"] >= 1
    assert c["kv_swap_saved_tokens"] >= 1
    text = server.telemetry.prometheus_text()
    assert "kv_swap_in_bytes" in text and "kv_host_spill_blocks" in text


def test_admission_queue_front_grant():
    """AdmissionQueue.put(front=True) — the re-admission grant — jumps
    fresh arrivals but still honors the queue bound."""
    q = AdmissionQueue(max_size=3)
    q.put("a")
    q.put("b")
    q.put("r", front=True)
    assert q.pop() == "r"
    q.put("c")                                  # back to capacity
    from paddle_tpu.serving import ServerQueueFull
    with pytest.raises(ServerQueueFull):
        q.put("late", block=False, front=True)
    assert [q.pop(), q.pop(), q.pop()] == ["a", "b", "c"]


# ---------------------------------------------------------------------------
# tiering x fault tolerance / cluster (engine-heavy: slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_supervised_restart_with_swapped_slots(tiny_model, prompts):
    """An injected crash while the engine holds host-tier state: the
    restart rebuilds the device pools AND drops the swap store (its
    entries describe buffers that no longer exist), re-admission
    re-prefills, and every stream continues token-exactly."""
    ref_eng = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                          kv_host_swap=True))
    server = AsyncLLMServer(ref_eng)
    server.start()
    try:
        want = [server.submit(p, max_new_tokens=10).result(timeout=300)
                .token_ids for p in prompts]
    finally:
        server.stop()

    eng = LLMEngine(tiny_model, **_kw(kv_pool_blocks=8,
                                      kv_host_swap=True))
    fi = FaultInjector()
    fi.crash_at_step(6)                  # mid-serve, post-preemption-ish
    server = AsyncLLMServer(eng, supervise=RestartPolicy(max_restarts=2,
                                                         backoff_s=0.01),
                            fault_injector=fi)
    server.start()
    try:
        handles = [server.submit(p, max_new_tokens=10) for p in prompts]
        got = [h.result(timeout=300).token_ids for h in handles]
    finally:
        server.stop()
    assert got == want
    assert server.restarts >= 1
    assert not eng._swap_store and not eng._swap_pending
    eng._check_pool_invariants()


@pytest.mark.slow
def test_router_counts_swap_resident_failover(tiny_model, prompts):
    """Hung-replica failover is swap-resident-aware: a request whose KV
    lives in the wedged replica's host tier is evicted + resumed like a
    running one, and the router books it (stats + snapshot kv_tier)."""
    from paddle_tpu.serving import ReplicaRouter
    fi0 = FaultInjector()
    srv0 = AsyncLLMServer(
        LLMEngine(tiny_model, **_kw(kv_pool_blocks=8, kv_host_swap=True)),
        replica=0, fault_injector=fi0, step_timeout_s=0.5)
    srv1 = AsyncLLMServer(
        LLMEngine(tiny_model, **_kw()), replica=1)
    for srv in (srv0, srv1):
        srv.engine.generate([prompts[0][:5]], max_new_tokens=2)
        srv.engine.reset()
    router = ReplicaRouter([srv0, srv1], resume_inflight=True)
    router.start()
    try:
        h = router.submit(prompts[0], max_new_tokens=10, replica=0)
        first = next(iter(h))
        # manufacture the swap-resident state deterministically on the
        # replica we are about to wedge: the entry's rid is the INNER
        # (replica-local) request id the router probes by
        srv0.engine._swap_store[h._inner.request_id] = {
            "tokens": np.zeros(1, np.int32), "adapter_id": 0,
            "n_blocks": 1, "k": [], "v": [], "ready": True, "nbytes": 0}
        snap = router.snapshot()
        assert snap["replicas"][0]["kv_tier"]["swap_resident"] == 1
        fi0.hang_at_step(5, seconds=3.5, interruptible=False)
        res = h.result(timeout=300)
        assert res.finish_reason in ("length", "eos")
        assert res.token_ids[0] == first
        assert router.stats["evicted_hung"] >= 1
        assert router.stats["swap_resident_failover"] >= 1
    finally:
        router.stop(timeout=120)
