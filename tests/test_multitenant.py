"""Multi-tenant serving tests: batched multi-LoRA adapters + prefill-only
embedding endpoints through one fused engine (serving/adapters.py,
serving/embedding.py, the engine's tenant dimension).

Correctness bars:
* with ZERO adapters registered the engine is bit-identical to the
  pre-adapter engine (regression: base serving pays nothing);
* per-tenant greedy streams are token-exact vs an offline reference
  whose weights were MERGED (W + A@B*alpha) — including any mix of
  tenants in one batch, and across preemption / supervised restart /
  router failover;
* the prefix cache never shares a KV block across adapter ids;
* embedding requests return the mean-pooled final hidden state and ride
  the same fused token-budget walk as generation chunks.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.bert import BertConfig, BertModel
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (AdapterStore, AsyncLLMServer,
                                BertEmbedEngine, FaultInjector,
                                ReplicaRouter, RestartPolicy, apply_merged,
                                random_lora_weights)

CFG = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=128)


def fresh_model():
    paddle.seed(7)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


@pytest.fixture(scope="module")
def store():
    s = AdapterStore(CFG, rank=4)
    s.register(random_lora_weights(CFG, rank=4, seed=3, scale=0.05),
               alpha=2.0)                                   # id 1
    s.register(random_lora_weights(CFG, rank=2, seed=9, scale=0.05),
               alpha=1.0)                                   # id 2 (padded)
    return s


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 96, size=(n,)).astype(np.int32)
            for n in (5, 11, 3)]


@pytest.fixture(scope="module")
def refs(store, prompts):
    """Greedy reference streams per tenant from MERGED-weights engines —
    the offline single-tenant ground truth every batched path must
    match token-exactly."""
    out = {}
    for aid in (0, 1, 2):
        m = fresh_model()
        if aid:
            apply_merged(m, store, aid)
        eng = LLMEngine(m, max_batch=2, max_seq_len=64, chunk_size=8,
                        scheduler="fused")
        out[aid] = [o.token_ids
                    for o in eng.generate(prompts, max_new_tokens=6)]
    return out


def _drain(eng, rids):
    while eng.has_unfinished():
        eng.step()
    return [eng.finished_outputs.pop(r).token_ids for r in rids]


# ---------------------------------------------------------------------------
# bit-identity + merged-weights parity
# ---------------------------------------------------------------------------

def test_zero_adapters_bit_identical(prompts):
    """An engine with an attached-but-EMPTY adapter store dispatches
    lora=None and must be BIT-identical to the plain engine — tokens
    AND the carried logits buffer."""
    plain = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                      chunk_size=8, scheduler="fused")
    base = [o.token_ids for o in plain.generate(prompts, max_new_tokens=6)]
    armed = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                      chunk_size=8, scheduler="fused",
                      adapter_store=AdapterStore(CFG, rank=4))
    outs = [o.token_ids for o in armed.generate(prompts, max_new_tokens=6)]
    assert outs == base
    np.testing.assert_array_equal(np.asarray(plain._logits),
                                  np.asarray(armed._logits))


#: tier-1 keeps the PAGED variant (the serving default and the richer
#: allocator path); the dense twin rides `slow` for wall-time headroom
@pytest.mark.parametrize("cache_impl", [
    pytest.param("dense", marks=pytest.mark.slow), "paged"])
def test_adapter_parity_vs_merged(store, prompts, refs, cache_impl):
    kw = dict(cache_impl=cache_impl)
    if cache_impl == "paged":
        kw.update(block_size=4, chunk_size=8)
    else:
        kw.update(chunk_size=8)
    eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                    scheduler="fused", adapter_store=store, **kw)
    rids = [eng.add_request(p, max_new_tokens=6, adapter_id=1)
            for p in prompts]
    assert _drain(eng, rids) == refs[1]


def test_mixed_batch_tenants_exact(store, prompts, refs):
    """One batch serving tenants 0, 1 and 2 CONCURRENTLY: every stream
    token-exact vs its own merged reference — the batched gather never
    leaks one tenant's delta into another's rows."""
    eng = LLMEngine(fresh_model(), max_batch=3, max_seq_len=64,
                    chunk_size=8, scheduler="fused", adapter_store=store)
    plan = [(prompts[0], 1), (prompts[1], 0), (prompts[2], 2)]
    rids = [eng.add_request(p, max_new_tokens=6, adapter_id=a)
            for p, a in plan]
    outs = _drain(eng, rids)
    assert outs[0] == refs[1][0]
    assert outs[1] == refs[0][1]     # base tenant untouched by neighbors
    assert outs[2] == refs[2][2]
    # tenant 1's stream must actually differ from base somewhere in the
    # suite's fixtures, or the parity assertions above are vacuous
    assert refs[1] != refs[0] or refs[2] != refs[0]


@pytest.mark.slow
def test_legacy_scheduler_adapter_parity(store, prompts, refs):
    eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                    chunk_size=8, scheduler="legacy", adapter_store=store)
    rids = [eng.add_request(p, max_new_tokens=6, adapter_id=2)
            for p in prompts]
    assert _drain(eng, rids) == refs[2]


@pytest.mark.slow
def test_multi_step_stride_adapter_parity(store, prompts, refs):
    eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                    chunk_size=8, scheduler="fused", readout_stride=4,
                    adapter_store=store)
    rids = [eng.add_request(p, max_new_tokens=6, adapter_id=1)
            for p in prompts]
    assert _drain(eng, rids) == refs[1]


# ---------------------------------------------------------------------------
# the adapter device cache: LRU swaps, refcount pinning, deferral
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_lru_swap_counters_and_reuse(store, prompts, refs):
    """One swappable slot, two adapters alternating: every admission is
    a miss+swap; with two slots both stay resident and later requests
    hit without swapping. Output stays token-exact throughout."""
    eng = LLMEngine(fresh_model(), max_batch=1, max_seq_len=64,
                    chunk_size=8, scheduler="fused", adapter_store=store,
                    adapter_cache_slots=1)
    for aid in (1, 2, 1, 2):
        rid = eng.add_request(prompts[0], max_new_tokens=6, adapter_id=aid)
        (out,) = _drain(eng, [rid])
        assert out == refs[aid][0]
    assert eng.stats["adapter_swaps"] == 4
    assert eng.stats["adapter_cache_hits"] == 0

    eng2 = LLMEngine(fresh_model(), max_batch=1, max_seq_len=64,
                     chunk_size=8, scheduler="fused", adapter_store=store,
                     adapter_cache_slots=2)
    for aid in (1, 2, 1, 2):
        rid = eng2.add_request(prompts[0], max_new_tokens=6,
                               adapter_id=aid)
        _drain(eng2, [rid])
    assert eng2.stats["adapter_swaps"] == 2
    assert eng2.stats["adapter_cache_hits"] == 2
    assert eng2.adapter_cache.occupancy() == 1.0


@pytest.mark.slow
def test_adapter_cache_full_defers_admission(store, prompts, refs):
    """More DISTINCT resident adapters than cache slots: the admission
    DEFERS (request waits) instead of evicting a pinned slot — and every
    stream still finishes token-exact once slots free."""
    eng = LLMEngine(fresh_model(), max_batch=3, max_seq_len=64,
                    chunk_size=8, scheduler="fused", adapter_store=store,
                    adapter_cache_slots=1)
    rids = [eng.add_request(prompts[i], max_new_tokens=6, adapter_id=a)
            for i, a in ((0, 1), (1, 2), (2, 0))]
    eng.step()
    # adapter 2's request must still be WAITING (slot pinned by tenant 1)
    waiting_ids = [r.request_id for r in eng.waiting]
    assert rids[1] in waiting_ids
    outs = _drain(eng, rids)
    assert outs[0] == refs[1][0]
    assert outs[1] == refs[2][1]
    assert outs[2] == refs[0][2]


def test_unknown_adapter_and_fused_qkv_rejected(store, prompts):
    eng = LLMEngine(fresh_model(), max_batch=1, max_seq_len=64,
                    chunk_size=8, scheduler="fused", adapter_store=store)
    with pytest.raises(ValueError, match="unknown adapter_id"):
        eng.add_request(prompts[0], adapter_id=99)
    plain = LLMEngine(fresh_model(), max_batch=1, max_seq_len=64,
                      chunk_size=8, scheduler="fused")
    with pytest.raises(ValueError, match="adapter_store"):
        plain.add_request(prompts[0], adapter_id=1)
    paddle.seed(7)
    fused_cfg = LlamaConfig(**{**CFG.__dict__, "fuse_attention_qkv": True})
    fm = LlamaForCausalLM(fused_cfg)
    fm.eval()
    with pytest.raises(ValueError, match="fuse_attention_qkv"):
        LLMEngine(fm, max_batch=1, max_seq_len=64, adapter_store=store)


# ---------------------------------------------------------------------------
# prefix cache: per-tenant hash roots, no cross-tenant block sharing
# ---------------------------------------------------------------------------

def test_prefix_cache_tenant_isolation(store):
    """Identical prompt under two tenants: the second tenant gets ZERO
    hit and disjoint physical blocks; the same tenant returning hits.
    The pool-invariant audit (PADDLE_TPU_POOL_CHECKS, armed suite-wide)
    runs through every alloc/free here."""
    rng = np.random.default_rng(5)
    p = rng.integers(1, 96, size=(17,)).astype(np.int32)
    eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                    chunk_size=8, cache_impl="paged", block_size=4,
                    scheduler="fused", enable_prefix_cache=True,
                    adapter_store=store)

    def run(aid):
        rid = eng.add_request(p, max_new_tokens=3, adapter_id=aid)
        blocks = None
        while eng.has_unfinished():
            eng.step()
            for b, slot in enumerate(eng.slots):
                if slot is not None and slot.req.request_id == rid:
                    blocks = set(eng._slot_blocks[b])
        eng.finished_outputs.pop(rid)
        return blocks or set()

    blocks1 = run(1)
    h0 = eng.stats["prefix_hit_tokens"]
    blocks2 = run(2)
    assert eng.stats["prefix_hit_tokens"] == h0, \
        "tenant 2 hit tenant 1's blocks"
    assert not (blocks1 & blocks2), "physical block shared across tenants"
    run(1)
    assert eng.stats["prefix_hit_tokens"] > h0, \
        "same tenant should hit its own registered prefix"
    # probe surface agrees: each tenant sees only its OWN chain (both
    # are registered by now), and the chains never collide
    assert eng.probe_prefix_len(p, adapter_id=1) > 0
    assert eng.probe_prefix_len(p, adapter_id=2) > 0
    h1 = eng.prefix_chain_hashes(p, adapter_id=1)
    h2 = eng.prefix_chain_hashes(p, adapter_id=2)
    assert h1 and h2 and h1[0] != h2[0]


# ---------------------------------------------------------------------------
# adapter identity across the fault machinery (chaos matrix)
# ---------------------------------------------------------------------------

def test_adapter_survives_preemption(store, prompts, refs):
    """Oversubscribed paged pool: pool pressure preempts adapter
    requests mid-decode; re-prefill re-acquires the adapter and the
    greedy streams stay token-exact per tenant."""
    eng = LLMEngine(fresh_model(), max_batch=3, max_seq_len=64,
                    chunk_size=8, cache_impl="paged", block_size=4,
                    scheduler="fused", kv_pool_blocks=7,
                    adapter_store=store, adapter_cache_slots=2)
    plan = [(prompts[0], 1), (prompts[1], 2), (prompts[2], 1)]
    rids = [eng.add_request(p, max_new_tokens=6, adapter_id=a)
            for p, a in plan]
    outs = _drain(eng, rids)
    assert eng.stats["preemptions"] > 0, \
        "pool must be small enough to force preemption"
    assert outs[0] == refs[1][0]
    assert outs[1] == refs[2][1]
    assert outs[2] == refs[1][2]


#: tier-1 keeps the PAGED restart (pool + adapter cache both rebuild);
#: the dense twin rides `slow`
@pytest.mark.parametrize("cache_impl", [
    pytest.param("dense", marks=pytest.mark.slow), "paged"])
def test_adapter_survives_restart(store, prompts, refs, cache_impl):
    """Supervised restart mid-serve: the crash snapshot re-admits each
    request as prompt⊕streamed WITH its adapter_id, the rebuilt adapter
    cache re-swaps, and per-tenant streams continue token-exact."""
    fi = FaultInjector()
    fi.crash_at_step(4)
    kw = dict(block_size=4) if cache_impl == "paged" else {}
    eng = LLMEngine(fresh_model(), max_batch=3, max_seq_len=64,
                    chunk_size=8, cache_impl=cache_impl,
                    scheduler="fused", adapter_store=store, **kw)
    srv = AsyncLLMServer(eng, supervise=RestartPolicy(max_restarts=2),
                         fault_injector=fi)
    srv.start()
    plan = [(prompts[0], 1), (prompts[1], 0), (prompts[2], 2)]
    hs = [srv.submit(p, max_new_tokens=6, adapter_id=a) for p, a in plan]
    outs = [h.result(timeout=240) for h in hs]
    srv.stop()
    assert srv.restarts >= 1
    assert [o.token_ids for o in outs] == \
        [refs[1][0], refs[0][1], refs[2][2]]


@pytest.mark.slow   # tier-1 wall budget (PR 14): the composition's
# halves stay tier-1 — adapter identity across preemption/restart
# (this file) and router failover token-exactness (test_cluster/
# test_faults); this is the cross-product soak
def test_adapter_survives_failover(store, prompts, refs):
    """Router failover: the dead replica's queued adapter request
    resubmits to a survivor (adapter_id rides the resubmission kwargs)
    and completes token-exact."""
    def mk_replica(i, fi=None):
        eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                        chunk_size=8, scheduler="fused",
                        adapter_store=store)
        return AsyncLLMServer(eng, max_queue_size=8, replica=i,
                              fault_injector=fi)

    fi = FaultInjector()
    router = ReplicaRouter([mk_replica(0, fi), mk_replica(1)])
    router.start()
    try:
        h0 = router.submit(prompts[0], max_new_tokens=6, adapter_id=1,
                           replica=0)
        assert h0.result(timeout=240).token_ids == refs[1][0]
        fi.kill()
        time.sleep(0.05)
        h1 = router.submit(prompts[1], max_new_tokens=6, adapter_id=1)
        out = h1.result(timeout=240)
        assert out.token_ids == refs[1][1]
        assert out.routing["replica"] == 1
    finally:
        router.stop()


@pytest.mark.slow
def test_router_adapter_affinity_placement(store, prompts):
    """Placement prefers the replica whose adapter cache already holds
    the tenant's adapter (no swap-in on admission)."""
    def mk_replica(i):
        eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                        chunk_size=8, scheduler="fused",
                        adapter_store=store)
        return AsyncLLMServer(eng, max_queue_size=8, replica=i)

    router = ReplicaRouter([mk_replica(0), mk_replica(1)])
    router.start()
    try:
        # warm tenant 1 onto replica 1 via an explicit pin
        router.submit(prompts[0], max_new_tokens=4, adapter_id=1,
                      replica=1).result(timeout=240)
        out = router.submit(prompts[2], max_new_tokens=4,
                            adapter_id=1).result(timeout=240)
        assert out.routing["replica"] == 1
        assert out.routing["adapter_resident"] is True
        assert router.stats["adapter_routed"] >= 1
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# prefill-only embedding endpoints
# ---------------------------------------------------------------------------

def _direct_pool(model, prompt):
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    h = model.llama(Tensor(jnp.asarray(prompt[None].astype(np.int32))))
    return np.asarray(h._value, np.float32).mean(axis=1)[0]


@pytest.mark.parametrize("cache_impl", [
    "dense", pytest.param("paged", marks=pytest.mark.slow)])
def test_llama_embed_matches_direct_pooling(prompts, cache_impl):
    kw = dict(block_size=4) if cache_impl == "paged" else {}
    model = fresh_model()
    eng = LLMEngine(model, max_batch=2, max_seq_len=64, chunk_size=8,
                    cache_impl=cache_impl, scheduler="fused", **kw)
    rid = eng.add_request(prompts[1], kind="embed")
    while eng.has_unfinished():
        eng.step()
    out = eng.finished_outputs.pop(rid)
    assert out.finish_reason == "embed" and out.token_ids == []
    ref = _direct_pool(model, prompts[1])
    np.testing.assert_allclose(out.embedding, ref, rtol=2e-4, atol=2e-5)


def test_embed_rides_mixed_steps_without_changing_generation(prompts,
                                                             refs):
    """Generate + embed through one server concurrently: the generated
    streams are bit-equal to a generate-only run, and every embedding
    matches the embed-only value."""
    model = fresh_model()
    eng = LLMEngine(model, max_batch=3, max_seq_len=64, chunk_size=8,
                    scheduler="fused")
    srv = AsyncLLMServer(eng, max_queue_size=16)
    srv.start()
    hs = [srv.submit(p, max_new_tokens=6) for p in prompts]
    ehs = [srv.submit_embed(p) for p in prompts[:2]]
    outs = [h.result(timeout=240) for h in hs]
    eouts = [h.result(timeout=240) for h in ehs]
    srv.stop()
    assert [o.token_ids for o in outs] == refs[0]
    for p, eo in zip(prompts, eouts):
        assert eo.finish_reason == "embed"
        np.testing.assert_allclose(eo.embedding, _direct_pool(model, p),
                                   rtol=2e-4, atol=2e-5)
    snap = srv.telemetry.snapshot()
    assert snap["counters"]["embed_requests"] == 2
    # per-tenant accounting counted the pooled prompt positions
    assert snap["tenant_tokens"]["0"] >= sum(
        len(p) for p in prompts[:2])


def test_embed_per_tenant_pooling(store, prompts):
    """An embed request under an adapter pools the ADAPTER's hidden
    states (== merged-weights model pooling), not the base model's."""
    eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                    chunk_size=8, scheduler="fused", adapter_store=store)
    rid = eng.add_request(prompts[0], kind="embed", adapter_id=1)
    while eng.has_unfinished():
        eng.step()
    got = eng.finished_outputs.pop(rid).embedding
    merged = fresh_model()
    apply_merged(merged, store, 1)
    ref = _direct_pool(merged, prompts[0])
    base = _direct_pool(fresh_model(), prompts[0])
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)
    assert np.abs(got - base).max() > 1e-3, \
        "adapter embed should differ from the base model's"


def test_embed_validation_and_kinds(prompts):
    legacy = LLMEngine(fresh_model(), max_batch=1, max_seq_len=64,
                       chunk_size=8, scheduler="legacy")
    with pytest.raises(ValueError, match="fused"):
        legacy.add_request(prompts[0], kind="embed")
    eng = LLMEngine(fresh_model(), max_batch=1, max_seq_len=64,
                    chunk_size=8, scheduler="fused")
    with pytest.raises(ValueError, match="kind"):
        eng.add_request(prompts[0], kind="classify")


def test_embed_full_length_prompt_accepted():
    """An embed prompt needs NO decode headroom: lengths the generate
    bound would reject (capacity-1) must embed fine — engine AND server
    validation — while capacity itself still rejects."""
    rng = np.random.default_rng(21)
    model = fresh_model()
    eng = LLMEngine(model, max_batch=1, max_seq_len=64, chunk_size=8,
                    scheduler="fused")
    long = rng.integers(1, 96, size=(63,)).astype(np.int32)
    with pytest.raises(ValueError, match="no room to generate"):
        eng.add_request(long, max_new_tokens=4)
    srv = AsyncLLMServer(eng, max_queue_size=4)
    srv.start()
    out = srv.submit_embed(long).result(timeout=240)
    srv.stop()
    assert out.finish_reason == "embed"
    np.testing.assert_allclose(out.embedding, _direct_pool(model, long),
                               rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="exceeds the engine capacity"):
        eng.add_request(rng.integers(1, 96, size=(64,)).astype(np.int32),
                        kind="embed")


@pytest.mark.slow
def test_embed_registers_prefix_for_generate(store, prompts):
    """An embed request never PROBES the prefix cache (its pooling needs
    every position computed) but REGISTERS its blocks — a same-tenant
    generate request then hits them."""
    rng = np.random.default_rng(11)
    p = rng.integers(1, 96, size=(16,)).astype(np.int32)
    eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                    chunk_size=8, cache_impl="paged", block_size=4,
                    scheduler="fused", enable_prefix_cache=True,
                    adapter_store=store)
    r1 = eng.add_request(p, kind="embed", adapter_id=1)
    while eng.has_unfinished():
        eng.step()
    e1 = eng.finished_outputs.pop(r1).embedding
    assert eng.stats["prefix_hit_tokens"] == 0
    r2 = eng.add_request(p, max_new_tokens=3, adapter_id=1)
    while eng.has_unfinished():
        eng.step()
    eng.finished_outputs.pop(r2)
    assert eng.stats["prefix_hit_tokens"] > 0
    # and a SECOND embed of the same prompt still recomputes (no probe)
    hits = eng.stats["prefix_hit_tokens"]
    r3 = eng.add_request(p, kind="embed", adapter_id=1)
    while eng.has_unfinished():
        eng.step()
    e3 = eng.finished_outputs.pop(r3).embedding
    assert eng.stats["prefix_hit_tokens"] == hits
    np.testing.assert_allclose(e1, e3, rtol=1e-6)


def test_bert_embed_engine_through_server():
    paddle.seed(3)
    bert = BertModel(BertConfig.tiny())
    bert.eval()
    eng = BertEmbedEngine(bert, max_batch=4, max_seq_len=32)
    srv = AsyncLLMServer(eng, max_queue_size=8)
    srv.start()
    rng = np.random.default_rng(1)
    ps = [rng.integers(1, 1024, size=(n,)).astype(np.int32)
          for n in (7, 12, 5)]
    outs = [h.result(timeout=240) for h in
            [srv.submit_embed(p) for p in ps]]
    # generation submit on an embed-only engine is rejected up front
    with pytest.raises(ValueError, match="embed-only"):
        srv.submit(ps[0], max_new_tokens=4)
    srv.stop()
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    for p, o in zip(ps, outs):
        assert o.finish_reason == "embed"
        seq, _ = bert(Tensor(jnp.asarray(p[None].astype(np.int32))))
        ref = np.asarray(seq._value, np.float32).mean(axis=1)[0]
        np.testing.assert_allclose(o.embedding, ref, rtol=2e-4,
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# observability: StepRecord tenant facts, adapter_swap cause, telemetry
# ---------------------------------------------------------------------------

@pytest.mark.slow   # tier-1 wall budget (PR 14): adapter
# StepRecord/telemetry schema stays pinned by the recorder-schema and
# telemetry-strictness tests; this is the serve-shaped plumbing soak
def test_recorder_and_telemetry_adapter_facts(store, prompts):
    """ONE served mix covers the whole observability surface: StepRecord
    tenant facts + embed grant kind, the adapter counters/gauge, and the
    per-tenant token counters through snapshot AND Prometheus."""
    eng = LLMEngine(fresh_model(), max_batch=2, max_seq_len=64,
                    chunk_size=8, scheduler="fused", adapter_store=store)
    srv = AsyncLLMServer(eng, max_queue_size=8, flight_recorder=True)
    srv.start()
    hs = [srv.submit(prompts[0], max_new_tokens=4, adapter_id=1),
          srv.submit_embed(prompts[1], adapter_id=2),
          srv.submit(prompts[2], max_new_tokens=4)]
    for h in hs:
        h.result(timeout=240)
    recs = srv.flight_recorder.records()
    snap = srv.telemetry.snapshot()
    text = srv.telemetry.prometheus_text()
    srv.stop()
    assert any((0, 1) in r.adapter_slots or (1, 1) in r.adapter_slots
               for r in recs), "StepRecord.adapter_slots missing tenant 1"
    assert any(r.adapter_swaps > 0 for r in recs)
    assert any(g[2] == "embed" for r in recs for g in r.grants), \
        "embed grant kind missing from StepRecord.grants"
    d = next(r for r in recs if r.adapter_slots).to_dict()
    assert "adapter_slots" in d and "adapter_swaps" in d
    assert snap["counters"]["adapter_cache_misses"] >= 2
    assert snap["counters"]["adapter_swaps"] >= 2
    assert snap["counters"]["embed_requests"] == 1
    # per-tenant tokens: 4 generated each for tenants 0/1, the embed's
    # pooled prompt positions for tenant 2
    assert snap["tenant_tokens"] == {"0": 4, "1": 4,
                                     "2": len(prompts[1])}
    assert 0.0 < snap["gauges"]["adapter_cache_occupancy"] <= 1.0
    assert 'tenant_tokens_total{tenant="1"} 4' in text
    assert "# TYPE paddle_tpu_serving_adapter_swaps_total counter" in text
    assert "# TYPE paddle_tpu_serving_adapter_cache_occupancy gauge" \
        in text


def test_explain_tail_adapter_swap_cause():
    """Synthetic taxonomy check: a gap whose causal step carried an
    adapter swap-in classifies as 'adapter_swap' (outranked only by
    restart_recovery and preemption)."""
    from paddle_tpu.profiler import FlightRecorder
    from paddle_tpu.profiler.flight_recorder import TAIL_CAUSES
    assert "adapter_swap" in TAIL_CAUSES
    rec = FlightRecorder(capacity=16)
    t0 = time.perf_counter()
    sid = rec.begin_step(
        scheduler="fused", kind="mixed",
        grants=((0, 7, "decode", 1),), tokens_scheduled=1,
        token_budget=8, queue_depth=0, free_blocks=None,
        total_blocks=None, pipeline_inflight=1, preemptions=(),
        admit_s=0.05, schedule_s=0.0, dispatch_s=0.001, t_begin=t0,
        adapter_slots=((0, 3),), adapter_swaps=1)
    rec.finish_step(sid, 0.0, 0.0)
    rec.on_token(7, sid, t=t0)
    rec.on_token(7, sid, t=t0 + 0.2)       # the tail gap
    (entry,) = rec.explain_tail(0.99, top=1)
    assert entry["cause"] == "adapter_swap"
    assert entry["step"]["adapter_slots"] == [[0, 3]]


# ---------------------------------------------------------------------------
# heavies: multi-tenant soak (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multitenant_soak_churn(store, prompts):
    """Churn many tenants (incl. embeds) through a small adapter cache
    and an oversubscribed pool with the invariant audits armed."""
    eng = LLMEngine(fresh_model(), max_batch=3, max_seq_len=64,
                    chunk_size=8, cache_impl="paged", block_size=4,
                    scheduler="fused", enable_prefix_cache=True,
                    kv_pool_blocks=14, adapter_store=store,
                    adapter_cache_slots=1)
    rng = np.random.default_rng(2)
    refs = {}
    for aid in (0, 1, 2):
        m = fresh_model()
        if aid:
            apply_merged(m, store, aid)
        refs[aid] = m
    for wave in range(6):
        rids, plan = [], []
        for i in range(4):
            aid = int(rng.integers(0, 3))
            if rng.random() < 0.25:
                p = rng.integers(1, 96, size=(int(rng.integers(4, 14)),)
                                 ).astype(np.int32)
                rids.append(eng.add_request(p, kind="embed",
                                            adapter_id=aid))
                plan.append((aid, p, "embed"))
            else:
                p = prompts[i % 3]
                rids.append(eng.add_request(p, max_new_tokens=4,
                                            adapter_id=aid))
                plan.append((aid, p, "generate"))
        while eng.has_unfinished():
            eng.step()
        for rid, (aid, p, kind) in zip(rids, plan):
            out = eng.finished_outputs.pop(rid)
            if kind == "embed":
                np.testing.assert_allclose(
                    out.embedding, _direct_pool(refs[aid], p),
                    rtol=2e-3, atol=2e-4)
            else:
                ref_eng = LLMEngine(refs[aid], max_batch=1,
                                    max_seq_len=64, chunk_size=8,
                                    scheduler="fused")
                (ref,) = ref_eng.generate([p], max_new_tokens=4)
                assert out.token_ids == ref.token_ids, (wave, aid)
    assert eng.stats["adapter_swaps"] > 4
