"""Automatic prefix caching (LLMEngine enable_prefix_cache=True).

The correctness bar is TOKEN-EXACTNESS against the uncached engine on the
same paged pool: content-hashed block reuse (full-block chain hits,
copy-on-write tails, LRU-cached retirement) reorders WHERE KV comes from
but must never change any slot's greedy stream. Covered here: mixed
shared/unshared workloads on both schedulers, live cross-slot sharing +
refcounts, COW tails, LRU eviction under pool pressure, preemption
interplay, the pool-invariant audit under churn (admit/cancel/preempt/
finish, dense and paged), allocation-order determinism, request-id reuse
with a hit in flight, and recorder/telemetry integration. The conftest
sets PADDLE_TPU_POOL_CHECKS=1, so every engine here
audits free + cached + live-refcounted == n_blocks after each alloc/free.

CPU-wall discipline: program compilation dominates, so the model is ONE
layer and the three workhorse engines (cache-off fused/legacy references
+ a cache-on fused engine) are module-scoped and reused drained; prompts
use per-test RNG seeds, so one test's cached content can never collide
with another's (different tokens -> different chain hashes). Tests that
need a dedicated pool shape (oversubscription, eviction, determinism)
build their own.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import AsyncLLMServer


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=1, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 96, size=(n,)).astype(np.int32) for n in sizes]


def _shared_workload(seed, sys_len, tail_sizes):
    """Prompts opening with one shared system prefix + unique tails."""
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(1, 96, size=(sys_len,)).astype(np.int32)
    return [np.concatenate([sys_p, rng.integers(1, 96, size=(n,))
                            .astype(np.int32)]) for n in tail_sizes]


def _engine(model, cache_on, scheduler="fused", **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("chunk_size", 16)
    kw.setdefault("block_size", 8)
    return LLMEngine(model, cache_impl="paged", scheduler=scheduler,
                     enable_prefix_cache=cache_on, **kw)


@pytest.fixture(scope="module")
def ref_fused(tiny_model):
    """Shared cache-OFF fused engine: the parity reference."""
    return _engine(tiny_model, False)


@pytest.fixture(scope="module")
def ref_legacy(tiny_model):
    return _engine(tiny_model, False, "legacy")


@pytest.fixture(scope="module")
def on_fused(tiny_model):
    """Shared cache-ON fused engine. Its store is WARM across tests —
    harmless by construction (per-test prompt seeds cannot collide) and
    exactly the long-lived-server shape the cache must serve."""
    return _engine(tiny_model, True)


def _fresh(eng):
    assert all(s is None for s in eng.slots) and not eng.waiting
    eng.finished_outputs.clear()
    eng.reset_stats()
    return eng


def _pool_accounted(eng):
    """free + LRU-cached + live-refcounted distinct blocks == n_blocks."""
    live = {p for blocks in eng._slot_blocks for p in blocks}
    return len(eng._free_blocks) + len(eng._lru) + len(live) == eng.n_blocks


class TestGreedyParity:
    @pytest.mark.parametrize("scheduler", ["fused", "legacy"])
    def test_mixed_shared_unshared_workload(self, request, tiny_model,
                                            scheduler):
        """Shared-prefix prompts interleaved with unrelated ones: cache-on
        streams identical to cache-off, with hit tokens actually served
        from the store."""
        shared = _shared_workload(1, 20, (5, 9, 3))
        lone = _prompts(2, (13,))
        prompts = [shared[0], lone[0], shared[1], shared[2]]
        off = _fresh(request.getfixturevalue(f"ref_{scheduler}"))
        ref = [o.token_ids for o in off.generate(prompts, max_new_tokens=6)]
        on = _fresh(request.getfixturevalue("on_fused")) \
            if scheduler == "fused" else _engine(tiny_model, True, "legacy")
        out = [o.token_ids for o in on.generate(prompts, max_new_tokens=6)]
        assert out == ref
        assert on.stats["prefix_hit_tokens"] > 0
        # hit tokens were NOT prefilled: the two stats partition the
        # prompt work
        assert on.stats["prefill_tokens"] < off.stats["prefill_tokens"]
        assert on.stats["prefill_tokens"] + on.stats["prefix_hit_tokens"] \
            == sum(len(p) for p in prompts)

    def test_warm_identical_prompt_capped_at_p_minus_1(self, ref_fused,
                                                       on_fused):
        """Resubmitting an identical prompt hits (almost) everything —
        capped at P-1 so the last position still recomputes and admission
        still has last-token logits to sample from."""
        (p,) = _shared_workload(3, 24, (0,))  # 24 tokens, block-aligned
        (ref,) = _fresh(ref_fused).generate([p], max_new_tokens=6)
        on = _fresh(on_fused)
        (first,) = on.generate([p], max_new_tokens=6)
        hits0 = on.stats["prefix_hit_tokens"]
        (again,) = on.generate([p], max_new_tokens=6)
        assert first.token_ids == ref.token_ids
        assert again.token_ids == ref.token_ids
        hit = on.stats["prefix_hit_tokens"] - hits0
        assert 0 < hit <= len(p) - 1

    def test_cow_tail_extends_hit_to_token_granularity(self, ref_fused,
                                                       on_fused):
        """A prefix hit ending mid-block copies the matching cached block
        into a private tail (copy-on-write) instead of re-prefilling or
        appending into shared content."""
        prompts = _shared_workload(4, 20, (7, 11))  # 20 % 8 != 0 -> tails
        ref = [o.token_ids for o in
               _fresh(ref_fused).generate(prompts, max_new_tokens=6)]
        on = _fresh(on_fused)
        # serialize so the second request admits after the first
        # registered its prompt blocks
        a = on.add_request(prompts[0], max_new_tokens=6)
        while on.has_unfinished():
            on.step()
        b = on.add_request(prompts[1], max_new_tokens=6)
        while on.has_unfinished():
            on.step()
        assert [on.finished_outputs[a].token_ids,
                on.finished_outputs[b].token_ids] == ref
        assert on.stats["prefix_cow_blocks"] >= 1
        # block-granular hit is 16 of the 20 shared tokens; the COW tail
        # reaches the full shared span
        assert on.stats["prefix_hit_tokens"] >= 20

    def test_dense_rejects_prefix_cache(self, tiny_model):
        with pytest.raises(ValueError, match="paged"):
            LLMEngine(tiny_model, max_batch=1, max_seq_len=64,
                      chunk_size=16, enable_prefix_cache=True)


class TestSharingAndEviction:
    def test_live_cross_slot_sharing_and_cancel(self, ref_fused, on_fused):
        """Two concurrent same-prefix requests reference the SAME physical
        blocks (refcount 2); cancelling one releases its refs without
        perturbing the survivor's stream."""
        prompts = _shared_workload(5, 16, (3, 5))
        ref = [o.token_ids for o in
               _fresh(ref_fused).generate(prompts, max_new_tokens=8)]
        on = _fresh(on_fused)
        # ramp the first fully in, then admit the second mid-decode
        a = on.add_request(prompts[0], max_new_tokens=8)
        for _ in range(4):
            on.step()
        b = on.add_request(prompts[1], max_new_tokens=8)
        for _ in range(2):
            on.step()
        sa = next(i for i, s in enumerate(on.slots)
                  if s is not None and s.req.request_id == a)
        sb = next(i for i, s in enumerate(on.slots)
                  if s is not None and s.req.request_id == b)
        shared_blocks = set(on._slot_blocks[sa]) & set(on._slot_blocks[sb])
        assert shared_blocks, "no physical block shared across slots"
        assert all(on._block_ref[p] == 2 for p in shared_blocks)
        on.cancel(b)
        assert all(on._block_ref[p] == 1 for p in shared_blocks)
        while on.has_unfinished():
            on.step()
        assert on.finished_outputs[a].token_ids == ref[0]
        assert _pool_accounted(on)

    def test_lru_eviction_under_pressure(self, tiny_model):
        """Distinct prompts through a small pool: retired content parks in
        the LRU and is evicted (oldest first) when allocation runs dry —
        never leaked, never blocking a new admission."""
        prompts = _prompts(6, (17, 19, 21, 15))
        off = _engine(tiny_model, False, max_batch=1, kv_pool_blocks=8)
        ref = [o.token_ids for o in off.generate(prompts, max_new_tokens=4)]
        on = _engine(tiny_model, True, max_batch=1, kv_pool_blocks=8)
        out = [o.token_ids for o in on.generate(prompts, max_new_tokens=4)]
        assert out == ref
        assert on.stats["prefix_evicted_blocks"] > 0
        assert len(on._free_blocks) + len(on._lru) == on.n_blocks
        assert all(t == -1 for t in on._tables.ravel())

    def test_oversubscribed_pool_preempts_exactly_with_cache(self,
                                                             tiny_model):
        """Cache-on over an oversubscribed pool: the LRU is consumed
        before any live slot is preempted, preemption still fires when
        both run dry (DISTINCT prompts growing together, so sharing
        cannot absorb the pressure), and the preempted request's
        re-prefill HITS its own previously committed blocks — streams
        stay exact throughout. Leaf-first LRU release is what keeps the
        chain's head cached here."""
        prompts = _prompts(7, (15, 17))
        off = _engine(tiny_model, False, kv_pool_blocks=8)
        ref = [o.token_ids for o in off.generate(prompts,
                                                 max_new_tokens=20)]
        on = _engine(tiny_model, True, kv_pool_blocks=8)
        outs = on.generate(prompts, max_new_tokens=20)
        assert [o.token_ids for o in outs] == ref
        assert on.stats["preemptions"] >= 1
        assert on.stats["prefix_hit_tokens"] > 0
        assert all(o.finished for o in outs)
        assert len(on._free_blocks) + len(on._lru) == on.n_blocks


class TestPoolInvariantsChurn:
    @pytest.mark.parametrize("cache_impl,cache_on",
                             [("dense", False), ("paged", False),
                              ("paged", True)])
    def test_churn_admit_cancel_preempt_finish(self, request, tiny_model,
                                               cache_impl, cache_on):
        """Random admit/cancel/finish churn (+ pool-pressure preemption
        on the oversubscribed paged variants) proving no block leaks: the
        per-operation audit (PADDLE_TPU_POOL_CHECKS, on suite-wide)
        asserts free + cached + live == n_blocks inside the loop, and the
        drained pool accounts for every block."""
        if cache_impl == "dense":
            eng = LLMEngine(tiny_model, cache_impl="dense", max_batch=2,
                            max_seq_len=64, chunk_size=16,
                            scheduler="fused")
        else:
            eng = _engine(tiny_model, cache_on, kv_pool_blocks=10)
            assert eng._debug_pool, "conftest must arm the pool audit"
        rng = np.random.default_rng(8)
        shared = _shared_workload(9, 10, tuple(rng.integers(2, 14, 10)))
        live = []
        for i, p in enumerate(shared):
            rid = eng.add_request(p, max_new_tokens=int(rng.integers(2, 8)))
            live.append(rid)
            for _ in range(int(rng.integers(1, 4))):
                for out in eng.step():
                    if out.request_id in live:
                        live.remove(out.request_id)
            if live and rng.random() < 0.5:
                victim = live.pop(int(rng.integers(0, len(live))))
                eng.cancel(victim)
        while eng.has_unfinished():
            eng.step()
        if cache_impl == "paged":
            assert not any(eng._slot_blocks)
            assert len(eng._free_blocks) + len(eng._lru) == eng.n_blocks
            assert all(t == -1 for t in eng._tables.ravel())
            if not cache_on:
                assert len(eng._free_blocks) == eng.n_blocks


class TestDeterministicLayout:
    @pytest.mark.slow  # 8s: runs the whole workload twice for layout
    # determinism (conftest wall-budget policy); functional prefix-cache
    # parity stays tier-1 throughout this file
    def test_identical_runs_produce_identical_tables(self, tiny_model):
        """Allocation pops the smallest free index (order-stable heap),
        so two identical runs — including retirements and LRU churn
        between requests — lay physical blocks out identically step for
        step (the old LIFO free list made layout depend on retirement
        history)."""
        def run(cache_on):
            eng = _engine(tiny_model, cache_on, max_batch=2)
            prompts = _shared_workload(11, 12, (5, 9, 7))
            for p in prompts[:2]:
                eng.add_request(p, max_new_tokens=4)
            history = []
            steps = 0
            while eng.has_unfinished():
                eng.step()
                steps += 1
                if steps == 3:  # mid-run admission reuses retired blocks
                    eng.add_request(prompts[2], max_new_tokens=4)
                history.append([list(b) for b in eng._slot_blocks])
            return history

        assert run(False) == run(False)
        assert run(True) == run(True)


class TestRequestIdReuse:
    def test_rid_reuse_and_cancel_with_hit_in_flight(self, tiny_model,
                                                     ref_fused):
        """Satellite: the PR-4 rid-reuse coverage, now on the CACHED
        path. A request with a prefix hit is cancelled mid-flight, its
        shared refs release cleanly, and a server restart that REUSES its
        request id starts a fresh timeline whose admission hits the
        cache — streams stay exact."""
        from paddle_tpu.profiler.flight_recorder import FlightRecorder

        seed, follow = _shared_workload(12, 16, (4, 7))
        (ref,) = _fresh(ref_fused).generate([follow], max_new_tokens=6)
        eng = _engine(tiny_model, True)
        rec = FlightRecorder()
        server = AsyncLLMServer(eng, max_queue_size=8, flight_recorder=rec)
        with server:
            server.submit(seed, max_new_tokens=4).result(timeout=240)
            h = server.submit(follow, max_new_tokens=30)  # rid 1: hits
            stream = h.tokens(timeout=240)
            next(stream)                                  # mid-decode
            h.cancel()
            assert h.result(timeout=240).finish_reason == "cancelled"
        # cancellation released the shared refs: nothing live remains
        assert _pool_accounted(eng)
        assert not any(eng._slot_blocks)
        # second server on the same engine: request ids RESTART, and the
        # reused rid 0 admission hits content cached by the first server
        hits0 = eng.stats["prefix_hit_tokens"]
        server2 = AsyncLLMServer(eng, max_queue_size=8,
                                 flight_recorder=rec)
        with server2:
            r = server2.submit(follow, max_new_tokens=6).result(timeout=240)
        assert r.token_ids == ref.token_ids
        assert eng.stats["prefix_hit_tokens"] > hits0
        tl = rec.request_trace(0)
        kinds = [e["kind"] for e in tl["events"]]
        # fresh lifecycle (no resurrection of server-1's rid 0) AND the
        # cached_prefix span landed on the reused id's new timeline
        assert kinds[0] == "queued"
        assert "cached_prefix" in kinds

    def test_engine_level_rid_reuse_after_cancel(self, ref_fused,
                                                 on_fused):
        seed, follow = _shared_workload(13, 16, (3, 5))
        (ref,) = _fresh(ref_fused).generate([follow], max_new_tokens=5)
        on = _fresh(on_fused)
        on.generate([seed], max_new_tokens=3)
        rid = on.add_request(follow, max_new_tokens=5, request_id=77)
        on.step()                        # hit admitted, decode in flight
        on.cancel(rid)
        on.finished_outputs.pop(rid)
        rid2 = on.add_request(follow, max_new_tokens=5, request_id=77)
        while on.has_unfinished():
            on.step()
        assert on.finished_outputs[rid2].token_ids == ref.token_ids
        assert _pool_accounted(on)


class TestObservability:
    def test_server_telemetry_and_recorder_join(self, on_fused):
        """Serving a shared-prefix workload surfaces the cache in every
        observability layer: telemetry counters + gauges, StepRecord
        prefix fields, and the cached_prefix span in request traces."""
        prompts = _shared_workload(14, 16, (3, 6, 4))
        eng = _fresh(on_fused)
        server = AsyncLLMServer(eng, max_queue_size=8,
                                flight_recorder=True)
        with server:
            handles = [server.submit(p, max_new_tokens=5) for p in prompts]
            results = [h.result(timeout=240) for h in handles]
        snap = server.telemetry.snapshot()
        assert snap["counters"]["prefix_hit_tokens"] \
            == eng.stats["prefix_hit_tokens"] > 0
        assert snap["gauges"]["prefix_cached_blocks"] >= 0
        assert 0.0 < snap["gauges"]["prefix_cache_hit_rate"] < 1.0
        text = server.telemetry.prometheus_text()
        assert "paddle_tpu_serving_prefix_hit_tokens_total" in text
        assert "# TYPE paddle_tpu_serving_prefix_cached_blocks gauge" \
            in text
        rec = server.flight_recorder
        recs = rec.records()
        assert any(r.prefix_hit_tokens for r in recs)
        assert all(r.cached_blocks is not None for r in recs)
        # at least one later request's timeline carries the hit span,
        # stamped with the step id that followed the admission
        hit_spans = [e for r in results if r.trace
                     for e in r.trace["events"]
                     if e["kind"] == "cached_prefix"]
        assert hit_spans and all(e["value"] > 0 for e in hit_spans)

    @pytest.mark.parametrize("step_hit,rid_hit,expect", [
        # cold admission's chunk grant interferes -> cold miss
        (0, None, True),
        # LATER chunk grant of a partially cache-served prompt: the
        # step's own hit delta is 0, but the REQUEST had a hit — must
        # not be labelled cold (the join goes through the request's
        # cached_prefix record, not the step delta)
        (0, 16, False),
        # the admission step itself, cache-served
        (16, 16, False),
        # cache off: no nod at all
        (None, None, None),
    ])
    def test_explain_tail_cold_miss_nod(self, step_hit, rid_hit, expect):
        """A tail gap caused by interfering prefill names whether the
        interfering REQUEST was a cold miss the cache could not absorb;
        without a prefix cache there is no nod."""
        from paddle_tpu.profiler.flight_recorder import FlightRecorder

        rec = FlightRecorder(capacity=16)
        if rid_hit is not None:
            rec.req_event(1, "cached_prefix", step_id=0, value=rid_hit)
        sid = rec.begin_step(
            scheduler="fused", kind="mixed",
            grants=((0, 1, "prefill", 16), (1, 2, "decode", 1)),
            tokens_scheduled=17, token_budget=32, queue_depth=0,
            free_blocks=4, total_blocks=16, pipeline_inflight=1,
            preemptions=(), admit_s=0.0, schedule_s=0.0,
            dispatch_s=0.1, t_begin=100.0, prefix_hit_tokens=step_hit,
            cached_blocks=3)
        rec.finish_step(sid, 0.0, 0.0)
        rec.get_step(sid).t_finish = 100.1        # pin the wall
        with rec._lock:                           # inject an exact gap
            tr = rec._trace(2)
            tr.events.append(("token", 100.0, sid, None))
            tr.events.append(("token", 100.1, sid, 0.1))
        (expl,) = rec.explain_tail(0.5)
        assert expl["cause"] == "interfering_prefill"
        if expect is None:
            assert "cold_miss" not in expl
        else:
            assert expl["cold_miss"] is expect

    @pytest.mark.parametrize("mixed_hit", [False, True])
    def test_explain_tail_cold_miss_legacy_admit_train(self, mixed_hit):
        """Legacy shape (no prefill grants; the admission train ran
        inside the step's admit split): the nod joins through the
        prefill spans stamped with the step's id, so a COLD admission is
        named even when a cache-served one admitted in the SAME train
        (whose hit would mask it in the step's own delta)."""
        from paddle_tpu.profiler.flight_recorder import FlightRecorder

        rec = FlightRecorder(capacity=16)
        sid = rec.next_step_id()
        if mixed_hit:
            # request 1: cache-served admission in the same train
            rec.req_event(1, "cached_prefix", step_id=sid, value=16)
            rec.req_event(1, "prefill", step_id=sid, value=8)
        rec.req_event(2, "prefill", step_id=sid, value=16)  # cold
        assert rec.begin_step(
            scheduler="legacy", kind="decode", grants=(),
            tokens_scheduled=0, token_budget=8, queue_depth=0,
            free_blocks=4, total_blocks=16, pipeline_inflight=1,
            preemptions=(), admit_s=0.08, schedule_s=0.0,
            dispatch_s=0.02, t_begin=100.0,
            prefix_hit_tokens=16 if mixed_hit else 0,
            cached_blocks=3) == sid
        rec.finish_step(sid, 0.0, 0.0)
        rec.get_step(sid).t_finish = 100.1
        with rec._lock:
            tr = rec._trace(2)
            tr.events.append(("token", 100.0, sid, None))
            tr.events.append(("token", 100.1, sid, 0.1))
        (expl,) = rec.explain_tail(0.5)
        assert expl["cause"] == "interfering_prefill"
        assert expl["cold_miss"] is True
