"""Multi-step on-device decode (LLMEngine readout_stride) + deep
pipelining — the host-sync-tax PR's acceptance matrix.

The correctness bar is GREEDY TOKEN-EXACTNESS against the legacy
admit-then-decode engine across readout_stride in {1, 2, 4} x pipeline
depth in {1, 2, 3} x dense/paged, including mid-stride in-graph early
exit (every slot finishes before the stride ends), per-request
latency-tier stride pins, the stride-aware in-flight write fence under
oversubscribed-pool preemption, and a supervised-restart chaos case
where the crash lands around a multi-step dispatch. The flag-off
contract — readout_stride=1 at depth <= 2 — must stay bit-identical to
the pre-stride engine (scan path only, no multi-step program compiled).
"""
import collections

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

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


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, size=(n,)).astype(np.int32) for n in sizes]


def _engine(model, cache_impl="dense", **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("chunk_size", 16)
    if cache_impl == "paged":
        kw.setdefault("block_size", 8)
    return LLMEngine(model, cache_impl=cache_impl, **kw)


@pytest.fixture(scope="module")
def engines(tiny_model):
    """One fused engine per (cache_impl, stride) plus the legacy parity
    references — module-scoped so each program set compiles once."""
    out = {}
    for cache in ("dense", "paged"):
        out[cache, "legacy"] = _engine(tiny_model, cache)
        for stride in (1, 2, 4):
            out[cache, stride] = _engine(tiny_model, cache,
                                         scheduler="fused",
                                         readout_stride=stride)
    return out


def _fresh(eng):
    assert all(s is None for s in eng.slots)
    assert not eng.waiting
    eng.finished_outputs.clear()
    eng.reset_stats()
    return eng


def _drain_at_depth(eng, depth):
    """Drive the engine with up to ``depth`` step_begin()s in flight
    before each oldest step_finish() — the deque discipline the serving
    loop uses, at engine level so the matrix needs no threads."""
    outs = {}
    pending = collections.deque()
    while eng.has_unfinished() or pending:
        while len(pending) < depth and eng.has_unfinished():
            p = eng.step_begin()
            if p is None:
                break
            pending.append(p)
        if not pending:
            break
        for o in eng.step_finish(pending.popleft()):
            outs[o.request_id] = o
    return outs


# ---------------------------------------------------------------------------
# the acceptance parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_impl", ["dense", "paged"])
@pytest.mark.parametrize("stride", [1, 2, 4])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_parity_matrix(engines, cache_impl, stride, depth):
    """Greedy token-exact vs the legacy engine for every
    (readout_stride, pipeline_depth) combination, dense and paged."""
    prompts = _prompts(1, (16, 17, 15, 5))
    legacy = _fresh(engines[cache_impl, "legacy"])
    ref = {i: o.token_ids
           for i, o in enumerate(legacy.generate(prompts,
                                                 max_new_tokens=8))}
    eng = _fresh(engines[cache_impl, stride])
    assert depth <= eng.max_pipeline_depth()
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = _drain_at_depth(eng, depth)
    assert [outs[r].token_ids for r in rids] == \
        [ref[i] for i in range(len(prompts))]
    if stride > 1:
        assert eng.stats["multi_steps"] > 0
    if cache_impl == "paged":
        assert len(eng._free_blocks) == eng.n_blocks
        assert not eng._write_fence and not eng._quarantine


def test_mid_stride_early_exit(engines):
    """Every slot hits eos before the stride ends: the while_loop exits
    in-graph, the readout sees only the live rows, and the stream
    matches the per-step engine exactly."""
    (p,) = _prompts(2, (9,))
    legacy = _fresh(engines["dense", "legacy"])
    (probe,) = legacy.generate([p], max_new_tokens=12)
    eos = probe.token_ids[2]        # eos lands 3 tokens in — mid-stride
    _fresh(legacy)
    (ref,) = legacy.generate([p], max_new_tokens=12, eos_token_id=eos)
    eng = _fresh(engines["dense", 4])
    (out,) = eng.generate([p], max_new_tokens=12, eos_token_id=eos)
    assert out.token_ids == ref.token_ids
    assert out.finish_reason == "eos"
    assert eng.stats["multi_steps"] >= 1
    # the whole post-ramp stream fit inside multi-step dispatches
    assert eng.stats["tokens_generated"] == len(ref.token_ids)
    _fresh(legacy)


def test_latency_tier_pin_forces_stride_1(engines):
    """A request pinning readout_stride=1 drags every all-decode step it
    is resident in back to per-step readout (the documented latency-tier
    tradeoff) — and tokens stay exact."""
    p1, p2 = _prompts(3, (16, 17))
    legacy = _fresh(engines["dense", "legacy"])
    ref = [o.token_ids for o in legacy.generate([p1, p2],
                                                max_new_tokens=8)]
    eng = _fresh(engines["dense", 4])
    a = eng.add_request(p1, max_new_tokens=8)
    b = eng.add_request(p2, max_new_tokens=8, readout_stride=1)
    while eng.has_unfinished():
        eng.step()
    assert eng.finished_outputs[a].token_ids == ref[0]
    assert eng.finished_outputs[b].token_ids == ref[1]
    # the pin suppressed every multi-step dispatch while b was resident
    assert eng.stats["multi_steps"] == 0
    eng.finished_outputs.clear()


def test_flag_off_bit_identical(tiny_model):
    """readout_stride=1 + depth <= 2 is the pre-stride engine: the scan
    path serves every all-decode step, no multi-step program is ever
    built, and the emit stamps carry no backdate."""
    prompts = _prompts(4, (9, 14))
    eng = _engine(tiny_model, "dense", scheduler="fused")
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    outs = _drain_at_depth(eng, 2)
    assert all(outs[r].finished for r in rids)
    assert eng.stats["multi_steps"] == 0
    assert eng._multi_fns == {}
    assert eng.emit_backdate_s == 0.0


# ---------------------------------------------------------------------------
# the depth contract + the in-flight write fence
# ---------------------------------------------------------------------------

def test_depth_contract(tiny_model, engines):
    assert engines["dense", "legacy"].max_pipeline_depth() == 2
    assert engines["paged", "legacy"].max_pipeline_depth() == 1
    assert engines["dense", 4].max_pipeline_depth() == 3
    assert engines["paged", 4].max_pipeline_depth() == 3   # full pool
    over = _engine(tiny_model, "paged", scheduler="fused",
                   kv_pool_blocks=8)
    assert over.max_pipeline_depth() == 2   # oversubscribed: fence-capped
    spec = LLMEngine(tiny_model, max_batch=1, max_seq_len=64,
                     chunk_size=16, speculative_k=3)
    assert spec.max_pipeline_depth() == 2


def test_paged_depth_guard_allows_3_rejects_4(engines):
    eng = _fresh(engines["paged", 2])
    eng.add_request(_prompts(5, (6,))[0], max_new_tokens=16)
    pendings = []
    while len(pendings) < 3:
        pendings.append(eng.step_begin())
    with pytest.raises(RuntimeError, match="pipeline"):
        eng.step_begin()
    for p in pendings:
        eng.step_finish(p)
    while eng.has_unfinished():
        eng.step()
    eng.finished_outputs.clear()


def test_oversubscribed_preemption_under_pipelining_stays_exact(
        tiny_model, engines):
    """Pool pressure preempts mid-flight at depth 2 with a stride: the
    write fence quarantines the victim's still-being-written blocks
    (never re-handed early), streams stay token-exact, and the pool
    reconciles to fully free with no fence residue."""
    prompts = _prompts(6, (25, 27))
    full = _fresh(engines["paged", "legacy"])
    ref = [o.token_ids for o in full.generate(prompts, max_new_tokens=10)]
    sub = _engine(tiny_model, "paged", scheduler="fused",
                  kv_pool_blocks=8, readout_stride=2)
    rids = [sub.add_request(p, max_new_tokens=10) for p in prompts]
    outs = _drain_at_depth(sub, 2)
    assert [outs[r].token_ids for r in rids] == ref
    assert sub.stats["preemptions"] >= 1
    assert len(sub._free_blocks) == 8
    assert not sub._write_fence and not sub._quarantine


def test_release_under_fence_quarantines(tiny_model):
    """Unit-level fence semantics: a fenced block released at refcount 0
    parks in quarantine (not the free heap) until its last in-flight
    fence drops, then returns to the free heap."""
    eng = _engine(tiny_model, "paged", scheduler="fused")
    eng.add_request(_prompts(7, (6,))[0], max_new_tokens=4)
    pending = eng.step_begin()          # admits + dispatches, fences blocks
    assert pending.fenced
    phys = pending.fenced[0]
    assert eng._write_fence[phys] >= 1
    # simulate the eviction path: force-release the slot's blocks while
    # the dispatch is still in flight
    eng.cancel(0)
    assert phys in eng._quarantine
    assert phys not in eng._free_blocks
    eng.step_finish(pending)            # fence drops -> block frees
    assert phys not in eng._quarantine
    assert phys in eng._free_blocks
    eng._check_pool_invariants()
    eng.finished_outputs.clear()


def test_registered_block_release_under_fence_quarantines(tiny_model):
    """The fence outranks prefix-cache registration: a mixed-step
    prefill grant REGISTERS its just-filled blocks at dispatch time, so
    a block can be registered and fenced at once — releasing it then
    must quarantine it (never park it in the LRU, where _pop_block
    would re-hand it fence-blind), and the unfence routes it onward to
    the LRU its registration earns."""
    eng = _engine(tiny_model, "paged", scheduler="fused",
                  enable_prefix_cache=True)
    (p,) = _prompts(14, (12,))
    eng.add_request(p, max_new_tokens=4)
    pending = eng.step_begin()      # one 12-token grant; block 0 fills,
    reg = [ph for ph in pending.fenced if ph in eng._block_hash]
    assert reg, "grant did not register a fenced block at dispatch"
    eng.cancel(0)                   # release while the fence is live
    for ph in reg:
        assert ph in eng._quarantine
        assert ph not in eng._lru and ph not in eng._free_blocks
    eng.step_finish(pending)        # fence drops -> registered -> LRU
    for ph in reg:
        assert ph in eng._lru and ph not in eng._quarantine
    eng._check_pool_invariants()
    eng.finished_outputs.clear()


def test_probe_attaches_quarantined_registered_block(tiny_model):
    """A prefix probe may attach a registered block straight out of
    quarantine (the in-flight write IS the registered content and
    precedes any reader in program order) — the block must leave
    quarantine on attach, the hit must serve, and the stream must stay
    token-exact vs a cold run."""
    (p,) = _prompts(15, (12,))
    ref_eng = _engine(tiny_model, "paged", scheduler="fused")
    (ref,) = ref_eng.generate([p], max_new_tokens=4)
    eng = _engine(tiny_model, "paged", scheduler="fused",
                  enable_prefix_cache=True)
    eng.add_request(p, max_new_tokens=4)
    pending = eng.step_begin()
    reg = [ph for ph in pending.fenced if ph in eng._block_hash]
    assert reg
    eng.cancel(0)
    assert all(ph in eng._quarantine for ph in reg)
    rid = eng.add_request(p, max_new_tokens=4)
    pending2 = eng.step_begin()     # admission probes the content store
    assert all(ph not in eng._quarantine for ph in reg)
    assert eng.stats["prefix_hit_tokens"] >= 8
    eng.step_finish(pending)
    eng.step_finish(pending2)
    while eng.has_unfinished():
        eng.step()
    assert eng.finished_outputs[rid].token_ids == ref.token_ids
    eng._check_pool_invariants()
    eng.finished_outputs.clear()


# ---------------------------------------------------------------------------
# serving: depth 3 + stride through AsyncLLMServer, amortized stamps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_impl", ["dense", "paged"])
def test_serve_depth3_stride4_token_exact(engines, cache_impl):
    from paddle_tpu.serving import AsyncLLMServer

    prompts = _prompts(8, (9, 17, 12, 5))
    legacy = _fresh(engines[cache_impl, "legacy"])
    ref = [o.token_ids for o in legacy.generate(prompts,
                                                max_new_tokens=8)]
    eng = _fresh(engines[cache_impl, 4])
    server = AsyncLLMServer(eng, max_queue_size=8, pipeline_depth=3)
    assert server.pipeline_depth == 3
    with server:
        handles = [server.submit(p, max_new_tokens=8) for p in prompts]
        results = [h.result(timeout=240) for h in handles]
    assert [r.token_ids for r in results] == ref
    snap = server.telemetry.snapshot()
    assert snap["counters"]["multi_steps"] >= 1
    assert snap["counters"]["tokens_emitted"] == sum(len(r) for r in ref)


def test_server_stride_pin_plumbs_through(engines):
    """submit(readout_stride=1) reaches the engine request (the pin
    survives re-admission) and the serve still streams exactly."""
    from paddle_tpu.serving import AsyncLLMServer

    (p,) = _prompts(9, (9,))
    legacy = _fresh(engines["dense", "legacy"])
    (ref,) = legacy.generate([p], max_new_tokens=6)
    eng = _fresh(engines["dense", 4])
    server = AsyncLLMServer(eng, max_queue_size=4)
    with server:
        h = server.submit(p, max_new_tokens=6, readout_stride=1)
        res = h.result(timeout=120)
        with pytest.raises(ValueError, match="readout_stride"):
            server.submit(p, readout_stride=0)
    assert res.token_ids == ref.token_ids
    assert eng.stats["multi_steps"] == 0     # pin held the whole serve


def test_amortized_stamps_monotonic_and_spread(engines):
    """A k-row batched readout backdates each row to its amortized
    device step boundary: the recorder's per-token gaps are monotone
    non-negative, and the k rows of one stride do NOT all collapse onto
    one stamp (k-1 zero-gaps + one spike is exactly the artifact the
    amortization removes)."""
    from paddle_tpu.profiler import FlightRecorder

    eng = _fresh(engines["dense", 4])
    rec = FlightRecorder()
    eng.flight_recorder = rec
    try:
        (out,) = eng.generate(_prompts(10, (9,)), max_new_tokens=12)
    finally:
        eng.flight_recorder = None
    tl = rec.request_trace(out.request_id)
    toks = [e for e in tl["events"] if e["kind"] == "token"]
    assert len(toks) == 12
    gaps = [e["value"] for e in toks if e["value"] is not None]
    assert all(g >= 0.0 for g in gaps)
    stamps = [e["t"] for e in toks]
    assert stamps == sorted(stamps)
    # rows within one multi-step readout carry distinct amortized stamps
    by_step = collections.Counter(e["step_id"] for e in toks)
    multi_sids = [sid for sid, n in by_step.items() if n > 1]
    assert multi_sids, "no multi-row readout recorded"
    for sid in multi_sids:
        row_stamps = [e["t"] for e in toks if e["step_id"] == sid]
        assert len(set(row_stamps)) == len(row_stamps)
    # the StepRecord schema carries the stride
    strides = {r.readout_stride for r in rec.records()}
    assert 4 in strides
    eng.finished_outputs.clear()


# ---------------------------------------------------------------------------
# supervised-restart chaos around a multi-step dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config", [
    dict(),
    dict(cache_impl="paged", block_size=8),
    dict(cache_impl="paged", block_size=8, enable_prefix_cache=True),
], ids=["dense", "paged", "paged_prefix"])
@pytest.mark.parametrize("phase", ["begin", "finish"])
def test_crash_around_multi_step_dispatch_recovers_exact(tiny_model,
                                                         config, phase):
    """A crash landing at a multi-step dispatch boundary (phase=finish:
    a whole stride's tokens are still unread on the device when the
    loop dies) recovers token-exactly under supervise= at depth 3 with
    readout_stride=4 — the injector's schedule counts STRIDES, so the
    fault lands inside the multi-step regime, not at a per-token host
    pass."""
    from paddle_tpu.serving import (AsyncLLMServer, FaultInjector,
                                    RestartPolicy)

    prompts = _prompts(11, (9, 5, 17))
    eng = _engine(tiny_model, scheduler="fused", readout_stride=4,
                  **config)
    want = [o.token_ids for o in eng.generate(prompts, max_new_tokens=8)]
    _fresh(eng)

    fi = FaultInjector().crash_at_step(4, phase=phase)
    server = AsyncLLMServer(
        eng, max_queue_size=8, fault_injector=fi, pipeline_depth=3,
        supervise=RestartPolicy(max_restarts=2, backoff_s=0.01))
    with server:
        handles = [server.submit(p, max_new_tokens=8) for p in prompts]
        results = [h.result(timeout=240) for h in handles]
    assert [r.token_ids for r in results] == want
    assert fi.fired and fi.fired[0][0] == "raise"
    assert 1 <= server.restarts <= 2
    assert server.telemetry.snapshot()["counters"]["requests_resumed"] >= 1
    if eng.cache_impl == "paged":
        assert not eng._write_fence and not eng._quarantine
        eng._check_pool_invariants()


def test_hang_inside_multi_step_dispatch_serves_out(tiny_model):
    """An injected non-interruptible hang landing at a multi-step
    dispatch boundary stalls the loop but changes nothing: the stride's
    tokens drain after the hang, streams stay exact, and the injector's
    stride-counted schedule fired exactly once."""
    from paddle_tpu.serving import AsyncLLMServer, FaultInjector

    prompts = _prompts(13, (9, 17))
    eng = _engine(tiny_model, "paged", scheduler="fused",
                  readout_stride=4, enable_prefix_cache=True)
    want = [o.token_ids for o in eng.generate(prompts, max_new_tokens=8)]
    _fresh(eng)
    fi = FaultInjector().hang_at_step(3, 0.15, interruptible=False)
    server = AsyncLLMServer(eng, max_queue_size=8, fault_injector=fi,
                            pipeline_depth=3)
    with server:
        handles = [server.submit(p, max_new_tokens=8) for p in prompts]
        results = [h.result(timeout=240) for h in handles]
    assert [r.token_ids for r in results] == want
    assert fi.fired == [("hang", 3, 0.15)]
    assert not eng._write_fence and not eng._quarantine
    eng._check_pool_invariants()


# ---------------------------------------------------------------------------
# constructor contract + the stride by its counts
# ---------------------------------------------------------------------------

def test_stride_needs_fused(tiny_model):
    with pytest.raises(ValueError, match="fused"):
        LLMEngine(tiny_model, max_batch=1, max_seq_len=64, chunk_size=16,
                  readout_stride=4)
    with pytest.raises(ValueError, match="horizon"):
        LLMEngine(tiny_model, max_batch=1, max_seq_len=64, chunk_size=16,
                  scheduler="fused", horizon=4, readout_stride=4)
    with pytest.raises(ValueError, match="readout_stride"):
        eng = LLMEngine(tiny_model, max_batch=1, max_seq_len=64,
                        chunk_size=16, scheduler="fused")
        eng.add_request(np.asarray([3, 4], np.int32), readout_stride=0)


def test_stride_arm_syncs_less_by_count(tiny_model):
    """What the stride is for, by the engine's own counters and no clock:
    the same prompts through ``readout_stride=8`` and ``readout_stride=1``
    give equal greedy streams, only the stride arm dispatches multi-step
    programs, and it reads tokens back strictly fewer times
    (``stats["steps"]`` counts one per readout, the stride's one sync)."""
    prompts = _prompts(12, (20, 33, 17, 9, 25, 40))
    streams, stats = {}, {}
    for stride in (1, 8):
        eng = _engine(tiny_model, max_batch=3, max_seq_len=128,
                      scheduler="fused", readout_stride=stride)
        streams[stride] = [o.token_ids
                           for o in eng.generate(prompts, max_new_tokens=48)]
        stats[stride] = eng.stats
    assert streams[8] == streams[1]
    assert stats[8]["multi_steps"] > 0 and stats[1]["multi_steps"] == 0
    assert stats[8]["tokens_generated"] == stats[1]["tokens_generated"]
    assert stats[8]["steps"] < stats[1]["steps"]
