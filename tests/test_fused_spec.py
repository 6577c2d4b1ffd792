"""Batched speculative decoding through the fused scheduler (PR 10).

The correctness bar is TOKEN-EXACTNESS against the NON-speculative fused
engine: a verify grant (k prompt-lookup drafts + 1 committed token
dispatched through the one jitted mixed step / the multi-window
all-decode program) reorders how tokens are produced but must never
change any stream — greedy AND sampled (the coupled acceptance rule
samples each position under its per-(rid, position) fold_in key and
accepts a draft iff it matches, so the committed stream IS the plain
engine's stream). Covered here: the parity matrix (dense + paged x
prefix cache on/off x readout_stride {1,4} x pipeline depth {1,2}),
rejection rollback under pool pressure with the allocator audit armed,
acceptance-adaptive verify-k convergence, chaos (crash mid-verify-window
-> supervised restart -> token-exact resume), spec telemetry/flight-
recorder plumbing, and the speculative_k=1 no-op contract.

Wall-time note: greedy streams are token-exact ACROSS cache backends /
prefix cache / stride (the prior PRs' parity suites own those cross
checks), so ONE module-scoped non-speculative reference engine serves
every greedy cell here — each matrix cell compiles only its spec
engine.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import AsyncLLMServer, FaultInjector, RestartPolicy

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


@pytest.fixture(scope="module")
def greedy_ref(tiny_model):
    """Memoized greedy reference streams off ONE non-speculative fused
    dense engine — valid for every backend/prefix/stride cell (their
    cross-parity is owned by test_fused_scheduler/test_multi_step/
    test_prefix_cache)."""
    eng = LLMEngine(tiny_model, max_batch=3, max_seq_len=96,
                    chunk_size=16, scheduler="fused")
    cache = {}

    def ref(prompts, n):
        key = (tuple(tuple(int(t) for t in p) for p in prompts), n)
        if key not in cache:
            cache[key] = [o.token_ids
                          for o in eng.generate(prompts, max_new_tokens=n)]
        return cache[key]

    return ref


def _prompts(seed=14):
    """Mixed workload: a repetition-heavy prompt (drafts accept) and a
    random one (drafts mostly reject) — parity must hold on both."""
    rng = np.random.default_rng(seed)
    base = rng.integers(1, V, size=(6,)).astype(np.int32)
    return [np.concatenate([base, base, base[:3]]),
            rng.integers(1, V, size=(9,)).astype(np.int32)]


def _engine(model, spec_k=1, cache_impl="dense", **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("chunk_size", 16)
    if cache_impl == "paged":
        kw.setdefault("block_size", 8)
    return LLMEngine(model, cache_impl=cache_impl, scheduler="fused",
                     speculative_k=spec_k, **kw)


# ---------------------------------------------------------------------------
# the parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride,cache_impl,prefix", [
    # tier-1 wall budget (PR 19): the dense cell joins the stride-4
    # dense cell on the slow lane (~10s back) — spec-on-dense parity
    # stays covered there, and the two paged cells below keep spec
    # parity tier-1 on the cache impl the serving stack runs
    pytest.param(1, "dense", False, marks=pytest.mark.slow),
    # tier-1 wall budget (PR 14): the prefix-OFF paged cell rides
    # the slow lane — (1, paged, True) and (4, paged, True) keep
    # stride-1 and stride-4 paged spec parity tier-1
    pytest.param(1, "paged", False, marks=pytest.mark.slow),
    (1, "paged", True),
    # stride-4 tier-1 keeps the most composed cell (paged + prefix);
    # the remaining stride-4 cells ride the slow lane (wall budget —
    # the stride machinery itself is one shared program)
    (4, "paged", True),
    pytest.param(4, "dense", False, marks=pytest.mark.slow),
    pytest.param(4, "paged", False, marks=pytest.mark.slow)])
def test_greedy_parity_matrix(tiny_model, greedy_ref, cache_impl, prefix,
                              stride):
    """dense+paged x prefix cache on/off x readout_stride {1,4}: the
    speculative fused engine's greedy streams are identical to the
    non-speculative fused engine's, and on the repetitive prompt drafts
    actually accept (the speedup exists, not just the parity)."""
    prompts = _prompts()
    ref = greedy_ref(prompts, 10)
    kw = dict(enable_prefix_cache=prefix) if prefix else {}
    eng = _engine(tiny_model, 4, cache_impl, readout_stride=stride, **kw)
    out = [o.token_ids for o in eng.generate(prompts, max_new_tokens=10)]
    assert out == ref
    assert eng.stats["spec_proposed_tokens"] > 0
    assert eng.stats["spec_accepted_tokens"] > 0  # repetitive prompt
    if stride > 1:
        assert eng.stats["multi_steps"] > 0        # stride composition
    if cache_impl == "paged":
        eng._check_pool_invariants()
        assert len(eng._free_blocks) + len(eng._lru) == eng.n_blocks


@pytest.mark.parametrize("cache_impl", [
    # paged is the strict cell (rollback + fence/quarantine under
    # chained dispatches); the dense variant rides the slow lane
    pytest.param("dense", marks=pytest.mark.slow), "paged"])
def test_depth2_pipelined_parity(tiny_model, greedy_ref, cache_impl):
    """Depth-2 pipelining (the fused-spec depth contract) through
    AsyncLLMServer: streams stay token-exact while verify dispatches
    chain, and the pool drains clean."""
    prompts = _prompts(3)
    ref = greedy_ref(prompts, 10)
    eng = _engine(tiny_model, 4, cache_impl)
    assert eng.max_pipeline_depth() == 2
    server = AsyncLLMServer(eng, max_queue_size=8)
    assert server.pipeline_depth == 2
    with server:
        hs = [server.submit(p, max_new_tokens=10) for p in prompts]
        got = [h.result(timeout=240).token_ids for h in hs]
    assert got == ref
    if cache_impl == "paged":
        eng._check_pool_invariants()
        assert len(eng._free_blocks) == eng.n_blocks


def test_sampled_token_exact(tiny_model):
    """SAMPLED streams (temperature/top_p) are token-identical to the
    non-speculative fused engine — the coupled acceptance contract."""
    prompts = _prompts(5)
    paddle.seed(123)
    want = [o.token_ids for o in _engine(tiny_model, 1).generate(
        prompts, max_new_tokens=10, temperature=0.8, top_p=0.9)]
    paddle.seed(123)
    got = [o.token_ids for o in _engine(tiny_model, 4).generate(
        prompts, max_new_tokens=10, temperature=0.8, top_p=0.9)]
    assert got == want


@pytest.mark.parametrize("cache_impl", ["dense", "paged"])
def test_a_sampled_row_beside_a_greedy_row_through_the_gate(
        tiny_model, greedy_ref, cache_impl):
    """``row_sample`` draws behind the same ``lax.cond`` on the step's
    ``temps`` as ``sample_next`` (``pick_tokens``): with one sampled row
    of two the verify windows' coupled targets are still the tokens the
    non-speculative engine samples one position at a time, the greedy row
    beside it stays the reference's, and once the sampled request retires
    the programs run their argmax alone (``sampling_steps`` stops)."""
    prompts = _prompts(5)

    def serve(spec_k):
        eng = _engine(tiny_model, spec_k, cache_impl, sampling_seed=3)
        rids = [eng.add_request(prompts[0], max_new_tokens=6,
                                temperature=0.8, top_p=0.9, request_id=40),
                eng.add_request(prompts[1], max_new_tokens=20,
                                request_id=41)]
        while eng.has_unfinished():
            eng.step()
        return [eng.finished_outputs[r].token_ids for r in rids], eng.stats

    want, _ = serve(1)
    got, stats = serve(4)
    assert got == want
    assert got[1] == greedy_ref([prompts[1]], 20)[0]
    assert stats["spec_proposed_tokens"] > 0
    assert 0 < stats["sampling_steps"] < stats["steps"]


@pytest.mark.slow
def test_spec_mixes_with_embed_and_generate(tiny_model, greedy_ref):
    """One token-budget walk serves speculative generation AND
    prefill-only embedding requests: the verify grants don't perturb
    the embed pooling (parity vs a direct non-spec embed) and the
    generate streams stay exact. The same serve pass asserts the
    observability satellite: spec counters + acceptance gauge in the
    serving telemetry, verify-grant rows + spec acceptance fields on
    StepRecords, explain_tail causes within the taxonomy."""
    from paddle_tpu.profiler import FlightRecorder
    from paddle_tpu.profiler.flight_recorder import TAIL_CAUSES
    prompts = _prompts(7)
    ref = greedy_ref(prompts, 10)
    ref_eng = _engine(tiny_model, 1)
    with AsyncLLMServer(ref_eng) as srv:
        e_ref = srv.submit_embed(prompts[1]).result(240).embedding
    eng = _engine(tiny_model, 4)
    rec = FlightRecorder()
    server = AsyncLLMServer(eng, max_queue_size=8, flight_recorder=rec)
    with server:
        h1 = server.submit(prompts[0], max_new_tokens=10)
        he = server.submit_embed(prompts[1])
        h2 = server.submit(prompts[1], max_new_tokens=10)
        got = [h1.result(240).token_ids, h2.result(240).token_ids]
        emb = he.result(240).embedding
    assert got == ref
    np.testing.assert_allclose(emb, e_ref, rtol=1e-5, atol=1e-6)
    # -- telemetry: counters + the acceptance gauge --
    snap = server.telemetry.snapshot()
    assert snap["counters"]["spec_proposed_tokens"] > 0
    assert 0 < snap["counters"]["spec_accepted_tokens"] <= \
        snap["counters"]["spec_proposed_tokens"]
    assert 0 < snap["gauges"]["spec_acceptance_rate"] <= 1.0
    # -- flight recorder: verify grants, spec fields, cause taxonomy --
    recs = rec.records()
    verify_grants = [g for r in recs for g in r.grants
                     if g[2] == "verify"]
    assert verify_grants and all(g[3] >= 1 for g in verify_grants)
    spec_steps = [r for r in recs if r.kind == "spec"]
    assert spec_steps
    # verify rows report through the readout_stride field (the
    # batched-readout row-count contract)
    assert all(r.readout_stride >= eng.speculative_k for r in spec_steps)
    assert any(r.spec_accepted or r.spec_rejected for r in recs)
    for entry in rec.explain_tail(0.5):
        assert entry["cause"] in TAIL_CAUSES


# ---------------------------------------------------------------------------
# rollback under pool pressure
# ---------------------------------------------------------------------------

def test_rollback_under_preemption(tiny_model, greedy_ref):
    """Oversubscribed pool: verify windows shrink under pressure, the
    block-table rollback releases rejected tails through the fence/
    quarantine machinery (PADDLE_TPU_POOL_CHECKS is armed suite-wide),
    preemption replays token-exactly — the re-admitted request carries
    its acceptance EWMA on the GenerationRequest (the stride-pin
    pattern) — and the drained pool accounts for every block."""
    rng = np.random.default_rng(9)
    base = rng.integers(1, V, size=(5,)).astype(np.int32)
    prompts = [np.tile(base, 4)[:18],
               np.tile(base[::-1].copy(), 4)[:14],
               rng.integers(1, V, size=(11,)).astype(np.int32)]
    ref = greedy_ref(prompts, 16)
    eng = _engine(tiny_model, 4, "paged", max_batch=3, kv_pool_blocks=9)
    out = [o.token_ids for o in eng.generate(prompts, max_new_tokens=16)]
    assert out == ref
    eng._check_pool_invariants()
    assert len(eng._free_blocks) + len(eng._lru) == eng.n_blocks
    assert eng.stats["spec_proposed_tokens"] > 0


# ---------------------------------------------------------------------------
# acceptance-adaptive verify-k
# ---------------------------------------------------------------------------

def test_adaptive_k_converges(tiny_model):
    """The EWMA drives the granted draft count: a zero-acceptance
    stream converges to the minimum window (1 draft), a full-acceptance
    stream recovers to the maximum (speculative_k - 1), and the state
    persists in the engine's rid-keyed mirror."""
    from paddle_tpu.inference.llm_engine import GenerationRequest, _Slot
    eng = _engine(tiny_model, 5)
    req = GenerationRequest(0, np.zeros((4,), np.int32))
    slot = _Slot(req, 4)
    assert eng._spec_k_for(slot) == 4          # optimistic default
    for _ in range(12):
        eng._update_spec_ewma(slot, proposed=4, accepted=0)
    assert eng._spec_k_for(slot) == 1          # collapsed, never 0
    assert eng._spec_ewma[0] == req.spec_ewma  # persisted mirror
    for _ in range(12):
        eng._update_spec_ewma(slot, proposed=4, accepted=4)
    assert eng._spec_k_for(slot) == 4          # recovered
    assert eng.spec_ewma_for(0) == pytest.approx(req.spec_ewma)


def test_adaptive_k_shrinks_on_low_acceptance_stream(tiny_model):
    """End-to-end: a random prompt (prompt-lookup drafts mostly reject)
    drags the request's EWMA below the optimistic default while it
    runs, and the mirror entry drops at finish."""
    rng = np.random.default_rng(11)
    p = rng.integers(1, V, size=(9,)).astype(np.int32)
    eng = _engine(tiny_model, 5, max_batch=1)
    rid = eng.add_request(p, max_new_tokens=24)
    ewmas = []
    while eng.has_unfinished():
        eng.step()
        ewmas.append(eng._spec_ewma.get(rid))
    seen = [e for e in ewmas if e is not None]
    assert seen and min(seen) < 1.0
    # terminal cleanup: the mirror entry drops at finish
    assert rid not in eng._spec_ewma


# ---------------------------------------------------------------------------
# chaos: crash mid-verify-window
# ---------------------------------------------------------------------------

def test_chaos_crash_mid_verify_window(tiny_model):
    """An injected crash lands between verify dispatches; supervised
    restart re-admits and the SAMPLED stream continues TOKEN-EXACTLY
    (the coupled rule has no acceptance randomness to replay; the
    greedy variant rides test_faults.py's chaos matrix via its
    fused_spec config). Pool invariants hold after recovery."""
    prompts = _prompts(17)
    eng = _engine(tiny_model, 4, "paged")

    def run(fi):
        server = AsyncLLMServer(
            eng, max_queue_size=8, fault_injector=fi,
            supervise=RestartPolicy(max_restarts=2, backoff_s=0.01))
        with server:
            hs = [server.submit(p, max_new_tokens=10, temperature=0.8,
                                top_p=0.9)
                  for p in prompts]
            return [h.result(timeout=240).token_ids for h in hs]

    want = run(FaultInjector())
    got = run(FaultInjector().crash_at_step(3))
    assert got == want
    eng._check_pool_invariants()
    assert len(eng._free_blocks) == eng.n_blocks


# ---------------------------------------------------------------------------
# telemetry / flight recorder / no-op contract
# ---------------------------------------------------------------------------

def test_draft_rejected_cause_classification():
    """A sync-dominated step whose verify windows mostly rolled back
    classifies as draft_rejected, not host_sync/batched_readout; the
    same step with healthy acceptance keeps the batched_readout
    verdict."""
    from paddle_tpu.profiler.flight_recorder import FlightRecorder

    def mk(accepted, rejected):
        rec = FlightRecorder()
        sid = rec.begin_step(
            scheduler="fused", kind="spec",
            grants=((0, 0, "verify", 4),), tokens_scheduled=4,
            token_budget=8, queue_depth=0, free_blocks=None,
            total_blocks=None, pipeline_inflight=1, preemptions=(),
            admit_s=0.0, schedule_s=0.0, dispatch_s=0.001,
            t_begin=0.0, readout_stride=4)
        rec.finish_step(sid, sync_s=1.0, emit_s=0.0,
                        spec_accepted=accepted, spec_rejected=rejected)
        step = rec.get_step(sid)
        step.t_finish = step.t_begin + 1.1  # sync-dominated wall
        return rec._classify(2.0, step)

    assert mk(accepted=0, rejected=3) == "draft_rejected"
    assert mk(accepted=3, rejected=1) == "batched_readout"


def test_spec_k1_is_plain_fused(tiny_model, greedy_ref):
    """speculative_k=1 keeps the exact pre-speculation fused engine: no
    device token history, no verify machinery, bit-identical streams."""
    eng = _engine(tiny_model, 1)
    assert eng._tokens is None
    prompts = _prompts(23)
    out = [o.token_ids for o in eng.generate(prompts, max_new_tokens=8)]
    assert out == greedy_ref(prompts, 8)
    assert eng.stats["spec_proposed_tokens"] == 0
