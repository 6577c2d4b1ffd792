"""Cross-replica KV shipping — disaggregated prefill/decode serving.

The acceptance bars from the ISSUE:

* a staged export → wire → import → stitched resume is TOKEN-EXACT vs
  the single-engine run, greedy AND sampled (``sampling_seed`` makes
  the per-(rid, position) fold_in keys replica-independent), on fp and
  on int8/int4 quantized pools (the (payload, scale) pairs ride the
  wire bit-exact);
* a migrated request pays ZERO re-prefill: the decode replica's
  restore covers the whole committed span and only the one-token
  stitch dispatches;
* shipping books on its OWN counters (``kv_ship_*``), never on the
  ``kv_swap_*`` deltas the preempt-vs-reprefill classifier owns, and
  the StepRecord split + explain_tail carry a ``kv_ship`` cause;
* failure is never correctness: a transport reject falls back to plain
  re-prefill resubmission (token-identical), a prefill replica lost
  mid-ship books ``kv_ship_abandoned`` and the request re-prefills on
  a survivor — pool invariants armed throughout (conftest);
* pull-on-miss: a pinned placement whose prefix probe misses fetches
  the covering blocks from the peer that has them, and the target's
  spill → promote path serves them instead of recomputing.

Engine-heavy cases ride the ``slow`` lane per the tier-1 wall-budget
policy (int4, the chaos kill, the TP-mesh export).
"""
import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (AsyncLLMServer, FaultInjector,
                                InProcessTransport, KVTransport,
                                ReplicaRouter, TransportError,
                                deserialize_entry, serialize_entry)

V = 96
CFG = LlamaConfig(vocab_size=V, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=128)
SEED = 11          # sampling_seed shared by every engine in this file


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompt():
    rng = np.random.default_rng(0)
    return rng.integers(1, V, size=(25,)).astype(np.int32)


def _kw(**over):
    kw = dict(max_batch=2, max_seq_len=64, chunk_size=16,
              cache_impl="paged", block_size=8, scheduler="fused",
              sampling_seed=SEED)
    kw.update(over)
    return kw


@pytest.fixture(scope="module")
def ref_engine(tiny_model):
    return LLMEngine(tiny_model, **_kw())


@pytest.fixture(scope="module")
def greedy_ref(ref_engine, prompt):
    """Uninterrupted greedy 10-token stream (rid-independent)."""
    return ref_engine.generate([prompt], max_new_tokens=10)[0].token_ids


@pytest.fixture(scope="module")
def sampled_ref(ref_engine, prompt):
    """Uninterrupted SAMPLED stream per rid: under ``sampling_seed``
    the per-(rid, position) fold_in keys make the stream a function of
    the rid, so cross-engine parity requires the same rid — which is
    exactly why the migration preserves it."""
    cache = {}

    def get(rid):
        if rid not in cache:
            ref_engine.add_request(prompt, max_new_tokens=10,
                                   request_id=rid, temperature=0.8,
                                   top_p=0.9)
            while ref_engine.has_unfinished():
                ref_engine.step()
            cache[rid] = ref_engine.finished_outputs.pop(rid).token_ids
        return cache[rid]

    return get


def _leg(eng, prompt, rid, **sampling):
    """Run the one-token prefill leg with export staging; returns the
    leg token and the materialized staged entry."""
    got = eng.add_request(prompt, max_new_tokens=1, request_id=rid,
                          export_kv=True, **sampling)
    assert got == rid
    while eng.has_unfinished():
        eng.step()
    tok = eng.finished_outputs.pop(rid).token_ids[0]
    entry = eng.export_kv(rid)
    assert entry is not None and entry["ready"]
    return tok, entry


def _treedefs(eng):
    return (jax.tree_util.tree_structure(eng._k),
            jax.tree_util.tree_structure(eng._v))


def _resume(eng, prompt, rid, tok, n=9, **sampling):
    eng.add_request(prompt, max_new_tokens=n, request_id=rid,
                    committed_tokens=[tok], **sampling)
    while eng.has_unfinished():
        eng.step()
    return eng.finished_outputs.pop(rid).token_ids


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

def _fake_entry(**over):
    rng = np.random.default_rng(4)
    k = [rng.standard_normal((3, 8, 4, 16)).astype(np.float32)
         for _ in range(2)]
    v = [rng.standard_normal((3, 8, 4, 16)).astype(np.float32)
         for _ in range(2)]
    e = {"rid": 7, "adapter_id": 0, "n_blocks": 3, "block_size": 8,
         "kv_quant": None, "tokens": np.arange(25, dtype=np.int32),
         "chain": [bytes([i] * 16) for i in range(3)],
         "k": k, "v": v, "ready": True,
         "nbytes": sum(a.nbytes for a in k + v)}
    e.update(over)
    return e


def test_wire_round_trip_bit_exact():
    """serialize → deserialize is byte-identical on every leaf —
    including a quantized-style (payload, scale) pair with mixed
    dtypes — and identity/chain fields survive the hex hop."""
    rng = np.random.default_rng(5)
    pair = [(rng.integers(-128, 128, (3, 8, 4, 16)).astype(np.int8),
             rng.standard_normal((3, 8, 4)).astype(np.float32))]
    e = _fake_entry(k=pair, v=pair,
                    nbytes=sum(a.nbytes for p in pair * 2 for a in p))
    back = deserialize_entry(serialize_entry(e))
    flat = jax.tree_util.tree_leaves(e["k"]) + \
        jax.tree_util.tree_leaves(e["v"])
    got = list(back["k"]) + list(back["v"])
    assert len(got) == len(flat)
    for a, b in zip(flat, got):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert back["chain"] == e["chain"]
    assert np.array_equal(back["tokens"], e["tokens"])
    assert back["rid"] == 7 and back["n_blocks"] == 3
    assert back["ready"] is True


def test_wire_rejects_corruption_and_mismatch():
    e = _fake_entry()
    wire = serialize_entry(e)
    with pytest.raises(TransportError, match="magic"):
        deserialize_entry(b"XXXX" + wire[4:])
    with pytest.raises(TransportError, match="trailing"):
        deserialize_entry(wire + b"\x00")
    # destination treedefs that don't match the header: the replicas
    # run different pool layouts — must refuse, not transpose
    bad = (jax.tree_util.tree_structure([0]),
           jax.tree_util.tree_structure([0]))
    with pytest.raises(TransportError, match="structure"):
        deserialize_entry(wire, bad)
    # an unmaterialized entry never reaches the wire
    with pytest.raises(TransportError, match="ready"):
        serialize_entry(_fake_entry(ready=False))


# ---------------------------------------------------------------------------
# staged export / import: the token-exact migration
# ---------------------------------------------------------------------------

def test_ship_token_exact_greedy_and_sampled(tiny_model, prompt,
                                             greedy_ref, sampled_ref):
    """THE migration acceptance: a 1-token prefill leg's export rides
    the real wire into a fresh engine, the stitched resume continues
    token-exactly (greedy AND sampled — same rid + sampling_seed), the
    decode side pays ZERO re-prefill, and the traffic books on
    kv_ship_* with the kv_swap_* classifier signal untouched."""
    src = LLMEngine(tiny_model, **_kw())
    dst = LLMEngine(tiny_model, **_kw())

    tok, entry = _leg(src, prompt, rid=100)
    assert [tok] == greedy_ref[:1]
    assert src.stats["kv_ship_out_blocks"] >= 1
    assert src.stats["kv_ship_out_bytes"] == entry["nbytes"]
    assert src.stats["kv_swap_out_bytes"] == 0
    wire = serialize_entry(entry)
    assert dst.import_kv(deserialize_entry(wire, _treedefs(dst)))
    assert _resume(dst, prompt, 100, tok) == greedy_ref
    # zero re-prefill: only the stitch position dispatched as prefill
    assert dst.stats["prefill_tokens"] == 1
    assert dst.stats["kv_swap_saved_tokens"] == len(prompt)
    assert dst.stats["kv_ship_in_blocks"] >= 1
    assert dst.stats["kv_ship_in_bytes"] == entry["nbytes"]
    assert dst.stats["kv_swap_in_bytes"] == 0     # classifier untouched

    tok_s, entry_s = _leg(src, prompt, rid=200, temperature=0.8,
                          top_p=0.9)
    assert [tok_s] == sampled_ref(200)[:1]
    assert dst.import_kv(deserialize_entry(serialize_entry(entry_s),
                                           _treedefs(dst)))
    assert _resume(dst, prompt, 200, tok_s, temperature=0.8,
                   top_p=0.9) == sampled_ref(200)
    assert not dst._swap_store                    # entries consumed
    src._check_pool_invariants()
    dst._check_pool_invariants()


# slow (tier-1 wall budget): the unquantized ship stays tier-1 in
# test_ship_token_exact_greedy_and_sampled, and the quantized
# (payload, scale) gather/scatter bit-exactness stays tier-1 in
# test_kv_tier's int8 swap cycle — the same tree_map-generic programs
@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["int8"])
def test_quantized_ship_bit_exact(tiny_model, prompt, dtype):
    """Quantized pools ship token-exactly: the (payload, scale) leaf
    pairs round-trip the wire bit-exact, so the imported blocks
    dequantize to what the uninterrupted quantized engine reads.
    (int4 twin below.)"""
    full = LLMEngine(tiny_model, **_kw(kv_cache_dtype=dtype))
    ref = full.generate([prompt], max_new_tokens=10)[0].token_ids
    src = LLMEngine(tiny_model, **_kw(kv_cache_dtype=dtype))
    dst = LLMEngine(tiny_model, **_kw(kv_cache_dtype=dtype))
    tok, entry = _leg(src, prompt, rid=300)
    assert dst.import_kv(deserialize_entry(serialize_entry(entry),
                                           _treedefs(dst)))
    assert _resume(dst, prompt, 300, tok) == ref
    assert dst.stats["kv_ship_in_blocks"] >= 1
    assert dst.stats["prefill_tokens"] == 1


@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["int4"])
def test_quantized_ship_bit_exact_slow(tiny_model, prompt, dtype):
    full = LLMEngine(tiny_model, **_kw(kv_cache_dtype=dtype))
    ref = full.generate([prompt], max_new_tokens=10)[0].token_ids
    src = LLMEngine(tiny_model, **_kw(kv_cache_dtype=dtype))
    dst = LLMEngine(tiny_model, **_kw(kv_cache_dtype=dtype))
    tok, entry = _leg(src, prompt, rid=300)
    assert dst.import_kv(deserialize_entry(serialize_entry(entry),
                                           _treedefs(dst)))
    assert _resume(dst, prompt, 300, tok) == ref
    assert dst.stats["kv_ship_in_blocks"] >= 1


def test_import_rejects_geometry_mismatch(tiny_model, prompt):
    """import_kv refuses entries the destination pool cannot hold —
    block size or quantization scheme mismatch — by returning False
    (the router's fallback trigger), never by raising or scattering."""
    src = LLMEngine(tiny_model, **_kw())
    _, entry = _leg(src, prompt, rid=400)
    assert LLMEngine(tiny_model,
                     **_kw(block_size=4)).import_kv(entry) is False
    assert LLMEngine(tiny_model, **_kw(kv_cache_dtype="int8")) \
        .import_kv(entry) is False
    unready = dict(entry, ready=False)
    assert LLMEngine(tiny_model, **_kw()).import_kv(unready) is False


# ---------------------------------------------------------------------------
# disaggregated router: roles, ship hook, observability
# ---------------------------------------------------------------------------

def test_disagg_router_end_to_end(tiny_model, prompt, greedy_ref,
                                  sampled_ref):
    """1 prefill + 1 decode replica: the prompt places on the prefill
    replica, the prefill-complete hook ships and resubmits on the
    decode replica, the caller's stream is token-exact with zero
    re-prefill on the decode side, and every observability surface
    carries the migration (router stats + snapshot, migration-latency
    histogram, transport counters, telemetry counter sync, the
    kv_host_spill_bytes gauge twin, StepRecord deltas, explain_tail's
    kv_ship cause)."""
    from paddle_tpu.profiler.flight_recorder import FlightRecorder
    srv0 = AsyncLLMServer(LLMEngine(tiny_model, **_kw()), replica=0)
    srv1 = AsyncLLMServer(LLMEngine(tiny_model, **_kw()), replica=1,
                          flight_recorder=FlightRecorder())
    router = ReplicaRouter([srv0, srv1],
                           roles={"prefill": [0], "decode": [1]})
    router.start()
    try:
        h = router.submit(prompt, max_new_tokens=10)
        res = h.result(timeout=300)
        assert res.token_ids == greedy_ref
        assert res.finish_reason == "length"
        # the iterator sees every token exactly once (leg tokens ride
        # the router-level carry, never re-emitted by the decode leg)
        assert list(h) == greedy_ref
        # second submit lands rid 1 on the prefill replica and the
        # migration carries that rid to the decode leg — sampled parity
        hs = router.submit(prompt, max_new_tokens=10, temperature=0.8,
                           top_p=0.9)
        assert hs.result(timeout=300).token_ids == sampled_ref(1)

        assert router.stats["kv_shipped"] >= 2
        assert router.stats["kv_ship_fallback"] == 0
        assert srv0.engine.stats["kv_ship_out_blocks"] >= 1
        assert srv1.engine.stats["kv_ship_in_blocks"] >= 1
        # zero re-prefill on the decode replica: stitches only
        assert srv1.engine.stats["prefill_tokens"] == 2
        snap = router.snapshot()
        assert snap["roles"] == {"prefill": [0], "decode": [1]}
        assert snap["migration_latency"]["count"] >= 2
        assert snap["transport"]["ship_count"] >= 2
        assert snap["transport"]["ship_bytes"] > 0
        assert snap["transport"]["fail_count"] == 0
        assert snap["replicas"][0]["kv_tier"]["ship_out_bytes"] > 0
        assert snap["replicas"][1]["kv_tier"]["ship_in_bytes"] > 0
        assert snap["replicas"][1]["kv_tier"]["spill_bytes"] == 0
        # telemetry: counter delta-sync + the spill-bytes gauge twin
        c = srv1.telemetry.counters
        assert c["kv_ship_in_blocks"] >= 1
        assert c["kv_ship_in_bytes"] > 0
        g = srv1.telemetry.get_gauges()
        assert g["kv_host_spill_bytes"] == 0
        text = srv1.telemetry.prometheus_text()
        assert "kv_ship_in_bytes" in text
        assert "kv_host_spill_bytes" in text
        # flight recorder: the restoring step carries the ship delta
        recs = srv1.flight_recorder.records()
        assert any((r.kv_ship_in_bytes or 0) > 0 for r in recs)
        d = recs[-1].to_dict()
        assert "kv_ship_in_bytes" in d and "kv_ship_out_bytes" in d
    finally:
        router.stop(timeout=120)
    srv0.engine._check_pool_invariants()
    srv1.engine._check_pool_invariants()


# slow (tier-1 wall budget): the StepRecord kv_ship byte-delta
# plumbing the classifier reads stays tier-1 in
# test_disagg_router_end_to_end; only the tail-cause classification
# itself rides the slow lane
@pytest.mark.slow
def test_explain_tail_names_kv_ship_cause(tiny_model, prompt,
                                          greedy_ref):
    """A resident decode stream's token on the stitch step joins to
    the ``kv_ship`` tail cause — checked before interfering_prefill,
    so the stitch grant doesn't file there. Engine-driven (no threads)
    so the import deterministically lands mid-decode."""
    from paddle_tpu.profiler.flight_recorder import FlightRecorder
    src = LLMEngine(tiny_model, **_kw())
    tok, entry = _leg(src, prompt, rid=600)
    eng = LLMEngine(tiny_model, **_kw())
    eng.flight_recorder = FlightRecorder()
    eng.add_request(np.arange(1, 10, dtype=np.int32), max_new_tokens=30)
    for _ in range(8):
        eng.step()
    assert eng.import_kv(deserialize_entry(serialize_entry(entry),
                                           _treedefs(eng)))
    eng.add_request(prompt, max_new_tokens=9, request_id=600,
                    committed_tokens=[tok])
    while eng.has_unfinished():
        eng.step()
    assert eng.finished_outputs.pop(600).token_ids == greedy_ref
    assert any((r.kv_ship_in_bytes or 0) > 0
               for r in eng.flight_recorder.records())
    tail = eng.flight_recorder.explain_tail(0.0)
    assert any(e["cause"] == "kv_ship" for e in tail)


class _BrokenTransport(KVTransport):
    """Every ship fails after the bytes were 'sent' — the RDMA-gone-bad
    shape the fallback rule exists for."""

    def __init__(self):
        self.attempts = 0

    def ship(self, entry, dst_engine):
        self.attempts += 1
        raise TransportError("wire down")

    def ship_prefix_blocks(self, entries, dst_engine):
        return 0, 0


def test_transport_failure_falls_back_to_reprefill(tiny_model, prompt,
                                                   greedy_ref):
    """Shipping is an optimization, never a correctness dependency: a
    dead transport books kv_ship_fallback, the decode replica
    re-prefills the full span, and the stream is token-identical."""
    t = _BrokenTransport()
    srv0 = AsyncLLMServer(LLMEngine(tiny_model, **_kw()), replica=0)
    srv1 = AsyncLLMServer(LLMEngine(tiny_model, **_kw()), replica=1)
    router = ReplicaRouter([srv0, srv1],
                           roles={"prefill": [0], "decode": [1]},
                           transport=t)
    router.start()
    try:
        res = router.submit(prompt, max_new_tokens=10).result(timeout=300)
        assert res.token_ids == greedy_ref
        assert t.attempts >= 1
        assert router.stats["kv_ship_fallback"] >= 1
        assert router.stats["kv_shipped"] == 0
        # the fallback re-prefilled prompt + leg token on the decode side
        assert srv1.engine.stats["prefill_tokens"] >= len(prompt)
        assert srv1.engine.stats["kv_ship_in_blocks"] == 0
    finally:
        router.stop(timeout=120)
    srv1.engine._check_pool_invariants()


# slow (tier-1 wall budget): the push-side ship path the pull reuses
# (export → wire → import) stays tier-1 in
# test_disagg_router_end_to_end, and the spill → promote machinery the
# pulled blocks land in stays tier-1 in test_kv_tier's promote tests
@pytest.mark.slow
def test_pull_on_miss_fetches_peer_prefix(tiny_model, prompt,
                                          greedy_ref):
    """A pinned placement whose prefix probe misses pulls the covering
    blocks from the peer that has them: the fetched span lands in the
    target's spill store (inbox drained ahead of admission) and the
    existing spill → promote path serves it instead of recomputing."""
    kw = _kw(kv_pool_blocks=8, enable_prefix_cache=True,
             kv_host_spill_bytes=4 << 20)
    srv0 = AsyncLLMServer(LLMEngine(tiny_model, **kw), replica=0)
    srv1 = AsyncLLMServer(LLMEngine(tiny_model, **kw), replica=1)
    # warm replica 0's content store with the prompt's blocks
    srv0.engine.generate([prompt], max_new_tokens=4)
    router = ReplicaRouter([srv0, srv1], pull_on_miss=True)
    router.start()
    try:
        res = router.submit(prompt, max_new_tokens=10,
                            replica=1).result(timeout=300)
        assert res.token_ids == greedy_ref
        assert router.stats["pull_on_miss_blocks"] >= 1
        assert srv1.engine.stats["kv_ship_in_blocks"] >= 1
        assert srv1.engine.stats["kv_promote_blocks"] >= 1
        assert srv1.engine.stats["prefix_hit_tokens"] >= \
            srv1.engine.block_size
        assert srv0.engine.stats["kv_ship_out_blocks"] >= 1
    finally:
        router.stop(timeout=120)
    srv0.engine._check_pool_invariants()
    srv1.engine._check_pool_invariants()


# ---------------------------------------------------------------------------
# chaos / TP (engine-heavy: slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_prefill_replica_killed_mid_ship(tiny_model, prompt,
                                         greedy_ref):
    """Kill the prefill replica during the prefill leg: the staged KV
    dies with it — kv_ship_abandoned books the lost transfer work —
    and the request re-prefills on the survivor token-exactly (which,
    as the only replica left, also absorbs the decode leg)."""
    fi0 = FaultInjector()
    srv0 = AsyncLLMServer(LLMEngine(tiny_model, **_kw()), replica=0,
                          fault_injector=fi0)
    srv1 = AsyncLLMServer(LLMEngine(tiny_model, **_kw()), replica=1)
    router = ReplicaRouter([srv0, srv1],
                           roles={"prefill": [0], "decode": [1]})
    router.start()
    try:
        fi0.crash_at_step(1)
        h = router.submit(prompt, max_new_tokens=10)
        res = h.result(timeout=300)
        assert res.token_ids == greedy_ref
        assert res.finish_reason == "length"
        assert router.stats["kv_ship_abandoned"] >= 1
        # the re-run leg on the survivor still split + shipped (to
        # itself — the only decode-capable replica left)
        assert router.stats["resubmitted"] >= 2
    finally:
        router.stop(timeout=120)
    srv1.engine._check_pool_invariants()


@pytest.mark.slow
def test_tp_mesh_export_import_and_spill(tiny_model, prompt, tp_mesh):
    """Disagg x TP: a tensor-parallel engine's export gathers the
    sharded pools into one staged entry a single-chip engine imports
    token-exactly, and its spill → promote path keeps working with the
    export machinery armed."""
    from paddle_tpu.serving.cluster import tp_engine
    ref = LLMEngine(tiny_model, **_kw()).generate(
        [prompt], max_new_tokens=10)[0].token_ids
    paddle.seed(7)
    m2 = LlamaForCausalLM(CFG)
    m2.set_state_dict(tiny_model.state_dict())
    m2.eval()
    tpe = tp_engine(m2, mesh=tp_mesh,
                    **_kw(kv_pool_blocks=8, enable_prefix_cache=True,
                          kv_host_spill_bytes=4 << 20))
    tok, entry = _leg(tpe, prompt, rid=500)
    assert [tok] == ref[:1]
    dst = LLMEngine(tiny_model, **_kw())
    assert dst.import_kv(deserialize_entry(serialize_entry(entry),
                                           _treedefs(dst)))
    assert _resume(dst, prompt, 500, tok) == ref
    assert dst.stats["prefill_tokens"] == 1
    # spill-promote still works on the TP engine under export staging
    rng = np.random.default_rng(5)
    churn = [rng.integers(1, V, size=(27,)).astype(np.int32)
             for _ in range(2)]
    tpe.generate(churn, max_new_tokens=8)
    assert tpe.stats["kv_spill_blocks"] >= 1
    tpe.generate([prompt], max_new_tokens=4)
    assert tpe.stats["kv_promote_blocks"] >= 1
    tpe._check_pool_invariants()
    dst._check_pool_invariants()
