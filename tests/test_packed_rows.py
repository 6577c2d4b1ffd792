"""The mixed step's packed row axis (``models/cache_layout.py``: ``RowMap``,
``packed_rows``; ``LLMEngine.fused_step``).

A mixed step hands the decoder ``ids[1, T]``, the step's granted rows
packed slot-major, and not ``ids[max_batch, chunk]``. Held here: the map
and its two gathers over random grants, the static height against the
scheduler's own grants, greedy streams token-exact against the legacy
admit-then-decode engine over the grant mixes that bend the map (one slot
ramping beside decodes, two ramping in one step, a budget below the live
decodes, a grant the pool shrank, a slot the in-graph capacity guard took
out), over the cache backends and the grant kinds, and the counter that
says the packing engaged."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.cache_layout import ROW_TILE, RowMap, packed_rows
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

CFG = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 96, size=(n,)).astype(np.int32) for n in sizes]


# ---------------------------------------------------------------------------
# the map and its two gathers
# ---------------------------------------------------------------------------

def _grants(rng):
    """Random (B, S, T, q_lens, seq_lens): zeros among the grants, their
    sum within T, sometimes exactly T, sometimes nothing at all."""
    B, S = int(rng.integers(1, 9)), int(rng.integers(1, 33))
    T = int(rng.integers(1, B * S + 1))
    q = rng.integers(0, S + 1, size=B)
    q[rng.random(B) < 0.3] = 0
    while q.sum() > T:
        q[int(np.argmax(q))] -= 1
    if rng.random() < 0.2 and T <= B * S:        # fill it to the brim
        for b in rng.permutation(B):
            q[b] += min(S - q[b], T - q.sum())
    return B, S, T, q.astype(np.int32), \
        rng.integers(0, 50, size=B).astype(np.int32)


@pytest.mark.parametrize("seed", range(16))
def test_row_map_and_gathers_over_random_grants(seed):
    rng = np.random.default_rng(seed)
    for _ in range(8):
        B, S, T, q, lens = _grants(rng)
        rows = RowMap(jnp.asarray(q), jnp.asarray(lens), T, S)
        n = int(q.sum())
        # slot-major, a slot's rows adjacent and in position order
        want = [(b, i) for b in range(B) for i in range(q[b])]
        slot, col = np.asarray(rows.slot), np.asarray(rows.col)
        assert [(int(s), int(c)) for s, c in zip(slot[:n], col[:n])] == want
        assert np.asarray(rows.live).tolist() == [t < n for t in range(T)]
        assert np.asarray(rows.pos)[:n].tolist() == \
            [int(lens[b]) + i for b, i in want]
        # padding rows index nothing out of range
        assert (slot >= 0).all() and (slot < B).all()
        assert (col[n:] == 0).all() and (np.asarray(rows.pos)[n:] == 0).all()
        for b in range(B):
            if q[b]:
                assert int(rows.last()[b]) == want.index((b, q[b] - 1))
        x = rng.standard_normal((T, 3)).astype(np.float32)
        view = np.asarray(rows.to_slots(jnp.asarray(x)))
        assert view.shape == (B, S, 3)
        for t, (b, i) in enumerate(want):        # row i of slot b at [b, i]
            np.testing.assert_array_equal(view[b, i], x[t])
        back = np.asarray(rows.from_slots(jnp.asarray(view)))
        np.testing.assert_array_equal(back[:n], x[:n])      # round trip
        # a narrower view (a verify window's rows)
        w = int(rng.integers(1, S + 1))
        narrow = np.asarray(rows.to_slots(jnp.asarray(x), w))
        np.testing.assert_array_equal(narrow, view[:, :w])


@pytest.mark.parametrize("budget,batch,chunk,window,want", [
    (263, 8, 256, 1, 272),        # doc_batch: chunk + max_batch - 1
    (256, 8, 256, 1, 256),
    (5, 8, 16, 1, 16),            # a budget below the slots: max_batch
    (2048, 8, 256, 1, 2048),      # the budget grants every row: padded
    (9999, 4, 16, 1, 64),
    (19, 4, 16, 4, 32),           # verify windows: budget + batch - 1
    (3, 2, 16, 1, 16),
])
def test_packed_height_is_derived(budget, batch, chunk, window, want):
    assert packed_rows(budget, batch, chunk, window) == want
    assert want % ROW_TILE == 0 or want == batch * chunk


# ---------------------------------------------------------------------------
# the packed mixed step against the legacy scheduler
# ---------------------------------------------------------------------------

def _watch(eng):
    """Records every mixed grant's (q_lens, how many slots ramp) and holds
    each to the packed height."""
    seen = []
    inner = eng._schedule_mixed

    def schedule(pool_done):
        out = inner(pool_done)
        ids, q_lens, is_dec, active = out[:4]
        assert int(q_lens.sum()) <= eng.mixed_rows
        seen.append((q_lens.copy(), int((active & ~is_dec).sum()),
                     int(is_dec.sum())))
        return out
    eng._schedule_mixed = schedule
    return seen


def _serve(eng, prompts, new, stagger=0):
    """Add the prompts (``stagger`` steps apart) and drain."""
    rids = []
    for p in prompts:
        rids.append(eng.add_request(p, max_new_tokens=new))
        for _ in range(stagger):
            eng.step()
    while eng.has_unfinished():
        eng.step()
    return [eng.finished_outputs.pop(r).token_ids for r in rids]


BACKENDS = {
    "dense": dict(cache_impl="dense"),
    "paged": dict(cache_impl="paged", block_size=8),
    "paged_int8": dict(cache_impl="paged", block_size=8,
                       kv_cache_dtype="int8"),
}


def _reference(model, backend, prompts, new):
    """The same backend under the legacy scheduler. An int8 pool rounds
    what it stores by the order it was written in, and the legacy
    prefill's last window slides back over written positions, so there
    the reference is each prompt alone in a one-slot fused engine, whose
    map is the identity."""
    if backend == "paged_int8":
        return [LLMEngine(model, max_batch=1, max_seq_len=96, chunk_size=16,
                          scheduler="fused", **BACKENDS[backend])
                .generate([p], max_new_tokens=new)[0].token_ids
                for p in prompts]
    eng = LLMEngine(model, max_batch=len(prompts), max_seq_len=96,
                    chunk_size=16, **BACKENDS[backend])
    return [o.token_ids for o in eng.generate(prompts, max_new_tokens=new)]


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_one_slot_ramps_beside_decodes(model, backend):
    prompts = _prompts(1, (9, 5, 40, 33))
    ref = _reference(model, backend, prompts, 12)
    eng = LLMEngine(model, max_batch=4, max_seq_len=96, chunk_size=16,
                    scheduler="fused", **BACKENDS[backend])
    seen = _watch(eng)
    assert _serve(eng, prompts, 12, stagger=2) == ref
    # a step with one slot ramping and at least two decoding went through
    assert any(ramps == 1 and decs >= 2 for _, ramps, decs in seen)
    assert eng.mixed_rows == packed_rows(16 + 3, 4, 16) == 32


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_two_slots_ramp_in_one_step(model, backend):
    prompts = _prompts(2, (30, 27, 7))
    ref = _reference(model, backend, prompts, 8)
    eng = LLMEngine(model, max_batch=3, max_seq_len=96, chunk_size=16,
                    scheduler="fused", max_step_tokens=32,
                    **BACKENDS[backend])
    seen = _watch(eng)
    assert _serve(eng, prompts, 8) == ref
    assert any(ramps >= 2 for _, ramps, _ in seen)
    assert any(int(q.sum()) == 32 for q, _, _ in seen)    # filled to T


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_budget_below_the_live_decodes(model, backend):
    """``max_step_tokens`` under the decoding slots: decode tokens land
    anyway and the oldest ramping slot gets its guaranteed token, so the
    packed height is ``max_batch`` and not the budget."""
    prompts = _prompts(3, (6, 4, 5, 21))
    ref = _reference(model, backend, prompts, 30)
    eng = LLMEngine(model, max_batch=4, max_seq_len=96, chunk_size=16,
                    scheduler="fused", max_step_tokens=2,
                    **BACKENDS[backend])
    seen = _watch(eng)
    assert eng.mixed_rows == 16
    assert _serve(eng, prompts, 30, stagger=6) == ref
    # three decode tokens and the guaranteed one: four rows on a budget
    # of two
    assert any(int(q.sum()) == 4 and decs == 3 for q, _, decs in seen)


def test_a_grant_the_pool_shrank(model):
    prompts = _prompts(4, (25, 27))
    ref = _reference(model, "paged", prompts, 10)
    eng = LLMEngine(model, max_batch=2, max_seq_len=96, chunk_size=16,
                    cache_impl="paged", block_size=8, scheduler="fused",
                    kv_pool_blocks=8)
    seen = _watch(eng)
    assert _serve(eng, prompts, 10) == ref
    # some prefill grant was cut below both the chunk and what was left
    # of the prompt: the pool's doing
    assert any(0 < int(q[b]) < 9 for q, ramps, _ in seen if ramps
               for b in range(2)) or eng.stats["preemptions"] >= 1


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_capacity_guard_takes_a_slot_out_in_the_graph(model, backend):
    """The guard ``lens + q_lens <= capacity`` runs in the graph, after the
    host has laid out ``ids`` and ``q_lens``: the map has to be made from
    what the guard leaves. A dispatch whose slot 0 asks for a window past
    the buffer's end must come out exactly as the same dispatch with
    slot 0 idle: slot 1's rows and slot 2's decode token keep their
    places."""
    eng = LLMEngine(model, max_batch=3, max_seq_len=32, chunk_size=16,
                    scheduler="fused", **BACKENDS[backend])
    p0, p1, p2 = _prompts(5, (20, 11, 6))
    for p in (p0, p2):
        eng.add_request(p, max_new_tokens=4)
    for _ in range(3):
        eng.step()                   # slots 0 and 1 hold 20+ and 6+ tokens
    lens = np.asarray(eng._lens)
    assert lens[0] >= 20 and lens[1] >= 6 and lens[2] == 0
    cap = eng.capacity

    def dispatch(slot0_asks):
        ids = np.zeros((3, 16), np.int32)
        q = np.zeros((3,), np.int32)
        if slot0_asks:
            q[0] = 16                            # lens + 16 > capacity
            ids[0] = _prompts(6, (16,))[0]
        q[1], q[2] = 1, len(p1)                  # a decode and a chunk
        ids[2, :len(p1)] = p1
        is_dec = np.array([False, True, False])
        active = q > 0
        args = [eng._state_vals, eng._k, eng._v, eng._logits, eng._lens,
                jax.random.key(0), ids, q, is_dec, active,
                np.zeros((3,), np.float32), np.ones((3,), np.float32),
                np.arange(3, dtype=np.int32)]
        # the program donates the pools and the logits: hand it copies
        args[1:4] = jax.tree_util.tree_map(jnp.copy, args[1:4])
        if backend == "paged":
            for b, n in ((0, cap), (1, cap), (2, len(p1))):
                eng._ensure_blocks(b, n - 1)
            args.append(eng._tables.copy())
        toks, was_dec, logits, _, _, new_lens = eng._fused_fn(*args)[:6]
        return (np.asarray(toks), np.asarray(was_dec), np.asarray(logits),
                np.asarray(new_lens))

    assert lens[0] + 16 > cap
    with_guard, idle = dispatch(True), dispatch(False)
    for a, b in zip(with_guard, idle):
        np.testing.assert_array_equal(a, b)
    # the guarded slot stood still, the others moved
    assert with_guard[3].tolist() == [lens[0], lens[1] + 1, len(p1)]


def test_verify_windows_ride_the_packed_axis(model):
    """A speculative engine's mixed step: verify windows of several rows
    a decode slot beside a ramping prompt, through the same map (the
    window's per-row logits are gathered from it)."""
    rng = np.random.default_rng(8)
    # repetitive prompts, so prompt-lookup drafts exist and get accepted
    prompts = [np.tile(rng.integers(1, 96, size=(5,)), 5)[:n]
               .astype(np.int32) for n in (21, 24, 19)]
    legacy = LLMEngine(model, max_batch=3, max_seq_len=96, chunk_size=16)
    ref = [o.token_ids for o in legacy.generate(prompts, max_new_tokens=14)]
    eng = LLMEngine(model, max_batch=3, max_seq_len=96, chunk_size=16,
                    scheduler="fused", speculative_k=4, max_step_tokens=9)
    seen = _watch(eng)
    assert eng.mixed_rows == packed_rows(9, 3, 16, 4) == 16
    assert _serve(eng, prompts, 14, stagger=3) == ref
    # a step with a window of several rows beside a prefill grant
    assert any(ramps and decs and int(q.sum()) > ramps_rows + decs
               for q, ramps, decs, ramps_rows in
               ((q, r, d, int(q.max())) for q, r, d in seen))
    assert eng.stats["spec_accepted_tokens"] > 0


def test_an_armed_adapter_rides_the_packed_axis():
    """A tenant's LoRA delta beside a base tenant, one ramping while the
    other decodes: the per-slot adapter gather takes the per-slot view of
    the packed rows and gives the delta back packed."""
    from paddle_tpu.serving import AdapterStore
    from paddle_tpu.serving.adapters import apply_merged, \
        random_lora_weights

    def fresh():
        paddle.seed(7)
        m = LlamaForCausalLM(CFG)
        m.eval()
        return m
    store = AdapterStore(CFG, rank=4)
    store.register(random_lora_weights(CFG, rank=4, seed=3, scale=0.05),
                   alpha=2.0)
    prompts = _prompts(9, (23, 18))
    refs = []
    for aid, p in zip((1, 0), prompts):
        m = fresh()
        if aid:
            apply_merged(m, store, aid)
        eng = LLMEngine(m, max_batch=1, max_seq_len=96, chunk_size=16)
        refs.append(eng.generate([p], max_new_tokens=8)[0].token_ids)
    eng = LLMEngine(fresh(), max_batch=2, max_seq_len=96, chunk_size=16,
                    scheduler="fused", adapter_store=store)
    seen = _watch(eng)
    r0 = eng.add_request(prompts[0], max_new_tokens=8, adapter_id=1)
    for _ in range(3):
        eng.step()
    r1 = eng.add_request(prompts[1], max_new_tokens=8)
    while eng.has_unfinished():
        eng.step()
    assert eng.finished_outputs.pop(r0).token_ids == refs[0]
    assert eng.finished_outputs.pop(r1).token_ids == refs[1]
    assert any(ramps == 1 and decs == 1 for _, ramps, decs in seen)


def test_an_embed_request_pools_its_packed_rows(model):
    """An embed request ramping beside a decoding slot: its mean-pooled
    hidden state is summed off the packed rows it owns and no other."""
    p_gen, p_emb = _prompts(10, (7, 37))
    alone = LLMEngine(model, max_batch=1, max_seq_len=96, chunk_size=16,
                      scheduler="fused")
    r = alone.add_request(p_emb, kind="embed")
    while alone.has_unfinished():
        alone.step()
    want = alone.finished_outputs.pop(r).embedding
    eng = LLMEngine(model, max_batch=2, max_seq_len=96, chunk_size=16,
                    scheduler="fused")
    seen = _watch(eng)
    g = eng.add_request(p_gen, max_new_tokens=12)
    for _ in range(2):
        eng.step()
    e = eng.add_request(p_emb, kind="embed")
    while eng.has_unfinished():
        eng.step()
    got = eng.finished_outputs.pop(e).embedding
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert any(ramps == 1 and decs == 1 for _, ramps, decs in seen)
    (ref,) = LLMEngine(model, max_batch=1, max_seq_len=96, chunk_size=16) \
        .generate([p_gen], max_new_tokens=12)
    assert eng.finished_outputs.pop(g).token_ids == ref.token_ids


# ---------------------------------------------------------------------------
# the counter that says it engaged
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_a_mixed_dispatch_books_the_packed_height(model, backend):
    eng = LLMEngine(model, max_batch=4, max_seq_len=96, chunk_size=16,
                    scheduler="fused", **BACKENDS[backend])
    assert eng.mixed_rows == 32 < 4 * 16
    eng.add_request(_prompts(11, (40,))[0], max_new_tokens=2)
    before = dict(eng.stats)
    eng.step()
    assert eng.stats["fused_steps"] - before["fused_steps"] == 1
    assert eng.stats["rows_computed"] - before["rows_computed"] == 32
    # the decoder was traced on [1, T] ids: no [max_batch, chunk] operand
    # is left in the mixed step's program
    assert eng.stats["prefill_tokens"] == 16


def test_the_padded_step_is_the_packed_step_when_the_budget_grants_it(
        model):
    """``max_step_tokens >= max_batch x chunk``: T is the padded height
    and the same streams come out."""
    prompts = _prompts(12, (30, 27, 7))
    ref = _reference(model, "paged", prompts, 8)
    eng = LLMEngine(model, max_batch=3, max_seq_len=96, chunk_size=16,
                    cache_impl="paged", block_size=8, scheduler="fused",
                    max_step_tokens=1000)
    assert eng.mixed_rows == 3 * 16
    assert _serve(eng, prompts, 8) == ref
