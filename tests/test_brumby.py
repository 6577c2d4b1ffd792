"""Brumby (``paddle_tpu/models/brumby.py``, power retention in every layer)
against its plain float32 reference (``benchmark/reference/brumby_plain.py``:
the ATTENTION form, the one file of the benchmark these tests import, so
that the tests' reference and the cell's cannot drift apart), at toy widths
on the CPU: the feature map, the three forms of the layer, five query heads
on one state, the model's forward, the engine (chunked prefill, mixed steps
with slots of mixed lengths, ``multi_step`` decode, slot reuse, a preemption
and its replay from zeroed state) held to the reference's logits at a
tolerance that a bfloat16 state and the int8 control both fail, the
counters, and the first layout without a paged layer: what the engine builds
for it, how it admits, and every option it refuses, by name."""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.harness import weights as W
from benchmark.reference import brumby_plain as R
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import brumby as M
from paddle_tpu.ops.kernels import power_retention as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "brumby-14b-base-d8.json")) as _f:
    SHIPPED = json.load(_f)
#: the toy cut of the shipped configuration's keys: hidden 64, 4 query heads
#: on 2 key/value heads of 16 (D = 136), three layers
TOY = dict(SHIPPED, hidden_size=64, intermediate_size=160,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           num_hidden_layers=3, vocab_size=256, max_position_embeddings=512)
#: max |carried logits - the reference's| that the float32 served path
#: stays under (it reads 2e-7 to 1e-6 here: the forms differ, rounding
#: only), and that a bfloat16 STATE (2.7e-3 to 1.3e-2) and the int8 control
#: (1.3e-2 to 4.1e-2) both pass by a factor of five and more; the toy
#: model's logits have a standard deviation of 0.155
LOGIT_TOL = 5e-4


def program():
    from benchmark.harness import loader
    return loader.module("programs", "brumby")


def build(cfg, seed):
    """The program's model with the reference's float32 seeded leaves;
    returns (model, {name: float32 array})."""
    model = program().build(cfg)
    model.eval()
    named = list(model.named_parameters())
    mine = {n: tuple(p._value.shape) for n, p in named}
    assert mine == {n: tuple(s) for n, s in R.specs(cfg)}
    vals = W.make(seed, [(n, mine[n]) for n, _ in named], jnp.bfloat16,
                  None, R.is_scale)
    params = {}
    for (n, p), v in zip(named, vals):
        p._value = params[n] = v.astype(jnp.float32)
    return model, params


# ---- the shipped configuration ---------------------------------------------

def test_specs_size_and_state_of_the_shipped_configuration():
    assert R.n_params(SHIPPED) == 4_198_652_928
    with paddle.LazyGuard():
        model = program().build(SHIPPED)
    assert {n: tuple(p._value.shape) for n, p in model.named_parameters()} \
        == {n: tuple(s) for n, s in R.specs(SHIPPED)}
    layout = model.cache_layout()
    assert [k.kind for k in layout] == ["recurrent"] * 8
    assert not any(k.paged for k in layout)
    assert layout[0].shapes == {"S": ((8, 8256, 128), np.dtype("float32")),
                                "z": ((8, 8256), np.dtype("float32"))}
    assert layout[0].bytes_per_slot() == 34_080_768
    assert model.step_counter_names == P.COUNTERS
    # every assumed item of the issue is an entry of the configuration
    assert {"retention_degree", "gate", "normaliser", "q_norm_k_norm",
            "rotation_pairs", "phi", "state_dtype", "torch_dtype",
            "sampling"} <= set(SHIPPED["assumed"])
    # and each is a commented line of the reference
    with open(R.__file__) as f:
        assert f.read().count("# assumed:") >= 6


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("rope_scaling", {"type": "yarn"}),
    ("attention_bias", True), ("use_sliding_window", True),
    ("hidden_act", "gelu")])
def test_a_value_the_program_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"brumby: {key}="):
        program().build(dict(TOY, **{key: value}))


def test_another_degree_training_and_a_mesh_are_refused_by_name():
    with pytest.raises(ValueError, match="retention_degree=3"):
        program().build(dict(TOY, assumed=dict(retention_degree=3)))
    with pytest.raises(NotImplementedError, match="one chip"):
        program().partition("lm_head.weight", "tp")
    model, _ = build(TOY, 1)
    ids = paddle.to_tensor(np.ones((1, 4), np.int32))
    with pytest.raises(NotImplementedError, match="backward"):
        model(ids, labels=ids)


# ---- the feature map -------------------------------------------------------

@pytest.mark.parametrize("d", [2, 16, 128])
def test_phi_of_a_dot_phi_of_b_is_the_square_of_a_dot_b(d):
    rng = np.random.default_rng(d)
    a, b = rng.normal(size=(2, 7, d))
    pa, pb = P.phi(jnp.asarray(a)), P.phi(jnp.asarray(b))
    assert pa.shape == (7, P.feature_dim(d)) == (7, d * (d + 1) // 2)
    want = np.sum(a * b, -1) ** 2
    np.testing.assert_allclose(np.sum(pa * pb, -1), want,
                               rtol=2e-5, atol=1e-5 * np.abs(want).max())


def test_phi_holds_every_symmetric_monomial_once():
    d = 8
    basis = np.eye(d)
    seen = {}
    for i in range(d):
        for j in range(i, d):
            v = np.asarray(P.phi(jnp.asarray(basis[i] + basis[j])))
            u = np.asarray(P.phi(jnp.asarray(basis[i] - basis[j])))
            # a_i a_j is the one monomial whose sign the flip changes
            at = np.nonzero(np.abs(v - u) > 1e-6)[0] if i != j \
                else np.nonzero(v)[0]
            assert len(at) == 1
            seen[(i, j)] = int(at[0])
    assert sorted(seen.values()) == list(range(P.feature_dim(d)))


# ---- the three forms -------------------------------------------------------

def _inputs(rng, b, t, hq, hk, d, decay):
    q = jnp.asarray(rng.normal(size=(b, t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, hk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, hk, d)), jnp.float32)
    if decay == "slow":         # log g near 0: nothing is forgotten soon
        lg = -rng.random(size=(b, t, hk)) / 64
    else:                       # what a seeded gate gives: g about 1/2
        lg = np.asarray(jax.nn.log_sigmoid(
            jnp.asarray(rng.normal(size=(b, t, hk)) * 1.5)))
    return q, k, v, jnp.asarray(lg, jnp.float32)


def _by_steps(q, k, v, lg):
    b, t, hq, d = q.shape
    hk = k.shape[2]
    S = jnp.zeros((b, hk, P.feature_dim(d), d))
    z = jnp.zeros((b, hk, P.feature_dim(d)))
    step = jax.jit(P.retention_step)
    outs = []
    for i in range(t):
        o, S, z = step(q[:, i], k[:, i], v[:, i], lg[:, i], S, z,
                       jnp.ones((b,), bool), jnp.full((b,), i == 0))
        outs.append(o)
    return jnp.stack(outs, 1), S, z


def _by_walk(q, k, v, lg, q_lens, sub, calls=1):
    """``calls`` walks over equal shares of the rows (a prompt's chunks),
    the state carried from one to the next."""
    b, t, hq, d = q.shape
    hk = k.shape[2]
    S = jnp.zeros((b, hk, P.feature_dim(d), d))
    z = jnp.zeros((b, hk, P.feature_dim(d)))
    share = t // calls
    walk = jax.jit(lambda *a: P.retention_walk(*a, sub=sub))
    outs = []
    for c in range(calls):
        sl = slice(c * share, (c + 1) * share)
        flat = [a[:, sl].reshape((b * share,) + a.shape[2:])
                for a in (q, k, v, lg)]
        lens = jnp.full((b,), c * share, jnp.int32)
        n = jnp.clip(jnp.asarray(q_lens) - c * share, 0, share)
        o, S, z = walk(*flat, S, z, jnp.arange(b, dtype=jnp.int32) * share,
                       n.astype(jnp.int32), lens)
        outs.append(o.reshape(b, share, hq, d))
    return jnp.concatenate(outs, 1), S, z


@pytest.mark.parametrize("decay,t,sub,calls,q_lens", [
    ("slow", 2048, 64, 4, (2048,)),        # 2,048 positions, 4 chunks of 512
    ("slow", 2048, 16, 1, (2048,)),
    ("slow", 150, 64, 1, (150, 1, 97)),    # a ragged last chunk, one row
    ("slow", 150, 16, 1, (150, 1, 97)),
    ("logsigmoid", 150, 64, 1, (150, 0, 97)),
    ("logsigmoid", 160, 16, 2, (160, 81, 97))])
def test_the_chunk_form_is_the_attention_form(decay, t, sub, calls, q_lens):
    rng = np.random.default_rng(t + sub)
    b = len(q_lens)
    q, k, v, lg = _inputs(rng, b, t, 4, 2, 16, decay)
    ref = P.retention_attention(q, k, v, lg)
    got, S, z = _by_walk(q, k, v, lg, q_lens, sub, calls)
    scale = float(jnp.abs(ref).max())
    for i, n in enumerate(q_lens):
        np.testing.assert_allclose(got[i, :n], ref[i, :n],
                                   atol=2e-5 * scale, rtol=2e-4)
        if n == 0:      # a slot without a live row: untouched, unread
            assert not np.asarray(S[i]).any() and not np.asarray(z[i]).any()
        if calls == 1:  # rows no slot owns read 0
            assert not np.asarray(got[i, n:]).any()


@pytest.mark.parametrize("decay", ["slow", "logsigmoid"])
def test_the_one_token_form_is_the_attention_form_and_the_chunk_forms_state(
        decay):
    rng = np.random.default_rng(11)
    q, k, v, lg = _inputs(rng, 2, 300, 4, 2, 16, decay)
    ref = P.retention_attention(q, k, v, lg)
    rec, S1, z1 = _by_steps(q, k, v, lg)
    scale = float(jnp.abs(ref).max())
    # (a row whose weights nearly cancel divides two small sums: the
    # forms' different roundings show most there)
    np.testing.assert_allclose(rec, ref, atol=5e-5 * scale, rtol=5e-4)
    _, S2, z2 = _by_walk(q, k, v, lg, (300, 300), 64)
    np.testing.assert_allclose(S2, S1, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(S1).max()))
    np.testing.assert_allclose(z2, z1, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(z1).max()))


def test_the_plain_reference_is_the_same_attention_form():
    """``brumby_plain.retention`` (blocks of queries, exponents summed
    backwards from the block) against the whole-matrix form here."""
    rng = np.random.default_rng(5)
    q, k, v, lg = _inputs(rng, 1, 300, 4, 2, 16, "logsigmoid")
    want = P.retention_attention(q, k, v, lg)[0]
    with jax.default_matmul_precision("highest"):
        got = R.retention(q[0], k[0], v[0], lg[0], q_block=128)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_five_query_heads_read_one_state():
    """Hq = 5 on Hk = 1: each query head alone against the SAME keys,
    values and gate gives the same state and its own head's output."""
    rng = np.random.default_rng(3)
    q, k, v, lg = _inputs(rng, 1, 40, 5, 1, 16, "slow")
    o, S, z = _by_walk(q, k, v, lg, (40,), 16)
    assert S.shape == (1, 1, 136, 16)
    for a in range(5):
        oa, Sa, za = _by_walk(q[:, :, a:a + 1], k, v, lg, (40,), 16)
        np.testing.assert_allclose(oa[:, :, 0], o[:, :, a], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(Sa, S, rtol=1e-6)
        np.testing.assert_allclose(za, z, rtol=1e-6)
    rec, _, _ = _by_steps(q, k, v, lg)
    np.testing.assert_allclose(rec, o, rtol=2e-4, atol=2e-5)


# ---- the model's forward ---------------------------------------------------

@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_forward_matches_the_reference(seed):
    model, params = build(TOY, seed)
    ids = np.random.default_rng(seed).integers(1, 256, size=(2, 150))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
    for b in range(2):
        want = np.asarray(R.forward(params, jnp.asarray(ids[b]), TOY))
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=2e-4)
    # a norm left out is seen: scales are 1 + N(0, 0.1^2)
    plain = dict(params)
    plain["model.layers.1.self_attn.k_norm.weight"] = jnp.ones((16,))
    off = R.forward(plain, jnp.asarray(ids[0]), TOY)
    assert np.abs(np.asarray(off) - got[0]).max() > 1e-3


# ---- the engine against the reference --------------------------------------

ENGINE = dict(scheduler="fused", cache_impl="paged", block_size=16,
              chunk_size=32, readout_stride=4, max_batch=3, max_seq_len=200)


def _serve(model, arrivals, preempt_at=None, **over):
    """Drive the engine a step at a time; ``arrivals``: {step: [(prompt,
    max_new)]}; ``preempt_at``: the step before which the newest resident
    request is preempted by hand (nothing else preempts where there is no
    pool). Returns ({rid: (prompt, tokens)}, engine)."""
    eng = LLMEngine(model, **dict(ENGINE, **over))
    eng.emitted, to = [], eng._to        # what rides on pt:engine.emit

    def recording(phase, **ids):
        if phase == "emit":
            eng.emitted.append(ids)
        return to(phase, **ids)
    eng._to = recording
    prompts, done, step = {}, {}, 0
    while step < 400:
        for prompt, n in arrivals.get(step, ()):
            rid = eng.add_request(list(prompt), max_new_tokens=n)
            prompts[rid] = prompt
        if step == preempt_at:
            eng._preempt_slot(max(
                (b for b, s in enumerate(eng.slots) if s is not None),
                key=lambda b: eng._admit_order[b]))
        for out in eng.step():
            done[out.request_id] = (prompts[out.request_id],
                                    np.asarray(out.token_ids, np.int32))
        step += 1
        if step > max(arrivals) and not eng.has_unfinished():
            break
    assert len(done) == len(prompts)
    assert eng._write_fence == {} and eng._quarantine == set()
    return done, eng


def _carried_errors(eng, done, params, precision="f32"):
    """A retired slot keeps the logits it carried until it is reused: the
    distribution after its last served token. For each finished request,
    the least max |carried - reference| over the slots (a request whose
    slot was reused since finds no slot near it)."""
    carried = np.asarray(eng._logits)
    out = []
    for prompt, toks in done.values():
        seq = jnp.asarray(np.concatenate([prompt, toks]))
        want = np.asarray(R.forward(params, seq, TOY, precision)[-1])
        out.append(float(np.abs(carried - want[None]).max(axis=1).min()))
    return sorted(out)


def _arrivals(case, rng):
    def doc(n):
        return rng.integers(1, 256, size=n).astype(np.int32)
    if case == "two_ramping":
        # a budget of two chunks: two documents prefill in ONE mixed step
        # beside a third's decode token
        return {0: [(doc(21), 20)], 2: [(doc(70), 9), (doc(61), 8)]}
    # arrivals spread over steps, a slot that idles while others decode,
    # a slot reused by later requests
    return {0: [(doc(70), 9)], 2: [(doc(45), 12)],
            9: [(doc(100), 6), (doc(33), 10)], 14: [(doc(5), 7)]}


@pytest.mark.parametrize("case", ["staggered", "preempted", "two_ramping",
                                  "one_token"])
def test_engine_serves_what_the_reference_would(case):
    """Chunked prefill, then decode through the state, held to the
    reference's FULL forward over prompt and served tokens: on the gaps of
    the served tokens' logits as ``served_gaps`` compares, and on the
    logits the slots still carry. ``preempted``: the newest request is
    preempted before step 4 (mid-prefill) and replays from its first token
    into a state zeroed in the graph. ``one_token``: ``readout_stride=1``,
    the one-token ``step`` program and no scan."""
    seed = 17
    model, params = build(TOY, seed)
    arrivals = _arrivals(case, np.random.default_rng(6))
    over = {"two_ramping": dict(max_step_tokens=64),
            "one_token": dict(readout_stride=1)}.get(case, {})
    done, eng = _serve(model, arrivals,
                       preempt_at=4 if case == "preempted" else None, **over)
    s = eng.stats
    n_req = sum(len(v) for v in arrivals.values())
    assert s["preemptions"] == (case == "preempted")
    assert s["state_resets"] == n_req + s["preemptions"]
    assert s["fused_steps"] > 0
    assert (s["multi_steps"] == 0) == (case == "one_token")
    if case == "two_ramping":
        assert s["prefill_chunks"] > s["fused_steps"]
    out = R.served_gaps(seed, TOY, list(done.values()), pad_to=64)
    gaps = np.concatenate(out["gaps"])
    # float32 engine against float32 reference: a served token is the
    # reference's choice, or loses to it by rounding
    assert gaps.max() < 1e-3 * out["logit_std"]
    errs = _carried_errors(eng, done, params)
    assert errs[min(len(done), 2) - 1] < LOGIT_TOL
    # the int8 control of the same requests is past the tolerance
    low = _carried_errors(eng, done, params, "int8")
    assert low[0] > 5 * LOGIT_TOL
    # the counters that left the step programs beside the tokens: every
    # live row went through one form or the other in every layer
    live = s["prefill_tokens"] + s["tokens_generated"]
    assert s["ret_rows_chunk"] + s["ret_rows_step"] == live * 3
    assert s["ret_rows_step"] >= s["tokens_generated"] * 3
    assert 0 < s["ret_state_live"] <= s["ret_state_walked"]
    # every pass over the states is counted in every one of the 3 layers
    assert s["ret_state_walked"] % 3 == 0
    for key, names in (("live_states", ("ret_state_live",)),
                       ("ret_rows", ("ret_rows_chunk", "ret_rows_step"))):
        assert sum(ids.get(key, 0) for ids in eng.emitted) == \
            sum(s[n] for n in names)
    # no pool: nothing of the pool's or the attention grid's was booked
    assert s["kv_grid_blocks"] == s["pool_blocks_total"] == 0


def test_a_bfloat16_state_is_past_the_tolerance(monkeypatch):
    """The same traffic with the state held in bfloat16 (every other
    operation as before) misses the reference by more than the tolerance
    the float32 state stays under: the state's type is part of the
    result."""
    shapes = M.PowerRetention.state_shapes
    monkeypatch.setattr(
        M.PowerRetention, "state_shapes",
        lambda self: {k: (s, jnp.bfloat16)
                      for k, (s, _) in shapes(self).items()})
    model, params = build(TOY, 17)
    done, eng = _serve(model, _arrivals("staggered",
                                        np.random.default_rng(6)))
    assert eng._k[0]["S"].dtype == jnp.bfloat16
    assert _carried_errors(eng, done, params)[0] > 5 * LOGIT_TOL


# ---- a layout without a paged layer ----------------------------------------

def test_what_the_engine_builds_for_a_layout_without_a_paged_layer():
    model, _ = build(TOY, 1)
    eng = LLMEngine(model, **ENGINE)
    lay = eng._layout
    assert lay.has_recurrent and not lay.has_paged and not lay.plain_kv
    # the state a (slot, layer), and nothing else: no pool
    for layer in range(3):
        assert set(eng._k[layer]) == {"S", "z"} and eng._v[layer] is None
        assert eng._k[layer]["S"].shape == (3, 2, 136, 16)
        assert eng._k[layer]["z"].shape == (3, 2, 136)
        assert eng._k[layer]["S"].dtype == jnp.float32
    assert eng.kv_pool_nbytes() == eng.kv_bytes_per_block() == 0
    assert eng.kv_pool_effective_blocks() == 0
    # the allocator and the tables are the empty formality: every slot can
    # cover its whole capacity (200 is not a multiple of the chunk, 32:
    # that rule is the paged pools')
    assert eng.capacity == 200 and eng._tables.shape == (3, 13)
    assert eng.n_blocks == 3 * 13 == len(eng._free_blocks)
    eng._book_kv_grid(4)
    assert eng.stats["kv_grid_blocks"] == eng.stats["pool_blocks_total"] == 0


def test_admission_is_bounded_by_slots_and_max_seq_len_alone():
    """Three slots of 200 tokens: three prompts that fill their slots to
    the last position are resident at once and finish at the capacity,
    the fourth waits for a slot, and nothing is preempted."""
    model, _ = build(TOY, 2)
    rng = np.random.default_rng(2)
    docs = [(rng.integers(1, 256, size=190).astype(np.int32), 40)
            for _ in range(4)]
    eng = LLMEngine(model, **ENGINE)
    with pytest.warns(RuntimeWarning, match="capping max_new_tokens"):
        for prompt, n in docs:
            eng.add_request(list(prompt), max_new_tokens=n)
        eng.step()
        assert sum(s is not None for s in eng.slots) == 3
        assert len(eng.waiting) == 1
        done = []
        while eng.has_unfinished():
            done += eng.step()
    assert len(done) == 4 and eng.stats["preemptions"] == 0
    # 190 + 9 = 199 positions: the room a slot of 200 leaves
    assert {len(o.token_ids) for o in done} == {9}
    assert len(eng._free_blocks) == eng.n_blocks


class _Store:
    pass


def _tp_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.parametrize("option,match", [
    (dict(scheduler="legacy", readout_stride=1),
     "no K and V for legacy admission"),
    (dict(cache_impl="dense"), "no K and V to put in the dense"),
    (dict(kv_pool_blocks=8), "no pool to size"),
    (dict(enable_prefix_cache=True), "no blocks for the content store"),
    (dict(kv_host_swap=True), "no pool blocks to swap out or spill"),
    (dict(kv_host_spill_bytes=1 << 20), "no pool blocks to swap out"),
    (dict(speculative_k=3), "cannot roll a rejected draft back"),
    (dict(kv_cache_dtype="int8"), "no K/V pool to quantize"),
    (dict(adapter_store=_Store()), "do not read the adapter scope"),
    (dict(mesh=_tp_mesh), "sharded by head is not written"),
])
def test_an_option_a_recurrent_only_layout_cannot_honour_raises(option,
                                                                 match):
    model, _ = build(TOY, 1)
    option = {k: v() if callable(v) and k == "mesh" else v
              for k, v in option.items()}
    with pytest.raises(ValueError, match=match) as e:
        LLMEngine(model, **dict(ENGINE, **option))
    # every one of them names the layout's own reason
    assert "a recurrent-only layout (no layer is paged" in str(e.value)
    assert "['recurrent'] layers" in str(e.value)


def test_horizon_shipping_and_embedding_are_refused_too():
    model, _ = build(TOY, 1)
    with pytest.raises(ValueError, match="readout_stride"):
        LLMEngine(model, **dict(ENGINE, horizon=4, readout_stride=1))
    eng = LLMEngine(model, **ENGINE)
    for call in (lambda: eng.add_request([1, 2, 3], export_kv=True),
                 lambda: eng.export_kv(0), lambda: eng.import_kv({}),
                 lambda: eng.export_prefix_blocks([]),
                 lambda: eng.import_prefix_blocks([])):
        with pytest.raises(ValueError, match="has no blocks at all"):
            call()
    with pytest.raises(ValueError, match="embed"):
        eng.add_request([1, 2, 3], kind="embed")


# ---- the real sizes, compiled for the chip ---------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from benchmark.tests import brumby_aot
    try:
        return brumby_aot.describe_one_chip()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_the_step_programs_compile_for_the_v5e_with_the_state_in_place(
        one_chip):
    """The mixed step and the one-token ``multi_step`` scan at the
    published widths, 8 layers, 16 slots, compiled by the TPU compiler
    installed here for a described v5e (nothing runs): arguments and
    temporaries fit the chip, every state is aliased to its output, and no
    instruction but the core's own update makes a state-shaped array
    (``benchmark/tests/test_aot_brumby.py`` holds the same for each of the
    three programs apart). And the TPU compiler keeps ``sample_next``'s
    ``lax.cond`` a ``conditional``: the sort over the 151,936 ids of the 16
    rows is an instruction of its sampling branch alone, so a step whose
    rows are all greedy does not run it."""
    from benchmark.tests import brumby_aot
    from test_device_scopes import sampling_branches
    eng, raw, args = brumby_aot.engine(one_chip)
    assert eng.B == 16 and eng.mixed_rows == 528
    assert eng.kv_pool_nbytes() == 0
    for name in ("fused_step", "multi_step"):
        compiled = brumby_aot.compile_for_the_chip(raw, args, name)
        arguments, temporaries = brumby_aot.held_in_place(compiled, eng)
        assert arguments > 16 * 8 * brumby_aot.STATE_BYTES + 8.39e9
        assert temporaries < 1e9
        greedy, sampling, comps = sampling_branches(compiled.as_text())
        vocabulary_sorts = [line for lines in comps.values()
                            for line in lines
                            if " sort(" in line and "[16,151936]" in line]
        assert len(vocabulary_sorts) == 1
        assert vocabulary_sorts[0] in sampling
        assert not [line for line in greedy
                    if " sort(" in line or "rng" in line]


@pytest.mark.parametrize("cell,b,t,s,h,mb,hq", [
    ("dsv2_rag_answers", 8, 528, 512, 128, 136, 16),
    ("kimi_long_docs", 16, 272, 256, 32, 260, 32),
])
def test_the_packed_latent_append_compiles_for_the_v5e(one_chip, cell, b, t,
                                                       s, h, mb, hq):
    """The latent append on a mixed step's packed rows (``[T, H, 576]``
    with ``(start, q_lens, seq_lens)`` prefetched) at the two latent
    cells' shapes, compiled by the TPU compiler installed here for a
    described v5e (nothing runs; this file has the tier-1 lane that lowers
    for the chip from a CPU host, and a second file could not describe the
    topology beside it): Mosaic takes a head group's resident block with a
    dynamic sublane offset at the latent width, with the slots' rows as
    they come (no gather: ``hq`` is a multiple of a sublane tile)."""
    from benchmark.tests import brumby_aot
    from paddle_tpu.ops.kernels import latent_attention as la
    d, dv, bs = 576, 512, 64
    assert la.heads_per_step(h, b, t, s, d, dv, bs) == hq
    assert la.slot_step(hq) == 1

    def fn(q, pool, tables, lens, q_lens, start):
        return la._append_rows(q, pool, tables, lens, q_lens, start,
                               width=s, dv=dv, every=None, interpret=False)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    i32 = jnp.int32
    args = (shape((t, h, d), jnp.bfloat16),
            shape((b * mb + 1, bs, d), jnp.bfloat16), shape((b, mb), i32),
            shape((b,), i32), shape((b,), i32), shape((b,), i32))
    # this suite asks every product for float32 passes; the kernel's are
    # one-pass bfloat16 products, as the served program traces them
    with jax.default_matmul_precision("default"):
        text = brumby_aot.compile_for_the_chip(
            {cell: jax.jit(fn)}, {cell: args}, cell).as_text()
    assert "tpu_custom_call" in text and "latent_attention_append" in text
    assert " gather(" not in text


@pytest.mark.parametrize("cell,config,program,slots,t,chunk,heads", [
    ("kimi_long_docs", "kimi-linear-48b-a3b-ep4-d8", "kimi_linear", 8, 272,
     256, 32),
    ("solar_long_reports", "solar-open2-250b-ep8-d4", "solar_open2", 16, 528,
     512, 64),
])
def test_the_packed_kda_layer_compiles_for_the_v5e(one_chip, monkeypatch,
                                                   cell, config, program,
                                                   slots, t, chunk, heads):
    """A KDA layer of the two cells that have one, on a mixed step's
    packed rows ``[1, T, hidden]`` at the cell's slots, rows and heads,
    compiled by the TPU compiler installed here for a described v5e
    (nothing runs): ``kda_chunk_walk`` takes the packed q, k, v, g and
    beta as the layer computed them (Mosaic accepts a head group's
    resident blocks read at ``start[b] + 64 c`` on the leading axis), so
    the program holds no gather but the convolution's own and nothing of
    the per-slot view's size ``[slots, chunk, heads, 128]``: no broadcast
    that fills one, no ``dynamic-update-slice`` that writes a slot into
    one, no operand."""
    from benchmark.harness import loader
    from benchmark.tests import brumby_aot
    from paddle_tpu.core.tensor import Tensor, functional_mode
    from paddle_tpu.jit.functional_call import bind_state
    from paddle_tpu.models import cache_layout as CL
    from paddle_tpu.models.kimi_linear import KimiDeltaAttention
    from paddle_tpu.ops.kernels import kda_chunk_walk as walk
    from paddle_tpu.ops.kernels import paged_attention
    # this process sees a CPU: route the kernel to Mosaic all the same
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    with paddle.LazyGuard():
        model = loader.module("programs", program).build(
            loader.data("configs", config))
    model.eval()
    layer = next(block.self_attn for block in model.model.layers
                 if isinstance(block.self_attn, KimiDeltaAttention))
    assert layer.H == heads and walk.serves(layer.K, layer.K)
    params = [p for _, p in layer.named_parameters()]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    def fn(vals, x, S, conv, lens, q_lens):
        rows = CL.RowMap(q_lens, lens, t, chunk)
        cache = CL.RecurrentCache({"S": S, "conv": conv}, lens, q_lens,
                                  None, rows)
        with paddle.no_grad(), functional_mode(), bind_state(params, vals):
            out, new = layer(Tensor(x), cache)
        return out._value, new.state["S"], new.state["conv"]

    bf16, i32 = jnp.bfloat16, jnp.int32
    state = layer.state_shapes(np.dtype(bf16))
    args = ([shape(p._value.shape, bf16) for p in params],
            shape((1, t, model.config.hidden_size), bf16),
            shape((slots,) + state["S"][0], state["S"][1]),
            shape((slots,) + state["conv"][0], state["conv"][1]),
            shape((slots,), i32), shape((slots,), i32))
    with jax.default_matmul_precision("default"):
        text = brumby_aot.compile_for_the_chip(
            {cell: jax.jit(fn)}, {cell: args}, cell).as_text()
    assert "tpu_custom_call" in text and "kda_chunk_walk" in text
    # the gathers that are left read the convolution's tails, under
    # ``pt.conv``: none moves a row to a slot or back
    for line in text.splitlines():
        if " gather(" in line:
            assert "self_attn/pt.conv/" in line, line
    view = f"[{slots},{chunk},{heads},128]"
    assert view not in text, [ln for ln in text.splitlines()
                              if view in ln][:3]
    for line in text.splitlines():
        if "pt.view" in line:
            assert " broadcast(" not in line or f"{slots},{chunk}," \
                not in line, line
            assert "dynamic-update-slice(" not in line, line

@pytest.mark.parametrize("cell,config,program,slots,t,chunk,heads,kv", [
    ("doc_batch", "mistral-7b-v0.3-d16", "llama", 8, 272, 256, 32, 8),
    ("solar_long_reports", "solar-open2-250b-ep8-d4", "solar_open2", 16, 528,
     512, 64, 8),
])
def test_the_packed_gqa_layer_compiles_for_the_v5e(one_chip, monkeypatch,
                                                   cell, config, program,
                                                   slots, t, chunk, heads, kv):
    """A GQA layer of the two cells whose every mixed step runs the paged
    append (``LlamaAttention`` at Mistral's widths, Solar's
    ``GatedAttention``) on the step's packed rows ``[1, T, hidden]`` at
    the cell's slots, rows, heads and table, compiled by the TPU compiler
    installed here for a described v5e (nothing runs):
    ``paged_attention_append`` takes the packed q, k, v as the layer
    computed them with ``rows.start`` prefetched (Mosaic accepts a head
    group's resident block read at ``start[b] * G`` rounded down to a
    sublane tile), so the program holds no ``rows_to_slots`` /
    ``rows_from_slots``, no gather but rope's of its table, and nothing of
    the per-slot view's size ``[slots, chunk, heads, 128]`` or of its
    head-major form ``[slots, kv, chunk * G, 128]``."""
    from benchmark.harness import loader
    from benchmark.tests import brumby_aot
    from paddle_tpu.core.tensor import Tensor, functional_mode
    from paddle_tpu.jit.functional_call import bind_state
    from paddle_tpu.models import cache_layout as CL
    from paddle_tpu.models.llama import LlamaAttention, PagedKVCache
    from paddle_tpu.ops.kernels import paged_attention
    # this process sees a CPU: route the op to the kernel, and the kernel
    # to Mosaic, all the same
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    cfg = loader.data("configs", config)
    with paddle.LazyGuard():
        model = loader.module("programs", program).build(cfg)
    model.eval()
    layer = next(block.self_attn for block in model.decoder.layers
                 if isinstance(block.self_attn, LlamaAttention)
                 or type(block.self_attn).__name__ == "GatedAttention")
    params = [p for _, p in layer.named_parameters()]
    llama = isinstance(layer, LlamaAttention)
    bs = int(cfg["engine"]["block_size"])
    mb = int(cfg["engine"]["max_seq_len"]) // bs

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    def fn(vals, x, k_pool, v_pool, tables, lens, q_lens, cos, sin):
        rows = CL.RowMap(q_lens, lens, t, chunk)
        cache = PagedKVCache(Tensor(k_pool), Tensor(v_pool), Tensor(tables),
                             Tensor(lens), Tensor(q_lens), rows=rows)
        with paddle.no_grad(), functional_mode(), bind_state(params, vals):
            if llama:
                out, new = layer(Tensor(x), (cos, sin), None, cache,
                                 Tensor(rows.pos[None]))
            else:
                out, new = layer(Tensor(x), cache)
        return out._value, new.k._value, new.v._value

    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = shape((slots * mb + 1, kv, bs, 128), bf16)
    rope = shape((int(cfg["max_position_embeddings"]), 128), jnp.float32)
    args = ([shape(p._value.shape, bf16) for p in params],
            shape((1, t, model.config.hidden_size), bf16), pool, pool,
            shape((slots, mb), i32), shape((slots,), i32),
            shape((slots,), i32), rope, rope)
    with jax.default_matmul_precision("default"):
        text = brumby_aot.compile_for_the_chip(
            {cell: jax.jit(fn, donate_argnums=(2, 3))}, {cell: args},
            cell).as_text()
    assert "tpu_custom_call" in text and "paged_attention_append" in text
    assert "rows_to_slots" not in text and "rows_from_slots" not in text
    # the gathers that are left read rope's table, one row of it a packed
    # row: none moves a row of q, k or v to a slot or back
    for line in text.splitlines():
        if " gather(" in line:
            assert f" = f32[{t},128]" in line, line
    group = heads // kv
    for view in (f"[{slots},{chunk},{heads},128]",
                 f"[{slots},{chunk},{kv},{group},128]",
                 f"[{slots},{kv},{chunk * group},128]"):
        assert view not in text, [ln for ln in text.splitlines()
                                  if view in ln][:3]


def test_the_exact_top_k_compiles_for_the_v5e_without_a_gather(one_chip):
    """``sparse_latent_attention.select`` at ``dots3_long_answers``' mixed
    step (528 packed rows against 33,280 positions, ``top_k`` 2,048) under
    the scope the layer calls it in, compiled by the TPU compiler
    installed here for a described v5e (nothing runs; in this file for
    its ``one_chip`` fixture): a block's 128 ranks reach an output by a
    product with the one-hot of the block's index, so the program holds no
    ``gather`` (a copy a row on the chip: 1.08 M of them a layer), no
    ``[528, 2048, 128]`` of ranks in ``u8``, and, the compare, the product
    and the count being one fusion, neither of the product's operands
    (562 MB) nor its result (277 MB and up) among its temporaries."""
    from benchmark.tests import brumby_aot
    from paddle_tpu.ops.kernels import sparse_latent_attention as dsa
    from paddle_tpu.profiler import scope
    t, s, k = 528, 33280, 2048

    def fn(scores, pos, live):
        with scope("pt.select"):
            return dsa.select(
                scores, types.SimpleNamespace(pos=pos, live=live), k)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    args = (shape((t, s), jnp.float32), shape((t,), jnp.int32),
            shape((t,), jnp.bool_))
    # one-pass bfloat16 products, as the served program traces them
    with jax.default_matmul_precision("default"):
        compiled = brumby_aot.compile_for_the_chip(
            {"select": jax.jit(fn)}, {"select": args}, "select")
    text = compiled.as_text()
    assert "pt.select" in text
    assert " gather(" not in text
    assert f"u8[{t},{k},128]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6


@pytest.mark.parametrize("form,rows", [("mixed", 640), ("one_token", 1)])
def test_the_lfm2_attention_layer_compiles_on_pools_of_128_lanes(
        one_chip, monkeypatch, form, rows):
    """``Lfm2Attention`` at ``lfm2_agent_turns``' sizes (32 query heads on
    8 K/V heads of SIXTY-FOUR, 128 slots of 32 blocks of 64, a mixed
    step's 640 packed rows or a decode scan's row a slot), compiled by the
    TPU compiler installed here for a described v5e (nothing runs; in this
    file for its ``one_chip`` fixture): two heads share a pool row, so the
    pools are ``[4097, 4, 64, 128]`` and reach the paged kernels in the
    row-major tiles they lie in, and no copy of a pool (268 MB) is among
    the temporaries. The pool ``[4097, 8, 64, 64]`` that the published
    head size would give is held block-axis-minor and copied whole into
    128 padded lanes around every call of either kernel (1.08 GB of
    temporaries): ``benchmark/tests/test_aot_lfm2.py`` compiles that
    too."""
    from benchmark.harness import loader
    from benchmark.tests import brumby_aot
    from paddle_tpu.core.tensor import Tensor, functional_mode
    from paddle_tpu.jit.functional_call import bind_state
    from paddle_tpu.models import cache_layout as CL
    from paddle_tpu.models.llama import PagedKVCache
    from paddle_tpu.ops.kernels import paged_attention
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    cfg = loader.data("configs", "lfm2-24b-a2b-pp4-d10")
    with paddle.LazyGuard():
        model = loader.module("programs", "lfm2_moe").build(cfg)
    model.eval()
    layer = model.decoder.layers[2].self_attn
    kind = layer.kind()
    assert (layer.pack, kind.kv_heads, kind.head_dim) == (2, 4, 128)
    params = [p for _, p in layer.named_parameters()]
    slots, chunk, bs, mb = 128, 512, 64, 32
    nb = slots * mb + 1

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)

    def fn(vals, x, k_pool, v_pool, tables, lens, q_lens):
        row_map = CL.RowMap(q_lens, lens, rows, chunk) \
            if form == "mixed" else None
        cache = PagedKVCache(Tensor(k_pool), Tensor(v_pool), Tensor(tables),
                             Tensor(lens), Tensor(q_lens), rows=row_map)
        with paddle.no_grad(), functional_mode(), bind_state(params, vals):
            out, new = layer(Tensor(x), cache)
        return out._value, new.k._value, new.v._value

    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = shape((nb, 4, bs, 128), bf16)
    lead = (1, rows) if form == "mixed" else (slots, 1)
    args = ([shape(p._value.shape, bf16) for p in params],
            shape(lead + (2048,), bf16), pool, pool, shape((slots, mb), i32),
            shape((slots,), i32), shape((slots,), i32))
    with jax.default_matmul_precision("default"):
        compiled = brumby_aot.compile_for_the_chip(
            {form: jax.jit(fn, donate_argnums=(2, 3))}, {form: args}, form)
    text = compiled.as_text()
    kernel = "paged_attention_append" if form == "mixed" \
        else "paged_attention_decode"
    assert "tpu_custom_call" in text and kernel in text
    assert f"bf16[{nb},4,64,128]{{3,2,1,0:" in text
    assert "pt.qk_norm" in text and "pt.rope" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
