"""Solar-Open2 (``paddle_tpu/models/solar_open2.py``) against its plain
float32 reference (``benchmark/reference/solar_open2_plain.py``, the one
file of the benchmark these tests import, so that the tests' reference and
the cell's cannot drift apart), at toy widths on the CPU: (a) the forward
and the engine (mixed steps with two slots ramping beside one decoding,
the one-token step, a ``multi_step`` scan, a request preempted and
replayed) compared as ``served_gaps`` compares; (b) the share test of the
expert layer over eight shares; (c) the three forms of KDA at a write
strength ``beta`` in (1, 2), the kernel at the published head width; (d)
``cache_layout.PagedKV`` as a kind of a mixed layout and the counters the
engine books over the K/V layers alone; (e) every option a recurrent layer
beside a pool refuses, by its words; (f) the expert layer beside the GQA
mixer sized by the step's row budget. A head width under 128 stays on
``kda.kda_chunk`` (``kda_chunk_walk.serves``); the kernel itself is held in
(c), called directly."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.harness import loader
from benchmark.harness import weights as W
from benchmark.reference import solar_open2_plain as R
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import cache_layout as CL
from paddle_tpu.models.llama import PagedKVCache
from paddle_tpu.ops.kernels import kda, kda_chunk_walk, moe_dropless, \
    paged_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the toy cut of the shipped configuration's keys: hidden 64, 4/2 heads of
#: 32 (NOT hidden / heads), KDA 4 heads x 16, 16 experts top-4 of which 4
#: held, one period (GQA, KDA, KDA, KDA); the published list of GQA layers
#: is kept whole and the program takes those inside the depth
TOY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=160,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, gqa_interval=3, gqa_layers=[0, 4, 8, 12],
    linear_attn_config=dict(num_heads=4, head_dim=16, num_kv_heads=None,
                            short_conv_kernel_size=4),
    gate_low_rank=8, first_k_dense_replace=0, moe_intermediate_size=32,
    n_routed_experts=4, n_routed_experts_published=16, expert_offset=0,
    num_experts_per_tok=4, n_shared_experts=1, routed_scaling_factor=1,
    norm_topk_prob=True, rms_norm_eps=1e-5, max_position_embeddings=4096,
    tie_word_embeddings=False, use_rope=False, use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True)


def program():
    return loader.module("programs", "solar_open2")


def build(cfg, seed):
    """The program's model with the reference's float32 seeded leaves;
    returns (model, {name: float32 array})."""
    model = program().build(cfg)
    model.eval()
    named = list(model.named_parameters())
    mine = {n: tuple(p._value.shape) for n, p in named}
    assert mine == {n: tuple(s) for n, s in R.specs(cfg)}
    # the benchmark's leaves are bfloat16 (the reference's ``served_gaps``
    # makes them so again); float32 copies of those values compute here
    vals = W.make(seed, [(n, mine[n]) for n, _ in named], jnp.bfloat16,
                  None, R.is_scale)
    params = {}
    for (n, p), v in zip(named, vals):
        p._value = params[n] = v.astype(jnp.float32)
    return model, params


def test_specs_size_and_layout_of_the_shipped_configuration():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "solar-open2-250b-ep8-d4.json")) as f:
        cfg = json.load(f)
    assert R.n_params(cfg) == 3_308_353_344
    with paddle.LazyGuard():
        model = program().build(cfg)
    assert {n: tuple(p._value.shape) for n, p in model.named_parameters()} \
        == {n: tuple(s) for n, s in R.specs(cfg)}
    layout = model.cache_layout()
    assert [k.kind for k in layout] == ["paged_kv"] + ["recurrent"] * 3
    # K and V of 8 x 128 a token a GQA layer; the group is the kind's
    assert layout[0].bytes_per_token(2) == 4096
    assert (layout[0].kv_heads, layout[0].head_dim, layout[0].q_heads) == \
        (8, 128, 64)
    assert layout[1].shapes["S"] == ((64, 128, 128), np.dtype("float32"))
    conv_shape, conv_dtype = layout[1].shapes["conv"]
    assert layout[1].bytes_per_slot() == 4_194_304 + \
        3 * 3 * 8192 * conv_dtype.itemsize     # 147,456 B in bfloat16
    assert conv_shape == (3, 3 * 8192)


@pytest.mark.parametrize("key,value", [
    ("use_rope", True), ("use_gqa_gate", False), ("kda_use_full_proj", True),
    ("kda_allow_neg_eigval", False), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("first_k_dense_replace", 1),
    ("n_shared_experts", 2)])
def test_the_program_refuses_by_name_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=f"solar_open2: {key}="):
        program().build(dict(TOY, **{key: value}))


def test_the_model_raises_on_labels_and_the_partition_stub_says_why():
    model, _ = build(TOY, 1)
    ids = paddle.to_tensor(np.ones((1, 8), np.int32))
    with pytest.raises(NotImplementedError, match="backward"):
        model(ids, labels=ids)
    with pytest.raises(NotImplementedError, match="experts over chips"):
        program().partition("model.embed_tokens.weight", "tp")


# ---- (a) the forward and the engine against the reference ----------------

@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_forward_matches_the_reference(seed):
    model, params = build(TOY, seed)
    ids = np.random.default_rng(seed).integers(1, 256, size=(2, 70))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
    for b in range(2):
        want = np.asarray(R.forward_logits(params, jnp.asarray(ids[b]), TOY))
        # float32 on both sides; the forms differ (chunked against a scan
        # a token, paged against full attention, sorted groups against a
        # loop over experts): rounding only
        np.testing.assert_allclose(got[b], want, atol=2e-4, rtol=2e-4)


def test_a_dropped_gate_or_an_unscaled_beta_is_not_the_reference():
    """The two mechanisms this model adds to the layers it shares change
    the logits by far more than the tolerance above: the comparison would
    catch either left out."""
    _, params = build(TOY, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 256, size=70))
    want = np.asarray(R.forward_logits(params, ids, TOY))
    no_gate = np.asarray(R.forward_logits(params, ids, TOY, "no_gate"))
    beta_1 = np.asarray(R.forward_logits(
        params, ids, dict(TOY, kda_allow_neg_eigval=False)))
    assert np.abs(no_gate - want).max() > 1e-2
    assert np.abs(beta_1 - want).max() > 1e-3


ENGINE = dict(scheduler="fused", cache_impl="paged", block_size=16,
              chunk_size=32, readout_stride=4, max_batch=3, max_seq_len=192)


def _serve(model, arrivals, **over):
    """Drive the engine a step at a time; ``arrivals``: {step: [(prompt,
    max_new)]}. Returns ({rid: (prompt, tokens)}, engine)."""
    eng = LLMEngine(model, **dict(ENGINE, **over))
    eng.emitted, eng.dispatched, to = [], [], eng._to

    def recording(phase, **ids):     # what rides on the engine's spans
        if phase == "emit":
            eng.emitted.append(ids)
        if phase == "dispatch":
            eng.dispatched.append(ids)
        return to(phase, **ids)
    eng._to = recording
    prompts, done, step = {}, {}, 0
    while step < 400:
        for prompt, n in arrivals.get(step, ()):
            rid = eng.add_request(list(prompt), max_new_tokens=n)
            prompts[rid] = prompt
        for out in eng.step():
            done[out.request_id] = (prompts[out.request_id],
                                    np.asarray(out.token_ids, np.int32))
        step += 1
        if step > max(arrivals) and not eng.has_unfinished():
            break
    assert len(done) == len(prompts)
    assert eng._write_fence == {} and eng._quarantine == set()
    return done, eng


@pytest.mark.parametrize("case", ["two_ramping", "one_token", "staggered",
                                  "preempted"])
def test_engine_serves_what_the_reference_would(case):
    """Chunked prefill through the K/V pool and the KDA states, then
    decoding, compared on the gaps of the served tokens' logits as
    ``served_gaps`` compares. ``two_ramping``: a budget of two chunks, so
    two documents prefill in ONE mixed step beside a third's decode token:
    the packed row axis holds two slots' chunks back to back, and the
    pool's append, the convolution tails and the recurrences each take
    their own slot's rows out of it. ``one_token``: ``readout_stride`` 1,
    every all-decode step the one-token program. ``staggered``: arrivals
    spread over steps, ``multi_step`` scans of stride 4, a slot that idles
    while others decode, a slot reused. ``preempted``: a pool too small
    for the batch, so a request is preempted (its K/V blocks freed) and
    replays from its first token into KDA state zeroed in the graph."""
    seed = 17
    model, _ = build(TOY, seed)
    rng = np.random.default_rng(6)

    def doc(n):
        return rng.integers(1, 256, size=n).astype(np.int32)
    if case == "two_ramping":
        arrivals = {0: [(doc(21), 20)], 2: [(doc(70), 9), (doc(61), 8)]}
        done, eng = _serve(model, arrivals, max_step_tokens=64)
        assert eng.mixed_rows == 64 < 3 * 32
        # some mixed step carried two prefill grants beside a decode row
        assert eng.stats["prefill_chunks"] > eng.stats["fused_steps"]
        assert any(d.get("prefill_rows", 0) > 32 and d["decode_rows"] == 1
                   for d in eng.dispatched)
        assert eng.stats["state_resets"] == 3
    elif case == "one_token":
        arrivals = {0: [(doc(40), 11)], 1: [(doc(33), 7)]}
        done, eng = _serve(model, arrivals, readout_stride=1)
        assert eng.stats["multi_steps"] == 0
        assert eng.stats["steps"] > eng.stats["fused_steps"] > 0
    elif case == "staggered":
        arrivals = {0: [(doc(70), 9)], 2: [(doc(45), 12)],
                    9: [(doc(100), 6), (doc(33), 10)], 14: [(doc(5), 7)]}
        done, eng = _serve(model, arrivals)
        assert eng.stats["preemptions"] == 0
        assert eng.stats["state_resets"] == 5
        assert eng.stats["multi_steps"] > 0
    else:
        arrivals = {0: [(doc(90), 30), (doc(80), 30), (doc(85), 30)]}
        done, eng = _serve(model, arrivals, kv_pool_blocks=16)
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["state_resets"] == 3 + eng.stats["preemptions"]
        assert eng.stats["multi_steps"] > 0
    assert eng.stats["fused_steps"] > 0
    out = R.served_gaps(seed, TOY, list(done.values()), pad_to=64)
    gaps = np.concatenate(out["gaps"])
    # float32 engine against float32 reference: a served token is the
    # reference's choice, or loses to it by rounding
    assert gaps.max() < 1e-3 * out["logit_std"]
    # the counters that left the step programs beside the tokens, each
    # over the layers of its own kind: 4 expert layers of 4 held experts
    s = eng.stats
    assert 0 < s["moe_assignments_held"] <= s["moe_assignments"]
    assert s["moe_assignments_dropped"] == 0
    assert s["moe_experts_held"] % (4 * 4) == 0
    assert 0 < s["moe_experts_nonempty"] <= s["moe_experts_held"]
    for key, name in (("held_rows", "moe_assignments_held"),
                      ("experts_read", "moe_experts_nonempty"),
                      ("experts_held", "moe_experts_held")):
        assert sum(ids.get(key, 0) for ids in eng.emitted) == s[name]
    assert s["kv_grid_blocks"] > 0 and s["pool_blocks_total"] > 0
    assert 0 < s["attn_tile_steps"] <= s["attn_tile_steps_grid"]
    # a mixed step's dispatch span carries the append kernel's live tiles
    mixed = [d for d in eng.dispatched if d["kind"] == 1]
    assert mixed and all("live_tiles" in d for d in mixed)
    assert sum(d["live_tiles"] for d in mixed) == s["attn_tile_steps"]


def test_the_engine_serves_two_chunks_a_step_through_the_kda_kernel(
        monkeypatch):
    """The rule on shapes lifted (the kernel interpreted at the toy
    width), a budget of two chunks: a mixed step's packed axis holds two
    slots' prompt chunks back to back beside a decode row, and
    ``kda_chunk_walk`` takes each slot's rows off it by ``(start, q_lens,
    seq_lens)`` at beta up to 2. The served tokens are the plain
    reference's, as on the XLA path; the kernel's table of two slots'
    chunks and a row is what the counters book."""
    monkeypatch.setattr(kda_chunk_walk, "serves", lambda k, v: True)
    seed = 17
    model, _ = build(TOY, seed)
    rng = np.random.default_rng(6)

    def doc(n):
        return rng.integers(1, 256, size=n).astype(np.int32)
    arrivals = {0: [(doc(21), 20)], 2: [(doc(70), 9), (doc(61), 8)]}
    done, eng = _serve(model, arrivals, max_step_tokens=64)
    assert eng.mixed_rows == 64
    assert any(d.get("prefill_rows", 0) > 32 and d["decode_rows"] == 1
               for d in eng.dispatched)
    out = R.served_gaps(seed, TOY, list(done.values()), pad_to=64)
    assert np.concatenate(out["gaps"]).max() < 1e-3 * out["logit_std"]
    s = eng.stats
    kda_layers = sum(kind == "kda" for kind in
                     (model.config.layer_kind(i)
                      for i in range(model.config.num_hidden_layers)))
    # 3 slots of at most one 64-row chunk each on 64 packed rows
    steps = kda_chunk_walk.table_steps(3, 64, 32)
    assert steps == 3 and kda_layers > 0
    assert s["kda_grid_steps"] == kda_layers * steps * s["fused_steps"]
    assert 0 < s["kda_grid_live"] <= s["kda_grid_steps"]


# ---- (b) the share test ---------------------------------------------------

def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    rng = np.random.default_rng(4)
    n, h, f, e_all, k, held = 50, 64, 32, 32, 4, 4
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, e_all)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e_all,)) * 0.1, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e_all, h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e_all, f, h)) * 0.1, jnp.float32)
    sg, su = (jnp.asarray(rng.normal(size=(h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    sd = jnp.asarray(rng.normal(size=(f, h)) * 0.1, jnp.float32)
    d = dict(topk=k, renorm=True, scale=1.0, off=0)
    whole = R._moe(x, (wr, bias, wg, wu, wd, sg, su, sd), d, "f32")
    idx, w = moe_dropless.route(x, wr, bias, k, 1.0)
    live = jnp.ones((n,), bool)
    total = R._swiglu(x, sg, su, sd, "f32")          # the shared expert ONCE
    landed = 0
    for off in range(0, e_all, held):                # the eight chips
        part, counts = moe_dropless.held_expert_ffn(
            x, idx, w, live, wg[off:off + held], wu[off:off + held],
            wd[off:off + held], off, rows=n * k)
        ref_part = R.routed_part(x, idx, w, wg[off:off + held],
                                 wu[off:off + held], wd[off:off + held],
                                 off, "f32")
        np.testing.assert_allclose(part, ref_part, atol=2e-5)
        total = total + part
        counts = dict(zip(moe_dropless.COUNTERS, np.asarray(counts)))
        assert counts["moe_assignments"] == n * k
        assert counts["moe_assignments_dropped"] == 0
        landed += counts["moe_assignments_held"]
    assert landed == n * k        # every assignment lands on one share
    np.testing.assert_allclose(total, whole, atol=5e-5)


# ---- (c) KDA at beta in (1, 2) ---------------------------------------------

def _kda_inputs(rng, beta_lo, beta_hi, b, t, h, k):
    q, kk = rng.normal(size=(2, b, t, h, k))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * k ** 0.5
    kk /= np.linalg.norm(kk, axis=-1, keepdims=True)
    v = rng.normal(size=(b, t, h, k))
    g = np.log(0.9) * (0.5 + rng.random(size=(b, t, h, k)))
    beta = beta_lo + (beta_hi - beta_lo) * rng.random(size=(b, t, h))
    return [jnp.asarray(a, jnp.float32) for a in (q, kk, v, g, beta)]


@pytest.mark.parametrize("beta_range", [(1.0, 2.0), (1.9, 2.0), (0.0, 1.0)])
@pytest.mark.parametrize("form", ["walk", "chunk", "recurrent"])
def test_the_three_forms_of_kda_are_the_recurrence_at_beta_up_to_two(
        form, beta_range):
    """``I - beta k k^T`` has the eigenvalue ``1 - beta`` along ``k``: in
    (-1, 0) for beta in (1, 2), where the in-chunk solve ``(I + diag(beta)
    A)^-1`` meets a matrix it never meets at beta <= 1. Live and dead rows
    mixed: a slot of a chunk and six rows, a slot of one row, an idle
    slot; the kernel at the published head width 128 with 16 heads (two
    head groups of 8), interpreted."""
    walk = form == "walk"
    b, t, h, k = 3, 70, (16 if walk else 3), (128 if walk else 16)
    rng = np.random.default_rng(12)
    q, kk, v, g, beta = _kda_inputs(rng, *beta_range, b, t, h, k)
    s0 = jnp.asarray(rng.normal(size=(b, h, k, k)), jnp.float32)
    q_lens = jnp.asarray([70, 1, 0], jnp.int32)
    live = jnp.arange(t)[None, :] < q_lens[:, None]
    gm = jnp.where(live[..., None, None], g, 0.0)
    bm = jnp.where(live[..., None], beta, 0.0)
    if walk:
        assert kda_chunk_walk.serves(k, k)
        assert h // kda_chunk_walk.heads_per_step(h) == 2
        lens = jnp.asarray([5, 9, 7], jnp.int32)
        o, s = kda_chunk_walk.kda_chunk_walk(q, kk, v, g, beta, s0, q_lens,
                                             lens)
    elif form == "chunk":
        o, s = kda.kda_chunk(q, kk, v, gm, bm, s0)
    else:
        o, s = kda.kda_recurrent(q, kk, v, gm, bm, s0)
    # the token recurrence of the reference, from the same state, on the
    # live rows alone
    for slot, n in enumerate([70, 1]):
        def step(S, xs):
            qt, kt, vt, gt, bt = xs
            S = S * jnp.exp(gt)[:, :, None]
            u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt,
                                               precision=R.HI))
            S = S + kt[:, :, None] * u[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, qt, precision=R.HI)
        s_ref, o_ref = jax.lax.scan(step, s0[slot], tuple(
            a[slot, :n] for a in (q, kk, v, g, beta)))
        scale = max(float(jnp.abs(o_ref).max()), 1.0)
        assert float(jnp.abs(o[slot, :n] - o_ref).max()) < 2e-5 * scale
        assert float(jnp.abs(s[slot] - s_ref).max()) < 2e-5 * max(
            float(jnp.abs(s_ref).max()), 1.0)
    np.testing.assert_array_equal(s[2], s0[2])        # idle: untouched


def test_the_reference_scan_at_beta_two_flips_the_state_along_k():
    """beta = 2 is a reflection: with no decay, writing the same unit key
    twice with v = 0 restores the state (eigenvalue -1 squared)."""
    rng = np.random.default_rng(2)
    k = rng.normal(size=(1, 16))
    k /= np.linalg.norm(k)
    kk = jnp.asarray(np.repeat(k[None], 2, 0), jnp.float32)    # [2, 1, 16]
    zeros = jnp.zeros((2, 1, 16), jnp.float32)
    beta = jnp.full((2, 1), 2.0, jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(1, 1, 16, 16)), jnp.float32)
    _, s = kda.kda_recurrent(kk[None], kk[None], zeros[None], zeros[None],
                             beta[None], s0)
    np.testing.assert_allclose(s, s0, atol=1e-5)


@pytest.mark.parametrize("q_lens", [[32, 1, 0, 7], [0, 0, 0, 0],
                                    [1, 1, 1, 1], [0, 2, 30, 3]])
def test_the_convolution_on_packed_rows_is_the_per_slot_convolution(q_lens):
    """A mixed step's short convolution runs on the packed rows
    (``kda.causal_conv_packed``): the same outputs on the live rows and
    the same new tails as ``causal_conv`` on the per-slot view, for a
    slot of a whole chunk, of one row, of fewer rows than taps, and an
    idle one (its tail untouched)."""
    rng = np.random.default_rng(5)
    b, s, d, taps = 4, 32, 24, 4
    q = jnp.asarray(q_lens, jnp.int32)
    lens = jnp.asarray([0, 9, 4, 17], jnp.int32)
    rows = CL.RowMap(q, lens, 48, s)
    x = jnp.asarray(rng.normal(size=(48, d)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(b, taps - 1, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, d)), jnp.float32)
    y, new_tail = kda.causal_conv_packed(x, tail, w, rows)
    want, want_tail = kda.causal_conv(rows.to_slots(x), tail, w, q)
    np.testing.assert_array_equal(new_tail, want_tail)
    live = np.asarray(rows.live)
    np.testing.assert_allclose(np.asarray(y)[live],
                               np.asarray(rows.from_slots(want))[live],
                               rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(y)).all()
    for slot, n in enumerate(q_lens):
        if n == 0:
            np.testing.assert_array_equal(new_tail[slot], tail[slot])


def test_the_append_kernel_serves_every_kv_head_a_step_at_the_cells_group():
    """At the cell's shapes (a group of 8 over a 512-row chunk: 4,096 rows
    a kv head) the append kernel's VMEM plan holds all 8 kv heads a grid
    step, so a row tile's update interleaves eight chains; the cells of
    the llama family's models already did (and still do)."""
    g, s, d, bs = 64 // 8, 512, 128, 64
    assert paged_attention._heads_per_step(8, g, s, d, bs, d, 2, 2, 2) == 8
    planned = paged_attention._append_vmem_bytes(8, g, s, d, bs, d, 2, 2, 2)
    assert planned <= paged_attention._APPEND_VMEM_BUDGET
    assert planned + (16 << 20) < 128 << 20        # a v5e core's VMEM
    # doc_batch (group 4, chunk 256) and the looped cell (group 1)
    assert paged_attention._heads_per_step(8, 4, 256, d, bs, d, 2, 2, 2) == 8
    assert paged_attention._heads_per_step(16, 1, 256, d, bs, d, 2, 2, 2) == 16


# ---- (d) PagedKV as a kind of a mixed layout ---------------------------------

def test_paged_kv_allocates_hands_out_and_takes_back_its_pools():
    kind = CL.PagedKV(2, 32, q_heads=4)
    k, v = kind.alloc(jnp.zeros, 12, 16, 3, jnp.float32)
    assert k.shape == v.shape == (12 + 1, 2, 16, 32)    # + the scratch block
    tables = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    lens = jnp.asarray([5, 0, 17], jnp.int32)
    rows = CL.RowMap(jnp.asarray([1, 32, 1]), lens, 48, 32)
    cache = kind.cache(k, v, tables, lens, jnp.asarray([1, 32, 1]), None, 40,
                       rows)
    assert isinstance(cache, PagedKVCache)
    assert cache.row_budget == 40 and cache.rows is rows
    a, b = kind.unpack(cache)
    assert a is k and b is v
    # a one-token step: q_lens says which slots hold a live row
    one = kind.cache(k, v, tables, lens, None, jnp.asarray([True, False,
                                                            True]), 3)
    np.testing.assert_array_equal(one.q_lens, [1, 0, 1])
    assert one.rows is None and one.row_budget == 3
    # the looped kind at R = 1 allocates what the general kind allocates
    lk, lv = CL.LoopedPagedKV(2, 32, 1).alloc(jnp.zeros, 12, 16, 3,
                                              jnp.float32)
    assert lk.shape == k.shape and lv.shape == v.shape


def test_a_kv_layer_beside_recurrent_layers_builds_and_is_sized_by_kind():
    model, _ = build(TOY, 1)
    eng = LLMEngine(model, **ENGINE)
    nb = eng.n_blocks
    lay = eng._layout
    assert not lay.plain_kv and lay.has_paged and lay.has_recurrent
    assert eng._k[0].shape == eng._v[0].shape == (nb + 1, 2, 16, 32)
    for layer in (1, 2, 3):
        assert eng._k[layer]["S"].shape == (3, 4, 16, 16)
        assert eng._k[layer]["S"].dtype == jnp.float32
        assert eng._k[layer]["conv"].shape == (3, 3, 3 * 64)
        assert eng._v[layer] is None
    # the pool's bytes are the ONE K/V layer's: the states are in no block
    assert eng.kv_pool_nbytes() == 2 * (nb + 1) * 2 * 16 * 32 * 4
    assert eng.kv_bytes_per_block() == 2 * 2 * 16 * 32 * 4


def test_a_two_layer_layout_of_both_kinds_serves():
    """The smallest mixed layout, [PagedKV, Recurrent]."""
    model, _ = build(dict(TOY, num_hidden_layers=2), 5)
    assert [k.kind for k in model.cache_layout()] == ["paged_kv",
                                                      "recurrent"]
    rng = np.random.default_rng(1)
    done, eng = _serve(model, {
        0: [(rng.integers(1, 256, size=40).astype(np.int32), 6)]})
    out = R.served_gaps(5, dict(TOY, num_hidden_layers=2),
                        list(done.values()), pad_to=64)
    assert np.concatenate(out["gaps"]).max() < 1e-3 * out["logit_std"]


def test_the_kv_counters_grow_by_the_kv_layers_alone(monkeypatch):
    """``kv_grid_blocks`` / ``kv_live_blocks`` count ONE K/V layer's walk
    in table entries (the latent kernel's rule is not asked), and
    ``attn_tile_steps*`` are the append kernel's own count at the K/V
    kind's group: what a layout of that one K/V layer alone would book,
    whatever the recurrent layers beside it."""
    from paddle_tpu.ops.kernels import latent_attention

    def boom(mb, bs):
        raise AssertionError("a K/V layout asked the latent kernel")
    monkeypatch.setattr(latent_attention, "entries_per_step", boom)
    calls, tiles = [], []
    real, real_tiles = LLMEngine._book_kv_grid, paged_attention \
        .append_tile_steps

    def spy(self, iterations):
        was = self.stats["kv_grid_blocks"], self.stats["kv_live_blocks"]
        real(self, iterations)
        calls.append((iterations,
                      [0 if s is None else s.sched_len() for s in self.slots],
                      self.stats["kv_grid_blocks"] - was[0],
                      self.stats["kv_live_blocks"] - was[1]))

    def spy_tiles(lens, q_lens, group, *a):
        out = real_tiles(lens, q_lens, group, *a)
        tiles.append((group, out))
        return out
    monkeypatch.setattr(LLMEngine, "_book_kv_grid", spy)
    monkeypatch.setattr(paged_attention, "append_tile_steps", spy_tiles)
    model, _ = build(TOY, 17)
    # a config whose heads would give another group: the kind's is taken
    layout = model.cache_layout()
    monkeypatch.setattr(model, "cache_layout", lambda: layout)
    model.config.num_attention_heads = 2
    rng = np.random.default_rng(9)
    done, eng = _serve(model, {
        0: [(rng.integers(1, 256, size=70).astype(np.int32), 9)],
        2: [(rng.integers(1, 256, size=45).astype(np.int32), 12)]})
    assert eng._tables.shape == (3, 12) and len(calls) > 5
    assert any(it > 1 for it, *_ in calls)
    for it, lens, grid, live in calls:
        assert grid == it * 3 * 12
        assert live == it * sum(-(-x // 16) for x in lens)
    assert tiles and all(group == 4 // 2 for group, _ in tiles)
    s = eng.stats
    assert s["attn_tile_steps"] == sum(t[0] for _, t in tiles)
    assert s["attn_tile_steps_grid"] == sum(t[1] for _, t in tiles)
    # the KDA grid is booked over the three recurrent layers' calls only
    # when the kernel serves the width; at 16 it books nothing
    assert s["kda_grid_steps"] == 0


# ---- (e) what a recurrent layer beside a K/V pool refuses -----------------

class _Store:
    pass


def _tp_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.parametrize("option,match", [
    (dict(scheduler="legacy", readout_stride=1), "StaticKVCache"),
    (dict(cache_impl="dense"), "dense slot buffers"),
    (dict(horizon=4, readout_stride=1), "horizon scan belongs"),
    (dict(enable_prefix_cache=True), "prefix hashing"),
    (dict(kv_host_swap=True), "list of pool blocks"),
    (dict(kv_host_spill_bytes=1 << 20, enable_prefix_cache=False),
     "list of pool blocks"),
    (dict(speculative_k=3), "cannot be rolled back"),
    (dict(kv_cache_dtype="int8"), "scale per"),
    (dict(adapter_store=_Store()), "LoRA"),
    (dict(mesh=_tp_mesh), "kv heads are the shard dimension"),
])
def test_an_option_a_recurrent_layer_beside_kv_pools_cannot_honour_raises(
        option, match):
    model, _ = build(TOY, 1)
    option = {k: v() if callable(v) and k == "mesh" else v
              for k, v in option.items()}
    with pytest.raises(ValueError, match=match) as err:
        LLMEngine(model, **dict(ENGINE, **option))
    # by the words of a recurrent layer beside a pool: the layout's other
    # kind is named, and K/V pools are not what is refused
    assert "['recurrent'] layers" in str(err.value)


def test_kv_shipping_and_embedding_are_refused_for_the_mixed_layout():
    model, _ = build(TOY, 1)
    eng = LLMEngine(model, **ENGINE)
    with pytest.raises(ValueError, match="not in blocks"):
        eng.add_request([1, 2, 3], export_kv=True)
    with pytest.raises(ValueError, match="not in blocks"):
        eng.export_kv(0)
    with pytest.raises(ValueError, match="not in blocks"):
        eng.import_kv({})
    with pytest.raises(ValueError, match="embed"):
        eng.add_request([1, 2, 3], kind="embed")


# ---- (f) the experts beside the GQA mixer take the step's row budget ------

def test_the_gqa_layers_experts_are_sized_by_the_row_budget(monkeypatch):
    """``SparseMoE`` sizes its grouped product by ``cache.row_budget``; the
    K/V kind's cache object carries it as the other kinds' do, so the
    experts of a GQA layer are not sized for every row live."""
    seen, real = [], moe_dropless.held_expert_ffn

    def spy(xf, idx, w, live, wg, wu, wd, offset, rows):
        seen.append((int(xf.shape[0]), int(rows)))
        return real(xf, idx, w, live, wg, wu, wd, offset, rows)
    monkeypatch.setattr(moe_dropless, "held_expert_ffn", spy)
    model, _ = build(TOY, 1)
    rng = np.random.default_rng(1)
    _serve(model, {0: [(rng.integers(1, 256, size=40).astype(np.int32), 6)]})
    budget = ENGINE["chunk_size"] + ENGINE["max_batch"] - 1     # 34
    mixed = [(n, rows) for n, rows in seen if n > ENGINE["max_batch"]]
    scan = [(n, rows) for n, rows in seen if n == ENGINE["max_batch"]]
    # every expert layer of a mixed step, the GQA layer's (the first of
    # each four) among them: the budget x top-k, not the packed height
    assert mixed and len(mixed) % 4 == 0
    assert {rows for _, rows in mixed} == {budget * 4}
    assert all(n > budget for n, _ in mixed)       # 48 packed rows
    assert scan and {rows for _, rows in scan} == {3 * 4}


@pytest.mark.parametrize("control", R.DEPARTURES)
def test_each_named_departure_of_the_reference_moves_the_logits(control):
    """The controls that name one departure each (a bfloat16 KDA state, a
    bfloat16 router, no output gate) are not the reference: each changes
    the logits, the dropped gate by far the most."""
    _, params = build(TOY, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 256, size=70))
    want = np.asarray(R.forward_logits(params, ids, TOY))
    got = np.asarray(R.forward_logits(params, ids, TOY, control))
    moved = np.abs(got - want).max()
    assert moved > (1e-2 if control == "no_gate" else 1e-5)
