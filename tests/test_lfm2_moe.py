"""LFM2-MoE (``paddle_tpu/models/lfm2_moe.py``) against its plain float32
reference (``benchmark/reference/lfm2_moe_plain.py``, the one file of the
benchmark these tests import, so that the tests' reference and the cell's
cannot drift apart), at toy widths on the CPU: (1) the forward and the
engine (prefill in chunks that end 1, 2 and 3 rows after a chunk's
boundary, then decoding through the one-token step and the ``multi_step``
scan) compared as ``served_gaps`` compares; (2) three slots' rows adjacent
on a mixed step's packed axis: no slot's convolution reads a neighbour's
rows; (3) a request preempted by hand and replayed, and a slot reused: the
tail starts from zero in the graph; (4) the routing (a bias that selects
and does not weigh, the 1e-6 under the weights, no shared expert, every
expert held, two shares that add up); (5) the tied head; (6) q and k
normed and rotated at ``RowMap.pos``; (7) ``cache_layout.Recurrent`` with a
tail and no matrix state; (8) the paged kernels at head size 64 and 32 / 8
heads, interpreted."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.harness import loader
from benchmark.harness import weights as W
from benchmark.reference import lfm2_moe_plain as R
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import cache_layout as CL
from paddle_tpu.models import lfm2_moe as M
from paddle_tpu.models.latent_moe import SparseMoE, StateCausalLM
from paddle_tpu.ops.kernels import kda, moe_dropless

import test_paged_attention as PA

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the toy cut of the shipped configuration's keys: hidden 64, 4/2 heads of
#: 16, 8 experts all held, top-2, 6 layers = 2 dense (conv, conv) + one
#: period (attention, conv, conv, conv), vocabulary 128; ``layer_types``
#: keeps more than the depth and the program takes the first six
TOY = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    num_hidden_layers=6,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv"],
    conv_L_cache=3, conv_bias=False, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    num_dense_layers=2, moe_intermediate_size=32, num_experts=8,
    num_experts_published=8, expert_offset=0, num_experts_per_tok=2,
    routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True,
    norm_eps=1e-5, max_position_embeddings=4096, tie_word_embeddings=True)


def program():
    return loader.module("programs", "lfm2_moe")


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


def shipped():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b-pp4-d10.json")) as f:
        return json.load(f)


def test_specs_size_and_layout_of_the_shipped_configuration():
    cfg = shipped()
    # 2 x 89.1 M (dense conv layers) + 8 x 604.1 M (experts) + 2 x 10.5 M
    # (attention) + 6 x 16.8 M (conv) + 134.2 M (the one embedding)
    assert R.n_params(cfg) == 5_267_090_176
    whole = dict(cfg, num_hidden_layers=40)
    assert round(R.n_params(whole) / 1e9, 2) == 23.84     # the name's 24 B
    with paddle.LazyGuard():
        model = program().build(cfg)
    assert {n: tuple(p._value.shape) for n, p in model.named_parameters()} \
        == {n: tuple(s) for n, s in R.specs(cfg)}
    layout = CL.Layout(model.cache_layout())
    assert [k.kind for k in layout] == [
        "recurrent", "recurrent", "paged_kv", "recurrent", "recurrent",
        "recurrent", "paged_kv", "recurrent", "recurrent", "recurrent"]
    assert layout.shape == "beside"
    # K and V of 8 x 64 a token in each of the two pools, 4 KiB a token,
    # two heads a pool row of 128 lanes: the kernels see 4 heads of 128
    assert layout.bytes_per_token(2) == 4096
    kv = layout.kv
    assert (kv.kv_heads, kv.head_dim, kv.q_heads) == (4, 128, 32)
    assert M.lane_pack(8, 64) == 2 and M.lane_pack(8, 128) == 1
    assert M.lane_pack(3, 32) == 3 and M.lane_pack(2, 16) == 2
    # eight tails of [2, 2048] in bfloat16... the dtype is the weights'
    shape, dt = layout.kinds[0].shapes["conv"]
    assert shape == (2, 2048) and set(layout.kinds[0].shapes) == {"conv"}
    assert layout.bytes_per_slot() == 8 * 2 * 2048 * dt.itemsize


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("norm_topk_prob", False), ("tie_word_embeddings", False),
    ("rope_parameters", dict(rope_theta=1e6, rope_type="yarn")),
    ("layer_types", ["conv", "conv", "full_attention"]),
    ("layer_types", ["conv"] * 5 + ["sliding_attention"])])
def test_the_program_refuses_by_name_what_it_does_not_compute(key, value):
    name = "rope_parameters.rope_type" if key == "rope_parameters" else key
    with pytest.raises(ValueError, match=f"lfm2_moe: {name}"):
        program().build(dict(TOY, **{key: value}))


def test_the_model_raises_on_labels_and_the_partition_stub_says_why():
    model, _ = build(TOY, 1)
    ids = paddle.to_tensor(np.ones((1, 8), np.int32))
    with pytest.raises(NotImplementedError, match="backward"):
        model(ids, labels=ids)
    with pytest.raises(NotImplementedError, match="stages over chips"):
        program().partition("model.embed_tokens.weight", "tp")


# ---- (1) the forward and the engine against the reference -----------------

@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_forward_matches_the_reference(seed):
    model, params = build(TOY, seed)
    ids = np.random.default_rng(seed).integers(1, 128, size=(2, 70))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
    for b in range(2):
        want = np.asarray(R.forward_logits(params, jnp.asarray(ids[b]), TOY))
        # float32 on both sides; the forms differ (paged against full
        # attention, sorted groups against a loop over experts): rounding
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("departure", R.DEPARTURES)
def test_a_departure_is_not_the_reference(departure):
    """What this family adds to the layers it shares moves the logits by
    far more than the tolerance above: the comparison would catch the q/k
    norms left out, or the bias weighing what it only selects."""
    _, params = build(TOY, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 128, size=70))
    want = np.asarray(R.forward_logits(params, ids, TOY))
    other = np.asarray(R.forward_logits(params, ids, TOY, departure))
    assert np.abs(other - want).max() > 1e-3


ENGINE = dict(scheduler="fused", cache_impl="paged", block_size=16,
              chunk_size=32, readout_stride=4, max_batch=3, max_seq_len=192)


def _serve(model, arrivals, preempt_at=None, snapshot=False, **over):
    """Drive the engine a step at a time; ``arrivals``: {step: [(prompt,
    max_new)]}; ``preempt_at``: the step before which the newest slot is
    preempted by hand. Returns ({rid: (prompt, tokens)}, engine);
    ``eng.carried``: the logits every slot carried after each step."""
    eng = LLMEngine(model, **dict(ENGINE, **over))
    eng.emitted, eng.dispatched, eng.carried, to = [], [], [], eng._to

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
        if step == preempt_at:
            eng._preempt_slot(max(
                (b for b, s in enumerate(eng.slots) if s is not None),
                key=lambda b: eng._admit_order[b]))
        for out in eng.step():
            done[out.request_id] = (prompts[out.request_id],
                                    np.asarray(out.token_ids, np.int32))
        if snapshot:
            eng.carried.append(np.asarray(eng._logits))
        step += 1
        if step > max(arrivals) and not eng.has_unfinished():
            break
    assert len(done) == len(prompts)
    assert eng._write_fence == {} and eng._quarantine == set()
    return done, eng


def _doc(rng, n):
    return rng.integers(1, 128, size=n).astype(np.int32)


def _gaps(seed, done):
    out = R.served_gaps(seed, TOY, list(done.values()), pad_to=64)
    return np.concatenate(out["gaps"]).max() / out["logit_std"]


@pytest.mark.parametrize("stride", [1, 4], ids=["one_token", "multi_step"])
@pytest.mark.parametrize("past", [1, 2, 3])
def test_engine_prefill_in_chunks_then_decode_is_the_reference(past, stride):
    """A prompt that ends ``past`` rows after a chunk's boundary: the last
    chunk's rows read 2, 1 and 0 rows of the tail the chunk before left,
    and leave a tail of (one old row, one new), (two new), (the last two of
    three): the tail's three cases. Then decoding, a row a step on the
    tail, through the one-token program or the ``multi_step`` scan."""
    seed = 17
    model, _ = build(TOY, seed)
    rng = np.random.default_rng(6)
    arrivals = {0: [(_doc(rng, 64 + past), 11)], 1: [(_doc(rng, 32 + past), 7)]}
    done, eng = _serve(model, arrivals, readout_stride=stride)
    assert (eng.stats["multi_steps"] > 0) == (stride > 1)
    assert eng.stats["prefill_chunks"] >= 3 + 2
    # float32 engine against float32 reference: a served token is the
    # reference's choice, or loses to it by rounding
    assert _gaps(seed, done) < 1e-3
    s = eng.stats
    # the counters that left the step programs beside the tokens, each
    # over the layers of its kind: 5 conv layers, 1 attention layer
    tokens = s["prefill_tokens"] + s["tokens_generated"]
    assert s["conv_rows"] == 5 * s["kv_rows"] and s["kv_rows"] > 0
    assert s["kv_rows"] >= tokens - len(done)
    assert 0 < s["conv_tails_live"] <= s["conv_tails_walked"]
    assert s["conv_tails_walked"] % (5 * 3) == 0
    assert s["kv_ctx_tokens"] > s["kv_rows"]
    for key, name in (("conv_rows", "conv_rows"),
                      ("conv_tails", "conv_tails_live"),
                      ("kv_rows", "kv_rows"),
                      ("kv_ctx_tokens", "kv_ctx_tokens"),
                      ("kv_slot_tokens", "kv_slot_tokens"),
                      ("held_rows", "moe_assignments_held"),
                      ("experts_read", "moe_experts_nonempty")):
        assert sum(ids.get(key, 0) for ids in eng.emitted) == s[name]
    # every expert is held: nothing routed lands elsewhere, nothing dropped
    assert s["moe_assignments_held"] == s["moe_assignments"] > 0
    assert s["moe_assignments_dropped"] == 0
    assert s["moe_rows_held"] * 2 == s["moe_assignments"]


def test_kv_ctx_tokens_is_the_sum_of_the_contexts_attended():
    """One request alone: row ``p`` of the sequence attends ``p + 1``
    positions, whatever step computes it."""
    model, _ = build(TOY, 5)
    rng = np.random.default_rng(2)
    done, eng = _serve(model, {0: [(_doc(rng, 45), 9)]})
    # every token but the last sampled is computed on; a decode scan may
    # run the row of a token it then finds over the budget
    rows = eng.stats["kv_rows"]
    assert 45 + 9 - 1 <= rows <= 45 + 9
    assert eng.stats["kv_ctx_tokens"] == rows * (rows + 1) // 2
    # a slot's context once a step: two chunks, then a row a step
    assert eng.stats["kv_slot_tokens"] == 32 + 45 + sum(range(46, rows + 1))
    assert eng.stats["conv_rows"] == 5 * rows


# ---- (2) three slots adjacent on the packed axis --------------------------

def test_no_slot_reads_a_neighbours_rows_on_the_packed_axis():
    """Three prompts prefill in ONE mixed step, their rows back to back on
    the packed axis. Another prompt in the middle slot leaves the logits
    the outer slots carry bit for bit what they were, step after step: no
    convolution tap, no tail and no attention row reaches into a
    neighbour's rows."""
    model, _ = build(TOY, 9)
    rng = np.random.default_rng(8)
    a, b, c = _doc(rng, 21), _doc(rng, 30), _doc(rng, 17)
    other = (b + 5) % 127 + 1
    runs = []
    for mid in (b, other):
        arrivals = {0: [(a, 6), (mid, 6), (c, 6)]}
        done, eng = _serve(model, arrivals, snapshot=True,
                           max_step_tokens=96)
        first = eng.dispatched[0]
        assert first["kind"] == 1 and first["prefill_rows"] == 21 + 30 + 17
        runs.append((done, np.stack(eng.carried)))
    (d0, l0), (d1, l1) = runs
    assert l0.shape == l1.shape
    np.testing.assert_array_equal(l0[:, 0], l1[:, 0])
    np.testing.assert_array_equal(l0[:, 2], l1[:, 2])
    assert np.abs(l0[:, 1] - l1[:, 1]).max() > 1e-3
    for rid in (0, 2):
        np.testing.assert_array_equal(d0[rid][1], d1[rid][1])


def test_the_packed_convolution_is_the_per_slot_convolution():
    """``Lfm2ShortConv`` on a packed ``[1, T]`` against the same rows a
    slot at a time from the same tails: the same outputs on live rows, the
    same new tails, for grants of 0, 1, 2 and many rows."""
    h, taps = 32, 3
    layer = M.Lfm2ShortConv(h, taps)
    rng = np.random.default_rng(0)
    q_lens = jnp.asarray([5, 0, 1, 2], jnp.int32)
    lens = jnp.asarray([0, 7, 9, 3], jnp.int32)
    tails = jnp.asarray(rng.normal(size=(4, taps - 1, h)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 16, h)), jnp.float32)
    rows = CL.RowMap(q_lens, lens, 16, 8)
    out, cache = layer(paddle.to_tensor(x), CL.RecurrentCache(
        {"conv": tails}, lens, q_lens, None, rows))
    out, new_tail = np.asarray(out._value)[0], np.asarray(
        CL._val(cache.state["conv"]))
    start = np.asarray(rows.start)
    for b, n in enumerate(np.asarray(q_lens)):
        if n == 0:
            # nothing granted: the tail stays (but for the reset at 0)
            np.testing.assert_array_equal(new_tail[b], tails[b])
            continue
        xb = x[:, start[b]:start[b] + n]
        ob, cb = layer(paddle.to_tensor(xb), CL.RecurrentCache(
            {"conv": tails[b:b + 1]}, lens[b:b + 1],
            jnp.asarray([n], jnp.int32)))
        np.testing.assert_allclose(out[start[b]:start[b] + n],
                                   np.asarray(ob._value)[0], atol=1e-6)
        np.testing.assert_array_equal(
            new_tail[b], np.asarray(CL._val(cb.state["conv"]))[0])
    # slot 0 stood at position 0: its tail was taken as zero, not as given
    zero = layer(paddle.to_tensor(x[:, :5]), CL.RecurrentCache(
        {"conv": jnp.zeros((1, taps - 1, h))}, lens[:1], q_lens[:1]))[0]
    np.testing.assert_allclose(out[:5], np.asarray(zero._value)[0],
                               atol=1e-6)


# ---- (3) preemption, replay and a slot reused ------------------------------

def test_a_preempted_request_replays_from_a_zero_tail():
    """A request preempted by hand while it decodes goes back to the
    queue, its tokens joined to its prompt; its slot's length is set to 0,
    which IS the tail's reset (the layer takes a slot at position 0 from
    zeros in the graph), and the replay serves what the reference would."""
    seed = 23
    model, _ = build(TOY, seed)
    rng = np.random.default_rng(4)
    arrivals = {0: [(_doc(rng, 40), 14)], 1: [(_doc(rng, 35), 12)]}
    done, eng = _serve(model, arrivals, preempt_at=6)
    assert eng.stats["preemptions"] == 1
    assert eng.stats["state_resets"] == 2 + 1
    assert _gaps(seed, done) < 1e-3


def test_a_finished_slots_tail_does_not_reach_the_next_request():
    """One slot, three requests one after the other: each starts on the
    tail the one before left, which the graph zeroes at position 0."""
    seed = 29
    model, _ = build(TOY, seed)
    rng = np.random.default_rng(5)
    arrivals = {0: [(_doc(rng, 33), 5), (_doc(rng, 20), 6),
                    (_doc(rng, 47), 4)]}
    done, eng = _serve(model, arrivals, max_batch=1)
    assert eng.stats["state_resets"] == 3
    assert _gaps(seed, done) < 1e-3
    # and a stale tail WOULD show: the layer alone, handed one at position 5
    layer = model.model.layers[0].self_attn
    x = jnp.asarray(rng.normal(size=(1, 4, 64)), jnp.float32)
    stale = jnp.ones((1, 2, 64), jnp.float32)

    def run(lens):
        return np.asarray(layer(paddle.to_tensor(x), CL.RecurrentCache(
            {"conv": stale}, jnp.asarray([lens], jnp.int32),
            jnp.asarray([4], jnp.int32)))[0]._value)
    assert np.abs(run(5) - run(0)).max() > 1e-4


# ---- (4) the routing -------------------------------------------------------

def _moe_case(rng, n=50, h=64, f=32, e=64, k=4):
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, e)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e, h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, h)) * 0.1, jnp.float32)
    return x, wr, bias, wg, wu, wd


def test_the_bias_selects_and_does_not_weigh():
    rng = np.random.default_rng(1)
    x, wr, bias, *_ = _moe_case(rng)
    idx, w = moe_dropless.route(x, wr, bias, 4, 1.0, renorm_eps=M.RENORM_EPS)
    idx0, w0 = moe_dropless.route(x, wr, None, 4, 1.0,
                                  renorm_eps=M.RENORM_EPS)
    assert (np.sort(idx, -1) != np.sort(idx0, -1)).any()  # it moves the set
    p = np.asarray(jax.nn.sigmoid(x @ wr))
    chosen = np.take_along_axis(p, np.asarray(idx), -1)
    # the weights are the chosen experts' own scores over their sum + 1e-6
    np.testing.assert_allclose(
        w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(w).sum(-1),
        chosen.sum(-1) / (chosen.sum(-1) + 1e-6), rtol=1e-6)
    # the reference routes alike
    ridx, rw = R.route(x, wr, bias, dict(topk=4, renorm=True, scale=1.0))
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ridx, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(rw, -1), rtol=1e-5)
    # without the epsilon the weights sum to 1: what every other family has
    _, w1 = moe_dropless.route(x, wr, bias, 4, 1.0)
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 1.0, rtol=1e-6)


def test_a_shared_width_of_zero_builds_no_shared_expert():
    moe = SparseMoE(64, 32, 8, 8, 0, 2, 1.0, 0, renorm_eps=M.RENORM_EPS)
    names = [n for n, _ in moe.named_parameters()]
    assert names and not any("shared" in n for n in names)
    assert moe.routing["renorm_eps"] == M.RENORM_EPS
    with_shared = SparseMoE(64, 32, 8, 8, 0, 2, 1.0, 32)
    assert sum("shared_experts" in n
               for n, _ in with_shared.named_parameters()) == 3
    assert "renorm_eps" not in with_shared.routing
    # the layer without one gives the routed part alone
    rng = np.random.default_rng(2)
    x, wr, bias, wg, wu, wd = _moe_case(rng, e=8, k=2)
    for leaf, v in ((moe.gate.weight, wr),
                    (moe.gate.e_score_correction_bias, bias),
                    (moe.experts.gate_proj, wg), (moe.experts.up_proj, wu),
                    (moe.experts.down_proj, wd)):
        leaf._value = v
    got = np.asarray(moe(paddle.to_tensor(x[None]))._value)[0]
    d = dict(topk=2, renorm=True, scale=1.0, off=0)
    np.testing.assert_allclose(
        got, R._moe(x, (wr, bias, wg, wu, wd), d, "f32"), atol=2e-5)


def test_every_expert_held_is_the_uncut_layer_and_two_shares_add_up():
    rng = np.random.default_rng(4)
    x, wr, bias, wg, wu, wd = _moe_case(rng)
    n, k, e = x.shape[0], 4, 64
    d = dict(topk=k, renorm=True, scale=1.0, off=0)
    whole = R._moe(x, (wr, bias, wg, wu, wd), d, "f32")
    idx, w = moe_dropless.route(x, wr, bias, k, 1.0, renorm_eps=M.RENORM_EPS)
    live = jnp.ones((n,), bool)
    # 64 of 64: the row budget is rows x 4 and nothing is left out
    all_held, counts = moe_dropless.held_expert_ffn(
        x, idx, w, live, wg, wu, wd, 0, rows=n * k)
    counts = dict(zip(moe_dropless.COUNTERS, np.asarray(counts)))
    assert counts["moe_assignments_held"] == counts["moe_assignments"] \
        == n * k
    assert counts["moe_rows_held"] == n and counts["moe_experts_held"] == e
    assert counts["moe_assignments_dropped"] == 0
    np.testing.assert_allclose(all_held, whole, atol=5e-5)
    total, landed = 0.0, 0
    for off in (0, 32):                      # two chips of 32 experts
        part, c = moe_dropless.held_expert_ffn(
            x, idx, w, live, wg[off:off + 32], wu[off:off + 32],
            wd[off:off + 32], off, rows=n * k)
        np.testing.assert_allclose(part, R.routed_part(
            x, idx, w, wg[off:off + 32], wu[off:off + 32],
            wd[off:off + 32], off, "f32"), atol=2e-5)
        total = total + part
        landed += int(np.asarray(c)[1])
    assert landed == n * k        # every assignment lands on one share
    np.testing.assert_allclose(total, whole, atol=5e-5)


# ---- (5) the tied head -----------------------------------------------------

def test_the_head_is_the_embeddings_matrix():
    model, params = build(TOY, 7)
    names = list(model.state_dict())
    assert "model.embed_tokens.weight" in names
    assert not any("lm_head" in n for n in names)
    assert not hasattr(model, "lm_head")
    h = np.random.default_rng(0).normal(size=(2, 5, 64)).astype(np.float32)
    got = np.asarray(model._logits(paddle.to_tensor(h))._value)
    emb = np.asarray(params["model.embed_tokens.weight"])
    np.testing.assert_allclose(got, h @ emb.T, atol=1e-5)
    # an untied StateCausalLM (what every other family builds) keeps its own

    class Cfg:
        hidden_size, vocab_size = 8, 16
    assert hasattr(StateCausalLM(Cfg, None), "lm_head")


# ---- (6) q and k normed, then rotated at the row's position ----------------

def test_q_and_k_are_normed_then_rotated_at_the_rows_position():
    """A mixed step's packed rows at scattered positions (three slots at
    lengths 7, 0 and 20 with 3, 0 and 5 rows): each row's q and k are the
    reference's at that position of a sequence whose rows they are."""
    model, params = build(TOY, 11)
    layer = model.model.layers[2].self_attn
    lw = [params[f"model.layers.2.self_attn.{n}"] for n in R.GQA_LEAVES]
    d = R.dims(TOY)
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    q_ref, k_ref = R.qk_of(u, lw, d)                    # row t at position t
    q_lens = jnp.asarray([3, 0, 5], jnp.int32)
    lens = jnp.asarray([7, 0, 20], jnp.int32)
    rows = CL.RowMap(q_lens, lens, 16, 8)
    pos = np.asarray(rows.pos)
    assert list(pos[:8]) == [7, 8, 9, 20, 21, 22, 23, 24]
    x = u[pos][None]                                     # row t = u[pos[t]]

    class Cache:
        seq_lens = lens
    Cache.q_lens, Cache.rows = q_lens, rows
    # as the kernels take them: two K/V heads a pool row, so query head a
    # (K/V head a // 2) fills that head's half of 32 lanes, times sqrt(2),
    # and is zero in the other; k and v are the rows they are
    assert layer.pack == 2 and layer.kind().head_dim == 32
    qkv, counts = layer.project(paddle.to_tensor(x), Cache)
    qkv = np.asarray(qkv._value)[0]
    H, Hkv, D = 4, 2, 16
    assert qkv.shape == (16, H * 2 * D + 2 * Hkv * D)
    q2 = qkv[:, :H * 2 * D].reshape(16, H, 2, D)
    mine = (np.arange(H) // (H // Hkv)) % 2
    q = q2[:, np.arange(H), mine] / 2 ** 0.5
    assert not q2[:, np.arange(H), 1 - mine].any()
    k = qkv[:, H * 2 * D:(H * 2 + Hkv) * D].reshape(16, Hkv, D)
    np.testing.assert_allclose(q[:8], np.asarray(q_ref)[pos[:8]], atol=2e-5)
    np.testing.assert_allclose(k[:8], np.asarray(k_ref)[pos[:8]], atol=2e-5)
    # 8 live rows; the contexts they attend; the two live slots' contexts
    assert list(np.asarray(counts._value)) == [
        8, int((pos[:8] + 1).sum()), (7 + 3) + (20 + 5)]
    # the per-slot form rotates at seq_lens + i: the same rows
    Cache.rows = None
    Cache.q_lens = jnp.asarray([5], jnp.int32)
    Cache.seq_lens = jnp.asarray([20], jnp.int32)
    qkv2, _ = layer.project(paddle.to_tensor(u[20:25][None]), Cache)
    np.testing.assert_allclose(np.asarray(qkv2._value)[0], qkv[3:8],
                               atol=1e-6)
    # unnormed or unrotated is far from it
    far = np.asarray(R.qk_of(u, lw, d, "no_qk_norm")[0])[pos[:8]]
    assert np.abs(far - q[:8]).max() > 1e-2
    assert np.abs(np.asarray(q_ref)[0] - np.asarray(q_ref)[9]).max() > 1e-2


# ---- (7) a recurrent kind that is a tail alone ------------------------------

def test_a_recurrent_kind_with_a_tail_and_no_matrix_state():
    tail = {"conv": ((2, 64), np.float32)}
    layout = CL.Layout([CL.Recurrent(tail), CL.PagedKV(2, 16, q_heads=4),
                        CL.Recurrent(tail)])
    assert layout.shape == "beside" and layout.has_recurrent
    assert layout.bytes_per_slot() == 2 * (2 * 64 * 4)
    assert layout.bytes_per_token(4) == 2 * 2 * 16 * 4
    a, b = layout.alloc(jnp.zeros, 8, 16, 3, jnp.float32)
    assert set(a[0]) == {"conv"} and a[0]["conv"].shape == (3, 2, 64)
    assert b[0] is None and a[1].shape == (9, 2, 16, 16)
    lens = jnp.asarray([0, 4, 9], jnp.int32)
    caches = layout.caches(a, b, jnp.zeros((3, 2), jnp.int32), lens, None,
                           jnp.asarray([True, False, True]), None)
    assert isinstance(caches[0], CL.RecurrentCache)
    assert list(np.asarray(caches[0].q_lens)) == [1, 0, 1]
    a2, b2 = layout.unpack(caches)
    assert a2[0]["conv"].shape == (3, 2, 64) and b2[0] is None
    # the engine's options that a state outside blocks refuses, by the
    # kind's name
    with pytest.raises(ValueError, match="recurrent"):
        layout.refuse(enable_prefix_cache=True)


def test_the_one_row_step_masks_an_idle_slot_and_resets_a_fresh_one():
    """``causal_conv`` a row a slot, as a decode scan runs it: an idle
    slot (no live row) keeps its tail; ``multi_step`` carries the tails
    through its iterations as the one-token step leaves them."""
    rng = np.random.default_rng(1)
    tail = jnp.asarray(rng.normal(size=(3, 2, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(3, 1, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    y, new = kda.causal_conv(x, tail, w, jnp.asarray([1, 0, 1], jnp.int32))
    np.testing.assert_array_equal(new[1], tail[1])
    np.testing.assert_array_equal(new[0], jnp.stack([tail[0, 1], x[0, 0]]))
    np.testing.assert_allclose(
        y[2, 0], tail[2, 0] * w[0] + tail[2, 1] * w[1] + x[2, 0] * w[2],
        rtol=1e-6)
    seed = 31
    model, _ = build(TOY, seed)
    arrivals = {0: [(_doc(rng, 20), 18)], 3: [(_doc(rng, 9), 4)]}
    done, eng = _serve(model, arrivals, readout_stride=4)
    assert eng.stats["multi_steps"] >= 3
    assert _gaps(seed, done) < 1e-3


# ---- (8) the paged kernels at head size 64, 32 / 8 heads -------------------

@pytest.mark.parametrize("form", ["per_slot", "packed"])
def test_append_kernel_at_head_size_64(form, rng):
    """``paged_attention_append`` interpreted at this model's geometry (32
    query heads on 8 K/V heads of 64) against the dense fallback, on
    windows that start on, one past and one before a block's boundary."""
    lens, qlens = [16, 17, 7, 3], [8, 1, 5, 0]
    q, kc, vc, tables, lens, qlens, kn, vn = PA._append_case(
        rng, lens, qlens, Hq=32, Hkv=8, D=64)
    if form == "per_slot":
        PA._assert_append_parity(q, kc, vc, tables, lens, qlens, kn, vn,
                                 rtol=1e-4, atol=1e-4)
    else:
        PA._assert_packed_is_per_slot(q, kc, vc, tables, lens, qlens, kn,
                                      vn, T=16)


def test_decode_kernel_at_head_size_64(rng):
    q, kc, vc, tables, lens, knew, vnew = PA._case(
        rng, [16, 17, 7, 40], Hq=32, Hkv=8, D=64, spare_block=True)
    ref = PA._dense_oracle(q, kc, vc, tables, lens, knew, vnew)
    out, kc2, vc2 = PA._decode(q, kc, vc, tables, lens, knew, vnew)
    np.testing.assert_allclose(np.asarray(out).reshape(4, -1), ref[0],
                               rtol=1e-4, atol=1e-4)
    nb = kc.shape[0] - 1
    np.testing.assert_array_equal(np.asarray(kc2)[:nb], ref[1][:nb])
    np.testing.assert_array_equal(np.asarray(vc2)[:nb], ref[2][:nb])


@pytest.mark.parametrize("hb", [4, 2])
def test_every_head_of_an_entry_a_step_at_a_group_is_the_one_head_decode(
        hb, rng):
    """``decode_heads_a_step``: the stacked-heads decode kernel at a GROUP
    of 8 (this model's packed pools: 4 rows of two heads, 32 query heads),
    ``hb`` heads and their ``hb x 8`` query rows a grid step, against the
    one-head-a-step call the shapes alone would plan: outputs and pools,
    block boundaries, -1 tail entries, a slot with no block at all, the
    fused write and the read-only form."""
    from paddle_tpu.ops.kernels import paged_attention as P
    case = PA._case(rng, [16, 17, 7, 3, 30], Hq=32, Hkv=4, BS=8,
                    spare_block=True)
    case[3][3, :] = -1
    assert P._decode_heads_per_step(4, 8, 8, 32, 4, None) == 1
    want = {fused: PA._decode(*case, fused=fused) for fused in (True, False)}
    with P.decode_heads_a_step(hb):
        out, kc2, vc2 = PA._decode(*case)
        alone = PA._decode(*case, fused=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[True][0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(kc2), np.asarray(want[True][1]))
    np.testing.assert_array_equal(np.asarray(vc2), np.asarray(want[True][2]))
    np.testing.assert_allclose(np.asarray(alone), np.asarray(want[False]),
                               rtol=1e-6, atol=1e-6)
    # the plan ends with the body, and refuses what the kernel cannot take
    assert getattr(P._DECODE_PLAN, "value", None) is None
    with P.decode_heads_a_step(3), pytest.raises(ValueError,
                                                 match="decode_heads_a_step"):
        PA._decode(*case)
