"""dots3-note (``paddle_tpu/models/dots3_note.py``) against its plain
float32 reference (``benchmark/reference/dots3_note_plain.py``, the one
file of the benchmark these tests import, so that the tests' reference and
the cell's cannot drift apart), at toy widths on the CPU: ``index_topk``
16, a window of 9 in a ring of 24 rows (shorter than the longest
sequence), two full and three window layers, 8 held of 32 experts.

(a) the forward and the engine (chunked prefill, then decode, through the
latent pool with its index pool AND the rings; two slots ramping in one
packed step, the one-token step, ``multi_step`` scans, a request preempted
mid-prompt and replayed) compared as ``served_gaps`` compares, with
contexts well past ``index_topk`` and past a turn of the ring; (b) the
selected sets equal the reference's at float32, by the sort and by the
threshold search, and the compaction alone is ``flatnonzero`` a row;
(c) contexts within ``index_topk`` are dense latent attention, to the
bit; (d) ``latent_attention_append(window=w)`` against a masked dense
form, ``window=None`` unchanged; (e) the two new kinds:
what they allocate, what a token and a slot cost, the derived table and
the ring's rule; (f) the eight expert shares add up; (g) every flag the
program does not compute, and every option this layout cannot take, is
refused by name; (h) each named departure moves the logits."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.harness import loader
from benchmark.harness import weights as W
from benchmark.reference import dots3_note_plain as R
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import cache_layout as CL
from paddle_tpu.models import latent_moe as LM
from paddle_tpu.ops.kernels import latent_attention as LA
from paddle_tpu.ops.kernels import moe_dropless, paged_attention
from paddle_tpu.ops.kernels import sparse_latent_attention as DSA

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "dots3-note-prev-ep8-d5.json")) as _f:
    SHIPPED = json.load(_f)
#: the toy cut of the shipped configuration's keys (the flags and
#: ``layer_types`` stay as published; the program takes the first five)
TOY = {k: v for k, v in SHIPPED.items()
       if k not in ("engine", "server", "assumed", "deployment")}
TOY.update(
    vocab_size=96, hidden_size=32, intermediate_size=48, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    index_n_heads=2, index_head_dim=8, index_topk=16,
    swa_num_attention_heads=2, swa_num_key_value_heads=2, swa_q_lora_rank=16,
    swa_kv_lora_rank=24, swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
    swa_v_head_dim=8, sliding_window_size=9, window_ring_rows=24,
    moe_intermediate_size=16, n_routed_experts=8,
    n_routed_experts_published=32, num_experts_per_tok=4,
    max_position_embeddings=4096)
ENGINE = dict(scheduler="fused", cache_impl="paged", block_size=8,
              chunk_size=16, readout_stride=4, max_batch=3, max_seq_len=128)
#: the seeded leaves are N(0, 0.02^2): at toy widths every product would
#: vanish beside the residual, so the tests scale the projections up
GAIN = 8.0


def program():
    return loader.module("programs", "dots3_note")


def build(cfg, seed):
    """The program's model with the reference's seeded leaves (float32
    copies of the bfloat16 values, the projections times ``GAIN``);
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
        p._value = params[n] = v.astype(jnp.float32) * (
            1.0 if R.is_scale(n) else GAIN)
    return model, params


def gaps(params, done, cfg=None):
    """For each served request the gaps of its tokens' logits under the
    reference, as ``served_gaps`` takes them (here from the leaves the
    test scaled), and the reference's logit spread."""
    out, std = [], 1.0
    for prompt, toks in done.values():
        seq = np.concatenate([prompt, toks[:-1]])
        lg = R.forward_logits(params, jnp.asarray(seq), cfg or TOY)
        lg = np.asarray(lg[len(prompt) - 1:])
        out.append(lg.max(-1) - lg[np.arange(len(toks)), toks])
        std = float(lg.std())
    return np.concatenate(out), std


# ---- the shipped configuration ---------------------------------------------

def test_specs_size_and_layout_of_the_shipped_configuration():
    cfg = SHIPPED
    # 2 full mixers, 3 window mixers, the dense layer, 4 x (32 experts +
    # shared + router and its bias), an eighth of the vocabulary twice
    assert R.n_params(cfg) == 4_087_154_176
    with paddle.LazyGuard():
        model = program().build(cfg)
    assert {n: tuple(p._value.shape) for n, p in model.named_parameters()} \
        == {n: tuple(s) for n, s in R.specs(cfg)}
    kinds = model.cache_layout()
    assert [k.kind for k in kinds] == ["paged_latent_indexed"] * 2 + \
        ["windowed_latent"] * 3
    assert (kinds[0].width, kinds[0].index_width) == (576, 128)
    assert (kinds[2].width, kinds[2].window, kinds[2].ring) == \
        (1088, 513, 1024)
    layout = CL.Layout(kinds)
    # a token costs the full layers' two pools; a slot the three rings
    assert layout.bytes_per_token(2) == 2 * 1408 == 2816
    # (the abstract model's leaves are float32; the served ones bfloat16)
    assert layout.bytes_per_slot() == 3 * 1024 * 1088 * 4
    assert CL.WindowedLatent(1088, 513, 1024, jnp.bfloat16) \
        .bytes_per_slot() == 2_228_224
    assert layout.shape == "indexed_windowed" and layout.has_paged
    assert not layout.has_recurrent
    e = cfg["engine"]
    # the ring holds a window behind a whole chunk
    assert cfg["window_ring_rows"] >= cfg["sliding_window_size"] - 1 \
        + e["chunk_size"]
    assert e["max_seq_len"] % e["chunk_size"] == 0


def test_no_width_differs_from_the_published_configuration():
    with open(os.path.join(ROOT, "benchmark", "configs", "published",
                           "dots3-note-prev.json")) as f:
        published = json.load(f)["config"]
    differs = sorted(k for k, v in published.items() if SHIPPED[k] != v)
    assert differs == sorted(SHIPPED["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert SHIPPED["n_routed_experts_published"] == \
        published["n_routed_experts"]
    assert SHIPPED["vocab_size_published"] == published["vocab_size"]


@pytest.mark.parametrize("key,value", [
    ("apply_mla_qkv_lora_rescale", False), ("attention_gate_type", "none"),
    ("swa_attention_gate_type", "elementwise"), ("attention_bias", True),
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("norm_topk_prob", False), ("rope_scaling", {"type": "yarn"}),
    ("hidden_act", "gelu"), ("moe_layer_freq", 2), ("n_shared_experts", 2),
    ("tie_word_embeddings", True), ("num_key_value_heads", 1),
    ("swa_num_key_value_heads", 1)])
def test_the_program_refuses_by_name_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=f"dots3_note: {key}="):
        program().build(dict(TOY, **{key: value}))


def test_an_unknown_layer_type_and_a_short_list_are_refused():
    with pytest.raises(ValueError, match="layer_types"):
        program().build(dict(TOY, layer_types=["full_attention"] * 3))
    with pytest.raises(ValueError, match="chunked_attention"):
        program().build(dict(TOY, layer_types=["chunked_attention"] * 5))


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
    ids = np.random.default_rng(seed).integers(1, 96, size=(2, 70))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
    for b in range(2):
        want = np.asarray(R.forward_logits(params, jnp.asarray(ids[b]), TOY))
        # float32 on both sides; the forms differ (absorbed against
        # expanded heads, gathered against masked, a ring against a mask):
        # rounding only, at 70 positions = 4 x index_topk = 8 windows
        np.testing.assert_allclose(got[b], want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("control", R.DEPARTURES)
def test_each_named_departure_of_the_reference_moves_the_logits(control):
    """What this model adds to the layers it shares, and the choice the
    indexer makes, each change the logits by far more than the tolerance
    above: the comparison would catch any of them left out."""
    _, params = build(TOY, 3)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 96, size=70))
    want = np.asarray(R.forward_logits(params, ids, TOY))
    got = np.asarray(R.forward_logits(params, ids, TOY, control))
    assert np.abs(got - want).max() > 1e-2


def _serve(model, arrivals, preempt_at=None, **over):
    """Drive the engine a step at a time; ``arrivals``: {step: [(prompt,
    max_new)]}; ``preempt_at`` (step, slot): preempt that slot then.
    Returns ({rid: (prompt, tokens)}, engine)."""
    eng = LLMEngine(model, **dict(ENGINE, **over))
    eng.emitted, to = [], eng._to

    def recording(phase, **ids):     # what rides on the engine's spans
        if phase == "emit":
            eng.emitted.append(ids)
        return to(phase, **ids)
    eng._to = recording
    prompts, done, step = {}, {}, 0
    while step < 400:
        for prompt, n in arrivals.get(step, ()):
            rid = eng.add_request(list(prompt), max_new_tokens=n)
            prompts[rid] = prompt
        if preempt_at is not None and step == preempt_at[0]:
            slot = eng.slots[preempt_at[1]]
            assert slot is not None and 0 < slot.prefill_pos < \
                len(slot.req.prompt_ids)              # mid-prompt
            eng._preempt_slot(preempt_at[1])
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
                                  "preempted", "replayed"])
def test_engine_serves_what_the_reference_would(case):
    """Chunked prefill through the latent pool, the index pool and the
    rings, then decoding, compared on the gaps of the served tokens'
    logits as ``served_gaps`` compares. Every prompt but one is several
    times ``index_topk`` (16) and longer than the ring (24 rows).
    ``two_ramping``: a budget of two chunks, so two documents prefill in
    ONE mixed step beside a third's decode token. ``one_token``:
    ``readout_stride`` 1, every all-decode step the one-token program.
    ``staggered``: arrivals spread over steps, ``multi_step`` scans of
    stride 4, a slot that idles while others decode, a slot reused (its
    ring still holds the request before: nothing resets it). ``preempted``:
    a pool too small for the batch, so a request is preempted for room and
    replays from its first token. ``replayed``: a slot preempted by hand in
    the middle of its prompt."""
    seed = 17
    model, params = build(TOY, seed)
    rng = np.random.default_rng(6)

    def doc(n):
        return rng.integers(1, 96, size=n).astype(np.int32)
    if case == "two_ramping":
        arrivals = {0: [(doc(21), 20)], 2: [(doc(70), 9), (doc(61), 8)]}
        done, eng = _serve(model, arrivals, max_step_tokens=32)
        assert eng.mixed_rows == 32 < 3 * 16
        assert eng.stats["prefill_chunks"] > eng.stats["fused_steps"]
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
        assert eng.stats["multi_steps"] > 0
    elif case == "preempted":
        arrivals = {0: [(doc(90), 30), (doc(80), 30), (doc(85), 30)]}
        done, eng = _serve(model, arrivals, kv_pool_blocks=32)
        assert eng.stats["preemptions"] >= 1
    else:
        arrivals = {0: [(doc(90), 12), (doc(60), 12)]}
        done, eng = _serve(model, arrivals, preempt_at=(3, 0))
        assert eng.stats["preemptions"] == 1
    assert eng.stats["fused_steps"] > 0
    # no recurrent kind: nothing is reset, a ring's stale rows are masked
    assert eng.stats["state_resets"] == 0
    g, std = gaps(params, done)
    # float32 engine against float32 reference: a served token is the
    # reference's choice, or loses to it by rounding
    assert g.max() < 1e-3 * std
    s = eng.stats
    # the counters that left the step programs beside the tokens: rows x
    # 2 full layers; a row scores its causal context and selects at most
    # index_topk of it; a window row reads at most 9, in 3 layers
    rows = s["dsa_rows"]
    assert rows > 0 and rows % 2 == 0
    assert rows <= s["dsa_keys_selected"] <= 16 * rows
    assert s["dsa_keys_selected"] < s["dsa_keys_scored"]
    assert rows // 2 * 3 <= s["win_keys_live"] <= 9 * rows // 2 * 3
    for key, name in (("selected_keys", "dsa_keys_selected"),
                      ("scored_keys", "dsa_keys_scored"),
                      ("indexed_rows", "dsa_rows"),
                      ("window_keys", "win_keys_live"),
                      ("held_rows", "moe_assignments_held")):
        assert sum(ids.get(key, 0) for ids in eng.emitted) == s[name]
    assert s["kv_grid_blocks"] > 0 and s["pool_blocks_total"] > 0


def test_the_counters_are_in_the_stats_from_construction():
    model, _ = build(TOY, 1)
    eng = LLMEngine(model, **ENGINE)
    for name in DSA.COUNTERS + moe_dropless.COUNTERS:
        assert eng.stats[name] == 0
    assert type(model).step_counter_names == \
        moe_dropless.COUNTERS + DSA.COUNTERS
    assert set(DSA.EMIT_IDS) <= set(type(model).step_emit_ids)
    # the rings are the slots', the pools the tokens': 2 full layers of
    # (20 + 8) values and 3 rings of 24 x 28, float32 here
    assert eng._layout.bytes_per_token(4) == 2 * (20 + 8) * 4
    assert eng._layout.bytes_per_slot() == 3 * 24 * 28 * 4
    assert [tuple(a.shape) for a in eng._k] == \
        [(49, 8, 20)] * 2 + [(3 * 3 + 1, 8, 28)] * 3
    assert [None if b is None else tuple(b.shape) for b in eng._v] == \
        [(49, 8, 8)] * 2 + [None] * 3


def test_a_ring_too_short_for_a_chunk_behind_its_window_is_refused():
    model, _ = build(dict(TOY, window_ring_rows=16), 1)
    eng = LLMEngine(model, **ENGINE)
    eng.add_request(list(range(1, 40)), max_new_tokens=2)
    with pytest.raises(ValueError, match="a ring of 16 rows"):
        eng.step()
    with pytest.raises(ValueError, match="whole number of blocks"):
        LLMEngine(build(dict(TOY, window_ring_rows=28), 1)[0], **ENGINE)


# ---- (b) the selected sets ----------------------------------------------------

def _index_case(rng, t, j, di):
    qi = jnp.asarray(rng.normal(size=(t, j, di)), jnp.float32)
    ki = jnp.asarray(rng.normal(size=(t, di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(t, j)), jnp.float32)
    return qi, ki, w


@pytest.mark.parametrize("bs", [64, 50])
@pytest.mark.parametrize("form", ["per_slot", "packed", "one_token"])
def test_the_selected_sets_are_the_references(form, bs):
    """``index_scores`` over the paged index pool and ``select`` choose,
    for every row, exactly the set the reference's float32 scores and
    ``jax.lax.top_k`` choose: one sequence of 1,100 positions, ``top_k``
    48, written through a scattered table in blocks of 64 (a row is a
    whole number of the compaction's blocks of 128) and of 50 (it is
    not, and is padded with ``-inf``)."""
    rng = np.random.default_rng(8)
    t, j, di, k = 1100, 3, 16, 48
    qi, ki, w = _index_case(rng, t, j, di)
    mb = 1152 // bs                               # >= 1,150 positions a slot
    want_idx, want_ok = R.selected(R.index_scores(
        (qi, ki, w), jnp.arange(t, dtype=jnp.int32)), k)
    want = [set(np.asarray(want_idx[i])[np.asarray(want_ok[i])].tolist())
            for i in range(t)]
    # two slots hold the SAME sequence at different lengths, on a table
    # that scatters their blocks
    tables = jnp.asarray(rng.permutation(2 * mb).reshape(2, mb), jnp.int32)
    pool = jnp.zeros((2 * mb + 1, bs, di), jnp.float32)
    lens0 = jnp.zeros((2,), jnp.int32)
    full = jnp.asarray([t, t], jnp.int32)
    pool = LA.latent_pool_write(pool, jnp.stack([ki, ki]), tables, lens0,
                                full)
    with jax.default_matmul_precision("highest"):
        if form == "per_slot":
            # both slots' last 40 rows
            lens = jnp.asarray([t - 40, t - 40], jnp.int32)
            rows = DSA.Rows((2, 40), lens, jnp.asarray([40, 25], jnp.int32))
            at = np.concatenate([np.arange(t - 40, t)] * 2)
            q, ww = qi[at], w[at]
        elif form == "packed":
            # slot 0 grants 23 rows from 600, slot 1 one row at 1,000
            q_lens = jnp.asarray([23, 1], jnp.int32)
            lens = jnp.asarray([600, 1000], jnp.int32)
            rows = CL.RowMap(q_lens, lens, 32, 24)
            at = np.zeros((32,), np.int64)
            at[:23], at[23] = np.arange(600, 623), 1000
            q, ww = qi[at], w[at]
        else:
            lens = jnp.asarray([777, 5], jnp.int32)
            rows = DSA.Rows((2, 1), lens, jnp.asarray([1, 1], jnp.int32))
            at = np.asarray([777, 5])
            q, ww = qi[at], w[at]
        scores = DSA.index_scores(q, ww, pool, tables, rows)
        idx, ok = DSA.select(scores, rows, k)
    idx, ok, live = np.asarray(idx), np.asarray(ok), np.asarray(rows.live)
    assert live.sum() == {"per_slot": 65, "packed": 24, "one_token": 2}[form]
    for r in np.nonzero(live)[0]:
        assert set(idx[r][ok[r]].tolist()) == want[at[r]], (r, at[r])
    assert not ok[~live].any()
    pos = np.asarray(rows.pos)[live]
    assert np.asarray(DSA.counts(rows, k)).tolist() == [
        live.sum(), (pos + 1).sum(), np.minimum(pos + 1, k).sum(), 0]
    assert np.asarray(DSA.counts(rows, window=9)).tolist() == [
        0, 0, 0, np.minimum(pos + 1, 9).sum()]


@pytest.mark.parametrize("q_lens,lens", [
    ([48, 1, 0, 7], [100, 150, 3, 0]),       # a chunk, a decode row, idle
    ([0, 0, 0, 0], [5, 5, 5, 5]),            # nothing live
    ([1, 1, 1, 1], [191, 0, 64, 33]),        # a row a slot: one row tile
    ([30, 30, 30, 6], [0, 17, 160, 100]),    # slots share row tiles
])
def test_the_scoring_kernel_is_the_xla_form(q_lens, lens):
    """The Pallas scoring kernel (interpreted) against ``index_scores_xla``
    on every causal pair of every live row, on packed rows whose slots
    start anywhere (a row tile is computed whole a slot and masked)."""
    rng = np.random.default_rng(0)
    b, mb, bs, t, width, j, di = 4, 12, 16, 96, 48, 4, 16
    tables = jnp.asarray(rng.permutation(b * mb).reshape(b, mb), jnp.int32)
    pool = jnp.asarray(rng.normal(size=(b * mb + 1, bs, di)), jnp.float32)
    qi = jnp.asarray(rng.normal(size=(t, j, di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(t, j)), jnp.float32)
    rows = CL.RowMap(jnp.asarray(q_lens, jnp.int32),
                     jnp.asarray(lens, jnp.int32), t, width)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(DSA.index_scores_xla(qi, w, pool, tables, rows))
        got = np.asarray(DSA._scores_call(
            qi, w, pool, tables, rows.start, rows.q_lens,
            DSA._first_pos(rows), interpret=True))
    assert got.shape == want.shape == (t, mb * bs)
    live, pos = np.asarray(rows.live), np.asarray(rows.pos)
    assert live.sum() == sum(q_lens)
    for r in np.nonzero(live)[0]:
        np.testing.assert_allclose(got[r, :pos[r] + 1], want[r, :pos[r] + 1],
                                   atol=1e-5)


def test_the_scoring_kernels_walk_lists_each_slots_row_tiles():
    tile, slot, flags = DSA._work_table(
        jnp.asarray([0, 70, 70, 71], jnp.int32),
        jnp.asarray([70, 0, 1, 25], jnp.int32), 4)
    # rows 0..69 of slot 0 lie in tiles 0, 1, 2; slot 2's one row in tile
    # 2; slot 3's rows 71..95 in tile 2: five items of 4 + 4 - 1
    assert np.asarray(tile).tolist() == [0, 1, 2, 2, 2, 2, 2]
    assert np.asarray(slot).tolist() == [0, 0, 0, 2, 3, 3, 3]
    assert np.asarray(flags).tolist() == [3, 3, 3, 1, 1, 0, 0]
    _, _, flags = DSA._work_table(jnp.zeros((4,), jnp.int32),
                                  jnp.zeros((4,), jnp.int32), 4)
    assert not np.asarray(flags).any()


@pytest.mark.parametrize("width", [1024, 1000])
def test_ties_go_to_the_lower_position(width):
    """Rows of ties, a short row, and a row that is no whole number of the
    compaction's blocks of 128 (it is padded with ``-inf``); the entries a
    row has come first and in ascending order of position."""
    class Geo:
        pos = jnp.asarray([width - 1, 700, 5, width - 1], jnp.int32)
        live = jnp.ones((4,), bool)
    sc = np.zeros((4, width), np.float32)
    sc[1, ::3] = 1.0
    sc[3] = np.random.default_rng(0).integers(0, 4, size=width)
    idx, ok = DSA.select(jnp.asarray(sc), Geo, 100)
    idx, ok = np.asarray(idx), np.asarray(ok)
    assert idx.shape == (4, 100) and (idx >= 0).all() and (idx < width).all()
    assert idx[0][ok[0]].tolist() == list(range(100))
    assert idx[1][ok[1]].tolist() == list(range(0, 300, 3))
    assert idx[2][ok[2]].tolist() == list(range(6)) and ok[2][:6].all()
    top = np.lexsort((np.arange(width), -sc[3]))[:100]
    assert idx[3][ok[3]].tolist() == sorted(top)


def compact_case(case):
    """``(mask [T, S] bool, k)`` of one case of the compaction's test: the
    masks ``select`` never hands it in the tests above."""
    rng = np.random.default_rng(47)
    s, k = 1024, 100
    if case == "one_block":
        s, k = 128, 48
        sel = rng.random((5, s)) < np.asarray(
            [0.0, 0.1, 0.4, 0.9, 1.0])[:, None]
    elif case.startswith("cell_"):
        # a row of the shipped cell: 260 blocks, the 2,048 selected of them
        s, k = 33280, 2048
        sel = rng.random((6, s)) < float(case[5:])
    elif case == "empty_rows":
        sel = np.zeros((3, s), bool)
        sel[1, 517] = True                      # one True between empty rows
    elif case == "fewer_than_k":
        sel = rng.random((4, s)) < np.asarray(
            [0.01, 0.03, 0.06, 0.09])[:, None]
        assert (sel.sum(1) < k).all()
    elif case == "exactly_k":
        sel = np.zeros((4, s), bool)
        for row in sel[:3]:
            row[rng.choice(s, size=k, replace=False)] = True
        sel[3, -k:] = True                      # the last k positions
    elif case == "every_position":
        sel = np.ones((2, s), bool)
    else:
        assert case == "last_block_only"
        sel = np.zeros((3, s), bool)
        sel[0, -128:] = True                    # more than k of them
        sel[1, -128::3] = True                  # fewer
        sel[2, -1] = True
    return sel, k


@pytest.mark.parametrize("case", [
    "empty_rows", "fewer_than_k", "exactly_k", "every_position",
    "last_block_only", "one_block", "cell_0.06", "cell_0.12"])
def test_the_compaction_is_flatnonzero_a_row(case):
    """``_compact`` alone against ``numpy.flatnonzero`` a row: the first
    ``k`` True in ascending order, every entry a row has equal, and every
    entry (those past a row's count too) a position inside ``[0, S)``. The
    block's counts come by a product with a one-hot in bfloat16: exact at
    every count up to a full block of 128."""
    sel, k = compact_case(case)
    got = np.asarray(jax.jit(DSA._compact, static_argnums=1)(
        jnp.asarray(sel), k))
    assert got.shape == (sel.shape[0], k) and got.dtype == np.int32
    assert (got >= 0).all() and (got < sel.shape[1]).all()
    for row, out in zip(sel, got):
        want = np.flatnonzero(row)[:k]
        assert out[:len(want)].tolist() == want.tolist()


@pytest.mark.parametrize("q_lens,lens", [
    ([12, 1, 0, 3], [20, 40, 0, 5]),         # a chunk, a decode row, idle
    ([5, 5, 5, 5], [0, 3, 30, 43]),          # short rows: fewer than top_k
    ([1, 1, 1, 1], [9, 47, 30, 0]),          # a row a slot
    ([0, 0, 0, 0], [5, 5, 5, 5]),            # nothing live
])
def test_the_attending_kernel_is_the_xla_form(q_lens, lens):
    """The Pallas kernel that holds a slot's context in VMEM and gathers a
    row's selected entries there (interpreted) against the plain gather,
    bit for bit: the same entries, the same products in bfloat16 with
    float32 sums."""
    rng = np.random.default_rng(0)
    b, mb, bs, t, width, h, d, dv, k = 4, 6, 8, 24, 12, 4, 40, 32, 16
    tables = jnp.asarray(rng.permutation(b * mb).reshape(b, mb), jnp.int32)
    pool = jnp.asarray(rng.normal(size=(b * mb + 1, bs, d)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(t, h, d)) * 0.3, jnp.bfloat16)
    rows = CL.RowMap(jnp.asarray(q_lens, jnp.int32),
                     jnp.asarray(lens, jnp.int32), t, width)
    idx, ok = DSA.select(
        jnp.asarray(rng.normal(size=(t, mb * bs)), jnp.float32), rows, k)
    want = np.asarray(DSA.sparse_attend_xla(q, pool, tables, rows, idx, ok,
                                            dv), np.float32)
    got = np.asarray(DSA._attend_call(
        q, pool, tables, rows.slot, DSA._first_pos(rows) + rows.q_lens, idx,
        ok, dv=dv, interpret=True), np.float32)
    assert np.asarray(ok).sum() == sum(
        min(k, p + 1) for p in np.asarray(rows.pos)[np.asarray(rows.live)])
    np.testing.assert_array_equal(got, want)


# ---- (c) within index_topk the layer is dense latent attention --------------

def _latent_layers(top_k):
    """An indexed layer and a plain one with the same projections."""
    paddle.seed(5)
    args = (32, 4, 16, 8, 4, 8, 1e-5)
    kw = dict(q_rank=16, rotary=None, rescale=(1.5, 2.0), head_gate=True)
    sparse = LM.LatentAttention(*args, indexer=LM.Indexer(32, 16, 2, 8,
                                                          top_k), **kw)
    dense = LM.LatentAttention(*args, **kw)
    for (n, p), (_, q) in zip(
            [(n, p) for n, p in sparse.named_parameters()
             if "indexer" not in n], dense.named_parameters()):
        q._value = p._value
    return sparse, dense


@pytest.mark.parametrize("capacity,bitwise", [(64, True), (128, False)])
def test_contexts_within_index_topk_are_dense_latent_attention(capacity,
                                                               bitwise):
    """While ``t + 1 <= index_topk`` the selected set is every causal
    position. A table that cannot hold more than ``index_topk`` positions
    is attended whole, by the very call the plain layer makes: the output
    is the plain layer's to the bit. A larger table goes through the
    scores, the top-k and the gather even while its contexts are short:
    the same set, summed in another order."""
    sparse, dense = _latent_layers(top_k=64)
    rng = np.random.default_rng(2)
    b, s, bs = 2, 24, 8
    mb = capacity // bs
    x = paddle.to_tensor(rng.normal(size=(b, s, 32)).astype(np.float32))
    tables = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
    lens = jnp.asarray([30, 0], jnp.int32)
    q_lens = jnp.asarray([24, 17], jnp.int32)
    pool = jnp.asarray(rng.normal(size=(b * mb + 1, bs, 20)), jnp.float32)
    ipool = jnp.zeros((b * mb + 1, bs, 8), jnp.float32)
    # operation by operation, so that the two layers' shared operations
    # round alike (two compiled programs need not)
    with jax.disable_jit():
        got, c1 = sparse(x, CL.LatentPagedCache(pool, tables, lens, q_lens,
                                                index_pool=ipool))
        want, c2 = dense(x, CL.LatentPagedCache(pool, tables, lens, q_lens))
    got, want = np.asarray(got._value), np.asarray(want._value)
    if bitwise:
        assert np.array_equal(got, want)
    else:
        live = np.arange(s)[None, :] < np.asarray(q_lens)[:, None]
        assert not np.array_equal(got[live], want[live])
        np.testing.assert_allclose(got[live], want[live], atol=1e-5)
    assert np.array_equal(np.asarray(CL._val(c1.pool)),
                          np.asarray(CL._val(c2.pool)))
    # the index keys of the step's rows were written, whichever path ran
    assert np.abs(np.asarray(CL._val(c1.index_pool))).sum() > 0


def test_an_indexed_layer_and_its_cache_have_to_match():
    sparse, dense = _latent_layers(top_k=8)
    x = paddle.to_tensor(np.zeros((1, 4, 32), np.float32))
    tables = jnp.zeros((1, 4), jnp.int32)
    z = jnp.zeros((1,), jnp.int32)
    pool = jnp.zeros((5, 8, 20), jnp.float32)
    with pytest.raises(ValueError, match="index pool"):
        sparse(x, CL.LatentPagedCache(pool, tables, z, z))
    with pytest.raises(ValueError, match="index pool"):
        dense(x, CL.LatentPagedCache(pool, tables, z, z, index_pool=pool))
    with pytest.raises(ValueError, match="window"):
        dense(x, CL.LatentPagedCache(pool, tables, z, z, window=9, base=z))
    with pytest.raises(ValueError, match="indexer"):
        LM.LatentAttention(32, 4, 16, 8, 4, 8, 1e-5, q_rank=16, window=9,
                           indexer=LM.Indexer(32, 16, 2, 8, 8))


# ---- (d) the latent kernel with a window --------------------------------------

def _window_case(rng, b, s, h, d, bs, mb):
    q = jnp.asarray(rng.normal(size=(b, s, h, d)) * 0.3, jnp.float32)
    pool = jnp.asarray(rng.normal(size=(b * mb + 1, bs, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(b * mb).reshape(b, mb), jnp.int32)
    return q, pool, tables


def _masked_dense(q, pool, tables, lens, q_lens, dv, window):
    """Attention with the window written as a mask over the whole
    gathered context, independent of the kernel module's own fallback."""
    b, s, h, d = q.shape
    ctx = np.asarray(pool)[np.asarray(tables)].reshape(b, -1, d)
    out = np.zeros((b, s, h, dv), np.float32)
    for i in range(b):
        for r in range(int(q_lens[i])):
            t = int(lens[i]) + r
            lo = 0 if window is None else max(0, t - window + 1)
            k = ctx[i, lo:t + 1]
            sc = np.einsum("hd,kd->hk", np.asarray(q[i, r]), k)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[i, r] = (p / p.sum(-1, keepdims=True)) @ k[:, :dv]
    return out


@pytest.mark.parametrize("window", [None, 1, 9, 40, 1000])
@pytest.mark.parametrize("form", ["per_slot", "packed"])
def test_the_latent_kernel_with_a_window_is_the_masked_dense_form(
        monkeypatch, form, window):
    """The Pallas kernel (interpreted) with ``window=w`` against plain
    attention over the window's positions: a chunk deep in its context (the
    walk starts past wholly masked wide entries), a chunk at position 0,
    one row, an idle slot; ``window=None`` is the kernel as it was."""
    monkeypatch.setattr(paged_attention, "paged_attention_enabled",
                        lambda: True)
    rng = np.random.default_rng(11)
    b, s, h, d, dv, bs, mb = 4, 16, 4, 24, 16, 8, 12
    q, pool, tables = _window_case(rng, b, s, h, d, bs, mb)
    lens = jnp.asarray([61, 0, 40, 17], jnp.int32)
    q_lens = jnp.asarray([16, 11, 1, 0], jnp.int32)
    want = _masked_dense(q, pool, tables, lens, q_lens, dv, window)
    kw = {} if window is None else {"window": window}
    with jax.default_matmul_precision("highest"):
        if form == "per_slot":
            got = np.asarray(LA.latent_attention_append(
                q, pool, tables, lens, q_lens, dv, **kw))
        else:
            rows = CL.RowMap(q_lens, lens, 32, s)
            packed = jnp.concatenate(
                [q[i, :int(q_lens[i])] for i in range(b)]
                + [jnp.zeros((32 - 28, h, d), jnp.float32)])
            out = np.asarray(LA.latent_attention_append(
                packed, pool, tables, lens, q_lens, dv, rows, **kw))
            assert not out[28:].any()
            got = np.zeros_like(want)
            at = 0
            for i in range(b):
                got[i, :int(q_lens[i])] = out[at:at + int(q_lens[i])]
                at += int(q_lens[i])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_window_none_lowers_to_the_kernel_without_one():
    rng = np.random.default_rng(1)
    q, pool, tables = _window_case(rng, 2, 8, 4, 24, 8, 4)
    lens, q_lens = jnp.asarray([9, 0], jnp.int32), jnp.asarray([8, 3],
                                                               jnp.int32)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q, pool: LA._append_call(
            q, pool, tables, lens, q_lens, dv=16, interpret=True, **kw))(
                q, pool))
    assert text() == text(window=None)
    assert text() != text(window=5)
    # the dense fallback too
    a = LA.latent_attention_dense(q, pool, tables, lens, q_lens, 16)
    b_ = LA.latent_attention_dense(q, pool, tables, lens, q_lens, 16, None)
    assert np.array_equal(np.asarray(a), np.asarray(b_))


# ---- (e) the two kinds ---------------------------------------------------------

def test_the_windowed_kind_derives_its_table_from_the_lengths():
    kind = CL.WindowedLatent(28, 9, 24, np.float32)
    assert (kind.kind, kind.paged) == ("windowed_latent", False)
    assert kind.bytes_per_slot() == 24 * 28 * 4
    ring, none = kind.alloc(jnp.zeros, 999, 8, 3, jnp.float32)
    assert ring.shape == (3 * 3 + 1, 8, 28) and none is None
    lens = jnp.asarray([0, 30, 100], jnp.int32)
    q_lens = jnp.asarray([16, 16, 1], jnp.int32)
    c = kind.cache(ring, None, None, lens, q_lens, None, None)
    assert (c.window, c.index_pool) == (9, None)
    # slot 1: rows 30..45 attend from 22 on: blocks 2..5 of the sequence,
    # ring blocks 3 + (2, 0, 1, 2): the window's two ends share a block
    assert np.asarray(c.base).tolist() == [0, 16, 88]
    t = np.asarray(c.block_tables)
    assert t.shape == (3, 4)
    assert t[0].tolist() == [0, 1, -1, -1]
    assert t[1].tolist() == [3 + 2, 3 + 0, 3 + 1, 3 + 2]
    assert t[2].tolist() == [6 + 2, 6 + 0, -1, -1]
    a, b = kind.unpack(c)
    assert a is ring and b is None
    # a one-token step: ``active`` in place of q_lens
    c = kind.cache(ring, None, None, lens, None,
                   jnp.asarray([True, False, True]), None)
    assert np.asarray(c.q_lens).tolist() == [1, 0, 1]


def test_the_indexed_kind_holds_two_pools_on_one_table():
    kind = CL.IndexedLatent(20, 8)
    assert (kind.kind, kind.paged) == ("paged_latent_indexed", True)
    assert kind.bytes_per_token(2) == 56
    a, b = kind.alloc(jnp.zeros, 12, 8, 3, jnp.float32)
    assert a.shape == (13, 8, 20) and b.shape == (13, 8, 8)
    tables = jnp.zeros((3, 4), jnp.int32)
    z = jnp.zeros((3,), jnp.int32)
    c = kind.cache(a, b, tables, z, z, None, 7)
    assert c.index_pool is b and c.window is None and c.row_budget == 7
    assert kind.unpack(c.with_pools(a + 1, b + 2))[1] is not b
    assert kind.entries_per_step(520, 64) == 8     # 512 index keys a tile
    assert kind.entries_per_step(12, 8) == 12


def test_a_row_map_shifted_keeps_padding_at_zero():
    rows = CL.RowMap(jnp.asarray([3, 0, 2], jnp.int32),
                     jnp.asarray([40, 7, 16], jnp.int32), 8, 4)
    got = rows.shifted(jnp.asarray([32, 0, 16], jnp.int32))
    assert np.asarray(got.pos).tolist() == [8, 9, 10, 0, 1, 0, 0, 0]
    assert np.asarray(rows.pos).tolist() == [40, 41, 42, 16, 17, 0, 0, 0]
    assert got.start is rows.start and got.width == rows.width


# ---- (f) the expert layer over eight shares ----------------------------------

def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    rng = np.random.default_rng(4)
    n, h, f, e_all, k, held = 50, 32, 16, 32, 4, 4
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, e_all)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e_all,)) * 0.1, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e_all, h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e_all, f, h)) * 0.1, jnp.float32)
    sg, su = (jnp.asarray(rng.normal(size=(h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    sd = jnp.asarray(rng.normal(size=(f, h)) * 0.1, jnp.float32)
    d = dict(k=k, renorm=True, scale=1.0, off=0)
    whole = R._moe(x, (wr, bias, wg, wu, wd, sg, su, sd), d, "f32")
    idx, w = moe_dropless.route(x, wr, bias, k, 1.0)
    ref_idx, ref_w = R.route(x, wr, bias, d)
    assert np.array_equal(np.sort(idx, -1), np.sort(ref_idx, -1))
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


# ---- (g) what this layout cannot take ------------------------------------------

class _Store:
    pass


def _tp_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.parametrize("option,match", [
    (dict(scheduler="legacy", readout_stride=1), "StaticKVCache"),
    (dict(cache_impl="dense"), "dense slot buffers"),
    (dict(horizon=4, readout_stride=1), "horizon scan belongs"),
    (dict(enable_prefix_cache=True), "latents AND its index keys"),
    (dict(kv_host_swap=True), "ring a slot is in no block"),
    (dict(kv_host_spill_bytes=1 << 20, enable_prefix_cache=False),
     "ring a slot is in no block"),
    (dict(speculative_k=3), "overwritten the positions one turn back"),
    (dict(kv_cache_dtype="int8"), "an index pool and a ring"),
    (dict(adapter_store=_Store()), "LoRA"),
    (dict(mesh=_tp_mesh), "its index pool and a windowed layer's ring"),
])
def test_an_option_the_indexed_and_windowed_layout_cannot_honour_raises(
        option, match):
    model, _ = build(TOY, 1)
    option = {k: v() if callable(v) and k == "mesh" else v
              for k, v in option.items()}
    with pytest.raises(ValueError, match=match) as err:
        LLMEngine(model, **dict(ENGINE, **option))
    assert "['paged_latent_indexed', 'windowed_latent'] layers" in \
        str(err.value)


def test_kv_shipping_and_embedding_are_refused_for_this_layout():
    model, _ = build(TOY, 1)
    eng = LLMEngine(model, **ENGINE)
    with pytest.raises(ValueError, match="ring of the last positions"):
        eng.add_request([1, 2, 3], export_kv=True)
    with pytest.raises(ValueError, match="ring of the last positions"):
        eng.export_kv(0)
    with pytest.raises(ValueError, match="ring of the last positions"):
        eng.import_kv({})
    with pytest.raises(ValueError, match="embed"):
        eng.add_request([1, 2, 3], kind="embed")


def test_what_a_latent_only_layout_refuses_this_one_refuses_too():
    """Every option ``REFUSALS`` refuses a latent-only layout (by a column
    of its own or by ``beside``'s) is refused here, in this layout's
    words where it has them."""
    model, _ = build(TOY, 1)
    mine = CL.Layout(model.cache_layout())
    latent = CL.Layout([CL.PagedLatent(20)] * 2)
    asked = dict(scheduler="legacy", cache_impl="dense", horizon=4,
                 enable_prefix_cache=True, kv_host_tier=True,
                 speculative_k=3, kv_cache_dtype="int8",
                 adapter_store=object(), mesh="tp", kv_shipping="export_kv()",
                 request_kind="embed")
    for name, value in asked.items():
        with pytest.raises(ValueError) as theirs:
            latent.refuse(**{name: value})
        with pytest.raises(ValueError) as ours:
            mine.refuse(**{name: value})
        if "indexed_windowed" in CL.REFUSALS[name][2] and \
                name != "request_kind":
            assert str(ours.value) != str(theirs.value), name
    mine.refuse(kv_pool_blocks=64)      # a pool of blocks: it may be sized
