"""DeepSeek-V2 (``paddle_tpu/models/deepseek_v2.py``) against its plain
float32 reference (``benchmark/reference/deepseek_v2_plain.py``, the one
file of the benchmark these tests import, so that the tests' reference and
the cell's cannot drift apart), at toy widths on the CPU: (i) the model's
forward, (ii) YaRN's frequencies against the formulas at the published
numbers, (iii) absorbed attention with rotated keys in the pool against
the per-head form, past the trained context too, (iv) group-limited
routing, (v) the share test of the expert layer, (vi) the engine (chunked
prefill, ``multi_step`` decode, staggered arrivals, slot reuse, preemption
and replay) compared as ``served_gaps`` compares, (vii) every option a
latent-only layout refuses, and (viii) that the layers lifted out of
``models/kimi_linear.py`` into ``models/latent_moe.py`` left the Kimi
model's traced program as it was."""
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.harness import weights as W
from benchmark.reference import deepseek_v2_plain as R
from paddle_tpu.core.tensor import functional_mode
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import cache_layout as CL
from paddle_tpu.nn import rotary
from paddle_tpu.ops.kernels import moe_dropless

import test_kimi_linear as KIMI

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED_ROPE = dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707,
                      mscale_all_dim=0.707,
                      original_max_position_embeddings=4096, type="yarn")
#: the toy cut of the shipped configuration's keys: hidden 64, 4 heads of
#: 16 + 8, query latent 24, key/value latent 32, 16 experts in 4 groups of
#: which 2 are kept, top-3, 4 held; the dense layer and two expert layers;
#: a trained context of 64 so that the toy documents run past it
TOY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=160,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000,
    rope_scaling=dict(PUBLISHED_ROPE, original_max_position_embeddings=64),
    first_k_dense_replace=1, moe_intermediate_size=32, moe_layer_freq=1,
    n_routed_experts=4, n_routed_experts_published=16, expert_offset=0,
    num_experts_per_tok=3, n_shared_experts=2, n_group=4, topk_group=2,
    routed_scaling_factor=16, norm_topk_prob=False, scoring_func="softmax",
    topk_method="group_limited_greedy", hidden_act="silu",
    attention_bias=False, rms_norm_eps=1e-6, max_position_embeddings=8192,
    tie_word_embeddings=False)


def program():
    from benchmark.harness import loader
    return loader.module("programs", "deepseek_v2")


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


def shipped():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v2-ep4-d5.json")) as f:
        return json.load(f)


def test_specs_and_size_of_the_shipped_configuration():
    cfg = shipped()
    assert R.n_params(cfg) == pytest.approx(5.16e9, rel=0.002)
    with paddle.LazyGuard():
        model = program().build(cfg)
    assert {n: tuple(p._value.shape) for n, p in model.named_parameters()} \
        == {n: tuple(s) for n, s in R.specs(cfg)}
    layout = model.cache_layout()
    assert [k.kind for k in layout] == ["paged_latent"] * 5
    # a token costs 576 values a layer, whatever the 128 heads
    assert {k.bytes_per_token(2) for k in layout} == {576 * 2}
    assert set(model.step_counter_names) >= {"moe_rows_held"}


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "sigmoid"), ("topk_method", "greedy"),
    ("rope_scaling", dict(PUBLISHED_ROPE, type="linear")),
    ("norm_topk_prob", True), ("hidden_act", "gelu")])
def test_a_value_the_program_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        program().build(dict(TOY, **{key: value}))


# ---- (i) the model's forward against the reference -----------------------

@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_forward_matches_the_reference(seed):
    model, params = build(TOY, seed)
    ids = np.random.default_rng(seed).integers(1, 256, size=(2, 150))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
    for b in range(2):
        want = np.asarray(R.forward_logits(params, jnp.asarray(ids[b]), TOY))
        # float32 on both sides; the forms differ (absorbed against
        # per-head attention, sorted groups against a loop over experts,
        # two ways of writing the rotation): rounding only
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=2e-4)


# ---- (ii) YaRN's frequencies ----------------------------------------------

def test_yarn_frequencies_at_the_published_numbers():
    rs = PUBLISHED_ROPE
    assert rotary.yarn_ramp(64, 10000.0, 4096, 32, 1) == (10, 23)
    f = rotary.yarn_inv_freq(64, 10000.0, rs["factor"], 4096, 32, 1)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    # the fast pairs as trained, the slow ones divided by the factor, a
    # straight ramp between
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    for i in range(11, 23):
        g = 1 - (i - 10) / 13.0
        assert f[i] == pytest.approx((1 - g) * plain[i] / 40 + g * plain[i],
                                     rel=1e-6)
    m = rotary.yarn_mscale(40, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    # the reference writes the same numbers out for itself
    ref_f, trig, scale = R.yarn(dict(qk_rope_head_dim=64, rope_theta=10000,
                                     rope_scaling=rs))
    np.testing.assert_allclose(ref_f, f, rtol=1e-6)
    assert trig == 1.0 and scale == pytest.approx(m * m)


def test_factor_one_is_plain_rotary():
    f = rotary.yarn_inv_freq(64, 10000.0, 1.0)
    np.testing.assert_allclose(f, 10000.0 ** (-np.arange(0, 64, 2) / 64.0),
                               rtol=1e-6)
    assert rotary.yarn_mscale(1.0, 0.707) == 1.0
    # the rotation of interleaved pairs is llama's rotate-half on the
    # de-interleaved vector: one fixed permutation apart
    from paddle_tpu.models.llama import apply_rope, precompute_rope
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 2, 64)),
                    jnp.float32)
    pos = jnp.arange(9, dtype=jnp.int32)[None] + 1000
    got = rotary.rotate_pairs(x, pos, f)
    cos, sin = precompute_rope(64, 1100)
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    want, _ = apply_rope(halves, halves, cos, sin, pos)
    # (llama's table rounds its frequencies in float32 before the
    # product with a position near 1000: angles apart by 1e-4)
    np.testing.assert_allclose(got[..., 0::2], want[..., :32], atol=5e-4)
    np.testing.assert_allclose(got[..., 1::2], want[..., 32:], atol=5e-4)


# ---- (iii) absorbed attention over rotated latents ------------------------

def test_absorbed_attention_with_rotated_keys_equals_the_per_head_reference():
    """The layer as served (compressed query, q_nope absorbed through the
    key half of W_kvb, q_pe and the shared k_pe rotated BEFORE the pool
    write, attention against the pool, the value half after) against the
    reference's per-head keys and values, prefilled in three chunks that
    end past the published trained context of 4096."""
    cfg = dict(TOY, num_hidden_layers=1, rope_scaling=PUBLISHED_ROPE)
    model, params = build(cfg, 13)
    layer = model.model.layers[0].self_attn
    d = R.dims(cfg)
    pre = "model.layers.0.self_attn."
    lw = [params[pre + n] for n in R.ATTN_LEAVES]
    t, s, bs = 4224, 1408, 64
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, t, 64)),
                    jnp.float32)
    q, k, v = R.mla_heads(x[0], lw, d, "f32")
    want = R._attention(q, k, v, d["attn_scale"], "f32").reshape(t, -1) \
        @ lw[6]
    pool = jnp.zeros((t // bs + 1, bs, 40), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(2).permutation(t // bs)[None],
                         jnp.int32)
    outs = []
    with paddle.no_grad():
        for lo in range(0, t, s):
            out, cache = layer(paddle.to_tensor(x[:, lo:lo + s]),
                               CL.LatentPagedCache(
                pool, tables, jnp.asarray([lo], jnp.int32),
                jnp.asarray([s], jnp.int32)))
            pool = cache.pool._value
            outs.append(np.asarray(out._value)[0])
    got = np.concatenate(outs)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    # positions matter: the same rows at other positions give other outputs
    with paddle.no_grad():
        moved, _ = layer(paddle.to_tensor(x[:, :s]), CL.LatentPagedCache(
            jnp.zeros_like(pool), tables, jnp.asarray([s], jnp.int32),
            jnp.asarray([s], jnp.int32)))
    assert pool.shape[-1] == 32 + 8       # (c, R k_pe): no per-head K or V
    # the pool holds ROTATED key parts: the entry's last 8 columns are the
    # reference's rotated k_pe, and not the projection as it left W_kva
    entry = np.asarray(pool)[np.asarray(tables)[0, 4200 // bs], 4200 % bs]
    np.testing.assert_allclose(entry[32:], np.asarray(k)[4200, 0, 16:],
                               atol=2e-5)
    raw = np.asarray(x[0, 4200] @ lw[3])[32:]
    assert np.abs(entry[32:] - raw).max() > 1e-2


def test_one_token_and_packed_rows_sit_at_their_positions():
    """The three step shapes rotate alike: a chunk in the per-slot [B, S]
    form, the same rows on a mixed step's packed axis (positions from
    ``RowMap.pos``, two slots back to back and padding after them), and
    one token a slot (position = ``seq_lens``)."""
    cfg = dict(TOY, num_hidden_layers=1)
    model, _ = build(cfg, 5)
    layer = model.model.layers[0].self_attn
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    tables = jnp.asarray([[3, 1, 4, 0], [2, 5, 7, 6]], jnp.int32)
    lens = jnp.asarray([70, 9], jnp.int32)
    q_lens = jnp.asarray([20, 32], jnp.int32)
    pool0 = jnp.asarray(rng.normal(size=(9, 32, 40)), jnp.float32)
    with paddle.no_grad():
        slot_out, slot_cache = layer(paddle.to_tensor(x), CL.LatentPagedCache(
            pool0, tables, lens, q_lens))
        rows = CL.RowMap(q_lens, lens, 64, 32)
        packed = jnp.zeros((1, 64, 64), jnp.float32) \
            .at[0, :20].set(x[0, :20]).at[0, 20:52].set(x[1])
        pack_out, pack_cache = layer(paddle.to_tensor(packed),
                                     CL.LatentPagedCache(
            pool0, tables, lens, q_lens, rows=rows))
    np.testing.assert_allclose(pack_out._value[0, :20], slot_out._value[0, :20],
                               atol=2e-5)
    np.testing.assert_allclose(pack_out._value[0, 20:52], slot_out._value[1],
                               atol=2e-5)
    np.testing.assert_allclose(pack_cache.pool._value, slot_cache.pool._value,
                               atol=1e-6)
    # one token a slot, after the chunk: position lens + q_lens
    nxt = jnp.asarray(rng.normal(size=(2, 1, 64)), jnp.float32)
    after = lens + q_lens
    with paddle.no_grad():
        one, _ = layer(paddle.to_tensor(nxt), CL.LatentPagedCache(
            slot_cache.pool._value, tables, after,
            jnp.asarray([1, 1], jnp.int32)))
        both = jnp.zeros((2, 32, 64), jnp.float32).at[:, :1].set(nxt)
        chunk, _ = layer(paddle.to_tensor(both), CL.LatentPagedCache(
            slot_cache.pool._value, tables, after,
            jnp.asarray([1, 1], jnp.int32)))
    np.testing.assert_allclose(one._value[:, 0], chunk._value[:, 0],
                               atol=2e-5)


# ---- (iv) group-limited routing -------------------------------------------

@pytest.mark.parametrize("q_lens", [(20, 32, 0), (1, 0, 32), (1, 1, 1)])
def test_the_layers_packed_rows_through_the_kernel_equal_the_gathered_form(
        q_lens, monkeypatch):
    """``LatentAttention.forward`` on a mixed step's packed rows with the
    Pallas kernel routed in (interpreted here) against the same call on
    the CPU's gathered form: the layer hands the kernel the row map's
    ``start`` and ``width`` and takes the packed output as it comes, so
    the live rows agree, the pools are equal bit for bit, and the packed
    axis' padding leaves the layer as ``o_proj`` of zeros."""
    from paddle_tpu.ops.kernels import latent_attention, paged_attention
    cfg = dict(TOY, num_hidden_layers=1)
    model, _ = build(cfg, 5)
    layer = model.model.layers[0].self_attn
    rng = np.random.default_rng(4)
    tables = jnp.asarray([[3, 1, 4, 0], [2, 5, 7, 6], [8, 9, 10, 11]],
                         jnp.int32)
    lens = jnp.asarray([70, 9, 40], jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    pool0 = jnp.asarray(rng.normal(size=(13, 32, 40)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 64, 64)), jnp.float32)
    rows = CL.RowMap(q_lens, lens, 64, 32)

    def run():
        with paddle.no_grad():
            out, cache = layer(paddle.to_tensor(x), CL.LatentPagedCache(
                pool0, tables, lens, q_lens, rows=rows))
        return np.asarray(out._value[0]), np.asarray(cache.pool._value)
    want, want_pool = run()
    monkeypatch.setattr(paged_attention, "paged_attention_enabled",
                        lambda: True)
    monkeypatch.setattr(paged_attention, "_interpret", lambda: True)
    assert latent_attention.latent_attention_enabled()
    widths, kernel = [], latent_attention._append_rows
    monkeypatch.setattr(
        latent_attention, "_append_rows",
        lambda *a, **kw: (widths.append(kw["width"]), kernel(*a, **kw))[1])
    got, got_pool = run()
    assert widths == [32]
    live = np.asarray(rows.live)
    np.testing.assert_array_equal(got_pool, want_pool)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    np.testing.assert_array_equal(got[~live], want[~live])
    assert (got[~live] == 0).all()      # no bias: o_proj of zeros


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_routing_keeps_the_best_groups_and_does_not_renormalise(seed):
    rng = np.random.default_rng(seed)
    n, h, e, groups, keep, k = 200, 64, 160, 8, 3, 6
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, e)) * 0.3, jnp.float32)
    idx, w = moe_dropless.route(x, wr, None, k, 16.0, renormalize=False,
                                scoring="softmax", n_group=groups,
                                topk_group=keep)
    idx, w = np.asarray(idx), np.asarray(w)
    s = np.asarray(jax.nn.softmax(x @ wr, axis=-1))
    # never more groups a row than are kept, and they are the best ones
    best = s.reshape(n, groups, -1).max(-1)
    for row in range(n):
        used = set(idx[row] // (e // groups))
        assert len(used) <= keep
        assert used <= set(np.argsort(-best[row])[:keep])
    # weights: 16 x the softmax score, as it is (they do not sum to 16)
    np.testing.assert_allclose(w, 16.0 * np.take_along_axis(s, idx, -1),
                               rtol=1e-6)
    assert (w.sum(-1) < 16.0).all() and np.median(w.sum(-1)) < 15.0
    # the reference selects the same experts with the same weights
    d = dict(groups=groups, keep=keep, topk=k, scale=16.0)
    ridx, rw = R.route(x, wr, d)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ridx, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(rw, -1), rtol=1e-6)
    # and the limit binds: unlimited routing picks from more groups
    free, _ = moe_dropless.route(x, wr, None, k, 16.0, renormalize=False,
                                 scoring="softmax")
    spread = [len(set(r // (e // groups))) for r in np.asarray(free)]
    assert max(spread) > keep


# ---- (v) the share test ---------------------------------------------------

def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    rng = np.random.default_rng(4)
    n, h, f, e_all, k = 50, 64, 32, 16, 3
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, e_all)) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e_all, h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e_all, f, h)) * 0.1, jnp.float32)
    sg, su = (jnp.asarray(rng.normal(size=(h, 2 * f)) * 0.1, jnp.float32)
              for _ in range(2))
    sd = jnp.asarray(rng.normal(size=(2 * f, h)) * 0.1, jnp.float32)
    d = dict(topk=k, scale=16.0, off=0, groups=4, keep=2)
    whole = R._moe(x, (wr, wg, wu, wd, sg, su, sd), d, "f32")
    idx, w = moe_dropless.route(x, wr, None, k, 16.0, renormalize=False,
                                scoring="softmax", n_group=4, topk_group=2)
    live = jnp.ones((n,), bool)
    total = R._swiglu(x, sg, su, sd, "f32")          # the shared expert ONCE
    held = rows_held = 0
    for off in range(0, e_all, 4):                   # a share = one group
        part, counts = moe_dropless.held_expert_ffn(
            x, idx, w, live, wg[off:off + 4], wu[off:off + 4],
            wd[off:off + 4], off, rows=n * k)
        ref_part = R.routed_part(x, idx, w, wg[off:off + 4], wu[off:off + 4],
                                 wd[off:off + 4], off, "f32")
        np.testing.assert_allclose(part, ref_part, atol=5e-5)
        total = total + part
        counts = dict(zip(moe_dropless.COUNTERS, np.asarray(counts)))
        assert counts["moe_assignments"] == n * k
        assert counts["moe_assignments_dropped"] == 0
        touched = np.any(np.asarray(idx) // 4 == off // 4, axis=1)
        assert counts["moe_rows_held"] == touched.sum() < n
        held += counts["moe_assignments_held"]
        rows_held += counts["moe_rows_held"]
    assert held == n * k          # every assignment lands on one share
    assert rows_held <= 2 * n     # a row reaches 2 of the 4 shares at most
    np.testing.assert_allclose(total, whole, atol=2e-4)


# ---- (vi) the engine against the reference --------------------------------

@pytest.mark.parametrize("case", ["staggered", "preempted"])
def test_engine_serves_what_the_reference_would(case):
    """Chunked prefill then ``multi_step`` decode over a latent-only
    layout, compared on the gaps of the served tokens' logits as
    ``served_gaps`` compares. ``staggered``: arrivals spread over steps, a
    slot that idles while others decode, a slot reused by later requests
    (its blocks hold another document's rotated latents until they are
    written over). ``preempted``: a pool too small for the batch, so a
    request is preempted and replays from its first token."""
    seed = 17
    model, _ = build(TOY, seed)
    rng = np.random.default_rng(6)

    def doc(n):
        return rng.integers(1, 256, size=n).astype(np.int32)
    if case == "staggered":
        arrivals = {0: [(doc(70), 9)], 2: [(doc(45), 12)],
                    9: [(doc(100), 6), (doc(33), 10)], 14: [(doc(5), 7)]}
        done, eng = KIMI._serve(model, arrivals)
        assert eng.stats["preemptions"] == 0
    else:
        arrivals = {0: [(doc(90), 30), (doc(80), 30), (doc(85), 30)]}
        done, eng = KIMI._serve(model, arrivals, kv_pool_blocks=16)
        assert eng.stats["preemptions"] >= 1
    s = eng.stats
    assert s["multi_steps"] > 0 and s["fused_steps"] > 0
    assert s["state_resets"] == 0          # no recurrent layer to reset
    out = R.served_gaps(seed, TOY, list(done.values()), pad_to=64)
    gaps = np.concatenate(out["gaps"])
    # float32 engine against float32 reference: a served token is the
    # reference's choice, or loses to it by rounding
    assert gaps.max() < 1e-3 * out["logit_std"]
    live = s["prefill_tokens"] + s["tokens_generated"]
    assert s["moe_assignments"] >= live * 3 * 2 - 3 * 2 * len(done) * 4
    assert 0 < s["moe_assignments_held"] <= s["moe_assignments"]
    assert s["moe_assignments_dropped"] == 0
    # a row with an assignment here has between one and top-k of them
    assert s["moe_rows_held"] <= s["moe_assignments_held"] \
        <= 3 * s["moe_rows_held"]
    # one routing group of four is held and two are kept a row: a row
    # reaches this chip less often than every other time
    assert s["moe_rows_held"] < 0.75 * s["moe_assignments"] / 3


def test_the_pools_are_latent_only_and_sized_by_the_layout():
    model, _ = build(TOY, 1)
    eng = LLMEngine(model, **KIMI.ENGINE)
    assert not eng._layout.plain_kv and not eng._layout.has_recurrent
    for layer in range(3):
        assert eng._k[layer].shape == (eng.n_blocks + 1, 16, 32 + 8)
        assert eng._v[layer] is None


# ---- (vii) what a latent-only layout refuses ------------------------------

@pytest.mark.parametrize("option,match", [
    (dict(scheduler="legacy", readout_stride=1), "StaticKVCache"),
    (dict(cache_impl="dense"), "dense slot buffers"),
    (dict(horizon=4, readout_stride=1), "horizon scan"),
    (dict(enable_prefix_cache=True), "one pool and no V"),
    (dict(kv_host_swap=True), "one pool and no V"),
    (dict(kv_host_spill_bytes=1 << 20, enable_prefix_cache=False),
     "one pool and no V"),
    (dict(speculative_k=3), "PagedKVCache alone"),
    (dict(kv_cache_dtype="int8"), "scale per"),
    (dict(adapter_store=KIMI._Store()), "LoRA"),
    (dict(mesh=KIMI._tp_mesh), "kv heads are the shard dimension"),
])
def test_an_option_a_latent_only_layout_cannot_honour_raises(option, match):
    model, _ = build(dict(TOY, num_hidden_layers=2), 1)
    option = {k: v() if callable(v) and k == "mesh" else v
              for k, v in option.items()}
    with pytest.raises(ValueError, match=match) as e:
        LLMEngine(model, **dict(KIMI.ENGINE, **option))
    assert "['paged_latent'] layers" in str(e.value)


def test_kv_shipping_and_embedding_are_refused_for_a_latent_only_layout():
    model, _ = build(dict(TOY, num_hidden_layers=2), 1)
    eng = LLMEngine(model, **KIMI.ENGINE)
    for call in (lambda: eng.add_request([1, 2, 3], export_kv=True),
                 lambda: eng.export_kv(0), lambda: eng.import_kv({}),
                 lambda: eng.export_prefix_blocks([]),
                 lambda: eng.import_prefix_blocks([])):
        with pytest.raises(ValueError, match="not in blocks of K and V"):
            call()
    with pytest.raises(ValueError, match="embed"):
        eng.add_request([1, 2, 3], kind="embed")
    with pytest.raises(ValueError, match="rope table"):
        LLMEngine(model, **dict(KIMI.ENGINE, max_seq_len=8193))


# ---- (viii) the lift left the Kimi model's program as it was --------------

def _digest(fn, *args):
    """The traced program of ``fn`` with what its outputs do not need
    taken out, as text (no source positions in it), hashed."""
    from jax._src.interpreters import partial_eval as pe
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16], \
        len(jaxpr.eqns)


#: read at the parent of the PR that lifted the layers (PR 32), on
#: ``tests/test_kimi_linear.py``'s toy model, traced as the engine traces
#: it (``functional_mode``) under this suite's settings (matmul precision
#: "highest" is part of the text): (digest, equations). The counts the
#: expert layer books are not among the outputs (that PR added one).
#: ``packed`` was read again in PR 41, which moved a mixed step's KDA
#: convolution from the per-slot view onto the packed rows
#: (``kda.causal_conv_packed``; before it: "3f818f51a25067db", 2145); the
#: two forms without a packed axis trace what they traced; and in PR 42,
#: which handed the latent pool's write and attention the packed rows as
#: they are (before it: "af32209e2d763e83", 2643), the same two again.
KIMI_PROGRAMS = {"plain": ("ec39cef70d58fe2f", 1867),
                 "packed": ("61594b51d6cc749b", 2638),
                 "one_token": ("75fe0e068be5bd85", 1474)}


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the digests are of jax 0.9.0's jaxpr text")
@pytest.mark.parametrize("form", sorted(KIMI_PROGRAMS))
def test_the_kimi_model_traces_the_program_it_traced_before_the_lift(form):
    model, _ = KIMI.build(KIMI.TOY, 3)

    def plain(ids):
        with paddle.no_grad(), functional_mode():
            return model(paddle.to_tensor(ids))._value

    def step(ids, lens, q_lens):
        b, bs, mb = 3, 16, 4
        tables = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
        rows = CL.RowMap(q_lens, lens, ids.shape[1], 32) \
            if form == "packed" else None
        layout = model.cache_layout()
        caches = []
        for kind in layout:
            a, c = kind.alloc(jnp.zeros, b * mb, bs, b, jnp.float32)
            caches.append(kind.cache(a, c, tables, lens, q_lens, None, 40,
                                     rows))
        with paddle.no_grad(), functional_mode():
            hidden, new = model.model(paddle.to_tensor(ids),
                                      kv_caches=caches)
        return [hidden._value] + [kind.unpack(c)
                                  for kind, c in zip(layout, new)]
    lens = jnp.asarray([5, 0, 17], jnp.int32)
    if form == "plain":
        got = _digest(plain, jnp.zeros((2, 40), jnp.int32))
    elif form == "packed":
        got = _digest(step, jnp.zeros((1, 48), jnp.int32), lens,
                      jnp.asarray([1, 32, 1], jnp.int32))
    else:
        got = _digest(step, jnp.zeros((3, 1), jnp.int32), lens,
                      jnp.asarray([1, 0, 1], jnp.int32))
    assert got == KIMI_PROGRAMS[form]
