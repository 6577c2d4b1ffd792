"""Ouro, the looped decoder (``paddle_tpu/models/ouro.py``), against its
plain float32 reference (``benchmark/reference/ouro_plain.py``, the one
file of the benchmark these tests import, so that the tests' reference and
the cell's cannot drift apart), at toy widths on the CPU: 3 layers run 3
times, 4 heads on 4 K/V heads. (i) the model's plain forward and the exit
distribution, (ii) one run with the sandwich norms, (iii) the engine
(chunked prefill across two chunk boundaries, the one-token step, the
``multi_step`` scan, staggered arrivals, preemption and replay) compared
as ``served_gaps`` compares and on the carried logits, (iv) a loop step's
attention reads and writes only that step's run of blocks, (v) the loop is
a loop in the step programs, (vi) every option a looped layout refuses,
(vii) the counters, and (viii) the llama family's step programs lower to
what they lowered to before the engine learnt of loop steps."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.harness import weights as W
from benchmark.reference import ouro_plain as R
from paddle_tpu.core.tensor import Tensor, functional_mode
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import cache_layout as CL
from paddle_tpu.models import ouro as OURO
from paddle_tpu.models.llama import PagedKVCache

import test_kimi_linear as KIMI

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the toy cut of the shipped configuration's keys: L = R = 3
TOY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=160,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, total_ut_steps=3, early_exit_threshold=1,
    max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=1e6,
    rope_scaling=None, sliding_window=None, use_sliding_window=False,
    hidden_act="silu", layer_types=["full_attention"] * 3,
    tie_word_embeddings=False)
ENGINE = dict(KIMI.ENGINE)      # fused, paged, block 16, chunk 32, stride 4


def program():
    from benchmark.harness import loader
    return loader.module("programs", "ouro")


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
                           "ouro-2.6b.json")) as f:
        return json.load(f)


def test_specs_and_size_of_the_shipped_configuration():
    cfg = shipped()
    # 48 x (4 x 2048^2 + 3 x 2048 x 5632 + 4 x 2048) + 2 x 49152 x 2048
    # + the final norm + the gate and its bias
    assert R.n_params(cfg) == 2_667_974_657
    assert cfg["reduced"] == []
    with open(os.path.join(ROOT, "benchmark", "configs", "published",
                           "ouro-2.6b.json")) as f:
        published = json.load(f)["config"]
    assert {k: cfg[k] for k in published} == published
    with paddle.LazyGuard():
        model = program().build(cfg)
    assert {n: tuple(p._value.shape) for n, p in model.named_parameters()} \
        == {n: tuple(s) for n, s in R.specs(cfg)}
    layout = model.cache_layout()
    assert [k.kind for k in layout] == ["paged_kv_looped"] * 48
    assert {k.loop_steps for k in layout} == {4}
    # a token costs 192 applications x K and V x 16 heads x 128 x 2 B
    assert {k.bytes_per_token(2) for k in layout} == {1_572_864 // 48}
    assert model.step_counter_names == (
        "loop_rows", "loop_exit_mass_1", "loop_exit_mass_2",
        "loop_exit_mass_3", "loop_exit_mass_4")
    assert [n for n, _ in R.specs(cfg) if R.is_scale(n)][:4] == [
        f"model.layers.0.{n}.weight" for n in (
            "input_layernorm", "input_layernorm_2",
            "post_attention_layernorm", "post_attention_layernorm_2")]
    assert sum(R.is_scale(n) for n, _ in R.specs(cfg)) == 4 * 48 + 1


@pytest.mark.parametrize("key,value,match", [
    ("early_exit_threshold", 0.9, "adaptive exit"),
    ("hidden_act", "gelu", "hidden_act"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("sliding_window", 4096, "sliding_window"),
    ("layer_types", ["full_attention", "sliding_attention",
                     "full_attention"], "layer_types"),
    ("head_dim", 32, "head_dim"),
    ("tie_word_embeddings", True, "tie_word_embeddings")])
def test_a_value_the_program_does_not_compute_is_refused_by_name(
        key, value, match):
    with pytest.raises(ValueError, match=match):
        program().build(dict(TOY, **{key: value}))


# ---- (i) the plain forward against the reference --------------------------

@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_forward_and_exit_distribution_match_the_reference(seed):
    model, params = build(TOY, seed)
    ids = np.random.default_rng(seed).integers(1, 256, size=(2, 70))
    with paddle.no_grad(), CL.collect_counts() as counted:
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
    counts = np.asarray(sum(counted))
    masses = 0
    for b in range(2):
        want, mass = R.forward(params, jnp.asarray(ids[b]), TOY)
        np.testing.assert_allclose(got[b], want, atol=2e-5, rtol=2e-4)
        assert np.allclose(np.asarray(mass).sum(0), 1, atol=1e-6)
        masses = masses + np.asarray(mass).sum(1)
    # every row is live in a plain forward: rows x R, and the masses of
    # the steps in 1/65536ths of a row, adding up to the rows exactly
    unit = OURO.MASS_UNIT
    assert counts[0] == 2 * 70 * 3 and counts[1:].sum() == 2 * 70 * unit
    np.testing.assert_allclose(counts[1:] / unit, masses, atol=0.01)
    assert (masses > 5).all()          # no step's gate is shut


# ---- (ii) one run ---------------------------------------------------------

def test_one_run_with_the_sandwich_norms_equals_one_pass():
    cfg = dict(TOY, total_ut_steps=1)
    model, params = build(cfg, 11)
    ids = np.random.default_rng(2).integers(1, 256, size=(1, 40))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
        # the same modules called once each, no loop
        dec = model.model
        x = dec.embed_tokens(paddle.to_tensor(ids.astype(np.int32)))
        rope = (dec.rope_cos._value, dec.rope_sin._value)
        for layer in dec.layers:
            x, _ = layer(x, rope)
        once = np.asarray(model._logits(dec.norm(x))._value)
    np.testing.assert_allclose(got, once, atol=1e-6)
    want, mass = R.forward(params, jnp.asarray(ids[0]), cfg)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-4)
    assert np.allclose(mass, 1)        # one step takes the whole mass
    # and a norm left out is seen: scales are 1 + N(0, 0.1^2)
    plain = dict(params)
    plain["model.layers.1.input_layernorm_2.weight"] = jnp.ones((64,))
    off, _ = R.forward(plain, jnp.asarray(ids[0]), cfg)
    assert np.abs(np.asarray(off) - got[0]).max() > 1e-3


# ---- (iii) the engine against the reference -------------------------------

def _carried_logits_match(eng, done, params, cfg=TOY):
    """A retired slot keeps the logits it carried until it is reused: the
    distribution after its last served token, against the reference's
    full forward over prompt and served tokens."""
    carried = np.asarray(eng._logits)
    seen = 0
    for prompt, toks in done.values():
        seq = jnp.asarray(np.concatenate([prompt, toks]))
        want = np.asarray(R.forward(params, seq, cfg)[0][-1])
        err = np.abs(carried - want[None]).max(axis=1)
        seen += bool((err < 5e-4).any())
    return seen


@pytest.mark.parametrize("case", ["one_token", "multi_step", "staggered",
                                  "preempted"])
def test_engine_serves_what_the_reference_would(case):
    """Chunked prefill across two chunk boundaries (70 rows of chunk 32),
    then decode through the looped cache, compared on the gaps of the
    served tokens' logits as ``served_gaps`` compares and on the carried
    logits. ``one_token``: ``readout_stride=1``, the one-token ``step``
    program. ``multi_step``: the stride-4 scan, a loop in a loop.
    ``staggered``: arrivals spread over steps, a slot that idles while
    others decode, a slot reused (its blocks hold another request's keys
    at every loop step until they are written over). ``preempted``: a
    pool too small for the batch, so a request is preempted and replays
    from its first token."""
    seed = 17
    model, params = build(TOY, seed)
    rng = np.random.default_rng(6)

    def doc(n):
        return rng.integers(1, 256, size=n).astype(np.int32)
    over = {}
    if case == "one_token":
        arrivals, over = {0: [(doc(70), 9)]}, dict(readout_stride=1)
    elif case == "multi_step":
        arrivals = {0: [(doc(70), 11), (doc(33), 14)]}
    elif case == "staggered":
        arrivals = {0: [(doc(70), 9)], 2: [(doc(45), 12)],
                    9: [(doc(100), 6), (doc(33), 10)], 14: [(doc(5), 7)]}
    else:
        arrivals = {0: [(doc(90), 30), (doc(80), 30), (doc(85), 30)]}
        over = dict(kv_pool_blocks=16)
    done, eng = KIMI._serve(model, arrivals, **dict(ENGINE, **over))
    s = eng.stats
    assert (s["preemptions"] >= 1) == (case == "preempted")
    assert s["fused_steps"] > 0
    if case == "one_token":
        assert s["multi_steps"] == 0 and s["steps"] > s["fused_steps"]
    else:
        assert s["multi_steps"] > 0
    out = R.served_gaps(seed, TOY, list(done.values()), pad_to=64)
    gaps = np.concatenate(out["gaps"])
    # float32 engine against float32 reference: a served token is the
    # reference's choice, or loses to it by rounding
    assert gaps.max() < 1e-3 * out["logit_std"]
    assert _carried_logits_match(eng, done, params) >= min(len(done), 2)
    # the pool: R runs of (n_blocks + 1) blocks a weight layer, one table
    assert eng._k[0].shape == (3 * (eng.n_blocks + 1), 4, 16, 16)
    assert len(eng._k) == len(eng._v) == 3 and not eng._layout.plain_kv
    assert eng.kv_pool_nbytes() == 3 * 2 * 3 * (eng.n_blocks + 1) \
        * 4 * 16 * 16 * 4


# ---- (iv) a loop step's attention reads and writes its own run ------------

def test_a_loop_step_reads_and_writes_only_its_own_run_of_blocks():
    model, _ = build(TOY, 5)
    dec, kind = model.model, model.model.kind
    b, bs, mb, nb = 2, 16, 4, 8
    rng = np.random.default_rng(0)
    k0, v0 = (jnp.asarray(rng.normal(size=(3 * (nb + 1), 4, bs, 16)),
                          jnp.float32) for _ in range(2))
    tables = jnp.asarray([[0, 1, 2, -1], [5, 4, -1, -1]], jnp.int32)
    lens = jnp.asarray([37, 20], jnp.int32)
    x = jnp.asarray(rng.normal(size=(b, 1, 64)), jnp.float32)
    rope = (dec.rope_cos._value, dec.rope_sin._value)
    run = nb + 1

    def at(t, k, v):
        cache = kind.cache(k, v, tables, lens, None, jnp.ones((b,), bool),
                           b)
        with paddle.no_grad(), functional_mode():
            y, new = dec.layers[1](Tensor(x), rope, kind.at_step(cache, t),
                                   Tensor(lens))
        return (np.asarray(y._value), np.asarray(CL._val(new.k)),
                np.asarray(CL._val(new.v)))
    for t in range(3):
        y, k1, v1 = at(t, k0, v0)
        mine = slice(t * run, (t + 1) * run)
        # poison every other step's run: nothing moves
        poison = np.full(k0.shape, 1e4, np.float32)
        poison[mine] = np.asarray(k0)[mine]
        pv = np.full(v0.shape, -1e4, np.float32)
        pv[mine] = np.asarray(v0)[mine]
        y2, k2, v2 = at(t, jnp.asarray(poison), jnp.asarray(pv))
        np.testing.assert_array_equal(y, y2)
        np.testing.assert_array_equal(k1[mine], k2[mine])
        # the write lands in the step's own run, at the slots' positions
        changed = np.nonzero((k1 != np.asarray(k0)).any(axis=(1, 2, 3)))[0]
        assert set(changed) == {t * run + 2, t * run + 4}
        # and poisoning the step's OWN history does move it
        own = np.asarray(k0).copy()
        own[t * run + 1] += 1.0
        assert np.abs(at(t, jnp.asarray(own), v0)[0] - y).max() > 1e-4


def test_an_unallocated_entry_stays_unallocated_at_every_loop_step():
    kind = CL.LoopedPagedKV(4, 16, 3)
    k, v = kind.alloc(jnp.zeros, 8, 16, 2, jnp.float32)
    assert k.shape == v.shape == (27, 4, 16, 16)
    tables = jnp.asarray([[3, -1], [-1, -1]], jnp.int32)
    cache = kind.cache(k, v, tables, jnp.zeros((2,), jnp.int32), None,
                       jnp.asarray([True, False]), 2)
    assert isinstance(cache, PagedKVCache)
    np.testing.assert_array_equal(cache.q_lens, [1, 0])
    for t, want in ((0, [[3, -1], [-1, -1]]), (2, [[21, -1], [-1, -1]])):
        np.testing.assert_array_equal(kind.at_step(cache, t).block_tables,
                                      want)
    assert kind.bytes_per_token(2) == 3 * 2 * 4 * 16 * 2


# ---- (v) the loop is a loop in the step programs --------------------------

def _raw_programs(model, **over):
    """The engine's step programs as the raw ``jax.jit`` objects, by name,
    with example arguments for the mixed and the decode forms."""
    raw, orig = {}, LLMEngine._program

    def keep(self, name, fn):
        raw[name] = fn
        return orig(self, name, fn)
    LLMEngine._program = keep
    try:
        eng = LLMEngine(model, **dict(ENGINE, **over))
        eng._programs()
        eng._multi_fn(4)
    finally:
        LLMEngine._program = orig
    b, chunk = eng.B, eng.chunk

    def z(dt, *shape):
        return jnp.zeros(shape, dt)
    key, tables = jax.random.key(0), jnp.asarray(eng._tables)
    head = (eng._state_vals, eng._k, eng._v, eng._logits, eng._lens)
    mixed = head + (key, z(jnp.int32, b, chunk), z(jnp.int32, b),
                    z(bool, b), z(bool, b), z(jnp.float32, b),
                    z(jnp.float32, b), z(jnp.int32, b), tables)
    decode = head + (z(bool, b), key, z(jnp.float32, b), z(jnp.float32, b),
                     z(jnp.int32, b), z(jnp.int32, b), z(jnp.int32, b),
                     tables)
    return raw, {"fused_step": mixed, "step": decode, "multi_step": decode}


def _count(jaxpr, pred):
    """Equations of ``jaxpr`` and of every jaxpr inside it that ``pred``
    accepts: an equation inside a loop's body counts ONCE (a kernel's own
    body is not looked into)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(pred(eqn))
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += _count(sub, pred)
    return n


@pytest.mark.parametrize("name", ["fused_step", "step", "multi_step"])
def test_a_step_program_holds_every_weight_layer_once(name, monkeypatch):
    """L = 3 layers run R = 3 times: the traced step calls the attention
    kernel 3 times inside ONE loop over the loop step, not 9 times, and
    multiplies by each weight once."""
    from paddle_tpu.ops.kernels import paged_attention as PA
    monkeypatch.setattr(PA, "paged_attention_enabled", lambda: True)
    model, _ = build(TOY, 1)
    raw, args = _raw_programs(model)
    jaxpr = raw[name].trace(*args[name]).jaxpr.jaxpr

    def kernel(eqn):
        return eqn.primitive.name == "pallas_call" and \
            "paged_attention" in str(eqn.params.get("name")
                                     or eqn.params.get("name_and_src_info"))
    assert _count(jaxpr, kernel) == 3
    # seven projections a layer, the head and the gate; nothing unrolled
    dots = _count(jaxpr, lambda e: e.primitive.name == "dot_general")
    assert dots == 3 * 7 + 2
    loops = _count(jaxpr, lambda e: e.primitive.name == "scan"
                   and e.params["length"] == 3)
    assert loops == 1


# ---- (vi) what a looped layout refuses ------------------------------------

@pytest.mark.parametrize("option,named", [
    (dict(scheduler="legacy", readout_stride=1), "scheduler='legacy'"),
    (dict(cache_impl="dense"), "cache_impl='dense'"),
    (dict(horizon=4, readout_stride=1), "horizon > 1"),
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(kv_host_swap=True), "kv_host_swap"),
    (dict(kv_host_spill_bytes=1 << 20, enable_prefix_cache=False),
     "kv_host_spill_bytes"),
    (dict(speculative_k=3), "speculative_k > 1"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype"),
    (dict(adapter_store=KIMI._Store()), "adapter_store"),
    (dict(mesh=KIMI._tp_mesh), "tensor-parallel mesh"),
])
def test_an_option_a_looped_layout_cannot_honour_raises(option, named):
    model, _ = build(dict(TOY, num_hidden_layers=2,
                          layer_types=["full_attention"] * 2), 1)
    option = {k: v() if callable(v) and k == "mesh" else v
              for k, v in option.items()}
    with pytest.raises(ValueError, match="looped layout") as e:
        LLMEngine(model, **dict(ENGINE, **option))
    assert named in str(e.value)
    assert "['paged_kv_looped'] layers" in str(e.value)


def test_kv_shipping_and_embedding_are_refused_for_a_looped_layout():
    model, _ = build(dict(TOY, num_hidden_layers=2,
                          layer_types=["full_attention"] * 2), 1)
    eng = LLMEngine(model, **ENGINE)
    for name, call in (
            ("export_kv", lambda: eng.add_request([1, 2, 3],
                                                  export_kv=True)),
            ("export_kv", lambda: eng.export_kv(0)),
            ("import_kv", lambda: eng.import_kv({})),
            ("export_prefix_blocks", lambda: eng.export_prefix_blocks([])),
            ("import_prefix_blocks", lambda: eng.import_prefix_blocks([]))):
        with pytest.raises(ValueError, match="looped layout") as e:
            call()
        assert name in str(e.value)
    with pytest.raises(ValueError, match="looped layout"):
        eng.add_request([1, 2, 3], kind="embed")


# ---- (vii) the counters ---------------------------------------------------

def test_the_counters_add_up():
    model, _ = build(TOY, 17)
    rng = np.random.default_rng(8)
    arrivals = {0: [(rng.integers(1, 256, size=50).astype(np.int32), 13)],
                3: [(rng.integers(1, 256, size=21).astype(np.int32), 9)]}
    done, eng = KIMI._serve(model, arrivals, **ENGINE)
    s = eng.stats
    # every prompt token and every served token (a step samples from the
    # carried logits and feeds what it sampled) ran the stack R times
    fed = 50 + 21 + 13 + 9
    assert s["loop_rows"] == 3 * fed
    masses = [s[f"loop_exit_mass_{t}"] for t in (1, 2, 3)]
    assert sum(masses) == s["loop_rows"] // 3 * OURO.MASS_UNIT
    assert all(m > 0 for m in masses)
    # the append kernel's tile count is booked for looped K/V pools too
    # (the loop steps multiply run and grid alike)
    assert 0 < s["attn_tile_steps"] < s["attn_tile_steps_grid"]
    # the pool, a dispatch with another
    assert s["pool_blocks_total"] % eng.n_blocks == 0
    assert 0 < s["pool_blocks_used"] < s["pool_blocks_total"]
    # the all-decode iterations and the context they attended: a slot at
    # length n attends n + 1 tokens, the new one included
    assert s["decode_iterations"] > 0
    assert s["decode_rows"] <= 2 * s["decode_iterations"]
    assert s["decode_rows"] + s["fused_steps"] >= 13 + 9 - 2
    lo, hi = 21 + 1, 50 + 13
    assert lo * s["decode_rows"] <= s["decode_ctx_tokens"] \
        <= hi * s["decode_rows"]
    # the grid: every table entry of every slot, R times a dispatch
    assert s["kv_grid_blocks"] % (3 * eng._tables.size) == 0
    assert 0 < s["kv_live_blocks"] < s["kv_grid_blocks"]


# ---- (viii) the llama family's step programs are what they were -----------

#: sha256 of the lowered text (no source positions in it) and its lines,
#: read at the parent of the PR that brought the looped kind (PR 34), of a
#: tiny llama's paged step programs under this suite's settings.
#: ``multi_step`` was read again at PR 44 (fc26eaa7ede29675, 1131 before):
#: two lines more, one ``stablehlo.convert`` of ``active`` a layer in the
#: loop's body. The llama family's caches are made by ``cache_layout.PagedKV``
#: since then, which hands a one-token step's cache object the live rows a
#: slot as every kind does; no llama layer reads them, XLA drops them, and
#: the COMPILED text of all three programs is the parent's (CHANGES.md,
#: PR 44: plain, bf16, int8, int4, tp = 2, speculative).
#: ``fused_step`` was read again at PR 46 (6e881ab004d879c4, 1412 before):
#: its attention takes the packed rows as they lie (the op's packed form),
#: so the three ``rows_to_slots`` slices a layer and ``rows_from_slots``'
#: gather are gone and the CPU's dense form slices the one view of the
#: concatenated ``qkv`` out at ``cu_seqlens_q`` inside the op; ``step`` and
#: ``multi_step``, which have no row map, are what they were.
#: All three were read again at PR 49 (88aa9dbe92e86a9b, 1401;
#: 760958861cb0a471, 1102; 94ba12370fe91d78, 1133 before): thirteen lines
#: more each, ``sample_next``'s ``lax.cond`` on the step's ``temps`` (the
#: predicate and its ``any``, the ``stablehlo.case`` with its two regions'
#: returns, the greedy branch's own call of ``@argmax``) in ``main`` (in
#: ``step`` in the scan body's ``closed_call``); every other function of
#: the three modules, the decoder's 40 / 27 / 27, is the parent's text
LLAMA_PROGRAMS = {"fused_step": ("7ed2c7ca1cd1f5f0", 1414),
                  "step": ("a6932061904449e1", 1115),
                  "multi_step": ("22e3f284562418a2", 1146)}


def _llama_digests():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=2))
    model.eval()
    raw, args = _raw_programs(model, max_seq_len=128)
    out = {}
    for name in sorted(LLAMA_PROGRAMS):
        text = raw[name].lower(*args[name]).as_text()
        out[name] = (hashlib.sha256(text.encode()).hexdigest()[:16],
                     len(text.splitlines()))
    return out


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the digests are of jax 0.9.0's lowering")
def test_the_llama_step_programs_lower_to_what_the_parent_lowered():
    assert _llama_digests() == LLAMA_PROGRAMS
