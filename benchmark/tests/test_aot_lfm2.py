"""The step programs of ``lfm2_agent_turns`` compiled for the real chip at
the cell's real sizes (8 conv layers with a tail of [2, 2048] a slot, 2
attention layers of 32 query heads on 8 K/V heads of 64, two heads a pool
row of 128 lanes, 64 held experts of 2048 x 1536 in 8 layers, a tied head
over 65,536 ids; 128 slots of 2,048 positions; a mixed step of 640 packed
rows and the ``multi_step`` scan of the configuration's stride) by the TPU
compiler that is installed here, for a v5e that is described and not
attached. Nothing runs: a compile that passes is not a chip run. The
topology is described inside a fixture, all in this one file.

Also held: WHY the pools are ``[NB, 4, 64, 128]`` and not ``[NB, 8, 64,
64]``: at a minor axis of 64 the compiler lays the pool out with its block
axis minor and copies it whole into padded row-major tiles around every
call of either paged kernel (over a gigabyte of temporaries a call); at 128
lanes it does not. And the five families that share ``SparseMoE``,
``StateCausalLM`` and ``moe_dropless.route`` trace to what they traced
without the arguments this PR added."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness import loader

BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
CONFIG = "lfm2-24b-a2b-pp4-d10"
#: slots, chunk, packed rows, query heads, block, blocks a slot, the pool
#: with its scratch block
B, S, T, HQ, BS, MB = 128, 512, 640, 32, 64, 32
NB = B * MB + 1


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def engine(one_chip):
    """(engine, {program name: its raw ``jax.jit``}, {name: arguments}) of
    the cell's configuration, every leaf, pool and buffer a shape on the
    described chip (``test_aot_dots3.engine``'s way)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    cfg = loader.data("configs", CONFIG)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=one_chip)
    with paddle.LazyGuard():
        model = loader.module("programs", "lfm2_moe").build(cfg)
    model.eval()
    for _, p in model.named_parameters():
        p._value = shape(p._value.shape, BF)
    raw, mp = {}, pytest.MonkeyPatch()
    orig = LLMEngine._program
    mp.setattr(LLMEngine, "_program", lambda self, name, fn: (
        raw.__setitem__(name, fn), orig(self, name, fn))[1])
    mp.setattr(LLMEngine, "_make_zeros",
               lambda self, s, dtype, spec=None: shape(s, dtype))
    try:
        eng = LLMEngine(model, **cfg["engine"])
        eng._programs()
        eng._multi_fn(int(cfg["engine"]["readout_stride"]))
    finally:
        mp.undo()
    b, chunk = eng.B, eng.chunk
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    head = ([shape(v.shape, v.dtype) for v in eng._state_vals], eng._k,
            eng._v, eng._logits, eng._lens)
    tables = shape(eng._tables.shape, I32)
    mixed = head + (key, shape((b, chunk), I32), shape((b,), I32),
                    shape((b,), bool), shape((b,), bool), shape((b,), F32),
                    shape((b,), F32), shape((b,), I32), tables)
    decode = head + (shape((b,), bool), key, shape((b,), F32),
                     shape((b,), F32), shape((b,), I32), shape((b,), I32),
                     shape((b,), I32), tables)
    return eng, raw, {"fused_step": mixed, "multi_step": decode}


@pytest.fixture()
def mosaic(monkeypatch):
    """Route the kernels to Mosaic although this process sees a CPU, keep
    the compiles out of the persistent cache (they cannot be read back
    without a chip), and the products at the precision the chip runs."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.kernels import paged_attention
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision("default"):
        yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def compiled(fn, one_chip, *shapes, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn, donate_argnums=donate).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


def test_the_cells_sizes(engine):
    eng, _, _ = engine
    assert eng.B == B and eng.capacity == 2048 and eng.mixed_rows == T
    assert eng.max_step_tokens == S + B - 1
    kinds = [k.kind for k in eng._layout]
    assert kinds == ["recurrent"] * 2 + ["paged_kv"] + ["recurrent"] * 3 \
        + ["paged_kv"] + ["recurrent"] * 3
    pools = [tuple(a.shape) for a, k in zip(eng._k, kinds)
             if k == "paged_kv"]
    assert pools == [(NB, 4, BS, 128)] * 2       # two heads of 64 a row
    tails = [tuple(a["conv"].shape) for a, k in zip(eng._k, kinds)
             if k == "recurrent"]
    assert tails == [(B, 2, 2048)] * 8
    assert eng._layout.bytes_per_token(2) == 4096
    assert eng._layout.bytes_per_slot() == 8 * 2 * 2048 * 2      # 64 KiB
    # 128 x 2,048 tokens of 4 KiB (and the scratch block): 1.07 GB
    assert eng.kv_pool_nbytes() == NB * BS * 4096


@pytest.mark.parametrize("name", ["fused_step", "multi_step"])
def test_a_step_program_compiles_and_fits(engine, mosaic, name):
    eng, raw, args = engine
    exe = raw[name].trace(*args[name]).lower(
        lowering_platforms=("tpu",)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < loader.peaks()["devices"]["TPU v5 lite"]["memory_bytes"]
    # the weights (5,267,090,176 x 2 B), the pools and the tails lead the
    # arguments; the float32 logits a slot are 33.6 MB
    assert mem.argument_size_in_bytes == pytest.approx(
        2 * 5_267_090_176 + eng.kv_pool_nbytes()
        + B * eng._layout.bytes_per_slot() + B * 65536 * 4, rel=0.02)
    # no copy of a pool (268 MB each) among the temporaries
    assert mem.temp_size_in_bytes < 1.2e9
    assert mem.alias_size_in_bytes >= eng.kv_pool_nbytes()
    # two attention layers of one paged kernel, eight expert layers of two
    # grouped products
    assert text.count("tpu_custom_call") == 2 + 16
    assert ("paged_attention_append" if name == "fused_step"
            else "paged_attention_decode") in text
    assert "grouped_expert_matmul" in text and "ragged" not in text.lower()
    for part in ("pt.conv", "pt.qk_norm", "pt.rope", "pt.route"):
        assert part in text
    assert "pt.shared" not in text           # there is no shared expert


def pool(hkv, d):
    return ((NB, hkv, BS, d), BF)


def append_at(one_chip, hkv, d):
    from paddle_tpu.ops.kernels.paged_attention import paged_attention_append

    def step(q, k, v, tables, lens, q_lens, nk, nv, start):
        return paged_attention_append(q, k, v, tables, lens, q_lens, nk, nv,
                                      start=start, width=S)
    return compiled(
        step, one_chip, ((T, HQ, d), BF), pool(hkv, d), pool(hkv, d),
        ((B, MB), I32), ((B,), I32), ((B,), I32), ((T, hkv, d), BF),
        ((T, hkv, d), BF), ((B,), I32), donate=(1, 2))


def decode_at(one_chip, hkv, d, plan=None):
    import contextlib
    from paddle_tpu.ops.kernels import paged_attention as P

    def call(q, k, v, t, n, nk, nv):
        with contextlib.nullcontext() if plan is None \
                else P.decode_heads_a_step(plan):
            return P.paged_attention_decode(q, k, v, t, n, new_k=nk, new_v=nv)
    return compiled(
        call, one_chip, ((B, HQ, d), BF), pool(hkv, d), pool(hkv, d),
        ((B, MB), I32), ((B,), I32), ((B, hkv, d), BF), ((B, hkv, d), BF),
        donate=(1, 2))


def test_the_decode_call_takes_every_packed_head_of_an_entry_a_step(
        one_chip, mosaic):
    """``decode_heads_a_step(4)``, as ``Lfm2Attention`` traces its
    one-token call: a grid of (128 slots, 1 head group, 32 entries), 32
    query rows against 4 x 64 stacked keys a step, where the shapes' own
    rule plans 4 times the steps at one head each. Mosaic takes it."""
    import re
    from paddle_tpu.ops.kernels import paged_attention as P
    sh = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((B, HQ, 128), BF), pool(4, 128), pool(4, 128), ((B, MB), I32),
        ((B,), I32), ((B, 4, 128), BF), ((B, 4, 128), BF))]

    def call(q, k, v, t, n, nk, nv):
        return P.paged_attention_decode(q, k, v, t, n, new_k=nk, new_v=nv)
    assert re.findall(r"grid=\([^)]*\)", str(jax.make_jaxpr(call)(*sh))) \
        == [f"grid=({B}, 4, {MB})"]
    with P.decode_heads_a_step(4):
        # (another function object: a trace is cached by the function, and
        # the plan is no part of that key)
        planned = str(jax.make_jaxpr(lambda *a: call(*a))(*sh))
    assert re.findall(r"grid=\([^)]*\)", planned) == [f"grid=({B}, 1, {MB})"]
    text = decode_at(one_chip, 4, 128, plan=4).as_text()
    assert "tpu_custom_call" in text and "paged_attention_decode" in text


@pytest.mark.parametrize("call", [append_at, decode_at],
                         ids=["append", "decode"])
def test_a_pool_of_64_lanes_is_copied_whole_and_one_of_128_is_not(
        one_chip, mosaic, call):
    """Both kernels lower at a head size of 64, but the pool ``[NB, 8,
    64, 64]`` is then held with its BLOCK axis minor (least padding) and
    converted to the kernels' row-major tiles, 128 padded lanes, around
    the call: both pools, in and out. The same bytes as ``[NB, 4, 64,
    128]`` go to the kernel where they lie."""
    narrow = call(one_chip, 8, 64)
    text, mem = narrow.as_text(), narrow.memory_analysis()
    assert "tpu_custom_call" in text
    assert f"bf16[{NB},8,64,64]{{0,3,2,1:" in text
    logical = NB * 8 * 64 * 64 * 2
    assert mem.temp_size_in_bytes > 2 * 2 * 0.95 * logical   # 1.08 GB
    wide = call(one_chip, 4, 128)
    text, mem = wide.as_text(), wide.memory_analysis()
    assert "tpu_custom_call" in text
    assert f"bf16[{NB},4,64,128]{{3,2,1,0:" in text
    assert mem.temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("step,n,bound", [("mixed", T, S + B - 1),
                                          ("scan", B, B)])
def test_the_expert_layer_compiles_to_the_kernel(one_chip, mosaic, step, n,
                                                 bound):
    from paddle_tpu.ops.kernels import moe_dropless as moe
    h, f, e, k = 2048, 1536, 64, 4

    def layer(x, idx, w, live, wg, wu, wd):
        return moe.held_expert_ffn(x, idx, w, live, wg, wu, wd, 0,
                                   rows=bound * k)
    text = compiled(
        layer, one_chip, ((n, h), BF), ((n, k), I32), ((n, k), F32),
        ((n,), jnp.bool_), ((e, h, f), BF), ((e, h, f), BF),
        ((e, f, h), BF)).as_text()
    assert "grouped_expert_matmul" in text
    assert "ragged" not in text.lower()
