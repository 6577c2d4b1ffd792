"""The step programs of ``ouro_worked_answers`` compiled for the real chip
at the cell's real sizes (48 layers run 4 times, 16 query heads on 16 K/V
heads of 128, group 1; pools of 4 x 81 blocks of 64 a weight layer; a
mixed step of 272 packed rows, the one-token step and the ``multi_step``
scan of stride 4) by the TPU compiler that is installed here, for a v5e
that is described and not attached. Nothing runs: a compile that passes is
not a chip run. In ``test_aot_kimi.py``'s manner: the topology described
inside a fixture, all in this one file. A program compiles in about 45 s.

What is held: both paged kernels compile at group 1; a program holds every
weight layer ONCE (48 kernel calls, not 192: the loop over the loop steps
is a loop); no pool is copied (no instruction makes an array of a pool's
shape but the kernel calls that update one in place, and every pool
argument is aliased to its output); the program fits the chip beside its
arguments. **The temporaries are 1.33-1.41 GB, not under the 1 GB ISSUE 34
reckoned with**: XLA lifts the re-layout of the 3 x 48 q/k/v projection
weights (8.4 MB each, 1.21 GB) out of the loop over the loop steps, since
they do not change in it, and keeps them all for the length of the
program. They are weights, not pools, so the bound here is 1.5 GB."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness import loader

TEMPORARIES_BOUND = 1.5e9


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
    """The cell's engine with nothing on any device: the model's leaves,
    the pools and every buffer are shapes on the described chip. Returns
    (engine, {program name: its raw ``jax.jit``}, arguments by form)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    cfg = loader.data("configs", "ouro-2.6b")

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=one_chip)
    with paddle.LazyGuard():
        model = loader.module("programs", "ouro").build(cfg)
    model.eval()
    for _, p in model.named_parameters():
        p._value = shape(p._value.shape, jnp.bfloat16)
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
    i32, f32 = jnp.int32, jnp.float32
    head = ([shape(v.shape, v.dtype) for v in eng._state_vals], eng._k,
            eng._v, eng._logits, eng._lens)
    tables = shape(eng._tables.shape, i32)
    mixed = head + (key, shape((b, chunk), i32), shape((b,), i32),
                    shape((b,), bool), shape((b,), bool), shape((b,), f32),
                    shape((b,), f32), shape((b,), i32), tables)
    decode = head + (shape((b,), bool), key, shape((b,), f32),
                     shape((b,), f32), shape((b,), i32), shape((b,), i32),
                     shape((b,), i32), tables)
    return eng, raw, {"fused_step": mixed, "step": decode,
                      "multi_step": decode}


@pytest.fixture()
def mosaic(monkeypatch):
    """Route the kernels to Mosaic although this process sees a CPU, and
    keep the compiles out of the persistent cache (they cannot be read
    back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.kernels import paged_attention
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_the_cells_sizes(engine):
    eng, _, _ = engine
    assert eng.mixed_rows == 272 and eng._loop_steps == 4
    assert len(eng._k) == len(eng._v) == 48
    assert {tuple(p.shape) for p in eng._k + eng._v} == {(4 * 81, 16, 64,
                                                          128)}
    # (80 + 1) blocks x 96 MiB
    assert eng.kv_pool_nbytes() == 81 * 96 * 2 ** 20
    assert eng._tables.shape == (8, 12)


@pytest.mark.parametrize("name,kernel", [
    ("fused_step", "paged_attention_append"),
    ("step", "paged_attention_decode"),
    ("multi_step", "paged_attention_decode")])
def test_a_step_program_compiles_with_the_loop_a_loop_and_no_pool_copied(
        engine, mosaic, name, kernel):
    eng, raw, args = engine
    compiled = raw[name].trace(*args[name]).lower(
        lowering_platforms=("tpu",)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    # every weight layer once: 48 kernel calls, inside the loop
    assert text.count("tpu_custom_call") == 48
    assert kernel in text
    if kernel == "paged_attention_decode":
        # group 1: the 16 kv heads of a table entry in one grid step, q as
        # [slot, head group, heads, D] (one head a step: [8,16,1,128])
        assert "bf16[8,1,16,128]" in text
    # no pool is copied: nothing but a kernel call, a parameter or the
    # reading of a tuple's element makes an array of a pool's shape
    made = re.findall(
        r"= bf16\[324,16,64,128\]\S* ([\w\-]+)\(", text)
    assert set(made) <= {"custom-call", "parameter", "get-tuple-element"}
    # the pools go out where they came in
    assert mem.alias_size_in_bytes >= eng.kv_pool_nbytes()
    # the arguments: the pools and 2,667,974,657 parameters of 2 B (and
    # the rotary table)
    assert mem.argument_size_in_bytes == pytest.approx(
        eng.kv_pool_nbytes() + 2 * 2_667_974_657, rel=0.01)
    assert mem.temp_size_in_bytes < TEMPORARIES_BOUND
    # and it fits: arguments and temporaries at least 12 GB, under the 16 GiB
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 12e9 < total < loader.peaks()["devices"]["TPU v5 lite"][
        "memory_bytes"]
