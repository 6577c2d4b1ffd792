"""The step programs of ``dots3_long_answers`` compiled for the real chip at
the cell's real sizes (two indexed latent layers of 128 heads on entries
of 576 with index keys of 128, three window layers of 64 heads on entries
of 1,088 in rings of 1,024 rows a slot, 32 held experts of 5120 x 1536; 8
slots of 33,280 positions; a mixed step of 528 packed rows and the
``multi_step`` scan of stride 4) by the TPU compiler that is installed
here, for a v5e that is described and not attached. Nothing runs: a
compile that passes is not a chip run. The topology is described inside a
fixture, all in this one file. A program compiles in 30 to 60 s.

What is held: the programs compile and fit the chip; the indexed layers
score and attend the selected latents through the Pallas kernels
``dsa_index_scores`` and ``dsa_sparse_attend`` (a slot's whole context, 51
MB of packed words, in VMEM); the window layers run
``latent_attention_append`` (Mosaic took the second geometry, D = 1088 and
dv = 1024 at 8 heads a grid step) on a derived table of 20 entries, five
wide entries a slot; no instruction attends a full layer's whole context
(no ``[528, 128, 33280]`` scores); and the two cells that share the kernel
compile to the grid and the VMEM plan they had: ``window=None`` traces
nothing."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness import loader

BF, I32 = jnp.bfloat16, jnp.int32


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
    described chip (``brumby_aot.engine``'s way)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import LLMEngine
    cfg = loader.data("configs", "dots3-note-prev-ep8-d5")

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=one_chip)
    with paddle.LazyGuard():
        model = loader.module("programs", "dots3_note").build(cfg)
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
    f32 = jnp.float32
    head = ([shape(v.shape, v.dtype) for v in eng._state_vals], eng._k,
            eng._v, eng._logits, eng._lens)
    tables = shape(eng._tables.shape, I32)
    mixed = head + (key, shape((b, chunk), I32), shape((b,), I32),
                    shape((b,), bool), shape((b,), bool), shape((b,), f32),
                    shape((b,), f32), shape((b,), I32), tables)
    decode = head + (shape((b,), bool), key, shape((b,), f32),
                     shape((b,), f32), shape((b,), I32), shape((b,), I32),
                     shape((b,), I32), tables)
    return eng, raw, {"fused_step": mixed, "multi_step": decode}


@pytest.fixture()
def mosaic(monkeypatch):
    """Route the kernels to Mosaic although this process sees a CPU, keep
    the compiles out of the persistent cache (they cannot be read back
    without a chip), and the products at the precision the chip runs
    (``tests/conftest.py``'s float32 passes are refused for bf16)."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.kernels import paged_attention
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.default_matmul_precision("default"):
        yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_the_cells_sizes(engine):
    eng, _, _ = engine
    assert eng.B == 8 and eng.capacity == 33280 == 65 * 512
    assert eng.mixed_rows == 528
    nb = 8 * 520 + 1
    assert [tuple(a.shape) for a in eng._k] == \
        [(nb, 64, 576)] * 2 + [(8 * 16 + 1, 64, 1088)] * 3
    assert [None if b is None else tuple(b.shape) for b in eng._v] == \
        [(nb, 64, 128)] * 2 + [None] * 3
    assert eng._layout.bytes_per_token(2) == 2816
    assert eng._layout.bytes_per_slot() == 3 * 1024 * 1088 * 2
    # the pools: 8 x 33,280 tokens of 2,816 B (and the scratch block)
    assert eng.kv_pool_nbytes() == nb * 64 * 2816


@pytest.mark.parametrize("name", ["fused_step", "multi_step"])
def test_a_step_program_compiles_and_attends_no_whole_context(engine, mosaic,
                                                             name):
    eng, raw, args = engine
    compiled = raw[name].trace(*args[name]).lower(
        lowering_platforms=("tpu",)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < loader.peaks()["devices"]["TPU v5 lite"]["memory_bytes"]
    # the weights (4,087,154,176 x 2 B) and the pools lead the arguments
    assert mem.argument_size_in_bytes == pytest.approx(
        2 * 4_087_154_176 + eng.kv_pool_nbytes()
        + eng.B * eng._layout.bytes_per_slot(), rel=0.02)
    assert mem.temp_size_in_bytes < 3.0e9
    # the pools and the rings go out where they came in
    assert mem.alias_size_in_bytes >= eng.kv_pool_nbytes()
    # two indexed layers' scoring and attending kernels, three window
    # layers of the latent kernel, four expert layers of two grouped
    # products
    assert text.count("tpu_custom_call") == 2 * 2 + 3 + 8
    assert "dsa_index_scores" in text and "dsa_sparse_attend" in text
    assert "latent_attention_append" in text
    assert "grouped_expert_matmul" in text
    rows = 528 if name == "fused_step" else 8
    # the indexer scores a whole context (that is the mechanism) ...
    assert re.search(rf"f32\[{rows}(,\d+)?,33280\]", text)
    # ... and nothing attends one: no 128-head scores over 33,280
    assert not re.search(r"\[\d+,128,33280\]|\[128,\d+,33280\]", text)
    assert "pt.select" in text and "pt.sparse" in text and \
        "pt.index" in text


@pytest.mark.parametrize("heads,rows,width,mb,grid,vmem", [
    (128, 528, 512, 136, "(8, 8, 34)", 82345984),    # dsv2_rag_answers
    (32, 272, 256, 272, "(1, 8, 68)", 83460096),     # kimi_long_docs
])
def test_the_cells_that_share_the_kernel_keep_their_grid_and_vmem_plan(
        one_chip, mosaic, heads, rows, width, mb, grid, vmem):
    """``window=None`` lowers to the kernel the two latent cells had: the
    same grid, the same VMEM limit (read in the parent commit's trace of
    the same call), and the same text whether ``window`` is passed or
    not. It compiles for the chip."""
    from paddle_tpu.ops.kernels import latent_attention as LA

    def call(**kw):
        return lambda q, pool, tables, lens, q_lens, start: LA._append_rows(
            q, pool, tables, lens, q_lens, start, width=width, dv=512,
            every=None, interpret=False, **kw)
    sh = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((rows, heads, 576), BF), ((8 * mb + 1, 64, 576), BF),
        ((8, mb), I32), ((8,), I32), ((8,), I32), ((8,), I32))]
    text = str(jax.make_jaxpr(call())(*sh))
    assert re.findall(r"grid=\([^)]*\)", text) == ["grid=" + grid]
    assert re.findall(r"vmem_limit_bytes=(\d+)", text) == [str(vmem)]
    assert text == str(jax.make_jaxpr(call(window=None))(*sh))
    assert text != str(jax.make_jaxpr(call(window=513))(*sh))
    exe = jax.jit(call()).trace(*sh).lower(
        lowering_platforms=("tpu",)).compile()
    assert "latent_attention_append" in exe.as_text()


def test_the_window_kernel_compiles_at_the_second_geometry(one_chip, mosaic):
    from paddle_tpu.ops.kernels import latent_attention as LA
    assert LA.heads_per_step(64, 8, 528, 512, 1088, 1024, 64) == 8

    def call(q, ring, tables, lens, q_lens, start):
        return LA._append_rows(q, ring, tables, lens, q_lens, start,
                               width=512, dv=1024, every=None, window=513,
                               interpret=False)
    sh = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((528, 64, 1088), BF), ((8 * 16 + 1, 64, 1088), BF), ((8, 20), I32),
        ((8,), I32), ((8,), I32), ((8,), I32))]
    assert re.findall(r"grid=\([^)]*\)", str(jax.make_jaxpr(call)(*sh))) \
        == ["grid=(8, 8, 5)"]
    exe = jax.jit(call).trace(*sh).lower(
        lowering_platforms=("tpu",)).compile()
    assert "latent_attention_append" in exe.as_text()
