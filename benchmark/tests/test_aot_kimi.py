"""The cores that ``kimi_long_docs`` adds, compiled for the real chip at the
cell's real shapes (32 heads against one latent head of 576 with values its
first 512, a pool of 8 x 260 blocks of 64; 64 held experts of 2304 x 1024
over 2,104 assignment rows; KDA's 32 heads of 128 over 8 x 256 rows) by the
TPU compiler that is installed here, for a v5e that is described and not
attached. Nothing runs: a compile that passes is not a chip run. In
``test_aot.py``'s manner: the topology described inside a fixture, all in
this one file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF = jnp.bfloat16
F32 = jnp.float32
I32 = jnp.int32


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


def compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()


@pytest.mark.parametrize("s", [256, 1])
def test_latent_attention_compiles_at_the_cells_shapes(one_chip, mosaic, s):
    from paddle_tpu.ops.kernels import latent_attention as la
    b, h, d, dv, bs, mb = 8, 32, 576, 512, 64, 260
    nb = b * mb + 1

    def step(q, pool, new, tables, lens, q_lens):
        pool = la.latent_pool_write(pool, new, tables, lens, q_lens)
        return la._append_call(q, pool, tables, lens, q_lens, dv=dv,
                               interpret=False), pool
    text = compiled_text(
        step, one_chip, ((b, s, h, d), BF), ((nb, bs, d), BF),
        ((b, s, d), BF), ((b, mb), I32), ((b,), I32), ((b,), I32))
    assert "tpu_custom_call" in text and "latent_attention_append" in text


def test_grouped_expert_product_compiles_at_the_cells_shapes(one_chip,
                                                             mosaic):
    from paddle_tpu.ops.kernels import moe_dropless as moe
    n, h, f, e, e_all, k = 8 * 256, 2304, 1024, 64, 256, 8

    def layer(x, wr, bias, live, wg, wu, wd):
        idx, w = moe.route(x, wr, bias, k, 2.446)
        return moe.held_expert_ffn(x, idx, w, live, wg, wu, wd, 0,
                                   rows=(256 + 8 - 1) * k)
    text = compiled_text(
        layer, one_chip, ((n, h), BF), ((h, e_all), BF), ((e_all,), BF),
        ((n,), jnp.bool_), ((e, h, f), BF), ((e, h, f), BF), ((e, f, h), BF))
    assert "ragged" in text.lower()


@pytest.mark.parametrize("s", [256, 1])
def test_kda_compiles_at_the_cells_shapes(one_chip, mosaic, s):
    from paddle_tpu.ops.kernels import kda
    b, h, k = 8, 32, 128
    run = kda.kda_chunk if s > 1 else kda.kda_recurrent
    compiled_text(run, one_chip, ((b, s, h, k), F32), ((b, s, h, k), F32),
                  ((b, s, h, k), F32), ((b, s, h, k), F32), ((b, s, h), F32),
                  ((b, h, k, k), F32))
