"""The cores that ``solar_long_reports`` adds shapes to, compiled for the
real chip at the cell's real shapes by the TPU compiler that is installed
here, for a v5e that is described and not attached: the paged K/V append
and decode kernels at 64 query heads on 8 key/value heads of 128 (a group
of 8) over a pool of 16 x 400 blocks of 64, a chunk of 512 rows a slot;
``kda_chunk_walk`` at 64 heads of 128 over 16 x 512 rows (eight head
groups of 8); ``grouped_expert_matmul`` at 40 held experts of 4096 x 1280
over a mixed step's 527 live rows x 8 on 528 packed rows, and a scan's 16
x 8. Nothing runs: a compile that passes is not a chip run. In
``test_aot_kimi.py``'s manner: the topology described inside a fixture,
all in this one file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF = jnp.bfloat16
F32 = jnp.float32
I32 = jnp.int32
#: slots, chunk, query heads, key/value heads, head size, block, blocks a
#: slot (25,600 / 64), the pool with its scratch block
B, S, HQ, HKV, D, BS, MB = 16, 512, 64, 8, 128, 64, 400
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


def compiled(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()


POOL = ((NB, HKV, BS, D), BF)


def test_paged_append_compiles_at_a_group_of_eight(one_chip, mosaic):
    from paddle_tpu.ops.kernels.paged_attention import paged_attention_append
    text = compiled(
        paged_attention_append, one_chip,
        ((B, S, HQ, D), BF), POOL, POOL, ((B, MB), I32), ((B,), I32),
        ((B,), I32), ((B, S, HKV, D), BF), ((B, S, HKV, D), BF)).as_text()
    assert "tpu_custom_call" in text and "paged_attention_append" in text


def test_paged_decode_compiles_at_a_group_of_eight(one_chip, mosaic):
    from paddle_tpu.ops.kernels.paged_attention import paged_attention_decode
    text = compiled(
        lambda q, k, v, t, n, nk, nv: paged_attention_decode(
            q, k, v, t, n, new_k=nk, new_v=nv),
        one_chip, ((B, HQ, D), BF), POOL, POOL, ((B, MB), I32), ((B,), I32),
        ((B, HKV, D), BF), ((B, HKV, D), BF)).as_text()
    assert "tpu_custom_call" in text and "paged_attention_decode" in text


def test_the_kda_kernel_compiles_at_sixty_four_heads(one_chip, mosaic):
    from paddle_tpu.ops.kernels import kda_chunk_walk as W
    h, k = 64, 128
    assert W.serves(k, k) and h // W.heads_per_step(h) == 8
    rows = [((B, S, h, k), F32)] * 4 + [((B, S, h), F32),
                                        ((B, h, k, k), F32)]
    exe = compiled(lambda *a: W.kda_chunk_walk(*a), one_chip, *rows,
                   ((B,), I32), ((B,), I32))
    text = exe.as_text()
    assert "tpu_custom_call" in text and "kda_chunk_walk" in text
    # the per-slot rows (268 MB an operand) go to the kernel as they lie:
    # no copy of one among the temporaries
    assert exe.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("step,n,bound", [("mixed", 528, 512 + 16 - 1),
                                          ("scan", 16, 16)])
def test_the_expert_layer_compiles_to_the_kernel(one_chip, mosaic, step, n,
                                                 bound):
    from paddle_tpu.ops.kernels import moe_dropless as moe
    h, f, e, k = 4096, 1280, 40, 8

    def layer(x, idx, w, live, wg, wu, wd):
        return moe.held_expert_ffn(x, idx, w, live, wg, wu, wd, 0,
                                   rows=bound * k)
    text = compiled(
        layer, one_chip, ((n, h), BF), ((n, k), I32), ((n, k), F32),
        ((n,), jnp.bool_), ((e, h, f), BF), ((e, h, f), BF),
        ((e, f, h), BF)).as_text()
    assert "grouped_expert_matmul" in text
    assert "ragged" not in text.lower()
