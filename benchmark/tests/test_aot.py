"""The kernels of the cells' paths compiled for the real chip at the cells'
real shapes — Mistral-7B-v0.3's 32 query over 8 KV heads of 128, and the
tensor-parallel share of 8 over 2 — by the TPU compiler that is installed
here, for a v5e that is described and not attached. Nothing runs: a compile
that passes is not a chip run. All in this one file, the topology described
inside a fixture (one process may hold the TPU library)."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF = jnp.bfloat16


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
    from paddle_tpu.ops.kernels import flash_attention, paged_attention
    monkeypatch.setattr(paged_attention, "_interpret", lambda: False)
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("hq,hkv", [(32, 8), (8, 2)])
def test_paged_decode_compiles_at_the_cells_shapes(one_chip, mosaic, hq, hkv):
    from paddle_tpu.ops.kernels.paged_attention import paged_attention_decode
    b, d, nb, bs, mb = 8, 128, 545, 64, 68
    compiled_text(
        lambda q, k, v, t, n, nk, nv: paged_attention_decode(
            q, k, v, t, n, new_k=nk, new_v=nv),
        one_chip, ((b, hq, d), BF), ((nb, hkv, bs, d), BF),
        ((nb, hkv, bs, d), BF), ((b, mb), jnp.int32), ((b,), jnp.int32),
        ((b, hkv, d), BF), ((b, hkv, d), BF))


@pytest.mark.parametrize("hq,hkv", [(32, 8), (8, 2)])
def test_paged_append_compiles_at_the_cells_shapes(one_chip, mosaic, hq, hkv):
    from paddle_tpu.ops.kernels.paged_attention import paged_attention_append
    b, s, d, nb, bs, mb = 8, 256, 128, 545, 64, 68
    compiled_text(
        paged_attention_append, one_chip,
        ((b, s, hq, d), BF), ((nb, hkv, bs, d), BF), ((nb, hkv, bs, d), BF),
        ((b, mb), jnp.int32), ((b,), jnp.int32), ((b,), jnp.int32),
        ((b, s, hkv, d), BF), ((b, s, hkv, d), BF))


def test_flash_forward_and_backward_compile_at_the_cells_shapes(one_chip,
                                                                mosaic):
    from paddle_tpu.ops.kernels.flash_attention import flash_attention_fwd
    b, s, hq, hkv, d = 8, 2048, 32, 8, 128

    def loss(q, k, v):
        return jnp.sum(flash_attention_fwd(q, k, v, causal=True)
                       .astype(jnp.float32))
    text = compiled_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                         ((b, s, hq, d), BF), ((b, s, hkv, d), BF),
                         ((b, s, hkv, d), BF))
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq"):
        assert kernel in text
