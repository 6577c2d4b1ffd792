"""``held_expert_ffn`` compiled for the real chip at both expert cells'
real shapes, a mixed step's height and an all-decode scan's (Kimi-Linear:
64 held experts of 2304 x 1024, 263 live rows x 8 on 272 packed rows, and
8 rows x 8; DeepSeek-V2: 40 of 5120 x 1536, 519 x 6 on 520, and 8 x 6),
by the TPU compiler that is installed here, for a v5e that is described
and not attached: the grouped product is the Pallas kernel
``grouped_expert_matmul`` (``paddle_tpu/ops/kernels/``) in every one of
them, and no ``ragged_dot`` is left. Nothing runs: a compile that passes
is not a chip run. In ``test_aot.py``'s manner: the topology described
inside a fixture, all in this one file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF = jnp.bfloat16
F32 = jnp.float32
I32 = jnp.int32

#: (hidden, expert width, experts held, experts a row)
CELLS = {"kimi_long_docs": (2304, 1024, 64, 8),
         "dsv2_rag_answers": (5120, 1536, 40, 6)}
#: (rows of the step, the caller's bound on live rows)
STEPS = {"kimi_long_docs": {"mixed": (272, 256 + 8 - 1), "scan": (8, 8)},
         "dsv2_rag_answers": {"mixed": (520, 512 + 8 - 1), "scan": (8, 8)}}


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


@pytest.mark.parametrize("step", ["mixed", "scan"])
@pytest.mark.parametrize("cell", CELLS)
def test_the_expert_layer_compiles_to_the_kernel(one_chip, mosaic, cell,
                                                 step):
    from paddle_tpu.ops.kernels import moe_dropless as moe
    h, f, e, k = CELLS[cell]
    n, bound = STEPS[cell][step]

    def layer(x, idx, w, live, wg, wu, wd):
        return moe.held_expert_ffn(x, idx, w, live, wg, wu, wd, 0,
                                   rows=bound * k)
    shapes = (((n, h), BF), ((n, k), I32), ((n, k), F32), ((n,), jnp.bool_),
              ((e, h, f), BF), ((e, h, f), BF), ((e, f, h), BF))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(layer).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text
    assert text.count("grouped_expert_matmul") >= 2     # gate + up, down
    assert "ragged" not in text.lower()
