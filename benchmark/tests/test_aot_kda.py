"""The KDA kernel (``paddle_tpu/ops/kernels/kda_chunk_walk.py``) compiled
for the real chip at ``kimi_long_docs``' shapes (8 slots of 256 rows, 32
heads of 128, float32) by the TPU compiler that is installed here, for a
v5e that is described and not attached: it is one Mosaic call, and the
pairwise-decay tensor ``[.., 16, 16, 128]`` that the XLA form
``kda.kda_chunk`` keeps in HBM is not in the program. Nothing runs: a
compile that passes is not a chip run. In ``test_aot.py``'s manner: the
topology described inside a fixture, all in this one file."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

F32 = jnp.float32
I32 = jnp.int32
B, S, H, K = 8, 256, 32, 128
#: the chunk form's pairwise decays, whatever leads them
DECAY = re.compile(r"f32\[[0-9,]*16,16,128\]")


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


ROWS = [((B, S, H, K), F32)] * 4 + [((B, S, H), F32), ((B, H, K, K), F32)]


def test_the_kernel_compiles_at_the_cells_shapes(one_chip, mosaic):
    from paddle_tpu.ops.kernels import kda_chunk_walk as W
    assert W.serves(K, K)
    exe = compiled(lambda *a: W.kda_chunk_walk(*a), one_chip, *ROWS,
                   ((B,), I32), ((B,), I32))
    text = exe.as_text()
    assert "tpu_custom_call" in text and "kda_chunk_walk" in text
    assert not DECAY.search(text)
    # the per-slot rows go to the kernel as they lie: no copy of an
    # operand (33.5 MB each) among the temporaries
    assert exe.memory_analysis().temp_size_in_bytes < 4 << 20


def test_the_xla_form_is_what_keeps_the_decay_tensor(one_chip, mosaic):
    """The oracle the kernel is compared with: its program holds the
    tensor the pattern looks for, so the pattern can see one."""
    from paddle_tpu.ops.kernels import kda
    text = compiled(kda.kda_chunk, one_chip, *ROWS).as_text()
    assert DECAY.search(text)
