"""The cores of ``dsv2_rag_answers``, compiled for the real chip at the
cell's real shapes (128 heads against one latent head of 576 with values
its first 512, a pool of 8 x 136 blocks of 64, a chunk of the
configuration's ``chunk_size`` rows and the one-token form; 40 held
experts of 5120 x 1536 under group-limited softmax routing over 160) by
the TPU compiler that is installed here, for a v5e that is described and
not attached. Nothing runs: a compile that passes is not a chip run. In
``test_aot_kimi.py``'s manner: the topology described inside a fixture,
all in this one file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.harness import loader

BF = jnp.bfloat16
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


@pytest.mark.parametrize("form", ["chunk", "one_token"])
def test_latent_attention_compiles_at_the_cells_shapes(one_chip, mosaic,
                                                       form):
    from paddle_tpu.ops.kernels import latent_attention as la
    cfg = loader.data("configs", "deepseek-v2-ep4-d5")
    eng = cfg["engine"]
    b, h, bs = eng["max_batch"], cfg["num_attention_heads"], \
        eng["block_size"]
    dv = cfg["kv_lora_rank"]
    d = dv + cfg["qk_rope_head_dim"]
    mb = eng["max_seq_len"] // bs
    s = eng["chunk_size"] if form == "chunk" else 1
    assert (h, d, mb) == (128, 576, 136)
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
    from paddle_tpu.models.cache_layout import packed_rows
    from paddle_tpu.ops.kernels import moe_dropless as moe
    cfg = loader.data("configs", "deepseek-v2-ep4-d5")
    eng = cfg["engine"]
    budget = eng["chunk_size"] + eng["max_batch"] - 1
    n = packed_rows(budget, eng["max_batch"], eng["chunk_size"])
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    e, e_all = cfg["n_routed_experts"], cfg["n_routed_experts_published"]
    k = cfg["num_experts_per_tok"]

    def layer(x, wr, live, wg, wu, wd):
        idx, w = moe.route(x, wr, None, k, cfg["routed_scaling_factor"],
                           renormalize=False, scoring="softmax",
                           n_group=cfg["n_group"],
                           topk_group=cfg["topk_group"])
        return moe.held_expert_ffn(x, idx, w, live, wg, wu, wd, 0,
                                   rows=budget * k)
    text = compiled_text(
        layer, one_chip, ((n, h), BF), ((h, e_all), BF), ((n,), jnp.bool_),
        ((e, h, f), BF), ((e, h, f), BF), ((e, f, h), BF))
    # the grouped-matmul kernel, and not every row times every expert
    # (519 x 6 = 3,114 rows is no multiple of 8: ``held_expert_ffn`` rounds)
    assert "ragged" in text.lower()
