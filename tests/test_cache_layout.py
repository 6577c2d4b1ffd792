"""``cache_layout.Layout``: what the serving engine asks of a model's state
kinds (the five layouts of the module's docstring), the one way to build,
carry and unpack a layer's state, and the model's counters in
``engine.stats``."""
import re
import types

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.llm_engine import default_engine_stats
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models import cache_layout as CL
from paddle_tpu.models.llama import PagedKVCache
from paddle_tpu.ops.kernels import latent_attention

import test_kimi_linear as KIMI

STATE = {"S": ((4, 8, 8), np.float32), "tail": ((3, 32), np.float32)}
STATE_BYTES = 4 * (4 * 8 * 8 + 3 * 32)
WIDE = latent_attention.entries_per_step(8, 16)

#: layout -> (kinds, what Layout answers)
LAYOUTS = {
    "every_layer_kv": (
        lambda: [CL.PagedKV(2, 16) for _ in range(2)],
        dict(shape=None, plain_kv=True, has_paged=True, has_recurrent=False,
             kv=CL.PagedKV, loop_steps=1, names=[], token=2 * 128,
             slot=0, entries=[1])),
    "latent": (
        lambda: [CL.PagedLatent(40) for _ in range(2)],
        dict(shape="latent_only", plain_kv=False, has_paged=True,
             has_recurrent=False, kv=None, loop_steps=1,
             names=["paged_latent"], token=2 * 80, slot=0, entries=[WIDE])),
    "recurrent_beside_latent": (
        lambda: [CL.Recurrent(STATE), CL.Recurrent(STATE),
                 CL.PagedLatent(40)],
        dict(shape="beside", plain_kv=False, has_paged=True,
             has_recurrent=True, kv=None, loop_steps=1,
             names=["paged_latent", "recurrent"], token=80,
             slot=2 * STATE_BYTES, entries=[WIDE])),
    "recurrent_beside_kv": (
        lambda: [CL.PagedKV(2, 16, q_heads=8), CL.Recurrent(STATE)],
        dict(shape="beside", plain_kv=False, has_paged=True,
             has_recurrent=True, kv=CL.PagedKV, loop_steps=1,
             names=["recurrent"], token=128, slot=STATE_BYTES,
             entries=[1])),
    "looped": (
        lambda: [CL.LoopedPagedKV(2, 16, 3)] * 2,
        dict(shape="looped", plain_kv=False, has_paged=True,
             has_recurrent=False, kv=CL.LoopedPagedKV, loop_steps=3,
             names=["paged_kv_looped"], token=2 * 3 * 128, slot=0,
             entries=[1])),
    "recurrent_alone": (
        lambda: [CL.Recurrent(STATE) for _ in range(2)],
        dict(shape="recurrent_only", plain_kv=False, has_paged=False,
             has_recurrent=True, kv=None, loop_steps=1, names=["recurrent"],
             token=0, slot=2 * STATE_BYTES, entries=[])),
}


@pytest.mark.parametrize("name", LAYOUTS)
def test_layout_answers_for_the_layouts_the_engine_serves(name):
    make, want = LAYOUTS[name]
    kinds = make()
    lay = CL.Layout(kinds)
    assert len(lay) == len(kinds)
    assert [type(k) for k in lay] == [type(k) for k in kinds]
    for attr in ("shape", "plain_kv", "has_paged", "has_recurrent",
                 "loop_steps"):
        assert getattr(lay, attr) == want[attr], attr
    assert (lay.kv is None) if want["kv"] is None \
        else type(lay.kv) is want["kv"]
    assert lay.names() == want["names"]
    assert lay.bytes_per_token(2) == want["token"]
    assert lay.bytes_per_slot() == want["slot"]
    assert lay.entries_per_step(8, 16) == want["entries"]
    if name == "recurrent_beside_kv":
        config = types.SimpleNamespace(num_attention_heads=32)
        assert lay.kv.group(config) == 4        # the kind's own q_heads
        assert CL.PagedKV(2, 16).group(config) == 16    # the config's
    # what it refuses: nothing for plain K/V; a pool's size for a layout
    # without a pool alone
    named = re.escape(str(want["names"]))
    for option, refused, says in (
            (dict(enable_prefix_cache=True), want["shape"] is not None,
             f"enable_prefix_cache cannot serve .*{named} layers"),
            (dict(kv_shipping="export_kv()"), want["shape"] is not None,
             rf"export_kv\(\) .*{named} layers"),
            (dict(request_kind="embed"), want["shape"] is not None,
             "kind='embed' pools the hidden rows"),
            (dict(kv_pool_blocks=8), want["shape"] == "recurrent_only",
             "kv_pool_blocks cannot serve .* has no pool to size"),
            (dict(scheduler="fused", cache_impl="paged", horizon=1,
                  kv_pool_blocks=None, enable_prefix_cache=False,
                  kv_host_tier=0, speculative_k=1, kv_cache_dtype=None,
                  adapter_store=None, mesh=None, kv_shipping=False,
                  request_kind="generate"), False, None)):
        if refused:
            with pytest.raises(ValueError, match=says):
                lay.refuse(**option)
        else:
            lay.refuse(**option)
    # one way to build, carry and unpack: what comes off the caches is what
    # went in, layer for layer
    k, v = lay.alloc(jnp.zeros, 4, 16, 3, jnp.float32)
    tables = jnp.zeros((3, 8), jnp.int32)
    lens = jnp.zeros((3,), jnp.int32)
    active = jnp.asarray([True, False, True])
    caches = lay.caches(k, v, tables, lens, None, active, 3)
    assert all(c.row_budget == 3 and c.rows is None for c in caches)
    for c in caches:        # a one-token step: a live row an active slot
        np.testing.assert_array_equal(c.q_lens, [1, 0, 1])
    k2, v2 = lay.unpack(caches)
    for got, put in ((k2, k), (v2, v)):
        assert [type(a) for a in got] == [type(a) for a in put]


@pytest.mark.parametrize("quant, last", [("int8", 16), ("int4", 8)])
def test_a_quantized_pool_is_the_kv_kinds_bundle(quant, last):
    kinds = [CL.PagedKV(2, 16) for _ in range(2)]
    lay = CL.Layout(kinds)
    k, v = lay.alloc(jnp.zeros, 4, 16, 3, jnp.bfloat16, quant)
    for pool in k + v:
        payload, scale = pool
        assert payload.shape == (5, 2, 16, last)
        assert payload.dtype == jnp.int8
        assert scale.shape == (5, 2) and scale.dtype == jnp.float32
    q_lens = jnp.asarray([2, 0, 1], jnp.int32)
    caches = lay.caches(k, v, jnp.zeros((3, 8), jnp.int32),
                        jnp.zeros((3,), jnp.int32), q_lens, None, 7)
    for c, (payload, scale) in zip(caches, k):
        assert isinstance(c, PagedKVCache) and c.quant == quant
        assert c.k is payload and c.k_scale is scale and c.q_lens is q_lens
    k2, v2 = lay.unpack(caches)
    assert all(a is b for x, y in zip(k2 + v2, k + v) for a, b in zip(x, y))
    # the format is the engine's layout's, not the model's kinds'
    assert all(kind.quant is None for kind in kinds)
    assert all(kind.quant == quant for kind in lay)


def test_a_models_counters_are_in_stats_from_the_start_and_only_its_own():
    model = KIMI.build(KIMI.TOY, 1)[0]
    names = model.step_counter_names
    assert {"moe_assignments", "kda_grid_steps"} <= set(names)
    eng = LLMEngine(model, **KIMI.ENGINE)
    assert all(eng.stats[name] == 0 for name in names)
    assert set(eng.stats) == set(default_engine_stats()) | set(names)
    eng.generate([np.arange(1, 20, dtype=np.int32)], max_new_tokens=3)
    assert eng.stats["moe_assignments"] > 0
    booked = {name: eng.stats[name] for name in names}
    eng.reset()         # the cumulative stats survive a reset, these too
    assert {name: eng.stats[name] for name in names} == booked
    # an engine whose model declares none has none: the engine's own keys
    paddle.seed(0)
    llama = LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=2))
    eng = LLMEngine(llama.eval() or llama, max_batch=2, max_seq_len=64,
                    chunk_size=16, cache_impl="paged", block_size=8,
                    scheduler="fused")
    assert set(eng.stats) == set(default_engine_stats())
    assert len(eng.stats) == 54
    assert not [k for k in eng.stats
                if k.startswith(("moe_", "ret_", "kda_", "loop_"))]
    eng.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=2)
    eng.reset()
    assert set(eng.stats) == set(default_engine_stats())
