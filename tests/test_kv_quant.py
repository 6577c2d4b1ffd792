"""Quantized KV serving — int8/int4 paged pools with per-block scales.

Three layers of coverage:

* **kernel parity** (interpret mode): the quantized Pallas
  decode/append variants vs the dense-gather fallback (the shipping CPU
  path inside ``block_multihead_attention``) — outputs to online-softmax
  tolerance, updated pools AND scale arrays bit-exact, including block
  boundaries (len % bs in {0, 1, bs-1}), GQA, the in-kernel scale update
  on fused writes, q_lens=0 window degeneracy, and int4 odd-D nibble
  padding (kernel-only: the op can't disambiguate odd head dims).
* **capacity**: an int8 (int4) pool fits >= 1.9x (>= 3.5x) the bf16
  block count at equal HBM bytes — asserted off the engines' real buffer
  nbytes (payload + scales), the PR's acceptance arithmetic.
* **engine composition**: quantized pool x {prefix cache, stride-k
  multi-step, legacy scheduler, speculative verify, multi-LoRA, TP mesh,
  supervised reset} — token-EXACT where quantization commutes with the
  feature (same quantized bytes either way), drift-BOUNDED where it
  cannot (speculative rollback re-rounds block scales; documented in
  docs/architecture.md), plus recorder/telemetry plumbing.
  ``kv_cache_dtype=None`` stays bit-identical to the
  pre-quantization engine (same traced programs — regression-tested
  against a plain bf16-pool engine).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.kernels.paged_attention import (
    KV_QMAX, kv_block_scale, kv_pack, kv_packed_dim, kv_quantize,
    kv_unpack, paged_attention_append, paged_attention_decode)

CFG = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=128)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 96, size=(n,)).astype(np.int32)
            for n in (13, 9)]


def _kw(**over):
    kw = dict(max_batch=2, max_seq_len=64, chunk_size=16,
              cache_impl="paged", block_size=8, scheduler="fused",
              kv_cache_dtype="int8")
    kw.update(over)
    return kw


def _toks(eng, prompts, n=10):
    return [o.token_ids for o in eng.generate(prompts, max_new_tokens=n)]


def _match_prefix(a, b):
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


# ---------------------------------------------------------------------------
# kernel parity (interpret mode) vs the dense fallback
# ---------------------------------------------------------------------------

def _quant_pools(rng, lens, grow, Hkv, D, BS, quant):
    """Quantized pools + tables covering ``lens`` (+``grow`` positions
    each), physical blocks shuffled, the trailing block reserved as the
    engine's scratch (never assigned — fallback drops what the kernel
    parks there)."""
    B = len(lens)
    need = [(int(L) + max(int(g), 1)) // BS + 1
            for L, g in zip(lens, grow)]
    MB = max(need) + 1
    NB = sum(need) + 2
    order = rng.permutation(NB - 1)
    tables = np.full((B, MB), -1, np.int32)
    it = iter(order)
    for b in range(B):
        for j in range(need[b]):
            tables[b, j] = next(it)
    kf = rng.standard_normal((NB, Hkv, BS, D)).astype(np.float32)
    vf = rng.standard_normal((NB, Hkv, BS, D)).astype(np.float32)
    ks = np.asarray(kv_block_scale(jnp.asarray(kf), quant, (2, 3)))
    vs = np.asarray(kv_block_scale(jnp.asarray(vf), quant, (2, 3)))
    kc = np.asarray(kv_quantize(jnp.asarray(kf),
                                jnp.asarray(ks)[..., None, None], quant))
    vc = np.asarray(kv_quantize(jnp.asarray(vf),
                                jnp.asarray(vs)[..., None, None], quant))
    return kc, vc, ks, vs, tables, np.asarray(lens, np.int32)


# the diagonal of {MHA, GQA} x {int8, int4}: both group widths and both
# packings run; the off-diagonal cells repeat them
@pytest.mark.parametrize("quant,group", [("int8", 1), ("int4", 2)])
def test_decode_kernel_parity(rng, quant, group):
    """Quantized decode kernel vs the dense fallback (public op), block
    boundaries len % bs in {0, 1, bs-1}, GQA: outputs to online-softmax
    tolerance, updated pools and scales BIT-exact (the scratch block may
    differ: the fallback drops -1-target writes, the kernel parks
    them)."""
    Hkv, D, BS = 2, 32, 8
    Hq = Hkv * group
    lens = [16, 17, 7, 3]
    kc, vc, ks, vs, tables, lens_ = _quant_pools(
        rng, lens, [1] * 4, Hkv, D, BS, quant)
    B = len(lens)
    q = rng.standard_normal((B, Hq, D)).astype(np.float32)
    knew = rng.standard_normal((B, Hkv, D)).astype(np.float32)
    vnew = rng.standard_normal((B, Hkv, D)).astype(np.float32)
    qkv = np.concatenate([q.reshape(B, -1), knew.reshape(B, -1),
                          vnew.reshape(B, -1)], -1)
    res = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc), paddle.to_tensor(vc),
        None, paddle.to_tensor(lens_), None,
        block_tables=paddle.to_tensor(tables),
        cache_k_quant_scales=paddle.to_tensor(ks),
        cache_v_quant_scales=paddle.to_tensor(vs),
        cache_quant_type=quant)
    ro, rkc, rvc, rks, rvs = [np.asarray(t._value) for t in res]
    out, kc2, vc2, ks2, vs2 = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens_),
        new_k=jnp.asarray(knew), new_v=jnp.asarray(vnew),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs), quant=quant)
    np.testing.assert_allclose(np.asarray(out), ro.reshape(B, Hq, D),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(kc2)[:-1], rkc[:-1])
    np.testing.assert_array_equal(np.asarray(vc2)[:-1], rvc[:-1])
    # scales to 1-ulp: the kernel reduces one [bs, D] block per grid
    # step, the fallback one whole-pool reduce — f32 ordering may differ
    np.testing.assert_allclose(np.asarray(ks2)[:-1], rks[:-1], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vs2)[:-1], rvs[:-1], rtol=1e-6)


def _assert_quant_append_parity(rng, quant, lens, q_lens, S=8, Hq=4, Hkv=2,
                                wiped=None):
    """Quantized append kernel (interpret mode) vs the dense fallback
    (public op): pools + scales bit-exact but for the scratch block, valid
    output rows to tolerance. ``wiped``: a slot whose table row is -1."""
    D, BS = 32, 8
    q_lens = np.asarray(q_lens, np.int32)
    kc, vc, ks, vs, tables, lens_ = _quant_pools(
        rng, lens, q_lens, Hkv, D, BS, quant)
    if wiped is not None:
        tables[wiped, :] = -1
    B = len(lens)
    qa = rng.standard_normal((B, S, Hq, D)).astype(np.float32)
    ka = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    va = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    qkv3 = np.concatenate([qa.reshape(B, S, -1), ka.reshape(B, S, -1),
                           va.reshape(B, S, -1)], -1)
    res = IF.block_multihead_attention(
        paddle.to_tensor(qkv3), paddle.to_tensor(kc), paddle.to_tensor(vc),
        None, paddle.to_tensor(lens_), paddle.to_tensor(q_lens),
        block_tables=paddle.to_tensor(tables),
        cache_k_quant_scales=paddle.to_tensor(ks),
        cache_v_quant_scales=paddle.to_tensor(vs),
        cache_quant_type=quant)
    ro3, rkc3, rvc3, rks3, rvs3 = [np.asarray(t._value) for t in res]
    out3, kc3, vc3, ks3, vs3 = paged_attention_append(
        jnp.asarray(qa), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens_), jnp.asarray(q_lens),
        jnp.asarray(ka), jnp.asarray(va),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs), quant=quant)
    ro3 = ro3.reshape(B, S, Hq, D)
    o3 = np.asarray(out3)
    for b in range(B):
        n = int(q_lens[b])
        if n:
            np.testing.assert_allclose(o3[b, :n], ro3[b, :n],
                                       atol=2e-5, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(kc3)[:-1], rkc3[:-1])
    np.testing.assert_array_equal(np.asarray(vc3)[:-1], rvc3[:-1])
    np.testing.assert_allclose(np.asarray(ks3)[:-1], rks3[:-1], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vs3)[:-1], rvs3[:-1], rtol=1e-6)


@pytest.mark.parametrize("quant", ["int8", "int4"])
def test_append_kernel_parity(rng, quant):
    """Quantized append kernel vs the dense fallback: q_lens covering
    {0 (idle slot), 1 (decode-shaped), mid, full chunk}, windows
    crossing block boundaries; pools + scales bit-exact, valid output
    rows to tolerance."""
    _assert_quant_append_parity(rng, quant, [16, 17, 7, 3], [0, 1, 5, 8])


# the mixes tests/test_paged_attention.py::_FOLLOWS holds the unquantized
# kernel to, at its shape (96 positions x 4 q heads = two row tiles of 192):
# name -> (lens, q_lens, wiped slot)
_FOLLOWS = {
    "cell_step_miniature": ([40, 201, 77, 9, 0], [96, 1, 1, 0, 0], 3),
    "rows_not_a_multiple_of_the_tile": ([3, 50, 64, 11], [50, 9, 8, 49],
                                        None),
    "window_straddles_two_and_three_blocks": ([6, 7, 15], [5, 12, 17], None),
    "verify_window_k_plus_1": ([33, 64, 95], [5, 3, 1], None),
    "first_chunk_from_empty": ([0, 0, 0], [96, 40, 1], None),
    "every_slot_idle": ([12, 0, 31], [0, 0, 0], 2),
}


@pytest.mark.parametrize("name", list(_FOLLOWS))
@pytest.mark.parametrize("quant", ["int8", "int4"])
def test_append_kernel_follows_q_lens_over_quantized_pools(rng, quant, name):
    """The re-quantizing merge runs for window blocks only and the walk
    skips what (seq_lens, q_lens) rule out: same pools, scales and live
    rows as the dense fallback on every mix."""
    lens, q_lens, wiped = _FOLLOWS[name]
    _assert_quant_append_parity(rng, quant, lens, q_lens, S=96, Hq=8,
                                wiped=wiped)


@pytest.mark.parametrize("quant,group", [("int8", 4), ("int4", 1)])
def test_the_packed_append_over_quantized_pools_is_the_per_slot_one(
        rng, quant, group):
    """The packed entry (``start`` prefetched; a chunk after a decode row
    and an idle slot, so it starts off a 16-row tile) re-quantizes the
    same window blocks under the same scales and hands back the same live
    rows as the per-slot entry, bit for bit."""
    Hkv, D, BS, S, T = 2, 32, 8, 16, 32
    q_lens = np.asarray([1, 0, 13, 1], np.int32)
    kc, vc, ks, vs, tables, lens = _quant_pools(
        rng, [16, 17, 7, 30], q_lens, Hkv, D, BS, quant)
    B = len(q_lens)
    qa, ka, va = (rng.standard_normal((B, S, h, D)).astype(np.float32)
                  for h in (Hkv * group, Hkv, Hkv))
    common = [jnp.asarray(a) for a in (kc, vc, tables, lens, q_lens)]
    scales = dict(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs),
                  quant=quant)
    want = paged_attention_append(jnp.asarray(qa), *common, jnp.asarray(ka),
                                  jnp.asarray(va), **scales)
    start = np.cumsum(q_lens) - q_lens

    def pack(x):
        out = np.zeros((T,) + x.shape[2:], x.dtype)
        for b, n in enumerate(q_lens):
            out[start[b]:start[b] + n] = x[b, :n]
        return jnp.asarray(out)
    got = paged_attention_append(
        pack(qa), *common, pack(ka), pack(va), **scales,
        start=jnp.asarray(start, jnp.int32), width=S)
    for a, b in zip(want[1:], got[1:]):     # pools, then scales
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for b, n in enumerate(q_lens):
        np.testing.assert_array_equal(
            np.asarray(got[0])[start[b]:start[b] + n],
            np.asarray(want[0])[b, :n])
    assert not np.asarray(got[0])[int(q_lens.sum()):].any()


def test_scale_update_on_fused_write(rng):
    """A new token whose magnitude dwarfs the block's content must GROW
    the written block's scale in-kernel (fresh absmax over the merged
    block) and saturate the stored int row at the grid edge."""
    quant = "int8"
    Hkv, D, BS = 2, 32, 8
    lens = [11]
    kc, vc, ks, vs, tables, lens_ = _quant_pools(
        rng, lens, [1], Hkv, D, BS, quant)
    knew = np.full((1, Hkv, D), 50.0, np.float32)   # >> unit-normal pool
    vnew = rng.standard_normal((1, Hkv, D)).astype(np.float32)
    q = rng.standard_normal((1, Hkv, D)).astype(np.float32)
    out, kc2, vc2, ks2, vs2 = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens_),
        new_k=jnp.asarray(knew), new_v=jnp.asarray(vnew),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs), quant=quant)
    blk = int(tables[0, lens[0] // BS])
    slot = lens[0] % BS
    ks2 = np.asarray(ks2)
    np.testing.assert_allclose(ks2[blk], 50.0 / KV_QMAX[quant], rtol=1e-6)
    assert (ks2[blk] > ks[blk]).all()
    row = np.asarray(kc2)[blk, :, slot]             # [Hkv, D] ints
    np.testing.assert_array_equal(row, np.full_like(row, 127))
    # untouched blocks keep their exact payload + scale
    others = [i for i in range(kc.shape[0]) if i != blk]
    np.testing.assert_array_equal(np.asarray(kc2)[others], kc[others])
    np.testing.assert_array_equal(ks2[others], ks[others])


def test_dirty_block_reuse_does_not_inflate_scale(rng):
    """A freed block is re-handed WITHOUT zeroing: its stale content can
    be orders of magnitude above the new owner's values. The fused
    write's absmax must ignore the dead tail (positions past the new
    token) — otherwise the stale garbage inflates the block scale and
    quantizes the live row to zero, making greedy output depend on
    pool-reuse history. Kernel AND fallback: scale == the live row's
    own absmax, dequantized row ~= the written token."""
    quant = "int8"
    Hkv, D, BS = 2, 32, 8
    lens = [8]                      # new token opens block 1 at row 0
    kc, vc, ks, vs, tables, lens_ = _quant_pools(
        rng, lens, [1], Hkv, D, BS, quant)
    # dirty the target block with huge stale content (magnitude ~100)
    kc, ks = kc.copy(), ks.copy()
    blk = int(tables[0, 1])
    stale = 100.0 * rng.standard_normal((Hkv, BS, D)).astype(np.float32)
    ks[blk] = np.abs(stale).max(axis=(1, 2)) / KV_QMAX[quant]
    kc[blk] = np.asarray(kv_quantize(jnp.asarray(stale),
                                     jnp.asarray(ks[blk])[:, None, None],
                                     quant))
    knew = np.full((1, Hkv, D), 0.01, np.float32)
    vnew = rng.standard_normal((1, Hkv, D)).astype(np.float32)
    q = rng.standard_normal((1, Hkv, D)).astype(np.float32)
    out, kc2, vc2, ks2, vs2 = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens_),
        new_k=jnp.asarray(knew), new_v=jnp.asarray(vnew),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs), quant=quant)
    ks2 = np.asarray(ks2)
    np.testing.assert_allclose(ks2[blk], 0.01 / KV_QMAX[quant],
                               rtol=1e-6)
    deq = np.asarray(kc2)[blk, :, 0].astype(np.float32) * ks2[blk][:, None]
    np.testing.assert_allclose(deq, 0.01, rtol=0.02)
    # dead tail rows stored zeroed (reuse history erased)
    assert not np.asarray(kc2)[blk, :, 1:].any()
    # fallback applies the identical rule (public op)
    qkv = np.concatenate([q.reshape(1, -1), knew.reshape(1, -1),
                          vnew.reshape(1, -1)], -1)
    res = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc), paddle.to_tensor(vc),
        None, paddle.to_tensor(lens_), None,
        block_tables=paddle.to_tensor(tables),
        cache_k_quant_scales=paddle.to_tensor(ks),
        cache_v_quant_scales=paddle.to_tensor(vs),
        cache_quant_type=quant)
    np.testing.assert_array_equal(np.asarray(res[1]._value)[blk],
                                  np.asarray(kc2)[blk])
    np.testing.assert_allclose(np.asarray(res[3]._value)[blk], ks2[blk],
                               rtol=1e-6)


def test_int4_odd_d_padding(rng):
    """int4 nibble packing with an ODD head dim: pack/unpack round-trips
    the split-half layout (pad nibble sliced off), and the decode kernel
    attends dequantized odd-D pools correctly (read-only call vs a NumPy
    reference over the dequantized gather)."""
    D = 5
    vals = rng.integers(-7, 8, size=(4, 3, D)).astype(np.int32)
    packed = np.asarray(kv_pack(jnp.asarray(vals), "int4"))
    assert packed.shape == (4, 3, kv_packed_dim(D, "int4"))
    back = np.asarray(kv_unpack(jnp.asarray(packed), "int4", D))
    np.testing.assert_array_equal(back, vals.astype(np.float32))

    Hkv, BS = 2, 8
    lens = [9]
    kc, vc, ks, vs, tables, lens_ = _quant_pools(
        rng, lens, [1], Hkv, D, BS, "int4")
    q = rng.standard_normal((1, Hkv, D)).astype(np.float32)
    out = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens_),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs), quant="int4")
    # NumPy reference on the dequantized logical sequence
    kf = np.asarray(kv_unpack(jnp.asarray(kc), "int4", D)) * \
        ks[..., None, None]
    vf = np.asarray(kv_unpack(jnp.asarray(vc), "int4", D)) * \
        vs[..., None, None]
    T = lens[0] + 1
    seq_k = np.concatenate([kf[tables[0, j]] for j in range(2)],
                           axis=1)[:, :T]           # [Hkv, T, D]
    seq_v = np.concatenate([vf[tables[0, j]] for j in range(2)],
                           axis=1)[:, :T]
    logits = np.einsum("hd,htd->ht", q[0], seq_k) / np.sqrt(D)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("ht,htd->hd", p, seq_v)
    np.testing.assert_allclose(np.asarray(out)[0], ref, atol=2e-5,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# capacity: blocks at equal HBM bytes (the acceptance arithmetic)
# ---------------------------------------------------------------------------

def test_pool_capacity_ratios():
    """An int8 (int4) pool fits >= 1.9x (>= 3.5x) the bf16 block count
    at equal HBM bytes — computed off the engines' REAL buffer nbytes
    (quantized pools pay their scale arrays here, not in a footnote)."""
    paddle.seed(7)
    m = LlamaForCausalLM(CFG).bfloat16()
    m.eval()
    engines = {q: LLMEngine(m, **_kw(kv_cache_dtype=q, block_size=16))
               for q in (None, "int8", "int4")}
    bpb = {q: e.kv_bytes_per_block() for q, e in engines.items()}
    assert bpb[None] / bpb["int8"] >= 1.9
    assert bpb[None] / bpb["int4"] >= 3.5
    # the effective-blocks gauge tells the same story off n_blocks
    # (integer blocks: the gauge floors, so the bound floors too)
    nb = engines[None].n_blocks
    assert engines[None].kv_pool_effective_blocks() == nb
    assert engines["int8"].kv_pool_effective_blocks() >= int(1.9 * nb)
    assert engines["int4"].kv_pool_effective_blocks() >= int(3.5 * nb)
    # nbytes is the real sum over payload + scale buffers
    for q, e in engines.items():
        leaves = jax.tree_util.tree_leaves([e._k, e._v])
        assert e.kv_pool_nbytes() == sum(
            int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
            for x in leaves)


def test_constructor_errors():
    m = LlamaForCausalLM(CFG)
    m.eval()
    with pytest.raises(ValueError, match="cache_impl='paged'"):
        LLMEngine(m, cache_impl="dense", kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="unknown kv_cache_dtype"):
        LLMEngine(m, **_kw(kv_cache_dtype="fp8"))


# ---------------------------------------------------------------------------
# engine drift + bit-identity
# ---------------------------------------------------------------------------
# Wall-budget note (the PR-8/PR-11 conftest policy): every test below
# that builds MORE THAN the two drift engines rides the `slow` marker —
# each fused paged engine costs a fresh program compile on CPU, and
# tier-1 sits ~60 s under its 870 s cap. Tier-1 keeps the acceptance
# core: kernel parity, capacity, constructor errors, and the int8-vs-
# bf16 drift bound; the composition matrix and plumbing tests run in
# the full (slow-inclusive) suite.

@pytest.fixture(scope="module")
def bf16_toks(tiny_model, prompts):
    return _toks(LLMEngine(tiny_model, **_kw(kv_cache_dtype=None)),
                 prompts, 12)


@pytest.fixture(scope="module")
def int8_toks(tiny_model, prompts):
    return _toks(LLMEngine(tiny_model, **_kw()), prompts, 12)


class TestEngineDrift:
    def test_int8_greedy_matches_bf16_prefix(self, bf16_toks, int8_toks):
        """int8 KV quantization must not derail greedy output early: the
        stream matches the bf16 engine for at least the first 8 tokens
        on the tiny model (measured: all 12 match — the bar leaves
        rounding-luck margin)."""
        for ref, got in zip(bf16_toks, int8_toks):
            assert _match_prefix(ref, got) >= 8

    @pytest.mark.slow
    def test_none_dtype_bit_identical(self, tiny_model, prompts,
                                      bf16_toks):
        """kv_cache_dtype=None is the pre-quantization engine: same
        tokens AND the same carried logits buffer as a plain paged
        engine (which every existing paged tier-1 suite exercises)."""
        plain = LLMEngine(tiny_model, **_kw(kv_cache_dtype=None))
        assert _toks(plain, prompts, 12) == bf16_toks
        none_eng = LLMEngine(tiny_model, **_kw(kv_cache_dtype=None))
        assert _toks(none_eng, prompts, 12) == bf16_toks
        np.testing.assert_array_equal(np.asarray(plain._logits),
                                      np.asarray(none_eng._logits))

    @pytest.mark.slow
    def test_int4_generates_and_packs(self, tiny_model, prompts):
        """int4 serving runs end to end with nibble-packed pools (half
        the payload bytes of int8); output quality is workload-dependent
        at 4 bits, so only structure is asserted here."""
        eng = LLMEngine(tiny_model, **_kw(kv_cache_dtype="int4"))
        outs = _toks(eng, prompts)
        assert all(len(t) == 10 for t in outs)
        payload = eng._k[0][0]
        assert payload.dtype == jnp.int8
        assert payload.shape[-1] == CFG.hidden_size \
            // CFG.num_attention_heads // 2


# ---------------------------------------------------------------------------
# the composition matrix: quantized pool x engine features
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestComposition:
    """Every engine feature x the quantized pool — `slow` as a CLASS
    per the wall-budget note above (each case compiles its own fused
    programs); the matrix is the full suite's contract, tier-1 keeps
    the kernel/capacity/drift core."""

    def test_prefix_cache_token_exact_and_reuses(self, tiny_model,
                                                 prompts):
        """Quantized pool x prefix cache: shared blocks are the same
        quantized bytes the slot would have written itself, so cache
        on/off is token-EXACT — and the second run actually hits."""
        base = _toks(LLMEngine(tiny_model, **_kw()), prompts)
        pc = LLMEngine(tiny_model, **_kw(enable_prefix_cache=True))
        assert _toks(pc, prompts) == base
        assert _toks(pc, prompts) == base      # re-run: served from cache
        assert pc.stats["prefix_hit_tokens"] > 0

    def test_stride_multi_step_exact(self, tiny_model, prompts):
        """Quantized pool x readout_stride: the compiled k-step loop runs
        the same quantized merge per iteration — bit-equal tokens."""
        base = _toks(LLMEngine(tiny_model, **_kw()), prompts)
        st = LLMEngine(tiny_model, **_kw(readout_stride=4))
        assert _toks(st, prompts) == base

    def test_legacy_scheduler_exact(self, tiny_model, prompts):
        """Quantized pool x legacy scheduler: admission prefill writes
        whole chunk-aligned blocks (one absmax scale per fresh block —
        the same bytes the fused append path produces for block-aligned
        grants), so the schedulers agree token-exactly here."""
        base = _toks(LLMEngine(tiny_model, **_kw()), prompts)
        leg = LLMEngine(tiny_model, **_kw(scheduler="legacy"))
        assert _toks(leg, prompts) == base

    def test_speculative_drift_bounded(self, tiny_model, prompts):
        """Quantized pool x verify grants: rejected drafts leave
        re-rounded block scales behind (rollback truncates tables, not
        the scale history), so spec streams are drift-BOUNDED vs the
        non-spec quantized engine, not bit-equal — the documented
        policy. Rollback itself must keep the pool invariants."""
        base = _toks(LLMEngine(tiny_model, **_kw()), prompts)
        sp = LLMEngine(tiny_model, **_kw(speculative_k=3))
        outs = _toks(sp, prompts)
        for ref, got in zip(base, outs):
            assert _match_prefix(ref, got) >= 6
        sp._check_pool_invariants()

    def test_lora_adapter_exact_vs_merged(self, prompts):
        """Quantized pool x batched multi-LoRA: the adapter delta lands
        in qkv BEFORE quantization, so the batched engine quantizes the
        same values a merged-weights engine does — token-exact."""
        from paddle_tpu.serving import (AdapterStore, apply_merged,
                                        random_lora_weights)
        store = AdapterStore(CFG, rank=4)
        store.register(random_lora_weights(CFG, rank=4, seed=3,
                                           scale=0.05), alpha=2.0)

        def fresh():
            paddle.seed(7)
            m = LlamaForCausalLM(CFG)
            m.eval()
            return m

        merged = fresh()
        apply_merged(merged, store, 1)
        ref = _toks(LLMEngine(merged, **_kw()), prompts, 6)
        eng = LLMEngine(fresh(), **_kw(adapter_store=store))
        rids = [eng.add_request(p, max_new_tokens=6, adapter_id=1)
                for p in prompts]
        while eng.has_unfinished():
            eng.step()
        outs = [eng.finished_outputs.pop(r).token_ids for r in rids]
        assert outs == ref

    def test_tp_mesh_exact(self, tiny_model, prompts, tp_mesh):
        """Quantized pool x TP mesh: scale arrays shard kv-heads with
        the pools and per-head absmax is shard-local — token-exact vs
        single-chip int8."""
        from paddle_tpu.serving.cluster import tp_engine
        base = _toks(LLMEngine(tiny_model, **_kw()), prompts)
        paddle.seed(7)
        m2 = LlamaForCausalLM(CFG)
        m2.set_state_dict(tiny_model.state_dict())
        m2.eval()
        tpe = tp_engine(m2, mesh=tp_mesh, **_kw())
        assert _toks(tpe, prompts) == base

    def test_reset_rebuilds_scales_and_stitches(self, tiny_model,
                                                prompts):
        """Quantized pool x supervised restart: reset() rebuilds the
        scale arrays with the pools (zeros over zeros = the cold state),
        pool bytes are unchanged, and a committed-token re-admission
        continues the stream with the committed prefix intact. The
        post-restart SUFFIX is drift-tolerant by policy (re-prefill
        re-quantizes whole blocks where the original run merged
        incrementally)."""
        eng = LLMEngine(tiny_model, **_kw())
        base = _toks(eng, prompts)
        nbytes = eng.kv_pool_nbytes()
        eng.reset()
        assert eng.kv_pool_nbytes() == nbytes
        for pool, scale in eng._k + eng._v:
            assert pool.dtype == jnp.int8
            assert not np.asarray(scale).any()
        committed = base[0][:4]
        rid = eng.add_request(prompts[0], max_new_tokens=10,
                              committed_tokens=committed)
        while eng.has_unfinished():
            eng.step()
        out = eng.finished_outputs.pop(rid)
        assert out.token_ids[:4] == committed
        assert len(out.token_ids) == 14


# ---------------------------------------------------------------------------
# observability plumbing
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_step_record_kv_fields(tiny_model, prompts):
    """StepRecords off a quantized engine carry the pool's byte size
    (payload + scales) and storage dtype; dense engines stamp None."""
    from paddle_tpu.profiler.flight_recorder import FlightRecorder
    eng = LLMEngine(tiny_model, **_kw())
    eng.flight_recorder = FlightRecorder(capacity=64)
    eng.generate(prompts[:1], max_new_tokens=3)
    recs = eng.flight_recorder.records()
    assert recs
    for r in recs:
        assert r.kv_cache_dtype == "int8"
        assert r.kv_pool_bytes == eng.kv_pool_nbytes() > 0
        d = r.to_dict()
        assert d["kv_cache_dtype"] == "int8"
    dense = LLMEngine(tiny_model, max_batch=2, max_seq_len=64,
                      chunk_size=16, scheduler="fused")
    dense.flight_recorder = FlightRecorder(capacity=64)
    dense.generate(prompts[:1], max_new_tokens=3)
    assert all(r.kv_cache_dtype is None and r.kv_pool_bytes is None
               for r in dense.flight_recorder.records())


@pytest.mark.slow
def test_kv_pool_effective_blocks_gauge(tiny_model, prompts):
    """The serve loop samples kv_pool_effective_blocks: ~2x n_blocks on
    an int8 pool, == n_blocks unquantized."""
    from paddle_tpu.serving import AsyncLLMServer
    eng = LLMEngine(tiny_model, **_kw())
    server = AsyncLLMServer(eng, max_queue_size=4)
    server.start()
    server.submit(prompts[0], max_new_tokens=3).result(timeout=60)
    snap = server.telemetry.snapshot()
    server.stop()
    eff = snap["gauges"]["kv_pool_effective_blocks"]
    assert eff >= 1.9 * eng.n_blocks
