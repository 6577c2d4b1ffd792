"""Pallas paged-attention decode kernel: interpret-mode parity vs the
dense-gather reference (the XLA fallback inside
incubate.nn.functional.block_multihead_attention — the shipping CPU path,
not a divergent test copy), plus the GQA paged serving plumbing the kernel
unlocks (cache_impl="paged" with num_kv_heads < num_heads).

Covers the block-sparse edge cases: exact block boundaries
(len % block_size in {0, 1, bs-1}), -1 (unallocated) table entries, mixed
per-sequence lengths, GQA group sizes {1, 2, 4}, bf16 pools, and the fused
new-token write (including its scratch-block routing for -1 targets).
Large shapes ride behind the `slow` marker."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.ops.kernels.paged_attention import (
    paged_attention_append, paged_attention_decode,
    paged_attention_enabled)


def _case(rng, lens, Hq=4, Hkv=4, D=32, BS=8, MB=None, dtype=np.float32,
          spare_block=False):
    """Pools + tables covering `lens` (+1 decode position each), physical
    blocks shuffled, unallocated tail entries left at -1."""
    B = len(lens)
    lens = np.asarray(lens, np.int32)
    MB = MB or int(lens.max()) // BS + 2
    need = [int(L) // BS + 1 for L in lens]
    NB = sum(need) + 2 + (1 if spare_block else 0)
    order = rng.permutation(NB - (1 if spare_block else 0))
    tables = np.full((B, MB), -1, np.int32)
    it = iter(order)
    for b in range(B):
        for j in range(need[b]):
            tables[b, j] = next(it)
    kc = rng.standard_normal((NB, Hkv, BS, D)).astype(dtype)
    vc = rng.standard_normal((NB, Hkv, BS, D)).astype(dtype)
    q = rng.standard_normal((B, Hq, D)).astype(dtype)
    knew = rng.standard_normal((B, Hkv, D)).astype(dtype)
    vnew = rng.standard_normal((B, Hkv, D)).astype(dtype)
    return q, kc, vc, tables, lens, knew, vnew


def _dense_oracle(q, kc, vc, tables, lens, knew, vnew):
    """The shipping fallback, via the public op (flag-off is the CPU
    default; conftest asserts it)."""
    B, Hq, D = q.shape
    Hkv = kc.shape[1]
    qkv = np.concatenate([q.reshape(B, Hq * D), knew.reshape(B, Hkv * D),
                          vnew.reshape(B, Hkv * D)], axis=-1)
    out, kc2, vc2 = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc), paddle.to_tensor(vc),
        None, paddle.to_tensor(lens), None,
        block_tables=paddle.to_tensor(tables))
    return (np.asarray(out._value), np.asarray(kc2._value),
            np.asarray(vc2._value))


def test_cpu_routes_to_dense_fallback():
    """Tier-1 runs the deterministic XLA fallback; the kernel is only the
    TPU fast path (FLAGS_use_paged_attention gates it there)."""
    assert not paged_attention_enabled()


# groups: MHA and the widest GQA ratio — 2 sits between them and exercises
# no code the two ends do not (tier-1 wall: these kernels now really run)
@pytest.mark.parametrize("group", [1, 4])
def test_fused_parity_block_boundaries_and_gqa(group, rng):
    """Mixed lengths hitting len % bs in {0, 1, bs-1}, -1 tail entries,
    GQA groups — kernel (fused write) vs the dense fallback, outputs AND
    updated pools."""
    Hkv = 2
    BS = 8
    lens = [16, 17, 7, 3]  # %bs: 0, 1, bs-1, mid
    q, kc, vc, tables, lens, knew, vnew = _case(
        rng, lens, Hq=Hkv * group, Hkv=Hkv, BS=BS)
    ref_out, ref_kc, ref_vc = _dense_oracle(q, kc, vc, tables, lens,
                                            knew, vnew)
    out, kc2, vc2 = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens),
        new_k=jnp.asarray(knew), new_v=jnp.asarray(vnew))
    np.testing.assert_allclose(np.asarray(out).reshape(ref_out.shape),
                               ref_out, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kc2), ref_kc)
    np.testing.assert_array_equal(np.asarray(vc2), ref_vc)


def test_read_only_parity_prescattered(rng):
    """Non-fused form: caller already scattered the new token; kernel
    attends the same positions the dense path does."""
    q, kc, vc, tables, lens, knew, vnew = _case(rng, [9, 24, 1], Hq=4,
                                                Hkv=4)
    ref_out, ref_kc, ref_vc = _dense_oracle(q, kc, vc, tables, lens,
                                            knew, vnew)
    out = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(ref_kc), jnp.asarray(ref_vc),
        jnp.asarray(tables), jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out).reshape(ref_out.shape),
                               ref_out, rtol=2e-5, atol=2e-5)


def test_bf16_pools_parity(rng):
    """bf16 pools, fp32 in-kernel accumulation: parity vs the dense path
    at bf16-appropriate tolerance."""
    import ml_dtypes
    q, kc, vc, tables, lens, knew, vnew = _case(
        rng, [12, 31], Hq=4, Hkv=2, dtype=np.float32)
    bf = ml_dtypes.bfloat16
    q, kc, vc = q.astype(bf), kc.astype(bf), vc.astype(bf)
    knew, vnew = knew.astype(bf), vnew.astype(bf)
    ref_out, ref_kc, ref_vc = _dense_oracle(q, kc, vc, tables, lens,
                                            knew, vnew)
    out, kc2, vc2 = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens),
        new_k=jnp.asarray(knew), new_v=jnp.asarray(vnew))
    np.testing.assert_allclose(
        np.asarray(out, np.float32).reshape(ref_out.shape),
        np.asarray(ref_out, np.float32), rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(np.asarray(kc2, np.float32),
                                  np.asarray(ref_kc, np.float32))
    np.testing.assert_array_equal(np.asarray(vc2, np.float32),
                                  np.asarray(ref_vc, np.float32))


def test_invalid_write_target_routes_to_scratch_block(rng):
    """A row whose write-target table entry is -1 (the engine's freed-slot
    shape: stale lens, wiped tables) must write NO real block — the fused
    write lands in the pool's trailing scratch block, mirroring the
    fallback's out-of-range drop."""
    q, kc, vc, tables, lens, knew, vnew = _case(rng, [5, 18], Hq=2, Hkv=2,
                                                spare_block=True)
    tables[0, :] = -1  # row 0: no blocks at all
    NB = kc.shape[0]
    out, kc2, vc2 = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens),
        new_k=jnp.asarray(knew), new_v=jnp.asarray(vnew))
    ref_out, ref_kc, _ = _dense_oracle(q, kc, vc, tables, lens, knew, vnew)
    # every real (non-scratch) block identical to the drop-mode reference
    np.testing.assert_array_equal(np.asarray(kc2)[:NB - 1],
                                  ref_kc[:NB - 1])
    # row 1 (valid) is still attended exactly
    np.testing.assert_allclose(np.asarray(out)[1].reshape(-1), ref_out[1],
                               rtol=2e-5, atol=2e-5)


# ---- every kv head of a table entry in one grid step (group 1) ------------

def _decode(q, kc, vc, tables, lens, knew, vnew, fused=True):
    a = [jnp.asarray(x) for x in (q, kc, vc, tables, lens)]
    if fused:
        return paged_attention_decode(*a, new_k=jnp.asarray(knew),
                                      new_v=jnp.asarray(vnew))
    return paged_attention_decode(*a)


@pytest.mark.parametrize("hb", [4, 2])
def test_heads_a_step_decode_is_the_one_head_decode(hb, rng, monkeypatch):
    """``hb`` kv heads a grid step at a group of one: outputs and pools of
    the one-head-a-step call (block boundaries, -1 tail entries, a slot
    with no block at all, the fused write and the read-only form), the
    other heads' keys of the stacked matmul masked to exact zeros."""
    from paddle_tpu.ops.kernels import paged_attention as PA
    case = _case(rng, [16, 17, 7, 3, 30], Hq=4, Hkv=4, BS=8,
                 spare_block=True)
    case[3][3, :] = -1
    want = {}
    monkeypatch.setattr(PA, "_decode_heads_per_step", lambda *a: 1)
    for fused in (True, False):
        want[fused] = _decode(*case, fused=fused)
    monkeypatch.setattr(PA, "_decode_heads_per_step", lambda *a: hb)
    out, kc2, vc2 = _decode(*case)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[True][0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(kc2), np.asarray(want[True][1]))
    np.testing.assert_array_equal(np.asarray(vc2), np.asarray(want[True][2]))
    np.testing.assert_allclose(np.asarray(_decode(*case, fused=False)),
                               np.asarray(want[False]), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape,hb", [
    ((16, 1, 64, 128, 2, None), 16),        # ouro_worked_answers: all heads
    ((8, 4, 64, 128, 2, None), 1),          # doc_batch: grouped, as it was
    ((16, 1, 64, 128, 1, "int8"), 1),       # a quantized pool, as it was
    ((32, 1, 128, 256, 2, None), 16),       # what fits the VMEM budget
    ((16, 1, 8, 128, 2, None), 1),          # a block of half a bf16 tile
    ((1, 1, 64, 128, 2, None), 1)])
def test_decode_heads_per_step_reads_only_static_shapes(shape, hb):
    from paddle_tpu.ops.kernels.paged_attention import _decode_heads_per_step
    assert _decode_heads_per_step(*shape) == hb


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the digest is of jax 0.9.0's jaxpr")
def test_a_grouped_decode_call_traces_to_what_the_parent_traced():
    """The one-head-a-step call (what ``doc_batch``'s scans run at a group
    of 4) is the program it was before a group of 1 got a kernel of its
    own: the digest of its jaxpr, kernel body and index maps included,
    read from the parent of PR 34 under this suite's settings."""
    import hashlib
    S, BF = jax.ShapeDtypeStruct, jnp.bfloat16
    b, hq, hkv, d, nb, bs, mb = 4, 8, 2, 32, 9, 8, 4
    text = str(jax.make_jaxpr(
        lambda q, k, v, t, n, nk, nv: paged_attention_decode(
            q, k, v, t, n, new_k=nk, new_v=nv))(
        S((b, hq, d), BF), S((nb, hkv, bs, d), BF), S((nb, hkv, bs, d), BF),
        S((b, mb), jnp.int32), S((b,), jnp.int32), S((b, hkv, d), BF),
        S((b, hkv, d), BF)))
    assert (hashlib.sha256(text.encode()).hexdigest()[:16],
            len(text.splitlines())) == GROUPED_DECODE


#: see the test above
GROUPED_DECODE = ("1236e86128893468", 217)


@pytest.mark.slow
def test_large_shape_parity(rng):
    """Production-ish decode shape (B=8, 32 q heads / 8 kv heads, D=128,
    bs=64) — interpret mode is slow, keep out of tier-1."""
    lens = [511, 512, 513, 64, 1, 300, 127, 63]
    q, kc, vc, tables, lens, knew, vnew = _case(
        rng, lens, Hq=32, Hkv=8, D=128, BS=64)
    ref_out, ref_kc, ref_vc = _dense_oracle(q, kc, vc, tables, lens,
                                            knew, vnew)
    out, kc2, vc2 = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens),
        new_k=jnp.asarray(knew), new_v=jnp.asarray(vnew))
    np.testing.assert_allclose(np.asarray(out).reshape(ref_out.shape),
                               ref_out, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(kc2), ref_kc)


# ---------------------------------------------------------------------------
# append attention (q_len = chunk): the fused scheduler's mixed step
# ---------------------------------------------------------------------------

def _append_case(rng, lens, qlens, Hq=4, Hkv=2, D=32, BS=8, S=8,
                 dtype=np.float32):
    """Pools + tables covering each sequence's append window
    [lens, lens+max(qlens,1)), shuffled physical blocks, -1 tails, and a
    trailing scratch block (the -1-write drop target)."""
    B = len(lens)
    lens = np.asarray(lens, np.int32)
    qlens = np.asarray(qlens, np.int32)
    MB = int((lens + np.maximum(qlens, 1)).max()) // BS + 2
    need = [(int(l) + max(int(q), 1) - 1) // BS + 1
            for l, q in zip(lens, qlens)]
    NB = sum(need) + 2
    order = rng.permutation(NB)
    tables = np.full((B, MB), -1, np.int32)
    it = iter(order)
    for b in range(B):
        for j in range(need[b]):
            tables[b, j] = next(it)
    kc = rng.standard_normal((NB + 1, Hkv, BS, D)).astype(dtype)
    vc = rng.standard_normal((NB + 1, Hkv, BS, D)).astype(dtype)
    q = rng.standard_normal((B, S, Hq, D)).astype(dtype)
    kn = rng.standard_normal((B, S, Hkv, D)).astype(dtype)
    vn = rng.standard_normal((B, S, Hkv, D)).astype(dtype)
    return q, kc, vc, tables, lens, qlens, kn, vn


def _append_oracle(q, kc, vc, tables, lens, qlens, kn, vn):
    """The shipping dense append fallback via the public op (flag-off is
    the CPU default; conftest asserts it)."""
    B, S, Hq, D = q.shape
    Hkv = kc.shape[1]
    qkv = np.concatenate([q.reshape(B, S, Hq * D),
                          kn.reshape(B, S, Hkv * D),
                          vn.reshape(B, S, Hkv * D)], axis=-1)
    out, kc2, vc2 = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc), paddle.to_tensor(vc),
        None, paddle.to_tensor(lens), paddle.to_tensor(qlens),
        block_tables=paddle.to_tensor(tables))
    return (np.asarray(out._value), np.asarray(kc2._value),
            np.asarray(vc2._value))


def _assert_append_parity(q, kc, vc, tables, lens, qlens, kn, vn,
                          rtol=2e-5, atol=2e-5, real_blocks=None):
    """``real_blocks``: compare the pools' first that-many blocks only
    (a wiped -1 table row parks a block in the trailing scratch block,
    which the fallback drops)."""
    ref_out, ref_kc, ref_vc = _append_oracle(q, kc, vc, tables, lens,
                                             qlens, kn, vn)
    out, kc2, vc2 = paged_attention_append(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(qlens),
        jnp.asarray(kn), jnp.asarray(vn))
    B, S = q.shape[0], q.shape[1]
    for b in range(B):
        n = int(qlens[b])
        if n:   # padding rows are garbage on BOTH paths; compare valid
            np.testing.assert_allclose(
                np.asarray(out, np.float32)[b, :n].reshape(n, -1),
                np.asarray(ref_out[b, :n], np.float32), rtol=rtol,
                atol=atol)
    np.testing.assert_array_equal(
        np.asarray(kc2, np.float32)[:real_blocks],
        np.asarray(ref_kc, np.float32)[:real_blocks])
    np.testing.assert_array_equal(
        np.asarray(vc2, np.float32)[:real_blocks],
        np.asarray(ref_vc, np.float32)[:real_blocks])
    return np.asarray(out, np.float32)


@pytest.mark.parametrize("group", [1, 4])
def test_append_parity_block_boundaries_and_gqa(group, rng):
    """Append windows starting at lens % bs in {0, 1, bs-1}, grants of a
    full chunk / one token / zero (idle slot), windows spanning several
    blocks — kernel vs the dense append fallback, outputs AND pools."""
    Hkv = 2
    lens = [16, 17, 7, 3]      # %bs: 0, 1, bs-1, mid
    qlens = [8, 1, 5, 0]       # chunk / decode-like / partial / idle
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, lens, qlens, Hq=Hkv * group, Hkv=Hkv)
    _assert_append_parity(q, kc, vc, tables, lens, qlens, kn, vn)


def test_append_more_heads_than_one_interleaved_group(rng):
    """12 kv heads a grid step: a row tile's update runs them in two
    groups of 6 (``_HEADS_INTERLEAVED`` = 8 does not divide 12), the
    groups by a loop, the heads of a group by a loop unrolled whole."""
    from paddle_tpu.ops.kernels import paged_attention as pa
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [16, 17, 7, 3], [8, 1, 5, 0], Hq=12, Hkv=12)
    G, S, D, BS = 1, 8, 32, 8
    assert pa._heads_per_step(12, G, S, D, BS, D, 4, 4, 4) == 12
    assert pa._HEADS_INTERLEAVED == 8
    _assert_append_parity(q, kc, vc, tables, lens, qlens, kn, vn)


def test_append_first_chunk_from_empty(rng):
    """lens == 0 (first prefill chunk of a fresh slot) including a full
    chunk that exactly fills a block."""
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [0, 0, 8], [8, 3, 8], Hq=4, Hkv=4)
    _assert_append_parity(q, kc, vc, tables, lens, qlens, kn, vn)


def test_append_bf16_pools(rng):
    import ml_dtypes
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [12, 31], [6, 2])
    bf = ml_dtypes.bfloat16
    q, kc, vc = q.astype(bf), kc.astype(bf), vc.astype(bf)
    kn, vn = kn.astype(bf), vn.astype(bf)
    _assert_append_parity(q, kc, vc, tables, lens, qlens, kn, vn,
                          rtol=2e-2, atol=2e-2)


def test_append_idle_wiped_slot_writes_scratch_only(rng):
    """A freed slot's shape (stale lens, wiped -1 table row, q_lens 0)
    must not touch any real block — mirroring the decode kernel's
    scratch-block routing."""
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [5, 18], [0, 4], Hq=2, Hkv=2)
    tables[0, :] = -1
    NB = kc.shape[0]
    ref_out, ref_kc, ref_vc = _append_oracle(q, kc, vc, tables, lens,
                                             qlens, kn, vn)
    out, kc2, vc2 = paged_attention_append(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(qlens),
        jnp.asarray(kn), jnp.asarray(vn))
    np.testing.assert_array_equal(np.asarray(kc2)[:NB - 1],
                                  ref_kc[:NB - 1])
    np.testing.assert_allclose(np.asarray(out)[1, :4].reshape(4, -1),
                               ref_out[1, :4], rtol=2e-5, atol=2e-5)


def test_append_decode_special_case_matches_decode_kernel(rng):
    """q_lens == 1 everywhere IS the decode step: the append kernel must
    agree with the decode kernel's fused write exactly."""
    lens = [9, 24, 1]
    q, kc, vc, tables, lens_a, qlens, kn, vn = _append_case(
        rng, lens, [1, 1, 1], Hq=4, Hkv=4, S=4)
    out_a, kc_a, vc_a = paged_attention_append(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens_a), jnp.asarray(qlens),
        jnp.asarray(kn), jnp.asarray(vn))
    out_d, kc_d, vc_d = paged_attention_decode(
        jnp.asarray(q[:, 0]), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens_a),
        new_k=jnp.asarray(kn[:, 0]), new_v=jnp.asarray(vn[:, 0]))
    np.testing.assert_allclose(np.asarray(out_a)[:, 0], np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kc_a), np.asarray(kc_d))
    np.testing.assert_array_equal(np.asarray(vc_a), np.asarray(vc_d))


# The shapes the kernel's (seq_lens, q_lens)-following makes special. S = 96
# positions x 4 q heads a kv head = 384 rows = two row tiles of 192
# (_row_tile), so live-row prefixes end inside, at and across a tile, the
# 32-row short path runs, and window blocks skip the tiles they are wholly
# masked for. name -> (lens, q_lens, slot whose table row is wiped or None)
_FOLLOWS = {
    # doc_batch's mixed step in miniature: one slot ramping by a full chunk,
    # decode rows, idle slots, one of them freed (stale lens, -1 table row)
    "cell_step_miniature": ([40, 201, 77, 130, 9, 0], [96, 1, 1, 1, 0, 0], 4),
    # q_lens * G = 200, 36, 32, 196 rows against tiles of 192 and 32
    "rows_not_a_multiple_of_the_tile": ([3, 50, 64, 11], [50, 9, 8, 49],
                                        None),
    # block 8: [6, 11) lies in two blocks, [7, 19) and [15, 32) in three
    "window_straddles_two_and_three_blocks": ([6, 7, 15], [5, 12, 17], None),
    # speculative verify grants: q_lens = k + 1 drafts, shrunk per slot
    "verify_window_k_plus_1": ([33, 64, 95, 18], [5, 5, 3, 1], None),
    "first_chunk_from_empty": ([0, 0, 0], [96, 40, 1], None),
    "every_slot_idle": ([12, 0, 31], [0, 0, 0], 2),
}


@pytest.mark.parametrize("name", list(_FOLLOWS))
def test_append_follows_q_lens_and_seq_lens(name, rng):
    """Kernel (interpret mode) vs the dense append fallback on the mixes
    above: outputs of live rows AND both pools; rows of row tiles that
    never run come back as zeros."""
    from paddle_tpu.ops.kernels.paged_attention import _row_tile
    lens, qlens, wiped = _FOLLOWS[name]
    S, G = 96, 4
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, lens, qlens, Hq=2 * G, Hkv=2, S=S)
    if wiped is not None:
        tables[wiped, :] = -1
    out = _assert_append_parity(
        q, kc, vc, tables, lens, qlens, kn, vn,
        real_blocks=None if wiped is None else kc.shape[0] - 1)
    tr = _row_tile(G, S)
    assert tr == 192
    for b, n in enumerate(qlens):
        first_idle = -(-int(n) * G // tr) * tr // G   # position, tile-aligned
        assert not out[b, first_idle:].any()


@pytest.mark.parametrize("G,S,BS", [(4, 96, 8), (4, 128, 16), (3, 128, 8),
                                    (1, 8, 8), (8, 64, 32)])
def test_append_tile_steps_is_the_brute_force_count(G, S, BS, rng):
    """``append_tile_steps`` (the counter behind ``engine.stats``, and the
    kernel's own skip rule through ``_tile_span``) against a count made
    row by row and key by key: a (row tile, table entry) pair runs iff the
    slot appends something, the entry lies at or before the block of the
    window's last position, and some LIVE row of the tile sees some key of
    the entry's block."""
    from paddle_tpu.ops.kernels.paged_attention import (
        _row_tile, append_tile_steps)
    tr, MB = _row_tile(G, S), 20
    n_tiles = G * S // tr
    for _ in range(12):
        B = int(rng.integers(1, 6))
        lens = rng.integers(0, MB * BS - S, size=B)
        qlens = rng.integers(0, S + 1, size=B)
        qlens[rng.integers(0, B)] = rng.choice([0, 1, S])
        run = 0
        for L, n in zip(lens, qlens):
            if n == 0:
                continue
            j_last = min((L + n - 1) // BS, MB - 1)
            for jj in range(j_last + 1):
                keys = jj * BS + np.arange(BS)
                for t in range(n_tiles):
                    r = np.arange(t * tr, min((t + 1) * tr, n * G))
                    if r.size and (keys[None, :] <= L + r[:, None] // G).any():
                        run += 1
        assert append_tile_steps(lens, qlens, G, S, BS, MB) == \
            (run, B * MB * n_tiles)


# ---------------------------------------------------------------------------
# the packed entry: a mixed step's rows on one axis, (start, q_lens, seq_lens)
# ---------------------------------------------------------------------------

def _pack(x, qlens, T, gap=0):
    """The per-slot ``x[B, S, ...]`` on one row axis of ``T`` rows: slot
    b's first ``qlens[b]`` rows from ``start[b]`` (the grants before it,
    and ``gap`` rows of nobody's a slot before it) on, the rows that hold
    no token random (the kernel must not let them into a live row, and
    hands them back zero)."""
    start = np.cumsum(qlens) - qlens + gap * np.arange(len(qlens))
    out = np.random.default_rng(int(np.sum(qlens)) + T).standard_normal(
        (T,) + x.shape[2:]).astype(x.dtype)
    for b, n in enumerate(qlens):
        out[start[b]:start[b] + n] = x[b, :n]
    return out, start.astype(np.int32)


def _assert_packed_is_per_slot(q, kc, vc, tables, lens, qlens, kn, vn, T,
                               gap=0, **quant):
    """The packed entry against the per-slot entry on the same step:
    live rows and every pool (and scale) BIT-equal, zeros on the rows
    that hold no token. Returns the two outputs."""
    S = q.shape[1]
    dev = [jnp.asarray(a) for a in (kc, vc, tables, lens, qlens)]
    qkw = {k: jnp.asarray(v) if k.endswith("scale") else v
           for k, v in quant.items()}
    want = paged_attention_append(jnp.asarray(q), *dev, jnp.asarray(kn),
                                  jnp.asarray(vn), **qkw)
    (qp, start), (kp, _), (vp, _) = (_pack(x, qlens, T, gap)
                                     for x in (q, kn, vn))
    got = paged_attention_append(
        jnp.asarray(qp), *dev, jnp.asarray(kp), jnp.asarray(vp),
        start=jnp.asarray(start), width=S, **qkw)
    assert got[0].shape == (T,) + q.shape[2:]
    for a, b in zip(want[1:], got[1:]):         # pools, then scales
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    o, op = np.asarray(want[0], np.float32), np.asarray(got[0], np.float32)
    live = np.zeros(T, bool)
    for b, n in enumerate(qlens):
        live[start[b]:start[b] + n] = True
        np.testing.assert_array_equal(op[start[b]:start[b] + n], o[b, :n])
    assert not op[~live].any()
    return o, op


# a step's (lens, q_lens, the slot whose table row is wiped or None), at
# block 8 and a chunk of 16. A slot's first packed row is the grants before
# it, so the starts are whatever the mix makes them: 16, 17, 18 after a full
# chunk, 5 after a tail. Times the group that is 16 / 68 / 136, 5 / 20 / 40:
# on and off a 16-row tile at every group
_PACKED = {
    "a_full_chunk_beside_decode_rows": ([24, 17, 7, 40], [16, 1, 1, 1], None),
    "a_chunks_tail_then_decode_rows": ([32, 9, 63, 5], [5, 1, 1, 1], None),
    "idle_slots_between_the_live": ([3, 11, 30, 8, 2], [0, 7, 0, 1, 0],
                                    None),
    "two_partial_chunks": ([0, 21, 50, 13], [9, 1, 11, 1], None),
    "a_window_crosses_two_block_boundaries": ([6, 15, 7, 3], [13, 1, 3, 0],
                                              None),
    "a_freed_slot_with_a_wiped_table_row": ([5, 18, 9], [0, 4, 1], 0),
    "every_slot_idle": ([12, 0, 31], [0, 0, 0], 2),
}


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("name", list(_PACKED))
def test_the_packed_append_is_the_per_slot_append_bit_for_bit(name, group,
                                                              rng):
    """``paged_attention_append`` on a mixed step's packed rows ``[T, Hq,
    D]`` with ``start`` prefetched (the interpreted kernel) against its
    per-slot entry: the same live rows, the same pools, bit for bit, and
    zeros elsewhere, whatever tile a slot's first row falls into."""
    lens, qlens, wiped = _PACKED[name]
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, lens, qlens, Hq=2 * group, Hkv=2, S=16)
    if wiped is not None:
        tables[wiped, :] = -1
    _assert_packed_is_per_slot(q, kc, vc, tables, lens, qlens, kn, vn, T=32)


def test_the_packed_append_over_bf16_pools_and_two_row_tiles(rng):
    """The served form: bf16 q against bf16 pools (the operands go to the
    MXU as stored), a slot of two row tiles (96 x 4 = 384 rows = 2 x 192)
    after three decode rows, so its tiles start 12 rows into the packed
    axis' grid and its last tile reaches past the axis' last row."""
    import ml_dtypes
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [17, 40, 9, 64], [1, 1, 1, 96], Hq=8, Hkv=2, S=96)
    bf = lambda a: a.astype(ml_dtypes.bfloat16)  # noqa: E731
    _assert_packed_is_per_slot(bf(q), bf(kc), bf(vc), tables, lens, qlens,
                               bf(kn), bf(vn), T=112)


def test_the_packed_append_needs_no_more_rows_than_it_is_given(rng):
    """A chunk that ends at the row axis' last row (16 rows, a slot's
    ``width`` of them) reads its K/V window from a clamped offset: the
    same rows and pools as the per-slot entry."""
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [9, 30], [3, 13], Hq=4, Hkv=2, S=16)
    _assert_packed_is_per_slot(q, kc, vc, tables, lens, qlens, kn, vn, T=16)


@pytest.mark.parametrize("group", [1, 4])
def test_the_packed_append_reads_start_and_not_a_running_sum(group, rng):
    """``start`` is read as given: three rows of nobody's between one
    slot's rows and the next's (no exclusive running sum of ``q_lens``)
    are left out of every live row and come back zero."""
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [24, 17, 7, 40], [9, 1, 0, 5], Hq=2 * group, Hkv=2, S=16)
    _assert_packed_is_per_slot(q, kc, vc, tables, lens, qlens, kn, vn, T=32,
                               gap=3)


def test_the_packed_append_is_refused_without_its_width(rng):
    """The row tile is derived from the most rows a slot may hold, which
    the row axis does not say: the packed entry without ``width``, or
    with a ``start`` that is not a slot's one number, is refused, by the
    kernel module and by the op."""
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [9, 30], [3, 13], Hq=4, Hkv=2, S=16)
    (qp, start), (kp, _), (vp, _) = (_pack(x, qlens, 16) for x in (q, kn, vn))
    dev = [jnp.asarray(a) for a in (kc, vc, tables, lens, qlens)]
    new = [jnp.asarray(kp), jnp.asarray(vp)]
    with pytest.raises(AssertionError, match="width"):
        paged_attention_append(jnp.asarray(qp), *dev, *new,
                               start=jnp.asarray(start))
    with pytest.raises(AssertionError):
        paged_attention_append(jnp.asarray(qp), *dev, *new, width=16,
                               start=jnp.asarray(np.append(start, 16)))
    qkv = paddle.to_tensor(np.concatenate(
        [x.reshape(16, -1) for x in (qp, kp, vp)], axis=-1))
    op = [paddle.to_tensor(a) for a in (kc, vc)] + [
        None, paddle.to_tensor(lens), paddle.to_tensor(qlens)]
    with pytest.raises(ValueError, match="max_seq_len"):
        IF.block_multihead_attention(
            qkv, *op, cu_seqlens_q=paddle.to_tensor(start),
            block_tables=paddle.to_tensor(tables))
    with pytest.raises(ValueError, match="cu_seqlens_q"):
        IF.block_multihead_attention(
            qkv, *op, cu_seqlens_q=paddle.to_tensor(np.append(start, 16)),
            block_tables=paddle.to_tensor(tables), max_seq_len=16)


@pytest.mark.parametrize("G,S,BS", [(4, 96, 8), (1, 256, 16), (8, 64, 32),
                                    (3, 128, 8)])
def test_append_tile_steps_follow_the_packed_axis_own_tiles(G, S, BS, rng):
    """``append_tile_steps`` with ``start``: a (row tile, table entry)
    pair runs iff some live row of the TILE OF THE PACKED AXIS sees some
    key of the entry's block, a slot's rows lying ``off`` = ``start * G
    mod 16`` into its first tile and a tile 16 rows longer than the
    per-slot entry's, so that a slot never has more tiles than its rows
    fill there; ``grid`` is what it was."""
    from paddle_tpu.ops.kernels.paged_attention import (
        _packed_row_tile, _row_tile, append_tile_steps)
    tr, tile, MB = _row_tile(G, S), _packed_row_tile(G, S), 20
    assert tile == tr + 16
    for _ in range(12):
        B = int(rng.integers(1, 6))
        lens = rng.integers(0, MB * BS - S, size=B)
        qlens = rng.integers(0, S + 1, size=B)
        qlens[rng.integers(0, B)] = rng.choice([0, 1, S])
        start = np.cumsum(qlens) - qlens
        run = 0
        for L, n, at in zip(lens, qlens, start):
            if n == 0:
                continue
            off = at * G % 16
            n_tiles = -(-(n * G + off) // tile)
            assert n_tiles <= -(-n * G // tr)
            j_last = min((L + n - 1) // BS, MB - 1)
            for jj in range(j_last + 1):
                keys = jj * BS + np.arange(BS)
                for t in range(n_tiles):
                    r = np.arange(max(t * tile, off),
                                  min((t + 1) * tile, n * G + off))
                    if r.size and (keys[None, :]
                                   <= L + (r[:, None] - off) // G).any():
                        run += 1
        assert append_tile_steps(lens, qlens, G, S, BS, MB, start) == \
            (run, B * MB * (G * S // tr))


@pytest.mark.parametrize("gap", [0, 1], ids=["a_running_sum", "gapped"])
def test_the_packed_form_of_the_op_is_its_per_slot_form(gap, rng):
    """``block_multihead_attention`` on ``qkv [T, W]`` with
    ``cu_seqlens_q`` (the reference's own layout; on a CPU the dense
    fallback on the view sliced out at ``cu_seqlens_q``, which it reads
    as given) returns the ``[B, S, W]`` form's live rows and pools, and
    zeros on the rows that hold no token."""
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, [24, 17, 7, 40], [8, 1, 0, 3], Hq=4, Hkv=2)
    ref_out, ref_kc, ref_vc = _append_oracle(q, kc, vc, tables, lens, qlens,
                                             kn, vn)
    B, S, T = q.shape[0], q.shape[1], 16
    (qp, start), (kp, _), (vp, _) = (_pack(x, qlens, T, gap)
                                     for x in (q, kn, vn))
    qkv = np.concatenate([qp.reshape(T, -1), kp.reshape(T, -1),
                          vp.reshape(T, -1)], axis=-1)
    out, kc2, vc2 = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc), paddle.to_tensor(vc),
        None, paddle.to_tensor(lens), paddle.to_tensor(qlens),
        cu_seqlens_q=paddle.to_tensor(start),
        block_tables=paddle.to_tensor(tables), max_seq_len=S)
    out = np.array(out._value)
    assert out.shape == (T, ref_out.shape[-1])
    np.testing.assert_array_equal(np.asarray(kc2._value), ref_kc)
    np.testing.assert_array_equal(np.asarray(vc2._value), ref_vc)
    for b, n in enumerate(qlens):
        np.testing.assert_array_equal(out[start[b]:start[b] + n],
                                      ref_out[b, :n])
        out[start[b]:start[b] + n] = 0
    assert not out.any()


# ---------------------------------------------------------------------------
# the operand rule: stored 16-bit operands go to the MXU as they are stored
# ---------------------------------------------------------------------------

def _bf16_ulp(x):
    """Spacing of bfloat16 (8 significant bits) at the magnitude of x."""
    x = np.maximum(np.abs(np.asarray(x, np.float32)), np.float32(2.0 ** -126))
    return np.exp2(np.floor(np.log2(x)) - 7).astype(np.float32)


@pytest.fixture()
def f32_form(monkeypatch):
    """Call it to pin ``_mxu_dtype`` to float32: the kernels then run
    the form they had before the rule (every operand converted, the scale
    on ``q``) on whatever they are fed. ``_append_call`` is jitted and
    does not see a patched global: its cache is dropped at each change."""
    from paddle_tpu.ops.kernels import paged_attention as pa

    def pin():
        monkeypatch.setattr(pa, "_mxu_dtype",
                            lambda *_: jnp.dtype(jnp.float32))
        pa._append_call.clear_cache()
    yield pin
    monkeypatch.undo()
    pa._append_call.clear_cache()


# name -> (kernel, lens, q_lens); S = 16 positions x 4 q heads a kv head =
# 64 rows = one row tile, the 32-row short path under it
_STORED_16BIT = {
    "append_chunk_over_history": ("append", [40, 19], [16, 9]),
    "append_chunk_from_empty": ("append", [0, 0], [16, 5]),
    "append_decode_rows": ("append", [33, 7, 64], [1, 1, 1]),
    "append_verify_window_of_4": ("append", [33, 64, 18], [4, 4, 3]),
    "append_idle_slot": ("append", [12, 25], [0, 7]),
    "decode_kernel": ("decode", [16, 17, 7, 3], None),
}


@pytest.mark.parametrize("name", list(_STORED_16BIT))
def test_bf16_operands_give_what_the_f32_product_gave(name, rng, f32_form):
    """bf16 ``q``, pools and new K/V through the 16-bit form (``QK^T`` and
    the merge on the arrays as stored, the scale on the f32 scores)
    against (1) the f32 form of the same kernel on the same bf16 arrays,
    which is what the kernel computed before the rule: equal to f32
    rounding, so the bf16 outputs differ by at most one of their own ulps,
    and only where that rounding crosses a bf16 boundary; and (2) the same
    values fed as f32 arrays: there ``P`` meets ``V`` unrounded where the
    bf16 pools' ``P`` is rounded to bf16 as it always was (at most 2^-9 of
    the largest |V|), and the output is not rounded, so the two agree
    within one bf16 ulp at the scale of the values attended. The pools
    come back bit-equal in all three."""
    import ml_dtypes
    kernel, lens, qlens = _STORED_16BIT[name]
    if kernel == "append":
        q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
            rng, lens, qlens, Hq=8, Hkv=2, S=16)
        live = [(b, slice(0, int(n))) for b, n in enumerate(qlens) if n]
    else:
        q, kc, vc, tables, lens, kn, vn = _case(rng, lens, Hq=8, Hkv=2)
        live = [(b, slice(None)) for b in range(len(lens))]

    def run(dt):
        """The kernel on the case's values rounded to bf16, held as dt."""
        q_, kc_, vc_, kn_, vn_ = (
            jnp.asarray(x.astype(ml_dtypes.bfloat16).astype(dt))
            for x in (q, kc, vc, kn, vn))
        if kernel == "append":
            return paged_attention_append(
                q_, kc_, vc_, jnp.asarray(tables), jnp.asarray(lens),
                jnp.asarray(qlens), kn_, vn_)
        return paged_attention_decode(
            q_, kc_, vc_, jnp.asarray(tables), jnp.asarray(lens),
            new_k=kn_, new_v=vn_)

    got = [np.asarray(x, np.float32) for x in run(ml_dtypes.bfloat16)]
    fed_f32 = [np.asarray(x) for x in run(np.float32)]
    f32_form()
    before = [np.asarray(x, np.float32) for x in run(ml_dtypes.bfloat16)]

    v_scale = _bf16_ulp(max(np.abs(fed_f32[2]).max(), np.abs(vn).max()))
    for b, rows in live:
        o, o_before, o_f32 = (x[0][b, rows] for x in (got, before, fed_f32))
        assert np.all(np.abs(o - o_before) <= _bf16_ulp(o_before))
        assert np.mean(o != o_before) < 0.01
        assert np.all(np.abs(o - o_f32) <= v_scale)
    for other in (before, fed_f32):
        np.testing.assert_array_equal(got[1], other[1])
        np.testing.assert_array_equal(got[2], other[2])


def _kernel_dot_operands(fn, *args):
    """``(lhs dtype, rhs dtype)`` of every ``dot_general`` in the program
    ``fn`` traces to, the Pallas kernel's body included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(tuple(v.aval.dtype for v in eqn.invars))
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) \
                        else (param,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


_BF, _F16, _F32 = jnp.bfloat16, jnp.float16, jnp.float32


@pytest.mark.parametrize("lhs,rhs,quant,want", [
    (_BF, _BF, None, _BF),         # the serving cells: stored bf16
    (_F16, _F16, None, _F16),
    (_F32, _BF, None, _F32),       # f32 q (the CPU parity tests)
    (_BF, _F32, None, _F32),
    (_BF, _F16, None, _F32),       # mismatched 16-bit floats
    (_F32, _F32, None, _F32),
    (_BF, _BF, "int8", _F32),      # a dequantized block is f32
    (_BF, jnp.int8, "int8", _F32),
    (_BF, jnp.int8, "int4", _F32),
    (jnp.int16, jnp.int16, None, _F32),
])
def test_mxu_operand_rule(lhs, rhs, quant, want):
    from paddle_tpu.ops.kernels.paged_attention import _mxu_dtype
    assert _mxu_dtype(lhs, rhs, quant) == jnp.dtype(want)


@pytest.mark.parametrize("q_dt,pool_dt,quant,want", [
    # QK^T, the merge and P.V of the window and history walks, all on
    # the stored arrays
    (_BF, _BF, None, {(_BF, _BF)}),
    # f32 q: the f32 product as before; the chunk's K/V is cast to the
    # pool's dtype on its way in, as before, so the merge's operands are
    # stored bf16, and P was always cast to V's dtype
    (_F32, _BF, None, {(_F32, _F32), (_BF, _BF)}),
    (_F32, _F32, None, {(_F32, _F32)}),
    # a quantized pool: nothing dequantized reaches the MXU in 16 bits
    (_BF, jnp.int8, "int8", {(_F32, _F32)}),
])
def test_kernels_take_the_form_the_rule_names(q_dt, pool_dt, quant, want):
    """The form a program takes, read from the traced kernels: the dtypes
    the append and the decode kernel hand their matmuls."""
    B, S, Hq, Hkv, D, NB, BS, MB = 2, 16, 8, 2, 32, 6, 8, 4
    pool = jnp.zeros((NB, Hkv, BS, D), pool_dt)
    scales = dict(k_scale=jnp.ones((NB, Hkv), _F32),
                  v_scale=jnp.ones((NB, Hkv), _F32),
                  quant=quant) if quant else {}
    tables = jnp.zeros((B, MB), jnp.int32)
    lens = jnp.zeros((B,), jnp.int32)
    want = {tuple(jnp.dtype(d) for d in pair) for pair in want}
    dots = _kernel_dot_operands(
        lambda q, k, v, nk, nv: paged_attention_append(
            q, k, v, tables, lens, lens + 1, nk, nv, **scales),
        jnp.zeros((B, S, Hq, D), q_dt), pool, pool,
        jnp.zeros((B, S, Hkv, D), q_dt), jnp.zeros((B, S, Hkv, D), q_dt))
    assert set(dots) == want and len(dots) >= 6
    dots = _kernel_dot_operands(
        lambda q, k, v, nk, nv: paged_attention_decode(
            q, k, v, tables, lens, new_k=nk, new_v=nv, **scales),
        jnp.zeros((B, Hq, D), q_dt), pool, pool,
        jnp.zeros((B, Hkv, D), q_dt), jnp.zeros((B, Hkv, D), q_dt))
    assert set(dots) == want and len(dots) == 2     # QK^T and P.V


@pytest.mark.slow
def test_append_large_shape_parity(rng):
    """Serving-ish append shape (GQA 32/8 heads, D=128, bs=64, chunk 64)
    — interpret mode is slow, keep out of tier-1."""
    lens = [511, 512, 64, 0]
    qlens = [64, 1, 33, 64]
    q, kc, vc, tables, lens, qlens, kn, vn = _append_case(
        rng, lens, qlens, Hq=32, Hkv=8, D=128, BS=64, S=64)
    _assert_append_parity(q, kc, vc, tables, lens, qlens, kn, vn,
                          rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the GQA paged path the kernel unlocks (num_kv_heads < num_heads)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gqa_model():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(11)
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def test_generate_paged_gqa_matches_static(gqa_model):
    """cache_impl="paged" now accepts GQA models; greedy output must match
    the static dense cache token-for-token."""
    rng = np.random.default_rng(5)
    ids = paddle.to_tensor(rng.integers(1, 96, size=(2, 9)))
    a = gqa_model.generate(ids, max_new_tokens=6, cache_impl="static")
    b = gqa_model.generate(ids, max_new_tokens=6, cache_impl="paged",
                           block_size=4)
    np.testing.assert_array_equal(a.numpy(), b.numpy())


def test_engine_paged_gqa_parity_with_dense(gqa_model):
    """The paged serving engine accepts GQA models and stays token-exact
    vs the dense-slot engine."""
    from paddle_tpu.inference import LLMEngine
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 96, size=(n,)).astype(np.int32)
               for n in (9, 14)]
    dense = LLMEngine(gqa_model, max_batch=2, max_seq_len=64, chunk_size=16)
    ref = [o.token_ids for o in dense.generate(prompts, max_new_tokens=8)]
    paged = LLMEngine(gqa_model, max_batch=2, max_seq_len=64, chunk_size=16,
                      cache_impl="paged", block_size=8)
    out = [o.token_ids for o in paged.generate(prompts, max_new_tokens=8)]
    assert out == ref


# ---------------------------------------------------------------------------
# _filter_logits top-k fast path (satellite: no full-vocab sort when top_k
# already bounds the candidate set)
# ---------------------------------------------------------------------------

def _filter_reference(logits, temp, top_k, top_p):
    """The pre-optimization pipeline: top-k mask, then nucleus cutoff over
    a FULL descending sort of the masked logits."""
    logits = logits.astype(jnp.float32) / temp
    V = logits.shape[-1]
    if top_k and 0 < top_k < V:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    sorted_desc = -jnp.sort(-logits, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


@pytest.mark.parametrize("top_k,top_p", [(8, 0.5), (8, 0.9), (4, 0.99),
                                         (16, 0.3)])
def test_filter_logits_topk_slice_matches_full_sort(top_k, top_p, rng):
    from paddle_tpu.models.llama import _filter_logits
    logits = jnp.asarray(rng.standard_normal((5, 333)), jnp.float32) * 3.0
    got = _filter_logits(logits, jnp.float32(0.8), top_k, jnp.float32(top_p))
    want = _filter_reference(logits, jnp.float32(0.8), top_k,
                             jnp.float32(top_p))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_filter_logits_no_topk_unchanged(rng):
    from paddle_tpu.models.llama import _filter_logits
    logits = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
    got = _filter_logits(logits, jnp.float32(1.0), 0, jnp.float32(0.7))
    want = _filter_reference(logits, jnp.float32(1.0), 0, jnp.float32(0.7))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
