"""The grouped expert product (``ops/kernels/grouped_expert_matmul.py``)
in interpret mode on the CPU: the Pallas kernel against
``jax.lax.ragged_dot`` and against plain einsums an expert at a time, the
walk it is handed, the rule on shapes, and the two counts that say how
much of the held weights a layer has to read."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.kernels import grouped_expert_matmul as gmm
from paddle_tpu.ops.kernels import moe_dropless

F32, BF = jnp.float32, jnp.bfloat16


def operands(rows, h, f, e, dtype, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(rows, h)), dtype)
    wg, wu = (jnp.asarray(rng.normal(size=(e, h, f)) * 0.1, dtype)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, h)) * 0.1, dtype)
    return xs, wg, wu, wd


def plain_ffn(xs, wg, wu, wd, sizes):
    """An expert at a time, float32 einsums: no grouped primitive."""
    out, at = np.zeros((xs.shape[0], wd.shape[2]), np.float32), 0
    for g, n in enumerate(sizes):
        x = xs[at:at + n].astype(F32)
        gate = jnp.einsum("rh,hf->rf", x, wg[g].astype(F32), precision="highest")
        up = jnp.einsum("rh,hf->rf", x, wu[g].astype(F32), precision="highest")
        act = (jax.nn.silu(gate) * up).astype(xs.dtype).astype(F32)
        out[at:at + n] = jnp.einsum("rf,fh->rh", act, wd[g].astype(F32),
                                    precision="highest")
        at += n
    return out


#: (rows, h, f, sizes, dtype, row tile or None for the rule's)
CASES = {
    "an_empty_expert_in_the_middle": (64, 128, 256, [10, 0, 30, 5], F32, None),
    "all_rows_on_one_expert": (64, 128, 256, [0, 0, 64, 0], F32, None),
    "no_held_row": (64, 128, 256, [0, 0, 0, 0], F32, None),
    "a_group_boundary_inside_a_row_tile": (64, 128, 128, [3, 7, 9, 2], F32, 16),
    "a_height_that_is_no_multiple_of_the_row_tile":
        (50, 128, 128, [17, 3, 0, 30], F32, 16),
    "an_expert_over_several_row_tiles": (96, 128, 128, [1, 70, 0, 20], F32, 8),
    "bf16_operands": (96, 256, 128, [17, 3, 0, 30, 1, 1, 0, 9], BF, None),
    "bf16_a_wide_row_tile": (96, 128, 384, [40, 0, 41, 2], BF, 64),
    "several_column_tiles": (32, 128, 512, [5, 0, 20, 1], F32, None),
}


@pytest.mark.parametrize("case", CASES)
def test_kernel_against_ragged_dot_and_plain_einsums(case, monkeypatch):
    rows, h, f, sizes, dtype, tm = CASES[case]
    e = len(sizes)
    if case == "several_column_tiles":      # a budget that splits the width
        monkeypatch.setattr(gmm, "_VMEM_BUDGET", 300 << 10)
        assert gmm._col_tile(8, h, f, 2, 4, 4) < f
    xs, wg, wu, wd = operands(rows, h, f, e, dtype)
    tm = tm or gmm.row_tile(rows, h, f, e, dtype)
    sz = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(gmm._ffn_call(xs, wg, wu, wd, sz, tm=tm, interpret=True))
    n = sum(sizes)
    assert got.shape == (rows, h) and got.dtype == np.float32
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == F32 \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        got[:n], np.asarray(gmm._ragged_ffn(xs, wg, wu, wd, sz))[:n], **tol)
    np.testing.assert_allclose(got[:n], plain_ffn(xs, wg, wu, wd, sizes)[:n],
                               **tol)


@pytest.mark.parametrize("dtype", [F32, BF])
def test_gate_and_up_in_one_call_equal_the_two_call_form(dtype):
    rows, h, f, sizes, tm = 64, 128, 256, [9, 0, 33, 11], 16
    xs, wg, wu, _ = operands(rows, h, f, len(sizes), dtype, seed=3)
    visits = gmm._visits(jnp.asarray(sizes, jnp.int32), rows, tm)
    fused = gmm._call(xs, (wg, wu), visits, tm, dtype, True)
    gate = gmm._call(xs, (wg,), visits, tm, F32, True)
    up = gmm._call(xs, (wu,), visits, tm, F32, True)
    two = (jax.nn.silu(gate) * up).astype(dtype)
    n = sum(sizes)
    # the same float32 products and the same one cast: equal, not close
    np.testing.assert_array_equal(np.asarray(fused[:n].astype(F32)),
                                  np.asarray(two[:n].astype(F32)))


@pytest.mark.parametrize("sizes,rows,tm,want", [
    # expert 0 fills tile 0 and shares tile 1 with expert 2; expert 1 empty
    ([20, 0, 8, 4], 48, 16, [(0, 0), (0, 1), (2, 1), (3, 1)]),
    ([0, 0, 0, 0], 32, 16, []),
    ([0, 0, 5, 0], 32, 16, [(2, 0)]),
    # more held assignments than the product is high: the walk stops at it
    ([30, 30, 30], 32, 16, [(0, 0), (0, 1), (1, 1)]),
])
def test_the_walk_visits_every_tile_expert_pair_that_shares_a_row(
        sizes, rows, tm, want):
    gid, tid, starts, ends, n = gmm._visits(jnp.asarray(sizes, jnp.int32),
                                            rows, tm)
    n = int(n)
    assert n == len(want)
    assert list(zip(np.asarray(gid)[:n], np.asarray(tid)[:n])) == want
    assert gid.shape[0] == -(-rows // tm) + len(sizes) - 1
    # entries past the live visits repeat the last live one: in range
    # wherever an index map is asked, and no block changes under them
    assert (np.asarray(gid)[n:] == (want[-1][0] if want else len(sizes) - 1)
            ).all()
    assert (np.asarray(tid)[n:] == (want[-1][1] if want else 0)).all()
    assert int(ends[-1]) == min(sum(sizes), rows)


def test_the_rule_on_shapes_and_the_row_tile():
    assert gmm.serves(2304, 1024) and gmm.serves(1536, 5120)
    assert not gmm.serves(16, 8) and not gmm.serves(128, 96)
    # the cells' mixed steps and scans, bf16
    assert gmm.row_tile(2104, 2304, 1024, 64, BF) == gmm.row_tile(
        2176, 2304, 1024, 64, BF)
    assert gmm.row_tile(64, 2304, 1024, 64, BF) == 16
    assert gmm.row_tile(48, 5120, 1536, 40, BF) == 16
    assert gmm.row_tile(48, 128, 128, 40, F32) == 8
    assert gmm.row_tile(10 ** 6, 128, 128, 4, BF) == gmm._ROW_TILE_MAX
    # a toy model's widths: XLA's grouped matmul and its height of 8
    assert gmm.row_tile(14, 16, 8, 8, F32) == 8
    # a weight block fits the budget double-buffered at both cells' widths
    for tm, k, n, n_w, osz in ((64, 2304, 1024, 2, 2), (64, 1024, 2304, 1, 4),
                               (128, 5120, 1536, 2, 2),
                               (128, 1536, 5120, 1, 4)):
        tn = gmm._col_tile(tm, k, n, n_w, 2, osz)
        assert n % tn == 0 and tn % 128 == 0
        assert 2 * n_w * k * tn * 2 <= gmm._VMEM_BUDGET


def routed(n, h, f, e, k, dtype, seed=5):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, h)), dtype)
    wg, wu = (jnp.asarray(rng.normal(size=(e, h, f)) * 0.1, dtype)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, h)) * 0.1, dtype)
    wr = jnp.asarray(rng.normal(size=(h, e)), F32)
    idx, w = moe_dropless.route(x, wr, jnp.zeros((e,)), k, 1.0)
    return x, idx, w, wg, wu, wd


@pytest.mark.parametrize("h,f,path", [(128, 128, "kernel"), (16, 8, "ragged")])
def test_a_layer_gives_the_same_on_both_sides_of_the_rule(h, f, path,
                                                          monkeypatch):
    """``held_expert_ffn`` whole, dead rows and an absent share of the
    experts included, on widths the kernel serves and on widths it leaves
    to ``ragged_dot``: the same as an expert at a time."""
    calls = []
    real = gmm._ffn_call
    monkeypatch.setattr(gmm, "_ffn_call",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    n, e, k, live_n = 40, 8, 2, 23
    x, idx, w, wg, wu, wd = routed(n, h, f, e, k, F32)
    live = jnp.arange(n) < live_n
    held = slice(2, 6)                      # experts 2..5 of the 8
    y, counts = moe_dropless.held_expert_ffn(
        x, idx, w, live, wg[held], wu[held], wd[held], 2, rows=live_n * k)
    assert bool(calls) == (path == "kernel")
    want = np.zeros((n, h), np.float32)
    for t in range(live_n):
        for j in range(k):
            g = int(idx[t, j])
            if 2 <= g < 6:
                one = plain_ffn(x[t:t + 1], wg[g:g + 1], wu[g:g + 1],
                                wd[g:g + 1], [1])
                want[t] += float(w[t, j]) * one[0]
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(y)[live_n:].any()   # rows past the live: untouched
    counts = dict(zip(moe_dropless.COUNTERS, np.asarray(counts)))
    assert counts["moe_assignments_dropped"] == 0
    assert counts["moe_rows_computed"] % (8 if path == "ragged" else 1) == 0


def test_the_two_counts_on_a_hand_made_routing():
    """8 rows x 2 experts a row over 6 published experts, this chip holds
    experts 1..4: the counts say which of the four got a row."""
    n, h, f = 8, 128, 128
    x, _, _, wg, wu, wd = routed(n, h, f, 4, 2, F32)
    idx = jnp.asarray([[1, 5], [1, 0], [3, 1], [0, 5],
                       [3, 3], [5, 0], [1, 3], [0, 0]], jnp.int32)
    # (a row may not name one expert twice in a real routing; row 4 does,
    # to show that assignments are counted and experts are not twice)
    w = jnp.full((n, 2), 0.5, F32)
    live = jnp.asarray([1, 1, 1, 1, 1, 1, 0, 1], bool)   # row 6 is dead
    _, counts = moe_dropless.held_expert_ffn(x, idx, w, live, wg, wu, wd, 1,
                                             rows=n * 2)
    counts = dict(zip(moe_dropless.COUNTERS, np.asarray(counts)))
    # held: expert 1 from rows 0, 1, 2; expert 3 from rows 2, 4, 4;
    # experts 2 and 4 got nothing
    assert counts["moe_assignments"] == 14
    assert counts["moe_assignments_held"] == 6
    assert counts["moe_expert_peak"] == 3
    assert counts["moe_rows_held"] == 4
    assert counts["moe_experts_nonempty"] == 2
    assert counts["moe_experts_held"] == 4
    none, counts = moe_dropless.held_expert_ffn(
        x, idx, w, jnp.zeros((n,), bool), wg, wu, wd, 1, rows=n * 2)
    counts = dict(zip(moe_dropless.COUNTERS, np.asarray(counts)))
    assert counts["moe_experts_nonempty"] == 0
    assert counts["moe_experts_held"] == 4
    assert not np.asarray(none).any()       # n_held 0: nothing is added
