"""The power-retention kernel (``paddle_tpu/ops/kernels/
power_retention_walk.py``, what the served path runs) against the attention
form (``power_retention.retention_attention``, what holds every form) and
against the XLA forms it replaced (``retention_walk`` / ``retention_step``),
interpreted on the CPU at small widths: ten query heads on two key/value
heads of 16 (``D`` = 136, one strip a half diagonal) and, once, of 32 (the
loops over whole groups of diagonals run)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.kernels import power_retention as P
from paddle_tpu.ops.kernels import power_retention_walk as W

HQ, HK = 10, 2
#: (rows a slot, tokens a slot had absorbed before); 0 before = fresh
MIXES = {
    "all_idle": ([0, 0, 0], [5, 0, 9]),
    "all_one_row": ([1, 1, 1, 1], [5, 1, 9, 70]),
    "one_chunk_beside_one_row_slots": ([1, 100, 1, 0, 1], [3, 64, 9, 4, 11]),
    "two_chunks": ([96, 1, 260], [7, 2, 40]),
    "a_ragged_last_sub_chunk": ([W.SUB + 6, 1], [12, 3]),
    "a_chunk_of_exactly_one_sub_chunk": ([W.SUB, 1, 0], [9, 9, 9]),
    "two_rows": ([2, 1], [9, 5]),
    "a_fresh_chunk_and_a_fresh_row": ([80, 1, 1], [0, 0, 6]),
    "an_idle_slot_first_and_last": ([0, 1, 2 * W.SUB + 2, 0], [4, 8, 15, 2]),
}


def rows_of(rng, n, d):
    unit = lambda a: a / np.sqrt((a * a).mean(-1, keepdims=True))  # noqa
    q = jnp.asarray(unit(rng.standard_normal((n, HQ, d))), jnp.float32)
    k = jnp.asarray(unit(rng.standard_normal((n, HK, d))), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, HK, d)), jnp.float32)
    lg = jnp.asarray(-rng.uniform(0, 1 / 64, (n, HK)), jnp.float32)
    return q, k, v, lg


def a_step(q_lens, lens, d=16, seed=0, gap=0):
    """One step's operands. A slot with history holds the state of that
    many earlier tokens (made by the XLA form from zeros); a fresh slot
    holds NaNs, which nothing may read. Returns (the step's arguments,
    every slot's whole history of rows for the attention form)."""
    rng = np.random.default_rng(seed)
    B, D = len(q_lens), P.feature_dim(d)
    S = jnp.zeros((B, HK, D, d), jnp.float32)
    z = jnp.zeros((B, HK, D), jnp.float32)
    before = []
    for b in range(B):
        past = rows_of(rng, max(lens[b], 1), d)
        before.append(past)
        if lens[b]:
            ql = jnp.zeros((B,), jnp.int32).at[b].set(lens[b])
            _, S, z = P.retention_walk(*past, S, z, jnp.zeros((B,), jnp.int32),
                                       ql, jnp.zeros((B,), jnp.int32))
    stale = jnp.asarray(lens) == 0
    S = jnp.where(stale[:, None, None, None], jnp.nan, S)
    z = jnp.where(stale[:, None, None], jnp.nan, z)
    start = np.concatenate([[0], np.cumsum(np.asarray(q_lens) + gap)[:-1]]) \
        + gap
    n = int(sum(q_lens)) + gap * (B + 1) + 3
    now = rows_of(rng, n, d)
    args = now + (S, z, jnp.asarray(start, jnp.int32),
                  jnp.asarray(q_lens, jnp.int32), jnp.asarray(lens, jnp.int32))
    return args, before, start


def by_attention(args, before, start):
    """{slot: o of its live rows} by the attention form over the slot's
    whole history."""
    q, k, v, lg, _, _, _, q_lens, lens = args
    out = {}
    for b, (n, had) in enumerate(zip(np.asarray(q_lens), np.asarray(lens))):
        if not n:
            continue
        at = slice(int(start[b]), int(start[b]) + int(n))
        whole = [jnp.concatenate([p[:had], a[at]])[None]
                 for p, a in zip(before[b], (q, k, v, lg))]
        out[b] = P.retention_attention(*whole)[0, had:]
    return out


kernel = jax.jit(W.retention_walk)


def hold(args, before, start, state_tol=2e-5):
    q_lens = np.asarray(args[7])
    o, S, z = kernel(*args)
    o_x, S_x, z_x = P.retention_walk(*args)
    ref = by_attention(args, before, start)
    owned = np.zeros(o.shape[0], bool)
    for b, want in ref.items():
        at = slice(int(start[b]), int(start[b]) + int(q_lens[b]))
        owned[at] = True
        scale = float(jnp.max(jnp.abs(want)))
        err = float(jnp.max(jnp.abs(o[at] - want)))
        err_x = float(jnp.max(jnp.abs(o_x[at] - want)))
        # no further from the attention form than the form it replaced
        assert err <= max(2 * err_x, 2e-5 * scale), (b, err, err_x, scale)
    # rows no slot owns read 0
    assert not np.asarray(o)[~owned].any()
    for b, n in enumerate(q_lens):
        if n == 0:
            # the state stays where it lies, bit for bit (NaNs and all)
            np.testing.assert_array_equal(np.asarray(S[b]),
                                          np.asarray(args[4][b]))
            np.testing.assert_array_equal(np.asarray(z[b]),
                                          np.asarray(args[5][b]))
        else:
            for mine, theirs in ((S[b], S_x[b]), (z[b], z_x[b])):
                scale = float(jnp.max(jnp.abs(theirs)))
                assert float(jnp.max(jnp.abs(mine - theirs))) \
                    <= state_tol * scale
    return o, S, z


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_the_kernel_is_the_attention_form_and_the_xla_forms(mix):
    q_lens, lens = MIXES[mix]
    hold(*a_step(q_lens, lens, seed=len(mix)))


def test_rows_in_any_order_with_gaps_between_the_slots():
    args, before, start = a_step([1, W.SUB + 3, 0, 1], [5, 20, 3, 0], seed=5,
                                 gap=2)
    # the last slot's row first on the axis: nothing assumes slot order
    start = np.asarray(start).copy()
    start[3], start[0] = 0, start[3]
    args = args[:6] + (jnp.asarray(start, jnp.int32),) + args[7:]
    hold(args, before, start)


def test_a_wider_head_runs_the_loop_over_groups_of_eight_diagonals():
    hold(*a_step([1, W.SUB + 6, 1], [6, 0, 30], d=32, seed=2))


def test_the_one_token_step_of_every_slot():
    args, before, start = a_step([1, 0, 1, 1], [9, 5, 0, 33], seed=4)
    q, k, v, lg, S, z, _, q_lens, lens = args
    at = jnp.asarray(start)
    live = q_lens > 0
    first = [a[at] for a in (q, k, v, lg)]
    o, S1, z1 = jax.jit(W.retention_step)(*first, S, z, live, lens)
    o_x, S_x, z_x = P.retention_step(*first, S, z, live, lens == 0)
    ref = by_attention(args, before, start)
    for b, want in ref.items():
        err = float(jnp.max(jnp.abs(o[b] - want[0])))
        err_x = float(jnp.max(jnp.abs(o_x[b] - want[0])))
        assert err <= max(2 * err_x, 2e-5 * float(jnp.max(jnp.abs(want))))
        assert float(jnp.max(jnp.abs(S1[b] - S_x[b]))) \
            <= 2e-5 * float(jnp.max(jnp.abs(S_x[b])))
    np.testing.assert_array_equal(np.asarray(S1[1]), np.asarray(S[1]))
    np.testing.assert_array_equal(np.asarray(z1[1]), np.asarray(z[1]))
    assert not np.asarray(o[1]).any()


def test_a_long_accumulation_is_no_further_from_the_attention_form():
    """1,280 positions of one slot in five calls of 256 rows beside a slot
    that decodes and one that idles, log g in [-1/64, 0]: the kernel's
    largest error against the attention form is no larger than that of
    the XLA form it replaced."""
    d, calls, rows = 16, 5, 256
    rng = np.random.default_rng(1)
    q, k, v, lg = rows_of(rng, calls * rows, d)
    ref = P.retention_attention(q[None], k[None], v[None], lg[None])[0]
    D = P.feature_dim(d)
    start = jnp.asarray([0, rows, 0], jnp.int32)
    q_lens = jnp.asarray([rows, 1, 0], jnp.int32)
    extra = rows_of(rng, calls, d)
    errs = {}
    for name, walk in (("kernel", kernel), ("xla", jax.jit(P.retention_walk))):
        S = jnp.zeros((3, HK, D, d), jnp.float32)
        z = jnp.zeros((3, HK, D), jnp.float32)
        out = []
        for c in range(calls):
            at = slice(c * rows, (c + 1) * rows)
            step = [jnp.concatenate([a[at], e[c:c + 1]])
                    for a, e in zip((q, k, v, lg), extra)]
            o, S, z = walk(*step, S, z, start, q_lens,
                           jnp.asarray([c * rows, c, 7], jnp.int32))
            out.append(o[:rows])
        errs[name] = float(jnp.max(jnp.abs(jnp.concatenate(out) - ref)))
    assert errs["kernel"] <= 1.5 * errs["xla"] + 1e-6, errs
    assert errs["kernel"] < 1e-4 * float(jnp.max(jnp.abs(ref))), errs


def state_in_float64(k, v, lg, d):
    """(S, z) after every row of one sequence from zeros, in numpy
    float64: ``S = sum_s exp(G_T - G_s) phi(k_s') v_s^T``."""
    k, v, lg = (np.asarray(a, np.float64) for a in (k, v, lg))
    ks = k * d ** -0.25
    blocks = [ks * ks] + [2 ** 0.5 * ks * np.roll(ks, -r, -1)
                          for r in range(1, d // 2)]
    blocks.append((2 ** 0.5 * ks * np.roll(ks, -(d // 2), -1))[..., :d // 2])
    pk = np.concatenate(blocks, -1)                           # [T, Hk, D]
    G = np.cumsum(lg, 0)
    w = np.exp(G[-1][None] - G)                               # [T, Hk]
    return np.einsum("th,thd,thv->hdv", w, pk, v), \
        np.einsum("th,thd->hd", w, pk)


@pytest.mark.parametrize("rows", [[1, 1, 1], [96, 160, 70], [256] * 5])
def test_bfloat16_rows_take_the_passes_written_out(rows):
    """q, k and v in bfloat16 (what the served model hands the core): the
    kernel writes out the one-pass products such operands need in place of
    ``Precision.HIGHEST``. The state it leaves after several calls is no
    further from a float64 sum than the XLA form's at ``HIGHEST`` on the
    same values, and its outputs are the XLA form's to a bfloat16
    rounding."""
    d = 16
    rng = np.random.default_rng(len(rows))
    q, k, v, lg = rows_of(rng, sum(rows), d)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    D = P.feature_dim(d)
    S = [jnp.zeros((2, HK, D, d), jnp.float32)] * 2
    z = [jnp.zeros((2, HK, D), jnp.float32)] * 2
    done = 0
    for n in rows:
        at = slice(done, done + n)
        args = (q[at], k[at], v[at], lg[at])
        tail = (jnp.asarray([0, 0], jnp.int32), jnp.asarray([n, 0], jnp.int32),
                jnp.asarray([done, 3], jnp.int32))
        o, S[0], z[0] = kernel(*args, S[0], z[0], *tail)
        o_x, S[1], z[1] = P.retention_walk(
            *(a.astype(jnp.float32) for a in args), S[1], z[1], *tail)
        assert o.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_x), rtol=2 ** -7, atol=1e-6)
        done += n
    S64, z64 = state_in_float64(k, v, lg, d)
    for mine, theirs, want in ((S[0][0], S[1][0], S64), (z[0][0], z[1][0], z64)):
        err = float(np.abs(np.asarray(mine, np.float64) - want).max())
        err_x = float(np.abs(np.asarray(theirs, np.float64) - want).max())
        assert err <= 1.5 * err_x + 1e-7 * float(np.abs(want).max()), (err, err_x)


@pytest.mark.parametrize("q_lens", [[0, 0, 0], [1, 1, 1, 1],
                                    [512] + [1] * 14 + [0], [70, 0, 130, 1]])
def test_the_counters_say_what_the_kernel_walks(q_lens):
    walked, live, chunk, step = (
        int(c) for c in P.walk_counts(jnp.asarray(q_lens, jnp.int32)))
    assert walked == live == sum(n > 0 for n in q_lens)
    assert chunk == sum(n for n in q_lens if n > 1)
    assert step == sum(n == 1 for n in q_lens)
    alive = jnp.asarray([n > 0 for n in q_lens])
    walked, live, chunk, step = (int(c) for c in P.step_counts(alive))
    assert walked == live == step == sum(n > 0 for n in q_lens)
    assert chunk == 0
    # the walk addresses a live slot's own state and no idle slot's
    src, any_live = W._walk(jnp.asarray(q_lens, jnp.int32))
    assert int(any_live[0]) == int(any(q_lens))
    assert set(np.asarray(src).tolist()) <= (
        {b for b, n in enumerate(q_lens) if n} or {0})


def test_widths_the_kernel_is_not_written_for_are_refused_by_name():
    args, _, _ = a_step([1], [0], d=16)
    with pytest.raises(ValueError, match="multiple of 16"):
        W.retention_walk(args[0][..., :8], args[1][..., :8],
                         args[2][..., :8], args[3], args[4][:, :, :36, :8],
                         args[5][:, :, :36], *args[6:])
