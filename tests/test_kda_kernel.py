"""The KDA kernel (``paddle_tpu/ops/kernels/kda_chunk_walk.py``) interpreted
on the CPU at small widths against the one-token form ``kda_recurrent`` and
the XLA chunked form ``kda_chunk``: every decay, every mix of live rows a
slot, a state carried through many calls, the walk's table of live (slot,
chunk) pairs, the grid counts, the packed entry against the per-slot one
bit for bit, the layer and the engine taking the kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import test_kimi_linear as KIMI
from benchmark.reference import kimi_linear_plain as R
from paddle_tpu.models import cache_layout as CL
from paddle_tpu.models import kimi_linear as KL
from paddle_tpu.models.latent_moe import live_rows
from paddle_tpu.ops.kernels import kda, kda_chunk_walk as W, moe_dropless

B, S, H, K = 3, 128, 2, 16
#: the tolerance ``test_chunked_recurrent_and_reference_kda_agree`` holds
TOL = 2e-5

MIXES = {
    # a full chunk of rows beside a one-row slot beside an empty slot
    "chunk_row_empty": [S, 1, 0],
    # neither a multiple of 64 nor of 16; a slot that ends inside chunk 0
    "ragged": [100, 37, 1],
    "all_empty": [0, 0, 0],
    # idle slots before and after the live one; 70 = a chunk and 6 rows
    "empty_live_empty": [0, 70, 0],
    "rows_then_chunk": [1, 1, S],
    "one_past_a_chunk": [65, 2, 64],
}


def _inputs(alpha, seed, b=B, t=S):
    return KIMI._kda_inputs(alpha, np.random.default_rng(seed), b=b, t=t,
                            h=H, k=K)


def _masked(g, beta, q_lens):
    live = live_rows(q_lens, g.shape[1])
    return live, jnp.where(live[..., None, None], g, 0.0), \
        jnp.where(live[..., None], beta, 0.0)


def _close(got, want, what):
    scale = max(float(jnp.abs(want).max()), 1.0)
    err = float(jnp.abs(got - want).max())
    assert err < TOL * scale, (what, err, scale)


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("alpha", [0.5, 0.99, 1e-4])
def test_the_kernel_is_the_recurrence_on_the_live_rows(alpha, mix):
    """Fast decay (1e-4 a row) is where a wrong reference point overflows,
    or underflows to a wrong answer."""
    q, k, v, g, beta = _inputs(alpha, 7)
    rng = np.random.default_rng(8)
    s0 = jnp.asarray(rng.normal(size=(B, H, K, K)), jnp.float32)
    q_lens = jnp.asarray(MIXES[mix], jnp.int32)
    lens = jnp.asarray([5, 0, 7], jnp.int32)     # slot 1 is at position 0
    o, s = W.kda_chunk_walk(q, k, v, g, beta, s0, q_lens, lens)
    live, gm, bm = _masked(g, beta, q_lens)
    # a slot at position 0 starts from zeros when its first row comes
    start = jnp.where(((lens == 0) & (q_lens > 0))[:, None, None, None],
                      0.0, s0)
    o_rec, s_rec = kda.kda_recurrent(q, k, v, gm, bm, start)
    o_chk, s_chk = kda.kda_chunk(q, k, v, gm, bm, start)
    rows = live[..., None, None]
    _close(jnp.where(rows, o, 0.0), jnp.where(rows, o_rec, 0.0), "o/rec")
    _close(jnp.where(rows, o, 0.0), jnp.where(rows, o_chk, 0.0), "o/chunk")
    _close(s, s_rec, "state/rec")
    _close(s, s_chk, "state/chunk")
    # a dead row's output is 0, in a live chunk and in one never walked
    assert float(jnp.abs(jnp.where(rows, 0.0, o)).max()) == 0.0
    # a slot without a live row keeps its state bit for bit (as kda_chunk
    # leaves it), also at position 0
    for b, n in enumerate(MIXES[mix]):
        if n == 0:
            np.testing.assert_array_equal(s[b], s0[b])


def _pack(args, q_lens, t):
    """The per-slot ``[B, S, ...]`` operands as a mixed step's packed rows
    ``[t, ...]`` (garbage where no slot's row lies) and their RowMap."""
    rows = CL.RowMap(q_lens, jnp.zeros_like(q_lens), t, args[0].shape[1])
    rng = np.random.default_rng(99)
    live = np.asarray(rows.live)
    slot, col = np.asarray(rows.slot), np.asarray(rows.col)
    packed = []
    for a in args:
        x = rng.normal(size=(t,) + a.shape[2:]).astype(np.float32)
        x[live] = np.asarray(a)[slot[live], col[live]]
        packed.append(jnp.asarray(x))
    return packed, rows


@pytest.mark.parametrize("entry", ["per_slot", "packed"])
@pytest.mark.parametrize("alpha", [0.5, 0.99, 1e-4])
def test_a_stream_in_calls_is_one_recurrent_pass(alpha, entry):
    """4,096 rows of slot 0 in 256-row calls, slot 1 one row a call, slot
    2 idle throughout: the state rides through 16 calls, of the per-slot
    entry and of the packed one (272 rows: 257 live, the rest garbage)."""
    t, chunk = 4096, 256
    n = t // chunk
    big = _inputs(alpha, 3, b=1, t=t)
    small = _inputs(alpha, 4, b=1, t=n)
    zero = jnp.zeros((1, H, K, K), jnp.float32)
    o_big, s_big = kda.kda_recurrent(*big, zero)
    o_small, s_small = kda.kda_recurrent(*small, zero)
    rng = np.random.default_rng(5)
    # garbage in every slot: position 0 resets the two that get rows
    s = s_idle = jnp.asarray(rng.normal(size=(3, H, K, K)), jnp.float32)
    lens = jnp.zeros((3,), jnp.int32)
    q_lens = jnp.asarray([chunk, 1, 0], jnp.int32)
    call = jax.jit(W.kda_chunk_walk)

    @jax.jit
    def packed_call(*a):
        rows = CL.RowMap(a[6], a[7], 272, chunk)
        o, s = W.kda_chunk_walk(*a, rows)
        return o, s, rows.to_slots(o)
    for c in range(n):
        args = []
        for a_big, a_small in zip(big, small):
            x = jnp.zeros((3, chunk) + a_big.shape[2:], jnp.float32)
            x = x.at[0].set(a_big[0, c * chunk:(c + 1) * chunk])
            args.append(x.at[1, 0].set(a_small[0, c]))
        if entry == "packed":
            o, s, o_slots = packed_call(*_pack(args, q_lens, 272)[0], s,
                                        q_lens, lens)
            assert float(jnp.abs(o[chunk + 1:]).max()) == 0.0
            o = o_slots
        else:
            o, s = call(*args, s, q_lens, lens)
        lens = lens + q_lens
        _close(o[0], o_big[0, c * chunk:(c + 1) * chunk], f"o, call {c}")
        _close(o[1, 0], o_small[0, c], f"decode row, call {c}")
    _close(s[0], s_big[0], "streamed state")
    _close(s[1], s_small[0], "decoded state")
    np.testing.assert_array_equal(s[2], s_idle[2])


#: mixes of a packed step: (q_lens, rows of the axis, the view's width, heads)
PACKED = {
    # one full chunk beside decode rows
    "chunk_and_rows": ([64, 1, 1, 1], 80, 64, 2),
    # two partial chunks at starts that are no multiple of 8, decode rows
    "two_partial": ([1, 300, 212, 1, 1], 528, 512, 2),
    "idle_first_middle_last": ([0, 70, 0, 0, 1, 130, 0], 208, 192, 2),
    "every_slot_idle": ([0, 0, 0], 48, 64, 2),
    # a slot's rows end with the axis: its last chunk reads the padding
    "to_the_last_row": ([1, 1, 1, 77], 80, 128, 2),
    # two head groups of eight
    "two_head_groups": ([3, 100, 0, 1], 112, 128, 16),
}


@pytest.mark.parametrize("fit", ["resident", "chunk_blocks"])
@pytest.mark.parametrize("mix", sorted(PACKED))
def test_the_packed_entry_is_the_per_slot_entry_bit_for_bit(mix, fit,
                                                            monkeypatch):
    """The kernel on a mixed step's packed rows against the same rows in
    the per-slot view ``[B, S, ...]``: every live row and every state
    equal to the last bit, every other row zero; slots 0 and 2 fresh
    (``seq_lens`` 0). ``chunk_blocks``: with no room for resident blocks
    the per-slot entry takes a chunk a block and the packed rows go
    through the view, to the same bits."""
    q_lens, t, width, heads = PACKED[mix]
    b = len(q_lens)
    rng = np.random.default_rng(31)
    per_slot = list(KIMI._kda_inputs(0.9, rng, b=b, t=width, h=heads, k=K))
    s0 = jnp.asarray(rng.normal(size=(b, heads, K, K)), jnp.float32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    lens = jnp.asarray([0, 5, 0, 9, 2, 40, 1][:b], jnp.int32)
    packed, rows = _pack(per_slot, q_lens, t)
    if mix == "two_partial":
        assert [int(x) % 8 for x in rows.start[1:3]] == [1, 5]
    want_o, want_s = W.kda_chunk_walk(*per_slot, s0, q_lens, lens)
    if fit == "chunk_blocks":
        monkeypatch.setattr(W, "_RESIDENT_BUDGET", 0)
        W._walk_rows.clear_cache()
        W._walk_call.clear_cache()
    try:
        o, s = W.kda_chunk_walk(*packed, s0, q_lens, lens, rows)
        slot_o, slot_s = W.kda_chunk_walk(*per_slot, s0, q_lens, lens)
    finally:
        W._walk_rows.clear_cache()
        W._walk_call.clear_cache()
    assert o.shape == (t, heads, K) and o.dtype == jnp.float32
    live = np.asarray(rows.live)
    np.testing.assert_array_equal(
        np.asarray(o)[live],
        np.asarray(want_o)[np.asarray(rows.slot)[live],
                           np.asarray(rows.col)[live]])
    assert float(jnp.abs(o[~live]).max() if (~live).any() else 0.0) == 0.0
    np.testing.assert_array_equal(s, want_s)
    # the two ways of holding the rows are one kernel
    np.testing.assert_array_equal(slot_o, want_o)
    np.testing.assert_array_equal(slot_s, want_s)
    for i, n in enumerate(PACKED[mix][0]):
        if n == 0:
            np.testing.assert_array_equal(s[i], s0[i])


@pytest.mark.parametrize("q_lens,rows,width,steps,live", [
    # kimi_long_docs' mixed step: (272 + 63 x 8) / 64 table steps where
    # the per-slot grid had 8 x 4
    ([256, 1, 1, 1, 1, 1, 1, 1], 272, 256, 12, 11),
    # solar_long_reports': 24 where 16 x 8
    ([512] + [1] * 15, 528, 512, 24, 23),
    ([0, 0, 0], 48, 256, 3, 0),
    # the per-slot form: never more than slots x chunks a slot
    ([65, 64, 300], 3 * 256, 256, 12, 2 + 1 + 4),     # clipped to the width
    ([17], 256, 256, 4, 1),
    ([1], 16, 16, 1, 1),
])
def test_grid_counts_are_the_tables_steps_and_the_live_ones(
        q_lens, rows, width, steps, live):
    got = W.grid_counts(jnp.asarray(q_lens, jnp.int32), rows, width)
    assert got.dtype == jnp.int32 and [int(x) for x in got] == [steps, live]
    assert W.table_steps(len(q_lens), rows, width) == steps >= live


@pytest.mark.parametrize("q_lens,n,slot,chunk,total", [
    # the live chunks in order, slots ascending; the steps past them
    # repeat the last live one
    ([256, 1, 130], 9, [0, 0, 0, 0, 1, 2, 2, 2, 2],
     [0, 1, 2, 3, 0, 0, 1, 2, 2], 8),
    # an idle slot is in no step of the table
    ([0, 0, 70, 0, 1, 0], 5, [2, 2, 4, 4, 4], [0, 1, 0, 0, 0], 3),
    # nothing live: every step on one block, none of them live
    ([0, 0, 0], 3, [2, 2, 2], [0, 0, 0], 0),
])
def test_the_table_lists_the_live_chunks_and_then_stays_where_it_is(
        q_lens, n, slot, chunk, total):
    got = W._table(jnp.asarray(q_lens, jnp.int32), n)
    assert [[int(x) for x in a] for a in got] == [slot, chunk, [total]]
    assert all(a.dtype == jnp.int32 for a in got)


def test_the_rule_on_shapes_and_the_heads_a_step():
    assert W.serves(128, 128) and W.serves(256, 128)
    assert not W.serves(16, 16) and not W.serves(128, 64)
    assert W.heads_per_step(32) == 8 and W.heads_per_step(8) == 8
    # no whole sublane tile of heads: all of them, the block's full extent
    assert W.heads_per_step(12) == 12 and W.heads_per_step(2) == 2


def test_counts_of_layers_that_count_different_names_add_up():
    """The experts count the first names, the KDA layers the two after
    them: the collected vectors come out at one length."""
    n = len(moe_dropless.COUNTERS)
    with CL.collect_counts() as counted:
        CL.count(jnp.arange(n, dtype=jnp.int32))
        CL.count(jnp.asarray([32, 11], jnp.int32), at=n)
        CL.count(jnp.asarray([32, 4], jnp.int32), at=n)
    total = sum(counted)
    assert total.shape == (n + 2,) and total.dtype == jnp.int32
    assert [int(x) for x in total] == list(range(n)) + [64, 15]
    assert KL.KimiLinearForCausalLM.step_counter_names == \
        moe_dropless.COUNTERS + W.COUNTERS


def _layer(head_dim):
    cfg = KL.KimiLinearConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1, kda_layers=(1,),
        linear_num_heads=2, linear_head_dim=head_dim, gate_low_rank=8)
    paddle.seed(11)
    layer = KL.KimiDeltaAttention(cfg)
    layer.eval()
    return layer


@pytest.mark.parametrize("head_dim", [128, 16])
def test_the_layer_takes_the_kernel_at_lane_widths_only(head_dim,
                                                        monkeypatch):
    """At the published head width the layer's chunk form is the kernel
    (interpreted here); at a toy width it is ``kda_chunk``. Both give what
    the layer gives with the kernel ruled out."""
    layer = _layer(head_dim)
    rng = np.random.default_rng(2)
    b, s = 3, 70
    x = paddle.to_tensor(rng.normal(size=(b, s, 32)).astype(np.float32))
    shapes = layer.state_shapes(np.dtype("float32"))
    state = {k: jnp.asarray(rng.normal(size=(b,) + sh), dt)
             for k, (sh, dt) in shapes.items()}
    lens = jnp.asarray([4, 0, 9], jnp.int32)
    q_lens = jnp.asarray([s, 1, 0], jnp.int32)

    def run():
        calls = []
        real = W.kda_chunk_walk
        monkeypatch.setattr(W, "kda_chunk_walk",
                            lambda *a: calls.append(1) or real(*a))
        with paddle.no_grad(), CL.collect_counts() as counted:
            out, cache = layer(x, CL.RecurrentCache(state, lens, q_lens))
        return np.asarray(out._value), cache.state, counted, len(calls)

    out, new, counted, calls = run()
    n = len(moe_dropless.COUNTERS)
    if head_dim == 128:
        assert calls == 1
        assert [int(c) for c in counted[0]] == [0] * n + [3 * 2, 2 + 1]
    else:
        assert calls == 0
        assert [int(c) for c in counted[0]] == [0] * (n + 2)
    monkeypatch.setattr(W, "serves", lambda k, v: False)
    want, want_new, _, calls = run()
    assert calls == 0
    live = np.arange(s)[None, :] < np.asarray(q_lens)[:, None]
    np.testing.assert_allclose(out[live], want[live], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(new["S"][:2], want_new["S"][:2], atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_array_equal(new["conv"], want_new["conv"])
    # the idle slot: the XLA path zeroes a slot at position 0 at once, the
    # kernel when its first row comes; here it is past position 0
    np.testing.assert_array_equal(new["S"][2], state["S"][2])


def test_the_engine_serves_through_the_kernel(monkeypatch):
    """The toy model through the engine with the rule on shapes lifted
    (interpreted, widths do not matter): chunked prefill beside decode
    rows, an idle slot, a slot reused from position 0; the grid's counts
    leave the step programs beside the experts'."""
    monkeypatch.setattr(W, "serves", lambda k, v: True)
    seed = 17
    cfg = dict(KIMI.TOY, num_hidden_layers=4)
    model, _ = KIMI.build(cfg, seed)
    rng = np.random.default_rng(6)

    def doc(n):
        return rng.integers(1, 256, size=n).astype(np.int32)
    arrivals = {0: [(doc(70), 9)], 2: [(doc(45), 12)],
                9: [(doc(100), 6), (doc(33), 10)], 14: [(doc(5), 7)]}
    done, eng = KIMI._serve(model, arrivals)
    s = eng.stats
    assert s["state_resets"] == 5 and s["fused_steps"] > 0
    out = R.served_gaps(seed, cfg, list(done.values()), pad_to=64)
    assert np.concatenate(out["gaps"]).max() < 1e-3 * out["logit_std"]
    # 3 KDA layers, 3 slots of one 64-row chunk (chunk_size 32) a mixed step
    assert s["kda_grid_steps"] == 3 * 3 * s["fused_steps"]
    assert 0 < s["kda_grid_live"] <= s["kda_grid_steps"]
    assert s["moe_assignments_dropped"] == 0 and s["moe_experts_held"] > 0
