"""Kimi-Linear (``paddle_tpu/models/kimi_linear.py``) against its plain
float32 reference (``benchmark/reference/kimi_linear_plain.py``, the one
file of the benchmark these tests import, so that the tests' reference and
the cell's cannot drift apart), at toy widths on the CPU: the model's
forward, the chunked and the one-token form of KDA, absorbed attention
over the latent pool, the share test of the expert layer, the engine
(chunked prefill, ``multi_step`` decode, staggered arrivals, an idle slot,
slot reuse, preemption and replay) compared as ``served_gaps`` compares,
every option a recurrent layout refuses, and the latent pool's bytes."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.harness import weights as W
from benchmark.reference import kimi_linear_plain as R
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models import cache_layout as CL
from paddle_tpu.ops.kernels import kda, latent_attention, moe_dropless

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the toy cut of the shipped configuration's keys: hidden 64, 2 heads x 16,
#: latent 32 + 8, 16 experts top-4 of which 4 held, two periods
TOY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=160,
    num_hidden_layers=8, num_attention_heads=2, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11],
                            full_attn_layers=[4, 8, 12], num_heads=2,
                            head_dim=16, short_conv_kernel_size=4),
    gate_low_rank=8, first_k_dense_replace=1, moe_intermediate_size=32,
    num_experts=4, num_experts_published=16, expert_offset=0,
    num_experts_per_token=4, num_shared_experts=1,
    routed_scaling_factor=2.446, moe_renormalize=True, rms_norm_eps=1e-5,
    model_max_length=4096, tie_word_embeddings=False,
    moe_router_activation_func="sigmoid", num_expert_group=1, topk_group=1,
    mla_use_nope=True, q_lora_rank=None, moe_layer_freq=1,
    hidden_act="silu")


def program():
    from benchmark.harness import loader
    return loader.module("programs", "kimi_linear")


def build(cfg, seed, shift_dt_bias=0.0):
    """The program's model with the reference's float32 seeded leaves;
    returns (model, {name: float32 array})."""
    model = program().build(cfg)
    model.eval()
    named = list(model.named_parameters())
    mine = {n: tuple(p._value.shape) for n, p in named}
    assert mine == {n: tuple(s) for n, s in R.specs(cfg)}
    # the benchmark's leaves are bfloat16 (the reference's ``served_gaps``
    # makes them so again); float32 copies of those values compute here
    vals = W.make(seed, [(n, mine[n]) for n, _ in named], jnp.bfloat16,
                  None, R.is_scale)
    params = {}
    for (n, p), v in zip(named, vals):
        v = v.astype(jnp.float32)
        if n.endswith("dt_bias"):
            v = v + shift_dt_bias
        p._value = params[n] = v
    return model, params


def test_specs_and_size_of_the_shipped_configuration():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b-ep4-d8.json")) as f:
        cfg = json.load(f)
    assert R.n_params(cfg) == pytest.approx(3.77e9, rel=0.01)
    with paddle.LazyGuard():
        model = program().build(cfg)
    assert {n: tuple(p._value.shape) for n, p in model.named_parameters()} \
        == {n: tuple(s) for n, s in R.specs(cfg)}
    layout = model.cache_layout()
    assert [k.kind for k in layout] == ["recurrent"] * 3 + ["paged_latent"] \
        + ["recurrent"] * 3 + ["paged_latent"]
    # (vii) a token costs 576 values a latent layer, whatever the heads
    assert layout[3].bytes_per_token(2) == 576 * 2
    assert layout[0].shapes["S"] == ((32, 128, 128), np.dtype("float32"))
    assert layout[0].shapes["conv"][0] == (3, 3 * 4096)


# ---- (i) the model's forward against the reference -----------------------

@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_forward_matches_the_reference(seed):
    model, params = build(TOY, seed)
    ids = np.random.default_rng(seed).integers(1, 256, size=(2, 70))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
    for b in range(2):
        want = np.asarray(R.forward_logits(params, jnp.asarray(ids[b]), TOY))
        # float32 on both sides; the forms differ (chunked against a scan
        # a token, absorbed against per-head attention, sorted groups
        # against a loop over experts): rounding only
        np.testing.assert_allclose(got[b], want, atol=2e-4, rtol=2e-4)


# ---- (ii) chunked KDA = recurrent KDA = the reference ---------------------

def _kda_inputs(alpha, rng, b=2, t=70, h=3, k=16):
    q, kk = rng.normal(size=(2, b, t, h, k))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * k ** 0.5
    kk /= np.linalg.norm(kk, axis=-1, keepdims=True)
    v = rng.normal(size=(b, t, h, k))
    g = np.log(alpha) * (0.5 + rng.random(size=(b, t, h, k)))
    beta = rng.random(size=(b, t, h))
    return [jnp.asarray(a, jnp.float32) for a in (q, kk, v, g, beta)]


@pytest.mark.parametrize("alpha", [0.5, 0.99, 1e-4])
def test_chunked_recurrent_and_reference_kda_agree(alpha):
    q, k, v, g, beta = _kda_inputs(alpha, np.random.default_rng(7))
    zero = jnp.zeros((2, 3, 16, 16), jnp.float32)
    o_rec, s_rec = kda.kda_recurrent(q, k, v, g, beta, zero)
    o_chk, s_chk = kda.kda_chunk(q, k, v, g, beta, zero)
    o_ref = jnp.stack([R.kda_scan(q[b], k[b], v[b], g[b], beta[b])
                       for b in range(2)])
    scale = float(jnp.abs(o_ref).max())
    assert float(jnp.abs(o_chk - o_ref).max()) < 2e-5 * max(scale, 1.0)
    assert float(jnp.abs(o_rec - o_ref).max()) < 2e-5 * max(scale, 1.0)
    assert float(jnp.abs(s_chk - s_rec).max()) < 2e-5 * max(
        float(jnp.abs(s_rec).max()), 1.0)


def test_dead_rows_leave_state_and_tail_as_after_the_live_rows():
    rng = np.random.default_rng(11)
    q, k, v, g, beta = _kda_inputs(0.9, rng, t=48)
    s0 = jnp.asarray(rng.normal(size=(2, 3, 16, 16)), jnp.float32)
    q_lens = jnp.asarray([5, 0])
    live = jnp.arange(48)[None, :] < q_lens[:, None]
    _, s_masked = kda.kda_chunk(
        q, k, v, jnp.where(live[..., None, None], g, 0.0),
        jnp.where(live[..., None], beta, 0.0), s0)
    _, s_five = kda.kda_recurrent(q[:1, :5], k[:1, :5], v[:1, :5],
                                  g[:1, :5], beta[:1, :5], s0[:1])
    np.testing.assert_allclose(s_masked[0], s_five[0], atol=1e-5)
    np.testing.assert_array_equal(s_masked[1], s0[1])     # idle: untouched
    x = jnp.asarray(rng.normal(size=(2, 48, 6)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(2, 3, 6)), jnp.float32)
    _, new_tail = kda.causal_conv(x, tail, jnp.ones((4, 6)), q_lens)
    np.testing.assert_array_equal(new_tail[0], x[0, 2:5])
    np.testing.assert_array_equal(new_tail[1], tail[1])


@pytest.mark.parametrize("shift", [0.0, -4.6])
def test_kda_layers_agree_at_fast_and_slow_decay(shift):
    """Seeded weights decay by about a half a token, which hides a chunked
    form's cumulative-decay errors; ``dt_bias`` shifted by -4.6 makes the
    decay about 0.99 a token."""
    cfg = dict(TOY, num_hidden_layers=3)
    model, params = build(cfg, 21, shift_dt_bias=shift)
    ids = np.random.default_rng(5).integers(1, 256, size=(1, 100))
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids.astype(np.int32)))._value)
    want = np.asarray(R.forward_logits(params, jnp.asarray(ids[0]), cfg))
    np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)
    x = jnp.take(params["model.embed_tokens.weight"], ids[0], axis=0)
    pre = "model.layers.0.self_attn."
    alpha = jnp.exp(-jnp.exp(params[pre + "A_log"])[:, None] *
                    jax.nn.softplus((x @ params[pre + "f_a_proj.weight"]
                                     @ params[pre + "f_b_proj.weight"]
                                     + params[pre + "dt_bias"])
                                    .reshape(100, 2, 16)))
    assert float(alpha.mean()) == pytest.approx(0.99 if shift else 0.5,
                                                abs=0.06)


# ---- (iii) absorbed attention over the latent pool ------------------------

def _pool_case(rng, b, s, h, d, bs, mb, lens, qlens):
    nb = b * mb + 1
    pool = jnp.asarray(rng.normal(size=(nb, bs, d)), jnp.float32)
    tables = np.full((b, mb), -1, np.int32)
    perm, at = rng.permutation(nb - 1), 0
    for i in range(b):
        n = -(-(lens[i] + qlens[i]) // bs)
        tables[i, :n] = perm[at:at + n]
        at += n
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) * 0.3
    return q, pool, jnp.asarray(tables), jnp.asarray(lens, jnp.int32), \
        jnp.asarray(qlens, jnp.int32)


#: (block, table entries, chunk, lens, q_lens, dtype) of the kernel's
#: cases. A table of 8 / 12 / 6 / 7 entries of 16 latents walks in wide
#: entries of 8 / 4 / 2 / 1 (``entries_per_step``); 8 of 64 in 4. With
#: ``n`` 4 of 16 a wide entry is 64 latents: [0, 64), [64, 128), [128, 192)
KERNEL_CASES = {
    "chunk_idle_decode_ramp": (16, 8, 32, [37, 0, 90, 5], [32, 0, 1, 20],
                               "float32"),
    "one_token": (16, 8, 1, [37, 64, 90, 0], [1, 1, 0, 0], "float32"),
    # n 4: a context that ends inside a wide entry, a window that
    # straddles two, an idle slot, a context that ends on an entry's edge
    "wide4_inside_straddle_idle_edge": (16, 12, 32, [70, 50, 0, 96],
                                        [32, 32, 0, 32], "float32"),
    # n 4: decode rows at the first and the last latent of a wide entry,
    # a ramp that starts on an edge, a chunk that fills the table
    "wide4_decode_rows_on_edges": (16, 12, 32, [64, 127, 128, 160],
                                   [1, 1, 9, 32], "float32"),
    "wide4_one_token": (16, 12, 1, [63, 64, 128, 191], [1, 1, 1, 0],
                        "float32"),
    "wide2": (16, 6, 32, [37, 0, 63, 5], [32, 0, 1, 20], "float32"),
    "wide2_one_token": (16, 6, 1, [31, 32, 95, 0], [1, 1, 1, 0], "float32"),
    "wide1": (16, 7, 32, [37, 0, 63, 5], [32, 0, 1, 20], "float32"),
    "wide1_one_token": (16, 7, 1, [15, 16, 111, 0], [1, 1, 1, 0],
                        "float32"),
    "blocks_of_64": (64, 8, 32, [250, 0, 256, 480], [32, 0, 1, 32],
                     "float32"),
    "blocks_of_64_one_token": (64, 8, 1, [255, 256, 511, 7], [1, 1, 1, 0],
                               "float32"),
    "bf16": (16, 12, 32, [70, 50, 0, 96], [32, 32, 0, 32], "bfloat16"),
    "bf16_one_token": (16, 12, 1, [63, 64, 128, 191], [1, 1, 1, 0],
                       "bfloat16"),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_latent_kernel_equals_the_gathered_form(case):
    """The Pallas kernel (interpret mode here) against attention over the
    slot's gathered context, on the rows that are live. Every table holds
    ``-1`` past its slot's length (``_pool_case``)."""
    bs, mb, s, lens, qlens, dtype = KERNEL_CASES[case]
    rng = np.random.default_rng(2)
    q, pool, tables, lens, qlens = _pool_case(rng, 4, s, 4, 48, bs, mb,
                                              lens, qlens)
    new = jnp.asarray(rng.normal(size=(4, s, 48)), jnp.float32)
    q, pool, new = (x.astype(dtype) for x in (q, pool, new))
    pool = latent_attention.latent_pool_write(pool, new, tables, lens, qlens)
    ctx = np.asarray(pool)[np.maximum(np.asarray(tables), 0)] \
        .reshape(4, -1, 48)
    for b in range(4):
        lo, n = int(lens[b]), int(qlens[b])
        np.testing.assert_array_equal(ctx[b, lo:lo + n], new[b, :n])
        assert (np.asarray(tables)[b, -(-(lo + n) // bs):] == -1).all()
    want = latent_attention.latent_attention_dense(q, pool, tables, lens,
                                                   qlens, 32)
    got = latent_attention._append_call(q, pool, tables, lens, qlens, dv=32,
                                        interpret=True)
    assert got.dtype == q.dtype
    live = np.arange(s)[None, :] < np.asarray(qlens)[:, None]
    # bf16: the kernel rounds the weights ``p`` and the output once each
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=2e-5 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("mb", [12, 6, 7])
def test_a_dead_table_entry_inside_a_context_masks_its_latents(mb):
    """A ``-1`` entry below a slot's length (the scheduler never grants
    one; a wiped table row could hold one): its latents are seen by no
    row, in a wide entry of 4, 2 or 1, in the history and in the window."""
    rng = np.random.default_rng(5)
    lens, qlens = [70, 40, 17, 0], [20, 1, 32, 0]
    q, pool, tables, lens, qlens = _pool_case(rng, 4, 32, 4, 48, 16, mb,
                                              lens, qlens)
    tables = np.array(tables)
    tables[0, 1], tables[1, 2], tables[2, 1] = -1, -1, -1
    want = _seen_by_each_row(q, pool, tables, lens, qlens, 32)
    got = latent_attention._append_call(q, pool, jnp.asarray(tables), lens,
                                        qlens, dv=32, interpret=True)
    live = np.arange(32)[None, :] < np.asarray(qlens)[:, None]
    np.testing.assert_allclose(np.asarray(got)[live], want[live], atol=2e-5)


def _seen_by_each_row(q, pool, tables, lens, qlens, dv):
    """[B, S, H, dv] in float64: row i of slot b over the latents of its
    table's held entries at positions ``<= lens[b] + i`` (a ``-1`` entry's
    are seen by no row); rows at or past ``qlens`` are zero."""
    q, tables = np.asarray(q, np.float64), np.asarray(tables)
    b, s, h, d = q.shape
    bs = pool.shape[1]
    ctx = np.asarray(pool, np.float64)[np.maximum(tables, 0)] \
        .reshape(b, -1, d)
    seen = np.repeat(tables >= 0, bs, axis=1)
    want = np.zeros((b, s, h, dv))
    for i in range(b):
        for r in range(int(qlens[i])):
            ok = seen[i] & (np.arange(ctx.shape[1]) <= int(lens[i]) + r)
            sc = q[i, r] @ ctx[i].T
            p = np.exp(sc - sc[:, ok].max(axis=1, keepdims=True)) * ok
            want[i, r] = (p / p.sum(axis=1, keepdims=True)) @ ctx[i, :, :dv]
    return want


#: (block, table entries, the per-slot width, rows of the packed axis,
#: lens, q_lens, dtype, head groups, (slot, entry) set to -1) of a mixed
#: step's packed rows through the kernel. 4 heads a group move a slot's
#: first row to a multiple of 4, 2 heads to one of 8.
PACKED_CASES = {
    # a decode row, a whole chunk, an idle slot and a chunk's tail in one
    # step: the slots start at rows 0, 1, 33, 33 of the axis, and 27 rows
    # of padding follow
    "row_chunk_idle_tail": (16, 12, 32, 80, [70, 50, 0, 96], [1, 32, 0, 20],
                            "float32", 1, ()),
    "bf16": (16, 12, 32, 80, [70, 50, 0, 96], [1, 32, 0, 20], "bfloat16", 1,
             ()),
    # the first slot idle and the axis full to its last row
    "idle_first_no_padding": (16, 12, 32, 48, [0, 31, 64, 100],
                              [0, 7, 32, 9], "float32", 1, ()),
    # a window that ends on the table's last latent, and a decode row there
    "the_tables_last_block": (16, 12, 32, 64, [175, 191, 0, 160],
                              [17, 1, 0, 32], "float32", 1, ()),
    # -1 entries below a slot's length, inside wide entries of 4: in the
    # history and in the window
    "dead_entries_in_wide_entries": (16, 12, 32, 64, [70, 40, 17, 0],
                                     [20, 1, 32, 0], "float32", 1,
                                     ((0, 1), (1, 2), (2, 1))),
    "decode_rows_only": (16, 12, 32, 16, [63, 64, 128, 191], [1, 1, 0, 1],
                         "float32", 1, ()),
    # a one-row view (every slot a row at most) on 8 packed rows
    "width_of_one": (16, 12, 1, 8, [63, 64, 128, 0], [1, 0, 1, 1],
                     "float32", 1, ()),
    "wide2": (16, 6, 32, 64, [37, 0, 63, 5], [32, 0, 1, 20], "float32", 1,
              ()),
    "wide1": (16, 7, 32, 64, [37, 0, 63, 5], [32, 0, 1, 20], "float32", 1,
              ()),
    "blocks_of_64": (64, 8, 32, 80, [250, 0, 256, 480], [32, 0, 1, 32],
                     "float32", 1, ()),
    # two heads a grid step: a head group's block is walked by every slot
    # before the next group's, and a slot starts on a multiple of 8 rows
    "two_head_groups": (16, 12, 32, 80, [70, 50, 0, 96], [1, 32, 0, 20],
                        "float32", 2, ()),
    "two_head_groups_bf16": (16, 12, 32, 80, [70, 50, 0, 96],
                             [1, 32, 0, 20], "bfloat16", 2, ()),
}


@pytest.fixture()
def fresh_append_programs():
    """The kernel's plan reads a module constant a case may patch: no
    program traced under one value serves a call under another."""
    def clear():
        latent_attention._append_rows.clear_cache()
        latent_attention._append_call.clear_cache()
    clear()
    yield
    clear()


@pytest.mark.parametrize("case", PACKED_CASES)
def test_the_packed_rows_go_through_the_kernel_as_they_are(
        case, monkeypatch, fresh_append_programs):
    """A mixed step's rows on ONE axis (``cache_layout.RowMap``): the
    pool's write lands where the per-slot form's does, bit for bit; the
    kernel (interpret mode here) gives each live row what the gathered
    form gives it and every other row zero; and the per-slot entries
    (``_append_call``, as ``benchmark/tests/test_aot_*.py`` call it) are
    the same program on another row axis, bit for bit."""
    la = latent_attention
    bs, mb, s, t, lens, qlens, dtype, groups, dead = PACKED_CASES[case]
    b, h, d, dv = 4, 4, 48, 32
    rng = np.random.default_rng(11)
    _, pool, tables, lens, qlens = _pool_case(rng, b, s, h, d, bs, mb, lens,
                                              qlens)
    tables = np.array(tables)
    for slot, entry in dead:
        tables[slot, entry] = -1
    tables = jnp.asarray(tables)
    rows = CL.RowMap(qlens, lens, t, s)
    q = (jnp.asarray(rng.normal(size=(t, h, d)), jnp.float32) * 0.3) \
        .astype(dtype)
    new = jnp.asarray(rng.normal(size=(t, d)), jnp.float32).astype(dtype)
    pool = pool.astype(dtype)
    if groups > 1:
        hq = h // groups
        monkeypatch.setattr(la, "_VMEM_BUDGET", la._vmem_bytes(
            hq, la.held_rows(hq, b, b * s, s), s, d, dv,
            max(la._KEY_TILE_MAX, bs), q.dtype.itemsize))
        assert la.heads_per_step(h, b, t, s, d, dv, bs,
                                 q.dtype.itemsize) == hq
    live = np.asarray(rows.live)
    assert live.sum() == int(qlens.sum()) and (
        case == "idle_first_no_padding") == bool(live.all())
    if case == "row_chunk_idle_tail":
        assert np.asarray(rows.start).tolist() == [0, 1, 33, 33]

    written = la.latent_pool_write(pool, new, tables, lens, qlens, rows)
    per_slot = la.latent_pool_write(pool, rows.to_slots(new), tables, lens,
                                    qlens)
    np.testing.assert_array_equal(np.asarray(written, np.float32),
                                  np.asarray(per_slot, np.float32))
    assert not np.array_equal(np.asarray(written, np.float32),
                              np.asarray(pool, np.float32))

    got = la._append_rows(q, written, tables, lens, qlens, rows.start,
                          width=s, dv=dv, every=None, interpret=True)
    assert got.shape == (t, h, dv) and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    assert (got[~live] == 0).all()
    want = np.asarray(rows.from_slots(_seen_by_each_row(
        rows.to_slots(q), written, tables, lens, qlens, dv)))
    np.testing.assert_allclose(got[live], want[live],
                               atol=2e-5 if dtype == "float32" else 2e-2)
    if not dead:
        dense = la.latent_attention_dense(rows.to_slots(q), written, tables,
                                          lens, qlens, dv)
        np.testing.assert_allclose(
            got[live], np.asarray(rows.from_slots(dense), np.float32)[live],
            atol=2e-5 if dtype == "float32" else 2e-2)
    slots = la._append_call(rows.to_slots(q), written, tables, lens, qlens,
                            dv=dv, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(rows.from_slots(slots), np.float32)[live], got[live])
    inside = np.arange(s)[None, :] < np.asarray(qlens)[:, None]
    assert (np.asarray(slots, np.float32)[~inside] == 0).all()


@pytest.mark.parametrize("mb,bs,n", [
    (136, 64, 4), (260, 64, 4),     # the two cells' tables
    (8, 64, 4), (6, 64, 2), (7, 64, 1), (130, 64, 2), (8, 16, 8),
    (12, 16, 4), (16, 256, 1), (16, 512, 1),
])
def test_the_walks_entries_a_grid_step_come_from_the_tables_shape(mb, bs, n):
    assert latent_attention.entries_per_step(mb, bs) == n
    assert mb % n == 0
    assert n * bs <= max(latent_attention._KEY_TILE_MAX, bs)


@pytest.mark.parametrize("shape,hq", [
    # (heads, slots, rows of the axis, rows a slot at most, D, dv, block)
    ((128, 8, 528, 512, 576, 512, 64), 16),  # dsv2_rag_answers' mixed step
    ((32, 16, 272, 256, 576, 512, 64), 32),  # kimi_long_docs' mixed step
    ((128, 8, 8, 1, 576, 512, 64), 128), ((32, 16, 16, 1, 576, 512, 64), 32),
    # the per-slot chunk form at the cells' sizes (the ahead-of-time
    # compiles of ``benchmark/tests``): every slot's 512 rows are held
    ((128, 8, 4096, 512, 576, 512, 64), 2),
])
def test_heads_a_grid_step_fit_vmem_with_the_key_tile_counted(shape, hq):
    la = latent_attention
    assert la.heads_per_step(*shape) == hq
    h, b, t, s, d, dv, bs = shape
    kt = la._KEY_TILE_MAX
    held = la.held_rows(hq, b, t, s)
    # a head group's block: every row of the axis, room for each slot to
    # start on a sublane tile (none where ``hq`` is a multiple of one)
    # and one row tile past the end
    step = la.slot_step(hq)
    assert step == 16 // math.gcd(hq, 16) and held % hq == 0
    assert t * hq + la._row_tile(hq, s) <= held <= \
        (t + b * (step - 1) + step) * hq + la._row_tile(hq, s) + hq
    with_tile = la._vmem_bytes(hq, held, s, d, dv, kt, 2)
    assert with_tile <= la._VMEM_BUDGET
    # the tile's two buffers, the tile and a row tile's f32 scores
    assert with_tile - la._vmem_bytes(hq, held, s, d, dv, 0, 2) == \
        3 * kt * d * 2 + la._row_tile(hq, s) * kt * 4
    if hq < h:
        assert la._vmem_bytes(2 * hq, la.held_rows(2 * hq, b, t, s), s, d,
                              dv, kt, 2) > la._VMEM_BUDGET


def test_absorbed_attention_equals_the_per_head_reference():
    """The MLA layer as served (q_nope absorbed through the key half of
    W_kvb, attention against the latent pool, the value half after) against
    the reference's per-head keys and values, prefilled in two chunks."""
    cfg = dict(TOY, num_hidden_layers=4)
    model, params = build(cfg, 13)
    layer = model.model.layers[3].self_attn
    d = R.dims(cfg)
    pre = "model.layers.3.self_attn."
    lw = [params[pre + n] for n in R.MLA_LEAVES]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 48, 64)),
                    jnp.float32)
    q, k, v = R.mla_heads(x[0], lw, d, "f32")
    want = R._attention(q, k, v, "f32").reshape(48, -1) @ lw[4]
    pool = jnp.zeros((5, 16, 40), jnp.float32)
    tables = jnp.asarray([[2, 0, 3, 1]], jnp.int32)
    outs = []
    with paddle.no_grad():
        for lo, n in ((0, 32), (32, 16)):
            xs = jnp.zeros((1, 32, 64), jnp.float32).at[:, :n].set(
                x[:, lo:lo + n])
            out, cache = layer(paddle.to_tensor(xs), CL.LatentPagedCache(
                pool, tables, jnp.asarray([lo], jnp.int32),
                jnp.asarray([n], jnp.int32)))
            pool = cache.pool._value
            outs.append(np.asarray(out._value)[0, :n])
    np.testing.assert_allclose(np.concatenate(outs), want, atol=2e-5,
                               rtol=2e-4)
    assert pool.shape[-1] == 32 + 8       # (c, k_pe): no per-head K or V


# ---- (iv) the share test --------------------------------------------------

def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    rng = np.random.default_rng(4)
    n, h, f, e_all, k = 50, 64, 32, 16, 4
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, e_all)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e_all,)) * 0.1, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e_all, h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e_all, f, h)) * 0.1, jnp.float32)
    sg, su = (jnp.asarray(rng.normal(size=(h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    sd = jnp.asarray(rng.normal(size=(f, h)) * 0.1, jnp.float32)
    d = dict(topk=k, renorm=True, scale=2.446, off=0)
    whole = R._moe(x, (wr, bias, wg, wu, wd, sg, su, sd), d, "f32")
    idx, w = moe_dropless.route(x, wr, bias, k, 2.446)
    live = jnp.ones((n,), bool)
    total = R._swiglu(x, sg, su, sd, "f32")          # the shared expert ONCE
    held = 0
    for off in range(0, e_all, 4):
        part, counts = moe_dropless.held_expert_ffn(
            x, idx, w, live, wg[off:off + 4], wu[off:off + 4],
            wd[off:off + 4], off, rows=n * k)
        ref_part = R.routed_part(x, idx, w, wg[off:off + 4], wu[off:off + 4],
                                 wd[off:off + 4], off, "f32")
        np.testing.assert_allclose(part, ref_part, atol=2e-5)
        total = total + part
        counts = dict(zip(moe_dropless.COUNTERS, np.asarray(counts)))
        assert counts["moe_assignments"] == n * k
        assert counts["moe_assignments_dropped"] == 0
        held += counts["moe_assignments_held"]
    assert held == n * k          # every assignment lands on one share
    np.testing.assert_allclose(total, whole, atol=5e-5)


def test_dead_rows_are_not_routed_and_nothing_is_dropped():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    idx, w = moe_dropless.route(x, wr, jnp.zeros((8,)), 2, 1.0)
    wg, wu = (jnp.asarray(rng.normal(size=(8, 16, 8)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(8, 8, 16)), jnp.float32)
    live = jnp.arange(40) < 7
    # rows = live rows x experts a token: the tightest bound that holds
    y, counts = moe_dropless.held_expert_ffn(x, idx, w, live, wg, wu, wd, 0,
                                             rows=7 * 2)
    counts = dict(zip(moe_dropless.COUNTERS, np.asarray(counts)))
    # (the product's height is the bound rounded up to a multiple of 8)
    assert counts == dict(moe_assignments=14, moe_assignments_held=14,
                          moe_rows_computed=16, moe_assignments_dropped=0,
                          moe_expert_peak=counts["moe_expert_peak"],
                          moe_rows_held=7,
                          moe_experts_nonempty=counts["moe_experts_nonempty"],
                          moe_experts_held=8)
    assert 0 < counts["moe_experts_nonempty"] <= 8
    assert not np.asarray(y)[7:].any()
    want = R.routed_part(x[:7], idx[:7], w[:7], wg, wu, wd, 0, "f32")
    np.testing.assert_allclose(y[:7], want, atol=2e-5)


# ---- (v) the engine against the reference ---------------------------------

ENGINE = dict(scheduler="fused", cache_impl="paged", block_size=16,
              chunk_size=32, readout_stride=4, max_batch=3, max_seq_len=192)


def _serve(model, arrivals, **over):
    """Drive the engine a step at a time; ``arrivals``: {step: [(prompt,
    max_new)]}. Returns ({rid: (prompt, tokens)}, engine)."""
    eng = LLMEngine(model, **dict(ENGINE, **over))
    eng.emitted, to = [], eng._to        # what rides on pt:engine.emit

    def recording(phase, **ids):
        if phase == "emit":
            eng.emitted.append(ids)
        return to(phase, **ids)
    eng._to = recording
    prompts, done, step = {}, {}, 0
    while step < 400:
        for prompt, n in arrivals.get(step, ()):
            rid = eng.add_request(list(prompt), max_new_tokens=n)
            prompts[rid] = prompt
        for out in eng.step():
            done[out.request_id] = (prompts[out.request_id],
                                    np.asarray(out.token_ids, np.int32))
        step += 1
        if step > max(arrivals) and not eng.has_unfinished():
            break
    assert len(done) == len(prompts)
    assert eng._write_fence == {} and eng._quarantine == set()
    return done, eng


@pytest.mark.parametrize("case", ["staggered", "preempted", "two_ramping"])
def test_engine_serves_what_the_reference_would(case):
    """Chunked prefill then ``multi_step`` decode, compared on the gaps of
    the served tokens' logits as ``served_gaps`` compares. ``staggered``:
    arrivals spread over steps, a slot that idles while others decode, a
    slot reused by later requests. ``preempted``: a pool too small for the
    batch, so a request is preempted and replays from its first token into
    zeroed state. ``two_ramping``: a budget of two chunks, so two
    documents prefill in ONE mixed step beside a third's decode token:
    the packed row axis holds two slots' chunks back to back, and the
    convolution tails, the recurrences and the latent pool each take
    their own slot's rows out of it."""
    seed = 17
    model, _ = build(TOY, seed)
    rng = np.random.default_rng(6)

    def doc(n):
        return rng.integers(1, 256, size=n).astype(np.int32)
    if case == "staggered":
        arrivals = {0: [(doc(70), 9)], 2: [(doc(45), 12)],
                    9: [(doc(100), 6), (doc(33), 10)], 14: [(doc(5), 7)]}
        done, eng = _serve(model, arrivals)
        assert eng.stats["preemptions"] == 0
        assert eng.stats["state_resets"] == 5
    elif case == "two_ramping":
        arrivals = {0: [(doc(21), 20)], 2: [(doc(70), 9), (doc(61), 8)]}
        done, eng = _serve(model, arrivals, max_step_tokens=64)
        assert eng.mixed_rows == 64 < 3 * 32
        # some mixed step carried two prefill grants
        assert eng.stats["prefill_chunks"] > eng.stats["fused_steps"]
        assert eng.stats["rows_computed"] >= 64 * eng.stats["fused_steps"]
        assert eng.stats["state_resets"] == 3
    else:
        arrivals = {0: [(doc(90), 30), (doc(80), 30), (doc(85), 30)]}
        done, eng = _serve(model, arrivals, kv_pool_blocks=16)
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["state_resets"] == 3 + eng.stats["preemptions"]
    assert eng.stats["multi_steps"] > 0 and eng.stats["fused_steps"] > 0
    out = R.served_gaps(seed, TOY, list(done.values()), pad_to=64)
    gaps = np.concatenate(out["gaps"])
    # float32 engine against float32 reference: a served token is the
    # reference's choice, or loses to it by rounding
    assert gaps.max() < 1e-3 * out["logit_std"]
    # the counters that left the step programs beside the tokens
    s = eng.stats
    live = s["prefill_tokens"] + s["tokens_generated"]
    assert s["moe_assignments"] >= live * 4 * 7 - 4 * 7 * len(done) * 4
    assert 0 < s["moe_assignments_held"] <= s["moe_assignments"]
    assert s["moe_assignments_held"] <= s["moe_rows_computed"]
    assert s["moe_assignments_dropped"] == 0
    assert s["moe_expert_peak"] > 0 and s["kv_grid_blocks"] > 0
    # 7 expert layers of 4 held experts a step or scan iteration; what a
    # step's emit span carries adds up to the counters
    assert s["moe_experts_held"] % (4 * 7) == 0
    assert 0 < s["moe_experts_nonempty"] <= s["moe_experts_held"]
    for key, name in (("held_rows", "moe_assignments_held"),
                      ("experts_read", "moe_experts_nonempty"),
                      ("experts_held", "moe_experts_held")):
        assert sum(ids.get(key, 0) for ids in eng.emitted) == s[name]


def _booked(monkeypatch):
    """Every ``_book_kv_grid`` call of the engines made from here on:
    (iterations, the slots' lengths as booked, growth of ``kv_grid_blocks``,
    growth of ``kv_live_blocks``)."""
    calls, real = [], LLMEngine._book_kv_grid

    def spy(self, iterations):
        was = self.stats["kv_grid_blocks"], self.stats["kv_live_blocks"]
        real(self, iterations)
        calls.append((iterations,
                      [0 if s is None else s.sched_len() for s in self.slots],
                      self.stats["kv_grid_blocks"] - was[0],
                      self.stats["kv_live_blocks"] - was[1]))
    monkeypatch.setattr(LLMEngine, "_book_kv_grid", spy)
    return calls


@pytest.mark.parametrize("n", [4, 2, 1])
def test_the_walks_grid_is_booked_in_the_latent_kernels_grid_steps(
        monkeypatch, n):
    """A latent layout books ``kv_grid_blocks`` / ``kv_live_blocks`` in the
    grid steps of its kernel's walk, ``entries_per_step`` table entries
    each, asked of the kernel module at each booking (4 for this table of
    12; 2 and 1 are that function patched: the booking follows it)."""
    assert latent_attention.entries_per_step(192 // 16, 16) == 4
    if n != 4:
        monkeypatch.setattr(latent_attention, "entries_per_step",
                            lambda mb, bs: n)
    calls = _booked(monkeypatch)
    model, _ = build(TOY, 17)
    rng = np.random.default_rng(9)
    done, eng = _serve(model, {
        0: [(rng.integers(1, 256, size=70).astype(np.int32), 9)],
        2: [(rng.integers(1, 256, size=45).astype(np.int32), 12)]})
    assert eng._tables.shape == (3, 12) and len(calls) > 5
    assert any(it > 1 for it, *_ in calls) and \
        any(it == 1 for it, *_ in calls)
    for it, lens, grid, live in calls:
        assert grid == it * 3 * (12 // n)
        assert live == it * sum(-(-(-(-x // 16)) // n) for x in lens)
    s = eng.stats
    assert s["kv_grid_blocks"] == sum(c[2] for c in calls)
    assert 0 < s["kv_live_blocks"] == sum(c[3] for c in calls)


def test_a_kv_layout_books_its_walk_in_table_entries_as_before(monkeypatch):
    """Paged K/V pools go through ``paged_attention_append``, whose grid
    is a step a table entry: the latent kernel's rule is not asked."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    def boom(mb, bs):
        raise AssertionError("a K/V layout asked the latent kernel")
    monkeypatch.setattr(latent_attention, "entries_per_step", boom)
    calls = _booked(monkeypatch)
    paddle.seed(7)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=256))
    model.eval()
    rng = np.random.default_rng(9)
    done, eng = _serve(model, {
        0: [(rng.integers(1, 256, size=70).astype(np.int32), 9)],
        2: [(rng.integers(1, 256, size=45).astype(np.int32), 12)]})
    assert eng._tables.shape == (3, 12) and len(calls) > 5
    for it, lens, grid, live in calls:
        assert grid == it * 3 * 12
        assert live == it * sum(-(-x // 16) for x in lens)


def test_the_latent_pool_and_the_state_are_sized_by_the_layout():
    model, _ = build(TOY, 1)
    eng = LLMEngine(model, **ENGINE)
    nb = eng.n_blocks
    for layer, kind in enumerate(model.cache_layout()):
        if kind.kind == "paged_latent":
            assert eng._k[layer].shape == (nb + 1, 16, 32 + 8)
            assert eng._v[layer] is None
        else:
            assert eng._k[layer]["S"].shape == (3, 2, 16, 16)
            assert eng._k[layer]["S"].dtype == jnp.float32
            assert eng._k[layer]["conv"].shape == (3, 3, 3 * 32)


# ---- (vi) what a recurrent layout refuses ---------------------------------

class _Store:
    pass


def _tp_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.parametrize("option,match", [
    (dict(scheduler="legacy", readout_stride=1), "StaticKVCache"),
    (dict(cache_impl="dense"), "dense slot buffers"),
    (dict(enable_prefix_cache=True), "prefix hashing"),
    (dict(kv_host_swap=True), "list of pool blocks"),
    (dict(kv_host_spill_bytes=1 << 20, enable_prefix_cache=False),
     "list of pool blocks"),
    (dict(speculative_k=3), "cannot be rolled back"),
    (dict(kv_cache_dtype="int8"), "scale per"),
    (dict(adapter_store=_Store()), "LoRA"),
    (dict(mesh=_tp_mesh), "kv heads are the shard dimension"),
])
def test_an_option_a_recurrent_layout_cannot_honour_raises(option, match):
    model, _ = build(dict(TOY, num_hidden_layers=4), 1)
    option = {k: v() if callable(v) and k == "mesh" else v
              for k, v in option.items()}
    with pytest.raises(ValueError, match=match):
        LLMEngine(model, **dict(ENGINE, **option))


def test_kv_shipping_is_refused_for_a_recurrent_layout():
    model, _ = build(dict(TOY, num_hidden_layers=4), 1)
    eng = LLMEngine(model, **ENGINE)
    with pytest.raises(ValueError, match="not in blocks"):
        eng.add_request([1, 2, 3], export_kv=True)
    with pytest.raises(ValueError, match="not in blocks"):
        eng.export_kv(0)
    with pytest.raises(ValueError, match="not in blocks"):
        eng.import_kv({})
    with pytest.raises(ValueError, match="not in blocks"):
        eng.export_prefix_blocks([])
    with pytest.raises(ValueError, match="not in blocks"):
        eng.import_prefix_blocks([])
    with pytest.raises(ValueError, match="embed"):
        eng.add_request([1, 2, 3], kind="embed")
