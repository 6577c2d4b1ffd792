"""Fused Pallas AdamW kernel + TrainStep gradient accumulation + fused
Llama projection modes — the single-chip MFU work."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.jit.api import TrainStep


def t2n(t):
    return np.asarray(t._value if hasattr(t, "_value") else t)


def _train(flag, shapes, steps=3, wd=0.01):
    rng = np.random.default_rng(0)
    paddle.set_flags({"use_fused_adamw": flag})
    ps = []
    for sh in shapes:
        p = paddle.create_parameter(list(sh), "bfloat16")
        p._value = jnp.asarray(rng.standard_normal(sh), jnp.bfloat16)
        ps.append(p)
    opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=ps,
                                 weight_decay=wd, multi_precision=True)
    for i in range(steps):
        for p in ps:
            p.grad = paddle.to_tensor(jnp.asarray(
                rng.standard_normal(p.shape) * (i + 1), jnp.bfloat16))
        opt.step()
    masters = [np.asarray(opt._slots[id(p)]["master_weight"]) for p in ps]
    return [np.asarray(p._value, np.float32) for p in ps], masters


@pytest.mark.parametrize("wd", [0.0, 0.01])
def test_fused_adamw_matches_generic(wd):
    shapes = [(16, 256), (256,), (8, 8, 4)]  # 2-D, 1-D, odd-rank
    try:
        pf, mf = _train(True, shapes, wd=wd)
        pg, mg = _train(False, shapes, wd=wd)
    finally:
        paddle.set_flags({"use_fused_adamw": True})
    for a, b in zip(pf, pg):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(mf, mg):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_fused_adamw_skips_unsupported():
    # coupled L2 (plain Adam with float weight_decay) must use the generic path
    paddle.set_flags({"use_fused_adamw": True})
    w = paddle.create_parameter([8, 128], "bfloat16")
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=[w],
                                weight_decay=0.1, multi_precision=True)
    assert opt._apply_fused(w, None, {"master_weight": 1}, None, None,
                            True) is None
    opt2 = paddle.optimizer.Adam(learning_rate=0.01, parameters=[w],
                                 amsgrad=True, multi_precision=True)
    assert opt2._apply_fused(w, None, {"master_weight": 1}, None, None,
                             True) is None


def test_trainstep_accumulation_equals_mean_grad():
    def build():
        paddle.seed(0)
        m = nn.Linear(8, 4)
        opt = paddle.optimizer.AdamW(learning_rate=0.1,
                                     parameters=m.parameters(),
                                     weight_decay=0.0)
        return m, opt

    rng = np.random.default_rng(0)
    xs = [paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
          for _ in range(4)]
    y = paddle.to_tensor(np.zeros((4, 4), np.float32))
    loss_fn = lambda m, a, b: nn.MSELoss()(m(a), b)

    m1, o1 = build()
    s1 = TrainStep(m1, loss_fn, o1, accumulate_steps=4)
    for x in xs:
        s1(x, y)
    # exactly one optimizer step happened
    assert o1._step_count == 1

    m2, o2 = build()
    loss = sum((loss_fn(m2, x, y) for x in xs), paddle.to_tensor(0.0)) / 4.0
    loss.backward()
    o2.step()
    np.testing.assert_allclose(t2n(m1.weight), t2n(m2.weight), atol=1e-6)
    np.testing.assert_allclose(t2n(m1.bias), t2n(m2.bias), atol=1e-6)


def test_trainstep_accumulation_multiple_cycles():
    paddle.seed(0)
    m = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=m.parameters())
    step = TrainStep(m, lambda mm, a: (mm(a) ** 2).sum(), opt,
                     accumulate_steps=2)
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((2, 4)).astype(np.float32))
    losses = [float(t2n(step(x))) for _ in range(6)]
    assert opt._step_count == 3
    assert losses[-1] < losses[0]


def test_llama_fused_projection_modes_match():
    import jax
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    rng = np.random.default_rng(0)
    paddle.seed(0)
    m_u = LlamaForCausalLM(LlamaConfig.tiny())
    m_f = LlamaForCausalLM(LlamaConfig.tiny(fuse_attention_qkv=True,
                                            fuse_swiglu=True))
    sd = dict(m_u.named_parameters())
    for name, p in m_f.named_parameters():
        if "qkv_proj" in name:
            base = name.replace("qkv_proj", "{}")
            p._value = jnp.concatenate(
                [sd[base.format(k)]._value
                 for k in ("q_proj", "k_proj", "v_proj")], axis=1)
        elif "gate_up_proj" in name:
            base = name.replace("gate_up_proj", "{}")
            p._value = jnp.concatenate(
                [sd[base.format(k)]._value
                 for k in ("gate_proj", "up_proj")], axis=1)
        elif name in sd:
            p._value = sd[name]._value
    ids = paddle.to_tensor(rng.integers(0, 1024, (2, 16)), dtype="int32")
    np.testing.assert_allclose(t2n(m_u(ids)), t2n(m_f(ids)), atol=5e-5)


def test_fused_adamw_untileable_shape_falls_back():
    # vocab padded to 32003 (odd leading dim, huge n): the kernel must refuse
    # (return None) and the generic XLA path must still train the tensor
    from paddle_tpu.ops.kernels.fused_adamw import fused_adamw_update
    m = jnp.zeros((32003, 64), jnp.float32)
    out = fused_adamw_update(jnp.zeros((32003, 64), jnp.bfloat16),
                             jnp.ones((32003, 64), jnp.bfloat16), m, m, m,
                             jnp.asarray(0.01), jnp.asarray(1, jnp.int32))
    assert out is None
    paddle.set_flags({"use_fused_adamw": True})
    w = paddle.create_parameter([1003, 8], "bfloat16")
    before = t2n(w).copy()
    opt = paddle.optimizer.AdamW(learning_rate=0.05, parameters=[w],
                                 multi_precision=True)
    w.grad = paddle.to_tensor(jnp.ones((1003, 8), jnp.bfloat16))
    opt.step()
    assert not np.allclose(t2n(w), before)


def test_fused_flag_toggle_takes_effect():
    # toggling the flag between steps must not be silently ignored by the
    # cached jit (cache is keyed on the flag)
    paddle.set_flags({"use_fused_adamw": True})
    w = paddle.create_parameter([8, 128], "bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=[w],
                                 multi_precision=True)
    w.grad = paddle.to_tensor(jnp.ones((8, 128), jnp.bfloat16))
    opt.step()
    k1 = opt._jit_shape_key
    paddle.set_flags({"use_fused_adamw": False})
    try:
        w.grad = paddle.to_tensor(jnp.ones((8, 128), jnp.bfloat16))
        opt.step()
        assert opt._jit_shape_key != k1
    finally:
        paddle.set_flags({"use_fused_adamw": True})


def test_fused_softmax_ce_matches_reference():
    # the memory-lean custom-vjp CE must match explicit fp32 log_softmax in
    # value AND gradient, including ignore_index and bf16 logits
    import jax
    from paddle_tpu.ops.kernels.fused_ce import fused_softmax_ce
    rng = np.random.default_rng(0)
    T, V = 32, 257
    logits = jnp.asarray(rng.standard_normal((T, V)) * 3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, T))
    labels = labels.at[3].set(-100)

    def ref(l):
        logp = jax.nn.log_softmax(l.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(
            logp, jnp.clip(labels, 0, V - 1)[:, None], -1)[:, 0]
        valid = labels != -100
        return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.sum(valid)

    def fused(l):
        valid = labels != -100
        return jnp.sum(fused_softmax_ce(l, labels, -100)) / jnp.sum(valid)

    for dt, atol in ((jnp.float32, 1e-6), (jnp.bfloat16, 2e-3)):
        v1, g1 = jax.value_and_grad(ref)(logits.astype(dt))
        v2, g2 = jax.value_and_grad(fused)(logits.astype(dt))
        assert abs(float(v1) - float(v2)) < 1e-5
        # bf16 grads are quantized post-computation — one ulp at these
        # magnitudes is ~1e-4, so the tolerance must be dtype-aware
        np.testing.assert_allclose(np.asarray(g1, np.float32),
                                   np.asarray(g2, np.float32), atol=atol)


def test_cross_entropy_routes_hard_label_fast_path(rng):
    # F.cross_entropy end-to-end through the fused path: grads + reductions
    import paddle_tpu.nn.functional as F
    logits = paddle.to_tensor(
        rng.standard_normal((4, 6, 11)).astype(np.float32),
        stop_gradient=False)
    labels = paddle.to_tensor(rng.integers(0, 11, (4, 6)))
    loss = F.cross_entropy(logits, labels)
    loss.backward()
    g = t2n(logits.grad)
    assert np.isfinite(g).all() and abs(float(g.sum())) < 1e-4
    # reduction='none' keeps label shape
    ln = F.cross_entropy(paddle.to_tensor(t2n(logits)), labels,
                         reduction="none")
    assert t2n(ln).shape == (4, 6)
    # weighted path must still take the generic branch (weights unsupported
    # in the fused kernel)
    w = paddle.to_tensor(rng.random(11).astype(np.float32))
    lw = F.cross_entropy(paddle.to_tensor(t2n(logits)), labels, weight=w)
    assert np.isfinite(float(t2n(lw)))


class TestStochasticRoundingAdamW:
    """Master-weight-free fused AdamW (flag adamw_stochastic_rounding):
    bf16 params + in-kernel stochastic rounding replace the fp32 master."""

    def _seed_f(self, s=3):
        return jax.lax.bitcast_convert_type(
            jnp.asarray([[np.int32(s)]], jnp.int32), jnp.float32)

    def test_rounding_is_unbiased(self):
        from paddle_tpu.ops.kernels.fused_adamw import fused_adamw_sr_update
        # one step from p=0 with constant grad: fp32 update is exactly
        # -lr * g / (|g| + eps) per element = -0.01; a bf16 write must
        # round stochastically AROUND the fp32 value — mean over many
        # elements ~= fp32 value, and BOTH neighboring bf16 values occur
        n = 65536
        p = jnp.zeros((8, n // 8), jnp.bfloat16)
        g = jnp.full((8, n // 8), 1.0, jnp.bfloat16)
        m = jnp.zeros((8, n // 8), jnp.bfloat16)
        v = jnp.zeros((8, n // 8), jnp.bfloat16)
        lr = jnp.float32(0.0103)  # exact value straddles bf16 grid points
        out = fused_adamw_sr_update(p, g, m, v, lr, jnp.int32(1),
                                    self._seed_f(), weight_decay=0.0,
                                    apply_decay=False)
        assert out is not None
        new_p = np.asarray(out[0], np.float32)
        uniq = np.unique(new_p)
        assert len(uniq) >= 2, "no stochasticity: single rounded value"
        # unbiased: the mean tracks the fp32 target much tighter than one ulp
        target = -0.0103 / (1.0 + 1e-8)
        ulp = np.abs(uniq[1] - uniq[0])
        assert abs(new_p.mean() - target) < 0.05 * ulp, \
            (new_p.mean(), target, ulp)

    def test_deterministic_per_seed(self):
        from paddle_tpu.ops.kernels.fused_adamw import fused_adamw_sr_update
        rng = np.random.default_rng(0)
        p = jnp.asarray(rng.standard_normal((8, 256)), jnp.bfloat16)
        g = jnp.asarray(rng.standard_normal((8, 256)), jnp.bfloat16)
        m = jnp.zeros((8, 256), jnp.bfloat16)
        v = jnp.zeros((8, 256), jnp.bfloat16)
        a = fused_adamw_sr_update(p, g, m, v, jnp.float32(1e-2), jnp.int32(1),
                                  self._seed_f(7))
        b = fused_adamw_sr_update(p, g, m, v, jnp.float32(1e-2), jnp.int32(1),
                                  self._seed_f(7))
        c = fused_adamw_sr_update(p, g, m, v, jnp.float32(1e-2), jnp.int32(1),
                                  self._seed_f(8))
        np.testing.assert_array_equal(np.asarray(a[0], np.float32),
                                      np.asarray(b[0], np.float32))
        assert not np.array_equal(np.asarray(a[0], np.float32),
                                  np.asarray(c[0], np.float32))

    def test_training_tracks_fp32_master_baseline(self):
        """bf16+SR training must track the fp32-master trajectory (loosely
        — rounding noise), while bf16 WITHOUT SR visibly stalls on small
        updates. The whole point of the flag."""
        import paddle_tpu as paddle
        from paddle_tpu.core.flags import set_flags
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as opt_mod
        from paddle_tpu.jit.api import TrainStep

        def build(sr):
            paddle.seed(0)
            model = nn.Sequential(nn.Linear(32, 64), nn.GELU(),
                                  nn.Linear(64, 32))
            for p in model.parameters():
                p._value = p._value.astype(jnp.bfloat16)
            opt = opt_mod.AdamW(learning_rate=3e-3,
                                parameters=model.parameters(),
                                multi_precision=not sr)
            return TrainStep(model, lambda m, x, y: F.mse_loss(m(x), y), opt)

        rng = np.random.default_rng(1)
        x = paddle.to_tensor(rng.standard_normal((64, 32)).astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((64, 32)).astype(np.float32))

        base = build(sr=False)         # fp32 master (reference chain)
        ref = [float(np.asarray(base(x, y)._value)) for _ in range(30)]

        set_flags({"adamw_stochastic_rounding": True})
        try:
            sr_step = build(sr=True)   # bf16-only + stochastic rounding
            got = [float(np.asarray(sr_step(x, y)._value))
                   for _ in range(30)]
        finally:
            set_flags({"adamw_stochastic_rounding": False})

        # final loss within 15% of the master-weight trajectory
        assert got[-1] < ref[-1] * 1.15 + 1e-3, (got[-1], ref[-1])
        assert got[-1] < got[0], "SR training did not progress"


def test_stochastic_rounding_under_zero_sharding():
    """SR + ZeRO composition (review finding: the generic fallback would
    DETERMINISTICALLY round bf16 and stall): the shard_map SR kernel runs on
    the sharded state, slots stay 1/N, and training makes progress."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet import fleet_state
    from paddle_tpu.core.flags import set_flags
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt_mod

    fleet_state.set_hcg(None)
    fleet_state.set_strategy(None)
    set_flags({"adamw_stochastic_rounding": True})
    try:
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(32, 64), nn.GELU(),
                              nn.Linear(64, 32))
        for p in model.parameters():
            p._value = p._value.astype(jnp.bfloat16)
        opt = opt_mod.AdamW(learning_rate=3e-3,
                            parameters=model.parameters(),
                            multi_precision=False)
        model_d, opt_d, _ = dist.group_sharded_parallel(model, opt, "os_g")
        step = TrainStep(model_d, lambda m, x, y: F.mse_loss(m(x), y), opt_d)
        rng = np.random.default_rng(1)
        x = paddle.to_tensor(rng.standard_normal((64, 32)).astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((64, 32)).astype(np.float32))
        losses = [float(np.asarray(step(x, y)._value)) for _ in range(20)]
        assert losses[-1] < 0.7 * losses[0], f"SR+ZeRO stalled: {losses[::5]}"
        for p in step.params:
            for k, v in opt._slots[id(p)].items():
                if hasattr(v, "addressable_shards") and v.shape:
                    s = next(iter(v.addressable_shards)).data
                    assert s.size == v.size // 8, (k, v.shape, s.shape)
    finally:
        set_flags({"adamw_stochastic_rounding": False})
        fleet_state.set_hcg(None)
        fleet_state.set_strategy(None)
