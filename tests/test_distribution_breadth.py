"""New distributions/transforms vs torch references + callbacks."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as P
import paddle_tpu.distribution as D

torch = pytest.importorskip("torch")
td = torch.distributions


class TestNewDistributions:
    def test_chi2(self):
        c = D.Chi2(3.0)
        v = P.to_tensor(np.asarray([0.5, 2.0, 5.0], "float32"))
        ref = td.Chi2(torch.tensor(3.0)).log_prob(torch.tensor(v.numpy()))
        np.testing.assert_allclose(c.log_prob(v).numpy(), ref.numpy(),
                                   rtol=1e-5)

    def test_binomial(self):
        b = D.Binomial(10.0, np.asarray(0.3, "float32"))
        v = P.to_tensor(np.asarray([0., 3., 10.], "float32"))
        ref = td.Binomial(10, torch.tensor(0.3)).log_prob(
            torch.tensor(v.numpy()))
        np.testing.assert_allclose(b.log_prob(v).numpy(), ref.numpy(),
                                   rtol=1e-4)
        P.seed(0)
        s = b.sample((2000,)).numpy()
        assert abs(s.mean() - 3.0) < 0.2
        np.testing.assert_allclose(b.mean.numpy(), 3.0, rtol=1e-6)

    def test_continuous_bernoulli(self):
        probs = np.asarray([0.2, 0.5, 0.9], "float32")
        cb = D.ContinuousBernoulli(probs)
        tref = td.ContinuousBernoulli(torch.tensor(probs))
        v = P.to_tensor(np.asarray([0.3, 0.6, 0.1], "float32"))
        np.testing.assert_allclose(cb.log_prob(v).numpy(),
                                   tref.log_prob(torch.tensor(v.numpy())),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(cb.mean.numpy(), tref.mean.numpy(),
                                   rtol=1e-4)
        P.seed(0)
        s = cb.sample((500,)).numpy()
        assert s.min() >= 0 and s.max() <= 1

    def test_multivariate_normal(self, rng):
        L = np.tril(rng.standard_normal((3, 3))).astype("float32")
        np.fill_diagonal(L, np.abs(np.diag(L)) + 0.5)
        loc = rng.standard_normal(3).astype("float32")
        mvn = D.MultivariateNormal(loc, scale_tril=L)
        tref = td.MultivariateNormal(torch.tensor(loc),
                                     scale_tril=torch.tensor(L))
        v = P.to_tensor(rng.standard_normal((5, 3)).astype("float32"))
        np.testing.assert_allclose(
            mvn.log_prob(v).numpy(),
            tref.log_prob(torch.tensor(v.numpy())).numpy(), rtol=1e-4,
            atol=1e-5)
        np.testing.assert_allclose(mvn.entropy().numpy(),
                                   tref.entropy().numpy(), rtol=1e-5)
        # covariance parameterization agrees
        mvn_cov = D.MultivariateNormal(loc, covariance_matrix=L @ L.T)
        np.testing.assert_allclose(
            mvn_cov.log_prob(v).numpy(),
            tref.log_prob(torch.tensor(v.numpy())).numpy(), rtol=1e-3,
            atol=1e-4)

    def test_mvn_kl(self, rng):
        def make(seed):
            r = np.random.default_rng(seed)
            L = np.tril(r.standard_normal((3, 3))).astype("float32")
            np.fill_diagonal(L, np.abs(np.diag(L)) + 0.5)
            return r.standard_normal(3).astype("float32"), L

        (l1, L1), (l2, L2) = make(0), make(1)
        ours = D.kl_divergence(D.MultivariateNormal(l1, scale_tril=L1),
                               D.MultivariateNormal(l2, scale_tril=L2))
        ref = td.kl_divergence(
            td.MultivariateNormal(torch.tensor(l1), scale_tril=torch.tensor(L1)),
            td.MultivariateNormal(torch.tensor(l2), scale_tril=torch.tensor(L2)))
        np.testing.assert_allclose(ours.numpy(), ref.numpy(), rtol=1e-4)

    @pytest.mark.parametrize("d,eta", [(3, 1.5), (4, 1.0), (5, 2.5)])
    def test_lkj_cholesky(self, d, eta):
        P.seed(0)
        lkj = D.LKJCholesky(d, eta)
        s = lkj.sample((3,))
        # valid Cholesky factors of correlation matrices: unit row norms
        np.testing.assert_allclose(np.linalg.norm(s.numpy(), axis=-1), 1.0,
                                   atol=1e-5)
        ref = td.LKJCholesky(d, torch.tensor(float(eta))).log_prob(
            torch.tensor(s.numpy()))
        np.testing.assert_allclose(lkj.log_prob(s).numpy(), ref.numpy(),
                                   rtol=1e-4, atol=1e-4)


class TestNewTransforms:
    def test_stick_breaking(self, rng):
        sb = D.StickBreakingTransform()
        x = jnp.asarray(rng.standard_normal(4).astype("float32"))
        y = sb.forward(x)
        np.testing.assert_allclose(float(y.sum()), 1.0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(sb.inverse(y)), np.asarray(x),
                                   atol=1e-5)
        J = jax.jacobian(lambda t: sb.forward(t)[:-1])(x)
        ref_ld = np.linalg.slogdet(np.asarray(J))[1]
        np.testing.assert_allclose(
            float(sb.forward_log_det_jacobian(x)), ref_ld, atol=1e-5)

    def test_tanh_and_power(self):
        tt = D.TanhTransform()
        x = jnp.asarray([-3.0, 0.0, 2.0])
        ref = td.transforms.TanhTransform().log_abs_det_jacobian(
            torch.tensor([-3.0, 0.0, 2.0]),
            torch.tanh(torch.tensor([-3.0, 0.0, 2.0])))
        np.testing.assert_allclose(
            np.asarray(tt.forward_log_det_jacobian(x)), ref.numpy(),
            rtol=1e-5, atol=1e-6)
        pw = D.PowerTransform(2.0)
        xs = jnp.asarray([1.0, 2.0, 3.0])
        np.testing.assert_allclose(np.asarray(pw.inverse(pw.forward(xs))),
                                   np.asarray(xs), rtol=1e-6)

    def test_chain_and_independent(self, rng):
        chain = D.ChainTransform([D.AffineTransform(1.0, 2.0),
                                  D.ExpTransform()])
        x = jnp.asarray(rng.standard_normal(5).astype("float32"))
        np.testing.assert_allclose(np.asarray(chain.inverse(chain.forward(x))),
                                   np.asarray(x), rtol=1e-5, atol=1e-6)
        ind = D.IndependentTransform(D.ExpTransform(), 1)
        ld = ind.forward_log_det_jacobian(x)
        np.testing.assert_allclose(float(ld), float(x.sum()), rtol=1e-6)

    def test_reshape_and_stack(self, rng):
        rt = D.ReshapeTransform((4,), (2, 2))
        x = jnp.asarray(rng.standard_normal((3, 4)).astype("float32"))
        assert rt.forward(x).shape == (3, 2, 2)
        np.testing.assert_allclose(np.asarray(rt.inverse(rt.forward(x))),
                                   np.asarray(x))
        st = D.StackTransform([D.ExpTransform(), D.AffineTransform(0.0, 2.0)],
                              axis=0)
        y = st.forward(jnp.asarray(np.ones((2, 3), "float32")))
        np.testing.assert_allclose(np.asarray(y[0]), np.e * np.ones(3),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(y[1]), 2 * np.ones(3))

    def test_softmax_and_abs(self):
        sm = D.SoftmaxTransform()
        y = sm.forward(jnp.asarray([1.0, 2.0, 3.0]))
        np.testing.assert_allclose(float(y.sum()), 1.0, rtol=1e-6)
        ab = D.AbsTransform()
        np.testing.assert_allclose(np.asarray(ab.forward(
            jnp.asarray([-2.0, 3.0]))), [2.0, 3.0])


class TestCallbacks:
    def test_reduce_lr_on_plateau(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        from paddle_tpu.hapi.callbacks import ReduceLROnPlateau

        cb = ReduceLROnPlateau(monitor="loss", factor=0.5, patience=1,
                               verbose=0)

        class FakeModel:
            _optimizer = opt.SGD(learning_rate=1.0,
                                 parameters=nn.Linear(2, 2).parameters())

        cb.set_model(FakeModel())
        # monitor="loss" = the TRAIN stream, checked at each epoch end
        cb.on_epoch_end(0, {"loss": 1.0})  # seeds best
        cb.on_eval_end({"loss": 99.0})     # eval stream ignored entirely
        cb.on_epoch_end(1, {"loss": 1.0})  # wait 1 -> reduce
        assert FakeModel._optimizer.get_lr() == pytest.approx(0.5)
        cb.on_epoch_end(2, {"loss": 0.2})  # improvement resets
        cb.on_epoch_end(3, {"loss": 0.2})  # flat -> reduce
        assert FakeModel._optimizer.get_lr() == pytest.approx(0.25)

    def test_reduce_lr_eval_stream_wins(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        from paddle_tpu.hapi.callbacks import ReduceLROnPlateau

        cb = ReduceLROnPlateau(monitor="eval_loss", factor=0.5, patience=1,
                               verbose=0)

        class FakeModel:
            _optimizer = opt.SGD(learning_rate=1.0,
                                 parameters=nn.Linear(2, 2).parameters())

        cb.set_model(FakeModel())
        # monitor="eval_loss" = the EVAL stream only; train logs are ignored
        cb.on_epoch_end(0, {"loss": 0.5})
        cb.on_eval_end({"loss": 0.8})  # seeds best from EVAL, not train
        assert FakeModel._optimizer.get_lr() == pytest.approx(1.0)
        cb.on_epoch_end(1, {"loss": 0.4})
        cb.on_eval_end({"loss": 0.8})  # one flat eval epoch -> reduce
        assert FakeModel._optimizer.get_lr() == pytest.approx(0.5)

    def test_reduce_lr_cooldown_holds(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        from paddle_tpu.hapi.callbacks import ReduceLROnPlateau

        cb = ReduceLROnPlateau(monitor="eval_loss", factor=0.5, patience=1,
                               cooldown=2, verbose=0)

        class FakeModel:
            _optimizer = opt.SGD(learning_rate=1.0,
                                 parameters=nn.Linear(2, 2).parameters())

        cb.set_model(FakeModel())
        lrs = []
        for epoch in range(7):
            cb.on_eval_end({"loss": 1.0})
            lrs.append(FakeModel._optimizer.get_lr())
        # Keras semantics: the epoch that exits cooldown DOES count toward
        # wait, so cooldown=2 + patience=1 holds each LR for two epochs
        assert lrs == pytest.approx([1.0, 0.5, 0.5, 0.25, 0.25, 0.125, 0.125])

    def test_reduce_lr_resets_between_fits(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        from paddle_tpu.hapi.callbacks import ReduceLROnPlateau

        cb = ReduceLROnPlateau(monitor="loss", factor=0.5, patience=2,
                               verbose=0)

        class FakeModel:
            _optimizer = opt.SGD(learning_rate=1.0,
                                 parameters=nn.Linear(2, 2).parameters())

        cb.set_model(FakeModel())
        cb.on_train_begin()
        cb.on_epoch_end(0, {"loss": 1.0})
        cb.on_epoch_end(1, {"loss": 1.0})  # wait 1
        cb.on_train_begin()                # new fit(): state resets
        cb.on_epoch_end(0, {"loss": 1.0})
        cb.on_epoch_end(1, {"loss": 1.0})  # wait 1 again, still no reduce
        assert FakeModel._optimizer.get_lr() == pytest.approx(1.0)

    def test_visualdl_gated(self):
        from paddle_tpu.hapi.callbacks import VisualDL
        with pytest.raises(RuntimeError, match="visualdl"):
            VisualDL()
