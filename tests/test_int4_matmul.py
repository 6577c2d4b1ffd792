"""Pallas fused int4-dequant matmul (ops/kernels/int4_matmul.py).
Reference analog: the weight-only cutlass GEMMs behind
nn/quant/quantized_linear.py. Runs in interpret mode off-TPU."""
import numpy as np

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nn.quant import weight_quantize
from paddle_tpu.ops.kernels.int4_matmul import (int4_matmul,
                                                int4_matmul_tileable)


def _make(n_in, n_out, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((n_in, n_out)).astype(np.float32)
    qw, sc = weight_quantize(paddle.to_tensor(w), algo="weight_only_int4")
    q_ref = np.clip(np.round(w / sc.numpy()[None]), -8, 7)
    deq = q_ref * sc.numpy()[None]
    return qw.numpy(), sc.numpy(), deq, rng


def test_matches_dequantized_reference():
    packed, sc, deq, rng = _make(2048, 512)
    for rows in (1, 5, 8):
        x = rng.standard_normal((rows, 2048)).astype(np.float32)
        out = np.asarray(int4_matmul(jnp.asarray(x), jnp.asarray(packed),
                                     jnp.asarray(sc)))
        ref = x @ deq
        rel = np.abs(out - ref).max() / np.abs(ref).max()
        assert rel < 1e-5, (rows, rel)


def test_tileable_gating():
    assert int4_matmul_tileable(2048, 512)
    assert int4_matmul_tileable(4096, 11264)
    assert not int4_matmul_tileable(4096, 32000)  # vocab not a lane multiple
    assert not int4_matmul_tileable(1000, 512)


def test_weight_only_linear_falls_back_off_tpu():
    """On non-TPU backends weight_only_linear must keep the split-nibble
    path and stay numerically consistent with dequantize."""
    from paddle_tpu.nn.quant import weight_only_linear

    # NON-tileable n_in (1000) pins the split-nibble path on EVERY backend
    packed, sc, deq, rng = _make(1000, 512, seed=1)
    x = paddle.to_tensor(rng.standard_normal((3, 1000)).astype(np.float32))
    y = weight_only_linear(x, paddle.to_tensor(packed),
                           weight_scale=paddle.to_tensor(sc),
                           weight_dtype="int4").numpy()
    np.testing.assert_allclose(y, x.numpy() @ deq, rtol=2e-4, atol=2e-4)
