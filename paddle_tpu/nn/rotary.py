"""Rotary frequencies for a context longer than the one trained on (YaRN),
and the rotation itself on interleaved pairs, as plain ``jax.numpy`` that
any rotary model can call (``models/llama.py``'s ``precompute_rope`` is the
unscaled table in the rotate-half layout).

YaRN (Peng et al. 2023, "YaRN: Efficient Context Window Extension of Large
Language Models") keeps the fast dimensions' frequencies as trained
(extrapolation), divides the slow ones' by ``factor`` (interpolation) and
blends linearly between the two over the dimensions whose wavelength lies
between ``original_max / beta_fast`` and ``original_max / beta_slow``
rotations of the trained context; the attention's logits are then scaled
by ``mscale(factor)^2``, which the caller folds into its softmax scale.
"""
from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp


def yarn_ramp(dim, theta, original_max, beta_fast=32, beta_slow=1):
    """(lo, hi): the pair indices between which the blend runs. A pair
    that turns ``n`` times over the trained context has index ``dim *
    ln(original_max / (2 pi n)) / (2 ln theta)``; ``lo`` is that of
    ``beta_fast`` rounded down, ``hi`` that of ``beta_slow`` rounded up,
    both kept inside ``[0, dim - 1]``."""
    def index(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    return (max(math.floor(index(beta_fast)), 0),
            min(math.ceil(index(beta_slow)), dim - 1))


def yarn_inv_freq(dim, theta=10000.0, factor=1.0, original_max=4096,
                  beta_fast=32, beta_slow=1):
    """[dim // 2] float32: the angle a unit of position turns pair ``i``.
    ``factor`` 1 gives the plain ``theta^(-2i/dim)``."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1:
        return plain.astype(np.float32)
    lo, hi = yarn_ramp(dim, theta, original_max, beta_fast, beta_slow)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp            # 1: as trained, 0: interpolated
    return (plain / factor * (1.0 - keep) + plain * keep).astype(np.float32)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's magnitude correction ``0.1 mscale ln(factor) + 1`` (1 where
    the context is not extended)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rotate_pairs(x, pos, inv_freq, magnitude=1.0):
    """Rotate the pairs ``(2i, 2i + 1)`` of ``x``'s last axis by ``pos *
    inv_freq[i]``, in float32. ``pos`` has ``x``'s leading axes (further
    axes of ``x`` between them and the last, such as heads, share a
    row's position). ``magnitude`` multiplies cos and sin alike. Returns
    float32 of ``x``'s shape."""
    x = x.astype(jnp.float32)
    angle = jnp.expand_dims(pos.astype(jnp.float32),
                            tuple(range(pos.ndim, x.ndim))) \
        * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angle) * magnitude, jnp.sin(angle) * magnitude
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)
