"""Pallas TPU fused AdamW update — single-pass multi-precision step.

Reference analog: phi's fused_adam / multi_tensor adam kernels
(paddle/phi/kernels/fused_adam_kernel.h) that the reference optimizer uses to
avoid per-tensor kernel-launch and read-modify-write traffic. On TPU the
bottleneck is HBM bandwidth: the XLA lowering of the update chain re-reads the
fp32 moment/master buffers across fusion boundaries, sustaining only ~½ of
peak bandwidth. This kernel does the whole update in ONE pass per block —
read g(bf16), m, v, master(fp32); write m, v, master, p(bf16) — which is the
minimum possible traffic (~24.5 GB for a 880M-param model vs ~45 GB observed
from the XLA path).

Math (AdamW, decoupled weight decay, bias-corrected):
    m = b1*m + (1-b1)*g
    v = b2*v + (1-b2)*g^2
    update = (m/bc1) / (sqrt(v)/sqrt(bc2) + eps)
    master = master - lr*update - lr*wd*master
    p_bf16 = cast(master)
Scalars alpha=lr/bc1, c2=1/sqrt(bc2), lr, lr*wd arrive via SMEM so one
compiled kernel serves every step.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Z = np.int32(0)  # i32 index-map literal (x64 is on)


def _interpret():
    return jax.default_backend() not in ("tpu",)


def _adamw_kernel(scal_ref, g_ref, m_ref, v_ref, mw_ref,
                  om_ref, ov_ref, omw_ref, op_ref, *, beta1, beta2, eps):
    alpha = scal_ref[0, 0]  # lr / bias_correction1
    c2 = scal_ref[0, 1]     # 1 / sqrt(bias_correction2)
    lrwd = scal_ref[0, 2]   # lr * weight_decay (0 when decay masked off)
    g = g_ref[...].astype(jnp.float32)
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * (g * g)
    denom = jnp.sqrt(v) * c2 + eps
    mw = mw_ref[...]
    new_mw = mw - alpha * (m / denom) - lrwd * mw
    om_ref[...] = m
    ov_ref[...] = v
    omw_ref[...] = new_mw
    op_ref[...] = new_mw.astype(op_ref.dtype)


def _pick_block(rows, cols):
    """Rows per block: 9 live fp32-sized buffers of (block_r, cols) must fit
    the ~16 MB scoped-VMEM budget; stay a multiple of 8 (f32 sublane)."""
    # pallas double-buffers every in/out block, so the scoped-VMEM footprint
    # is ~2x the 9 live fp32-sized buffers — budget 4 MB of logical blocks
    target = 4 * 1024 * 1024 // (9 * 4 * max(cols, 1))
    br = max(8, min(rows, (target // 8) * 8))
    while rows % br:
        br -= 8
        if br <= 0:
            return rows
    return br


def _fused_adamw_2d(scalars, g, m, v, mw, *, beta1, beta2, eps, out_dtype):
    rows, cols = m.shape
    br = _pick_block(rows, cols)
    grid = (rows // br,)

    Z = np.int32(0)

    def idx(i):
        return (i, Z)

    bs = lambda: pl.BlockSpec((br, cols), idx)
    scal_spec = pl.BlockSpec((1, 3), lambda i: (Z, Z))
    out_shapes = (
        jax.ShapeDtypeStruct((rows, cols), jnp.float32),  # m
        jax.ShapeDtypeStruct((rows, cols), jnp.float32),  # v
        jax.ShapeDtypeStruct((rows, cols), jnp.float32),  # master
        jax.ShapeDtypeStruct((rows, cols), out_dtype),    # bf16/low param
    )
    kernel = functools.partial(_adamw_kernel, beta1=float(beta1),
                               beta2=float(beta2), eps=float(eps))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[scal_spec, bs(), bs(), bs(), bs()],
        out_specs=(bs(), bs(), bs(), bs()),
        out_shape=out_shapes,
        # m/v/master update in place — no state copies in HBM (the outer
        # train step donates these buffers)
        input_output_aliases={2: 0, 3: 1, 4: 2},
        name="fused_adamw",
        interpret=_interpret(),
    )(scalars, g, m, v, mw)


def _tile_plan(shape):
    """(rows, cols) 2-D factorization for the kernel, or None when the shape
    cannot be tiled within the VMEM budget. Pure shape computation — callers
    (incl. the shard_map wrapper) can pre-flight before committing to the
    pallas path."""
    n = int(np.prod(shape)) if shape else 1
    # factor into (rows, cols) with cols a multiple of 128 when possible
    if len(shape) >= 2:
        rows = int(shape[0])
        cols = n // rows
    else:
        cols = min(n, 131072)
        while n % cols:
            cols //= 2
        cols = max(cols, 1)
        rows = n // cols
    if rows * cols != n or (rows % 8 != 0 and rows != 1):
        # odd leading dim: try to refactor n into tileable (rows, cols)
        cols = 1
        for c in (131072, 65536, 32768, 16384, 8192, 4096, 2048, 1024, 512,
                  256, 128):
            if n % c == 0 and (n // c) % 8 == 0:
                cols = c
                break
        if cols > 1:
            rows = n // cols
        else:
            rows, cols = 1, n
    # unified VMEM guard: 9 live fp32-sized buffers, double-buffered by
    # pallas, must stay within the ~16 MB scoped budget. _pick_block can't go
    # below 8 rows, so wide-column tensors can still exceed it — refuse and
    # let the generic XLA update handle those.
    br = _pick_block(rows, cols)
    if br * cols > (4 * 1024 * 1024) // (9 * 4):
        return None
    return rows, cols


def fused_adamw_update(p_low, g, m, v, master, lr, step, *, beta1=0.9,
                       beta2=0.999, eps=1e-8, weight_decay=0.0,
                       apply_decay=True):
    """One fused AdamW step for a low-precision param with fp32 master/moments.

    Returns (new_p_low, new_m, new_v, new_master), or None when the shape
    cannot be tiled within the VMEM budget (caller falls back to the generic
    XLA update). All tensors keep their logical shape; internally flattened
    to 2-D blocks.
    """
    shape = m.shape
    plan = _tile_plan(shape)
    if plan is None:
        return None
    rows, cols = plan
    stepf = step.astype(jnp.float32)
    bc1 = 1.0 - jnp.power(beta1, stepf)
    bc2 = 1.0 - jnp.power(beta2, stepf)
    lr32 = lr.astype(jnp.float32)
    wd = lr32 * weight_decay if (weight_decay and apply_decay) else \
        jnp.zeros((), jnp.float32)
    scalars = jnp.stack([lr32 / bc1, 1.0 / jnp.sqrt(bc2), wd]) \
        .astype(jnp.float32).reshape(1, 3)

    g2 = g.reshape(rows, cols)
    m2 = m.reshape(rows, cols)
    v2 = v.reshape(rows, cols)
    mw2 = master.reshape(rows, cols)
    nm, nv, nmw, np_low = _fused_adamw_2d(
        scalars, g2, m2, v2, mw2, beta1=beta1, beta2=beta2, eps=eps,
        out_dtype=p_low.dtype)
    return (np_low.reshape(shape), nm.reshape(shape), nv.reshape(shape),
            nmw.reshape(shape))


def _local_shape(mesh, spec, shape):
    """Per-device shape of `shape` stored as PartitionSpec `spec`, or None if
    a sharded dim doesn't divide (caller falls back to the XLA update)."""
    spec_t = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    local = list(shape)
    for d, ax in enumerate(spec_t):
        if ax is None:
            continue
        n = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            n *= mesh.shape[a]
        if local[d] % n:
            return None
        local[d] //= n
    return tuple(local)


def fused_adamw_update_sharded(mesh, spec, p_low, g, m, v, master, lr, step,
                               **kw):
    """Fused AdamW over SHARDED state: each device runs the single-pass pallas
    kernel on its local shard via shard_map (the update is elementwise, so no
    communication is needed inside). This is what lets ZeRO keep the fused
    optimizer — GSPMD can't partition a pallas_call, but it doesn't have to.

    Returns (new_p_low, new_m, new_v, new_master) or None when the local
    shard isn't tileable (caller falls back to the generic XLA update).
    Reference analog: the sharded fused update in
    fleet/meta_optimizers/dygraph_optimizer/dygraph_sharding_optimizer.py:54.
    """
    local = _local_shape(mesh, spec, tuple(m.shape))
    if local is None or _tile_plan(local) is None:
        return None
    from jax.sharding import PartitionSpec
    ps = PartitionSpec(*(tuple(spec) + (None,) * (m.ndim - len(tuple(spec)))))
    rep = PartitionSpec()

    def local_update(p_l, g_l, m_l, v_l, mw_l, lr_s, step_s):
        return fused_adamw_update(p_l, g_l, m_l, v_l, mw_l, lr_s, step_s, **kw)

    f = jax.shard_map(local_update, mesh=mesh,
                      in_specs=(ps, ps, ps, ps, ps, rep, rep),
                      out_specs=(ps, ps, ps, ps), check_vma=False)
    return f(p_low, g, m, v, master, jnp.asarray(lr), jnp.asarray(step))


# ---------------------------------------------------------------------------
# master-weight-free AdamW with stochastic rounding
# ---------------------------------------------------------------------------

def _sr_round_bf16(x_f32, seed_i, base_idx):
    """Stochastically round fp32 -> bf16: add position-hashed uniform bits
    below the bf16 mantissa cut, then truncate. E[round(x)] == x, which is
    what lets bf16 params integrate small updates WITHOUT an fp32 master
    copy (the classic TPU trick; reference keeps fp32 masters instead)."""
    bits = jax.lax.bitcast_convert_type(x_f32, jnp.int32)
    h = base_idx * np.int32(-1640531527) + seed_i
    h = h ^ jax.lax.shift_right_logical(h, np.int32(16))
    h = h * np.int32(-2048144789)
    h = h ^ jax.lax.shift_right_logical(h, np.int32(13))
    h = h * np.int32(-1028477387)
    h = h ^ jax.lax.shift_right_logical(h, np.int32(16))
    r16 = h & np.int32(0xFFFF)
    rounded = (bits + r16) & np.int32(-65536)   # keep the top 16 bits
    return jax.lax.bitcast_convert_type(rounded, jnp.float32) \
        .astype(jnp.bfloat16)


def _adamw_sr_kernel(scal_ref, seed_ref, g_ref, p_ref, m_ref, v_ref,
                     om_ref, ov_ref, op_ref, *, beta1, beta2, eps, bi, cols):
    alpha = scal_ref[0, 0]   # lr / bias_correction1
    c2 = scal_ref[0, 1]      # 1 / sqrt(bias_correction2)
    lrwd = scal_ref[0, 2]    # lr * weight_decay (0 when decay masked off)
    seed_i = jax.lax.bitcast_convert_type(seed_ref[...], jnp.int32)[0, 0]
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    m = beta1 * m_ref[...].astype(jnp.float32) + (1.0 - beta1) * g
    v = beta2 * v_ref[...].astype(jnp.float32) + (1.0 - beta2) * (g * g)
    denom = jnp.sqrt(v) * c2 + eps
    new_p = p - alpha * (m / denom) - lrwd * p
    # absolute element index (rows offset by the grid program)
    i = pl.program_id(0)
    br = om_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, om_ref.shape, 0) \
        + i * np.int32(br)
    cc = jax.lax.broadcasted_iota(jnp.int32, om_ref.shape, 1)
    idx = rows * np.int32(cols) + cc + np.int32(bi)
    om_ref[...] = m.astype(om_ref.dtype)
    ov_ref[...] = v.astype(ov_ref.dtype)
    op_ref[...] = _sr_round_bf16(new_p, seed_i, idx)


def fused_adamw_sr_update(p, g, m, v, lr, step, seed_f, *, beta1=0.9,
                          beta2=0.999, eps=1e-8, weight_decay=0.0,
                          apply_decay=True):
    """Master-weight-free fused AdamW: bf16 params + bf16 moments, fp32 math
    in-VMEM, stochastic rounding on the param write. One pass reads
    g+p+m+v (8 B/param) and writes p+m+v (6 B/param) — ~36% less HBM
    traffic than the master-weight chain, and no fp32 master resident at
    all. Returns (new_p, new_m, new_v) or None when untileable."""
    shape = m.shape
    plan = _tile_plan(shape)
    if plan is None:
        return None
    rows, cols = plan
    stepf = step.astype(jnp.float32)
    bc1 = 1.0 - jnp.power(beta1, stepf)
    bc2 = 1.0 - jnp.power(beta2, stepf)
    lr32 = lr.astype(jnp.float32)
    wd = lr32 * weight_decay if (weight_decay and apply_decay) else \
        jnp.zeros((), jnp.float32)
    scalars = jnp.stack([lr32 / bc1, 1.0 / jnp.sqrt(bc2), wd]) \
        .astype(jnp.float32).reshape(1, 3)

    br = _pick_block(rows, cols)
    g2, p2 = g.reshape(rows, cols), p.reshape(rows, cols)
    m2, v2 = m.reshape(rows, cols), v.reshape(rows, cols)
    kernel = functools.partial(_adamw_sr_kernel, beta1=float(beta1),
                               beta2=float(beta2), eps=float(eps), bi=0,
                               cols=cols)
    bs = lambda: pl.BlockSpec((br, cols), lambda i: (i, Z))
    nm, nv, np_ = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((1, 3), lambda i: (Z, Z)),
                  pl.BlockSpec((1, 1), lambda i: (Z, Z)),
                  bs(), bs(), bs(), bs()],
        out_specs=(bs(), bs(), bs()),
        out_shape=(
            jax.ShapeDtypeStruct((rows, cols), m.dtype),
            jax.ShapeDtypeStruct((rows, cols), v.dtype),
            jax.ShapeDtypeStruct((rows, cols), p.dtype),
        ),
        input_output_aliases={4: 0, 5: 1, 3: 2},
        name="fused_adamw_sr",
        interpret=_interpret(),
    )(scalars, seed_f, g2, p2, m2, v2)
    return (np_.reshape(shape), nm.reshape(shape), nv.reshape(shape))


def fused_adamw_sr_update_sharded(mesh, spec, p, g, m, v, lr, step, seed_f,
                                  **kw):
    """Stochastic-rounding AdamW over SHARDED state (the ZeRO/TP composition
    of :func:`fused_adamw_sr_update`, mirroring
    :func:`fused_adamw_update_sharded`). Each device runs the SR kernel on
    its local shard; the rounding seed is folded with the device's mesh
    coordinates so shards draw decorrelated rounding streams. Returns
    (new_p, new_m, new_v) or None when the local shard isn't tileable."""
    local = _local_shape(mesh, spec, tuple(m.shape))
    if local is None or _tile_plan(local) is None:
        return None
    from jax.sharding import PartitionSpec
    ps = PartitionSpec(*(tuple(spec) + (None,) * (m.ndim - len(tuple(spec)))))
    rep = PartitionSpec()
    axes = [a for e in tuple(spec) if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]

    def local_update(p_l, g_l, m_l, v_l, lr_s, step_s, seed_l):
        si = jax.lax.bitcast_convert_type(seed_l, jnp.int32)
        for ax in axes:
            si = si ^ (jax.lax.axis_index(ax).astype(jnp.int32)
                       * np.int32(-1640531527))
        seed_dev = jax.lax.bitcast_convert_type(si, jnp.float32)
        return fused_adamw_sr_update(p_l, g_l, m_l, v_l, lr_s, step_s,
                                     seed_dev, **kw)

    f = jax.shard_map(local_update, mesh=mesh,
                      in_specs=(ps, ps, ps, ps, rep, rep, rep),
                      out_specs=(ps, ps, ps), check_vma=False)
    return f(p, g, m, v, jnp.asarray(lr), jnp.asarray(step), seed_f)
