"""SPMD collective pipeline — the compute core of pipeline parallelism.

Reference analog: fleet/meta_parallel/pipeline_parallel.py (1F1B at
forward_backward_pipeline:684) + p2p_communication.py over NCCL send/recv.

TPU-native design: the pipeline is ONE compiled program. Stages are structurally
identical (transformer repeat blocks); per-stage params carry a leading [S] dim
sharded over the 'pp' mesh axis. A lax.scan steps microbatches through the ring:
each tick every stage runs its block, then activations ppermute to the next stage
over ICI. Backward is jax autodiff of the scan — XLA schedules it as the reverse
pipeline (the 1F1B-equivalent interleave emerges from the dependence structure
rather than a hand-written schedule); `remat` trades activation memory like the
reference's recompute_interval.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _psum(y, axis):
    """psum that survives the XLA *CPU* backend's AllReducePromotion pass.

    jax 0.7 lowers an in-shard_map psum with a sharding annotation INSIDE the
    reduction body (sdy.sharding_constraint -> an HLO `copy`); promoting a
    16-bit all-reduce then dies in CloneAllReduce ("Invalid binary
    instruction opcode copy"). CPU promotes all 16-bit all-reduces, so
    reduce in f32 there; real TPU backends reduce bf16 natively and keep the
    half-width ICI traffic."""
    if jax.default_backend() == "cpu" and y.dtype in (jnp.bfloat16,
                                                      jnp.float16):
        return jax.lax.psum(y.astype(jnp.float32), axis).astype(y.dtype)
    return jax.lax.psum(y, axis)


def spmd_pipeline(stage_fn, stacked_params, x_mb, mesh, axis="pp", remat=False):
    """Run microbatches through a ring of identical stages.

    stage_fn(params, x) -> y, with y.shape == x.shape (inter-stage activation).
    stacked_params: pytree, each leaf [S, ...] (S = #stages), sharded over `axis`.
    x_mb: [M, microbatch, ...] inputs for stage 0, replicated over `axis`; any
          dp/mp sharding on the microbatch dims stays automatic under GSPMD.
    Returns y_mb [M, microbatch, ...] — last stage's outputs, replicated over axis.
    """
    jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
    S = jmesh.shape[axis]
    M = x_mb.shape[0]
    assert M >= 1
    T = M + S - 1
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    # dp (and any other non-pp axis) is automatic: the input batch keeps its own
    # sharding and GSPMD partitions the body; specs only name the manual pp axis.
    batch_spec = P()

    def per_device(params_l, x):
        params = jax.tree_util.tree_map(lambda a: a[0], params_l)
        idx = jax.lax.axis_index(axis)

        def step(state, t):
            mb = jax.lax.dynamic_index_in_dim(x, jnp.clip(t, 0, M - 1), 0,
                                              keepdims=False)
            cur = jnp.where(idx == 0, mb, state)
            out = fn(params, cur)
            perm = [(i, (i + 1) % S) for i in range(S)]
            nxt = jax.lax.ppermute(out, axis, perm)
            return nxt, out

        _, outs = jax.lax.scan(step, jnp.zeros_like(x[0]), jnp.arange(T))
        y = outs[S - 1:]                       # [M, mb, ...] valid on last stage
        y = jnp.where(idx == S - 1, y, jnp.zeros_like(y))
        return _psum(y, axis)           # replicate last stage's outputs

    spec_params = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    # manual over the pipeline axis only: dp/mp/sharding axes stay automatic, so
    # GSPMD partitions the stage body (TP matmuls, dp batch) inside the ring.
    return shard_map(per_device, mesh=jmesh,
                     in_specs=(spec_params, batch_spec),
                     out_specs=batch_spec, axis_names={axis},
                     check_vma=False)(stacked_params, x_mb)


def scheduled_pipeline(stage_fn, stacked_params, x_mb, mesh, axis="pp",
                       zero_bubble=False):
    """Explicit micro-batch schedule: 1F1B / ZBH1 (reference:
    fleet/meta_parallel/pipeline_parallel.py:684 forward_backward_pipeline,
    passes/pipeline_scheduler_pass/pipeline_zero_bubble.py).

    Unlike :func:`spmd_pipeline` (whole-scan autodiff — the FThenB residency
    policy: XLA keeps every microbatch's intermediates), this runtime owns the
    backward schedule via ``jax.custom_vjp``:

    - **forward**: ring scan; each stage stores ONLY its M stage-boundary
      inputs, sharded over `axis` (per-device boundary memory = M x microbatch,
      the 1F1B residency bound with recompute — nothing else survives).
    - **backward (1F1B)**: reverse ring scan; at each tick a stage recomputes
      one microbatch's block from its saved boundary and applies its vjp —
      at most one microbatch's intermediates are ever live per device; dx
      ppermutes upstream; dw accumulates into the stage's param-grad shard.
    - **backward (ZBH1, zero_bubble=True)**: the reference's W-split, the
      TPU-native way: the reverse scan computes ONLY dx (XLA dead-code
      eliminates the dw GEMMs), so the serial cross-stage dependency chain —
      the thing that makes the bubble — contains just the dx work; dw for all
      stages/microbatches is computed afterwards in a scan with NO ppermute,
      i.e. completely off the ring's critical path, free for XLA's
      latency-hiding scheduler to overlap. Costs one extra forward recompute
      and an M-deep dy buffer per stage — the same memory-for-bubble trade
      zero-bubble makes.

    Micro-timing within a tick is XLA's prerogative (there is no host schedule
    loop to drive on TPU); what each mode pins is the *residency policy* and
    the *dependency structure*, which is what the schedules differ by.
    Compiled-program evidence that the W-split lands as claimed — loop
    computations carrying the dw matmuls with ZERO collective-permutes,
    disjoint from the permute-carrying ring loops — is captured in
    ``docs/artifacts/zbh1_schedule_proof.json`` (regenerated by
    tests/test_pipeline_schedules.py::TestZBH1ScheduleArtifact).

    RNG: one base key is drawn per call and folded with (stage, microbatch),
    so the backward recompute sees the forward's randomness by construction.
    """
    from ..core import random as _random

    jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
    S = jmesh.shape[axis]
    M = x_mb.shape[0]
    T = M + S - 1
    batch_spec = P()
    key_base = _random.next_key()

    def run_stage(params, x, stage_i, mb_i):
        k = jax.random.fold_in(jax.random.fold_in(key_base, stage_i), mb_i)
        with _random.provide_key(k):
            return stage_fn(params, x)

    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def _masked_row_write(buf, row_i, value, valid):
        """Write `value` into buf[row_i] only when valid (read-modify-write —
        keeps the scan carry at exactly M rows instead of stacking T ticks)."""
        old = jax.lax.dynamic_index_in_dim(buf, row_i, 0, keepdims=False)
        new = jnp.where(valid, value, old)
        return jax.lax.dynamic_update_slice_in_dim(buf, new[None], row_i, 0)

    def fwd_device(params_l, x):
        params = jax.tree_util.tree_map(lambda a: a[0], params_l)
        idx = jax.lax.axis_index(axis)

        def step(carry, t):
            state, y_buf, resid_buf = carry
            mb = jax.lax.dynamic_index_in_dim(x, jnp.clip(t, 0, M - 1), 0,
                                              keepdims=False)
            cur = jnp.where(idx == 0, mb, state)
            f = t - idx                       # this stage's microbatch number
            fc = jnp.clip(f, 0, M - 1)
            valid = (f >= 0) & (f < M)
            resid_buf = _masked_row_write(resid_buf, fc, cur, valid)
            out = run_stage(params, cur, idx, fc)
            yf = t - (S - 1)                  # last stage's microbatch number
            y_buf = _masked_row_write(y_buf, jnp.clip(yf, 0, M - 1), out,
                                      (yf >= 0) & (yf < M))
            return (jax.lax.ppermute(out, axis, fwd_perm), y_buf,
                    resid_buf), None

        zero_mb = jnp.zeros_like(x[0])
        (_, y_buf, resid), _ = jax.lax.scan(
            step, (zero_mb, jnp.zeros_like(x), jnp.zeros_like(x)),
            jnp.arange(T))
        y = jnp.where(idx == S - 1, y_buf, jnp.zeros_like(y_buf))
        return _psum(y, axis), resid[None]  # [1(pp), M, mb...]

    def bwd_device(params_l, resid_l, dy_mb):
        params = jax.tree_util.tree_map(lambda a: a[0], params_l)
        resid = resid_l[0]                        # [M, mb...]
        idx = jax.lax.axis_index(axis)
        U = M + S - 1

        def tick(carry, u):
            state, dw_acc, dx_buf, dy_buf = carry
            b = u - (S - 1 - idx)                 # this stage's microbatch
            bc = jnp.clip(b, 0, M - 1)
            valid = (b >= 0) & (b < M)
            dy_last = jax.lax.dynamic_index_in_dim(dy_mb, bc, 0,
                                                   keepdims=False)
            dy = jnp.where(idx == S - 1, dy_last, state)
            x_b = jax.lax.dynamic_index_in_dim(resid, bc, 0, keepdims=False)
            if zero_bubble:
                # dx-only chain: dw GEMMs are dead code here (W-split); dy is
                # buffered (microbatch-aligned) for the deferred W pass
                _, vjp_x = jax.vjp(
                    lambda xx: run_stage(params, xx, idx, bc), x_b)
                (dx,) = vjp_x(dy)
                dy_buf = _masked_row_write(dy_buf, bc, dy, valid)
            else:
                _, vjp_fn = jax.vjp(
                    lambda pp, xx: run_stage(pp, xx, idx, bc), params, x_b)
                dw, dx = vjp_fn(dy)
                dw_acc = jax.tree_util.tree_map(
                    lambda acc, g: acc + jnp.where(valid, g, 0), dw_acc, dw)
            dx = jnp.where(valid, dx, jnp.zeros_like(dx))
            dx_buf = _masked_row_write(dx_buf, bc, dx, valid)
            nxt = jax.lax.ppermute(dx, axis, bwd_perm)
            return (nxt, dw_acc, dx_buf, dy_buf), None

        dw0 = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), params)
        zero_buf = jnp.zeros((M,) + dy_mb.shape[1:], dy_mb.dtype)
        (_, dw_acc, dx_buf, dy_buf), _ = jax.lax.scan(
            tick, (jnp.zeros_like(dy_mb[0]), dw0, zero_buf,
                   zero_buf if zero_bubble else jnp.zeros((), dy_mb.dtype)),
            jnp.arange(U))

        if zero_bubble:
            # deferred W pass: per-stage, no ppermute — off the ring's
            # critical path (dy_buf is already microbatch-aligned)

            def w_tick(dw_acc, bm):
                x_b = jax.lax.dynamic_index_in_dim(resid, bm, 0,
                                                   keepdims=False)
                dy_b = jax.lax.dynamic_index_in_dim(dy_buf, bm, 0,
                                                    keepdims=False)
                _, vjp_p = jax.vjp(
                    lambda pp: run_stage(pp, x_b, idx, bm), params)
                (dw,) = vjp_p(dy_b)
                return jax.tree_util.tree_map(lambda a, g: a + g,
                                              dw_acc, dw), None

            dw_acc, _ = jax.lax.scan(w_tick, dw0, jnp.arange(M))

        dx_mb = jnp.where(idx == 0, dx_buf, jnp.zeros_like(dx_buf))
        dparams = jax.tree_util.tree_map(lambda a: a[None], dw_acc)
        return dparams, _psum(dx_mb, axis)

    spec_params = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    resid_spec = P(axis)

    fwd_sm = shard_map(fwd_device, mesh=jmesh,
                       in_specs=(spec_params, batch_spec),
                       out_specs=(batch_spec, resid_spec), axis_names={axis},
                       check_vma=False)
    bwd_sm = shard_map(bwd_device, mesh=jmesh,
                       in_specs=(spec_params, resid_spec, batch_spec),
                       out_specs=(spec_params, batch_spec), axis_names={axis},
                       check_vma=False)

    @jax.custom_vjp
    def pipe(params, x):
        y, _ = fwd_sm(params, x)
        return y

    def pipe_fwd(params, x):
        y, resid = fwd_sm(params, x)
        return y, (params, resid)

    def pipe_bwd(res, dy):
        params, resid = res
        dparams, dx = bwd_sm(params, resid, dy)
        return dparams, dx

    pipe.defvjp(pipe_fwd, pipe_bwd)
    return pipe(stacked_params, x_mb)


def interleaved_pipeline(stage_fn, stacked_params, x_mb, mesh, axis="pp",
                         num_chunks=2, remat=False):
    """Interleaved (VPP) schedule: each device owns `num_chunks` non-adjacent model
    chunks (reference: PipelineParallelWithInterleave, pipeline_parallel.py:1308).
    Param leaves are [S*num_chunks, ...] in ring order; the ring is traversed
    num_chunks times per microbatch."""
    jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
    S = jmesh.shape[axis]
    V = num_chunks
    M = x_mb.shape[0]
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    batch_spec = P()

    def per_device(params_l, x):
        # leaf [V, ...]: chunk v on this device is global stage (v*S + idx)
        idx = jax.lax.axis_index(axis)

        def run_ring(carry_x, v):
            # leaf local shape [V, 1(pp-local), L, ...]: pick chunk v, drop pp dim
            chunk_params = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False)[0],
                params_l)
            T = M + S - 1

            def step(state, t):
                mb = jax.lax.dynamic_index_in_dim(carry_x, jnp.clip(t, 0, M - 1), 0,
                                                  keepdims=False)
                cur = jnp.where(idx == 0, mb, state)
                out = fn(chunk_params, cur)
                perm = [(i, (i + 1) % S) for i in range(S)]
                return jax.lax.ppermute(out, axis, perm), out

            _, outs = jax.lax.scan(step, jnp.zeros_like(carry_x[0]), jnp.arange(T))
            y = outs[S - 1:]
            y = jnp.where(idx == S - 1, y, jnp.zeros_like(y))
            return _psum(y, axis), None

        y, _ = jax.lax.scan(run_ring, x, jnp.arange(V))
        return y

    spec_params = jax.tree_util.tree_map(lambda _: P(None, axis), stacked_params)

    # reshape leaves [S*V, ...] -> [V, S, ...] so chunk-major scan + pp shard works
    def reshape_leaf(a):
        return a.reshape((V, S) + a.shape[1:])

    stacked_vs = jax.tree_util.tree_map(reshape_leaf, stacked_params)
    return shard_map(per_device, mesh=jmesh,
                     in_specs=(spec_params, batch_spec),
                     out_specs=batch_spec, axis_names={axis},
                     check_vma=False)(stacked_vs, x_mb)


def scheduled_interleaved_pipeline(stage_fn, stacked_params, x_mb, mesh,
                                   axis="pp", num_chunks=2):
    """ZBVPP: zero-bubble x interleaved virtual chunks (reference:
    passes/pipeline_scheduler_pass/pipeline_zero_bubble.py composed with
    PipelineParallelWithInterleave).

    Composition of :func:`scheduled_pipeline`'s W-split with
    :func:`interleaved_pipeline`'s chunk loop:

    - **forward**: the ring is traversed ``num_chunks`` times (chunk v on
      device d = global stage v*S+d); each chunk pass stores only its M
      stage-boundary inputs — residency [V, M, microbatch] per device.
    - **backward**: chunks unwind in reverse; each reverse ring computes
      ONLY dx (the W-split — the serial cross-chunk/cross-stage chain holds
      just dx work) and buffers dy per (chunk, microbatch).
    - **deferred W pass**: all V*M dw contributions run afterwards with NO
      ppermute — off the ring's critical path, XLA-overlappable, exactly the
      zero-bubble trade paid with an extra forward recompute and the
      [V, M]-deep dy buffer.

    Params: leaves [S*num_chunks, ...] in ring order (chunk-major after the
    internal [V, S] reshape), sharded over `axis`. Differentiable like
    scheduled_pipeline (custom_vjp).
    """
    from ..core import random as _random

    jmesh = mesh.jax_mesh() if hasattr(mesh, "jax_mesh") else mesh
    S = jmesh.shape[axis]
    V = num_chunks
    M = x_mb.shape[0]
    T = M + S - 1
    batch_spec = P()
    key_base = _random.next_key()

    def run_stage(params, x, stage_i, mb_i):
        k = jax.random.fold_in(jax.random.fold_in(key_base, stage_i), mb_i)
        with _random.provide_key(k):
            return stage_fn(params, x)

    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def _masked_row_write(buf, row_i, value, valid):
        old = jax.lax.dynamic_index_in_dim(buf, row_i, 0, keepdims=False)
        new = jnp.where(valid, value, old)
        return jax.lax.dynamic_update_slice_in_dim(buf, new[None], row_i, 0)

    def _chunk(params_l, v):
        # local leaf [V, 1(pp), ...] -> chunk v's stage params [...]
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, v, 0,
                                                   keepdims=False)[0],
            params_l)

    def fwd_device(params_l, x):
        idx = jax.lax.axis_index(axis)

        def chunk_fwd(carry_x, v):
            params = _chunk(params_l, v)
            sid = v * S + idx

            def step(carry, t):
                state, y_buf, resid_buf = carry
                mb = jax.lax.dynamic_index_in_dim(
                    carry_x, jnp.clip(t, 0, M - 1), 0, keepdims=False)
                cur = jnp.where(idx == 0, mb, state)
                f = t - idx
                fc = jnp.clip(f, 0, M - 1)
                valid = (f >= 0) & (f < M)
                resid_buf = _masked_row_write(resid_buf, fc, cur, valid)
                out = run_stage(params, cur, sid, fc)
                yf = t - (S - 1)
                y_buf = _masked_row_write(y_buf, jnp.clip(yf, 0, M - 1), out,
                                          (yf >= 0) & (yf < M))
                return (jax.lax.ppermute(out, axis, fwd_perm), y_buf,
                        resid_buf), None

            (_, y_buf, resid_buf), _ = jax.lax.scan(
                step, (jnp.zeros_like(carry_x[0]), jnp.zeros_like(carry_x),
                       jnp.zeros_like(carry_x)), jnp.arange(T))
            y = jnp.where(idx == S - 1, y_buf, jnp.zeros_like(y_buf))
            return _psum(y, axis), resid_buf

        y, resid_v = jax.lax.scan(chunk_fwd, x, jnp.arange(V))
        return y, resid_v[None]                  # [1(pp), V, M, mb...]

    def bwd_device(params_l, resid_l, dy_mb):
        resid = resid_l[0]                       # [V, M, mb...]
        idx = jax.lax.axis_index(axis)
        U = M + S - 1

        def chunk_bwd(carry_dy, v):
            params = _chunk(params_l, v)
            sid = v * S + idx
            resid_c = jax.lax.dynamic_index_in_dim(resid, v, 0,
                                                   keepdims=False)

            def tick(carry, u):
                state, dx_buf, dy_buf = carry
                b = u - (S - 1 - idx)
                bc = jnp.clip(b, 0, M - 1)
                valid = (b >= 0) & (b < M)
                dy_last = jax.lax.dynamic_index_in_dim(carry_dy, bc, 0,
                                                       keepdims=False)
                dy = jnp.where(idx == S - 1, dy_last, state)
                x_b = jax.lax.dynamic_index_in_dim(resid_c, bc, 0,
                                                   keepdims=False)
                # dx-only chain (W-split): dw GEMMs are dead code here
                _, vjp_x = jax.vjp(
                    lambda xx: run_stage(params, xx, sid, bc), x_b)
                (dx,) = vjp_x(dy)
                dy_buf = _masked_row_write(dy_buf, bc, dy, valid)
                dx = jnp.where(valid, dx, jnp.zeros_like(dx))
                dx_buf = _masked_row_write(dx_buf, bc, dx, valid)
                return (jax.lax.ppermute(dx, axis, bwd_perm), dx_buf,
                        dy_buf), None

            zero_buf = jnp.zeros((M,) + dy_mb.shape[1:], dy_mb.dtype)
            (_, dx_buf, dy_buf), _ = jax.lax.scan(
                tick, (jnp.zeros_like(dy_mb[0]), zero_buf, zero_buf),
                jnp.arange(U))
            dx_mb = jnp.where(idx == 0, dx_buf, jnp.zeros_like(dx_buf))
            # stage-0 dx of chunk v is the upstream dy of chunk v-1
            return _psum(dx_mb, axis), dy_buf

        dx_final, dy_bufs_rev = jax.lax.scan(chunk_bwd, dy_mb,
                                             jnp.arange(V - 1, -1, -1))
        dy_bufs = jnp.flip(dy_bufs_rev, 0)       # chunk-major [V, M, mb...]

        # deferred W pass: V*M dw contributions, NO ppermute anywhere —
        # completely off the ring's serial chain
        def w_chunk(_, v):
            params = _chunk(params_l, v)
            sid = v * S + idx
            resid_c = jax.lax.dynamic_index_in_dim(resid, v, 0,
                                                   keepdims=False)
            dy_c = jax.lax.dynamic_index_in_dim(dy_bufs, v, 0,
                                                keepdims=False)

            def w_tick(dw_acc, bm):
                x_b = jax.lax.dynamic_index_in_dim(resid_c, bm, 0,
                                                   keepdims=False)
                dy_b = jax.lax.dynamic_index_in_dim(dy_c, bm, 0,
                                                    keepdims=False)
                _, vjp_p = jax.vjp(
                    lambda pp: run_stage(pp, x_b, sid, bm), params)
                (dw,) = vjp_p(dy_b)
                return jax.tree_util.tree_map(lambda a, g: a + g,
                                              dw_acc, dw), None

            dw0 = jax.tree_util.tree_map(jnp.zeros_like, params)
            dw_v, _ = jax.lax.scan(w_tick, dw0, jnp.arange(M))
            return None, dw_v

        _, dw_stacked = jax.lax.scan(w_chunk, None, jnp.arange(V))
        dparams = jax.tree_util.tree_map(lambda a: a[:, None], dw_stacked)
        return dparams, dx_final

    spec_params = jax.tree_util.tree_map(lambda _: P(None, axis),
                                         stacked_params)
    resid_spec = P(axis)

    fwd_sm = shard_map(fwd_device, mesh=jmesh,
                       in_specs=(spec_params, batch_spec),
                       out_specs=(batch_spec, resid_spec), axis_names={axis},
                       check_vma=False)
    bwd_sm = shard_map(bwd_device, mesh=jmesh,
                       in_specs=(spec_params, resid_spec, batch_spec),
                       out_specs=(spec_params, batch_spec), axis_names={axis},
                       check_vma=False)

    @jax.custom_vjp
    def pipe(params_vs, x):
        y, _ = fwd_sm(params_vs, x)
        return y

    def pipe_fwd(params_vs, x):
        y, resid = fwd_sm(params_vs, x)
        return y, (params_vs, resid)

    def pipe_bwd(res, dy):
        params_vs, resid = res
        dparams, dx = bwd_sm(params_vs, resid, dy)
        return dparams, dx

    pipe.defvjp(pipe_fwd, pipe_bwd)

    # [S*V, ...] ring order -> chunk-major [V, S, ...] (differentiable
    # reshape: grads flow back to the caller's stacked form)
    stacked_vs = jax.tree_util.tree_map(
        lambda a: a.reshape((V, S) + a.shape[1:]), stacked_params)
    return pipe(stacked_vs, x_mb)
