"""ZeRO-style sharding (reference: DygraphShardingOptimizer at
fleet/meta_optimizers/dygraph_optimizer/dygraph_sharding_optimizer.py:54 — ZeRO-1
param-group partitioning + post-update broadcast; stage2/3 in
fleet/meta_parallel/sharding/group_sharded_stage2.py / group_sharded_stage3.py,
fused flat storage in group_sharded_storage.py).

TPU-native: "sharding" is a placement policy enforced inside the compiled step,
not a host-side comm protocol. Per stage:

- **Stage 1 (os)**: every optimizer slot array (moments, master weights) lives
  Shard over the 'sharding' mesh axis — each device stores 1/N of all state.
  Grads are reduced full (all-reduce); the sharded update reads 1/N of them.
- **Stage 2 (os_g)**: additionally, gradients are constrained to the same
  sharded placement *before* the update — GSPMD turns the data-parallel grad
  reduction into a reduce-scatter into shards (the reference's overlapped
  reduce_scatter schedule), and with gradient accumulation the fp32
  accumulators persist sharded at 1/N (see TrainStep._call_accumulate).
- **Stage 3 (p_g_os)**: parameters are stored sharded too; XLA all-gathers
  each weight just before use in the forward/backward and the updated param is
  written back as shards (no step-wide full-param materialization).

Placement plan per param (``_plan_for``): the first dim divisible by the
sharding degree that no existing mesh axis (e.g. TP's 'mp') already occupies
becomes the sharding dim, preserving TP placements. Params with no such dim
are stored **flattened and zero-padded** to a multiple of N so their states
and grads still shard evenly (the analog of the reference's
group_sharded_storage fused slices) — nothing silently stays replicated; only
tensors smaller than the sharding degree fall back to replication.

New-param / slot outputs are re-constrained to their stored placements, so the
compiled HLO provably carries: sharded state inputs+outputs (1/N per-device
bytes), grad reduce-scatter for stage>=2, and no full-param state residency
for stage 3 — asserted by tests/test_hlo_contracts.py.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ...core.tensor import Tensor


class ShardPlan(NamedTuple):
    spec: object        # PartitionSpec for the (possibly flat) stored form
    flat: bool          # stored flattened+padded to pad_to
    pad_to: int         # padded flat length (0 when not flat)
    param_spec: object  # placement for the *param* output (stage3: sharded)


class AccPlacement(NamedTuple):
    """Storage contract for a persistent grad accumulator (stage>=2): where it
    lives and whether it is kept in the flat-padded stored form."""
    sharding: object    # NamedSharding
    flat: bool
    pad_to: int


def _existing_spec(value):
    sh = getattr(value, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.spec is not None:
        return tuple(sh.spec) + (None,) * (value.ndim - len(tuple(sh.spec)))
    return (None,) * getattr(value, "ndim", 0)


def _plan_for(mesh, axis, shape, existing=None):
    """Choose the sharded storage form for a tensor of `shape`.

    Returns a ShardPlan whose `spec` describes the stored slot/grad placement
    and `param_spec` the param's own stored placement (existing TP axes kept).
    """
    n = mesh.shape[axis]
    existing = tuple(existing) if existing is not None else (None,) * len(shape)
    size = int(np.prod(shape)) if shape else 1
    if any(axis == e or (isinstance(e, tuple) and axis in e)
           for e in existing):
        # param already stored sharded over `axis` (stage3): states mirror it
        return ShardPlan(PartitionSpec(*existing), False, 0,
                         PartitionSpec(*existing))
    for d, s in enumerate(shape):
        if existing[d] is None and s % n == 0 and s >= n:
            spec = list(existing)
            spec[d] = axis
            # slots/grads shard on dim d; the param itself returns to its own
            # stored placement (stage1/2: the post-update all-gather point)
            return ShardPlan(PartitionSpec(*spec), False, 0,
                             PartitionSpec(*existing))
    if size >= n:  # no divisible free dim: flat-pad storage
        pad_to = -(-size // n) * n
        return ShardPlan(PartitionSpec(axis), True, pad_to,
                         PartitionSpec(*existing))
    return ShardPlan(PartitionSpec(*((None,) * len(shape))), False, 0,
                     PartitionSpec(*existing))


def _to_stored(plan, mesh, v):
    """Eager transform of a slot array into its sharded stored form.
    Abstract (ShapeDtypeStruct) slots — from a LazyGuard model under AOT
    planning — get the same stored shape/placement without materializing."""
    if isinstance(v, jax.ShapeDtypeStruct):
        shape = (plan.pad_to,) if plan.flat else tuple(v.shape)
        sharding = (None if all(s is None for s in plan.spec)
                    else NamedSharding(mesh, plan.spec))
        return jax.ShapeDtypeStruct(shape, v.dtype, sharding=sharding)
    if plan.flat:
        flat = jnp.ravel(v)
        flat = jnp.pad(flat, (0, plan.pad_to - flat.shape[0]))
        return jax.device_put(flat, NamedSharding(mesh, plan.spec))
    if all(s is None for s in plan.spec):
        return v
    return jax.device_put(v, NamedSharding(mesh, plan.spec))


class DygraphShardingOptimizer:
    """ZeRO-1 wrapper: optimizer slot states live sharded; the update runs on
    shards inside the compiled step; updated params are re-gathered.

    stage=2 additionally reduce-scatters grads into the sharded update;
    stage=3 is composed by GroupShardedStage3 (params stored sharded)."""

    _IS_SHARDING_WRAPPER = True

    def __init__(self, optimizer, hcg=None, axis="sharding", stage=1):
        from . import fleet_state
        self._inner = optimizer
        self._hcg = hcg or fleet_state.hcg()
        self._axis = axis
        self._stage = stage
        self._plans = []      # positionally aligned with the last _ensure_slots
        self._plan_params = []
        # id-keyed view of the same plans: stable across later _ensure_slots
        # calls with a different param list (eager step() vs TrainStep mixes).
        # Values are (plan, weakref) — the entry self-deletes when the param
        # dies (the callback runs during deallocation, before the id can be
        # recycled), so the dict stays bounded and pins no dead arrays.
        self._plan_by_id: dict = {}
        # route every update entry point through the wrapper, so code holding
        # the inner optimizer (TrainStep built on it, Optimizer.step) still
        # gets the sharded update — the slots ARE stored in sharded form
        optimizer._ensure_slots = self._ensure_slots
        optimizer._traced_update = self._traced_update
        optimizer.apply_updates = self.apply_updates
        optimizer._jit_update = None

    # -- state placement ------------------------------------------------------
    def _mesh(self):
        return self._hcg.mesh.jax_mesh()

    def _ensure_slots(self, params):
        inner = self._inner
        type(inner)._ensure_slots(inner, params)
        mesh = self._mesh()
        if self._axis not in mesh.shape or mesh.shape[self._axis] <= 1:
            self._plans = [None] * len(params)
            self._plan_params = list(params)
            for p in params:
                self._remember_plan(p, None)
            return
        self._plans, self._plan_params = [], []
        for p in params:
            plan = _plan_for(mesh, self._axis, tuple(p.shape),
                             _existing_spec(p._value))
            self._plans.append(plan)
            self._plan_params.append(p)
            self._remember_plan(p, plan)
            slots = inner._slots[id(p)]
            for k, v in list(slots.items()):
                if not (isinstance(v, (jax.Array, jax.ShapeDtypeStruct))
                        and v.shape):
                    continue
                if plan.flat:
                    if v.shape != (plan.pad_to,):
                        slots[k] = _to_stored(plan, mesh, v)
                elif not self._is_stored(plan, v):
                    slots[k] = _to_stored(plan, mesh, v)

    def _remember_plan(self, p, plan):
        import weakref
        pid = id(p)
        table = self._plan_by_id
        table[pid] = (plan,
                      weakref.ref(p, lambda _r, pid=pid, table=table:
                                  table.pop(pid, None)))

    @staticmethod
    def _is_stored(plan, v):
        sh = getattr(v, "sharding", None)
        if not isinstance(sh, NamedSharding):
            return False
        have = tuple(sh.spec) + (None,) * (v.ndim - len(tuple(sh.spec)))
        want = tuple(plan.spec) + (None,) * (v.ndim - len(tuple(plan.spec)))
        return have == want

    def _plans_for(self, vals):
        # positional match must also agree on shapes — a same-length call
        # with different membership would otherwise pad/reshape wrongly
        if self._plans and len(vals) == len(self._plans) and \
                all(tuple(v.shape) == tuple(p.shape)
                    for v, p in zip(vals, self._plan_params)):
            return self._plans
        # fallback (apply_updates without a preceding ensure): derive from
        # shapes alone — correct unless a same-shaped param carries TP axes
        mesh = self._mesh()
        if self._axis not in mesh.shape or mesh.shape[self._axis] <= 1:
            return [None] * len(vals)
        return [_plan_for(mesh, self._axis, tuple(v.shape)) for v in vals]

    def _grad_placement(self, param):
        """AccPlacement for `param`'s persistent grad accumulator (stage>=2),
        or None (replicated, original shape). Used by TrainStep gradient
        accumulation. Keyed by the param object, not position — the plan list
        realigns on every _ensure_slots and positions need not match the
        caller's trainable-param ordering."""
        if self._stage < 2:
            return None
        entry = self._plan_by_id.get(id(param))
        if entry is None:
            return None
        plan = entry[0]
        if plan is None:
            return None
        if plan.flat:
            # flat-pad params accumulate in the flat stored form so the
            # accumulator still shards at 1/N (e.g. vocab-padded embeddings
            # under gradient accumulation)
            return AccPlacement(NamedSharding(self._mesh(), plan.spec),
                                True, plan.pad_to)
        if all(s is None for s in tuple(plan.spec)):
            return None
        return AccPlacement(NamedSharding(self._mesh(), plan.spec), False, 0)

    # -- the pure sharded update (runs under jit) -----------------------------
    def apply_updates(self, vals, grads, slots, lr, step, decay_flags,
                      fused_ctx=None):
        """``fused_ctx``: the caller's stored placements
        (``optimizer.stored_placements``) — used for params this wrapper
        does not re-plan; planned params run on their ZeRO shards."""
        inner = self._inner
        plans = self._plans_for(vals)
        mesh = self._mesh()
        if all(pl is None for pl in plans):
            return type(inner).apply_updates(inner, vals, grads, slots, lr,
                                             step, decay_flags,
                                             fused_ctx=fused_ctx)
        if inner._grad_clip is not None:
            grads = inner._grad_clip.apply(vals, grads)

        stored = fused_ctx if fused_ctx is not None else [None] * len(vals)
        t_vals, t_grads, fused_ctx = [], [], []
        for v, g, pl, st in zip(vals, grads, plans, stored):
            if pl is None or g is None:
                t_vals.append(v)
                t_grads.append(g)
                fused_ctx.append(st)
                continue
            if pl.flat:
                v = jnp.pad(jnp.ravel(v), (0, pl.pad_to - v.size))
                if g.ndim != 1 or g.shape != (pl.pad_to,):
                    # grads from an AccPlacement-aware accumulator arrive
                    # already in the flat stored form
                    g = jnp.pad(jnp.ravel(g), (0, pl.pad_to - g.size))
            if self._stage >= 2 and any(s is not None for s in tuple(pl.spec)):
                # ZeRO-2: reduce the dp-partial grad directly into shards
                g = jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, pl.spec))
            t_vals.append(v)
            t_grads.append(g)
            # fused Pallas update runs shard_map-wise on the stored shards —
            # GSPMD can't partition a pallas_call, so we partition for it
            fused_ctx.append((mesh, pl.spec)
                             if any(s is not None for s in tuple(pl.spec))
                             else st)

        # inner update on the stored (sharded/flat) forms; clip already done
        saved_clip = inner._grad_clip
        inner._grad_clip = None
        try:
            new_vals, new_slots = type(inner).apply_updates(
                inner, t_vals, t_grads, slots, lr, step, decay_flags,
                fused_ctx=fused_ctx)
        finally:
            inner._grad_clip = saved_clip

        out_vals, out_slots = [], []
        for v0, nv, ns, pl in zip(vals, new_vals, new_slots, plans):
            if pl is None:
                out_vals.append(nv)
                out_slots.append(ns)
                continue
            if pl.flat:
                nv = jnp.reshape(nv[:v0.size], v0.shape)
            # param goes back to its stored placement (stage1/2: original —
            # the all-gather point; stage3: sharded, no gather emitted)
            nv = jax.lax.with_sharding_constraint(
                nv, NamedSharding(mesh, pl.param_spec))
            ns = {k: (jax.lax.with_sharding_constraint(
                          s, NamedSharding(mesh, pl.spec))
                      if isinstance(s, jax.Array) and s.shape else s)
                  for k, s in ns.items()}
            out_vals.append(nv)
            out_slots.append(ns)
        return out_vals, out_slots

    def _traced_update(self, vals, grads, slots, lr, step, decay_flags,
                       fused_ctx=None):
        return self.apply_updates(vals, grads, slots, lr, step, decay_flags,
                                  fused_ctx=fused_ctx)

    # -- checkpoint portability ----------------------------------------------
    def state_dict(self):
        """Slots in portable form: flat-pad storage restored to the param's
        original shape so checkpoints load under any sharding degree."""
        out = self._inner.state_dict()
        names = self._inner._param_names()
        for p, plan in zip(self._plan_params, self._plans):
            if plan is None or not plan.flat:
                continue
            pname = names.get(id(p))
            if pname is None:
                continue
            size = int(np.prod(p.shape)) if tuple(p.shape) else 1
            for key in list(out):
                if isinstance(key, str) and key.startswith(pname + "."):
                    v = out[key]
                    arr = v._value if isinstance(v, Tensor) else jnp.asarray(v)
                    if arr.ndim == 1 and arr.shape == (plan.pad_to,):
                        out[key] = Tensor(jnp.reshape(arr[:size],
                                                      tuple(p.shape)))
        return out

    def set_state_dict(self, state):
        self._inner.set_state_dict(state)
        # re-establish the stored (sharded / flat-padded) forms under the
        # CURRENT mesh, whatever form the checkpoint carried
        mesh = self._mesh()
        for p, plan in zip(self._plan_params, self._plans):
            if plan is None:
                continue
            slots = self._inner._slots.get(id(p))
            if not slots:
                continue
            for k, v in list(slots.items()):
                if not (isinstance(v, jax.Array) and v.shape):
                    continue
                if plan.flat:
                    if v.shape != (plan.pad_to,):
                        slots[k] = _to_stored(plan, mesh, v)
                elif not self._is_stored(plan, v):
                    slots[k] = _to_stored(plan, mesh, v)

    # -- delegation -----------------------------------------------------------
    @property
    def _step_count(self):
        return self._inner._step_count

    @_step_count.setter
    def _step_count(self, v):
        # augmented assignment through the wrapper (TrainStep does
        # `opt._step_count += 1`) must reach the inner optimizer — a plain
        # attribute would shadow it and checkpoints would save step 0,
        # corrupting AdamW bias correction on resume
        self._inner._step_count = v

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self):
        return self._inner.step()

    def clear_grad(self, *a, **k):
        return self._inner.clear_grad(*a, **k)

    @property
    def _parameter_list(self):
        return self._inner._parameter_list


class GroupShardedStage2(DygraphShardingOptimizer):
    """ZeRO-2 (reference: group_sharded_stage2.py GroupShardedStage2 —
    grad segmentation + reduce_scatter into the owning rank): grads are
    constrained to the sharded state placement inside the compiled step, so
    the dp reduction lands as reduce-scatter and persistent accumulation
    buffers (gradient merge) hold only 1/N per device."""

    def __init__(self, optimizer, hcg=None, axis="sharding"):
        super().__init__(optimizer, hcg=hcg, axis=axis, stage=2)


class GroupShardedStage3:
    """ZeRO-3 (reference: group_sharded_stage3.py — segmented param storage,
    gather-on-use, release-after-use): params are *stored* sharded over the
    sharding axis; XLA inserts the per-use all-gather in forward/backward and
    the update writes shards back (param_spec keeps the sharded placement)."""

    def __init__(self, model, optimizer=None, hcg=None, axis="sharding",
                 segment_size=2 ** 20):
        from . import fleet_state
        self._hcg = hcg or fleet_state.hcg()
        mesh = self._hcg.mesh.jax_mesh()
        n = mesh.shape[axis] if axis in mesh.shape else 1
        for name, p in model.named_parameters():
            if p.stop_gradient or n <= 1:
                continue
            plan = _plan_for(mesh, axis, tuple(p.shape),
                             _existing_spec(p._value))
            if plan.flat or all(s is None for s in tuple(plan.spec)):
                # params cannot be stored flat (forward needs the true shape);
                # loud fallback instead of a silent memory-budget surprise
                warnings.warn(
                    f"GroupShardedStage3: param {name!r} shape {tuple(p.shape)}"
                    f" has no dim divisible by sharding degree {n}; it stays "
                    f"replicated (its optimizer states still shard flat)",
                    RuntimeWarning, stacklevel=2)
                continue
            p._value = jax.device_put(
                p._value, NamedSharding(mesh, plan.spec))
        self._model = model
        self._optimizer = (DygraphShardingOptimizer(optimizer, self._hcg,
                                                    axis, stage=3)
                           if optimizer is not None else None)

    def __call__(self, *a, **k):
        return self._model(*a, **k)

    def __getattr__(self, name):
        return getattr(self._model, name)
