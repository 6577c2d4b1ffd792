"""Optimizer base + the standard family.

Reference: python/paddle/optimizer/{optimizer,adam,adamw,momentum,...}.py → phi fused
adam/momentum kernels. TPU-native design: each optimizer is a *pure update rule*
(`_apply`: (param, grad, slots, lr, step) -> (new_param, new_slots)); the whole
parameter set updates in ONE jitted, buffer-donated call (the analog of the
reference's multi_tensor fused_adam path), and the same pure rule is reused by the
jit train-step, ZeRO sharding, and the distributed shard_optimizer.

Master weights: like the reference's multi_precision mode, bf16/fp16 params keep an
fp32 master copy in the slot dict; updates happen in fp32 and cast down.
"""
from __future__ import annotations

import functools
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, no_grad
from ..nn.layer_base import Parameter
from .clip import ClipGradBase, ClipGradByGlobalNorm
from .lr import LRScheduler
from ..profiler import scope


def _is_low_precision(dtype):
    return dtype in (jnp.bfloat16, jnp.float16)


def stored_placements(values):
    """Per-array ``(mesh, spec)`` for ``apply_updates(fused_ctx=...)`` when
    the arrays are stored over a multi-device mesh, else None (every array
    on one device: the plain kernel call). The fused update is a Mosaic
    call, which GSPMD cannot partition — interpret mode on CPU hides that —
    so under a sharded layout it runs shard_map-wise on the stored shards;
    arrays not on the mesh ride replicated (an empty spec). ``values`` are
    the concrete (or abstract, sharding-carrying) stored arrays."""
    from jax.sharding import NamedSharding, PartitionSpec

    def mesh_of(v):
        sh = getattr(v, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
            return sh.mesh
        return None

    meshes = [mesh_of(v) for v in values]
    mesh = next((m for m in meshes if m is not None), None)
    if mesh is None:
        return None
    return tuple((mesh, v.sharding.spec if m == mesh else PartitionSpec())
                 for v, m in zip(values, meshes))


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        if parameters is not None:
            parameters = list(parameters)
        self._parameter_list = parameters or []
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        # paddle: float weight_decay == L2Decay coupled regularization;
        # regularizer objects carry _kind ("l1"/"l2", regularizer/__init__.py)
        self._wd_kind = "l2"
        if weight_decay is None:
            self._wd = 0.0
            self._decoupled_wd = False
        elif isinstance(weight_decay, (int, float)):
            self._wd = float(weight_decay)
            self._decoupled_wd = False
        else:  # L1Decay/L2Decay object
            self._wd = float(getattr(weight_decay, "_coeff", 0.0))
            self._wd_kind = getattr(weight_decay, "_kind", "l2")
            self._decoupled_wd = False
        self._slots: dict[int, dict] = {}
        self._step_count = 0
        self._jit_update = None
        self._jit_shape_key = None

    # -- subclass interface ---------------------------------------------------
    def _init_slots(self, p_val) -> dict:
        return {}

    def _apply(self, p, g, slots, lr, step) -> tuple:
        raise NotImplementedError

    def _decay_mask(self, param) -> bool:
        """Whether decoupled weight decay applies to this param (AdamW hook)."""
        return True

    # -- lr -------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate.get_lr())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    # -- pure tree update (shared by eager + jit paths) -----------------------
    def apply_updates(self, vals, grads, slots, lr, step, decay_flags,
                      fused_ctx=None):
        """Pure: lists of arrays -> (new_vals, new_slots). Used under jit.

        ``fused_ctx`` (optional, aligned with vals): per-param context for the
        fused kernel — None for the default whole-array path (a one-device
        program), or ``(mesh, spec)`` to run it shard_map-wise on sharded
        state: :func:`stored_placements` of the params (TrainStep, the
        pipeline step, ``step()``), overridden per param by the ZeRO
        wrapper's own plans."""
        if self._grad_clip is not None:
            with scope("pt.clip"):
                grads = self._grad_clip.apply(vals, grads)
        fused = getattr(self, "_apply_fused", None)
        fused_takes_pid = self.__dict__.get("_fused_takes_param_id")
        if fused is not None and fused_takes_pid is None:
            import inspect
            try:
                fused_takes_pid = "param_id" in inspect.signature(
                    fused).parameters
            except (TypeError, ValueError):
                fused_takes_pid = False
            self._fused_takes_param_id = fused_takes_pid
        new_vals, new_slots = [], []
        for i, (p, g, s, dm) in enumerate(zip(vals, grads, slots, decay_flags)):
            if g is None:
                new_vals.append(p)
                new_slots.append(s)
                continue
            if fused is not None:
                ctx = fused_ctx[i] if fused_ctx is not None else None
                kw = {"param_id": i} if fused_takes_pid else {}
                out = fused(p, g, s, lr, step, dm, shard_ctx=ctx, **kw)
                if out is not None:
                    new_vals.append(out[0])
                    new_slots.append(out[1])
                    continue
            master = s.get("master_weight")
            work_p = master if master is not None else p
            g32 = g.astype(work_p.dtype)
            if self._wd and not self._decoupled_wd:
                if self._wd_kind == "l1":
                    g32 = g32 + self._wd * jnp.sign(work_p)
                else:
                    g32 = g32 + self._wd * work_p
            np_, ns = self._apply(work_p, g32, s, lr, step)
            if self._decoupled_wd and self._wd and dm:
                np_ = np_ - lr * self._wd * work_p
            if master is not None:
                ns = dict(ns)
                ns["master_weight"] = np_
                new_vals.append(np_.astype(p.dtype))
            else:
                new_vals.append(np_)
            new_slots.append(ns)
        return new_vals, new_slots

    # -- eager step -----------------------------------------------------------
    def _ensure_slots(self, params):
        for p in params:
            if id(p) not in self._slots:
                v = p._value

                def build(v):
                    s = self._init_slots(
                        v.astype(jnp.float32)
                        if (self._multi_precision and
                            _is_low_precision(v.dtype)) else v)
                    if self._multi_precision and _is_low_precision(v.dtype):
                        s["master_weight"] = v.astype(jnp.float32)
                    return s

                if isinstance(v, jax.ShapeDtypeStruct):
                    # LazyGuard-abstract param: slots stay abstract too (the
                    # same _init_slots logic, evaluated shape-only) — enables
                    # AOT compile/memory planning of the full train step
                    # without materializing optimizer state. eval_shape drops
                    # shardings, so param-shaped slots re-attach the param's
                    # (matching eager, where zeros_like(v) inherits it)
                    slots = jax.eval_shape(build, v)
                    sh = getattr(v, "sharding", None)
                    if sh is not None:
                        slots = {
                            k: (jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                     sharding=sh)
                                if tuple(s.shape) == tuple(v.shape) else s)
                            for k, s in slots.items()}
                    self._slots[id(p)] = slots
                else:
                    self._slots[id(p)] = build(v)

    @no_grad()
    def step(self):
        params = [p for p in self._parameter_list
                  if not p.stop_gradient and p.grad is not None]
        if not params:
            self._step_count += 1
            if isinstance(self._learning_rate, LRScheduler):
                pass
            return
        self._ensure_slots(params)
        vals = [p._value for p in params]
        grads = [p.grad._value for p in params]
        slots = [self._slots[id(p)] for p in params]
        decay_flags = tuple(bool(self._decay_mask(p)) for p in params)
        self._step_count += 1
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        step = jnp.asarray(self._step_count, jnp.int32)

        from ..core.flags import flag_value
        # the fused-update flag is read at trace time — key the jit cache on
        # it so set_flags toggles take effect on the next step
        fused_ctx = stored_placements(vals)
        shape_key = tuple((v.shape, str(v.dtype)) for v in vals) + \
            (decay_flags, bool(flag_value("use_fused_adamw")),
             bool(flag_value("adamw_stochastic_rounding")), fused_ctx)
        if self._jit_update is None or self._jit_shape_key != shape_key:
            fn = functools.partial(self._traced_update, decay_flags=decay_flags,
                                   fused_ctx=fused_ctx)
            self._jit_update = jax.jit(fn, donate_argnums=(0, 2))
            self._jit_shape_key = shape_key
        new_vals, new_slots = self._jit_update(vals, grads, slots, lr, step)
        for p, nv, ns in zip(params, new_vals, new_slots):
            p._value = nv
            self._slots[id(p)] = ns

    def _traced_update(self, vals, grads, slots, lr, step, decay_flags,
                       fused_ctx=None):
        return self.apply_updates(vals, grads, slots, lr, step, decay_flags,
                                  fused_ctx=fused_ctx)

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.grad = None

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    # -- state dict -----------------------------------------------------------
    def state_dict(self):
        out = {"_step_count": self._step_count}
        name_map = self._param_names()
        for p in self._parameter_list:
            if id(p) in self._slots:
                pname = name_map[id(p)]
                for k, v in self._slots[id(p)].items():
                    out[f"{pname}.{k}"] = Tensor(v)
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        self._step_count = int(state.get("_step_count", 0))
        if "LR_Scheduler" in state and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
        names = self._param_names()
        for p in self._parameter_list:
            pname = names[id(p)]
            slot = {}
            for key, value in state.items():
                if isinstance(key, str) and key.startswith(pname + "."):
                    slot_name = key[len(pname) + 1:]
                    slot[slot_name] = value._value if isinstance(value, Tensor) \
                        else jnp.asarray(value)
            if slot:
                self._slots[id(p)] = slot

    def _param_names(self):
        return {id(p): (p.name or f"param_{i}")
                for i, p in enumerate(self._parameter_list)}


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _apply(self, p, g, slots, lr, step):
        return p - lr.astype(p.dtype) * g, slots


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_slots(self, v):
        return {"velocity": jnp.zeros_like(v)}

    def _apply(self, p, g, slots, lr, step):
        vel = self._momentum * slots["velocity"] + g
        if self._nesterov:
            update = g + self._momentum * vel
        else:
            update = vel
        return p - lr.astype(p.dtype) * update, {"velocity": vel}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, use_multi_tensor=False, amsgrad=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._amsgrad = amsgrad

    def _init_slots(self, v):
        from ..core.flags import flag_value
        mdt = jnp.bfloat16 if (flag_value("adamw_bf16_moments")
                               and v.dtype == jnp.float32) else v.dtype
        s = {"moment1": jnp.zeros(v.shape, mdt),
             "moment2": jnp.zeros(v.shape, mdt)}
        if self._amsgrad:
            s["moment2_max"] = jnp.zeros(v.shape, mdt)
        return s

    def _apply(self, p, g, slots, lr, step):
        b1, b2 = self._beta1, self._beta2
        mdt = slots["moment1"].dtype
        m1 = slots["moment1"].astype(p.dtype)  # fp32 math; bf16-storable
        m2 = slots["moment2"].astype(p.dtype)
        m = b1 * m1 + (1 - b1) * g
        v = b2 * m2 + (1 - b2) * jnp.square(g)
        stepf = step.astype(jnp.float32)
        bc1 = 1 - jnp.power(b1, stepf)
        bc2 = 1 - jnp.power(b2, stepf)
        ns = {"moment1": m.astype(mdt), "moment2": v.astype(mdt)}
        if self._amsgrad:
            vmax = jnp.maximum(slots["moment2_max"].astype(p.dtype), v)
            ns["moment2_max"] = vmax.astype(mdt)
            denom = jnp.sqrt(vmax / bc2) + self._eps
        else:
            denom = jnp.sqrt(v / bc2) + self._eps
        update = (m / bc1) / denom
        return p - lr.astype(p.dtype) * update, ns

    def _apply_fused(self, p, g, slots, lr, step, decay_mask, shard_ctx=None,
                     param_id=0):
        """Single-pass Pallas update for the multi-precision path (the
        reference's fused_adam/multi_tensor analog). Covers plain Adam with
        no coupled decay and AdamW's decoupled decay; anything else falls
        back to the generic chain. With ``shard_ctx=(mesh, spec)`` the kernel
        runs shard_map-wise on each device's local shard (ZeRO state)."""
        if self._amsgrad or (self._wd and not self._decoupled_wd):
            return None
        from ..core.flags import flag_value
        if not flag_value("use_fused_adamw"):
            return None
        kw = dict(beta1=self._beta1, beta2=self._beta2, eps=self._eps,
                  weight_decay=self._wd if self._decoupled_wd else 0.0,
                  apply_decay=bool(decay_mask))
        if slots.get("master_weight") is None:
            # master-weight-free path: bf16 params integrate updates via
            # in-kernel STOCHASTIC ROUNDING (flag-gated — different
            # trajectories than the fp32-master reference chain)
            if not flag_value("adamw_stochastic_rounding"):
                return None
            if p.dtype != jnp.bfloat16:
                return None
            # per-(step, param) rounding seed, derived in-graph — folding the
            # param index in decorrelates the rounding streams of same-shaped
            # parameters (step-only seeding repeats the identical per-position
            # stream across every layer)
            seed_f = jax.lax.bitcast_convert_type(
                ((step.astype(jnp.int32) + jnp.int32(int(param_id) * 2654435761
                                                    & 0x7FFFFFFF))
                 * jnp.int32(-1640531527)
                 ^ jnp.int32(0x5BD1E995)).reshape(1, 1), jnp.float32)
            if shard_ctx is not None:
                # ZeRO/TP-sharded state: shard_map the SR kernel over the
                # local shards — falling back to the generic chain here
                # would DETERMINISTICALLY round bf16 params and silently
                # stall training on small updates
                from ..ops.kernels.fused_adamw import (
                    fused_adamw_sr_update_sharded)
                mesh, spec = shard_ctx
                out = fused_adamw_sr_update_sharded(
                    mesh, spec, p, g, slots["moment1"], slots["moment2"],
                    lr, step, seed_f, **kw)
            else:
                from ..ops.kernels.fused_adamw import fused_adamw_sr_update
                out = fused_adamw_sr_update(p, g, slots["moment1"],
                                            slots["moment2"], lr, step,
                                            seed_f, **kw)
            if out is None:
                import warnings
                warnings.warn(
                    "adamw_stochastic_rounding: shape not tileable for the "
                    "SR kernel — falling back to DETERMINISTIC bf16 "
                    "rounding for this parameter (small updates may stall)",
                    RuntimeWarning, stacklevel=2)
                return None
            new_p, nm, nv = out
            return new_p, {"moment1": nm, "moment2": nv}
        if slots["moment1"].dtype != jnp.float32:
            return None  # the master-weight Pallas kernel assumes fp32 moments
        if shard_ctx is not None:
            from ..ops.kernels.fused_adamw import fused_adamw_update_sharded
            mesh, spec = shard_ctx
            out = fused_adamw_update_sharded(
                mesh, spec, p, g, slots["moment1"], slots["moment2"],
                slots["master_weight"], lr, step, **kw)
        else:
            from ..ops.kernels.fused_adamw import fused_adamw_update
            out = fused_adamw_update(
                p, g, slots["moment1"], slots["moment2"],
                slots["master_weight"], lr, step, **kw)
        if out is None:  # untileable shape — generic path
            return None
        new_p, nm, nv, nmw = out
        return new_p, {"moment1": nm, "moment2": nv, "master_weight": nmw}


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, amsgrad=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision,
                         amsgrad=amsgrad, name=name)
        self._wd = float(weight_decay) if not hasattr(weight_decay, "_coeff") \
            else float(weight_decay._coeff)
        self._decoupled_wd = True
        self._apply_decay_param_fun = apply_decay_param_fun

    def _decay_mask(self, param):
        if self._apply_decay_param_fun is not None:
            return bool(self._apply_decay_param_fun(param.name or ""))
        return True


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _init_slots(self, v):
        return {"moment": jnp.full_like(v, self._init_acc)}

    def _apply(self, p, g, slots, lr, step):
        acc = slots["moment"] + jnp.square(g)
        return p - lr.astype(p.dtype) * g / (jnp.sqrt(acc) + self._eps), {"moment": acc}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_slots(self, v):
        s = {"mean_square": jnp.zeros_like(v), "velocity": jnp.zeros_like(v)}
        if self._centered:
            s["mean_grad"] = jnp.zeros_like(v)
        return s

    def _apply(self, p, g, slots, lr, step):
        ms = self._rho * slots["mean_square"] + (1 - self._rho) * jnp.square(g)
        ns = {"mean_square": ms}
        if self._centered:
            mg = self._rho * slots["mean_grad"] + (1 - self._rho) * g
            ns["mean_grad"] = mg
            denom = jnp.sqrt(ms - jnp.square(mg) + self._eps)
        else:
            denom = jnp.sqrt(ms + self._eps)
        vel = self._momentum * slots["velocity"] + lr.astype(p.dtype) * g / denom
        ns["velocity"] = vel
        return p - vel, ns


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._rho, self._eps = rho, epsilon

    def _init_slots(self, v):
        return {"avg_squared_grad": jnp.zeros_like(v),
                "avg_squared_update": jnp.zeros_like(v)}

    def _apply(self, p, g, slots, lr, step):
        asg = self._rho * slots["avg_squared_grad"] + (1 - self._rho) * jnp.square(g)
        update = -jnp.sqrt(slots["avg_squared_update"] + self._eps) / \
            jnp.sqrt(asg + self._eps) * g
        asu = self._rho * slots["avg_squared_update"] + \
            (1 - self._rho) * jnp.square(update)
        return p + lr.astype(p.dtype) * update, \
            {"avg_squared_grad": asg, "avg_squared_update": asu}


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _init_slots(self, v):
        return {"moment": jnp.zeros_like(v), "inf_norm": jnp.zeros_like(v)}

    def _apply(self, p, g, slots, lr, step):
        m = self._beta1 * slots["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * slots["inf_norm"], jnp.abs(g))
        stepf = step.astype(jnp.float32)
        bc1 = 1 - jnp.power(self._beta1, stepf)
        return p - lr.astype(p.dtype) / bc1 * m / (u + self._eps), \
            {"moment": m, "inf_norm": u}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_slots(self, v):
        return {"moment1": jnp.zeros_like(v), "moment2": jnp.zeros_like(v)}

    def _apply(self, p, g, slots, lr, step):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment1"] + (1 - b1) * g
        v = b2 * slots["moment2"] + (1 - b2) * jnp.square(g)
        stepf = step.astype(jnp.float32)
        mh = m / (1 - jnp.power(b1, stepf))
        vh = v / (1 - jnp.power(b2, stepf))
        r = mh / (jnp.sqrt(vh) + self._eps) + self._lamb_wd * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p - lr.astype(p.dtype) * trust * r, {"moment1": m, "moment2": v}


# canonical definitions live in paddle_tpu.regularizer; re-exported here for the
# paddle.optimizer.L1Decay/L2Decay call sites
from ..regularizer import L1Decay, L2Decay  # noqa: E402,F401
