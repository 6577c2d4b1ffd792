"""paddle.jit.to_static analog + compiled train step.

Reference: python/paddle/jit/api.py:197 (to_static), jit/sot (bytecode capture),
pir_partial_program (graph into executor). TPU-native: `to_static` wraps a function or
Layer so calls trace once through jax.jit (XLA is the executor; the jaxpr is the IR);
parameters/buffers enter as jit inputs so weight updates don't recompile, and buffer
mutations (BN running stats) round-trip as outputs. `TrainStep` fuses
forward+backward+optimizer into ONE compiled program with buffer donation — the analog
of the reference's Plan/Job executor running a whole iteration.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, functional_mode, no_grad
from ..core import random as _random
from ..nn.layer_base import Layer
from ..optimizer.optimizer import stored_placements
from ..profiler import build, scope, span
from .functional_call import collect_state, bind_state, read_values


def _find_layers(fn, args):
    """Discover Layer instances a callable touches: self, args, and closure cells
    (the analog of SOT guarding on the frame's free variables)."""
    layers = []

    def add(obj):
        if isinstance(obj, Layer) and all(obj is not l for l in layers):
            layers.append(obj)
        elif isinstance(obj, (list, tuple)):
            for item in obj:
                add(item)

    def scan_callable(f):
        add(f)
        if hasattr(f, "__self__"):
            add(f.__self__)
        for cell in getattr(f, "__closure__", None) or ():
            try:
                add(cell.cell_contents)
            except ValueError:
                continue
        for d in getattr(f, "__defaults__", None) or ():
            add(d)
        for d in (getattr(f, "__kwdefaults__", None) or {}).values():
            add(d)

    scan_callable(fn)
    if isinstance(fn, functools.partial):
        add(list(fn.args))
        add(list(fn.keywords.values()))
        scan_callable(fn.func)
    for a in jax.tree_util.tree_leaves(args, is_leaf=lambda x: isinstance(x, Layer)):
        add(a)
    return layers


def _split_leaves(tree):
    """Split pytree into (dynamic tensor/array leaves, static structure key)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, Tensor))
    dyn, static_key, layout = [], [], []
    for leaf in leaves:
        if isinstance(leaf, Tensor):
            dyn.append(leaf._value)
            layout.append("T")
        elif isinstance(leaf, (jax.Array, np.ndarray)):
            dyn.append(jnp.asarray(leaf))
            layout.append("A")
        else:
            static_key.append(leaf)
            layout.append("S")
    return dyn, tuple(static_key), tuple(layout), treedef


_EAGER_FALLBACK = object()  # cache sentinel: this signature runs eagerly


class _PrefixEntry:
    """Cache entry: compiled-prefix capture after a whole-array graph break
    (see jit/prefix_capture.py)."""

    __slots__ = ("program",)

    def __init__(self, program):
        self.program = program


class _Specializer:
    """Per-signature state after a data-dependent graph break (reference:
    jit/sot opcode_executor.py:353 — SOT keeps the compiled prefix and guards
    on the concretized values; torch.compile splits frames the same way).

    TPU-native version: *speculative specialization with post-validation*.
    On a break, the call runs eagerly once while every concretized scalar
    (bool(t)/int(t)/t.item()) is recorded — that's the branch profile. A
    program specialized to the profile is then compiled, with the concretized
    scalars as extra outputs (the guards). Later calls run the compiled
    program and compare the guard outputs to the profile: match -> compiled
    result stands (the hot branch never leaves XLA); mismatch -> results are
    discarded, the call re-runs eagerly, and the new profile gets its own
    compiled program. Safe because traced programs are pure: buffer updates
    are applied only after validation.
    """

    def __init__(self):
        self.programs = {}     # profile tuple -> jitted specialized program
        self.last_profile = None
        self.failed = False    # a specialized trace also broke -> plain eager


class StaticFunction:
    """Traced+compiled callable with a guard cache keyed on static structure."""

    def __init__(self, function, input_spec=None, full_graph=True, backend=None):
        self._fn = function
        self._cache = {}
        functools.update_wrapper(self, function,
                                 assigned=("__name__", "__doc__", "__qualname__"),
                                 updated=())

    @property
    def function(self):
        return self._fn

    def __get__(self, instance, owner):
        if instance is None:
            return self
        return functools.partial(self.__call__, instance)

    def _make_body(self, static_key, layout, treedef, params, buffers):
        fn = self._fn
        state_tensors = params + buffers

        def compiled(state_vals, dyn_vals, rng_key):
            # rebuild args with traced leaves
            it = iter(dyn_vals)
            statics = iter(static_key)
            leaves = []
            for tag in layout:
                if tag == "S":
                    leaves.append(next(statics))
                elif tag == "T":
                    leaves.append(Tensor(next(it)))
                else:
                    leaves.append(next(it))
            a, k = jax.tree_util.tree_unflatten(treedef, leaves)
            with functional_mode(), bind_state(state_tensors, state_vals), \
                    _random.provide_key(rng_key):
                out = fn(*a, **k)
                new_buf_vals = [b._value for b in buffers]
            out_vals = jax.tree_util.tree_map(
                lambda t: t._value if isinstance(t, Tensor) else t, out,
                is_leaf=lambda x: isinstance(x, Tensor))
            return out_vals, new_buf_vals

        return compiled

    #: max distinct branch profiles compiled per signature before giving up
    #: (the torch.compile recompile_limit analog)
    _MAX_PROFILES = 8

    #: canonical stand-in for NaN guard values in profile keys: NaN never
    #: compares (or, per-instance, hashes) equal to itself, so raw-NaN tuples
    #: would miss both _profiles_match and the programs-dict lookups,
    #: recompiling an identical program per call until the cap. The RAW
    #: recorded values (real NaNs) still feed the specialized trace.
    _NAN = object()

    @classmethod
    def _canon_profile(cls, values):
        return tuple(cls._NAN if isinstance(v, float) and v != v else v
                     for v in values)

    @staticmethod
    def _profiles_match(observed, profile):
        # EXACT equality, floats included: a spurious mismatch merely costs an
        # eager re-profile, but any tolerance can validate a guard that sits
        # across a python comparison threshold and commit the wrong branch.
        # (Both sides are canonical profiles — NaN already collapsed to _NAN.)
        return len(observed) == len(profile) and \
            all(o == p for o, p in zip(observed, profile))

    def _call_specialized(self, spec, body, args, kwargs, state_vals, dyn,
                          buffers):
        from ..core.tensor import ConcretizeScope, concretize_scope
        # try the last profile's program; on guard divergence, the observed
        # guards name the true profile — if it's already compiled, run it and
        # validate ITS guards (alternating-branch workloads stay compiled)
        candidate = spec.last_profile
        tried = set()
        while not spec.failed and candidate is not None \
                and candidate not in tried:
            tried.add(candidate)
            prog = spec.programs.get(candidate)
            if prog is None:
                break
            try:
                out_vals, new_buf_vals, guards = prog(
                    state_vals, dyn, _random.next_key())
                observed = self._canon_profile(
                    np.asarray(g).item() for g in guards)
            except (jax.errors.ConcretizationTypeError,
                    jax.errors.TracerIntegerConversionError,
                    jax.errors.TracerArrayConversionError,
                    jax.errors.NonConcreteBooleanIndexError,
                    IndexError):
                # the specialized trace itself broke (.numpy() on a tracer,
                # profile under-recorded, ...) — plain eager from now on
                spec.failed = True
                return self._fn(*args, **kwargs)
            if self._profiles_match(observed, candidate):
                spec.last_profile = candidate
                for b, nv in zip(buffers, new_buf_vals):
                    b._value = nv
                return jax.tree_util.tree_map(
                    lambda v: Tensor(v) if isinstance(v, jax.Array)
                    else v, out_vals)
            # speculative results discarded (pure program — nothing was
            # committed); the observed prefix points at the true profile
            candidate = observed if observed in spec.programs else None
        if spec.failed:
            return self._fn(*args, **kwargs)

        # eager profiling run: record every concretized scalar
        scope = ConcretizeScope()
        with concretize_scope(scope):
            result = self._fn(*args, **kwargs)
        profile_raw = tuple(scope.recorded)
        profile = self._canon_profile(profile_raw)
        spec.last_profile = profile
        if profile not in spec.programs:
            if len(spec.programs) >= self._MAX_PROFILES:
                import warnings
                warnings.warn(
                    f"to_static: {getattr(self._fn, '__name__', '?')} exceeded "
                    f"{self._MAX_PROFILES} branch profiles (data-dependent "
                    f"value with many distinct outcomes); running eagerly",
                    RuntimeWarning, stacklevel=2)
                spec.failed = True
                return result
            profile_list = list(profile_raw)

            def specialized(state_vals, dyn_vals, rng_key):
                sc = ConcretizeScope(feed=profile_list)
                with concretize_scope(sc):
                    out_vals, new_bufs = body(state_vals, dyn_vals, rng_key)
                return out_vals, new_bufs, tuple(sc.guards)

            spec.programs[profile] = jax.jit(specialized)
        return result

    def __call__(self, *args, **kwargs):
        layers = _find_layers(self._fn, args)
        pnames, params, bnames, buffers = collect_state(layers)
        dyn, static_key, layout, treedef = _split_leaves((args, kwargs))
        # the autocast policy is part of the program identity: a body (or
        # captured prefix) traced under one policy bakes its casts in and
        # must not serve calls under another
        from ..amp import policy_fingerprint
        key = (static_key, layout, treedef, tuple(id(p) for p in params),
               policy_fingerprint())

        entry = self._cache.get(key)
        if entry is None:
            entry = self._cache[key] = jax.jit(
                self._make_body(static_key, layout, treedef, params, buffers))

        if entry is _EAGER_FALLBACK:
            return self._fn(*args, **kwargs)

        state_vals = read_values(params) + read_values(buffers)
        if isinstance(entry, _Specializer):
            body = self._make_body(static_key, layout, treedef, params,
                                   buffers)
            return self._call_specialized(entry, body, args, kwargs,
                                          state_vals, dyn, buffers)

        if isinstance(entry, _PrefixEntry):
            from .prefix_capture import _ReplayAbandoned
            from ..core.tensor import is_grad_enabled
            grads_will_record = is_grad_enabled() and (
                any(not p.stop_gradient for p in params)
                or any(isinstance(a, Tensor) and not a.stop_gradient
                       for a in jax.tree_util.tree_leaves(
                           (args, kwargs),
                           is_leaf=lambda x: isinstance(x, Tensor))))
            # grads will record but the prefix compiled no vjp (captured
            # under no-grad): run plain eager WITHOUT executing the compiled
            # prefix and WITHOUT counting a divergence (train/eval
            # alternation must not demote the eval-path capture). A
            # grad-capable prefix replays with a tape node instead.
            if grads_will_record and not entry.program.grad_capable:
                return self._fn(*args, **kwargs)
            # input tensors aligned with state_vals + dyn (None for raw
            # arrays) — the training prefix's tape parents; only grad-capable
            # programs consume them, so eval prefixes skip the tree walk
            input_tensors = None
            if entry.program.grad_capable:
                input_tensors = list(params) + list(buffers) + [
                    leaf if isinstance(leaf, Tensor) else None
                    for leaf in jax.tree_util.tree_leaves(
                        (args, kwargs),
                        is_leaf=lambda x: isinstance(x, Tensor))
                    if isinstance(leaf, (Tensor, jax.Array, np.ndarray))]
            try:
                result, diverged = entry.program.run(
                    list(state_vals) + list(dyn),
                    lambda: self._fn(*args, **kwargs),
                    input_tensors=input_tensors)
            except _ReplayAbandoned:
                # the prefix program itself failed to trace/run — raised
                # BEFORE any user code, so a plain eager call is safe
                self._cache[key] = _EAGER_FALLBACK
                return self._fn(*args, **kwargs)
            if diverged:
                # result is still correct (replayed values are provenance-
                # verified; the diverged tail ran eagerly) — but repeated
                # divergence means the prefix isn't stable for this fn
                entry.program.failures += 1
                if entry.program.failures >= 2:
                    self._cache[key] = _EAGER_FALLBACK
            return result

        rng_key = _random.next_key()
        try:
            out_vals, new_buf_vals = entry(state_vals, dyn, rng_key)
        except (jax.errors.TracerArrayConversionError,
                jax.errors.NonConcreteBooleanIndexError) as e:
            # whole-array concretization (.numpy() on a tracer, boolean mask
            # indexing): no scalar profile can fix this wholesale — but the
            # ops BEFORE the break are compilable. SOT-style prefix capture:
            # one eager recording run; when a clean prefix exists (no RNG /
            # grads / AMP in it), later calls run it as ONE compiled program
            # and resume eager at the break (reference:
            # jit/sot/opcode_translator/executor/opcode_executor.py:353).
            import warnings
            from ..core import tensor as _tensor_mod
            from .prefix_capture import PrefixRecorder
            recorder = PrefixRecorder(list(state_vals) + list(dyn))
            saved_rec = _tensor_mod._capture.recorder
            _tensor_mod._capture.recorder = recorder
            try:
                result = self._fn(*args, **kwargs)
            finally:
                _tensor_mod._capture.recorder = saved_rec
            program = recorder.build()
            if program is not None:
                warnings.warn(
                    f"to_static: graph break in "
                    f"{getattr(self._fn, '__name__', '?')} "
                    f"({type(e).__name__}); compiled a "
                    f"{len(program.records)}-op prefix, eager after the "
                    f"break", RuntimeWarning, stacklevel=2)
                self._cache[key] = _PrefixEntry(program)
            else:
                warnings.warn(
                    f"to_static: graph break in "
                    f"{getattr(self._fn, '__name__', '?')} "
                    f"({type(e).__name__}; "
                    f"{recorder.aborted or 'no capturable prefix'}); this "
                    f"call signature now runs eagerly",
                    RuntimeWarning, stacklevel=2)
                self._cache[key] = _EAGER_FALLBACK
            return result
        except (jax.errors.ConcretizationTypeError,
                jax.errors.TracerIntegerConversionError) as e:
            # NOTE: in this jax version only TracerBoolConversionError is a
            # ConcretizationTypeError subclass — integer conversion must be
            # listed separately.
            # Data-dependent SCALAR control flow: specialize per branch
            # profile instead of abandoning compilation (reference: jit/sot
            # guards on the concretized value, opcode_executor.py:353).
            import warnings
            warnings.warn(
                f"to_static: data-dependent control flow in "
                f"{getattr(self._fn, '__name__', '?')} ({type(e).__name__}); "
                f"specializing per branch profile with guard validation",
                RuntimeWarning, stacklevel=2)
            spec = self._cache[key] = _Specializer()
            body = self._make_body(static_key, layout, treedef, params,
                                   buffers)
            return self._call_specialized(spec, body, args, kwargs,
                                          state_vals, dyn, buffers)
        for b, nv in zip(buffers, new_buf_vals):
            b._value = nv
        return jax.tree_util.tree_map(
            lambda v: Tensor(v) if isinstance(v, jax.Array) else v, out_vals)

    def concrete_program_specify_input_spec(self, *a, **k):  # parity shim
        return None


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              full_graph=True, **kwargs):
    """paddle.jit.to_static — decorator or call-form."""
    def deco(fn):
        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward.__func__.__get__(fn, type(fn))
                                        if hasattr(fn.forward, "__func__") else fn.forward,
                                        input_spec)
            return fn
        return StaticFunction(fn, input_spec)

    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    return fn



def _make_loss_of(model, loss_fn, params, frozen, buffers, static_key, layout,
                  treedef):
    """Build the pure loss closure shared by the single-step and
    gradient-accumulation paths: re-interleaves dynamic/static batch leaves,
    binds param/buffer values, and captures updated buffers as aux."""

    def loss_of(pv, frozen_vals, buf_vals, rng_key, dyn_vals):
        it = iter(dyn_vals)
        statics = iter(static_key)
        leaves = []
        for tag in layout:
            if tag == "S":
                leaves.append(next(statics))
            elif tag == "T":
                leaves.append(Tensor(next(it)))
            else:
                leaves.append(next(it))
        (b,) = (jax.tree_util.tree_unflatten(treedef, leaves),)
        with functional_mode(), \
                bind_state(params + frozen + buffers,
                           list(pv) + list(frozen_vals) + list(buf_vals)), \
                _random.provide_key(rng_key):
            loss = loss_fn(model, *b)
            new_bufs = [bf._value for bf in buffers]
        return loss._value, new_bufs

    return loss_of


class TrainStep:
    """One fused compiled training iteration: fwd + bwd + optimizer + buffer updates.

    loss_fn: (model, *batch) -> scalar loss Tensor (pure w.r.t. our op library).
    Donation: parameter/slot buffers are donated so param memory is updated in place
    (no 2x weight footprint) — the analog of the reference executor's inplace pass.
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, donate=True,
                 accumulate_steps=1):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._cache = {}
        pnames, params, bnames, buffers = collect_state(model)
        self.params = [p for p in params if not p.stop_gradient]
        self.frozen = [p for p in params if p.stop_gradient]
        self.buffers = buffers
        self.donate = donate
        # gradient accumulation (reference: gradient_merge pass /
        # fleet accumulate_steps): K-1 grad-only microsteps into fp32
        # accumulators, optimizer-state traffic only on the K-th
        self.accumulate_steps = int(accumulate_steps)
        self._acc = None
        self._acc_placements = None
        self._acc_count = 0
        self._grad_cache = {}
        self._update_fn = None
        optimizer._ensure_slots(self.params)

    def __call__(self, *batch):
        """One optimizer step, in a profile ``pt:train.step`` (``step``:
        the optimizer's step count) over its host phases: ``prepare``
        (batch leaves, state values, key, lr), ``build`` (a new batch
        signature only: the program is made and, in the ``dispatch``
        inside it, traced and compiled), ``dispatch`` (the jitted call)
        and ``commit`` (the new values written back). All host work: the
        device is waited for by whoever reads the loss."""
        with span("pt:train.step", step=self.optimizer._step_count + 1):
            if self.accumulate_steps > 1:
                return self._call_accumulate(*batch)
            return self._call_fused(*batch)

    def _call_fused(self, *batch):
        opt = self.optimizer
        with span("pt:train.prepare"):
            dyn, static_key, layout, treedef = _split_leaves(batch)
            param_vals = read_values(self.params)
            fused_ctx = stored_placements(param_vals)
            key = self._step_key(dyn, static_key, layout, treedef, fused_ctx)
            slot_vals = [opt._slots[id(p)] for p in self.params]
            buf_vals = read_values(self.buffers)
            frozen_vals = read_values(self.frozen)
            opt._step_count += 1
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_i = jnp.asarray(opt._step_count, jnp.int32)
            rng_key = _random.next_key()

        fn = self._cache.get(key)
        with build("pt:train.build", "train_step") if fn is None \
                else contextlib.nullcontext():
            if fn is None:
                fn = self._cache[key] = self._build_step_jit(
                    static_key, layout, treedef, fused_ctx)
            with span("pt:train.dispatch"):
                out = fn(param_vals, slot_vals, buf_vals, frozen_vals, lr,
                         step_i, rng_key, dyn)
        loss_val, new_pv, new_slots, new_bufs = out
        with span("pt:train.commit"):
            for p, nv in zip(self.params, new_pv):
                p._value = nv
            for p, ns in zip(self.params, new_slots):
                opt._slots[id(p)] = ns
            for b, nv in zip(self.buffers, new_bufs):
                b._value = nv
        return Tensor(loss_val)

    def _step_key(self, dyn, static_key, layout, treedef, fused_ctx):
        """Cache key of the single-step program. ``fused_ctx`` is the
        params' ``stored_placements``: the fused optimizer kernel is built
        for them, so a re-placed model gets a new program."""
        from ..core.flags import flag_value
        return (static_key, layout, treedef,
                tuple((tuple(v.shape), str(v.dtype)) for v in dyn),
                bool(flag_value("use_fused_adamw")),
                bool(flag_value("adamw_stochastic_rounding")), fused_ctx)

    def _build_step_jit(self, static_key, layout, treedef, fused_ctx=None):
        """The fused fwd+bwd+update program for one batch signature."""
        opt = self.optimizer
        decay_flags = tuple(bool(opt._decay_mask(p)) for p in self.params)
        loss_of_full = _make_loss_of(self.model, self.loss_fn, self.params,
                                     self.frozen, self.buffers, static_key,
                                     layout, treedef)

        def step_fn(param_vals, slot_vals, buf_vals, frozen_vals, lr, step_i,
                    rng_key, dyn_vals):
            def loss_of(pv):
                return loss_of_full(pv, frozen_vals, buf_vals, rng_key,
                                    dyn_vals)

            (loss_val, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals)
            with scope("pt.optimizer"):
                new_pv, new_slots = opt.apply_updates(
                    param_vals, grads, slot_vals, lr, step_i, decay_flags,
                    fused_ctx=fused_ctx)
            return loss_val, new_pv, new_slots, new_bufs

        donate = (0, 1, 2) if self.donate else ()
        return jax.jit(step_fn, donate_argnums=donate)

    def aot_compile(self, *batch):
        """AOT-compile the train step program(s) WITHOUT executing them.

        Works on a LazyGuard-abstract model: parameter, slot, and batch
        leaves may be ``jax.ShapeDtypeStruct``s (with shardings attached), so
        a model too large to materialize on one host can still be compiled,
        partitioned, and memory-checked on a virtual mesh.

        Returns the jax ``Compiled`` object (``memory_analysis()``,
        ``as_text()``) for the fused single-step program; with
        ``accumulate_steps > 1`` returns ``(microstep, update)`` Compileds —
        the microstep's arguments include the persistent fp32 accumulators
        and the update's include the optimizer slots, so a memory verdict
        must consider both. Reference analog: the static executor's
        build-program + memory planning pass, run compile-only."""
        import jax.tree_util as jtu
        opt = self.optimizer
        # the documented contract admits bare ShapeDtypeStruct batch leaves;
        # _split_leaves would classify those as static — wrap them as Tensors
        batch = jtu.tree_map(
            lambda x: Tensor(x) if isinstance(x, jax.ShapeDtypeStruct) else x,
            batch, is_leaf=lambda x: isinstance(x, (Tensor,
                                                    jax.ShapeDtypeStruct)))
        dyn, static_key, layout, treedef = _split_leaves(batch)
        param_vals = read_values(self.params)
        buf_vals = read_values(self.buffers)
        frozen_vals = read_values(self.frozen)
        rng_key = jax.eval_shape(lambda: jax.random.key(0))

        if self.accumulate_steps > 1:
            placements = tuple(self._acc_shardings())
            acc_avals = self._acc_avals(placements)
            grad_jit = self._build_grad_jit(static_key, layout, treedef,
                                            placements)
            grad_compiled = grad_jit.lower(param_vals, acc_avals, buf_vals,
                                           frozen_vals, rng_key,
                                           dyn).compile()
            slot_vals = [opt._slots[id(p)] for p in self.params]
            update_jit = self._build_update_jit(placements)
            update_compiled = update_jit.lower(
                param_vals, slot_vals, acc_avals,
                jnp.asarray(0.0, jnp.float32),
                jnp.asarray(1, jnp.int32)).compile()
            return grad_compiled, update_compiled

        # share the jit with __call__'s cache: a later real step with the
        # same signature reuses this trace instead of recompiling
        fused_ctx = stored_placements(param_vals)
        key = self._step_key(dyn, static_key, layout, treedef, fused_ctx)
        if key not in self._cache:
            self._cache[key] = self._build_step_jit(static_key, layout,
                                                    treedef, fused_ctx)
        jitted = self._cache[key]
        slot_vals = [opt._slots[id(p)] for p in self.params]
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        step_i = jnp.asarray(1, jnp.int32)
        return jitted.lower(param_vals, slot_vals, buf_vals, frozen_vals,
                            lr, step_i, rng_key, dyn).compile()

    def _build_grad_jit(self, static_key, layout, treedef, placements):
        """The accumulation MICROSTEP program: fwd+bwd, grads added into the
        persistent fp32 accumulators (ZeRO-2: constrained into 1/N shards,
        reduce-scattering the dp reduction straight into the shard)."""
        loss_of_full = _make_loss_of(self.model, self.loss_fn, self.params,
                                     self.frozen, self.buffers, static_key,
                                     layout, treedef)
        acc_shardings = placements

        def grad_fn(param_vals, acc_vals, buf_vals, frozen_vals, rng_key,
                    dyn_vals):
            def loss_of(pv):
                return loss_of_full(pv, frozen_vals, buf_vals, rng_key,
                                    dyn_vals)

            (loss_val, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals)
            new_acc = []
            for a, g, sh in zip(acc_vals, grads, acc_shardings):
                g = g.astype(jnp.float32)
                if sh is not None:
                    if sh.flat:
                        # flat-pad storage: accumulate in the 1-D padded
                        # stored form so the buffer shards at 1/N
                        g = jnp.pad(jnp.ravel(g), (0, sh.pad_to - g.size))
                    g = jax.lax.with_sharding_constraint(g, sh.sharding)
                new_acc.append(a + g)
            return loss_val, new_acc, new_bufs

        # acc buffers are internal (never user-visible) — always donated
        return jax.jit(grad_fn, donate_argnums=(1,))

    def _build_update_jit(self, placements):
        """The accumulation-boundary UPDATE program: optimizer step on the
        accumulated mean gradient."""
        opt = self.optimizer
        decay_flags = tuple(bool(opt._decay_mask(p)) for p in self.params)
        K = self.accumulate_steps
        shapes = tuple(tuple(p.shape) for p in self.params)
        fused_ctx = stored_placements(read_values(self.params))

        def update_fn(param_vals, slot_vals, acc_vals, lr, step_i):
            # keep the fp32 mean — both the generic multi-precision path
            # and the fused kernel upcast anyway, so downcasting here
            # would only discard the accumulated precision. Flat-stored
            # accumulators are restored to the param's shape first:
            # apply_updates resolves its own plans and must never be
            # handed grads in a storage form those plans didn't choose.
            grads = []
            for a, sh, shp in zip(acc_vals, placements, shapes):
                if sh is not None and sh.flat:
                    size = 1
                    for s in shp:
                        size *= s
                    a = jnp.reshape(a[:size], shp)
                grads.append(a / K)
            with scope("pt.optimizer"):
                return opt.apply_updates(param_vals, grads, slot_vals, lr,
                                         step_i, decay_flags,
                                         fused_ctx=fused_ctx)

        donate = (0, 1, 2) if self.donate else (2,)
        return jax.jit(update_fn, donate_argnums=donate)

    def _acc_avals(self, placements):
        """Abstract accumulator buffers matching ``placements``."""
        out = []
        for p, sh in zip(self.params, placements):
            if sh is None:
                out.append(jax.ShapeDtypeStruct(tuple(p.shape), jnp.float32))
            else:
                shape = (sh.pad_to,) if sh.flat else tuple(p.shape)
                out.append(jax.ShapeDtypeStruct(shape, jnp.float32,
                                                sharding=sh.sharding))
        return out

    def _acc_shardings(self):
        """Per-param placement for grad accumulators: the ZeRO-2+ wrapper's
        AccPlacement when present (keyed by the param object), else the
        PARAM's own sharding — under TP, a grad has the param's placement,
        and a replicated fp32 accumulator would cost full bytes per device
        (27 GB at 7B scale). None = keep replicated."""
        from jax.sharding import NamedSharding
        from ..distributed.fleet.sharding_optimizer import AccPlacement
        placement = getattr(self.optimizer, "_grad_placement", None)
        out = []
        for p in self.params:
            sh = placement(p) if placement is not None else None
            if sh is None:
                psh = getattr(p._value, "sharding", None)
                if isinstance(psh, NamedSharding) and psh.spec is not None \
                        and any(s is not None for s in tuple(psh.spec)):
                    sh = AccPlacement(psh, False, 0)
            out.append(sh)
        return out

    # -- gradient-accumulation path ------------------------------------------
    def _call_accumulate(self, *batch):
        opt = self.optimizer
        dyn, static_key, layout, treedef = _split_leaves(batch)

        # accumulator placements are resolved ONCE per accumulation cycle and
        # frozen; the grad/update programs are keyed on them, so a sharding-
        # plan change between cycles recompiles instead of reusing a closure
        # baked for the old placements against new-shape accumulators
        if self._acc is None:
            self._acc_placements = tuple(self._acc_shardings())
            self._acc = []
            for p, sh in zip(self.params, self._acc_placements):
                if sh is None:
                    self._acc.append(jnp.zeros(p.shape, jnp.float32))
                else:
                    shape = (sh.pad_to,) if sh.flat else tuple(p.shape)
                    self._acc.append(jax.device_put(
                        jnp.zeros(shape, jnp.float32), sh.sharding))
        placements = self._acc_placements
        key = (static_key, layout, treedef,
               tuple((tuple(v.shape), str(v.dtype)) for v in dyn), placements)

        if key not in self._grad_cache:
            self._grad_cache[key] = self._build_grad_jit(
                static_key, layout, treedef, placements)

        from ..core.flags import flag_value
        update_key = (bool(flag_value("use_fused_adamw")),
                      bool(flag_value("adamw_stochastic_rounding")),
                      placements)
        if self._update_fn is None or getattr(self, "_update_key", None) \
                != update_key:
            self._update_key = update_key
            self._update_fn = self._build_update_jit(placements)

        param_vals = read_values(self.params)
        buf_vals = read_values(self.buffers)
        frozen_vals = read_values(self.frozen)
        rng_key = _random.next_key()
        with span("pt:train.dispatch"):
            loss_val, self._acc, new_bufs = self._grad_cache[key](
                param_vals, self._acc, buf_vals, frozen_vals, rng_key, dyn)
        for b, nv in zip(self.buffers, new_bufs):
            b._value = nv
        self._acc_count += 1
        if self._acc_count >= self.accumulate_steps:
            slot_vals = [opt._slots[id(p)] for p in self.params]
            opt._step_count += 1
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_i = jnp.asarray(opt._step_count, jnp.int32)
            with span("pt:train.dispatch"):
                new_pv, new_slots = self._update_fn(
                    param_vals, slot_vals, self._acc, lr, step_i)
            for p, nv in zip(self.params, new_pv):
                p._value = nv
            for p, ns in zip(self.params, new_slots):
                opt._slots[id(p)] = ns
            self._acc = None
            self._acc_count = 0
        return Tensor(loss_val)


def save(layer, path, input_spec=None, **config):
    """paddle.jit.save analog: params + a serialized AOT-lowered program.

    The reference serializes a ProgramDesc+params (jit/api.py save). We save the
    state_dict plus an input spec; `jit.load` rebuilds a callable by re-jitting.
    For true AOT deployment see static.InputSpec + Predictor (inference module).
    """
    import pickle
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    from ..framework_io import _pack
    state = {"state_dict": _pack(dict(layer.state_dict())),
             "class_name": type(layer).__name__,
             "input_spec": input_spec}
    with open(path + ".pdparams", "wb") as f:
        pickle.dump(state, f)


def load(path, **config):
    import pickle
    from ..framework_io import _unpack
    with open(path + ".pdparams", "rb") as f:
        state = pickle.load(f)
    return _unpack(state["state_dict"])


def ignore_module(modules):
    return None


class ProgramTranslator:  # parity shim
    _instance = None

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, flag):
        pass


def enable_to_static(flag=True):
    pass


class TranslatedLayer:
    """Loaded-program layer (reference: jit/translated_layer.py
    TranslatedLayer — what jit.load returns in the reference). Our jit.load
    returns the callable program directly; this wrapper restores the layer
    interface (program(), train/eval flags) for API parity."""

    def __init__(self, fn, input_spec=None):
        self._fn = fn
        self._input_spec = input_spec
        self._is_test = True

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)

    forward = __call__

    def train(self):
        self._is_test = False
        return self

    def eval(self):
        self._is_test = True
        return self

    def program(self, method_name="forward"):
        return getattr(self._fn, "jaxpr", None)


_LOG_VERBOSITY = 0
_CODE_LEVEL = -1


def set_verbosity(level=0, also_to_stdout=False):
    """reference: jit/dy2static/logging_utils.py set_verbosity — transform
    logging verbosity."""
    global _LOG_VERBOSITY
    _LOG_VERBOSITY = int(level)


def set_code_level(level=100, also_to_stdout=False):
    """reference: jit/dy2static/logging_utils.py set_code_level — which
    transformed-code stage to log."""
    global _CODE_LEVEL
    _CODE_LEVEL = int(level)
