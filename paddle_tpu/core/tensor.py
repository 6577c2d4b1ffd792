"""Eager Tensor + tape autograd.

Reference analog: the dygraph stack — AutogradMeta (paddle/fluid/eager/autograd_meta.h:61),
GradNodeBase (grad_node_info.h:197), TensorWrapper (tensor_wrapper.h:39), and the
generated per-op ad_func (eager_gen.py:372) that records grad nodes at forward time.

TPU-native design: every eager op goes through :func:`dispatch`. Forward compute is a
pure jax function; when gradients are required we call ``jax.vjp`` at forward time, so
the returned closure *is* the grad node — it owns the residuals (the TensorWrapper
analog) and jax derives the backward rule (no hand-written GradNode per op). The tape is
the DAG of ``Node`` objects linked through their input tensors; ``.backward()`` executes
it in reverse topological order (autograd/backward.py).

Inside ``jit``-traced (functional) code the same ops run tape-free on tracers, so one op
library serves both the eager and the compiled path — the analog of the reference's
single YAML op set feeding both eager and PIR engines.
"""
from __future__ import annotations

import functools
import threading
import weakref
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes
from .device import Place, get_place
from .flags import flag_value


# ---------------------------------------------------------------------------
# grad / functional mode state
# ---------------------------------------------------------------------------

class _ModeState(threading.local):
    def __init__(self):
        self.grad_enabled = True
        self.functional = 0  # >0 while tracing inside jit (tape disabled)


_mode = _ModeState()


def is_grad_enabled() -> bool:
    return _mode.grad_enabled and _mode.functional == 0


def set_grad_enabled(value: bool):
    _mode.grad_enabled = bool(value)


class _GradModeCtx:
    def __init__(self, target: bool):
        self._target = target

    def __enter__(self):
        self._saved = _mode.grad_enabled
        _mode.grad_enabled = self._target
        return self

    def __exit__(self, *exc):
        _mode.grad_enabled = self._saved
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with type(self)(self._target) if False else _GradModeCtx(self._target):
                return fn(*args, **kwargs)

        return wrapper


def no_grad():
    return _GradModeCtx(False)


def enable_grad():
    return _GradModeCtx(True)


class functional_mode:
    """Disables the tape while a jax transform traces through our ops."""

    def __enter__(self):
        _mode.functional += 1
        return self

    def __exit__(self, *exc):
        _mode.functional -= 1
        return False


def in_functional_mode() -> bool:
    return _mode.functional > 0


# ---------------------------------------------------------------------------
# tape node
# ---------------------------------------------------------------------------

class Node:
    """One recorded op. ``vjp_fn`` maps output cotangents -> input cotangents."""

    __slots__ = (
        "vjp_fn", "parents", "out_treedef", "out_avals", "outputs", "name", "fwd_fn",
        "__weakref__",
    )

    def __init__(self, vjp_fn, parents, out_treedef, out_avals, name, fwd_fn=None):
        self.vjp_fn = vjp_fn
        self.parents = parents          # list[Tensor] — differentiable inputs, vjp order
        self.out_treedef = out_treedef  # treedef of the op's full output pytree
        self.out_avals = out_avals      # ShapeDtypeStruct per output leaf
        self.outputs = []               # list[weakref to output Tensors | None] per leaf
        self.name = name
        # pure fn of the diff input *values* — used by create_graph (double grad) to
        # re-derive a vjp whose inputs are live tape tensors rather than baked residuals
        self.fwd_fn = fwd_fn

    def __repr__(self):
        return f"<Node {self.name} n_in={len(self.parents)} n_out={len(self.out_avals)}>"


# ---------------------------------------------------------------------------
# Tensor
# ---------------------------------------------------------------------------

def _is_tensor(x):
    return isinstance(x, Tensor)


# -- scalar-concretization interception (to_static graph-break machinery) ----
# When a traced program hits bool(t)/int(t)/t.item() on a tracer, jax raises a
# concretization error. to_static installs a scope here instead: in RECORD
# mode (eager profiling run) every concretized scalar is logged; in FEED mode
# (specialized re-trace) the logged profile is fed back as static values while
# the traced scalars are collected as guard outputs. See jit/api.py.

class _ConcretizeState(threading.local):
    """Per-thread (like _mode): a scope installed by thread A must not see
    scalars concretized by other threads (data loaders, metric threads)."""
    scope = None


_concretize_state = _ConcretizeState()


class ConcretizeScope:
    __slots__ = ("feed", "i", "recorded", "guards")

    def __init__(self, feed=None):
        self.feed = feed          # None = record mode; list = feed mode
        self.i = 0
        self.recorded = []
        self.guards = []

    def intercept(self, value, concrete=False):
        if self.feed is None:     # eager profiling: value is concrete
            v = value.item() if hasattr(value, "item") else value
            self.recorded.append(v)
            return v
        self.i += 1               # consume the slot either way: feed order
        if concrete:              # must mirror record order exactly
            # a concrete (non-traced) scalar inside the specialized trace:
            # its real value is authoritative and becomes a baked guard
            # constant — if it ever differs from the profile, validation
            # falls back to eager
            v = value.item() if hasattr(value, "item") else value
            self.guards.append(v)
            return v
        self.guards.append(value)
        return self.feed[self.i - 1]


class _ConcretizeCtx:
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        self._saved = _concretize_state.scope
        _concretize_state.scope = self.scope
        return self.scope

    def __exit__(self, *exc):
        _concretize_state.scope = self._saved
        return False


def concretize_scope(scope):
    return _ConcretizeCtx(scope)


def _intercept_scalar(value):
    """Route a would-be concretization through the active scope, if any."""
    scope = _concretize_state.scope
    if scope is None:
        return None
    if scope.feed is None:
        return scope.intercept(value)
    if isinstance(value, jax.core.Tracer):
        return scope.intercept(value)
    # feed mode, concrete value (e.g. a closed-over eager tensor): record
    # mode logged it, so feed alignment must consume its slot too
    return scope.intercept(value, concrete=True)


class Tensor:
    """Eager tensor facade over ``jax.Array``.

    Reference analog: paddle::Tensor (paddle/phi/api/include/tensor.h:82) +
    AutogradMeta. ``stop_gradient`` defaults True like paddle's non-parameter tensors.
    """

    __slots__ = (
        "_value", "stop_gradient", "grad", "name", "_node", "_out_index",
        "_retain_grads", "_hooks", "persistable", "is_leaf_override", "__weakref__",
        "_dist_meta", "_feed_name",
    )

    def __init__(self, value, stop_gradient: bool = True, name: str | None = None):
        if isinstance(value, Tensor):
            value = value._value
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad = None
        self.name = name
        self._node = None
        self._out_index = 0
        self._retain_grads = False
        self._hooks = []
        self.persistable = False
        self.is_leaf_override = None
        self._dist_meta = None  # set by paddle_tpu.distributed for DistTensor semantics

    # -- basic properties ---------------------------------------------------
    @property
    def value(self):
        return self._value

    @property
    def shape(self) -> list:
        return list(self._value.shape)

    @property
    def ndim(self) -> int:
        return self._value.ndim

    ndimension = ndim

    @property
    def dtype(self):
        return np.dtype(self._value.dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self) -> Place:
        try:
            dev = next(iter(self._value.devices()))
            return Place(dev.platform, dev.id)
        except Exception:
            return get_place()

    @property
    def is_leaf(self) -> bool:
        if self.is_leaf_override is not None:
            return self.is_leaf_override
        return self.stop_gradient or self._node is None

    def numel(self) -> int:
        return self.size

    def element_size(self) -> int:
        return self.dtype.itemsize

    # -- conversion ---------------------------------------------------------
    def numpy(self) -> np.ndarray:
        if _capture.recorder is not None:
            # whole-array host read: the prefix-capture break point
            _capture.recorder.on_host_read(self._value)
        return np.asarray(self._value)

    def item(self):
        v = _intercept_scalar(self._value)
        return v if v is not None else self._value.item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        v = _intercept_scalar(self._value)
        return float(v) if v is not None else float(self._value.item())

    def __int__(self):
        v = _intercept_scalar(self._value)
        return int(v) if v is not None else int(self._value.item())

    def __index__(self):
        v = _intercept_scalar(self._value)
        return int(v) if v is not None else self._value.__index__()

    def __bool__(self):
        v = _intercept_scalar(self._value)
        return bool(v) if v is not None else bool(self._value)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph: bool = False):
        from ..autograd.backward import run_backward
        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def retain_grads(self):
        self._retain_grads = True

    def register_hook(self, hook: Callable):
        self._hooks.append(hook)

        class _Removable:
            def remove(_self):
                if hook in self._hooks:
                    self._hooks.remove(hook)

        return _Removable()

    def clear_grad(self):
        self.grad = None

    clear_gradient = clear_grad

    def detach(self) -> "Tensor":
        t = Tensor(self._value, stop_gradient=True, name=self.name)
        t._dist_meta = self._dist_meta
        return t

    def clone(self) -> "Tensor":
        from .. import ops
        return ops.assign(self)

    @property
    def gradient(self):
        return None if self.grad is None else self.grad.numpy()

    # -- in-place value rebinding (optimizer updates, __setitem__) ----------
    def _replace_value(self, new_value):
        self._value = new_value
        return self

    def copy_(self, other, blocking: bool = True):
        src = other._value if isinstance(other, Tensor) else jnp.asarray(other)
        self._value = jnp.asarray(src, dtype=self._value.dtype)
        return self

    def set_value(self, other):
        return self.copy_(other)

    # -- repr ---------------------------------------------------------------
    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        try:
            data = np.array2string(self.numpy(), precision=6, threshold=64)
        except Exception:
            data = "<traced>"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"place={self.place}{grad_info},\n       {data})")

    # arithmetic/method surface is attached in paddle_tpu/__init__.py via
    # _bind_tensor_methods() once the ops library is importable (avoids an
    # import cycle ops -> tensor -> ops).


# Register Tensor as a pytree node so jax transforms can carry it transparently
# (values only; autograd metadata does not survive a tree round-trip on purpose).
jax.tree_util.register_pytree_node(
    Tensor,
    lambda t: ((t._value,), (t.stop_gradient, t.name)),
    lambda aux, children: Tensor(children[0], stop_gradient=aux[0], name=aux[1]),
)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


# observers called as (op_name, out_leaves) after every dispatch — used by
# amp.debugging operator-stats collection; empty in the hot path
_OP_OBSERVERS: list = []


def _check_numerics(name, leaves):
    level = flag_value("check_nan_inf_level")
    for v in leaves:
        if isinstance(v, jax.Array) and jnp.issubdtype(v.dtype, jnp.inexact):
            bad = bool(jnp.any(~jnp.isfinite(v)))
            if bad:
                msg = f"[check_nan_inf] op {name!r} produced nan/inf in output {v.shape} {v.dtype}"
                if level >= 1:
                    import logging
                    logging.getLogger("paddle_tpu").warning(msg)
                else:
                    raise FloatingPointError(msg)


_amp_cast_fn = None


def _maybe_amp_cast(name, vals):
    """AMP autocast hook — the injection point the reference generates into every
    ad_func (eager_gen.py AMP logic). Lazily bound to avoid an import cycle."""
    global _amp_cast_fn
    if _amp_cast_fn is None:
        return vals
    return _amp_cast_fn(name, vals)


def install_amp_hook(fn):
    global _amp_cast_fn
    _amp_cast_fn = fn


# -- compiled eager dispatch -------------------------------------------------
# The reference spends 4.2k lines of codegen making per-op eager dispatch
# allocation-free (fluid/eager/auto_code_generator/generator/eager_gen.py:372).
# Here the analog is a compile cache: for REGISTERED ops (stable fn identity),
# the forward—and, when recording, the jax.vjp pair—is jitted once per
# (op, structure, static args, shapes/dtypes, diff-mask) and reused, so an
# eager op call is one compiled-executable invocation instead of an un-jitted
# trace + fresh vjp construction. Ad-hoc closures (functional wrappers) keep
# the direct path; ops observed drawing RNG during trace are blacklisted so
# their randomness never bakes into a cached executable.

_DISPATCH_CACHE: dict = {}   # insertion-ordered; maintained as LRU
_UNCACHEABLE_OPS: set = set()
_CACHE_BYPASS = object()
_BWD_JIT = None
_DISPATCH_CACHE_MAX = 4096
#: observability for the eager hot path (reference: the codegen'd dispatch
#: counters); read via dispatch_cache_stats(), reset on clear
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0, "bypasses": 0,
                "evict_streak": 0}


def dispatch_cache_stats() -> dict:
    """Hit/miss/eviction/bypass counters plus current size of the compiled
    eager-dispatch cache."""
    return dict(_CACHE_STATS, size=len(_DISPATCH_CACHE),
                max_size=_DISPATCH_CACHE_MAX)


# -- compiled-prefix capture hooks (jit/prefix_capture.py) -------------------
class _CaptureState(threading.local):
    """Thread-local recorder/replay hooks — like _mode, so a concurrent
    thread dispatching during record/replay can neither interleave its ops
    into the captured prefix nor race the replay cursor."""

    def __init__(self):
        #: when set, every dispatch on THIS thread is logged with argument
        #: provenance (record mode)
        self.recorder = None
        #: when set, prefix-position dispatches on THIS thread are answered
        #: from a compiled prefix
        self.replay = None


_capture = _CaptureState()
#: sentinel: the replay state declined this op (past the prefix) — dispatch
#: proceeds normally
_REPLAY_PASS = object()


class _Unfreezable(Exception):
    pass


def _freeze(v, depth=0):
    """Hashable, value-stable token for an op callable: its code object plus
    recursively frozen closure cells/defaults. Only immutable primitives are
    admitted as cell values — anything stateful (arrays, Tensors, lists,
    layers) raises, which routes that call to the uncached path."""
    if depth > 3:
        raise _Unfreezable
    if v is None:
        return v
    if isinstance(v, (int, float, bool, str, bytes)):
        # type-tag scalars: 1, 1.0 and True hash/compare equal but trace to
        # different programs
        return (type(v), v)
    if isinstance(v, type):
        return ("T", v)
    if isinstance(v, np.dtype):
        return ("D", str(v))
    if isinstance(v, (tuple, list)):
        # lists freeze by VALUE — the key reflects call-time contents, so a
        # mutated list simply maps to a different cache entry
        return ("t",) + tuple(_freeze(e, depth + 1) for e in v)
    if isinstance(v, dict):
        return ("d",) + tuple((k, _freeze(e, depth + 1))
                              for k, e in sorted(v.items(), key=repr))
    if callable(v):
        code = getattr(v, "__code__", None)
        if code is not None:
            # A bound method's __code__/__closure__ belong to the underlying
            # function; two methods of different instances would collide. The
            # instance itself is almost always stateful, so freeze it too —
            # stateful selves raise and route to the uncached path.
            slf = getattr(v, "__self__", None)
            frozen_self = _freeze(slf, depth + 1) if slf is not None else None
            cells = getattr(v, "__closure__", None) or ()
            frozen = tuple(_freeze(c.cell_contents, depth + 1) for c in cells)
            defaults = tuple(_freeze(d, depth + 1)
                             for d in (getattr(v, "__defaults__", None) or ()))
            return ("F", code, frozen_self, frozen, defaults)
        mod = getattr(v, "__module__", None) or \
            getattr(type(v), "__module__", "")
        if str(mod).startswith(("jax", "numpy")):
            # module-level jax/numpy callables (incl. ufunc objects): key by
            # (module, qualname) — stable for the process lifetime — but only
            # after confirming the name genuinely resolves back to v, so
            # dynamically created instances (np.vectorize etc.) can't alias
            # a module attr or leak via pinned id()s
            name = getattr(v, "__qualname__", None) or \
                getattr(v, "__name__", None)
            if name is not None:
                import sys
                target = sys.modules.get(str(mod))
                for part in str(name).split("."):
                    target = getattr(target, part, None)
                    if target is None:
                        break
                if target is v:
                    return ("G", str(mod), str(name))
    raise _Unfreezable


def clear_dispatch_cache():
    _DISPATCH_CACHE.clear()
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


# flag flips invalidate cached executables (op bodies read flags at trace
# time); clearing beats epoch-keying, which would orphan entries at the cap
from .flags import register_flags_hook as _register_flags_hook  # noqa: E402
_register_flags_hook(clear_dispatch_cache)


def _bwd_call(vjp_obj, ct):
    """Apply a cached VJP closure under jit (float0 cotangents go eagerly —
    they don't cross the jit boundary)."""
    global _BWD_JIT
    for leaf in jax.tree_util.tree_leaves(ct):
        if isinstance(leaf, np.ndarray) and leaf.dtype == jax.dtypes.float0:
            return vjp_obj(ct)
    if _BWD_JIT is None:
        _BWD_JIT = jax.jit(lambda v, c: v(c))
    return _BWD_JIT(vjp_obj, ct)


def _rng_counters():
    from . import random as _random
    prov = _random._key_providers
    # _draw_epoch counts draws from EVERY Generator (default + tracker
    # streams), so a first trace that consumes randomness through any of
    # them gets blacklisted, not just draws through default_generator
    return (_random._draw_epoch,
            prov[-1].counter if prov else -1)


def _dispatch_cached(fn, name, cache_key, leaves, treedef, record):
    """Compiled-path dispatch. Returns _CACHE_BYPASS when this call can't be
    cached (unhashable static leaf / RNG draw detected on first trace)."""
    layout, dyn_vals, statics, diff_idx, diff_tensors = [], [], [], [], []
    for leaf in leaves:
        if isinstance(leaf, Tensor):
            layout.append("D")
            if record and not leaf.stop_gradient:
                diff_idx.append(len(dyn_vals))
                diff_tensors.append(leaf)
            dyn_vals.append(leaf._value)
        elif isinstance(leaf, (jax.Array, np.ndarray)):
            layout.append("D")
            dyn_vals.append(leaf)
        else:
            try:
                hash(leaf)
            except TypeError:
                return _CACHE_BYPASS
            layout.append("S")
            statics.append(leaf)

    dyn_vals = _maybe_amp_cast(name, dyn_vals)
    key = (cache_key, record, treedef, tuple(layout),
           tuple((type(s), s) for s in statics),  # 1 != 1.0 != True as keys
           tuple(diff_idx),
           tuple((tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", type(v))))
                 for v in dyn_vals))

    entry = _DISPATCH_CACHE.get(key)
    first = entry is None
    if not first:
        # LRU maintenance: re-insert at the MRU end so long-running jobs
        # with shape churn (variable seq lens, generation loops) keep their
        # hot entries instead of freezing the first 4096 shapes forever
        _DISPATCH_CACHE[key] = _DISPATCH_CACHE.pop(key)
        _CACHE_STATS["hits"] += 1
        _CACHE_STATS["evict_streak"] = 0
    else:
        _CACHE_STATS["misses"] += 1
        if _DISPATCH_CACHE_MAX <= 0:
            _CACHE_STATS["bypasses"] += 1
            return _CACHE_BYPASS
        if _CACHE_STATS["evict_streak"] > _DISPATCH_CACHE_MAX // 4:
            # thrash guard: a working set that cycles without EVER hitting
            # (e.g. unbucketed lengths > cache size) must not pay a jit
            # trace+compile per dispatch — serve it from the direct path
            # like the old insert-cap did; hits on resident entries still
            # reset the streak and re-enable inserts
            _CACHE_STATS["bypasses"] += 1
            return _CACHE_BYPASS
        while len(_DISPATCH_CACHE) >= _DISPATCH_CACHE_MAX:
            _DISPATCH_CACHE.pop(next(iter(_DISPATCH_CACHE)))
            _CACHE_STATS["evictions"] += 1
            _CACHE_STATS["evict_streak"] += 1
    if first:
        layout_t, statics_t, di = tuple(layout), tuple(statics), tuple(diff_idx)

        def rebuilt(vals_dyn):
            it, st = iter(vals_dyn), iter(statics_t)
            vals = [next(it) if tag == "D" else next(st) for tag in layout_t]
            a, k = jax.tree_util.tree_unflatten(treedef, vals)
            return fn(*a, **k)

        if record:
            def fwd(vals_dyn):
                def closed(*diff_vals):
                    vv = list(vals_dyn)
                    for j, v in zip(di, diff_vals):
                        vv[j] = v
                    return rebuilt(vv)
                return jax.vjp(closed, *[vals_dyn[j] for j in di])
            entry = (jax.jit(fwd), rebuilt)
        else:
            entry = (jax.jit(rebuilt), rebuilt)
        _DISPATCH_CACHE[key] = entry

    jitted, rebuilt = entry
    if first:
        rng_before = _rng_counters()
    result = jitted(dyn_vals)
    if first and _rng_counters() != rng_before:
        # the op drew randomness during its trace — a cached executable would
        # replay the same key forever; evict and take the direct path
        del _DISPATCH_CACHE[key]
        _UNCACHEABLE_OPS.add(cache_key)
        return _CACHE_BYPASS

    if not record:
        return _wrap_outputs(result, node=None, name=name)

    out, vjp_obj = result
    base_vals = list(dyn_vals)
    di = tuple(diff_idx)

    def closed_eager(*diff_vals):
        vv = list(base_vals)
        for j, v in zip(di, diff_vals):
            vv[j] = v
        return rebuilt(vv)

    out_leaves, out_treedef = jax.tree_util.tree_flatten(out)
    out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_leaves]
    node = Node(functools.partial(_bwd_call, vjp_obj), diff_tensors,
                out_treedef, out_avals, name, fwd_fn=closed_eager)
    return _wrap_outputs(out, node=node, name=name)


def dispatch(fn: Callable, args: tuple, kwargs: dict, name: str | None = None,
             cache_key: str | None = None):
    """Run one op eagerly, recording a tape node when gradients are required.

    ``fn`` must be a pure jax function of the *values* inside any Tensor leaves of
    (args, kwargs). Non-tensor leaves are closed over (static from autograd's view).
    ``cache_key`` (set by the op registry) opts the call into the compiled
    dispatch cache — only valid when ``fn`` is a stable pure function.
    """
    name = name or getattr(fn, "__name__", "op")
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_is_tensor)

    tensor_pos = [i for i, leaf in enumerate(leaves) if isinstance(leaf, Tensor)]
    record = (
        is_grad_enabled()
        and any(not leaves[i].stop_gradient for i in tensor_pos)
    )

    rep = _capture.replay
    if rep is not None:
        # compiled-prefix replay (jit/prefix_capture.py): prefix-position
        # ops are answered from the precompiled program; divergence (or a
        # grad-recording op) ends the replay and execution continues eagerly
        out = rep.try_replay(fn, name, leaves, treedef, record)
        if out is not _REPLAY_PASS:
            return out

    rec = _capture.recorder
    if cache_key is None and not _OP_OBSERVERS and _mode.functional == 0 \
            and rec is None:
        try:
            cache_key = (name, _freeze(fn))
        except (_Unfreezable, ValueError):  # ValueError: empty closure cell
            cache_key = None
    if cache_key is not None and cache_key not in _UNCACHEABLE_OPS \
            and not _OP_OBSERVERS and _mode.functional == 0 and rec is None:
        out = _dispatch_cached(fn, name, cache_key, leaves, treedef, record)
        if out is not _CACHE_BYPASS:
            return out

    rng_before = _rng_counters() if rec is not None else None

    if not record:
        vals = _maybe_amp_cast(name, [_unwrap(x) for x in leaves])
        a, k = jax.tree_util.tree_unflatten(treedef, vals)
        out = fn(*a, **k)
        result = _wrap_outputs(out, node=None, name=name)
        if rec is not None:
            rec.after_op(fn, name, leaves, treedef, result, False,
                         _rng_counters() != rng_before)
        return result

    diff_pos = [i for i in tensor_pos if not leaves[i].stop_gradient]
    diff_tensors = [leaves[i] for i in diff_pos]
    base_vals = _maybe_amp_cast(name, [_unwrap(x) for x in leaves])

    def closed(*diff_vals):
        vals = list(base_vals)
        for p, v in zip(diff_pos, diff_vals):
            vals[p] = v
        a, k = jax.tree_util.tree_unflatten(treedef, vals)
        return fn(*a, **k)

    out, vjp_fn = jax.vjp(closed, *[base_vals[i] for i in diff_pos])
    out_leaves, out_treedef = jax.tree_util.tree_flatten(out)
    out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in out_leaves]
    node = Node(vjp_fn, diff_tensors, out_treedef, out_avals, name, fwd_fn=closed)
    result = _wrap_outputs(out, node=node, name=name)
    if rec is not None:
        rec.after_op(fn, name, leaves, treedef, result, True,
                     _rng_counters() != rng_before)
    return result


def _wrap_outputs(out, node: Node | None, name: str):
    out_leaves, out_treedef = jax.tree_util.tree_flatten(out)
    if flag_value("check_nan_inf"):
        _check_numerics(name, out_leaves)
    for _obs in _OP_OBSERVERS:
        _obs(name, out_leaves)
    wrapped = []
    for i, leaf in enumerate(out_leaves):
        if not isinstance(leaf, (jax.Array, np.ndarray)) and not hasattr(leaf, "dtype"):
            wrapped.append(leaf)
            if node is not None:
                node.outputs.append(None)
            continue
        diff_out = node is not None and jnp.issubdtype(leaf.dtype, jnp.inexact)
        t = Tensor(leaf, stop_gradient=not diff_out)
        if node is not None:
            t._node = node
            t._out_index = i
            node.outputs.append(weakref.ref(t))
        wrapped.append(t)
    result = jax.tree_util.tree_unflatten(out_treedef, wrapped)
    return result


class OpDef:
    """Registered op: a named pure function invokable on Tensors via dispatch."""

    __slots__ = ("fn", "name", "__wrapped__")

    def __init__(self, fn, name=None):
        self.fn = fn
        self.name = name or fn.__name__
        self.__wrapped__ = fn

    def __call__(self, *args, **kwargs):
        return dispatch(self.fn, args, kwargs, name=self.name,
                        cache_key=self.name)

    def __repr__(self):
        return f"<op {self.name}>"


_OP_REGISTRY: dict[str, OpDef] = {}


def register_op(fn=None, *, name: str | None = None):
    """Decorator: make a pure jax function an eager-dispatchable op.

    The registry is the analog of the reference KernelFactory
    (paddle/phi/core/kernel_factory.h:316) — a flat name->callable map; backend
    selection is XLA's job, not ours.
    """
    def deco(f):
        op = OpDef(f, name)
        _OP_REGISTRY[op.name] = op
        return op

    return deco(fn) if fn is not None else deco


def get_op(name: str) -> OpDef:
    return _OP_REGISTRY[name]


def all_ops() -> dict:
    return dict(_OP_REGISTRY)
