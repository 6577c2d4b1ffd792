"""paddle.static analog — deferred-execution graph API over the eager tape.

Reference: python/paddle/static/ (Program/Executor/data, SURVEY.md §2.6) where
a Program is a protobuf op graph executed by the C++ PirInterpreter.

TPU-native redesign: there is no separate graph IR — the eager tape (core/
tensor.py Node DAG, each node carrying a pure `fwd_fn`) IS the captured
program. `static.data` creates named placeholder tensors; building ops under
`program_guard` records the tape; `Executor.run(prog, feed, fetch_list)`
REPLAYS the tape DAG with feed values substituted at the placeholders,
compiled once per (feed shapes, fetches) signature with jax.jit — the analog
of PirInterpreter's first-run lowering + cached instruction list. Training
loops belong to the dygraph/jit path (TrainStep); the static surface covers
graph capture, feed/fetch execution, and save/load_inference_model.
"""
from __future__ import annotations

import contextlib
import os
import pickle

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import dtype as dtypes
from ..profiler import scope

__all__ = [
    "Program", "program_guard", "default_main_program", "default_startup_program",
    "data", "InputSpec", "Executor", "save_inference_model",
    "load_inference_model", "name_scope", "nn", "append_backward", "gradients",
    "global_scope", "scope_guard", "Scope", "BuildStrategy", "CompiledProgram",
    "ExecutionStrategy", "Print", "py_func", "WeightNormParamAttr",
    "ExponentialMovingAverage", "save", "load", "serialize_program",
    "serialize_persistables", "save_to_file", "deserialize_program",
    "deserialize_persistables", "load_from_file", "normalize_program",
    "load_program_state", "set_program_state", "cpu_places", "cuda_places",
    "xpu_places", "Variable", "create_global_var", "create_parameter",
    "accuracy", "auc", "device_guard", "ipu_shard_guard", "IpuCompiledProgram",
    "IpuStrategy", "set_ipu_shard", "ctr_metric_bundle",
]


class Program:
    """Captured-graph container: tracks placeholders + fetch targets created
    in its guard scope (reference: base/framework.py Program:5890)."""

    def __init__(self):
        self.placeholders = {}
        self.random_seed = None
        self._tensors = []

    def global_block(self):
        return self

    def clone(self, for_test=False):
        return self

    def __repr__(self):
        return (f"Program(placeholders={list(self.placeholders)}, "
                f"tensors={len(self._tensors)})")


_default_main = Program()
_default_startup = Program()
_prog_stack = [_default_main]


def default_main_program():
    return _prog_stack[-1]


def default_startup_program():
    return _default_startup


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    _prog_stack.append(main_program)
    try:
        yield
    finally:
        _prog_stack.pop()


@contextlib.contextmanager
def name_scope(prefix=None):
    with scope(prefix or "scope"):
        yield


class InputSpec:
    """Shape/dtype spec (reference: static/input.py InputSpec)."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=False):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tensor.shape, str(tensor.dtype), name)

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


def data(name, shape, dtype="float32", lod_level=0):
    """Feed placeholder (reference: static/input.py data). Returns a zero
    Tensor tagged with the feed name; -1 dims become 1 at trace time and are
    re-specialized per feed shape at Executor.run."""
    shp = [1 if (d is None or d < 0) else int(d) for d in shape]
    t = Tensor(jnp.zeros(shp, dtypes.convert_dtype(dtype)), stop_gradient=False)
    t.name = name
    t._feed_name = name
    default_main_program().placeholders[name] = t
    return t


def _replay(fetch_leaf_tensors, feed_values):
    """Recompute fetch values by walking the tape DAG, substituting feeds.

    feed_values: {feed_name: jax value}. Pure: usable under jax.jit.
    """
    node_memo = {}

    def tensor_value(t):
        fname = getattr(t, "_feed_name", None)
        if fname is not None and fname in feed_values:
            return feed_values[fname]
        node = t._node
        if node is None:
            return t._value
        leaves = node_leaves(node)
        return leaves[t._out_index]

    def node_leaves(node):
        got = node_memo.get(id(node))
        if got is not None:
            return got
        ins = [tensor_value(p) for p in node.parents]
        out = node.fwd_fn(*ins)
        leaves = jax.tree_util.tree_flatten(out)[0]
        node_memo[id(node)] = leaves
        return leaves

    return [tensor_value(t) for t in fetch_leaf_tensors]


class Executor:
    """Feed/fetch executor over captured graphs (reference: base/executor.py
    Executor:1237 -> StandaloneExecutor). jit-compiles the replay per
    (fetches, feed signature) and caches the executable."""

    def __init__(self, place=None):
        self.place = place
        self._cache = {}

    def run(self, program=None, feed=None, fetch_list=None, return_numpy=True):
        feed = feed or {}
        fetch_list = fetch_list or []
        fetches = [f for f in fetch_list]
        for f in fetches:
            if not isinstance(f, Tensor):
                raise TypeError(f"fetch_list entries must be Tensors, got {f!r}")
        feed_vals = {k: jnp.asarray(v._value if isinstance(v, Tensor) else v)
                     for k, v in feed.items()}
        key = (tuple(id(f) for f in fetches),
               tuple(sorted((k, v.shape, str(v.dtype))
                            for k, v in feed_vals.items())))
        fn = self._cache.get(key)
        if fn is None:
            names = sorted(feed_vals)

            def run_fn(*vals):
                return _replay(fetches, dict(zip(names, vals)))
            fn = jax.jit(run_fn)
            self._cache[key] = (fn, names)
        fn, names = self._cache[key]
        outs = fn(*[feed_vals[n] for n in names])
        if return_numpy:
            return [np.asarray(o) for o in outs]
        return [Tensor(o, stop_gradient=True) for o in outs]

    def close(self):
        self._cache.clear()


def save_inference_model(path_prefix, feed_vars, fetch_vars, executor=None,
                         program=None, **kwargs):
    """Serialize a captured graph (reference: static/io.py save_inference_model).

    TPU-native: stores the REPLAY CLOSURE's jaxpr-equivalent by re-tracing the
    fetches as a function of the feeds, plus all captured constants, with
    pickle of the jitted function's inputs — practically: we store feed specs
    and the fetch values' computation via jax.export when available, else the
    feed/fetch tensors for same-process reuse."""
    feed_vars = feed_vars if isinstance(feed_vars, (list, tuple)) else [feed_vars]
    fetch_vars = (fetch_vars if isinstance(fetch_vars, (list, tuple))
                  else [fetch_vars])
    names = [getattr(v, "_feed_name", getattr(v, "name", None))
             for v in feed_vars]

    def fn(*vals):
        return _replay(fetch_vars, dict(zip(names, vals)))

    args = [jnp.zeros(v.shape, v._value.dtype) for v in feed_vars]
    payload = {"feed_names": names,
               "feed_specs": [(v.shape, str(np.dtype(v.dtype))) for v in feed_vars],
               "fetch_names": [getattr(v, "name", None) or f"fetch_{i}"
                               for i, v in enumerate(fetch_vars)]}
    os.makedirs(os.path.dirname(path_prefix) or ".", exist_ok=True)
    try:
        from jax import export as jax_export
        exported = jax_export.export(jax.jit(fn))(
            *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args])
        payload["serialized"] = exported.serialize()
        payload["format"] = "jax_export"
    except Exception:
        outs = fn(*args)
        payload["format"] = "none"
    with open(path_prefix + ".pdmodel", "wb") as f:
        pickle.dump(payload, f)
    return path_prefix + ".pdmodel"


def load_inference_model(path_prefix, executor=None, _return_meta=False,
                         **kwargs):
    """Load a saved inference graph; returns (program, feed_names, fetch_fn),
    or (fetch_fn, payload_meta) when _return_meta=True (paddle.inference path)."""
    path = path_prefix
    if not path.endswith(".pdmodel"):
        path = path_prefix + ".pdmodel"
    with open(path, "rb") as f:
        payload = pickle.load(f)
    names = payload["feed_names"]
    if payload.get("format") == "jax_export":
        from jax import export as jax_export
        exported = jax_export.deserialize(payload["serialized"])

        def fetch_fn(*vals):
            return exported.call(*[jnp.asarray(v) for v in vals])

        if _return_meta:
            return fetch_fn, payload
        return Program(), names, fetch_fn
    raise RuntimeError("model was saved without jax.export support")

from . import nn  # noqa: E402,F401


# ---------------------------------------------------------------------------
# Program state: parameters, scopes, save/load (reference: static/io.py,
# base/executor.py global_scope)
# ---------------------------------------------------------------------------

Variable = Tensor  # the static Variable IS a Tensor here (one tensor model)


class _ScopeVar:
    def __init__(self, value=None):
        self._value = value

    def get_tensor(self):
        return self

    def set(self, value, place=None):
        self._value = np.asarray(value)

    def __array__(self, dtype=None):
        arr = np.asarray(self._value)
        return arr.astype(dtype) if dtype else arr


class Scope:
    """Name → variable map (reference: framework Scope, scope.h:50)."""

    def __init__(self):
        self._vars = {}

    def var(self, name):
        return self._vars.setdefault(name, _ScopeVar())

    def find_var(self, name):
        return self._vars.get(name)

    def local_scope(self):
        return Scope()


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope():
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """reference: static (create_parameter) — registers into the current
    Program so static.save can find it."""
    import paddle_tpu as _paddle
    p = _paddle.create_parameter(shape, dtype, name=name, attr=attr,
                                 is_bias=is_bias,
                                 default_initializer=default_initializer)
    prog = default_main_program()
    prog._parameters = getattr(prog, "_parameters", {})
    prog._parameters[p.name or f"param_{len(prog._parameters)}"] = p
    return p


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    t = Tensor(jnp.full(tuple(shape), value, dtypes.convert_dtype(dtype)),
               stop_gradient=True)
    t.name = name
    t.persistable = persistable
    prog = default_main_program()
    prog._parameters = getattr(prog, "_parameters", {})
    prog._parameters[name or f"var_{len(prog._parameters)}"] = t
    return t


def _program_state(program):
    params = getattr(program or default_main_program(), "_parameters", {})
    return {k: np.asarray(v._value) for k, v in params.items()}


def save(program, model_path, protocol=4):
    """reference: static/io.py save — persistables of the program."""
    state = _program_state(program)
    with open(model_path + ".pdparams", "wb") as f:
        pickle.dump(state, f, protocol=protocol)
    return model_path + ".pdparams"


def load(program, model_path, executor=None, var_list=None):
    """reference: static/io.py load."""
    with open(model_path + ".pdparams", "rb") as f:
        state = pickle.load(f)
    set_program_state(program, state)
    return state


def load_program_state(model_path, var_list=None):
    with open(model_path + ".pdparams", "rb") as f:
        return pickle.load(f)


def set_program_state(program, state_dict):
    params = getattr(program or default_main_program(), "_parameters", {})
    for k, p in params.items():
        if k in state_dict:
            p._value = jnp.asarray(state_dict[k], p._value.dtype)


def serialize_program(feed_vars, fetch_vars, **kwargs):
    """reference: static/io.py serialize_program — bytes of the graph."""
    import pickle as _pickle
    names = [getattr(v, "_feed_name", getattr(v, "name", None))
             for v in (feed_vars if isinstance(feed_vars, (list, tuple))
                       else [feed_vars])]
    return _pickle.dumps({"feed_names": names})


def serialize_persistables(feed_vars, fetch_vars, **kwargs):
    return pickle.dumps(_program_state(default_main_program()))


def save_to_file(path, content):
    with open(path, "wb") as f:
        f.write(content)


def load_from_file(path):
    with open(path, "rb") as f:
        return f.read()


def deserialize_program(data):
    meta = pickle.loads(data)
    prog = Program()
    prog._meta = meta
    return prog


def deserialize_persistables(program, data, executor=None):
    state = pickle.loads(data)
    set_program_state(program, state)
    return state


def normalize_program(program, feed_vars, fetch_vars, **kwargs):
    """reference: static/io.py normalize_program — prune to the feed→fetch
    slice. The tape replay already computes only the fetch closure, so the
    program passes through."""
    return program


# ---------------------------------------------------------------------------
# Autograd on the captured tape (reference: base/backward.py)
# ---------------------------------------------------------------------------

def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None, checkpoints=None):
    """reference: base/backward.py append_backward — returns
    [(param, grad_var)] pairs."""
    from ..autograd.backward import grad as _grad
    if parameter_list is None:
        # reference resolves params from loss.block.program; our tape IS the
        # program, so walk the loss's autograd graph for Parameter leaves
        # (works outside program_guard too), falling back to the registry.
        from ..nn.layer_base import Parameter
        found, seen, stack = [], set(), [loss]
        while stack:
            t = stack.pop()
            node = getattr(t, "_node", None)
            if isinstance(t, Parameter) and id(t) not in seen:
                seen.add(id(t))
                found.append(t)
            if node is not None and id(node) not in seen:
                seen.add(id(node))
                stack.extend(node.parents)
        prog = default_main_program()
        registry = list(getattr(prog, "_parameters", {}).values())
        parameter_list = found or registry
    parameter_list = [p for p in parameter_list if not p.stop_gradient]
    grads = _grad([loss], parameter_list, retain_graph=True,
                  allow_unused=True)
    return list(zip(parameter_list, grads))


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """reference: base/backward.py gradients."""
    from ..autograd.backward import grad as _grad
    targets = targets if isinstance(targets, (list, tuple)) else [targets]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    return _grad(list(targets), list(inputs), grad_outputs=target_gradients,
                 retain_graph=True, allow_unused=True)


# ---------------------------------------------------------------------------
# Execution config + devices (XLA owns the pass pipeline; these are contracts)
# ---------------------------------------------------------------------------

class BuildStrategy:
    """reference: pybind BuildStrategy — graph-pass knobs. XLA performs the
    fusion/memory passes; flags are recorded for inspection only."""

    def __init__(self):
        self.enable_inplace = True
        self.fuse_elewise_add_act_ops = True
        self.fuse_bn_act_ops = True
        self.memory_optimize = True
        self.build_cuda_graph = False


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 10


class CompiledProgram:
    """reference: base/compiler.py CompiledProgram — wraps a Program with a
    BuildStrategy. Executor.run accepts it transparently."""

    def __init__(self, program, build_strategy=None):
        self._program = program
        self._build_strategy = build_strategy or BuildStrategy()

    def __getattr__(self, item):
        return getattr(self._program, item)


def cpu_places(device_count=None):
    n = device_count or int(os.environ.get("CPU_NUM", 1))
    from ..core.device import CPUPlace
    return [CPUPlace() for _ in range(n)]


def cuda_places(device_ids=None):
    from ..core.device import CUDAPlace
    ids = device_ids if device_ids is not None else range(len(jax.devices()))
    return [CUDAPlace(i) for i in ids]


def xpu_places(device_ids=None):
    return cuda_places(device_ids)


@contextlib.contextmanager
def device_guard(device=None):
    """reference: static/device_guard — pin ops to a device. Maps to
    jax.default_device for the guarded region."""
    if device in (None, "cpu"):
        dev = jax.devices("cpu")[0] if device == "cpu" else None
    else:
        idx = int(device.split(":")[1]) if ":" in str(device) else 0
        devs = jax.devices()
        dev = devs[min(idx, len(devs) - 1)]
    if dev is None:
        yield
    else:
        with jax.default_device(dev):
            yield


# ---------------------------------------------------------------------------
# Debug / host-callback ops
# ---------------------------------------------------------------------------

def Print(input, first_n=-1, message=None, summarize=20, print_tensor_name=True,
          print_tensor_type=True, print_tensor_shape=True,
          print_tensor_layout=True, print_tensor_lod=True,
          print_phase="both"):
    """reference: static/nn/control_flow.py Print op — passthrough + host print."""
    v = np.asarray(input._value)
    parts = [message or ""]
    if print_tensor_name and input.name:
        parts.append(f"name: {input.name}")
    if print_tensor_shape:
        parts.append(f"shape: {list(v.shape)}")
    if print_tensor_type:
        parts.append(f"dtype: {v.dtype}")
    flat = v.ravel() if summarize < 0 else v.ravel()[:summarize]
    parts.append(f"data: {flat}")
    print("  ".join(p for p in parts if p))
    return input


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host python op with optional custom backward (reference:
    static/nn/common.py py_func → py_func op). Eager: runs on host values and
    re-enters autograd through PyLayer when backward_func is given."""
    from ..autograd import PyLayer
    xs = x if isinstance(x, (list, tuple)) else [x]

    if backward_func is None:
        vals = func(*[np.asarray(t._value) for t in xs])
        vals = vals if isinstance(vals, (list, tuple)) else [vals]
        outs = out if isinstance(out, (list, tuple)) else [out]
        results = []
        for o, v in zip(outs, vals):
            t = Tensor(jnp.asarray(v), stop_gradient=True)
            t.name = getattr(o, "name", None)
            results.append(t)
        return results[0] if not isinstance(out, (list, tuple)) else results

    class _PyFunc(PyLayer):
        @staticmethod
        def forward(ctx, *inputs):
            ctx.save_for_backward(*inputs)
            vals = func(*[np.asarray(t._value) for t in inputs])
            vals = vals if isinstance(vals, (list, tuple)) else [vals]
            outs2 = [Tensor(jnp.asarray(v)) for v in vals]
            return outs2[0] if len(outs2) == 1 else tuple(outs2)

        @staticmethod
        def backward(ctx, *grads):
            saved = ctx.saved_tensor()
            gvals = backward_func(
                *[np.asarray(t._value) for t in saved],
                *[np.asarray(g._value) for g in grads])
            gvals = gvals if isinstance(gvals, (list, tuple)) else [gvals]
            gts = [Tensor(jnp.asarray(g)) for g in gvals]
            return gts[0] if len(gts) == 1 else tuple(gts)

    return _PyFunc.apply(*xs)


# ---------------------------------------------------------------------------
# Metrics + EMA + weight-norm attr (reference: static/nn/metric.py,
# incubate ExponentialMovingAverage, WeightNormParamAttr)
# ---------------------------------------------------------------------------

def accuracy(input, label, k=1, correct=None, total=None, name=None):
    from ..metric import accuracy as _acc
    return _acc(input, label, k=k)


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1,
        slide_steps=1, ins_tag_weight=None):
    """Batch AUC (reference: static/nn/metric.py auc). Returns
    (auc_out, [stat_pos, stat_neg]) like the static op's main outputs."""
    from ..metric import Auc
    m = Auc(curve=curve, num_thresholds=num_thresholds)
    pred = np.asarray(input._value)
    if pred.ndim == 2 and pred.shape[1] >= 2:
        # (N, C) softmax: column 1 is the positive-class probability (same
        # convention as metric.Auc.update and the reference auc op)
        preds2 = pred[:, :2] if pred.shape[1] == 2 else \
            np.stack([1 - pred[:, 1], pred[:, 1]], axis=1)
    else:
        p1 = pred.reshape(-1)
        preds2 = np.stack([1 - p1, p1], axis=1)
    m.update(preds=preds2, labels=np.asarray(label._value).reshape(-1, 1))
    val = Tensor(jnp.asarray(m.accumulate(), jnp.float64))
    return val, [Tensor(jnp.asarray(m._stat_pos)), Tensor(jnp.asarray(m._stat_neg))]


def ctr_metric_bundle(input, label, ins_tag_weight=None):
    """reference: static/nn/metric.py ctr_metric_bundle — local CTR stats:
    (mean positive rate, mean prediction, batch size)."""
    pred = np.asarray(input._value).reshape(-1)
    lab = np.asarray(label._value).reshape(-1)
    sq = float(np.mean((pred - lab) ** 2))
    return (Tensor(jnp.asarray(sq)),
            Tensor(jnp.asarray(float(pred.mean()))),
            Tensor(jnp.asarray(float(lab.size))))


class ExponentialMovingAverage:
    """EMA of trainable parameters with apply/restore swap (reference:
    static/ema.py ExponentialMovingAverage; thres_steps ramps the decay)."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._thres_steps = thres_steps
        self._step = 0
        self._ema = {}
        self._backup = {}
        self._params = {}

    def _tracked(self, parameters=None):
        if parameters is not None:
            return {(p.name or str(id(p))): p for p in parameters}
        prog = default_main_program()
        return {k: p for k, p in getattr(prog, "_parameters", {}).items()
                if not p.stop_gradient}

    def update(self, parameters=None):
        self._step += 1
        decay = self._decay
        if self._thres_steps is not None:
            decay = min(self._decay, (1 + self._step) / (10 + self._step))
        params = self._tracked(parameters)
        self._params.update(params)
        for k, p in params.items():
            v = np.asarray(p._value, np.float32)
            if k not in self._ema:
                self._ema[k] = v.copy()
            else:
                self._ema[k] = decay * self._ema[k] + (1 - decay) * v

    @contextlib.contextmanager
    def apply(self, executor=None, need_restore=True):
        for k, p in self._params.items():
            self._backup[k] = p._value
            if k in self._ema:
                p._value = jnp.asarray(self._ema[k], p._value.dtype)
        try:
            yield
        finally:
            if need_restore:
                self.restore()

    def restore(self, executor=None):
        for k, p in self._params.items():
            if k in self._backup:
                p._value = self._backup[k]
        self._backup = {}


class WeightNormParamAttr:
    """reference: static/param_attr.py WeightNormParamAttr — declares
    weight-norm reparameterization (g * v/|v|) on a created parameter. Our
    layers apply it via nn.utils.weight_norm; this attr carries the config."""

    def __init__(self, dim=None, name=None, initializer=None,
                 learning_rate=1.0, regularizer=None, trainable=True,
                 do_model_average=False, need_clip=True):
        self.dim = dim
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip


# ---------------------------------------------------------------------------
# IPU stubs: exist for API parity, raise like a build without IPU support
# ---------------------------------------------------------------------------

def _no_ipu(*a, **k):
    raise RuntimeError("Can not use this function since PaddlePaddle is not "
                       "compiled with IPU")


class IpuStrategy:
    def __init__(self):
        _no_ipu()


class IpuCompiledProgram:
    def __init__(self, *a, **k):
        _no_ipu()


@contextlib.contextmanager
def ipu_shard_guard(index=-1, stage=-1):
    _no_ipu()
    yield


def set_ipu_shard(call_func, index=-1, stage=-1):
    _no_ipu()
