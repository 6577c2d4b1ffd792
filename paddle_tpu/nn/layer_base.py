"""Layer base class + Parameter (paddle.nn.Layer analog).

Reference: python/paddle/nn/layer/layers.py:353 — parameters/sublayers/buffers
registries, hooks, state_dict. Design deviation from the reference: a Layer here is a
*thin stateful shell* over pure-functional compute — its parameters can be temporarily
rebound to traced values (jit/functional_call.py), which is how one Layer definition
serves both the eager tape and the compiled pjit path.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Iterator

import numpy as np
import jax.numpy as jnp

from ..core import dtype as dtypes
from ..core.tensor import Tensor
from ..profiler import scope

_GLOBAL_WEIGHT_INIT = None
_GLOBAL_BIAS_INIT = None

#: nesting depth of active LazyGuard scopes (reference:
#: python/paddle/base/core LazyGuard / lazy_init) — under a guard,
#: create_parameter produces ABSTRACT values (jax.ShapeDtypeStruct) and
#: records the initializer for later materialization. An abstract model
#: costs no memory: the basis for AOT memory/sharding planning at scales
#: that cannot materialize on one host (tests/test_7b_scale.py).
#: Thread-local (like core.tensor's mode state): a guard on one thread must
#: not leak abstract params into layers built concurrently on another.
import threading as _threading


class _LazyState(_threading.local):
    def __init__(self):
        self.depth = 0


_LAZY_INIT = _LazyState()


def lazy_init_active() -> bool:
    return _LAZY_INIT.depth > 0


class Parameter(Tensor):
    """Trainable tensor (stop_gradient=False by default, optimizer-visible)."""

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v

    def initialize(self):
        """Materialize a LazyGuard-created parameter by running its recorded
        initializer. No-op for already-materialized parameters. Honors dtype
        rewrites applied while abstract (e.g. ``layer.bfloat16()``) and any
        sharding assigned to the abstract value (materializes placed)."""
        spec = self.__dict__.pop("_lazy_init", None)
        if spec is not None:
            init, shape, _ = spec
            sharding = getattr(self._value, "sharding", None)
            value = init(shape, str(np.dtype(self._value.dtype)))
            value = value._value if isinstance(value, Tensor) else value
            if sharding is not None:
                import jax
                value = jax.device_put(value, sharding)
            self._value = value
        return self

    def __repr__(self):
        return "Parameter " + super().__repr__()


class ParamAttr:
    """paddle.ParamAttr — per-parameter config bundle."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if attr is False:
            return None
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        # bare initializer
        return ParamAttr(initializer=attr)


class HookRemoveHelper:
    def __init__(self, container, key):
        self._container, self._key = container, key

    def remove(self):
        self._container.pop(self._key, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        d = object.__setattr__
        d(self, "_parameters", OrderedDict())
        d(self, "_sub_layers", OrderedDict())
        d(self, "_buffers", OrderedDict())
        d(self, "_non_persistable_buffer_names", set())
        d(self, "training", True)
        d(self, "_dtype", dtypes.convert_dtype(dtype) if dtype else dtypes.float32)
        d(self, "_forward_pre_hooks", OrderedDict())
        d(self, "_forward_post_hooks", OrderedDict())
        d(self, "_hook_id", 0)
        d(self, "_name_scope", name_scope or type(self).__name__.lower())
        #: the token this layer's operations carry in a device profile
        #: (``profiler.scope``): the name its FIRST parent registered it
        #: under; None (a root, an unregistered layer) reads as the class
        d(self, "_scope_name", None)

    # -- attribute routing ---------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        if params is not None:
            for d in (self._parameters, self._sub_layers, self._buffers):
                d.pop(name, None)
            if isinstance(value, Parameter):
                self.__dict__.pop(name, None)  # drop any shadowing plain attr
                params[name] = value
                return
            if isinstance(value, Layer):
                self.__dict__.pop(name, None)
                self.add_sublayer(name, value)
                return
        object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for dname in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(dname)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} has no attribute {name!r}")

    def __delattr__(self, name):
        for d in (self._parameters, self._sub_layers, self._buffers):
            if name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # -- construction helpers ------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """LayerHelper analog (reference: python/paddle/base/layer_helper.py:39)."""
        from .initializer import Constant, XavierNormal, Uniform
        attr = ParamAttr._to_attr(attr)
        if attr is None:
            return None
        dtype = dtypes.convert_dtype(dtype) if dtype is not None else self._dtype
        init = attr.initializer or default_initializer
        if init is None:
            if is_bias:
                init = _GLOBAL_BIAS_INIT or Constant(0.0)
            else:
                init = _GLOBAL_WEIGHT_INIT or XavierNormal()
        if _LAZY_INIT.depth:
            import jax
            value = jax.ShapeDtypeStruct(tuple(int(s) for s in shape),
                                         jnp.dtype(dtype))
            p = Parameter(value, trainable=attr.trainable, name=attr.name)
            p._lazy_init = (init, [int(s) for s in shape], dtype)
            return p
        value = init(shape, dtype)
        p = Parameter(value, trainable=attr.trainable, name=attr.name)
        return p

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(jnp.asarray(tensor))
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        else:
            tensor.persistable = True
        return tensor

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        if sublayer is not None and \
                sublayer.__dict__.get("_scope_name") is None:
            object.__setattr__(sublayer, "_scope_name", str(name))
        return sublayer

    def add_parameter(self, name, parameter):
        self._parameters[str(name)] = parameter
        return parameter

    # -- iteration -----------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + name if not prefix else f"{prefix}.{name}"), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(sub_prefix, True):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters("", include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix, True)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers("", include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield sub_prefix, layer
            yield from layer.named_sublayers(sub_prefix, False)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers("", include_self)]

    def children(self):
        return iter(l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return iter((n, l) for n, l in self._sub_layers.items() if l is not None)

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # -- mode ---------------------------------------------------------------
    def train(self):
        for l in self.sublayers(include_self=True):
            object.__setattr__(l, "training", True)
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            object.__setattr__(l, "training", False)
        return self

    # -- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(structured_name_prefix.rstrip("."),
                                             include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(structured_name_prefix.rstrip("."),
                                          include_sublayers):
            short = name.rsplit(".", 1)[-1]
            # find owning layer to check persistability
            dest[name] = b
        # drop non-persistable buffers
        for lname, layer in self.named_sublayers("", include_self=True):
            for bname in layer._non_persistable_buffer_names:
                full = f"{lname}.{bname}" if lname else bname
                dest.pop(full, None)
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, value in state_dict.items():
            if name not in own:
                unexpected.append(name)
                continue
            target = own[name]
            v = value._value if isinstance(value, Tensor) else jnp.asarray(value)
            if tuple(v.shape) != tuple(target.shape):
                raise ValueError(
                    f"shape mismatch for {name}: got {tuple(v.shape)}, "
                    f"expected {tuple(target.shape)}")
            # explicit copy: the source may belong to another live model whose
            # buffers get donated by a compiled train step
            target._value = jnp.array(v, dtype=target._value.dtype, copy=True)
        for name in own:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    # -- dtype / cast ---------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_to(dtypes.convert_dtype(dtype))
        return self

    def astype(self, dtype):
        self._cast_to(dtypes.convert_dtype(dtype))
        return self

    def _cast_to(self, d):
        import jax
        for l in self.sublayers(include_self=True):
            object.__setattr__(l, "_dtype", d)
        for p in self.parameters():
            if jnp.issubdtype(p._value.dtype, jnp.floating):
                if isinstance(p._value, jax.ShapeDtypeStruct):
                    # abstract (LazyGuard) param: rewrite the aval dtype;
                    # initialize() materializes at the rewritten dtype
                    p._value = jax.ShapeDtypeStruct(
                        p._value.shape, jnp.dtype(d),
                        sharding=p._value.sharding)
                else:
                    p._value = p._value.astype(d)
        for b in self.buffers():
            if b is not None and jnp.issubdtype(b._value.dtype, jnp.floating):
                b._value = b._value.astype(d)

    def materialize(self):
        """Run the recorded initializers of every LazyGuard-created (abstract)
        parameter in this layer tree. Returns self."""
        for p in self.parameters():
            if hasattr(p, "initialize"):
                p.initialize()
        return self

    def float(self):
        return self.astype(dtypes.float32)

    def bfloat16(self):
        return self.astype(dtypes.bfloat16)

    def half(self):
        return self.astype(dtypes.float16)

    # -- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call ----------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        # the model tree rides on every operation lowered below as one
        # token a layer (forward and backward): trace-time metadata only
        with scope(self.__dict__.get("_scope_name") or type(self).__name__):
            for hook in self._forward_pre_hooks.values():
                res = hook(self, args)
                if res is not None:
                    args = res if isinstance(res, tuple) else (res,)
            out = self.forward(*args, **kwargs)
            for hook in self._forward_post_hooks.values():
                res = hook(self, args, out)
                if res is not None:
                    out = res
            return out

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        lines = []
        extra = self.extra_repr()
        for name, layer in self._sub_layers.items():
            mod_str = repr(layer)
            mod_str = "\n".join("  " + l for l in mod_str.split("\n"))
            lines.append(f"({name}): {mod_str.strip()}")
        main = type(self).__name__
        if extra and not lines:
            return f"{main}({extra})"
        if not lines:
            return f"{main}()"
        body = "\n".join("  " + l for l in lines)
        return f"{main}(\n{body}\n)"
