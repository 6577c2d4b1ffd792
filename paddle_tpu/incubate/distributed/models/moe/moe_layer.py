"""MoELayer — expert-parallel mixture-of-experts (reference:
incubate/distributed/models/moe/moe_layer.py:261).

TPU-native deviations from the reference:
- experts are STACKED weight tensors ([E, D, F] / [E, F, D]) rather than a
  python list of sub-Layers — one einsum over the expert dim instead of a
  per-expert loop, so the MXU sees large batched matmuls and the expert dim
  shards over the `ep` mesh axis with plain NamedSharding;
- dispatch is the static-shape capacity algorithm (ops/kernels/moe.py), not
  ragged global_scatter/global_gather CUDA ops;
- expert parallelism = one lax.all_to_all each way inside shard_map.
"""
from __future__ import annotations

import math

import numpy as np
import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .....core.tensor import Tensor, dispatch
from .....nn.layer_base import Layer
from .....nn.initializer import XavierUniform, Normal
from .....ops.kernels.moe import moe_forward_dense, moe_forward_ep
from .gates import BaseGate, GShardGate, SwitchGate, NaiveGate

_GATES = {"gshard": GShardGate, "switch": SwitchGate, "naive": NaiveGate}


class MoELayer(Layer):
    """Token-routed FFN experts with optional expert parallelism.

    Args:
        d_model: hidden size.
        d_ffn: per-expert FFN width.
        num_experts: total expert count E (divisible by ep degree when parallel).
        gate: "gshard" | "switch" | "naive" or a BaseGate instance.
        activation: "swiglu" (llama-style, uses a gate projection) or "gelu".
        mesh / axis_name: expert-parallel mesh axis; None → single-device dense.

    forward(x): x [B, S, D] or [T, D] -> same shape; the load-balancing loss of
    the last call is available as `.l_aux` (add it to the training loss).
    """

    def __init__(self, d_model, d_ffn, num_experts, gate="gshard",
                 activation="swiglu", capacity_factor=None, top_k=None,
                 mesh=None, axis_name="ep", name=None):
        super().__init__()
        if isinstance(gate, str):
            gate_cls = _GATES[gate]
            kwargs = {}
            if capacity_factor is not None:
                kwargs["capacity_factor"] = capacity_factor
            if top_k is not None and gate != "switch":
                kwargs["top_k"] = top_k
            self.gate = gate_cls(d_model, num_experts, **kwargs)
        elif isinstance(gate, BaseGate):
            self.gate = gate
        else:
            raise ValueError(f"gate must be a name or BaseGate, got {gate!r}")
        self.d_model = d_model
        self.d_ffn = d_ffn
        self.num_experts = num_experts
        self.activation = activation
        self.mesh = mesh
        self.axis_name = axis_name
        scale = 1.0 / math.sqrt(d_model)
        init = Normal(std=scale)
        self.w_gate = self.create_parameter((num_experts, d_model, d_ffn),
                                            default_initializer=init)
        self.w_up = self.create_parameter((num_experts, d_model, d_ffn),
                                          default_initializer=init)
        self.w_down = self.create_parameter((num_experts, d_ffn, d_model),
                                            default_initializer=Normal(
                                                std=1.0 / math.sqrt(d_ffn)))
        self.l_aux = None

    def _jax_mesh(self):
        m = self.mesh
        if m is None:
            return None
        return m.jax_mesh() if hasattr(m, "jax_mesh") else m

    def forward(self, x):
        orig_shape = x.shape
        if len(orig_shape) == 3:
            x = x.reshape([-1, orig_shape[-1]])
        cf = self.gate.effective_capacity_factor()
        top_k = self.gate.top_k
        mesh = self._jax_mesh()

        if mesh is None:
            def fn(xv, rw, wg, wu, wd):
                return moe_forward_dense(
                    xv, rw, wg, wu, wd, top_k=top_k, capacity_factor=cf,
                    activation=self.activation)
        else:
            ax = self.axis_name

            def fn(xv, rw, wg, wu, wd):
                f = shard_map(
                    lambda a, b, c, d, e: moe_forward_ep(
                        a, b, c, d, e, ax, top_k=top_k, capacity_factor=cf,
                        activation=self.activation),
                    mesh=mesh,
                    in_specs=(P(ax, None), P(None, None), P(ax, None, None),
                              P(ax, None, None), P(ax, None, None)),
                    out_specs=(P(ax, None), P()))
                return f(xv, rw, wg, wu, wd)

        y, aux = dispatch(fn, (x, self.gate.weight, self.w_gate, self.w_up,
                               self.w_down), {}, name="moe")
        self.l_aux = aux
        if len(orig_shape) == 3:
            y = y.reshape(orig_shape)
        return y
