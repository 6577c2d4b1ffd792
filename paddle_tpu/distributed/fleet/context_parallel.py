"""Context parallelism — user-facing ring/Ulysses attention over the `sep` axis.

The reference's `sep` hybrid axis (fleet/base/topology.py:199,
fleet/meta_parallel/segment_parallel.py:26) only provides comm groups and leaves
sequence splitting + ring attention to out-of-tree code (PaddleNLP). Here the full
context-parallel story is in-core: zigzag sharding helpers, a functional API, and a
drop-in attention layer — all lowering to ppermute/all_to_all on ICI via shard_map.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ...core.tensor import Tensor, dispatch
from ...ops.kernels.ring_attention import (
    ring_attention, ulysses_attention, zigzag_positions,
)


def _resolve_mesh(mesh=None, axis_name="sep"):
    if mesh is None:
        from . import fleet_state
        h = fleet_state.hcg()
        if h is not None and axis_name in h.mesh.dim_names:
            mesh = h.mesh
    if mesh is None:
        devs = np.asarray(jax.devices(), dtype=object)
        return Mesh(devs, (axis_name,))
    if hasattr(mesh, "jax_mesh"):  # ProcessMesh
        return mesh.jax_mesh()
    return mesh


def shard_zigzag(x, n_ranks, seq_axis=1):
    """Reorder the full sequence into the zigzag layout: rank r gets chunks
    (r, 2N-1-r). Apply BEFORE sharding the sequence axis; invert with
    unshard_zigzag after gathering."""
    def fn(v):
        s = v.shape[seq_axis]
        if s % (2 * n_ranks) != 0:
            raise ValueError(
                f"zigzag layout needs seq len divisible by 2*n_ranks "
                f"({s} vs 2*{n_ranks})")
        chunks = jnp.split(v, 2 * n_ranks, axis=seq_axis)
        order = []
        for r in range(n_ranks):
            order += [chunks[r], chunks[2 * n_ranks - 1 - r]]
        return jnp.concatenate(order, axis=seq_axis)
    if isinstance(x, Tensor):
        return dispatch(fn, (x,), {}, name="shard_zigzag")
    return fn(jnp.asarray(x))


def unshard_zigzag(x, n_ranks, seq_axis=1):
    """Inverse of shard_zigzag on the gathered (full-sequence) tensor."""
    def fn(v):
        chunks = jnp.split(v, 2 * n_ranks, axis=seq_axis)
        inv = [None] * (2 * n_ranks)
        j = 0
        for r in range(n_ranks):
            inv[r] = chunks[j]; j += 1
            inv[2 * n_ranks - 1 - r] = chunks[j]; j += 1
        return jnp.concatenate(inv, axis=seq_axis)
    if isinstance(x, Tensor):
        return dispatch(fn, (x,), {}, name="unshard_zigzag")
    return fn(jnp.asarray(x))


def ring_flash_attention(query, key, value, mesh=None, axis_name="sep",
                         causal=False, scale=None, balanced=None):
    """Ring attention on FULL-SIZE [B, S, H, D] tensors; this wrapper owns the
    shard_map over `axis_name`. From inside an existing shard_map (e.g. a fused
    hybrid-parallel step), call ops.kernels.ring_attention.ring_attention on the
    per-shard arrays instead — nesting this wrapper raises a mesh-context error,
    and per-shard inputs here would be silently re-sharded to 1/N of the sequence.

    balanced=None → auto: zigzag layout for causal (uniform per-rank work).
    """
    mesh = _resolve_mesh(mesh, axis_name)
    if balanced is None:
        balanced = causal
    n = mesh.shape[axis_name]

    spec = P(None, axis_name, None, None)

    def fn(q, k, v):
        if balanced:
            q, k, v = (shard_zigzag(t, n) for t in (q, k, v))
        f = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis_name, causal=causal,
                                           scale=scale, balanced=balanced),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        o = f(q, k, v)
        if balanced:
            o = unshard_zigzag(o, n)
        return o

    return dispatch(fn, (query, key, value), {}, name="ring_flash_attention")


def ulysses_flash_attention(query, key, value, mesh=None, axis_name="sep",
                            causal=False, scale=None, attn_fn=None):
    """Ulysses all-to-all attention on [B, S, H, D]; H must divide by axis size.

    attn_fn overrides the local (post-all-to-all) attention; the default is the
    Pallas flash kernel on TPU, exact fp32-softmax attention elsewhere.
    """
    mesh = _resolve_mesh(mesh, axis_name)
    spec = P(None, axis_name, None, None)

    def fn(q, k, v):
        f = shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, axis_name, causal=causal,
                                              scale=scale, attn_fn=attn_fn),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            # pallas_call out_shapes carry no vma info; the flash-kernel local
            # step would fail shard_map's vma check
            check_vma=False)
        return f(q, k, v)

    return dispatch(fn, (query, key, value), {}, name="ulysses_flash_attention")


class ContextParallelAttention:
    """Drop-in SDPA replacement for models running with a sep/context axis.

    mode: "ring" (arbitrary lengths, P2P ppermute ring) or "ulysses"
    (all-to-all head swap; needs heads % sep_degree == 0).
    """

    def __init__(self, mesh=None, axis_name="sep", mode="ring", causal=True):
        if mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown context-parallel mode {mode!r} "
                             "(expected 'ring' or 'ulysses')")
        self.mesh = mesh
        self.axis_name = axis_name
        self.mode = mode
        self.causal = causal

    def __call__(self, q, k, v):
        fn = (ring_flash_attention if self.mode == "ring"
              else ulysses_flash_attention)
        return fn(q, k, v, mesh=self.mesh, axis_name=self.axis_name,
                  causal=self.causal)
