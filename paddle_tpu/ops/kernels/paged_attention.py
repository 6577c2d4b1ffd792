"""Pallas TPU paged-attention decode kernel (block-sparse KV reads + GQA).

Reference analog: the phi block_multi_head_attention CUDA kernel behind
python/paddle/incubate/nn/functional/block_multihead_attention.py — the
vLLM-style paged attention the serving path decodes through. The XLA
fallback in incubate (gather every sequence's whole KV out of the pools,
dense einsum over the padded horizon) moves O(B * max_blocks * block_size)
HBM bytes per decode step regardless of live lengths; this kernel reads KV
**directly from the physical block pools**, touching only each sequence's
live blocks.

Design (mirrors ops/kernels/flash_attention.py idiom, adapted to paging):

- grid = (batch, kv_head, max_blocks); ``block_tables`` [B, MB] and
  ``seq_lens`` [B] ride in as **scalar-prefetched** SMEM operands
  (``PrefetchScalarGridSpec``), so the K/V BlockSpec index maps translate
  the logical block id of each grid step into the physical pool block to
  DMA — the pools never materialize a gathered [B, MB, H, bs, D] copy.
- block-sparse reads: grid steps past a sequence's last live block clamp
  their index map to the last live block's physical index. Pallas only
  issues a copy when the mapped block CHANGES between steps, so the dead
  tail costs zero HBM traffic; its compute is skipped with ``pl.when``.
- online softmax across the block loop: fp32 (m, l, acc) VMEM scratch
  carried over the innermost grid dimension, initialized at block 0,
  finalized (acc / l) into the output at the last block step.
- what is f32 and what is not (``_mxu_dtype``, one rule for both
  kernels): every matmul accumulates in f32, and the scores, the softmax
  scale, the mask, the running max/normalizer and the finalize are f32.
  The matmuls' OPERANDS are converted to f32 only when they have to be:
  operands stored in the same 16-bit float (bf16 q against a bf16 pool,
  the chunk's K/V against the block it merges into) go to the MXU as
  stored — every bf16 x bf16 product is exact in f32 — and the scale
  then sits on the f32 scores, ``(q . k) * scale``: 1/sqrt(D) is not a
  power of two, so ``q * scale`` has no exact 16-bit form. An f32 q,
  mismatched dtypes and quantized pools (dequantized to f32 in VMEM) take
  the f32 form with the scale on q; Mosaic's default precision rounds
  that form's operands to bf16 for one MXU pass, so the 16-bit form is
  the exact one and costs the same.
- GQA zero-copy: q arrives [B, Hkv, G, D] (G = q-heads per kv head); each
  (batch, kv_head) window attends its whole q-head group against one
  stream of that kv head's blocks.
- a group of one (``G == 1``): a (batch, kv_head) window would hold a
  single query row and a grid step would be all fixed cost, so the call
  takes every kv head of a table entry in one step, grid
  ``(batch, 1, max_blocks)``, the heads' blocks stacked as the keys of one
  matmul pair and a row's scores against the other heads' keys masked
  (``_decode_heads_per_step``, ``_decode_kernel_heads``). Grouped and
  quantized calls keep the one-head step.
- optional fused new-token write: the decode step's fresh K/V (one token
  per sequence) is merged into the last live block IN VMEM — attention
  sees the new token without a prior XLA scatter round-trip through HBM —
  and the merged block is written back to the pools via
  ``input_output_aliases`` (in-place, one [bs, D] block write per
  (batch, kv_head)).

Invalid (-1) table entries: reads clamp to physical block 0 and are either
compute-skipped (dead tail) or masked by ``seq_lens``; fused writes route
to the pool's LAST physical block. Callers whose live write target can be
-1 (the serving engine: freed slots keep stale lens with wiped tables)
must reserve one trailing scratch block in the pool — see
``LLMEngine``'s ``+1`` pool allocation. Callers that guarantee valid
tables everywhere (``generate()``'s arange tables) need no spare block:
the clamp never fires.

On non-TPU backends the same kernel runs under interpret mode (parity
tests); the production CPU path stays the XLA dense-gather fallback in
``incubate.nn.functional.block_multihead_attention`` (see
``paged_attention_enabled``).

``paged_attention_append`` extends the decode kernel from q_len=1 to
q_len=chunk **append attention** — the mixed prefill+decode step of the
fused token-budget scheduler (``LLMEngine(scheduler="fused")``): each
sequence appends ``q_lens[b]`` new positions at ``seq_lens[b]``, every
query row attends causally to its own chunk prefix plus all prior pooled
KV, and the whole chunk's K/V writes back to the pools in-kernel (the
write can span several blocks; each overlapped block is merged in VMEM
and stored through the aliased pool outputs). Its work follows the
scalar-prefetched ``(seq_lens, q_lens)``: rows position-major so live rows
are a prefix, row tiles of a derived size of which only the live and
causally visible run, every kv head of a table entry in one grid step, the
merge for window blocks only (see :func:`paged_attention_append`). Same
gating: TPU fast path behind ``FLAGS_use_paged_attention``, dense append
fallback on CPU.

**Quantized KV pools** (``quant="int8"|"int4"``, the serving engine's
``kv_cache_dtype``): the physical pools store int8 (or int4
nibble-packed on D — see :func:`kv_unpack` for the split-half layout)
with one fp32 scale per (physical block, kv head) riding in
``k_scale``/``v_scale`` [num_blocks, Hkv] arrays. Both kernels
dequantize each block IN VMEM during the online-softmax walk
(``int * scale`` right after the block DMA — HBM traffic shrinks by
2x/4x, and the attention math takes the f32 form of ``_mxu_dtype``:
this file never casts a dequantized block to 16 bits), and the fused write
re-quantizes IN VMEM too: the written block is merged in f32, its new
per-head absmax scale computed in-kernel, and the int payload + scale
store back through aliased outputs — no bf16 block ever round-trips to
HBM. Scale granularity is deliberately per-(block, head): one f32 per
``block_size * head_dim`` ints (<0.1% overhead), small enough that the
whole scale array sits in VMEM for the call (see ``_scale_spec``), fine
enough that one outlier head can't flatten the whole pool.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = np.float32(-1e30)
# index-map literals MUST be i32: python ints become i64 constants under the
# framework's jax_enable_x64 and Mosaic then fails to legalize the index maps
Z = np.int32(0)


#: symmetric integer grid per KV quantization mode. int4 uses [-7, 7]
#: (not -8) so the grid is symmetric and the absmax scale is exact at
#: both ends; the nibble stores the value offset by +8 (range [1, 15]).
KV_QMAX = {"int8": 127.0, "int4": 7.0}


def kv_packed_dim(D, quant):
    """Last (head) dim of the quantized pool storage: D int8 bytes for
    int8, ceil(D/2) bytes for int4 (two nibbles per byte; odd D pads one
    nibble — see :func:`kv_unpack`)."""
    if quant is None:
        return D
    if quant == "int8":
        return D
    if quant == "int4":
        return (D + 1) // 2
    raise ValueError(f"unknown kv quant dtype {quant!r}")


def kv_unpack(vals, quant, D):
    """Quantized storage -> UNSCALED f32 integer grid values, last dim
    packed->D. int4 uses a SPLIT-HALF layout (Mosaic-friendly: no
    per-element interleave): byte j of a row holds element ``j`` in its
    low nibble and element ``Dp + j`` (Dp = ceil(D/2)) in its high
    nibble, each stored offset-8 (q + 8 in [1, 15]); odd D leaves the
    final high nibble as padding, sliced off here."""
    if quant == "int8":
        return vals.astype(jnp.float32)
    b = vals.astype(jnp.int32) & 0xFF
    lo = (b & 0xF) - 8
    hi = ((b >> 4) & 0xF) - 8
    return jnp.concatenate([lo, hi], axis=-1)[..., :D] \
        .astype(jnp.float32)


def kv_pack(q, quant):
    """Integer grid values (f32/int, already clipped to the symmetric
    grid) -> int8 storage, packing nibble pairs for int4 in the
    split-half layout of :func:`kv_unpack`."""
    q = q.astype(jnp.int32)
    if quant == "int8":
        return q.astype(jnp.int8)
    D = q.shape[-1]
    Dp = (D + 1) // 2
    if 2 * Dp != D:
        pad = jnp.zeros(q.shape[:-1] + (1,), q.dtype)
        q = jnp.concatenate([q, pad], axis=-1)
    lo = q[..., :Dp] + 8
    hi = q[..., Dp:] + 8
    return (lo | (hi << 4)).astype(jnp.int8)


def kv_quantize(x, scale, quant):
    """f32 values + (broadcastable) per-block scale -> packed storage:
    round-to-nearest-even onto the symmetric grid, clip, pack."""
    qmax = np.float32(KV_QMAX[quant])
    q = jnp.clip(jnp.round(x / jnp.maximum(scale, np.float32(1e-20))),
                 -qmax, qmax)
    return kv_pack(q, quant)


def kv_block_scale(x, quant, axes, keepdims=False):
    """Absmax scale of one (or a batch of) f32 block(s) over ``axes``:
    THE one copy of the scale rule — the Pallas fused writes, the XLA
    dense fallback, and the engine's prefill scatter all compute the
    block scale through here, so kernel-vs-fallback parity holds to
    rounding."""
    return jnp.max(jnp.abs(x), axis=axes, keepdims=keepdims) \
        / np.float32(KV_QMAX[quant])


def _interpret():
    return jax.default_backend() not in ("tpu",)


def paged_attention_enabled():
    """True when ``block_multihead_attention`` routes decode through this
    kernel: the ``use_paged_attention`` flag (env: FLAGS_use_paged_attention)
    is on AND the backend is a real TPU. Tier-1 CI runs under
    JAX_PLATFORMS=cpu, so CPU always takes the dense-gather fallback —
    deterministic and kernel-free (tests/conftest.py asserts this); the
    kernel itself is still exercised on CPU by the interpret-mode parity
    suite calling :func:`paged_attention_decode` directly."""
    from ...core.flags import flag_value
    return bool(flag_value("use_paged_attention")) and not _interpret()


# ---------------------------------------------------------------------------
# tensor-parallel routing (the multichip serving subsystem)
# ---------------------------------------------------------------------------

#: trace-time TP context: (mesh, axis) while an LLMEngine with a tp mesh is
#: tracing its paged step programs, else None. A pallas_call cannot be
#: auto-partitioned by GSPMD, so the sharded engine must route through the
#: explicit shard_map wrappers below — the engine arms this context around
#: its (trace-triggering) paged dispatches and block_multihead_attention's
#: kernel branch consults it. THREAD-LOCAL: N replica servers (one engine
#: thread each, possibly different meshes/models) may trace concurrently,
#: and replica A's trace must never read replica B's mesh.
_TP_CTX = threading.local()


@contextlib.contextmanager
def paged_tp_context(mesh, axis="tp"):
    """Arm the kernel TP routing for the duration of a (possibly
    trace-triggering) dispatch. Trace-time state, not run-time: once the
    program is compiled the context is a no-op thread-local set/reset."""
    prev = getattr(_TP_CTX, "value", None)
    _TP_CTX.value = (mesh, axis)
    try:
        yield
    finally:
        _TP_CTX.value = prev


def current_paged_tp():
    """The armed (mesh, axis) TP context of THIS thread, or None."""
    return getattr(_TP_CTX, "value", None)


#: trace-time plan of the decode call: the kv heads one grid step serves,
#: while a layer that knows its pools better than their shapes say traces
#: its call, else None (``_decode_heads_per_step`` decides, from the
#: shapes alone). THREAD-LOCAL for the reason ``_TP_CTX`` is.
_DECODE_PLAN = threading.local()


@contextlib.contextmanager
def decode_heads_a_step(hb):
    """Trace the decode calls of the body with ``hb`` kv heads a grid step
    WHATEVER their group (``_decode_kernel_heads`` on ``hb x group`` query
    rows). For a layer whose pool rows hold several narrow heads
    (``models/lfm2_moe.py``: two heads of 64 a row of 128 lanes, so four
    "heads" at a group of eight): at 128 slots the one-head call is
    16,384 grid steps of fixed cost, 6.2 ms where the rows it reads take
    0.3 (PERF.md section 6, PR 48). The shapes alone cannot tell such a
    call from the grouped calls of the cells at a head size of 128, whose
    programs stay what they were; so the layer says it. Trace-time state:
    once the program is compiled it is a thread-local set and reset."""
    prev = getattr(_DECODE_PLAN, "value", None)
    _DECODE_PLAN.value = int(hb)
    try:
        yield
    finally:
        _DECODE_PLAN.value = prev


def _tp_shard_map(fn, mesh, axis, in_specs, out_specs):
    if isinstance(in_specs, list):
        in_specs = tuple(in_specs)
    if isinstance(out_specs, list):
        out_specs = tuple(out_specs)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def paged_attention_decode_tp(q, k_pool, v_pool, block_tables, seq_lens,
                              mesh, axis="tp", scale=None, new_k=None,
                              new_v=None, k_scale=None, v_scale=None,
                              quant=None):
    """:func:`paged_attention_decode` sharded over a tensor-parallel mesh
    axis: kv-heads (pool dim 1) split across ``axis`` and each shard runs
    the unmodified kernel on its local head group — the grid's
    (batch, kv_head, max_blocks) shape makes kv-heads the natural shard
    dim, so per-shard programs are byte-identical to the single-chip
    kernel at Hkv/ntp heads. Block tables and seq_lens ride in REPLICATED
    (the allocator is host-global); q's head dim shards alongside
    (kv-head-major GQA layout: q heads [h*G, (h+1)*G) follow kv head h,
    so an even kv-head split carries its q groups with it). No collective
    is issued — attention output heads stay sharded and the caller's
    o_proj (row-parallel) reduces them. Quantized pools (``quant``)
    thread their per-(block, head) scale arrays with the SAME kv-head
    sharding (scale dim 1 == pool dim 1), so each shard quantizes its
    own heads — the per-head absmax rule makes the sharded result
    bit-identical to single-chip."""
    from jax.sharding import PartitionSpec as P

    write_new = new_k is not None
    q_spec = P(None, axis, None)
    pool_spec = P(None, axis, None, None)
    scale_spec = P(None, axis)
    in_specs = [q_spec, pool_spec, pool_spec, P(), P()]
    out_specs = [q_spec, pool_spec, pool_spec] if write_new else q_spec
    args = [q, k_pool, v_pool, block_tables, seq_lens]
    if quant:
        in_specs += [scale_spec, scale_spec]
        args += [k_scale, v_scale]
        if write_new:
            out_specs += [scale_spec, scale_spec]
    if write_new:
        in_specs += [P(None, axis, None), P(None, axis, None)]
        args += [new_k, new_v]

    def body(q_s, k_s, v_s, tables, lens, *rest):
        if quant:
            ks_s, vs_s, *rest = rest
        else:
            ks_s = vs_s = None
        nk_s, nv_s = rest if rest else (None, None)
        return paged_attention_decode(q_s, k_s, v_s, tables, lens,
                                      scale=scale, new_k=nk_s, new_v=nv_s,
                                      k_scale=ks_s, v_scale=vs_s,
                                      quant=quant)

    return _tp_shard_map(body, mesh, axis, in_specs, out_specs)(*args)


def paged_attention_append_tp(q, k_pool, v_pool, block_tables, seq_lens,
                              q_lens, new_k, new_v, mesh, axis="tp",
                              scale=None, k_scale=None, v_scale=None,
                              quant=None, start=None, width=None):
    """:func:`paged_attention_append` sharded over a tensor-parallel mesh
    axis — the mixed prefill+decode step's kernel under the TP serving
    engine. Same layout contract as the decode wrapper: pools/new-KV/q
    (and, quantized, the per-(block, head) scale arrays) shard on their
    head dims, tables/seq_lens/q_lens (and the packed entry's ``start``)
    replicated, output heads stay sharded for the row-parallel o_proj to
    reduce."""
    from jax.sharding import PartitionSpec as P

    pool_spec = P(None, axis, None, None)
    scale_spec = P(None, axis)
    # [B, S, Hq, D], or the packed entry's [T, Hq, D]
    q_spec = P(*[None] * (q.ndim - 2), axis, None)
    starts = jnp.zeros_like(q_lens) if start is None else start
    in_specs = [q_spec, pool_spec, pool_spec, P(), P(), P(), P()]
    out_specs = [q_spec, pool_spec, pool_spec]
    args = [q, k_pool, v_pool, block_tables, seq_lens, q_lens, starts]
    if quant:
        in_specs += [scale_spec, scale_spec]
        out_specs += [scale_spec, scale_spec]
        args += [k_scale, v_scale]
    in_specs += [q_spec, q_spec]                # new_k/new_v, as q
    args += [new_k, new_v]

    def body(q_s, k_s, v_s, tables, lens, qlens, starts_s, *rest):
        if quant:
            ks_s, vs_s, nk_s, nv_s = rest
        else:
            ks_s = vs_s = None
            nk_s, nv_s = rest
        return paged_attention_append(
            q_s, k_s, v_s, tables, lens, qlens, nk_s, nv_s, scale=scale,
            k_scale=ks_s, v_scale=vs_s, quant=quant,
            start=None if start is None else starts_s, width=width)

    return _tp_shard_map(body, mesh, axis, in_specs, out_specs)(*args)


def _last_live(lens_ref, b, bs, mb):
    """Logical index of the block holding position ``lens[b]`` (where the
    decode step's new token lands), clamped into the table. lax.div keeps
    i32 under x64 (a plain ``//`` promotes and breaks Mosaic's lowering)."""
    return jnp.minimum(jax.lax.div(lens_ref[b], np.int32(bs)),
                       np.int32(mb - 1))


def _q_index_map(b, h, j, tables_ref, lens_ref):
    return (b, h, Z, Z)


def _kv_index_map(bs, mb):
    def im(b, h, j, tables_ref, lens_ref):
        j_last = _last_live(lens_ref, b, bs, mb)
        jj = jnp.minimum(j, j_last)          # dead tail re-maps to last live
        phys = tables_ref[b, jj]
        return (jnp.maximum(phys, Z), h, Z, Z)   # -1 -> block 0 (masked read)
    return im


def _new_kv_index_map(b, h, j, tables_ref, lens_ref):
    return (b, h, Z, Z)


def _pool_out_index_map(bs, mb, nb):
    """Fused-write destination: the last live block of sequence b. A -1
    (unallocated) target must not clobber a real block — route it to the
    pool's trailing scratch block instead (the analog of the XLA path's
    out-of-range ``mode="drop"`` scatter)."""
    def im(b, h, j, tables_ref, lens_ref):
        phys = tables_ref[b, _last_live(lens_ref, b, bs, mb)]
        return (jnp.where(phys < Z, np.int32(nb - 1), phys), h, Z, Z)
    return im


def _scale_read(s_ref, phys, h):
    """One (block, head) scale as a [1, 1] tile off the VMEM-resident
    [num_blocks, Hkv] scale array: a dynamic one-row load, then a lane
    select on the head (Mosaic has no dynamic lane index)."""
    row = s_ref[pl.ds(phys, 1), :]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == h, row, np.float32(0.0)), axis=1,
                   keepdims=True)


def _scale_write(s_ref, phys, h, val):
    """Store one (block, head) scale: read-modify-write of the block's
    row (the other heads' lanes keep their values)."""
    row = s_ref[pl.ds(phys, 1), :]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    s_ref[pl.ds(phys, 1), :] = jnp.where(lane == h, val, row)


def _scale_spec(nb, hkv):
    """Scale arrays ride WHOLE in VMEM (one block = the array, fetched
    once, stored once): a (1, 1) window over [num_blocks, Hkv] breaks
    Mosaic's block-shape rule, and the array is tiny next to the pools
    (one f32 per block_size * head_dim ints)."""
    return pl.BlockSpec((nb, hkv), lambda b, h, j, *refs: (Z, Z))


def _mxu_dtype(lhs, rhs, quant):
    """THE operand rule of this file's matmuls: the dtype both operands of
    a product go to the MXU in. Operands that are stored in the same
    16-bit float (bf16 ``q`` against a bf16 pool, the chunk's K/V against
    the block it merges into) go as they are stored: every bf16 x bf16
    product is exact in f32 and the MXU accumulates in f32, so nothing is
    rounded on the way. Anything else is converted to f32 first (the f32
    form): an f32 ``q``, mismatched dtypes, and a quantized pool, whose
    block is dequantized to f32 in VMEM. What the f32 form keeps of its
    32 bits is the compiler's to say: at default precision Mosaic rounds
    f32 operands to bf16 and runs ONE pass (read on a v5e, PERF.md
    section 6, PR 31: the two forms take the same time, and the f32 form
    of a bf16 ``q * scale`` gave ``bf16(q * scale) . k``, 2^-9 off).
    Read at trace time from what the code can observe; no option sets it.
    The kernels and the tests call the same rule."""
    lhs, rhs = jnp.dtype(lhs), jnp.dtype(rhs)
    if not quant and lhs == rhs and lhs.itemsize == 2 \
            and jnp.issubdtype(lhs, jnp.floating):
        return lhs
    return jnp.dtype(jnp.float32)


def _scores(q, k, scale, dt):
    """f32 scaled scores ``[n, bs]`` of query rows ``q`` [n, D] against a
    block ``k`` [bs, D] that its caller already holds in ``dt`` =
    :func:`_mxu_dtype` of the two. In the f32 form the scale goes on
    ``q``, as it always did. In the 16-bit form it goes on the f32
    scores: ``1/sqrt(D)`` is not a power of two at most head sizes, so
    ``q * scale`` has no exact 16-bit form (and the f32 form's is rounded
    to one on its way into the MXU), where ``(q . k) * scale`` is the
    exact product to f32 rounding."""
    nt = (((1,), (1,)), ((), ()))
    if dt == jnp.float32:
        return jax.lax.dot_general(
            q.astype(jnp.float32) * np.float32(scale), k, nt,
            preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        q, k, nt, preferred_element_type=jnp.float32) * np.float32(scale)


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest, scale,
                   bs, mb, nb, write_new, quant=None, d_head=None):
    if quant:
        if write_new:
            (ks_ref, vs_ref, nk_ref, nv_ref, o_ref, ko_ref, vo_ref,
             kso_ref, vso_ref, m_ref, l_ref, acc_ref) = rest
        else:
            ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    elif write_new:
        nk_ref, nv_ref, o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    bs_i = np.int32(bs)
    L = lens_ref[b]
    j_last = _last_live(lens_ref, b, bs, mb)
    jj = jnp.minimum(j, j_last)
    phys = tables_ref[b, jj]
    # dead tail (past the live blocks) and unallocated (-1) entries skip
    # compute; their clamped reads are either unused or masked below
    live = (j <= j_last) & (phys >= Z)

    @pl.when(j == Z)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if quant and write_new:
        # the aliased scale outputs are separate VMEM buffers: seed them
        # from the inputs once, then read AND write the outputs only
        @pl.when((b == Z) & (h == Z) & (j == Z))
        def _seed_scales():
            kso_ref[...] = ks_ref[...]
            vso_ref[...] = vs_ref[...]
        ks_ref, vs_ref = kso_ref, vso_ref

    k_blk = k_ref[0, 0]                                   # [bs, D]
    v_blk = v_ref[0, 0]
    if quant:
        # in-VMEM dequant right after the (2x/4x smaller) block DMA: the
        # attention math below takes the f32 form (``_mxu_dtype``)
        phys_r = jnp.maximum(phys, Z)
        k_blk = kv_unpack(k_blk, quant, d_head) * _scale_read(ks_ref,
                                                              phys_r, h)
        v_blk = kv_unpack(v_blk, quant, d_head) * _scale_read(vs_ref,
                                                              phys_r, h)
    if write_new:
        # merge the new token's K/V into the last live block in VMEM: the
        # attention below sees it this step, and the merged block writes
        # back through the aliased pool outputs (in-place)
        slot = L - j_last * bs_i
        row = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        sel = (row == slot) & (j == j_last)
        k_blk = jnp.where(sel, nk_ref[0, 0].astype(k_blk.dtype), k_blk)
        v_blk = jnp.where(sel, nv_ref[0, 0].astype(v_blk.dtype), v_blk)
        if quant:
            # in-VMEM re-quantize of the merged block: new per-head
            # absmax scale, int payload + scale back through the aliased
            # outputs — no dequantized block reaches HBM. DEAD ROWS
            # (positions past the new token, i.e. stale content of a
            # reused freed block) are ZEROED first: attention always
            # masks them, but an unmasked absmax would let a dirty
            # block's garbage inflate the scale and crush the live
            # token's resolution — quantized output must not depend on
            # pool-reuse history. Attention then reads the
            # ROUND-TRIPPED values (what the pool stores), so this
            # step's logits equal a later re-read of the same cache —
            # and match the dense fallback bit-for-bit.
            dead = (j == j_last) & (row > slot)
            k_blk = jnp.where(dead, np.float32(0.0), k_blk)
            v_blk = jnp.where(dead, np.float32(0.0), v_blk)
            ks_new = kv_block_scale(k_blk, quant, axes=(0, 1),
                                    keepdims=True)
            vs_new = kv_block_scale(v_blk, quant, axes=(0, 1),
                                    keepdims=True)
            kq_new = kv_quantize(k_blk, ks_new, quant)
            vq_new = kv_quantize(v_blk, vs_new, quant)
            k_blk = jnp.where(j == j_last,
                              kv_unpack(kq_new, quant, d_head) * ks_new,
                              k_blk)
            v_blk = jnp.where(j == j_last,
                              kv_unpack(vq_new, quant, d_head) * vs_new,
                              v_blk)

        @pl.when(j == j_last)
        def _store_block():
            if quant:
                # same destination rule as the pool out index map
                dst = jnp.where(phys < Z, np.int32(nb - 1), phys)
                _scale_write(kso_ref, dst, h, ks_new)
                _scale_write(vso_ref, dst, h, vs_new)
                ko_ref[0, 0] = kq_new
                vo_ref[0, 0] = vq_new
            else:
                ko_ref[0, 0] = k_blk.astype(ko_ref.dtype)
                vo_ref[0, 0] = v_blk.astype(vo_ref.dtype)

    g = q_ref.shape[2]

    @pl.when(live)
    def _attend():
        dt = _mxu_dtype(q_ref.dtype, k_blk.dtype, quant)
        s = _scores(q_ref[0, 0], k_blk.astype(dt), scale, dt)     # [G, bs]
        pos = jj * bs_i + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        s = jnp.where(pos <= L, s, NEG_INF)          # include new token at L
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == np.int32(mb - 1))
    def _finalize():
        l = jnp.maximum(l_ref[...], np.float32(1e-30))
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


#: VMEM a decode call at a group of one plans its pool blocks into, in and
#: out, double-buffered (``_decode_heads_per_step``)
_DECODE_VMEM_BUDGET = 12 << 20


def _decode_heads_per_step(hkv, g, bs, dk, pool_isz, quant):
    """KV heads one grid step of the decode call serves. With a group of
    one (every query head has its own kv head) a (slot, head, entry) step
    moves one ``[bs, D]`` block of K and of V for a single query row, and
    the step's fixed cost is all of its time (0.39 us a step, 3 % of the
    kernel's roofline at 16 heads; PERF.md section 6, PR 34): such a call
    takes every head of a table entry in one step, the largest divisor of
    ``hkv`` whose double-buffered blocks in and out fit
    ``_DECODE_VMEM_BUDGET`` (they lie together in the pool: one DMA).
    A grouped or quantized call is left at one head a step, the program
    ``doc_batch`` runs, and so is a block that is not whole native tiles
    (8 rows of 4 bytes, 16 of 2), whose heads do not stack for free.
    Static shapes only: no option sets it."""
    if g != 1 or quant or bs % (32 // pool_isz):
        return 1
    for hb in range(hkv, 1, -1):
        if hkv % hb == 0 and \
                2 * 2 * 2 * hb * bs * dk * pool_isz <= _DECODE_VMEM_BUDGET:
            return hb
    return 1


def _decode_kernel_heads(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                         scale, bs, mb, write_new):
    """``_decode_kernel`` for ``hb`` kv heads a grid step (16-bit or f32
    pools, not quantized; a group of one by ``_decode_heads_per_step``'s
    rule, any group under ``decode_heads_a_step``: the ``hb x group`` query
    rows of the heads lie together, row ``r`` reads head ``r // group``):
    the heads of a table entry are
    attended in ONE pair of matmuls, their blocks stacked as
    ``[hb * bs, D]`` keys, the heads' query rows against all of them, and
    every score of a row against another head's keys masked like a dead
    position, so that its ``p`` is exactly 0 and the sum over the stacked
    values adds exact zeros to the head's own: a head reads what the
    one-head kernel gives it, bit for bit on a v5e. The MXU does ``hb``
    times the products the heads need, which it has room for (a decode
    row fills 1 of its 128 rows), and is loaded ``hb`` times less often:
    16 heads in one pair read 93 us a call where one head a step read
    657, and pairs of 8 / 4 / 2 / 1 heads 95 / 115 / 113 / 162 (PERF.md
    section 6, PR 34)."""
    if write_new:
        nk_ref, nv_ref, o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    bs_i = np.int32(bs)
    L = lens_ref[b]
    j_last = _last_live(lens_ref, b, bs, mb)
    jj = jnp.minimum(j, j_last)
    phys = tables_ref[b, jj]
    live = (j <= j_last) & (phys >= Z)
    hb, _, d = k_ref.shape[1:]

    @pl.when(j == Z)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_blk = k_ref[0]                                      # [hb, bs, D]
    v_blk = v_ref[0]
    if write_new:
        slot = L - j_last * bs_i
        row = jax.lax.broadcasted_iota(jnp.int32, k_blk.shape, 1)
        sel = (row == slot) & (j == j_last)
        k_blk = jnp.where(sel, nk_ref[0].astype(k_blk.dtype), k_blk)
        v_blk = jnp.where(sel, nv_ref[0].astype(v_blk.dtype), v_blk)

        @pl.when(j == j_last)
        def _store_block():
            ko_ref[0] = k_blk
            vo_ref[0] = v_blk

    @pl.when(live)
    def _attend():
        k2 = k_blk.reshape(hb * bs, d)
        v2 = v_blk.reshape(hb * bs, d)
        dt = _mxu_dtype(q_ref.dtype, k2.dtype, None)
        s = _scores(q_ref[0, 0], k2.astype(dt), scale, dt)   # [hb*g, hb*bs]
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        g = s.shape[0] // hb
        if g > 1:
            head = _div_i32(head, g)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        within = col - head * bs_i           # the key's place in ITS block
        seen = (within >= Z) & (within < bs_i) & (jj * bs_i + within <= L)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v2.dtype), v2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == np.int32(mb - 1))
    def _finalize():
        l = jnp.maximum(l_ref[...], np.float32(1e-30))
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_decode(q, k_pool, v_pool, block_tables, seq_lens,
                           scale=None, new_k=None, new_v=None,
                           k_scale=None, v_scale=None, quant=None):
    """One decode step of paged attention, straight off the block pools.

    q: [B, Hq, D] (this step's query, one token per sequence);
    k_pool/v_pool: [num_blocks, Hkv, block_size, D] physical pools;
    block_tables: [B, max_blocks] logical->physical (-1 = unallocated);
    seq_lens: [B] tokens already cached — the new token sits at position
    ``seq_lens[b]`` and attention covers positions <= seq_lens[b].

    Hq must be a multiple of Hkv (GQA: each kv head serves Hq/Hkv q heads).

    new_k/new_v ([B, Hkv, D], both or neither): fuse the new token's K/V
    write into the kernel — returns (out, k_pool, v_pool) with the pools
    updated in place (aliased). Without them the caller must have already
    scattered the new token into the pools; returns out only.
    Out: [B, Hq, D] in q.dtype. Inside, accumulation, scores, scale and
    softmax are f32; ``QK^T`` takes q and the block as stored when both
    are the same 16-bit float (:func:`_mxu_dtype`), with the scale on
    the f32 scores, and converts both to f32 (scale on q) otherwise.

    ``quant="int8"|"int4"`` + ``k_scale``/``v_scale`` [num_blocks, Hkv]
    fp32: the pools are QUANTIZED storage (int4 nibble-packed on D, so
    the pool's last dim is :func:`kv_packed_dim`). Each block dequantizes
    in VMEM during the walk; the fused write re-quantizes the merged
    block in VMEM (new per-head absmax scale computed in-kernel) and the
    scale arrays return updated alongside the pools:
    ``(out, k_pool, v_pool, k_scale, v_scale)``.
    """
    B, Hq, D = q.shape
    NB, Hkv, BS, Dk = k_pool.shape
    if quant:
        assert k_scale is not None and v_scale is not None
        assert Dk == kv_packed_dim(D, quant), (q.shape, k_pool.shape, quant)
    else:
        assert k_scale is None and v_scale is None
        assert D == Dk, (q.shape, k_pool.shape)
    assert Hq % Hkv == 0, f"GQA needs Hq % Hkv == 0, got {Hq=} {Hkv=}"
    G = Hq // Hkv
    MB = block_tables.shape[1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    write_new = new_k is not None
    assert (new_v is not None) == write_new

    hb = getattr(_DECODE_PLAN, "value", None)
    if hb is None:
        hb = _decode_heads_per_step(Hkv, G, BS, Dk, k_pool.dtype.itemsize,
                                    quant)
    elif quant or Hkv % hb:
        raise ValueError(f"decode_heads_a_step({hb}): {Hkv} kv heads, "
                         f"quant={quant!r}")
    if hb > 1:
        return _decode_heads_call(q, k_pool, v_pool, block_tables, seq_lens,
                                  scale, new_k, new_v, hb)

    q4 = q.reshape(B, Hkv, G, D)
    tables = block_tables.astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)

    in_specs = [
        pl.BlockSpec((1, 1, G, D), _q_index_map),
        pl.BlockSpec((1, 1, BS, Dk), _kv_index_map(BS, MB)),
        pl.BlockSpec((1, 1, BS, Dk), _kv_index_map(BS, MB)),
    ]
    out_specs = [pl.BlockSpec((1, 1, G, D), _q_index_map)]
    out_shape = [jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype)]
    inputs = [tables, lens, q4, k_pool, v_pool]
    io_aliases = {}
    if quant:
        in_specs += [_scale_spec(NB, Hkv)] * 2
        inputs += [k_scale.astype(jnp.float32),
                   v_scale.astype(jnp.float32)]
    if write_new:
        # new-token K/V arrives in the model dtype regardless of pool
        # quantization — the kernel quantizes in VMEM
        nk_dt = k_pool.dtype if not quant else new_k.dtype
        # [B, Hkv, 1, D] with a (1, D) trailing window: Mosaic wants the
        # last two block dims tile-aligned or equal to the array's, and a
        # second-minor block of 1 against Hkv is neither
        in_specs += [pl.BlockSpec((1, 1, 1, D), _new_kv_index_map),
                     pl.BlockSpec((1, 1, 1, D), _new_kv_index_map)]
        inputs += [new_k.reshape(B, Hkv, 1, D).astype(nk_dt),
                   new_v.reshape(B, Hkv, 1, D).astype(nk_dt)]
        pool_spec = pl.BlockSpec((1, 1, BS, Dk),
                                 _pool_out_index_map(BS, MB, NB))
        out_specs += [pool_spec, pool_spec]
        out_shape += [jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                      jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
        # flat input indices INCLUDE the scalar-prefetch operands
        io_aliases = {3: 1, 4: 2}
        if quant:
            out_specs += [_scale_spec(NB, Hkv)] * 2
            out_shape += [jax.ShapeDtypeStruct((NB, Hkv), jnp.float32),
                          jax.ShapeDtypeStruct((NB, Hkv), jnp.float32)]
            io_aliases = {3: 1, 4: 2, 5: 3, 6: 4}

    kernel = functools.partial(_decode_kernel, scale=scale, bs=BS, mb=MB,
                               nb=NB, write_new=write_new, quant=quant,
                               d_head=D)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, MB),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),    # running max m
                pltpu.VMEM((G, 1), jnp.float32),    # running normalizer l
                pltpu.VMEM((G, D), jnp.float32),    # output accumulator
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=io_aliases,
        # every dim sequential: scratch carries over blocks, and the fused
        # write's clamped scratch-block destinations may collide across
        # batch windows — megacore parallelism would race them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="paged_attention_decode",
        interpret=_interpret(),
    )(*inputs)
    out = outs[0].reshape(B, Hq, D)
    if write_new:
        if quant:
            return out, outs[1], outs[2], outs[3], outs[4]
        return out, outs[1], outs[2]
    return out


def _decode_heads_call(q, k_pool, v_pool, block_tables, seq_lens, scale,
                       new_k, new_v, hb):
    """The decode call with ``hb`` kv heads a grid step
    (``_decode_kernel_heads``): grid ``(B, Hkv / hb, MB)``, the index maps
    of the one-head call with a block of ``hb`` heads, and of their ``hb x
    group`` query rows, where it has one."""
    B, Hq, D = q.shape
    NB, H, BS, Dk = k_pool.shape
    rows = hb * (Hq // H)
    MB = block_tables.shape[1]
    tables = block_tables.astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)
    write_new = new_k is not None

    q_spec = pl.BlockSpec((1, 1, rows, D), _q_index_map)
    kv_spec = pl.BlockSpec((1, hb, BS, Dk), _kv_index_map(BS, MB))
    in_specs = [q_spec, kv_spec, kv_spec]
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B, H // hb, rows, D), q.dtype)]
    inputs = [tables, lens, q.reshape(B, H // hb, rows, D), k_pool, v_pool]
    io_aliases = {}
    if write_new:
        new_spec = pl.BlockSpec((1, hb, 1, D), _new_kv_index_map)
        pool_spec = pl.BlockSpec((1, hb, BS, Dk),
                                 _pool_out_index_map(BS, MB, NB))
        in_specs += [new_spec, new_spec]
        inputs += [new_k.reshape(B, H, 1, D).astype(k_pool.dtype),
                   new_v.reshape(B, H, 1, D).astype(k_pool.dtype)]
        out_specs += [pool_spec, pool_spec]
        out_shape += [jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                      jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
        io_aliases = {3: 1, 4: 2}
    outs = pl.pallas_call(
        functools.partial(_decode_kernel_heads, scale=scale, bs=BS, mb=MB,
                          write_new=write_new),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb, MB),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=io_aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_DECODE_VMEM_BUDGET + (8 << 20)),
        name="paged_attention_decode",
        interpret=_interpret(),
    )(*inputs)
    out = outs[0].reshape(B, Hq, D)
    return (out, outs[1], outs[2]) if write_new else out


# ---------------------------------------------------------------------------
# append attention: q_len = chunk (the fused prefill+decode mixed step)
# ---------------------------------------------------------------------------

#: most query rows in one row tile. An update (one tile against one block
#: for one kv head) is a chain of two small matmuls with a softmax between
#: them, whose latency a v5e pays whatever the rows, so tiles want to be
#: tall. One chain at a time, 256 ran a full chunk fastest (PERF.md
#: section 6, PR 26: 128 / 256 / 512 rows = 8.1 / 5.4 / 7.1 ms a call of
#: eight chunks). With the heads interleaved (``_HEADS_INTERLEAVED``) it
#: is 4.57 / 4.05 / 3.28 (PR 31), and 512 is left to the change that
#: restates ``append_tile_steps``' counters, which count these tiles
_ROW_TILE_MAX = 256
#: rows a tile runs when its live rows end inside them (see _row_subtile)
_ROW_SUBTILE = 32
#: kv heads whose updates of one row tile share a basic block. The heads'
#: chains (QK^T -> max -> exp -> P.V) are independent: one after the other
#: with constant head indices and no loop between them (a ``fori_loop``
#: unrolled whole), the scheduler overlaps their latencies and spreads
#: their matmuls over the core's four MXUs. A loop over the heads ran
#: them one at a time: eight decode rows 1.13 -> 0.51 ms a call, eight
#: chunks 5.38 -> 4.05 at the same tile (PERF.md section 6, PR 31; 4 heads
#: a block: 0.66). A step that serves more heads than this runs them in
#: groups of it. Unrolled by the loop and not in Python, so that the body
#: is traced once: the kernel's lowering is Python time in every
#: process's set-up, compile cache or not (1.9 s against 0.3 in
#: ``doc_batch``'s).
_HEADS_INTERLEAVED = 8
#: VMEM the append call plans its per-step buffers into (a v5e core has
#: 128 MiB; the compiler's own default limit for a kernel is 16 MiB and
#: the call raises it to what it planned plus 16 MiB, see
#: ``_append_vmem_bytes``). It was 40 MiB while no cell's heads needed
#: more; a group of 8 over a 512-row chunk (4,096 rows a kv head, 10.6 MiB
#: a head) then ran 2 kv heads a step, two chains interleaved where the
#: tile is built for eight, and took 36.5 ms a call where all 8 heads a
#: step (85 MiB planned) take 18.3, bit for bit the same output (read on
#: the v5e at 16 x 400 blocks, PERF.md section 6, PR 41)
_APPEND_VMEM_BUDGET = 92 << 20
#: rows of a sublane tile of the widest packing (bf16; f32's is 8): what a
#: dynamic row offset into a VMEM block has to be a multiple of
_SUBLANE = 16


def _row_tile(g, s):
    """Rows of one query row tile of a slot's ``g * s`` rows: the largest
    multiple of 16 (a packed bf16 sublane tile) up to ``_ROW_TILE_MAX``
    that divides them, or all of them when they are few or no such size
    divides them. Static shapes only: no option sets it."""
    rows = g * s
    if rows <= _ROW_TILE_MAX:
        return rows
    for tr in range(_ROW_TILE_MAX - _ROW_TILE_MAX % 16, 15, -16):
        if rows % tr == 0:
            return tr
    return rows


def _row_subtile(tr):
    """Rows a tile runs when its live rows end inside them: one native
    (32, 128) tile, the tallest among the pool dtypes' — a decode row's
    ``g`` rows or a verify window's fit it at the usual group sizes —
    or the whole tile where that does not divide it."""
    return _ROW_SUBTILE if tr % _ROW_SUBTILE == 0 else tr


def _append_vmem_bytes(hb, g, s, d, bs, dk, q_isz, pool_isz, new_isz,
                       resident=None):
    """VMEM one grid step of the append call holds with ``hb`` kv heads a
    step: q and out blocks, pool blocks in and out and the chunk's K/V
    (each double-buffered by the pipeline), and the f32 scratch (m and l
    are one lane wide and pad to 128). The per-slot plan's q and out
    blocks are one slot's ``g * s`` rows and its K/V block the slot's
    ``s``; the packed plan's are a head's rows of EVERY slot,
    ``resident`` = (rows of q, rows of K/V), and its scratch is a row
    tile longer (a slot's first row lies anywhere in its first tile)."""
    rows, new_rows = resident or (g * s, s)
    q_out = 2 * 2 * hb * rows * d * q_isz
    pools = 2 * 2 * 2 * hb * bs * dk * pool_isz
    new = 2 * 2 * hb * new_rows * d * new_isz
    scratch = hb * _scratch_rows(g, s, bool(resident)) * (d + 2 * 128) * 4
    return q_out + pools + new + scratch


def _packed_row_tile(g, s):
    """Rows of a row tile of the packed plan: :func:`_row_tile`'s and one
    sublane tile more. A slot's rows lie up to ``_SUBLANE - 1`` rows into
    their first tile, and with the longer tile they still fit the
    ``g * s // _row_tile`` tiles they fill when they start one: a tile's
    update is a chain whose latency the chip pays whatever the rows, so
    an extra tile an entry for 15 rows would cost half a full one (read
    on the v5e, PERF.md section 6, PR 46)."""
    return _row_tile(g, s) + _SUBLANE


def _scratch_rows(g, s, packed):
    """Rows of a slot's f32 accumulator: its ``g * s``, or on the packed
    axis as many tiles of :func:`_packed_row_tile`."""
    if not packed:
        return g * s
    return g * s // _row_tile(g, s) * _packed_row_tile(g, s)


def _heads_per_step(hkv, *shape):
    """KV heads one grid step serves: every head of a block when their
    buffers fit ``_APPEND_VMEM_BUDGET`` (one DMA brings the block's heads,
    which lie together in the pool, and the grid is ``hkv`` times
    shorter), else the largest divisor of ``hkv`` that fits."""
    for hb in range(hkv, 1, -1):
        if hkv % hb == 0 and \
                _append_vmem_bytes(hb, *shape) <= _APPEND_VMEM_BUDGET:
            return hb
    return 1


def _div_i32(a, b):
    # lax.div keeps i32 under x64 (see _last_live)
    return jax.lax.div(a, np.int32(b))


def _tile_span(L, QL, jj, g, bs, tr, xp, div, off=0):
    """THE skip rule of the append kernel: the row tiles ``[t_lo, t_end)``
    of a slot that table entry ``jj`` has work for. Rows are
    position-major (row ``i * g + q_head``), so the ``QL * g`` live rows
    are a prefix and ``t_end`` tiles cover it; row ``r`` sees kv position
    ``p`` iff ``(p - L) * g <= r``, so a block that starts ``d`` positions
    into the chunk is wholly masked for the tiles before row ``d * g``'s.
    ``off``: the rows before the slot's first in its first tile (the
    packed entry, whose tiles lie on the packed axis' own grid; 0 where a
    slot's rows start a tile): every row moves up by it. The kernel
    (traced i32 scalars) and :func:`append_tile_steps` (numpy arrays)
    both call this, so the counter cannot drift from the rule."""
    t_end = div(QL * g + off + (tr - 1), tr)
    t_lo = div(xp.maximum(jj * bs - L, 0) * g + off, tr)
    return t_lo, t_end


def _tile_offset(row):
    """Rows between ``row`` (a slot's first on the packed axis: ``start *
    g`` of a head's q rows, ``start`` of the chunk's K/V) and the tile
    boundary at or below it: tiles start on multiples of
    :data:`_SUBLANE`, a power of two (one ``and``: the kernel asks at
    every grid step)."""
    return row & np.int32(_SUBLANE - 1)


def append_tile_steps(seq_lens, q_lens, group, chunk, block_size,
                      max_blocks, start=None):
    """``(run, grid)`` of one :func:`paged_attention_append` call, per kv
    head: ``run`` = (row tile, table entry) pairs the kernel computes —
    for each slot with ``q_lens > 0``, over the entries up to the block of
    its window's last position, the tiles of :func:`_tile_span` — and
    ``grid`` = every row tile against every entry of every slot, which is
    what a kernel blind to ``(seq_lens, q_lens)`` would compute. ``start``
    [B] (the packed entry: a slot's first packed row): the tiles are the
    packed axis' own, :func:`_packed_row_tile` rows each from the tile
    boundary at or below the slot's first row, never more of them than
    the slot's rows fill when they start a tile. ``grid`` is the same
    NUMBER either way, ``group * chunk // _row_tile`` tiles a (slot,
    entry): what a blind kernel of the same plan would run, so its tiles
    are the plan's too (the packed plan's are ``_SUBLANE`` rows taller
    than the per-slot plan's, and as many). Host side, numpy; a ``-1`` entry inside a live
    context (the kernel skips it) is not looked for: the scheduler
    allocates before it grants."""
    L = np.asarray(seq_lens, np.int64).reshape(-1, 1)
    QL = np.minimum(np.asarray(q_lens, np.int64), chunk).reshape(-1, 1)
    tr = tile = _row_tile(group, chunk)
    off = 0
    if start is not None:
        tile = _packed_row_tile(group, chunk)
        off = _tile_offset(np.asarray(start, np.int64).reshape(-1, 1) * group)
    j_last = np.minimum((L + np.maximum(QL - 1, 0)) // block_size,
                        max_blocks - 1)
    jj = np.arange(max_blocks, dtype=np.int64)[None, :]
    t_lo, t_end = _tile_span(L, QL, jj, group, block_size, tile, np,
                             np.floor_divide, off)
    walked = (jj <= j_last) & (QL > 0)
    run = int(np.sum(np.where(walked, t_end - t_lo, 0)))
    return run, int(L.size * max_blocks * (group * chunk // tr))


def _apd_blk(lens_ref, qlens_ref, b, bs, mb, last):
    """Block index of the append window's first (``last=False``) or last
    (``last=True``) written position, clamped into the table. q_lens == 0
    degenerates both to the block holding ``lens`` (nothing is written;
    that block is stored back unchanged so the aliased out window never
    copies out undefined VMEM)."""
    pos = lens_ref[b] + (jnp.maximum(qlens_ref[b] - 1, 0) if last else 0)
    return jnp.minimum(jax.lax.div(pos, np.int32(bs)), np.int32(mb - 1))


def _apd_walk(lens_ref, qlens_ref, b, j, bs, mb):
    """Table entry grid step ``j`` of slot ``b`` reads: its own up to the
    window's last block, that block again past it (the mapped block does
    not change, so the dead tail issues no copy) — and for an idle slot
    (q_lens 0) the boundary block at every step: it has one block to
    carry through and no context to walk."""
    j_last = _apd_blk(lens_ref, qlens_ref, b, bs, mb, True)
    return jnp.where(qlens_ref[b] > Z, jnp.minimum(j, j_last), j_last)


def _apd_q_index_map(b, h, j, *refs):
    return (b, h, Z, Z)


def _apd_rows_index_map(b, h, j, *refs):
    # the packed plan: a head group's rows of every slot, one block for
    # the whole of its walk, fetched and written once
    return (Z, h, Z, Z)


def _apd_kv_index_map(bs, mb):
    def im(b, h, j, tables_ref, lens_ref, qlens_ref, start_ref):
        jj = _apd_walk(lens_ref, qlens_ref, b, j, bs, mb)
        return (jnp.maximum(tables_ref[b, jj], Z), h, Z, Z)
    return im


def _apd_pool_out_index_map(bs, mb, nb):
    """Fused-write destinations: the blocks overlapping the append window
    [lens, lens+q_lens). Steps outside the window pin to its boundary
    blocks, so their mapping never changes and no copy is issued — only
    the overlapped blocks (each merged + stored in the kernel) pay a
    write. -1 targets (a freed slot's wiped table row) route to the
    pool's trailing scratch block, as in the decode kernel."""
    def im(b, h, j, tables_ref, lens_ref, qlens_ref, start_ref):
        w0 = _apd_blk(lens_ref, qlens_ref, b, bs, mb, False)
        w1 = _apd_blk(lens_ref, qlens_ref, b, bs, mb, True)
        phys = tables_ref[b, jnp.clip(j, w0, w1)]
        return (jnp.where(phys < Z, np.int32(nb - 1), phys), h, Z, Z)
    return im


def _heads_outermost(im):
    """An index map written for the grid (slot, head group, entry), on
    the packed plan's grid (head group, slot, entry)."""
    return lambda h, b, j, *refs: im(b, h, j, *refs)


def _append_kernel(tables_ref, lens_ref, qlens_ref, start_ref, q_ref, k_ref,
                   v_ref, *rest, scale, bs, mb, nb, s_chunk, g, tr, ts, hb,
                   kw=None, quant=None):
    """One grid step = one table entry of one slot, for ``hb`` kv heads.
    All vector work sits under a ``pl.when`` read from the slot's
    ``(seq_lens, q_lens)``: a step that is neither live nor in the append
    window does none.

    ``kw`` None is the per-slot plan: grid (slot, head group, entry), the
    q, output and chunk K/V blocks one slot's, its rows from row 0 of
    them. ``kw`` (static: the rows of the chunk K/V window) is the packed
    plan: grid (head group, slot, entry), those blocks a head's rows of
    EVERY slot, and slot ``b``'s the ones from ``start[b] * g`` on. A
    dynamic row offset has to lie on a sublane tile, so the slot's row
    tiles start at the multiple of :data:`_SUBLANE` at or below its first
    row: its first tile begins with ``off`` rows of the slots before it
    (computed like its own and dropped at the store), every row index
    below is the slot's own plus ``off``, and its last tile is read and
    written whole (the rows past its own are later slots', which the grid
    has yet to come to, or nobody's). ``tr`` is then
    :func:`_packed_row_tile`'s, a sublane tile longer, so that the rows
    and ``off`` fit the tiles the rows alone would. None of it changes a
    live row's arithmetic."""
    if quant:
        (ks_ref, vs_ref, nk_ref, nv_ref, o_ref, ko_ref, vo_ref, kso_ref,
         vso_ref, m_ref, l_ref, acc_ref) = rest
    else:
        (nk_ref, nv_ref, o_ref, ko_ref, vo_ref, m_ref, l_ref,
         acc_ref) = rest
    f32 = jnp.float32
    packed = kw is not None
    b, hg = pl.program_id(int(packed)), pl.program_id(int(not packed))
    j = pl.program_id(2)
    bs_i = np.int32(bs)
    tr_i = np.int32(tr)
    d = q_ref.shape[3]
    L = lens_ref[b]
    QL = jnp.minimum(qlens_ref[b], np.int32(s_chunk))
    if packed:
        # the slot's first row of a head and the tile boundary at or
        # below it
        first = start_ref[b] * np.int32(g)
        base = pl.multiple_of(first - _tile_offset(first), _SUBLANE)
        # an idle slot has no tile: it stores nothing
        off = jnp.where(QL > Z, first - base, Z)
    else:
        off = base = 0
    j_last = _apd_blk(lens_ref, qlens_ref, b, bs, mb, True)
    w0 = _apd_blk(lens_ref, qlens_ref, b, bs, mb, False)
    jj = _apd_walk(lens_ref, qlens_ref, b, j, bs, mb)
    phys = tables_ref[b, jj]
    phys_r = jnp.maximum(phys, Z)
    # same destination rule as the pool out index map
    dst = jnp.where(phys < Z, np.int32(nb - 1), phys)
    live = (j <= j_last) & (phys >= Z) & (QL > Z)
    in_window = (j >= w0) & (j <= j_last)
    t_lo, t_end = _tile_span(L, QL, jj, g, bs, tr, jnp, _div_i32, off)
    h0 = hg * np.int32(hb)                 # first kv head of this step

    def block_rows(r0, n):
        """The slot's rows ``[r0, r0 + n)`` (its own plus ``off``) in
        the q and output blocks."""
        if not packed:
            return pl.ds(r0, n)
        return pl.ds(pl.multiple_of(base + r0, math.gcd(tr, _SUBLANE)), n)

    def heads(fn):
        """``fn(h)`` on each kv head of this step. ``h`` is the loop's
        carry: its own index is an i64 under x64 (static bounds), which
        Mosaic cannot convert."""
        def body(_, h):
            fn(h)
            return h + np.int32(1)
        jax.lax.fori_loop(0, hb, body, Z)

    def tiles(lo, hi, fn):
        """``fn(r0)``, ``r0`` a tile's first row, on the row tiles
        [lo, hi) (traced i32 bounds: the loop runs the tiles the scalars
        name and no others)."""
        def body(t, c):
            fn(pl.multiple_of(t * tr_i, tr))
            return c
        jax.lax.fori_loop(lo, hi, body, Z)

    @pl.when(j == Z)
    def _init():
        def head(h):
            def tile(r0):
                rows = pl.ds(r0, tr)
                m_ref[h, rows, :] = jnp.full((tr, 1), NEG_INF, f32)
                l_ref[h, rows, :] = jnp.zeros((tr, 1), f32)
                acc_ref[h, rows, :] = jnp.zeros((tr, d), f32)
            tiles(Z, t_end, tile)
        heads(head)

    if quant:
        # scale outputs seeded from the inputs once, then read and
        # written in place (decode-kernel rule)
        @pl.when((b == Z) & (hg == Z) & (j == Z))
        def _seed_scales():
            kso_ref[...] = ks_ref[...]
            vso_ref[...] = vs_ref[...]

    def dequant(blk, s_ref, h):
        # in-VMEM dequant right after the block DMA (decode-kernel rule)
        return kv_unpack(blk, quant, d) * _scale_read(s_ref, phys_r, h0 + h)

    @pl.when(in_window)
    def _merge_and_store():
        # merge the chunk rows that land in THIS block into it in VMEM:
        # block row r holds chunk index i = j*bs + r - lens when
        # 0 <= i < q_lens. The gather is expressed as a one-hot selection
        # matmul ([bs, S] @ [S, D] — MXU-friendly; Mosaic has no per-row
        # dynamic gather), so attention sees the whole new chunk this
        # step and the merged block writes back through the aliased pool
        # outputs. Only window blocks pay it.
        # The packed plan reads the chunk through a window of ``kw``
        # rows from the tile boundary ``k0`` at or below the slot's first
        # (and ending inside the block), which hold it from row ``koff``
        if packed:
            k0 = pl.multiple_of(jnp.minimum(
                start_ref[b] - _tile_offset(start_ref[b]),
                np.int32(nk_ref.shape[2] - kw)), _SUBLANE)
            koff, nkw = start_ref[b] - k0, kw
        else:
            k0, koff, nkw = 0, 0, s_chunk
        row = jax.lax.broadcasted_iota(jnp.int32, (bs, nkw), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (bs, nkw), 1) - koff
        sel = ((jj * bs_i + row - L) == ci) & (ci >= Z) & (ci < QL)
        # block row r takes a chunk row iff its chunk index lands in
        # [0, q_lens) — index math, not a bool reduction over ``sel``
        # (Mosaic has no i1 reduce)
        idx = jj * bs_i + row[:, :1] - L                      # [bs, 1]
        has_new = (idx >= Z) & (idx < QL)

        def merged(blk, new_ref, h):
            # 0 and 1 are exact in every float, and each output is one
            # stored value times 1: the operand rule changes no bit here
            dt = _mxu_dtype(new_ref.dtype, blk.dtype, quant)
            m = jax.lax.dot_general(
                sel.astype(dt), new_ref[0, h, pl.ds(k0, nkw), :].astype(dt),
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
            return jnp.where(has_new, m.astype(blk.dtype), blk)

        def head(h):
            if not quant:
                ko_ref[0, h] = merged(k_ref[0, h], nk_ref, h)
                vo_ref[0, h] = merged(v_ref[0, h], nv_ref, h)
                return
            # in-VMEM re-quantize of each window block: old rows re-round
            # under the merged block's new absmax scale (drift-free when
            # the max is unchanged: absmax quantization round-trips its
            # own grid exactly). DEAD ROWS — positions at or past the
            # window's new end (stale content of a reused freed block) —
            # are ZEROED before the scale so a dirty block's garbage
            # can't inflate it (decode-kernel rule; quantized output must
            # not depend on pool-reuse history). A q_lens==0 slot writes
            # nothing: its boundary block stores back its ORIGINAL
            # payload + scale (the unquantized path's "stored back
            # unchanged" contract — no zeroing, no re-round). Attention
            # then reads the stored payload under the stored scale — the
            # ROUND-TRIPPED values, so this step's logits equal a later
            # re-read of the cache, and match the dense fallback
            # bit-for-bit.
            for src, new_ref, s_ref, out in ((k_ref, nk_ref, kso_ref, ko_ref),
                                             (v_ref, nv_ref, vso_ref, vo_ref)):
                blk = merged(dequant(src[0, h], s_ref, h), new_ref, h)
                blk = jnp.where(idx >= QL, np.float32(0.0), blk)
                s_new = kv_block_scale(blk, quant, axes=(0, 1),
                                       keepdims=True)
                out[0, h] = jnp.where(QL > Z, kv_quantize(blk, s_new, quant),
                                      src[0, h])

                @pl.when(QL > Z)
                def _store_scale():
                    _scale_write(s_ref, dst, h0 + h, s_new)
        heads(head)

    def attend(kr, vr, masked):
        """Online-softmax update of the row tiles [t_lo, t_end) against
        this step's block, read from ``kr``/``vr``. ``masked``: the block
        reaches into the chunk, so the causal mask applies; a block of
        the pooled history is visible to every live row."""
        def update(r0, n):
            """One row tile's ``n`` rows against the block, for this
            step's ``hb`` kv heads: ``hu`` = ``_HEADS_INTERLEAVED`` of
            them (or the largest divisor of ``hb`` under it) in one basic
            block, by a loop that is unrolled whole (its body is traced
            once, its head indices are constants), the groups by a loop
            that is not."""
            rows = pl.ds(r0, n)
            if masked:
                # row r (chunk index (r - off) // g) sees kv position p
                # iff (p - lens) * g + off <= r — no vector division
                rel = jj * bs_i - L + jax.lax.broadcasted_iota(
                    jnp.int32, (n, bs), 1)
                r = r0 + jax.lax.broadcasted_iota(jnp.int32, (n, bs), 0)
                seen = rel * np.int32(g) + off <= r

            def head(_, h):
                k_blk, v_blk = kr[0, h], vr[0, h]
                if quant:
                    k_blk = dequant(k_blk, kso_ref, h)
                    v_blk = dequant(v_blk, vso_ref, h)
                dt = _mxu_dtype(q_ref.dtype, k_blk.dtype, quant)
                s = _scores(q_ref[0, h, block_rows(r0, n), :],
                            k_blk.astype(dt), scale, dt)
                if masked:
                    s = jnp.where(seen, s, NEG_INF)
                m_prev = m_ref[h, rows, :]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h, rows, :] = l_ref[h, rows, :] * alpha + jnp.sum(
                    p, axis=1, keepdims=True)
                acc_ref[h, rows, :] = acc_ref[h, rows, :] * alpha + \
                    jax.lax.dot_general(
                        p.astype(v_blk.dtype), v_blk,
                        (((1,), (0,)), ((), ())), preferred_element_type=f32)
                m_ref[h, rows, :] = m_new
                return h + np.int32(1)

            hu = max(u for u in range(1, _HEADS_INTERLEAVED + 1)
                     if hb % u == 0)

            def group(_, h0):
                return jax.lax.fori_loop(0, hu, head, h0, unroll=True)
            if hu == hb:
                group(0, Z)
            else:
                jax.lax.fori_loop(0, hb // hu, group, Z)

        def tile(r0):
            if ts == tr:
                return update(r0, tr)
            # a tile whose live rows end inside its first ``ts`` (a
            # decode row's, a verify window's, a chunk's tail) runs
            # those alone; its other rows keep their initial state
            # and finalize to zeros
            short = QL * np.int32(g) + off - r0 <= np.int32(ts)
            pl.when(short)(lambda: update(r0, ts))
            pl.when(jnp.logical_not(short))(lambda: update(r0, tr))
        tiles(t_lo, t_end, tile)

    # a window block is read where the merge just stored it (the aliased
    # out buffers: merged, and for quantized pools round-tripped)
    @pl.when(live & in_window)
    def _attend_window():
        attend(ko_ref, vo_ref, True)

    @pl.when(live & jnp.logical_not(in_window))
    def _attend_history():
        attend(k_ref, v_ref, False)

    @pl.when(j == np.int32(mb - 1))
    def _finalize():
        def head(h):
            def live_tile(r0):
                rows = pl.ds(r0, tr)
                l = jnp.maximum(l_ref[h, rows, :], np.float32(1e-30))
                o = (acc_ref[h, rows, :] / l).astype(o_ref.dtype)
                if packed:
                    # the first tile's rows of earlier slots stay as those
                    # slots stored them
                    r = r0 + jax.lax.broadcasted_iota(jnp.int32, (tr, d), 0)
                    o = jnp.where(r < off, o_ref[0, h, block_rows(r0, tr), :],
                                  o)
                o_ref[0, h, block_rows(r0, tr), :] = o

            def idle_tile(r0):
                o_ref[0, h, pl.ds(r0, tr), :] = jnp.zeros((tr, d),
                                                          o_ref.dtype)
            tiles(Z, t_end, live_tile)
            if not packed:
                # (the packed entry's wrapper zeroes the rows that hold no
                # token: a tile past a slot's last is another slot's)
                tiles(t_end, np.int32(q_ref.shape[2] // tr), idle_tile)
        heads(head)


def paged_attention_append(q, k_pool, v_pool, block_tables, seq_lens,
                           q_lens, new_k, new_v, scale=None, k_scale=None,
                           v_scale=None, quant=None, start=None, width=None):
    """Append attention off the block pools: one fused prefill+decode step.

    q: [B, S, Hq, D] — up to S new positions per sequence (rows past
    ``q_lens[b]`` are padding; their outputs are garbage the caller
    ignores); k_pool/v_pool: [num_blocks, Hkv, block_size, D];
    block_tables: [B, max_blocks]; seq_lens: [B] tokens already cached —
    sequence b's chunk occupies positions [seq_lens[b],
    seq_lens[b]+q_lens[b]); q_lens: [B] valid rows (0 = inactive slot:
    no compute, no write). new_k/new_v: [B, S, Hkv, D], the chunk's K/V
    — always fused-written (blocks overlapping the window are merged in
    VMEM, attention sees the chunk without a prior scatter round-trip,
    and write back through aliased outputs).

    Query row i of sequence b attends causally: pooled positions plus its
    own chunk prefix (kv position <= seq_lens[b] + i). The caller must
    have blocks allocated to cover the window (the fused scheduler does);
    a -1 target writes to the pool's trailing scratch block.

    **The work follows (seq_lens, q_lens)**, both scalar-prefetched. Query
    rows of a kv head's group are laid POSITION-major (row ``i * G +
    q_head``), so a slot's live rows are the prefix ``[0, q_lens * G)`` of
    its ``G * S``; the kernel walks them in row tiles of
    :func:`_row_tile` rows (derived from G and S alone: at most
    ``_ROW_TILE_MAX``, a multiple of 16 that divides ``G * S``; no option)
    and, per table entry, runs only the tiles under ``q_lens * G`` that
    the entry's block is not wholly causally masked for
    (:func:`_tile_span`; :func:`append_tile_steps` counts the same rule
    on the host). A tile whose live rows end inside its first
    :func:`_row_subtile` rows computes those alone: a decode row (q_lens
    1) costs one such short tile a block, a full chunk every tile. Rows
    of tiles never run come back as zeros. One grid step serves every kv head of a table entry
    (:func:`_heads_per_step`: as many as fit VMEM), its heads in one DMA,
    and runs a row tile's update for ``_HEADS_INTERLEAVED`` of them in one
    basic block, so that their independent chains overlap;
    entries past the window's last block re-map to it (no copy) and do no
    vector work; the merge of the chunk into a block runs for window
    blocks only; an idle slot (q_lens 0) carries its boundary block
    through unchanged and walks nothing.

    **What is f32**: every matmul's accumulation, the scores, the scale,
    the mask, ``m``/``l``/``acc`` and the finalize. **What is not**: the
    operands of ``QK^T`` and of the merge's one-hot selection when q, the
    pools and (cast on the way in) the chunk's K/V are stored in the same
    16-bit float — they go to the MXU as stored, exact
    (:func:`_mxu_dtype`), and the scale multiplies the f32 scores, since
    ``q * scale`` has no exact 16-bit form. ``P`` is cast to V's dtype
    for ``P.V`` as it always was. An f32 q, mismatched dtypes or
    ``quant`` take the f32 form (operands converted, scale on q).

    Returns (out [B, S, Hq, D] in q.dtype, k_pool, v_pool).

    **The packed entry** (``start`` [B] given: a mixed step's rows as the
    decoder holds them, ``cache_layout.RowMap.start``): q is ``[T, Hq,
    D]`` and new_k/new_v ``[T, Hkv, D]`` on ONE row axis, slot ``b``'s
    rows the ``q_lens[b]`` from ``start[b]`` on, in position order, slots
    ascending and no two overlapping; ``width`` (static, required with
    ``start``) is the most rows a slot may hold, the per-slot entry's S
    (the engine's chunk, what :func:`append_tile_steps` is asked with),
    from which the row tile is derived. ``start`` is scalar-prefetched beside
    ``(seq_lens, q_lens)``. The wrapper moves the T rows alone to the
    head-major layout (``[T, Hkv, G, D] -> [Hkv, T * G, D]``), the grid is
    (head group, slot, table entry), and q, the output and the chunk's K/V
    are blocks RESIDENT a head group, fetched and written once a call:
    slot ``b``'s row tiles are read and stored at ``start[b] * G`` of
    them, on the row axis' own 16-row grid (``_append_kernel``'s
    docstring: the slot's first tile holds the last rows of the slots
    before it, which are kept at the store). The walk, the skip rule, the
    merge and the pools are the per-slot entry's lines, and a live row's
    arithmetic is the same in both: the outputs on live rows and the
    pools are bit-equal. Returns (out ``[T, Hq, D]``, k_pool, v_pool):
    a row that holds no token comes back zero. Which entry a call takes
    is a shape of the call; the per-slot entry ``[B, S, ...]`` (a
    one-process ``generate()``, the legacy scheduler, the tests) keeps
    its blocks a slot, which is what fits when every slot's S rows exist.

    ``quant`` + ``k_scale``/``v_scale`` [num_blocks, Hkv]: quantized
    pools exactly as in :func:`paged_attention_decode` — blocks dequant
    in VMEM for the walk, every window block re-quantizes in VMEM with
    its new per-head absmax scale, and the return grows to
    ``(out, k_pool, v_pool, k_scale, v_scale)``.
    """
    Hq, D = q.shape[-2:]
    NB, Hkv, BS, Dk = k_pool.shape
    if quant:
        assert k_scale is not None and v_scale is not None
        assert Dk == kv_packed_dim(D, quant), (q.shape, k_pool.shape, quant)
    else:
        assert k_scale is None and v_scale is None
        assert D == Dk, (q.shape, k_pool.shape)
    assert Hq % Hkv == 0, f"GQA needs Hq % Hkv == 0, got {Hq=} {Hkv=}"
    assert q.ndim == (4 if start is None else 3), (q.shape, start)
    if start is not None:
        assert width is not None, "the packed entry needs the slot's width"
        assert start.shape == q_lens.shape, (start.shape, q_lens.shape)
        width = int(width)
    return _append_call(
        q, k_pool, v_pool, block_tables, seq_lens, q_lens, new_k, new_v,
        k_scale, v_scale, start,
        scale=float(scale) if scale is not None else 1.0 / math.sqrt(D),
        quant=quant, interpret=_interpret(), width=width)


@functools.partial(jax.jit, static_argnames=("scale", "quant", "interpret",
                                             "width"), inline=True)
def _append_call(q, k_pool, v_pool, block_tables, seq_lens, q_lens, new_k,
                 new_v, k_scale, v_scale, start=None, *, scale, quant,
                 interpret, width=None):
    """:func:`paged_attention_append`'s transposes and Pallas call.
    Jitted so that a model's layers, which call it with the same shapes,
    share ONE trace of the kernel (a bare ``pallas_call`` re-traces its
    kernel at every call site: 16 layers cost 16 traces in the step
    program's build), and ``inline``: left as a call in the step program,
    XLA ran the 16-layer mixed step 3.5 ms slower on the v5e (PERF.md
    section 6, PR 26). ``start`` None: the per-slot plan; given, the
    packed plan with ``width`` the most rows of a slot."""
    packed = start is not None
    Hq, D = q.shape[-2:]
    NB, Hkv, BS, Dk = k_pool.shape
    G = Hq // Hkv
    B, MB = block_tables.shape
    nk_dt = k_pool.dtype if not quant else new_k.dtype
    tables = block_tables.astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)
    qlens = q_lens.astype(jnp.int32)

    # rows of a kv head are position-major: row r = i*G + g is position i
    # of q head h*G + g, so live rows are a prefix (the (Hkv, G) grouping
    # is the decode kernel's)
    if packed:
        # [T, Hq, D] -> [1, Hkv, T*G, D] (T to a whole sublane tile). The
        # q and output BLOCKS are a row tile longer than the arrays, for a
        # slot's last tile to reach into: only the arrays' rows are
        # fetched and written, what lies past them is nobody's, and no
        # row's arithmetic reads another's. The chunk's K/V [T, Hkv, D] ->
        # [1, Hkv, TK, D], zeros past T (the merge sums over a window's
        # rows): a window of ``kw`` rows of it holds any slot's chunk
        # from a tile boundary on
        T, S = q.shape[0], width
        tr = _packed_row_tile(G, S)
        t16 = -(-T // _SUBLANE) * _SUBLANE
        kw = -(-min(S, T) // _SUBLANE) * _SUBLANE + _SUBLANE
        tk = max(t16, kw)
        resident = (-(-(t16 * G + tr) // _SUBLANE) * _SUBLANE, tk)
        q4 = jnp.pad(q.reshape(T, Hkv, G, D),
                     [(0, t16 - T), (0, 0), (0, 0), (0, 0)])
        q4 = jnp.transpose(q4, (1, 0, 2, 3)).reshape(1, Hkv, t16 * G, D)
        nk, nv = (jnp.transpose(
            jnp.pad(x.astype(nk_dt), [(0, tk - T), (0, 0), (0, 0)]),
            (1, 0, 2))[None] for x in (new_k, new_v))
        starts = start.astype(jnp.int32)
        order = _heads_outermost
    else:
        # [B, S, Hq, D] -> [B, Hkv, S*G, D], a slot a block
        S = q.shape[1]
        tr = _row_tile(G, S)
        kw, resident = None, None
        q4 = jnp.transpose(q.reshape(B, S, Hkv, G, D),
                           (0, 2, 1, 3, 4)).reshape(B, Hkv, S * G, D)
        nk = jnp.transpose(new_k, (0, 2, 1, 3)).astype(nk_dt)
        nv = jnp.transpose(new_v, (0, 2, 1, 3)).astype(nk_dt)
        starts = jnp.zeros((B,), jnp.int32)      # not read
        order = lambda im: im  # noqa: E731

    shape = (G, S, D, BS, Dk, q.dtype.itemsize, k_pool.dtype.itemsize,
             jnp.dtype(nk_dt).itemsize, resident)
    q_rows, new_rows = resident or (G * S, S)
    hb = _heads_per_step(Hkv, *shape)
    HG = Hkv // hb
    pool_spec = pl.BlockSpec((1, hb, BS, Dk),
                             order(_apd_pool_out_index_map(BS, MB, NB)))
    kv_spec = pl.BlockSpec((1, hb, BS, Dk),
                           order(_apd_kv_index_map(BS, MB)))
    rows_map = order(_apd_rows_index_map if packed else _apd_q_index_map)
    q_spec = pl.BlockSpec((1, hb, q_rows, D), rows_map)
    new_spec = pl.BlockSpec((1, hb, new_rows, D), rows_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    out_specs = [q_spec, pool_spec, pool_spec]
    out_shape = [jax.ShapeDtypeStruct(q4.shape, q.dtype),
                 jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                 jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
    inputs = [tables, lens, qlens, starts, q4, k_pool, v_pool]
    # flat input indices INCLUDE the scalar-prefetch operands
    io_aliases = {5: 1, 6: 2}
    if quant:
        in_specs += [_scale_spec(NB, Hkv)] * 2
        out_specs += [_scale_spec(NB, Hkv)] * 2
        out_shape += [jax.ShapeDtypeStruct((NB, Hkv), jnp.float32),
                      jax.ShapeDtypeStruct((NB, Hkv), jnp.float32)]
        inputs += [k_scale.astype(jnp.float32),
                   v_scale.astype(jnp.float32)]
        io_aliases = {5: 1, 6: 2, 7: 3, 8: 4}
    in_specs += [new_spec, new_spec]
    inputs += [nk, nv]

    kernel = functools.partial(_append_kernel, scale=scale, bs=BS, mb=MB,
                               nb=NB, s_chunk=S, g=G, tr=tr,
                               ts=_row_subtile(_row_tile(G, S)), hb=hb,
                               kw=kw, quant=quant)
    acc_rows = _scratch_rows(G, S, packed)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(HG, B, MB) if packed else (B, HG, MB),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((hb, acc_rows, 1), jnp.float32),  # running max m
                pltpu.VMEM((hb, acc_rows, 1), jnp.float32),  # running norm l
                pltpu.VMEM((hb, acc_rows, D), jnp.float32),  # out accumulator
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=io_aliases,
        # sequential everywhere: scratch carries over blocks and clamped
        # write destinations may collide across batch windows; the packed
        # plan's slots store into one resident block in ascending order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(
                32 << 20,
                _append_vmem_bytes(hb, *shape) + (16 << 20))),
        name="paged_attention_append",
        interpret=interpret,
    )(*inputs)
    if packed:
        out = outs[0][0, :, :T * G].reshape(Hkv, T, G, D)
        out = jnp.transpose(out, (1, 0, 2, 3)).reshape(T, Hq, D)
        # a row that holds no token: whatever a neighbour's tile left
        t = jnp.arange(T, dtype=jnp.int32)[:, None]
        held_by = (t >= starts[None]) & (
            t < (starts + jnp.minimum(qlens, np.int32(S)))[None])
        out = jnp.where(jnp.any(held_by, axis=1)[:, None, None], out, 0.0)
    else:
        out = outs[0].reshape(B, Hkv, S, G, D)
        out = jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, S, Hq, D)
    if quant:
        return out, outs[1], outs[2], outs[3], outs[4]
    return out, outs[1], outs[2]
