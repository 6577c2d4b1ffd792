"""PTL006 — device↔host KV-pool copies outside the fence-tracked swap
API.

The host KV tier (``LLMEngine(kv_host_swap=..., kv_host_spill_bytes=
...)``) moves pool blocks between device HBM and host RAM through
exactly four functions — ``_swap_out_slot`` / ``_spill_block`` (D2H)
and ``_try_swap_restores`` / ``_promote_spilled`` (H2D) — and the
cross-replica ship path (PR 17) adds four more on the same fences:
``_export_slot_kv`` / ``export_prefix_blocks`` (D2H staging for a ship)
and the transport's ``serialize_entry`` / ``deserialize_entry`` (wire
encode/decode over the staged, already-booked buffers). Those functions
are where the correctness obligations live: the gather must take the
engine's NEWEST pool futures (so it sequences after every in-flight
writer), the scatter must target freshly allocated blocks the write
fence keeps out of every in-flight dispatch, and each direction books
its bytes/blocks on the ``kv_swap_*`` / ``kv_ship_*`` stats the
StepRecord split and the preemption A/B read.

A KV copy issued anywhere else has none of those guarantees: it can
race a pipelined writer (silently on CPU, corrupt KV on TPU), and its
bytes vanish from the swap accounting — ``kv_swap_saved_tokens``
quietly lies. This check makes that a lint error:

* any ``np.asarray`` / ``np.array`` / ``jax.device_get`` /
  ``jax.device_put`` / ``.copy_to_host_async()`` call whose argument
  expression touches a KV pool (``self._k`` / ``self._v``, or the
  conventional pool parameter names ``k_pools``/``v_pools``/
  ``k_bufs``/``v_bufs``), and
* any call of the compiled tier programs themselves
  (``_kv_gather_fn`` / ``_kv_scatter_fn``) — the tracked API boundary,

outside the allowlisted swap-API functions, is flagged. Deliberate
exceptions carry ``# ptlint: disable=PTL006 -- reason`` like every
other check.
"""
from __future__ import annotations

import ast

from .core import Check

__all__ = ["KVTransferCheck", "KV_POOL_ATTRS", "KV_POOL_NAMES",
           "SWAP_PROGRAMS", "ALLOWED_TRANSFER_FUNCS"]

#: attribute names that ARE the paged KV pools in this codebase
KV_POOL_ATTRS = frozenset({"_k", "_v"})

#: conventional parameter/local names bound to the pools (the jit
#: program bodies and staging helpers)
KV_POOL_NAMES = frozenset({"k_pools", "v_pools", "k_bufs", "v_bufs"})

#: the compiled tier programs — calling one IS a device↔host KV
#: transfer commitment, wherever the bytes end up
SWAP_PROGRAMS = frozenset({"_kv_gather_fn", "_kv_scatter_fn"})

#: (path suffix, function) pairs naming THE fence-tracked transfer API —
#: the only places a KV-pool transfer may be issued: the host-tier swap
#: halves, the cross-replica ship staging points (same gather, entries
#: book on kv_ship_* instead), and the transport's wire encode/decode
#: (which materializes pool-derived leaf buffers). Kept in sync with
#: the source files by tests/test_analysis_clean.py (a rename there
#: makes the repo scan light up here).
ALLOWED_TRANSFER_FUNCS = (
    ("inference/llm_engine.py", "_swap_out_slot"),
    ("inference/llm_engine.py", "_try_swap_restores"),
    ("inference/llm_engine.py", "_spill_block"),
    ("inference/llm_engine.py", "_promote_spilled"),
    ("inference/llm_engine.py", "_export_slot_kv"),
    ("inference/llm_engine.py", "export_prefix_blocks"),
    ("serving/kv_transport.py", "serialize_entry"),
    ("serving/kv_transport.py", "deserialize_entry"),
)

_TRANSFER_FUNCS = {("jax", "device_get"), ("jax", "device_put"),
                   ("np", "asarray"), ("np", "array"),
                   ("numpy", "asarray"), ("numpy", "array")}


def _touches_pool(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in KV_POOL_ATTRS:
            return True
        if isinstance(sub, ast.Name) and sub.id in KV_POOL_NAMES:
            return True
    return False


def _classify_call(node):
    """(label, needs_pool_mention) for a transfer-shaped call, else
    None."""
    callee = node.func
    if isinstance(callee, ast.Attribute):
        if callee.attr == "copy_to_host_async":
            return ".copy_to_host_async()", True
        if callee.attr in SWAP_PROGRAMS:
            return f"self.{callee.attr}(...)", False
        root = callee.value
        if isinstance(root, ast.Name) and \
                (root.id, callee.attr) in _TRANSFER_FUNCS:
            return f"{root.id}.{callee.attr}", True
    return None


class KVTransferCheck(Check):
    id = "PTL006"
    describe = ("device<->host KV-pool copy outside the fence-tracked "
                "swap API (races in-flight writers, skips the swap "
                "accounting)")

    def run(self, mod):
        # textual prefilter: a module with no transfer-shaped call and
        # no tier-program reference cannot fire
        if not any(tok in mod.text for tok in
                   ("copy_to_host_async", "device_get", "device_put",
                    "asarray", "np.array", "numpy.array",
                    "_kv_gather_fn", "_kv_scatter_fn")):
            return
        yield from self._scan_scope(mod, mod.tree, "<module>")
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._scan_scope(mod, node, node.name)

    def _allowed(self, mod, func):
        return any(mod.relpath.endswith(suffix) and func == fn
                   for suffix, fn in ALLOWED_TRANSFER_FUNCS)

    def _scan_scope(self, mod, scope, func):
        if self._allowed(mod, func):
            return
        # scan this scope's body without descending into nested defs —
        # each nested function is judged under its OWN name (a helper
        # inside an allowed function is not itself allowed; an allowed
        # function nested in a disallowed one still is)
        stack = list(scope.body if hasattr(scope, "body") else [])
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(node, ast.Call):
                hit = _classify_call(node)
                if hit is not None:
                    label, needs_pool = hit
                    if not needs_pool or _touches_pool(node):
                        yield self.finding(
                            mod, node,
                            f"`{label}` moves KV-pool bytes across the "
                            f"device boundary outside the fence-tracked "
                            f"transfer API (the swap halves "
                            f"_swap_out_slot/_try_swap_restores/"
                            f"_spill_block/_promote_spilled, the ship "
                            f"stagers _export_slot_kv/"
                            f"export_prefix_blocks, and the transport "
                            f"serialize_entry/deserialize_entry) — it "
                            f"can race an in-flight writer and its "
                            f"bytes skip the kv_swap_*/kv_ship_* "
                            f"accounting",
                            key=f"kv-transfer:{label}", func=func)
                        continue     # one finding per transfer call
            stack.extend(ast.iter_child_nodes(node))
