"""PTL001 — implicit device→host sync detector for serving hot paths.

PR 8's headline win was structural: the fused all-decode stride pays
exactly ONE device→host sync per ``readout_stride`` tokens, and every
other host touch of device state in the dispatch→readout window shows
up straight in p99 inter-token latency. Nothing in Python stops the
next feature from dropping an ``int(self._lens[b])`` into
``step_begin`` — it works, it is just 10x the sync budget. This check
makes that a lint error.

Scope: functions whose NAME is one of the engine/serving hot-path
entry points (``step_begin``/``step_finish``/the fused walk/multi-step
scheduling/readout/gauge sampling). Nested ``def``s inside a hot
function are NOT scanned — in this codebase those are jit program
bodies (device-side, where ``int()`` is a trace-time cast, not a
sync).

Flagged patterns (each only when the expression *mentions device
state* — an attribute/name from the engine's device-buffer vocabulary,
or any ``jax.*``/``jnp.*`` call):

* ``.item()`` / ``.tolist()`` / ``.block_until_ready()`` — always
  flagged, device-state mention or not (they are syncs by definition
  on anything jax-shaped).
* ``jax.device_get(...)`` / ``jax.block_until_ready(...)``.
* ``np.asarray(...)`` / ``np.array(...)`` — THE implicit D2H.
* ``int(...)`` / ``float(...)`` / ``bool(...)`` — scalar pulls.
* ``for _ in <device state>`` — iterating a jax array is one sync per
  element.

Documented readout sites — the one place per engine where the stride's
single sync is SUPPOSED to happen — are allowlisted by (path suffix,
function, snippet substring) in :data:`ALLOWED_SYNCS`; anything else
deliberate carries an inline ``# ptlint: disable=PTL001 -- reason``.
"""
from __future__ import annotations

import ast

from .core import Check

__all__ = ["HostSyncCheck", "HOT_FUNCTIONS", "ALLOWED_SYNCS"]

#: the engine/serving hot-path functions this check patrols. A name
#: match anywhere makes fixtures (and future engines speaking the step
#: protocol) patrol the same contract without a config edit.
HOT_FUNCTIONS = frozenset({
    # engine step protocol + fused scheduler walk
    "step_begin", "_step_begin_impl", "step_finish",
    "_begin_mixed_step", "_begin_spec_decode", "_schedule_mixed",
    "_admit_waiting", "_admit_fused", "_record_dispatch",
    # serving loop: dispatch/readout wrappers, gauge sampling,
    # telemetry stamping
    "_serve_loop", "_serve_pass", "_begin_step", "_finish_step", "_update_gauges",
    "_feed_engine", "_on_token", "_note_admissions",
    "_sweep_cancels_and_deadlines", "_handle_done",
})

#: attribute names that ARE device state in this codebase (engine
#: buffers and PendingStep futures) — an expression touching one of
#: these inside a hot function is a device touch.
DEVICE_ATTRS = frozenset({
    "_lens", "_logits", "_k", "_v", "_tokens", "_rng_key", "_state_vals",
    "toks", "counts", "was_active", "offered", "pooled", "out",
})

#: bare names treated as device state (locals conventionally bound to
#: dispatch outputs before the readout).
DEVICE_NAMES = frozenset({"toks", "counts", "was_active", "offered",
                          "pooled", "logits"})

#: (path suffix, function, snippet substring) triples naming the
#: DOCUMENTED readout sites — the one sync per stride each engine is
#: contractually allowed. The anchor is the specific readout FORM
#: (materializing this dispatch's device futures), not the pending
#: object: a future `int(pending.counts[b])` scalar pull in the same
#: function still fires. Everything else needs an inline suppression
#: with a reason.
ALLOWED_SYNCS = (
    ("inference/llm_engine.py", "step_finish", "np.asarray(pending."),
    ("serving/embedding.py", "step_finish", "np.asarray(pending."),
)

_SYNC_METHODS = ("item", "tolist", "block_until_ready")
_CAST_FUNCS = ("int", "float", "bool")
_NP_FUNCS = ("asarray", "array")


def _mentions_device(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in DEVICE_ATTRS:
            return True
        if isinstance(sub, ast.Name) and sub.id in DEVICE_NAMES:
            return True
        if isinstance(sub, ast.Call):
            root = sub.func
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in ("jax", "jnp"):
                return True
    return False


class HostSyncCheck(Check):
    id = "PTL001"
    describe = ("implicit device->host sync inside an engine/serving "
                "hot path (one sync per stride is the contract)")

    def run(self, mod):
        # textual prefilter: most modules define no hot-path function
        if not any(name in mod.text for name in HOT_FUNCTIONS):
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in HOT_FUNCTIONS:
                yield from self._scan_hot(mod, node)

    def _allowed(self, mod, func, node):
        seg = mod.segment(node)
        for suffix, fn, sub in ALLOWED_SYNCS:
            if mod.relpath.endswith(suffix) and func == fn and sub in seg:
                return True
        return False

    def _scan_hot(self, mod, fn):
        # walk the hot function body but never descend into nested defs
        # (jit program bodies are device-side; a lambda/callback is not
        # this function's sync budget)
        stack = list(fn.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            hits = list(self._scan_node(mod, fn.name, node))
            for f in hits:
                if not self._allowed(mod, fn.name, f[0]):
                    yield self.finding(mod, f[0], f[1], func=fn.name)
            if not hits:
                stack.extend(ast.iter_child_nodes(node))
                continue
            # one finding per sync EXPRESSION: don't re-flag nested
            # parts of an already-reported (or allowlisted) sync like
            # `int(pending.counts[0].item())` — but keep scanning
            # sibling subtrees (a flagged `for ... in self.toks:` must
            # not exempt the syncs inside its body)
            skip = set()
            for anchor, _ in hits:
                for sub in ast.walk(anchor):
                    skip.add(id(sub))
            stack.extend(c for c in ast.iter_child_nodes(node)
                         if id(c) not in skip)

    def _scan_node(self, mod, func, node):
        if isinstance(node, ast.For) and _mentions_device(node.iter):
            yield (node.iter,
                   f"iterating device state "
                   f"`{mod.segment(node.iter)}` syncs once per element")
            return
        if not isinstance(node, ast.Call):
            return
        callee = node.func
        if isinstance(callee, ast.Attribute):
            if callee.attr in _SYNC_METHODS:
                yield (node, f"`.{callee.attr}()` forces a device->host "
                             f"sync: `{mod.segment(node)}`")
                return
            root = callee.value
            if isinstance(root, ast.Name):
                if root.id == "jax" and callee.attr in (
                        "device_get", "block_until_ready",
                        "effects_barrier"):
                    yield (node, f"`jax.{callee.attr}` syncs the host: "
                                 f"`{mod.segment(node)}`")
                    return
                if root.id in ("np", "numpy") and \
                        callee.attr in _NP_FUNCS and node.args and \
                        _mentions_device(node.args[0]):
                    yield (node, f"`np.{callee.attr}` of device state is "
                                 f"an implicit D2H sync: "
                                 f"`{mod.segment(node)}`")
                    return
        elif isinstance(callee, ast.Name) and callee.id in _CAST_FUNCS \
                and node.args and _mentions_device(node.args[0]):
            yield (node, f"`{callee.id}()` of device state is a scalar "
                         f"device->host pull: `{mod.segment(node)}`")
