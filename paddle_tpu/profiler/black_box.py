"""Postmortem black box — bounded debug bundles for serving incidents.

When a replica crashes, wedges, or starts burning an SLO budget, the
question five minutes later is always the same: *what was it doing?*
The live observability stack (flight recorder, metrics store, alert
log, engine stats) holds the answer — but only until the process exits
or the ring wraps. This module is the flight-data-recorder dump: one
bounded, schema-tagged JSON file capturing the tails of every in-memory
diagnostic surface at the moment of the incident:

* the flight recorder's StepRecord **ring tail** and its worst
  ``explain_tail`` gaps (with their cause verdicts and trace ids),
* the metrics store's **series tails** and the full **alert log**,
* an **engine snapshot**: config, cumulative stats, paged-pool / host
  KV-tier / ship-store occupancy,
* the server's health/restart state and the fault injector's fired
  record (chaos runs are self-describing).

Triggers (armed via ``AsyncLLMServer(black_box=...)``): crash→restart,
the watchdog's hang verdict, and each metrics-store alert RAISE —
**edge-triggered** (one bundle per alert instance, not per evaluation)
and **deduped** (a crash loop produces one bundle per
``dedup_window_s``, not one per restart). Manual dumps via
``server.dump_debug_bundle(path)`` / ``router.dump_debug_bundle(dir)``
skip both gates. Every bundle is **byte-bounded**: the dump shrinks its
tails until the serialized JSON fits ``max_bytes``, so an armed black
box can never fill a disk however long the incident runs.

Read a bundle back with ``python -m paddle_tpu.profiler.bundle <path>``.
"""
from __future__ import annotations

import json
import os
import threading
import time

from ._build import builds

__all__ = ["BlackBox", "collect_bundle", "write_bundle",
           "BUNDLE_SCHEMA", "TRIGGER_REASONS"]

#: the schema tag every bundle carries — the pretty-printer (and any
#: downstream tooling) validates it before trusting field shapes
BUNDLE_SCHEMA = "paddle_tpu.debug_bundle/v1"

#: every reason an automatic or manual dump may carry
TRIGGER_REASONS = ("crash", "hang", "burn_alert", "manual")


def _json_safe(obj, depth=0):
    """Coerce ``obj`` into JSON-encodable primitives: numpy scalars to
    Python numbers, small arrays to lists, anything else to ``str``.
    Depth-bounded — a cyclic or pathological structure degrades to its
    repr instead of recursing forever."""
    if depth > 6:
        return str(obj)
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, (bytes, bytearray)):
        return obj.hex()
    if isinstance(obj, dict):
        return {str(k): _json_safe(v, depth + 1) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set, frozenset)):
        seq = list(obj)
        if len(seq) > 256:
            seq = seq[:256]
        return [_json_safe(v, depth + 1) for v in seq]
    item = getattr(obj, "item", None)
    if callable(item):
        try:
            return _json_safe(item(), depth + 1)  # numpy scalar
        except (TypeError, ValueError):
            pass
    tolist = getattr(obj, "tolist", None)
    if callable(tolist):
        try:
            return _json_safe(tolist(), depth + 1)  # small numpy array
        except (TypeError, ValueError):
            pass
    return str(obj)


def _engine_snapshot(engine):
    """Config + occupancy facts of one engine, read defensively (every
    field is a plain attribute read — safe from any thread, even while
    the engine thread is wedged inside a step)."""
    if engine is None:
        return None
    snap = {}
    for attr in ("cache_impl", "scheduler", "B", "capacity", "block_size",
                 "n_blocks", "speculative_k", "readout_stride",
                 "kv_cache_dtype", "kv_host_swap"):
        v = getattr(engine, attr, None)
        if v is not None:
            snap[attr] = _json_safe(v)
    stats = getattr(engine, "stats", None)
    if isinstance(stats, dict):
        snap["stats"] = {k: _json_safe(v) for k, v in stats.items()
                         if isinstance(v, (int, float))}
    free = getattr(engine, "_free_blocks", None)
    if free is not None:
        snap["pool"] = {
            "free_blocks": len(free),
            "cached_blocks": len(getattr(engine, "_lru", ())),
            "spill_blocks": len(getattr(engine, "_spill", ())),
            "spill_bytes": _json_safe(getattr(engine, "_spill_bytes", 0)),
            "swap_store_rids": sorted(
                _json_safe(r)
                for r in getattr(engine, "_swap_store", {}) or ()),
            "export_store_rids": sorted(
                _json_safe(r)
                for r in getattr(engine, "_export_store", {}) or ()),
            "kv_pool_bytes": _json_safe(
                getattr(engine, "_kv_nbytes", None)),
        }
    slots = getattr(engine, "slots", None)
    if slots is not None:
        snap["resident_rids"] = [_json_safe(s.req.request_id)
                                 for s in slots if s is not None]
        snap["waiting"] = len(getattr(engine, "waiting", ()))
    return snap


def collect_bundle(server=None, engine=None, recorder=None,
                   metrics_store=None, reason="manual", detail=None,
                   ring_tail=64, series_tail=32, tail_top=16):
    """Assemble one debug-bundle dict from whatever diagnostic surfaces
    exist. Pass a ``server`` and the engine / recorder / store are
    taken from it; any surface may be absent (its section is None).
    Every read is lock-cheap and defensive — collection must work
    while the serve loop is crashed or wedged."""
    if reason not in TRIGGER_REASONS:
        raise ValueError(f"unknown bundle reason {reason!r} "
                         f"(one of {TRIGGER_REASONS})")
    if server is not None:
        engine = engine or server.engine
        recorder = recorder or server.flight_recorder
        metrics_store = metrics_store or server.metrics_store
    bundle = {
        "schema": BUNDLE_SCHEMA,
        "reason": reason,
        "detail": detail,
        "pid": os.getpid(),
        "monotonic_t": round(time.monotonic(), 6),
        "perf_t": round(time.perf_counter(), 6),
    }
    if server is not None:
        try:
            health = server.health()
        except Exception:
            health = None
        bundle["server"] = {
            "replica": server.replica,
            "health": _json_safe(health),
            "restarts": getattr(server, "restarts", 0),
            "outstanding": server.num_outstanding(),
            "queue_depth": len(server._queue),
        }
        fi = getattr(server, "fault_injector", None)
        if fi is not None:
            bundle["faults"] = _json_safe(
                fi.snapshot() if hasattr(fi, "snapshot")
                else list(fi.fired))
    bundle["engine"] = _engine_snapshot(engine)
    #: the process's program builds (profiler.builds()): which program
    #: compiled when, on which step, and where its wall went
    bundle["builds"] = builds()[-ring_tail:]
    if recorder is not None:
        try:
            tail = recorder.explain_tail(0.0, top=tail_top)
        except Exception:
            tail = []
        bundle["flight_recorder"] = {
            "snapshot": _json_safe(recorder.snapshot(tail=tail)),
            "ring_tail": [_json_safe(r.to_dict())
                          for r in recorder.records()[-ring_tail:]],
            "explain_tail": _json_safe(tail),
        }
    else:
        bundle["flight_recorder"] = None
    if metrics_store is not None:
        bundle["metrics"] = _json_safe(
            metrics_store.snapshot(max_samples=series_tail))
    else:
        bundle["metrics"] = None
    return bundle


def _shrink(bundle):
    """Halve the bundle's variable-size tails in place; returns False
    once nothing shrinkable remains (the caller then drops sections)."""
    shrunk = False
    fr = bundle.get("flight_recorder")
    if isinstance(fr, dict):
        for key in ("ring_tail", "explain_tail"):
            seq = fr.get(key)
            if isinstance(seq, list) and len(seq) > 1:
                fr[key] = seq[-(len(seq) // 2):]
                shrunk = True
    ms = bundle.get("metrics")
    if isinstance(ms, dict):
        for s in ms.get("series", ()):
            tail = s.get("tail")
            if isinstance(tail, list) and len(tail) > 1:
                s["tail"] = tail[-(len(tail) // 2):]
                shrunk = True
    if len(bundle.get("builds") or ()) > 1:
        bundle["builds"] = bundle["builds"][-(len(bundle["builds"]) // 2):]
        shrunk = True
    return shrunk


def write_bundle(bundle, path, max_bytes=262144):
    """Serialize ``bundle`` to ``path``, shrinking its tails until the
    JSON fits ``max_bytes`` (sorted keys — byte-identical bundles for
    identical state). Returns ``path``."""
    data = json.dumps(bundle, sort_keys=True, indent=1)
    while len(data) > max_bytes:
        if not _shrink(bundle):
            # last resort: drop the bulky sections outright, keep the
            # header + server/engine state, and say so
            bundle["flight_recorder"] = None
            bundle["metrics"] = None
            bundle["builds"] = None
            bundle["truncated"] = True
            data = json.dumps(bundle, sort_keys=True, indent=1)
            break
        bundle["truncated"] = True
        data = json.dumps(bundle, sort_keys=True, indent=1)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        f.write(data)
    return path


class BlackBox:
    """The armed automatic dumper: dedup + rotation around
    :func:`collect_bundle`/:func:`write_bundle`.

    * **dedup** — at most one bundle per ``(reason)`` per
      ``dedup_window_s`` (a crash loop or a flapping alert produces a
      bounded trickle, not a flood); the window is per-reason so a hang
      verdict still dumps while a crash window is open.
    * **rotation** — at most ``max_bundles`` files in ``out_dir``;
      oldest (lowest sequence number) deleted first.
    * **bounds** — every file obeys ``max_bytes`` via
      :func:`write_bundle`.

    Thread-safe: the engine thread (crash), the watchdog thread (hang)
    and the serve loop (alert edges) may all dump concurrently."""

    def __init__(self, out_dir="debug_bundles", max_bytes=262144,
                 max_bundles=8, dedup_window_s=30.0, ring_tail=64,
                 series_tail=32, tail_top=16):
        self.out_dir = str(out_dir)
        self.max_bytes = int(max_bytes)
        self.max_bundles = int(max_bundles)
        self.dedup_window_s = float(dedup_window_s)
        self.ring_tail = int(ring_tail)
        self.series_tail = int(series_tail)
        self.tail_top = int(tail_top)
        self._lock = threading.Lock()
        self._last_dump: dict[str, float] = {}   # reason -> monotonic t
        self._seq = 0
        #: every path this instance wrote, newest last (the test-side
        #: record, and the rotation order)
        self.dumped: list[str] = []

    def dump(self, reason, server=None, engine=None, recorder=None,
             metrics_store=None, detail=None, path=None):
        """Collect + write one bundle. Returns the written path, or
        None when the per-reason dedup window suppressed the dump.
        ``path=None`` writes ``bundle_<seq>_<reason>.json`` under
        ``out_dir`` and rotates; an explicit path skips rotation."""
        now = time.monotonic()
        with self._lock:
            last = self._last_dump.get(reason)
            if path is None and last is not None \
                    and now - last < self.dedup_window_s:
                return None
            self._last_dump[reason] = now
            self._seq += 1
            seq = self._seq
        bundle = collect_bundle(
            server=server, engine=engine, recorder=recorder,
            metrics_store=metrics_store, reason=reason, detail=detail,
            ring_tail=self.ring_tail, series_tail=self.series_tail,
            tail_top=self.tail_top)
        bundle["seq"] = seq
        if path is None:
            path = os.path.join(self.out_dir,
                                f"bundle_{seq:04d}_{reason}.json")
            rotate = True
        else:
            rotate = False
        out = write_bundle(bundle, path, max_bytes=self.max_bytes)
        with self._lock:
            self.dumped.append(out)
            if rotate:
                mine = [p for p in self.dumped
                        if os.path.dirname(p) == self.out_dir]
                while len(mine) > self.max_bundles:
                    victim = mine.pop(0)
                    self.dumped.remove(victim)
                    try:
                        os.remove(victim)
                    except OSError:
                        pass
        return out
