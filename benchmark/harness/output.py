"""The last line of a run, built from the cell's declared metric names and
checked against the driver's contract before it is printed.

A violation exits non-zero and names the field: a line that only looks
right is worse than none (PR 23 was refused over one traced run's last
line)."""
from __future__ import annotations

import json
import math
import os
import sys


class OutputError(ValueError):
    """The result does not meet the output contract; str() names the field."""


def _number(path, v):
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise OutputError(f"{path}: {v!r} is not a number")
    if not math.isfinite(v):
        raise OutputError(f"{path}: {v!r} is not finite")
    return v


def validate(obj, declared, trace, chips=None):
    """Check ``obj`` (the parsed last line) against the contract.

    ``declared``: {metric name: unit} the cell must report in this mode
    (its end-to-end metrics with ``trace`` false, its per-layer metrics
    with it true; a per-layer reader that found nothing to read is simply
    absent from ``declared``). Returns ``obj``; raises OutputError."""
    if not isinstance(obj, dict):
        raise OutputError("result: not a JSON object")
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in obj:
            raise OutputError(f"{key}: missing")
    if not isinstance(obj["correct"], bool):
        raise OutputError(f"correct: {obj['correct']!r} is not a boolean")
    for key in ("attempted", "failed"):
        v = obj[key]
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            raise OutputError(f"{key}: {v!r} is not a count")
    if obj["failed"] > obj["attempted"]:
        raise OutputError("failed: more than attempted")
    metrics = obj["metrics"]
    if not isinstance(metrics, dict) or not metrics:
        raise OutputError("metrics: empty or not an object")
    for name in declared:
        if name not in metrics:
            raise OutputError(f"metrics.{name}: declared by the cell, "
                              f"missing from the line")
    for name, m in metrics.items():
        if name not in declared:
            raise OutputError(f"metrics.{name}: not declared by the cell")
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            raise OutputError(f"metrics.{name}: wants value and unit")
        _number(f"metrics.{name}.value", m["value"])
        if m["unit"] != declared[name]:
            raise OutputError(f"metrics.{name}.unit: {m['unit']!r}, "
                              f"declared {declared[name]!r}")
        if ("roofline" in name or "mfu" in name) \
                and not 0 <= m["value"] <= 105:
            raise OutputError(f"metrics.{name}.value: {m['value']} is a "
                              f"share of a peak outside 0..105")
    dev = obj["device"]
    if not isinstance(dev, dict):
        raise OutputError("device: not an object")
    for key in ("platform", "kind"):
        if not isinstance(dev.get(key), str) or not dev[key]:
            raise OutputError(f"device.{key}: missing")
    for key in ("count", "memory_peak_bytes"):
        v = dev.get(key)
        if isinstance(v, bool) or not isinstance(v, int) or v <= 0:
            raise OutputError(f"device.{key}: {v!r} is not a positive "
                              f"whole number")
    if chips is not None and dev["count"] < chips:
        raise OutputError(f"device.count: {dev['count']} < the cell's "
                          f"{chips} chips")
    if trace:
        for key in ("busy_s", "window_s"):
            _number(f"device.{key}", dev.get(key))
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise OutputError(
                f"device.busy_s: {dev['busy_s']} is not above 0 and at "
                f"most window_s {dev['window_s']}")
    if "breakdown" in obj:
        bd = obj["breakdown"]
        if not isinstance(bd, dict) or \
                set(bd) - {"device_ops", "idle_gaps"}:
            raise OutputError("breakdown: wants device_ops and idle_gaps")
        for key, rows in bd.items():
            if not isinstance(rows, list) or len(rows) > 10:
                raise OutputError(f"breakdown.{key}: at most 10 entries")
            for i, row in enumerate(rows):
                if not (isinstance(row, list) and len(row) == 2
                        and isinstance(row[0], str)):
                    raise OutputError(f"breakdown.{key}[{i}]: wants "
                                      f"[name, seconds]")
                _number(f"breakdown.{key}[{i}][1]", row[1])
    return obj


def build(correct, attempted, failed, values, declared, device, trace,
          breakdown=None):
    """The result object: ``values`` {name: number} against ``declared``
    {name: unit}."""
    obj = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {n: {"value": values[n], "unit": declared[n]}
                       for n in values},
           "device": device}
    if trace and breakdown:
        obj["breakdown"] = breakdown
    return obj


def dumps(obj, declared, trace, chips=None):
    """Validate, serialise strictly (no NaN can pass), parse the text back
    and validate that too: what is printed is what was checked."""
    validate(obj, declared, trace, chips)
    try:
        text = json.dumps(obj, allow_nan=False, separators=(", ", ": "))
    except ValueError as e:
        raise OutputError(f"result: {e}") from None
    if "\n" in text:
        raise OutputError("result: more than one line")
    validate(json.loads(text), declared, trace, chips)
    return text


def finish(text):
    """Print the line last, flush, and leave at once so that nothing — a
    profiler, libtpu or a server thread at teardown — prints after it."""
    sys.stderr.flush()
    sys.stdout.write(text + "\n")
    sys.stdout.flush()
    os._exit(0)
