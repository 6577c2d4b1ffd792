"""paddle.profiler analog (reference: python/paddle/profiler/profiler.py:358,
utils.py:47 RecordEvent, profiler_statistic.py, timer.py).

Two coordinated layers, like the reference (SURVEY.md §5.1):
1. Host events: RecordEvent context manager -> in-process buffer ->
   export_chrome_tracing writes a chrome://tracing JSON.
2. Device profile: jax.profiler start/stop trace (xplane -> TensorBoard /
   Perfetto), the TPU-native replacement for the CUPTI tracer.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from enum import Enum

from ._span import scope, span  # noqa: F401
from ._build import build, build_retraced, builds, startup  # noqa: F401
from ._host_gc import watch_gc  # noqa: F401
from .timer import benchmark  # noqa: F401
from .serving_telemetry import (  # noqa: F401
    LABELED_GAUGE_FAMILIES, LatencyHistogram, ServingTelemetry)
from .flight_recorder import (  # noqa: F401
    COUNTER_TRACKS, FLOW_EVENT_NAME, FlightRecorder, REQUEST_EVENT_KINDS,
    StepRecord, TAIL_CAUSES)
from .black_box import (  # noqa: F401
    BlackBox, BUNDLE_SCHEMA, collect_bundle, TRIGGER_REASONS,
    write_bundle)
from .metrics_store import (  # noqa: F401
    Alert, ALERT_KINDS, MetricsStore, Series)
from .slo import (  # noqa: F401
    SLO, SLOEngine, default_detectors, evaluate_slo, format_slo_report)

__all__ = [
    "Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent", "span", "scope",
    "build", "build_retraced", "builds", "startup", "watch_gc",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
    "SummaryView", "benchmark", "merge_profile",
    "ServingTelemetry", "LatencyHistogram", "LABELED_GAUGE_FAMILIES",
    "FlightRecorder", "StepRecord", "TAIL_CAUSES",
    "REQUEST_EVENT_KINDS", "COUNTER_TRACKS", "FLOW_EVENT_NAME",
    "BlackBox", "collect_bundle", "write_bundle", "BUNDLE_SCHEMA",
    "TRIGGER_REASONS",
    "MetricsStore", "Series", "Alert", "ALERT_KINDS",
    "SLO", "SLOEngine", "default_detectors", "evaluate_slo",
    "format_slo_report",
]


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class _EventBuffer:
    def __init__(self):
        self.events = []
        self.enabled = False
        self.lock = threading.Lock()

    def add(self, name, ts, dur, tid):
        if self.enabled:
            with self.lock:
                self.events.append({"name": name, "ts": ts, "dur": dur,
                                    "tid": tid})


_BUFFER = _EventBuffer()


class RecordEvent:
    """Host-side scope event (reference: profiler/utils.py:47). While a
    profiler is recording it is also a :func:`span` of the same name, so
    it shows on the host plane of a device profile taken alongside.

    When NO profiler is recording, enter/exit is a single flag check —
    no clock read, no span — so always-on instrumentation (library
    internals wrapping hot paths in RecordEvent) costs ~nothing in
    production. A profiler that starts recording mid-event picks the
    event up from its NEXT entry."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._t0 = None
        self._scope = None

    def begin(self):
        self.__enter__()

    def end(self):
        self.__exit__(None, None, None)

    def __enter__(self):
        if not _BUFFER.enabled:
            self._t0 = None  # disabled fast path: nothing to undo on exit
            self._scope = None
            return self
        self._scope = span(self.name)
        self._scope.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._t0 is None:
            return False  # entered while disabled: no span to record
        t1 = time.perf_counter_ns()
        self._scope.__exit__(*exc)
        _BUFFER.add(self.name, self._t0 / 1e3, (t1 - self._t0) / 1e3,
                    threading.get_ident())
        return False


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Step-state schedule closure (reference: profiler.py make_scheduler)."""
    period = closed + ready + record

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready callback factory (reference: profiler.py:227)."""
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_time_{int(time.time())}"
                                      ".paddle_trace.json")
        prof._export_chrome(path)
        return path
    return handler


def export_protobuf(dir_name, worker_name=None):  # parity stub -> chrome json
    return export_chrome_tracing(dir_name, worker_name)


class Profiler:
    """Reference: profiler/profiler.py:358. step()-driven scheduler states;
    on_trace_ready fires at RECORD_AND_RETURN boundaries.

    When `timer_only=False` and a TPU/devices are present, a jax.profiler trace
    (xplane) is captured alongside host events into `trace_dir`."""

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 trace_dir=None, emit_nvtx=False, custom_device_types=None):
        if scheduler is None:
            self._schedule = lambda step: ProfilerState.RECORD
        elif callable(scheduler):
            self._schedule = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            start, end = scheduler
            self._schedule = make_scheduler(closed=max(start, 0), ready=0,
                                            record=end - start, repeat=1)
        else:
            raise TypeError(f"bad scheduler: {scheduler!r}")
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.trace_dir = trace_dir
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._device_trace_on = False
        self._events_snapshot = []

    # -- lifecycle ------------------------------------------------------
    def start(self):
        self.current_state = self._schedule(self.step_num)
        self._apply_state()

    def stop(self):
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._finish_record()
        _BUFFER.enabled = False
        self._stop_device_trace()
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples=None):
        benchmark().step(num_samples)
        old = self.current_state
        self.step_num += 1
        self.current_state = self._schedule(self.step_num)
        recording = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        # finish on the scheduled boundary OR any transition out of recording
        if old == ProfilerState.RECORD_AND_RETURN or (
                old in recording and self.current_state not in recording):
            self._finish_record()
        self._apply_state()

    def step_info(self, unit=None):
        return benchmark().step_info(unit)

    def _apply_state(self):
        st = self.current_state
        _BUFFER.enabled = st in (ProfilerState.RECORD,
                                 ProfilerState.RECORD_AND_RETURN)
        if _BUFFER.enabled and not self.timer_only:
            self._start_device_trace()
        elif not _BUFFER.enabled:
            self._stop_device_trace()

    def _start_device_trace(self):
        if self._device_trace_on or self.trace_dir is None:
            return
        try:
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self._device_trace_on = True
        except Exception:
            self._device_trace_on = False

    def _stop_device_trace(self):
        if self._device_trace_on:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_trace_on = False

    def _finish_record(self):
        with _BUFFER.lock:
            self._events_snapshot = list(_BUFFER.events)
            _BUFFER.events.clear()
        self._stop_device_trace()
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- export/summary -------------------------------------------------
    def _export_chrome(self, path):
        events = [{"ph": "X", "cat": "host", "pid": os.getpid(),
                   "tid": e["tid"], "name": e["name"], "ts": e["ts"],
                   "dur": e["dur"]} for e in self._events_snapshot]
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)

    def export(self, path, format="json"):
        self._export_chrome(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated host-event table (reference: profiler_statistic.py)."""
        agg = {}
        for e in self._events_snapshot:
            a = agg.setdefault(e["name"], [0, 0.0, 0.0])
            a[0] += 1
            a[1] += e["dur"]
            a[2] = max(a[2], e["dur"])
        div = {"ms": 1e3, "us": 1.0, "s": 1e6}[time_unit]
        lines = [f"{'Name':<40} {'Calls':>8} {'Total(' + time_unit + ')':>14} "
                 f"{'Avg':>10} {'Max':>10}"]
        for name, (cnt, tot, mx) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name[:40]:<40} {cnt:>8} {tot / div:>14.4f} "
                         f"{tot / cnt / div:>10.4f} {mx / div:>10.4f}")
        table = "\n".join(lines)
        print(table)
        return table


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def load_profiler_result(filename):
    """Load an exported chrome-trace JSON back as a list of events."""
    with open(filename) as f:
        return json.load(f).get("traceEvents", [])


def merge_profile(rank_dirs_or_files, output_path, align_start=True):
    """Merge per-rank chrome traces into one cluster-wide timeline.

    Reference: tools/CrossStackProfiler/ (merges per-rank profiles into a
    single view for cluster-wide hang/straggler diagnosis — SURVEY.md §5.1).
    Each rank's events land in their own process lane (pid = rank index, with
    a process_name metadata row); with align_start, per-rank clocks are
    shifted so every rank's first event starts at t=0, compensating unsynced
    host clocks.
    """
    import glob
    import re

    def _natural(s):
        return [int(t) if t.isdigit() else t
                for t in re.split(r"(\d+)", os.path.basename(s))]

    files = []
    for entry in rank_dirs_or_files:
        if os.path.isdir(entry):
            # natural sort so rank10 sorts after rank9, not after rank1
            files.extend(sorted(glob.glob(os.path.join(entry, "*.json")),
                                key=_natural))
        else:
            files.append(entry)
    if not files:
        raise ValueError("no trace files to merge")

    merged = []
    for rank, path in enumerate(files):
        with open(path) as f:
            trace = json.load(f)
        events = trace["traceEvents"] if isinstance(trace, dict) else trace
        t0 = min((e["ts"] for e in events
                  if e.get("ph") != "M" and "ts" in e), default=0)
        shift = -t0 if align_start else 0
        label = os.path.splitext(os.path.basename(path))[0]
        merged.append({"ph": "M", "pid": rank, "name": "process_name",
                       "args": {"name": f"rank{rank}:{label}"}})
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                continue  # replaced by the rank lane name
            e = dict(e)
            e["pid"] = rank
            if "ts" in e:
                e["ts"] = e["ts"] + shift
            merged.append(e)

    os.makedirs(os.path.dirname(output_path) or ".", exist_ok=True)
    with open(output_path, "w") as f:
        json.dump({"traceEvents": merged}, f)
    return output_path


class SortedKeys(Enum):
    """Sort orders for summary tables (reference: profiler/profiler.py
    SortedKeys)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


__all__.append("SortedKeys")
