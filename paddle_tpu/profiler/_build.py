"""A program build, booked where it opens: :func:`build` is the ONE way
``LLMEngine._program`` (``pt:engine.build``) and ``TrainStep``
(``pt:train.build``) account a first call, and what it leaves behind is a
record of where the call's wall went.

jax times its own compile pipeline and says so through ``jax.monitoring``:
``jaxpr_trace_duration`` (the Python trace), ``jaxpr_to_mlir_module_duration``
(the lowering) and ``backend_compile_duration`` (``compile_or_get_cached``:
the compile, or on a persistent-cache hit the executable's read), and the
events ``cache_hits`` / ``cache_misses``. ONE duration listener and ONE
event listener, registered at the first build, lay them on the
``perf_counter`` clock a thread; they fire on compile events only, never
on a call that found its program compiled.

**A nested trace is not summed.** A program that calls jitted functions
inside its own trace fires ``jaxpr_trace_duration`` for each of them, and
their seconds lie INSIDE the outer program's (a lowering rule that traces
a function does the same inside the lowering). An event arrives when it
ends, inner ones first, so each thread keeps only intervals that no later
one contains: an arriving event takes out those that began after it did.
What is left is disjoint, and a record's ``trace_s + lower_s +
compile_or_load_s`` is at most its ``wall_s``."""
from __future__ import annotations

import collections
import contextlib
import logging
import threading
import time

import jax.monitoring

from ._span import span

__all__ = ["build", "build_retraced", "builds", "startup"]

_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_load_s",
}
_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}
_SUMMED = tuple(_PARTS.values()) + tuple(_COUNTS.values())

#: compile events of a thread that no build has claimed yet, kept for a
#: retrace to claim once its call is back; older ones fall off
_UNCLAIMED = 64
#: a retrace claims the events that began after its call did, less this
#: much: the two clocks (jax's durations, our stamps) agree to far better
_SLACK_S = 1e-3

_log = logging.getLogger("paddle_tpu.profiler")


class _Thread(threading.local):
    """One thread's compile events that no later one contains, oldest
    first: ``[began (perf_counter s), part, seconds, hits, misses]``.
    ``floor``: the open build's first index (events under it are
    another's and stay, and nothing is trimmed); None with no build
    open."""

    def __init__(self):
        self.events, self.floor = [], None


_thread = _Thread()
_lock = threading.Lock()
_records = collections.deque(maxlen=512)
_totals = {**dict.fromkeys(_PARTS.values(), 0.0), "wall_s": 0.0,
           **dict.fromkeys(_COUNTS.values(), 0), "builds": 0, "retraces": 0}
_listening = False


def _arrived(began, part, secs, hits=0, misses=0):
    events, floor = _thread.events, _thread.floor
    while len(events) > (floor or 0) and events[-1][0] >= began:
        inner = events.pop()
        hits, misses = hits + inner[3], misses + inner[4]
    events.append([began, part, secs, hits, misses])
    if floor is None and len(events) > _UNCLAIMED:
        del events[0]


def _on_duration(event, secs, **_):
    part = _PARTS.get(event)
    if part is not None:
        _arrived(time.perf_counter() - secs, part, secs)


def _on_event(event, **_):
    count = _COUNTS.get(event)
    if count is not None:
        _arrived(time.perf_counter(), None, 0.0,
                 int(count == "cache_hits"), int(count == "cache_misses"))


def _listen():
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True


def _book(span_name, program, pc_ns, wall_s, events, retrace, step_id):
    rec = {"program": program,
           "owner": span_name.partition(":")[2].partition(".")[0],
           "pc_ns": pc_ns, "wall_s": wall_s,
           **dict.fromkeys(_PARTS.values(), 0.0),
           **dict.fromkeys(_COUNTS.values(), 0), "retrace": retrace}
    for _, part, secs, hits, misses in events:
        if part is not None:
            rec[part] += secs
        rec["cache_hits"] += hits
        rec["cache_misses"] += misses
    if step_id is not None:
        rec["step_id"] = step_id
    with _lock:
        _records.append(rec)
        for key in _SUMMED + ("wall_s",):
            _totals[key] += rec[key]
        _totals["retraces" if retrace else "builds"] += 1
    return rec


@contextlib.contextmanager
def build(span_name, program, /, step_id=None, **ids):
    """A program's first call: the span ``span_name`` (``pt:engine.build``
    / ``pt:train.build``, ``ids`` riding on it: the engine's ``program`` is
    an index, so the name is positional), and on exit one record of
    the call in :func:`builds`: ``program`` (its name), ``owner`` (the
    span's layer: ``engine`` / ``train``), ``pc_ns`` (``perf_counter_ns``
    at entry, the clock ``pt:engine.dispatch`` lays on a profile),
    ``wall_s``, and of the compile events this thread fired while the
    build was open ``trace_s``, ``lower_s``, ``compile_or_load_s``,
    ``cache_hits`` and ``cache_misses``; ``retrace`` False; ``step_id``
    (the engine step the build rode on) where one is given."""
    _listen()
    events, outer = _thread.events, _thread.floor
    base = _thread.floor = len(events)
    pc_ns = time.perf_counter_ns()
    try:
        with span(span_name, **ids):
            yield
    finally:
        wall_s = (time.perf_counter_ns() - pc_ns) / 1e9
        _thread.floor = outer
        _book(span_name, program, pc_ns, wall_s, events[base:], False,
              step_id)
        del events[base:]


def build_retraced(span_name, program, t0, wall_s, step_id=None):
    """A later call of a built program that compiled again (a new
    argument structure), known only now that it is back: ``t0`` is the
    ``perf_counter()`` it began at, ``wall_s`` its wall. Books the
    record a build would have, ``retrace`` True, from this thread's
    compile events since ``t0``, and logs ONE warning that names the
    program, the step and the wall."""
    _listen()
    events = _thread.events
    n = len(events)
    while n > (_thread.floor or 0) and events[n - 1][0] >= t0 - _SLACK_S:
        n -= 1
    rec = _book(span_name, program, int(t0 * 1e9), wall_s, events[n:], True,
                step_id)
    del events[n:]
    _log.warning(
        "%s program %r compiled again at step %s: %.3fs (trace %.3f, lower "
        "%.3f, compile or load %.3f; cache hits %d, misses %d)",
        rec["owner"], program, step_id, wall_s, rec["trace_s"],
        rec["lower_s"], rec["compile_or_load_s"], rec["cache_hits"],
        rec["cache_misses"])
    return rec


def builds():
    """The process's build records, oldest first (the newest 512)."""
    with _lock:
        return [dict(r) for r in _records]


def startup():
    """The process's start-up as the program itself can account it: the
    totals of every build record ever booked (``trace_s``, ``lower_s``,
    ``compile_or_load_s``, ``wall_s``, ``builds``, ``retraces``,
    ``cache_hits``, ``cache_misses``) beside the package's import
    (``import_s``: the wall of ``paddle_tpu/__init__.py`` from its first
    line to its last; ``jax_preimported``: whether ``jax``, which it
    imports first thing, was loaded already)."""
    import paddle_tpu
    with _lock:
        out = dict(_totals)
    out["import_s"], out["jax_preimported"] = paddle_tpu._IMPORTED
    return out
