"""The collector's pauses, named. Python's cyclic collector stops the
thread that tripped it (and, holding the interpreter lock, every other)
for as long as a collection takes, and announces each one through
``gc.callbacks``. ONE entry, installed when the first engine is built,
makes ``start`` -> ``stop`` a ``pt:host.gc`` span (``generation``,
``pc_ns``: ``perf_counter_ns`` at its start, the clock
``pt:engine.dispatch`` lays on a profile; ``collected``) and adds the
pause to ``gc_pause_time_s`` / ``gc_pauses`` / ``gc_pause_max_s`` of every
live engine's ``stats``. It is host work, not a wait: a gap of the device
under it is the host's."""
from __future__ import annotations

import gc
import time
import weakref

from ._span import span

__all__ = ["watch_gc"]

_engines = weakref.WeakSet()
#: the collection in progress: (its span, perf_counter_ns at its start).
#: Collections never overlap (the collector refuses to re-enter), so one
#: slot serves every thread
_open = None


def _on_gc(phase, info):
    global _open
    if phase == "start":
        pc_ns = time.perf_counter_ns()
        ann = span("pt:host.gc", generation=info["generation"], pc_ns=pc_ns)
        ann.__enter__()
        _open = (ann, pc_ns)
    elif _open is not None:
        (ann, pc_ns), _open = _open, None
        ann.set_metadata(collected=info["collected"])
        ann.__exit__(None, None, None)
        secs = (time.perf_counter_ns() - pc_ns) / 1e9
        for eng in list(_engines):
            stats = eng.stats
            stats["gc_pause_time_s"] += secs
            stats["gc_pauses"] += 1
            if secs > stats["gc_pause_max_s"]:
                stats["gc_pause_max_s"] = secs


def watch_gc(engine):
    """Book the collector's pauses into ``engine.stats`` for as long as
    the engine lives; the first call installs the callback."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    _engines.add(engine)
