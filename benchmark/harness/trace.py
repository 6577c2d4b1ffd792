"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to what the per-layer metrics need.

A device plane carries overlapping families of spans, one per line:
whole programs ("XLA Modules"), single operations ("XLA Ops") and step
markers. Kernel time is a SUM over one name within the operations line;
busy time is the UNION of the operations line's intervals, clipped to the
traced window, per chip — a sum over families or chips would pass the
window. The window is one host annotation, so that it and the device
intervals are on the profile's one clock."""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans the benchmark itself wraps around its calls into the system
HOST_SPAN_PREFIX = "bench:"


#: operations that only contain others (their bodies are on the line too)
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")


#: names XLA gives by the hundred: kept apart, one row an operation
_GENERIC = re.compile(
    r"(fusion|^copy|^convert|^bitcast|^reshape|^transpose|^broadcast|"
    r"^slice|^concatenate|^pad|^select|^reduce|^dynamic|^custom-call)"
    r"[-\w]*$")


class TraceError(RuntimeError):
    pass


def family(name):
    """The row an operation is counted under in the breakdown: a kernel's
    calls (one operation a layer, ``paged_attention_decode.160`` ...
    ``.175``) go under the kernel's name, XLA's own fusions stay apart."""
    base = re.sub(r"\.\d+$", "", name)
    return name if _GENERIC.search(base) else base


def op_name(text):
    """An operation's event carries its whole HLO text, ``%name = shape
    op(operands)``: the name alone identifies it, and a pattern must not
    match an operand's name by accident. A Pallas kernel's operation is
    named after the kernel (``paged_attention_decode.175``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def newest_xplane(log_dir):
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise TraceError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union_seconds(intervals, lo, hi):
    """Length in seconds of the union of [start, end) ns intervals,
    clipped to [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


class Trace:
    """``chips``: {ordinal: {line name: [(start_ns, end_ns, name)]}};
    ``host``: [(start_ns, end_ns, name)] of the benchmark's own spans;
    ``window``: (start_ns, end_ns) of the WINDOW annotation."""

    def __init__(self, chips, host, window):
        if not chips:
            raise TraceError("the trace has no device plane")
        self.chips, self.host, self.window = chips, host, window

    @classmethod
    def from_profile(cls, data):
        chips, host, window = {}, [], None
        seen = []
        for plane in data.planes:
            seen.append(plane.name)
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m:
                    rows = chips.setdefault(int(m.group(1)), {}) \
                        .setdefault(line.name, [])
                    for ev in line.events:
                        rows.append((int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     op_name(ev.name)))
                    continue
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                    elif ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     ev.name))
        if not chips:
            raise TraceError(f"no device plane among {seen}")
        if window is None:
            raise TraceError(f"no {WINDOW!r} annotation in the trace "
                             f"(planes: {seen})")
        return cls(chips, host, window)

    @classmethod
    def from_file(cls, path, **kw):
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path), **kw)

    # -- the numbers ------------------------------------------------------
    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def _line(self, chip, name):
        lines = self.chips[chip]
        if name not in lines:
            raise TraceError(f"chip {chip} has no line {name!r} (it has "
                             f"{sorted(lines)})")
        return lines[name]

    def busy_s(self):
        """Mean over chips of the union of the operations line's intervals
        inside the window."""
        lo, hi = self.window
        per = [union_seconds([(s, e) for s, e, _ in self._line(c, OPS_LINE)],
                             lo, hi) for c in sorted(self.chips)]
        return sum(per) / len(per)

    def _inside(self, chip, line):
        lo, hi = self.window
        for s, e, name in self._line(chip, line):
            if s >= lo and e <= hi:
                yield s, e, name

    def op_seconds(self, pattern, line=OPS_LINE):
        """Mean over chips of (summed seconds, count) of the events whose
        name matches ``pattern`` and that lie wholly inside the window.
        None of them anywhere is an error that names what was seen."""
        rx = re.compile(pattern)
        secs, count = [], []
        for c in sorted(self.chips):
            hit = [(e - s) for s, e, n in self._inside(c, line)
                   if rx.search(n)]
            secs.append(sum(hit) / 1e9)
            count.append(len(hit))
        if not any(count):
            names = sorted({n for c in self.chips
                            for _, _, n in self._inside(c, line)})
            raise TraceError(
                f"no event matching {pattern!r} on line {line!r} inside "
                f"the window; names seen: {names[:60]}")
        return sum(secs) / len(secs), sum(count) / len(count)

    def top_ops(self, n=10, chip=None):
        chip = min(self.chips) if chip is None else chip
        agg = {}
        for s, e, name in self._inside(chip, OPS_LINE):
            if not CONTAINERS.match(name):
                key = family(name)
                agg[key] = agg.get(key, 0) + (e - s)
        rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in rows]

    def idle_gaps(self, n=10, chip=None):
        """The longest idle gaps of one chip inside the window, each named
        by the benchmark's host span that covers its middle."""
        chip = min(self.chips) if chip is None else chip
        lo, hi = self.window
        ivs = sorted((max(s, lo), min(e, hi))
                     for s, e, _ in self._line(chip, OPS_LINE)
                     if e > lo and s < hi)
        gaps, cur = [], lo
        for s, e in ivs:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            owner = [name for hs, he, name in self.host if hs <= mid < he]
            out.append([owner[-1] if owner else "host:unattributed",
                        (e - s) / 1e9])
        return out
