"""Per-layer metrics that read what the program says of itself: its
``pt:<layer>.<phase>`` host spans (``paddle_tpu.profiler.span``) on the
profile's own clock, laid against the device's intervals, and the counters
``engine.stats`` carries where rows are padded and requests wait.

The device intervals and the window come from ``ctx["trace"]`` as the
harness reduced it; the ``pt:`` spans are read here, once, from the newest
profile under the cell's trace directory, and cached on ``ctx``. A program
that writes no such span or counter (the parent of the PR that brought
them) gives None everywhere, and the line leaves the metric out."""
from __future__ import annotations

from .trace import OPS_LINE, newest_xplane

PREFIX = "pt:"
#: spans in which the host waits (for work; for the device's tokens):
#: every other ``pt:`` span is host work
WAITS = ("pt:server.idle", "pt:engine.sync")


def log(msg):
    print(msg, flush=True)


# -- the spans ----------------------------------------------------------------
class Span:
    """One ``pt:`` span: [start, end) in the profile's ns, its name, its
    integer stats, the spans nested directly inside it (same thread) and
    its parent."""
    __slots__ = ("start", "end", "name", "ids", "children", "parent")

    def __init__(self, start, end, name, ids):
        self.start, self.end, self.name, self.ids = start, end, name, ids
        self.children, self.parent = [], None

    def self_ns(self, lo, hi):
        """Its length inside [lo, hi] less its children's."""
        def clip(s):
            return max(0, min(s.end, hi) - max(s.start, lo))
        return clip(self) - sum(clip(c) for c in self.children)


def nest(rows):
    """``rows``: [(start, end, name, ids)] of ONE thread. Returns the
    spans, each knowing its children and parent (a span that starts with
    its parent and ends with it is its child)."""
    spans = [Span(*r) for r in sorted(rows, key=lambda r: (r[0], -r[1]))]
    stack = []
    for s in spans:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack and s.end <= stack[-1].end:
            s.parent = stack[-1]
            stack[-1].children.append(s)
        stack.append(s)
    return spans


def read_lines(data):
    """{line index: spans} of every thread of a profile that carries a
    ``pt:`` span (``data``: a ``jax.profiler.ProfileData``)."""
    lines, k = {}, 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            rows = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                     ev.name, dict(ev.stats))
                    for ev in line.events if ev.name.startswith(PREFIX)]
            if rows:
                lines[k] = nest(rows)
            k += 1
    return lines


def merged(intervals):
    """Sorted, disjoint intervals covering the same instants."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def overlap_ns(a, b):
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


class Inside:
    """The ``pt:`` spans of a profile against one chip's busy intervals
    inside the window."""

    def __init__(self, lines, busy, window):
        self.lines, self.window = lines, window
        lo, hi = window
        self.busy = sorted((max(s, lo), min(e, hi)) for s, e in busy
                           if e > lo and s < hi)

    @property
    def spans(self):
        return [s for line in self.lines.values() for s in line]

    def named(self, prefix, wholly=True):
        lo, hi = self.window
        return [s for s in self.spans if s.name.startswith(prefix)
                and ((s.start >= lo and s.end <= hi) if wholly
                     else (s.end > lo and s.start < hi))]

    def gaps(self):
        """The chip's idle intervals inside the window."""
        lo, hi = self.window
        out, cur = [], lo
        for s, e in self.busy:
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            out.append((cur, hi))
        return out

    def _cover(self, keep):
        """Intervals in which some thread's innermost span passes
        ``keep`` (a span's own time: its length less its children's)."""
        out = []
        for line in self.lines.values():
            for s in line:
                if not keep(s):
                    continue
                cur = s.start
                for c in sorted(s.children, key=lambda c: c.start):
                    if c.start > cur:
                        out.append((cur, c.start))
                    cur = max(cur, c.end)
                if s.end > cur:
                    out.append((cur, s.end))
        return out

    def idle_split(self):
        """Seconds of the chip's idle time under a host work span, under
        a wait span and under none."""
        gaps = self.gaps()
        total = sum(hi - lo for lo, hi in gaps)
        work = overlap_ns(gaps, merged(self._cover(
            lambda s: s.name not in WAITS)))
        wait = overlap_ns(gaps, merged(self._cover(
            lambda s: s.name in WAITS)))
        return work / 1e9, wait / 1e9, max(total - work - wait, 0) / 1e9

    def innermost(self, t):
        """The ``pt:`` span over instant ``t`` that started last (of two
        that started together, the shorter: the child)."""
        over = [s for s in self.spans if s.start <= t < s.end]
        return max(over, key=lambda s: (s.start, -s.end)) if over else None

    def longest_gaps(self, n=10):
        """[(seconds, span name or None, its step id or None)] of the
        longest idle gaps, each named by the innermost span over its
        middle; the step id is the span's own or its nearest ancestor's."""
        out = []
        for lo, hi in sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]:
            s = self.innermost((lo + hi) // 2)
            sid, a = None, s
            while a is not None and sid is None:
                sid, a = a.ids.get("step_id", a.ids.get("step")), a.parent
            out.append(((hi - lo) / 1e9, s.name if s else None, sid))
        return out


def inside(ctx):
    """The run's :class:`Inside`, read once; None where there is no trace
    or the program wrote no ``pt:`` span into it."""
    if "inside" in ctx:
        return ctx["inside"]
    ctx["inside"] = None
    tr = ctx.get("trace")
    if tr is None:
        return None
    from jax.profiler import ProfileData
    lines = read_lines(ProfileData.from_file(
        newest_xplane(ctx["cell"].trace_dir)))
    if not lines:
        log("[inside] the program wrote no pt: span into the profile")
        return None
    chip = min(tr.chips)
    ins = Inside(lines, [(s, e) for s, e, _ in tr.chips[chip][OPS_LINE]],
                 tr.window)
    ctx["inside"] = ins
    work, wait, none = ins.idle_split()
    log(f"[inside] chip {chip} idle in the stretch: {work * 1e3:.3f} ms "
        f"under host work spans, {wait * 1e3:.3f} ms under wait spans "
        f"{WAITS}, {none * 1e3:.3f} ms under none")
    for secs, name, sid in ins.longest_gaps():
        log(f"[inside] idle gap {secs * 1e6:.1f} us under "
            f"{name or 'no pt: span'} (step {sid})")
    by = {}
    for s in ins.named(PREFIX):
        by.setdefault(s.name, []).append((s.end - s.start) / 1e6)
    for name, ms in sorted(by.items()):
        ms.sort()
        log(f"[inside] {name}: {len(ms)} spans, {sum(ms):.3f} ms; each "
            f"mean {sum(ms) / len(ms):.4f}, median {ms[len(ms) // 2]:.4f}, "
            f"longest {ms[-1]:.4f} ms")
    return ins


# -- the metrics read from the trace --------------------------------------------
def idle_by_program_pct(ctx):
    """Share of the window in which the chip was idle while the program's
    host code was at work (not waiting, and not outside every span)."""
    ins = inside(ctx)
    if ins is None:
        return None
    lo, hi = ins.window
    return 100.0 * ins.idle_split()[0] / ((hi - lo) / 1e9)


def server_self_ms_per_step(ctx):
    """What the server loop itself costs a step: the ``pt:server.*``
    spans' own time (children and ``idle`` left out) per
    ``pt:engine.dispatch`` span of the stretch."""
    ins = inside(ctx)
    if ins is None:
        return None
    steps = len(ins.named("pt:engine.dispatch"))
    if not steps:
        return None
    lo, hi = ins.window
    own = sum(s.self_ns(lo, hi) for s in ins.named("pt:server.", False)
              if s.name not in WAITS)
    return own / 1e6 / steps


def dispatch_host_ms(ctx):
    """Mean host time of a ``TrainStep`` call (``pt:train.step``)."""
    ins = inside(ctx)
    if ins is None:
        return None
    steps = ins.named("pt:train.step")
    if not steps:
        return None
    return sum(s.end - s.start for s in steps) / 1e6 / len(steps)


# -- the metrics read from engine.stats -------------------------------------------
def _delta(ctx, *keys):
    """Σ of the window's deltas of ``keys``; None where the run kept no
    such counters."""
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if not s0 or not s1 or any(k not in s0 for k in keys):
        return None
    return sum(s1[k] - s0[k] for k in keys)


def _ratio(ctx, num, den, scale):
    n, d = _delta(ctx, *num), _delta(ctx, *den)
    if n is None or not d:
        return None
    return scale * n / d


def row_occupancy_pct(ctx):
    """Tokens the window's steps were asked for over the rows their
    programs computed."""
    return _ratio(ctx, ("prefill_tokens", "tokens_generated"),
                  ("rows_computed",), 100.0)


def slot_wait_ms(ctx):
    """Mean wait of a request in its slot before its first prefill grant."""
    return _ratio(ctx, ("slot_wait_time_s",), ("first_grants",), 1e3)


def kv_grid_live_pct(ctx):
    """Block-table entries holding a live token over those the paged
    attention grid walked."""
    return _ratio(ctx, ("kv_live_blocks",), ("kv_grid_blocks",), 100.0)


def program_build_s(ctx):
    """Seconds the engine spent in first calls of its programs (build,
    or load from the compile cache) — all of it before the window."""
    s0 = ctx.get("stats0")
    if not s0 or "program_build_time_s" not in s0:
        return None
    log(f"[inside] programs built before the window: "
        f"{s0['programs_built']} in {s0['program_build_time_s']:.3f}s; "
        f"inside it (must be 0): {_delta(ctx, 'programs_built')} in "
        f"{_delta(ctx, 'program_build_time_s'):.3f}s")
    return s0["program_build_time_s"]


# -- until BENCHMARK.json and the cells' files list them: the benchmark PR that
# -- appends the names there deletes METRICS, extend() and tools/inside_run.py ----
#: the metrics of this module, by the cell that reports them
METRICS = {
    "doc_batch": ["row_occupancy_pct.batch", "slot_wait_ms.batch",
                  "kv_grid_live_pct.batch", "program_build_s.batch",
                  "server_self_ms_per_step.batch",
                  "idle_by_program_pct.batch"],
    "pretrain_2k": ["idle_by_program_pct.train", "dispatch_host_ms.train"],
}


def extend(cell):
    """``cell`` reporting this module's metrics too in a traced run, their
    units from their own files. For the builder's runs and the rehearsals
    (``tools/inside_run.py``, ``tests/test_inside.py``): a cell's list is
    its file's, which only a benchmark PR may edit (PERF.md section 7)."""
    from . import loader
    names = METRICS.get(cell.name, [])
    listed = cell.declared

    def declared(trace):
        out = listed(trace)
        if trace:
            out.update({n: loader.module("metrics", n).UNIT for n in names})
        return out
    cell.declared = declared
    return cell
