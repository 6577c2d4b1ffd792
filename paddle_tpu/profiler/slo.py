"""SLO engine + live pathology detectors over the metrics store.

Two halves of the sensor layer ROADMAP item 4's fleet controller will
close its loop against:

* **SLO engine** — declarative latency objectives
  (``SLO(metric="ttft_p99", tenant=3, target_s=0.25, window_s=60)``)
  evaluated from the :class:`~paddle_tpu.profiler.metrics_store
  .MetricsStore`'s windowed latency samples with Google-SRE-style
  MULTI-WINDOW burn-rate alerting: the error budget is ``1 -
  objective`` (a p99 target budgets 1% bad events), the burn rate of a
  window is ``bad_fraction / budget`` (1.0 = burning exactly the
  budget), and the alert condition requires the FAST window (recent,
  catches the onset and clears quickly on recovery) AND the SLOW
  window (sustained, immune to one bad sample) to both burn past the
  threshold — the standard trade that keeps pages fast without
  flapping on blips. Results surface as ``slo_report()`` (JSON +
  human text) and as the ``slo_burn_rate{slo=...}`` /
  ``slo_breached{slo=...}`` telemetry gauges.
* **pathology detectors** — the ``explain_tail`` cause taxonomy
  promoted from post-hoc to STREAMING: each detector subscribes to the
  flight recorder's completed StepRecords
  (:meth:`FlightRecorder.subscribe`) and watches a bounded window of
  recent steps for its shape — ramp-thrash (preempt/admit churn with
  zero committed decode progress), host-sync regression (sync share of
  stride-1 step wall above budget), speculative-acceptance collapse,
  adapter-swap storm, swap-stall dominance. A firing detector raises a
  structured :class:`~paddle_tpu.profiler.metrics_store.Alert` into
  the store and flips the ``pathology_active{kind=...}`` gauge; it
  clears both when the window recovers.

Every metric family and alert kind here is STRICT-NAMED: the PTL007
pass (``paddle_tpu.analysis.slo_names``) checks detector kinds and
``set_labeled_gauge`` call sites against the
:data:`~paddle_tpu.profiler.metrics_store.ALERT_KINDS` /
``LABELED_GAUGE_FAMILIES`` registries at lint time.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import threading
import time

from .metrics_store import nearest_rank_quantile as _quantile

__all__ = ["SLO", "SLOEngine", "evaluate_slo", "format_slo_report",
           "format_fleet_report", "default_detectors",
           "RampThrashDetector", "HostSyncRegressionDetector",
           "SpecCollapseDetector", "AdapterSwapStormDetector",
           "SwapStallDetector", "SLO_METRIC_BASES"]

#: latency families an SLO metric may target — each maps to the store
#: series the server feeds (``<base>_s``, labeled ``tenant="i"``) and
#: to the per-tenant telemetry histograms of the same name.
SLO_METRIC_BASES = ("ttft", "inter_token", "e2e", "queue_wait")

_METRIC_RE = re.compile(
    r"^(?P<base>" + "|".join(SLO_METRIC_BASES) + r")_p(?P<pct>\d{2})$")


@dataclasses.dataclass(frozen=True)
class SLO:
    """One declarative latency objective.

    ``metric``: ``"<base>_p<NN>"`` with base one of
    :data:`SLO_METRIC_BASES` — e.g. ``"ttft_p99"`` reads "the p99 of
    TTFT must stay under ``target_s``". ``tenant``: an adapter id to
    scope the objective to one tenant's traffic (None = all traffic).
    ``window_s`` is the SLOW alert window; ``fast_window_s`` defaults
    to ``window_s / 12`` (the SRE workbook's 1h:5m ratio).
    ``burn_threshold``: both windows must burn at this multiple of the
    error budget before the alert fires (1.0 = burning exactly the
    budget; the default 6.0 pages on a budget that would exhaust in
    window/6)."""
    name: str
    metric: str = "ttft_p99"
    target_s: float = 1.0
    tenant: int | None = None
    window_s: float = 60.0
    fast_window_s: float | None = None
    burn_threshold: float = 6.0

    def __post_init__(self):
        if not re.fullmatch(r"[A-Za-z0-9_.:\- ]+", self.name or ""):
            # the name becomes a Prometheus label VALUE — quotes,
            # backslashes or newlines would corrupt the exposition a
            # whole fleet scrape hangs off
            raise ValueError(
                f"SLO name must be non-empty [A-Za-z0-9_.:- ] "
                f"(it is exported as a label value), got {self.name!r}")
        if _METRIC_RE.match(self.metric) is None:
            raise ValueError(
                f"SLO metric must be '<base>_p<NN>' with base in "
                f"{SLO_METRIC_BASES}, got {self.metric!r}")
        if not self.target_s > 0:
            raise ValueError(f"target_s must be > 0, got {self.target_s}")
        if not self.window_s > 0:
            raise ValueError(f"window_s must be > 0, got {self.window_s}")

    @property
    def metric_base(self):
        return _METRIC_RE.match(self.metric).group("base")

    @property
    def objective(self):
        """The quantile as a fraction: p99 -> 0.99."""
        return int(_METRIC_RE.match(self.metric).group("pct")) / 100.0

    @property
    def fast_window(self):
        return self.fast_window_s if self.fast_window_s is not None \
            else self.window_s / 12.0

    @property
    def series_name(self):
        return self.metric_base + "_s"

    @property
    def series_labels(self):
        return {"tenant": str(self.tenant)} \
            if self.tenant is not None else None


def _burn(values, target_s, budget):
    """Burn rate of one window: bad_fraction / error_budget. 0.0 on an
    empty window (no evidence is not evidence of burning)."""
    if not values:
        return 0.0
    bad = sum(1 for v in values if v > target_s)
    return (bad / len(values)) / max(budget, 1e-9)


def evaluate_slo(slo, fast_values, slow_values, window_truncated=False):
    """THE one copy of the burn-rate math — shared by the per-server
    :class:`SLOEngine` and the router's fleet-level evaluation (which
    feeds it windowed samples concatenated across replica stores).
    ``window_truncated``: the caller's store reported that a ring
    wrapped INSIDE the slow window — the evaluation then saw less
    history than ``window_s`` asked for (surfaced on the result so a
    high-rate series cannot silently collapse the slow window's
    blip-immunity into the fast window's reactivity; grow the store
    capacity when it shows)."""
    budget = 1.0 - slo.objective
    bf = _burn(fast_values, slo.target_s, budget)
    bs = _burn(slow_values, slo.target_s, budget)
    measured = _quantile(slow_values, slo.objective)
    return {
        "window_truncated": bool(window_truncated),
        "slo": slo.name, "metric": slo.metric, "tenant": slo.tenant,
        "target_s": slo.target_s, "objective": slo.objective,
        "window_s": slo.window_s, "fast_window_s": slo.fast_window,
        "samples_slow": len(slow_values), "samples_fast": len(fast_values),
        "measured_s": round(measured, 6),
        #: the objective itself, over the slow window
        "breached": bool(slow_values) and measured > slo.target_s,
        "burn_rate_fast": round(bf, 4), "burn_rate_slow": round(bs, 4),
        "burn_threshold": slo.burn_threshold,
        #: the multi-window ALERT condition: fast AND slow both burning
        #: (epsilon absorbs the 1-0.99 float representation error so a
        #: burn of exactly-threshold compares true)
        "burning": (bf >= slo.burn_threshold - 1e-9
                    and bs >= slo.burn_threshold - 1e-9),
    }


def format_slo_report(report):
    """Human text for one server's ``slo_report()`` dict."""
    lines = []
    for r in report.get("slos", ()):
        tenant = f" tenant={r['tenant']}" if r["tenant"] is not None else ""
        state = "BURNING" if r["burning"] else (
            "breached" if r["breached"] else "ok")
        lines.append(
            f"[{state:>8}] {r['slo']}: {r['metric']}{tenant} = "
            f"{r['measured_s'] * 1e3:.1f}ms (target "
            f"{r['target_s'] * 1e3:.1f}ms) burn fast/slow = "
            f"{r['burn_rate_fast']:.1f}/{r['burn_rate_slow']:.1f} "
            f"(threshold {r['burn_threshold']:.1f}, "
            f"n={r['samples_slow']})")
    active = [a for a in report.get("alerts", ()) if a["active"]]
    for a in active:
        lines.append(f"[   ALERT] {a['kind']} {a['labels']}: "
                     f"{a['message']}")
    for kind, on in sorted(report.get("pathologies", {}).items()):
        if on:
            lines.append(f"[PATHOLOGY] {kind} active")
    if not lines:
        lines.append("[      ok] no SLOs configured / nothing burning")
    return "\n".join(lines)


def format_fleet_report(report):
    """Human text for ``ReplicaRouter.slo_report()``."""
    lines = ["fleet:"]
    fleet = report.get("fleet", {})
    lines.append(format_slo_report(
        {"slos": fleet.get("slos", ()), "alerts": fleet.get("alerts", ()),
         "pathologies": {}}))
    for kind, reps in sorted(fleet.get("pathologies", {}).items()):
        lines.append(f"[PATHOLOGY] {kind} active on replicas {reps}")
    for t, fams in sorted(fleet.get("tenant_latency", {}).items()):
        ttft = fams.get("ttft", {})
        if ttft.get("count"):
            lines.append(
                f"tenant {t}: ttft p99 {ttft['p99_s'] * 1e3:.1f}ms "
                f"p50 {ttft['p50_s'] * 1e3:.1f}ms (n={ttft['count']})")
    return "\n".join(lines)


class SLOEngine:
    """Evaluates a list of :class:`SLO`\\ s against one store,
    maintaining the ``slo_burn_rate``/``slo_breached`` labeled gauges
    and the ``slo_burn`` alert per objective. Cheap enough to run on a
    throttled serve-loop cadence: each evaluation walks at most
    ``capacity`` ring samples per (SLO, window)."""

    def __init__(self, slos, store, telemetry=None):
        self.slos = list(slos)
        for s in self.slos:
            if not isinstance(s, SLO):
                raise TypeError(f"expected SLO, got {type(s).__name__}")
        self.store = store
        self.telemetry = telemetry
        #: serializes evaluations: the serve loop's throttled pass and
        #: any-thread slo_report() callers both evaluate — unserialized,
        #: a delayed raise off stale windows could land AFTER the clear
        #: a fresher evaluation just published
        self._lock = threading.Lock()

    def add(self, slo):
        """Append an objective at runtime (a caller calibrates a target
        from a warmup phase, then arms the SLO)."""
        if not isinstance(slo, SLO):
            raise TypeError(f"expected SLO, got {type(slo).__name__}")
        with self._lock:
            self.slos.append(slo)
        return slo

    def evaluate(self, now=None):
        """Evaluate every SLO; updates gauges + alerts; returns the
        per-SLO result dicts (see :func:`evaluate_slo`). Serialized —
        concurrent callers (loop pass + slo_report) evaluate one at a
        time so alert raise/clear transitions stay ordered by window
        freshness."""
        with self._lock:
            return self._evaluate_locked(now)

    def _evaluate_locked(self, now):
        if now is None:
            now = time.monotonic()
        out = []
        tel = self.telemetry
        for s in list(self.slos):
            slow, fast, truncated = self.store.windowed_values(
                s.series_name, s.window_s, fast_window_s=s.fast_window,
                now=now, labels=s.series_labels)
            r = evaluate_slo(s, fast, slow, window_truncated=truncated)
            out.append(r)
            if tel is not None:
                tel.set_labeled_gauge("slo_burn_rate", s.name,
                                      r["burn_rate_fast"])
                tel.set_labeled_gauge("slo_breached", s.name,
                                      1.0 if r["burning"] else 0.0)
            if r["burning"]:
                self.store.raise_alert(
                    "slo_burn",
                    f"{s.name}: {s.metric} burn fast/slow "
                    f"{r['burn_rate_fast']:.1f}/{r['burn_rate_slow']:.1f} "
                    f">= {s.burn_threshold:.1f} "
                    f"(measured {r['measured_s'] * 1e3:.1f}ms, target "
                    f"{s.target_s * 1e3:.1f}ms)",
                    labels={"slo": s.name}, data=r)
            else:
                self.store.clear_alert("slo_burn", labels={"slo": s.name})
        return out


# ---------------------------------------------------------------------------
# live pathology detectors — explain_tail's taxonomy, streaming
# ---------------------------------------------------------------------------

class _StepWindowDetector:
    """Base: keep the last ``window`` completed StepRecords, evaluate a
    shape predicate after each, raise/clear the alert + the
    ``pathology_active`` gauge on edge transitions. ``on_step`` runs on
    the engine thread (the recorder's subscriber callback) — state is
    single-writer; ``active`` reads are racy-but-monotonic booleans."""

    kind = "unnamed"
    min_steps = 8

    def __init__(self, store, telemetry=None, window=32, min_steps=None):
        self.store = store
        self.telemetry = telemetry
        self._recs = collections.deque(maxlen=int(window))
        if min_steps is not None:
            self.min_steps = int(min_steps)
        self.active = False
        self.fired = 0          # raise edges this lifetime

    # subclasses: (fire: bool, data: dict) over the current window
    def _evaluate(self, recs):
        raise NotImplementedError

    def _message(self, data):
        return f"{self.kind}: {data}"

    def on_step(self, rec):
        self._recs.append(rec)
        recs = tuple(self._recs)
        if len(recs) < self.min_steps:
            return
        fire, data = self._evaluate(recs)
        if fire and not self.active:
            self.active = True
            self.fired += 1
            self.store.raise_alert(self.kind, self._message(data),
                                   data=data)
            if self.telemetry is not None:
                self.telemetry.set_labeled_gauge("pathology_active",
                                                 self.kind, 1.0)
        elif self.active and not fire:
            self.active = False
            self.store.clear_alert(self.kind)
            if self.telemetry is not None:
                self.telemetry.set_labeled_gauge("pathology_active",
                                                 self.kind, 0.0)

    def reset(self):
        """Drop the step window AND clear any active alert/gauge — the
        server calls this at start() so a restarted serve never
        evaluates a window mixing two runs' records, and an alert that
        was active at stop() does not outlive the loop it described."""
        self._recs.clear()
        if self.active:
            self.active = False
            self.store.clear_alert(self.kind)
            if self.telemetry is not None:
                self.telemetry.set_labeled_gauge("pathology_active",
                                                 self.kind, 0.0)


def _decode_tokens(rec):
    return sum(n for _, _, kind, n in rec.grants
               if kind in ("decode", "verify"))


class RampThrashDetector(_StepWindowDetector):
    """Preemption/admission churn with NO committed decode progress —
    the livelock shape the PR-13 admission-defer guarantee fixed for
    ramp-vs-ramp, still reachable under adversarial churn. Fires when
    the window carries ``min_preemptions`` preemption events while not
    one decode/verify token was granted."""

    kind = "ramp_thrash"
    min_steps = 6

    def __init__(self, store, telemetry=None, window=32, min_steps=None,
                 min_preemptions=3):
        super().__init__(store, telemetry, window, min_steps)
        self.min_preemptions = int(min_preemptions)

    def _evaluate(self, recs):
        preempts = sum(len(r.preemptions) for r in recs)
        decode = sum(_decode_tokens(r) for r in recs)
        data = {"preemptions": preempts, "decode_tokens": decode,
                "steps": len(recs)}
        return (preempts >= self.min_preemptions and decode == 0), data

    def _message(self, data):
        return (f"ramp thrash: {data['preemptions']} preemptions over "
                f"{data['steps']} steps with zero committed decode "
                f"tokens — admissions are churning each other out")


class HostSyncRegressionDetector(_StepWindowDetector):
    """Host-sync share of STRIDE-1 step wall above budget, sustained.
    Amortized readouts (``readout_stride > 1``) are excluded — a
    sync-dominated stride step is ``batched_readout`` working as
    designed, exactly like the explain_tail split."""

    kind = "host_sync_regression"
    min_steps = 8

    def __init__(self, store, telemetry=None, window=32, min_steps=None,
                 budget=0.5):
        super().__init__(store, telemetry, window, min_steps)
        self.budget = float(budget)

    def _evaluate(self, recs):
        ones = [r for r in recs if r.readout_stride == 1 and r.t_finish]
        wall = sum(r.wall_s for r in ones)
        sync = sum(r.sync_s for r in ones)
        share = sync / wall if wall > 0 else 0.0
        data = {"sync_share": round(share, 4), "budget": self.budget,
                "stride1_steps": len(ones)}
        return (len(ones) >= self.min_steps
                and share > self.budget), data

    def _message(self, data):
        return (f"host-sync regression: token syncs are "
                f"{data['sync_share']:.0%} of stride-1 step wall "
                f"(budget {data['budget']:.0%}) — raise readout_stride "
                f"or chase the transfer path")


class SpecCollapseDetector(_StepWindowDetector):
    """Speculative draft acceptance collapsed: the window verified at
    least ``min_proposed`` drafts and committed under ``min_rate`` of
    them — verify windows are burning compute on tokens that roll
    back (the adaptive-k EWMA should already be shrinking k; sustained
    collapse means the drafter does not fit the workload)."""

    kind = "spec_acceptance_collapse"
    min_steps = 4

    def __init__(self, store, telemetry=None, window=32, min_steps=None,
                 min_proposed=16, min_rate=0.2):
        super().__init__(store, telemetry, window, min_steps)
        self.min_proposed = int(min_proposed)
        self.min_rate = float(min_rate)

    def _evaluate(self, recs):
        acc = sum(r.spec_accepted for r in recs)
        rej = sum(r.spec_rejected for r in recs)
        total = acc + rej
        rate = acc / total if total else 1.0
        data = {"accepted": acc, "rejected": rej,
                "acceptance_rate": round(rate, 4)}
        return (total >= self.min_proposed and rate < self.min_rate), data

    def _message(self, data):
        return (f"speculative acceptance collapse: "
                f"{data['acceptance_rate']:.0%} of "
                f"{data['accepted'] + data['rejected']} drafts committed "
                f"(floor {self.min_rate:.0%})")


class AdapterSwapStormDetector(_StepWindowDetector):
    """Adapter device-cache swap-ins riding a large fraction of recent
    steps: the multi-tenant working set is larger than
    ``adapter_cache_slots`` and admissions are paying a host upload
    each — grow the cache or shard tenants across replicas."""

    kind = "adapter_swap_storm"
    min_steps = 8

    def __init__(self, store, telemetry=None, window=32, min_steps=None,
                 min_swaps=4, swap_share=0.5):
        super().__init__(store, telemetry, window, min_steps)
        self.min_swaps = int(min_swaps)
        self.swap_share = float(swap_share)

    def _evaluate(self, recs):
        swaps = sum(r.adapter_swaps for r in recs)
        share = swaps / len(recs)
        data = {"adapter_swaps": swaps, "steps": len(recs),
                "swaps_per_step": round(share, 4)}
        return (swaps >= self.min_swaps
                and share >= self.swap_share), data

    def _message(self, data):
        return (f"adapter swap storm: {data['adapter_swaps']} swap-ins "
                f"over {data['steps']} steps "
                f"({data['swaps_per_step']:.2f}/step) — working set "
                f"exceeds the adapter cache")


class SwapStallDetector(_StepWindowDetector):
    """KV host-tier swap traffic on a dominant share of recent steps:
    preemption pressure is converting into device<->host copies every
    few steps — the pool is undersized for the resident set even WITH
    the cheap eviction path (grow the pool, or shed admissions)."""

    kind = "swap_stall"
    min_steps = 8

    def __init__(self, store, telemetry=None, window=32, min_steps=None,
                 min_swap_steps=3, swap_share=0.25):
        super().__init__(store, telemetry, window, min_steps)
        self.min_swap_steps = int(min_swap_steps)
        self.swap_share = float(swap_share)

    def _evaluate(self, recs):
        swapping = [r for r in recs
                    if (r.kv_swap_in_bytes or 0) + (r.kv_swap_out_bytes
                                                    or 0) > 0]
        share = len(swapping) / len(recs)
        byts = sum((r.kv_swap_in_bytes or 0) + (r.kv_swap_out_bytes or 0)
                   for r in swapping)
        data = {"swap_steps": len(swapping), "steps": len(recs),
                "swap_step_share": round(share, 4), "swap_bytes": byts}
        return (len(swapping) >= self.min_swap_steps
                and share >= self.swap_share), data

    def _message(self, data):
        return (f"swap-stall dominance: host-tier traffic on "
                f"{data['swap_steps']}/{data['steps']} recent steps "
                f"({data['swap_bytes']} bytes) — the pool is undersized "
                f"for the resident set")


def default_detectors(store, telemetry=None):
    """The standard detector set the server arms when a metrics store
    AND a flight recorder are both attached."""
    return [RampThrashDetector(store, telemetry),
            HostSyncRegressionDetector(store, telemetry),
            SpecCollapseDetector(store, telemetry),
            AdapterSwapStormDetector(store, telemetry),
            SwapStallDetector(store, telemetry)]
