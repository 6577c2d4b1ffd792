"""``paddle_tpu.analysis`` — project-specific static checkers + runtime
sanitizers for the invariants the serving stack's performance rests on.

The last several PRs bought their wins by enforcing source-level
disciplines — one D2H sync per readout stride, donation-consumed
buffers rebuilt only via ``reset()``, allocator mutations confined to
the engine thread, strict telemetry names. This package encodes those
as AST-level checks so the NEXT change to a hot path fails lint, not a
p99 bench three rounds later:

==========  =========================================================
 PTL000      ``ptlint: disable`` suppression without a reason string
 PTL001      implicit device→host sync in an engine/serving hot path
 PTL002      retrace/concretization hazards reaching ``jax.jit``
 PTL003      donated buffer read after the donating call
 PTL004      unguarded allocator/cache mutations + lock-order cycles
 PTL005      telemetry names missing from the ServingTelemetry registry
 PTL006      device↔host KV-pool copy outside the fence-tracked swap API
 PTL007      SLO/pathology names missing from the ALERT_KINDS /
             LABELED_GAUGE_FAMILIES registries
 PTL008      tracing names (request-event kinds, trace-hop vias,
             Perfetto counter/flow tracks, tail causes) off their
             flight-recorder/types registries
==========  =========================================================

CLI::

    python -m paddle_tpu.analysis [paths ...] [--json] [--all]
        [--baseline analysis_baseline.json] [--write-baseline]

Per-line suppression: ``# ptlint: disable=PTL001 -- reason`` (the
reason is mandatory — PTL000 flags bare suppressions). Grandfathered
findings live in the checked-in ``analysis_baseline.json``;
``tests/test_analysis_clean.py`` keeps the repo finding-free modulo
that baseline in tier-1.

Runtime sanitizers (the dynamic halves):

* transfer-guard window — ``PADDLE_TPU_TRANSFER_CHECKS=1`` (armed by
  the test conftest) makes the engine hold
  ``jax.transfer_guard("disallow")`` across the fused all-decode
  stride's dispatch→readout window and counts the documented readout
  as ``stats["guarded_syncs"]`` — the one-sync-per-stride contract as
  an assertion.
* lock-order watchdog — ``PADDLE_TPU_LOCK_CHECKS=1`` wraps the
  documented serving locks, records actual acquisition edges, raises
  on cycles online, and :func:`lock_watchdog.assert_consistent` checks
  the observed edges against PTL004's static graph.
"""
from .core import (Finding, Report, JSON_SCHEMA_VERSION, default_checks,
                   iter_py_files, load_baseline, run_analysis)
from .locks import static_lock_graph
from . import lock_watchdog

__all__ = ["Finding", "Report", "JSON_SCHEMA_VERSION", "default_checks",
           "iter_py_files", "load_baseline", "run_analysis",
           "static_lock_graph", "lock_watchdog"]
