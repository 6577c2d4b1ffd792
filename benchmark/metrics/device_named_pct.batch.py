"""Per-layer metric ``device_named_pct.batch``: layer "programs", moves
``serve_tok_s`` (better higher, source device_trace). Share of the step
programs' operations' summed seconds, inside the traced stretch, that
carries a scope (``paddle_tpu.profiler.scope``: its own, or the one of the
operation it serves): the guard that the component table of
``benchmark/harness/components.py`` is whole. Nothing to read (None) for a
program without scopes."""
from benchmark.harness.components import named_pct as read  # noqa: F401

UNIT = "%"
LAYER = "programs"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
