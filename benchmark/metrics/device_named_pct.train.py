"""Per-layer metric ``device_named_pct.train``: layer "programs", moves
``train_tok_s`` (better higher, source device_trace). Share of the train
step's operations' summed seconds, inside the traced steps, that carries a
scope (``paddle_tpu.profiler.scope``): the guard that the component table of
``benchmark/harness/components.py`` is whole. Nothing to read (None) for a
program without scopes."""
from benchmark.harness.components import named_pct as read  # noqa: F401

UNIT = "%"
LAYER = "programs"
MOVES = "train_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
