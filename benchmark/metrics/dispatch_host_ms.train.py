"""Per-layer metric ``dispatch_host_ms.train``: layer "programs", moves
``train_tok_s`` (better lower, source device_trace). Mean host time of a
TrainStep call (pt:train.step)."""
from benchmark.harness.inside import dispatch_host_ms as read  # noqa: F401

UNIT = "ms"
LAYER = "programs"
MOVES = "train_tok_s"
BETTER = "lower"
SOURCE = "device_trace"
