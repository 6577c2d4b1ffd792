"""Per-layer metric ``head_loss_device_ms.train``: layer "programs", moves
``train_tok_s`` (better lower, source device_trace). Device ms a train step
of the final norm, the head and the loss (``pt.loss``), forward and
backward."""
from benchmark.harness.components import device_ms

UNIT = "ms"
LAYER = "programs"
MOVES = "train_tok_s"
BETTER = "lower"
SOURCE = "device_trace"

read = device_ms("head", "loss")
