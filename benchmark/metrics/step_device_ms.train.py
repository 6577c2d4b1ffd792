"""Per-layer metric ``step_device_ms.train``: layer "programs", moves ``train_tok_s``."""
from benchmark.harness.readers import step_device_ms as read  # noqa: F401

UNIT = "ms"
LAYER = "programs"
MOVES = "train_tok_s"
