"""Per-layer metric ``device_idle_pct.train``: layer "device", moves ``train_tok_s``."""
from benchmark.harness.readers import device_idle_pct as read  # noqa: F401

UNIT = "%"
LAYER = "device"
MOVES = "train_tok_s"
