"""Per-layer metric ``device_idle_pct.batch``: layer "device", moves ``serve_tok_s``."""
from benchmark.harness.readers import device_idle_pct as read  # noqa: F401

UNIT = "%"
LAYER = "device"
MOVES = "serve_tok_s"
