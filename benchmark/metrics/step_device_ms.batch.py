"""Per-layer metric ``step_device_ms.batch``: layer "programs", moves ``serve_tok_s``."""
from benchmark.harness.readers import step_device_ms as read  # noqa: F401

UNIT = "ms"
LAYER = "programs"
MOVES = "serve_tok_s"
