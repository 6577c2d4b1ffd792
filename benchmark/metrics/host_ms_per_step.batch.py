"""Per-layer metric ``host_ms_per_step.batch``: layer "server loop and scheduler", moves ``serve_tok_s``."""
from benchmark.harness.readers import host_ms_per_step as read  # noqa: F401

UNIT = "ms"
LAYER = "server loop and scheduler"
MOVES = "serve_tok_s"
