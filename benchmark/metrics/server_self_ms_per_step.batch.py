"""Per-layer metric ``server_self_ms_per_step.batch``: layer "server loop and
scheduler", moves ``serve_tok_s`` (better lower, source device_trace). The
pt:server.* spans' own time a dispatched step, idle left out."""
from benchmark.harness.inside import server_self_ms_per_step as read  # noqa: F401

UNIT = "ms"
LAYER = "server loop and scheduler"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "device_trace"
