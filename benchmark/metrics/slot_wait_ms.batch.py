"""Per-layer metric ``slot_wait_ms.batch``: layer "server loop and
scheduler", moves ``serve_tok_s`` (better lower, source program_counter).
Mean wait of a request in its slot before its first prefill grant."""
from benchmark.harness.inside import slot_wait_ms as read  # noqa: F401

UNIT = "ms"
LAYER = "server loop and scheduler"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "program_counter"
