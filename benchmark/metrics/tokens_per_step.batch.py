"""Per-layer metric ``tokens_per_step.batch``: layer "scheduler and cache", moves ``serve_tok_s``."""
from benchmark.harness.readers import tokens_per_step as read  # noqa: F401

UNIT = "tokens"
LAYER = "scheduler and cache"
MOVES = "serve_tok_s"
