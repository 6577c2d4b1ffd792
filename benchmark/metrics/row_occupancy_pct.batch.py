"""Per-layer metric ``row_occupancy_pct.batch``: layer "scheduler and cache",
moves ``serve_tok_s`` (better higher, source program_counter). Tokens
asked of the window's steps over the rows their programs computed."""
from benchmark.harness.inside import row_occupancy_pct as read  # noqa: F401

UNIT = "%"
LAYER = "scheduler and cache"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "program_counter"
