"""Per-layer metric ``kv_grid_live_pct.batch``: layer "kernels", moves
``serve_tok_s`` (better higher, source program_counter). Block-table
entries that hold a live token over those the paged attention grid walked."""
from benchmark.harness.inside import kv_grid_live_pct as read  # noqa: F401

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "program_counter"
