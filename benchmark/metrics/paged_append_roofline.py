"""Per-layer metric ``paged_append_roofline``: layer "kernels", moves ``serve_tok_s``."""
from benchmark.harness.readers import paged_append_roofline as read  # noqa: F401

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
