"""Per-layer metric ``flash_train_roofline``: layer "kernels", moves ``train_tok_s``."""
from benchmark.harness.readers import flash_train_roofline as read  # noqa: F401

UNIT = "%"
LAYER = "kernels"
MOVES = "train_tok_s"
