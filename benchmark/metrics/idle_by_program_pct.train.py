"""Per-layer metric ``idle_by_program_pct.train``: layer "device", moves
``train_tok_s`` (better lower, source device_trace). Share of the stretch
the chip idled under a pt:train.* host work span."""
from benchmark.harness.inside import idle_by_program_pct as read  # noqa: F401

UNIT = "%"
LAYER = "device"
MOVES = "train_tok_s"
BETTER = "lower"
SOURCE = "device_trace"
