"""Per-layer metric ``idle_by_program_pct.batch``: layer "device", moves
``serve_tok_s`` (better lower, source device_trace). Share of the stretch
the chip idled under a pt: host work span."""
from benchmark.harness.inside import idle_by_program_pct as read  # noqa: F401

UNIT = "%"
LAYER = "device"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "device_trace"
