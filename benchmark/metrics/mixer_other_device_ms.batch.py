"""Per-layer metric ``mixer_other_device_ms.batch``: layer "programs", moves
``serve_tok_s`` (better lower, source device_trace). Device ms a step
program (all kinds of the stretch together) of what a token mixer runs that
is neither its core nor a projection: views and relayouts (``pt.view``),
rotary positions, KDA's convolutions and gates."""
from benchmark.harness.components import device_ms

UNIT = "ms"
LAYER = "programs"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "device_trace"

read = device_ms("mixer.other")
