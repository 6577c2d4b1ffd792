"""Per-layer metric ``step_other_device_ms.batch``: layer "programs", moves
``serve_tok_s`` (better lower, source device_trace). Device ms a step
program (all kinds of the stretch together) outside the mixers and the feed-
forward layers: embedding, block-level norms and adds, final norm and head,
sampling, packing, readout, a looped model's exit gate."""
from benchmark.harness.components import STEP_OTHER, device_ms

UNIT = "ms"
LAYER = "programs"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "device_trace"

read = device_ms(*STEP_OTHER)
