"""Per-layer metric ``ffn_device_ms.batch``: layer "programs", moves
``serve_tok_s`` (better lower, source device_trace). Device ms a step
program (all kinds of the stretch together) of the feed-forward layers
(``mlp``), dense or expert: routing, dispatch, the grouped product, the
shared expert and the weighted sum included."""
from benchmark.harness.components import device_ms

UNIT = "ms"
LAYER = "programs"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "device_trace"

read = device_ms("ffn")
