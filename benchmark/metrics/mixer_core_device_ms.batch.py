"""Per-layer metric ``mixer_core_device_ms.batch``: layer "programs", moves
``serve_tok_s`` (better lower, source device_trace). Device ms a step
program (all kinds of the stretch together) of the token mixers' cores: what
runs under ``self_attn/.../pt.core`` — the paged and latent attention
kernels with their wrappers' transposes, and chunked KDA."""
from benchmark.harness.components import device_ms

UNIT = "ms"
LAYER = "programs"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "device_trace"

read = device_ms("mixer.core")
