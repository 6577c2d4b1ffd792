"""Per-layer metric ``retention_state_live_pct.batch``: layer "kernels",
moves ``serve_tok_s`` (better higher, source program_counter). Of the
(slot, layer) states the power-retention core read and wrote in the
window, the share that served a live row (``engine.stats``:
``ret_state_live`` over ``ret_state_walked``, the window's deltas; the
retention layers count both on the device: a one-token step walks every
slot's state; a mixed step every slot's once, for its one-row slots, and
each slot with a chunk once more). It moves with the traffic and with a
core that skips dead slots, not with the core's speed. None where the program keeps no such counters."""
from benchmark.harness import loader

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "program_counter"


def read(ctx):
    delta = loader.module("metrics", "expert_row_occupancy_pct.batch").delta
    live, walked = delta(ctx, "ret_state_live"), \
        delta(ctx, "ret_state_walked")
    if live is None or not walked:
        return None
    return 100.0 * live / walked
