"""Per-layer metric ``kda_grid_live_pct.batch``: layer "kernels", moves
``serve_tok_s`` (better higher, source program_counter). Of the (slot,
chunk) grid steps the KDA kernel walked in the window, the share that held
a live row: how often its skip engages (``engine.stats``:
``kda_grid_live`` over ``kda_grid_steps``, the window's deltas; the KDA
layers count both on the device from the step's own ``q_lens``). It moves
with the traffic and the chunk, not with the kernel's speed. None where
the program keeps no such counters."""
from benchmark.harness import loader

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "program_counter"


def read(ctx):
    occupancy = loader.module("metrics", "expert_row_occupancy_pct.batch")
    live = occupancy.delta(ctx, "kda_grid_live")
    steps = occupancy.delta(ctx, "kda_grid_steps")
    if live is None or not steps:
        return None
    return 100.0 * live / steps
