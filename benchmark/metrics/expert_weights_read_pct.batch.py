"""Per-layer metric ``expert_weights_read_pct.batch``: layer "kernels",
moves ``serve_tok_s`` (better lower, source program_counter). The share
of the held experts that got at least one row, summed over the expert
layers' calls of the window (``engine.stats``: ``moe_experts_nonempty``
over ``moe_experts_held``, the window's deltas): the share of the held
expert weights the grouped product has to stream, since it reads an
expert only if a row landed on it. It moves with the traffic and the
routing, not with the kernel. None where the program keeps no such
counters."""
from benchmark.harness import loader

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(ctx):
    occupancy = loader.module("metrics", "expert_row_occupancy_pct.batch")
    nonempty = occupancy.delta(ctx, "moe_experts_nonempty")
    held = occupancy.delta(ctx, "moe_experts_held")
    if nonempty is None or not held:
        return None
    return 100.0 * nonempty / held
