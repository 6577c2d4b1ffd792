"""Per-layer metric ``expert_load_peak_x.batch``: layer "kernels", moves
``serve_tok_s`` (better lower, source program_counter). The fullest held
expert's rows, summed over the window's steps and expert layers
(``moe_expert_peak``), over the mean rows a held expert got in them
(``moe_assignments_held`` / experts held): 1.0 is a perfectly even
routing. None where the program keeps no such counters."""
from benchmark.harness import loader

UNIT = "x"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(ctx):
    occupancy = loader.module("metrics", "expert_row_occupancy_pct.batch")
    peak = occupancy.delta(ctx, "moe_expert_peak")
    held = occupancy.delta(ctx, "moe_assignments_held")
    experts = ctx["cell"].config.get("num_experts")
    if peak is None or not held or not experts:
        return None
    return peak * float(experts) / held
