"""Per-layer metric ``expert_row_occupancy_pct.batch``: layer "kernels",
moves ``serve_tok_s`` (better higher, source program_counter). Assignments
that landed on an expert this chip holds over the rows its grouped expert
product ran, padding included (``engine.stats``: ``moe_assignments_held``
over ``moe_rows_computed``, the window's deltas). None where the program
keeps no such counters."""
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "program_counter"


def delta(ctx, key):
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if not s0 or not s1 or key not in s0 or key not in s1:
        return None
    return s1[key] - s0[key]


def read(ctx):
    held = delta(ctx, "moe_assignments_held")
    rows = delta(ctx, "moe_rows_computed")
    if held is None or not rows:
        return None
    return 100.0 * held / rows
