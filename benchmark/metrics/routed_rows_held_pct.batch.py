"""Per-layer metric ``routed_rows_held_pct.batch``: layer "kernels", moves
``serve_tok_s`` (better lower, source program_counter). Of the live rows
an expert layer routed, the share with at least one assignment on an
expert this chip holds: the rows the deployment's exchange would bring
here (``engine.stats``: ``moe_rows_held`` over ``moe_assignments`` /
``num_experts_per_tok``, the window's deltas, summed over the expert
layers). With 2 of 8 routing groups held and 3 kept a row, exchangeable
scores give at most 1 - C(6,3)/C(8,3) = 64 %; without the group limit it
would be about 82 %. It moves only if the routing changes. None where the
program keeps no such counter or the configuration no such key."""
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    top_k = ctx["cell"].config.get("num_experts_per_tok")
    if not s0 or not s1 or not top_k or any(
            key not in s for s in (s0, s1)
            for key in ("moe_rows_held", "moe_assignments")):
        return None
    routed = (s1["moe_assignments"] - s0["moe_assignments"]) / float(top_k)
    if routed <= 0:
        return None
    return 100.0 * (s1["moe_rows_held"] - s0["moe_rows_held"]) / routed
