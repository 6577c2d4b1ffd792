"""Per-layer metric ``dsa_context_read_pct.batch``: layer "kernels", moves
``serve_tok_s`` (better lower, source program_counter). Of the causal
positions the indexed layers' rows could attend, the share they did: 100 x
``dsa_keys_selected`` / ``dsa_keys_scored`` (``engine.stats``, the
window's deltas: the sums over live rows and indexed layers of ``min(
index_topk, pos + 1)`` and of ``pos + 1``). How sparse the traffic made
the indexed layers: 100 would mean that every context is within
``index_topk`` and the mechanism selects nothing. It moves only with the
traffic or ``index_topk``. None where the program keeps no such counter."""
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if not s0 or not s1 or any(
            key not in s for s in (s0, s1)
            for key in ("dsa_keys_selected", "dsa_keys_scored")):
        return None
    scored = s1["dsa_keys_scored"] - s0["dsa_keys_scored"]
    if scored <= 0:
        return None
    return 100.0 * (s1["dsa_keys_selected"] - s0["dsa_keys_selected"]) \
        / scored
