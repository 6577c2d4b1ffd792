"""Per-layer metric ``kv_pool_used_pct.batch``: layer "scheduler and
cache", moves ``serve_tok_s`` (better higher, source program_counter). Of
the paged pool's blocks, the share that belonged to a request, a dispatch
with another (``engine.stats``: ``pool_blocks_used`` over
``pool_blocks_total``, the window's deltas; a block is used when it is
neither free, nor a cached prefix, nor waiting out a write fence). The
pool is reserved whole at start-up: what of it the traffic fills is what
the reservation buys, and where the pool caps the batch (a looped model's
block holds every loop step's keys and values) a change that packs them
tighter shows here first. None where the program keeps no such counter.
The reader also prints the window's deltas of the counters a looped model
and the decode path keep (``loop_*``, ``decode_*``, ``pool_blocks_*``,
``preemptions``), which no metric carries whole; the exit distribution's
masses a loop step (``loop_exit_mass_t``, fixed-point counts) as shares of
their sum."""
UNIT = "%"
LAYER = "scheduler and cache"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if not s0 or not s1 or any(
            key not in s for s in (s0, s1)
            for key in ("pool_blocks_used", "pool_blocks_total")):
        return None
    total = s1["pool_blocks_total"] - s0["pool_blocks_total"]
    if total <= 0:
        return None
    shown = {k: s1[k] - s0.get(k, 0) for k in sorted(s1)
             if k.startswith(("loop_", "decode_", "pool_blocks_"))
             or k == "preemptions"}
    masses = {k: shown.pop(k) for k in list(shown)
              if k.startswith("loop_exit_mass_")}
    if sum(masses.values()) > 0:
        shown["loop_exit_mass_pct"] = [
            round(100.0 * v / sum(masses.values()), 2)
            for _, v in sorted(masses.items())]
    print(f"[trace] counters over the window: {shown}", flush=True)
    return 100.0 * (s1["pool_blocks_used"] - s0["pool_blocks_used"]) / total
