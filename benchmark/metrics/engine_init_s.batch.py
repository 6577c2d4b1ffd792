"""Per-layer metric ``engine_init_s.batch``: layer "scheduler and cache",
moves ``setup_s`` (better lower, source program_counter). The host wall of
``LLMEngine.__init__`` (``engine.stats["engine_init_time_s"]``): the model
seam, the pools reserved whole, the tables and the allocator."""


def read(ctx):
    s0 = ctx.get("stats0")
    if not s0 or "engine_init_time_s" not in s0:
        return None
    return s0["engine_init_time_s"]


UNIT = "s"
LAYER = "scheduler and cache"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_counter"
