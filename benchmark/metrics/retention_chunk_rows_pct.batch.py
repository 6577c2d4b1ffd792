"""Per-layer metric ``retention_chunk_rows_pct.batch``: layer "scheduler
and cache", moves ``serve_tok_s`` (better higher, source program_counter).
Of the live rows the power-retention layers took in the window, the share
that went through the chunk form (a prefill chunk's rows: one state pass a
sub-chunk of 64 rows) and not the one-token form (a decode row: one state
pass a row) (``engine.stats``: ``ret_rows_chunk`` over ``ret_rows_chunk +
ret_rows_step``, the window's deltas). It moves with the traffic and the
scheduler, not with a kernel. None where the program keeps no such
counters."""
from benchmark.harness import loader

UNIT = "%"
LAYER = "scheduler and cache"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "program_counter"


def read(ctx):
    delta = loader.module("metrics", "expert_row_occupancy_pct.batch").delta
    chunk, step = delta(ctx, "ret_rows_chunk"), delta(ctx, "ret_rows_step")
    if chunk is None or step is None or not chunk + step:
        return None
    return 100.0 * chunk / (chunk + step)
