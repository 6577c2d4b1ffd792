"""Per-layer metric ``dsa_select_device_ms.batch``: layer "programs",
moves ``serve_tok_s`` (better lower, source device_trace). Device ms a
step program (all kinds of the stretch together) of the exact top-k that
turns the indexer's scores into a row's selected positions: the
operations whose innermost scope is ``pt.select``. The part of the
mechanism with no roofline worth the name: its least work is one pass
over the scores. None where there is no trace, no component table or no
such scope."""
from benchmark.harness import loader
from benchmark.harness.components import components

UNIT = "ms"
LAYER = "programs"
MOVES = "serve_tok_s"
BETTER = "lower"
SOURCE = "device_trace"
LEAF = "pt.select"


def read(ctx):
    table = components(ctx)
    if table is None or not table.programs():
        return None
    secs = loader.module("kernels", "dsa_index").leaf_seconds(table, LEAF)
    return 1e3 * secs / table.programs() if secs > 0 else None
