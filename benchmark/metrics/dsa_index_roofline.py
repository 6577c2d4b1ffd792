"""Per-layer metric ``dsa_index_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). The least time the
chip could take for the learned indexer's scores of the traced stretch
(``benchmark/kernels/dsa_index.py``: 2 x ``index_n_heads`` x
``index_head_dim`` flops a scored pair, a row's index queries and a live
slot's index keys read once; a step's least is the larger of its byte
time and its flop time) over the device time of the operations whose
innermost scope is the scoring kernel ``dsa_index_scores`` or, in its
plain form, ``pt.index`` itself, in the stretch's step programs
(``harness/components.py``; the index projections have scopes of their
own and are not in it).
The scored pairs and the rows are the STRETCH's own, a step at a time:
what the program's ``pt:engine.emit`` spans inside it carry
(``scored_keys``, ``indexed_rows``: a step's ``dsa_keys_scored`` and
``dsa_rows``, summed over the indexed layers). The one assumption is
``expert_matmul_roofline``'s: a step is emitted up to ``pipeline_depth``
steps after the device ran it. Nothing to read (None) where there is no
trace, no component table, no such scope or no such ids."""
from benchmark.harness import loader
from benchmark.harness.components import components
from benchmark.harness.inside import inside

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
IDS = ("scored_keys", "indexed_rows")


def emitted(ctx, ids):
    """The ids of the ``pt:engine.emit`` spans inside the stretch that
    carry every one of ``ids`` ([] where there are none)."""
    ins = inside(ctx)
    return [s.ids for s in (ins.named("pt:engine.emit") if ins else [])
            if all(key in s.ids for key in ids)]


def stretch(ctx, ids, *leaves):
    """(the emitted steps' ids inside the stretch that carry ``ids``, the
    seconds of ``leaves`` in its step programs, the table), or None."""
    if ctx.get("trace") is None:
        return None
    emits = emitted(ctx, ids)
    if not emits:
        return None
    table = components(ctx)
    if table is None:
        return None
    secs = loader.module("kernels", "dsa_index").leaf_seconds(table, *leaves)
    return (emits, secs, table) if secs > 0 else None


def read(ctx):
    k = loader.module("kernels", "dsa_index")
    got = stretch(ctx, IDS, k.LEAF, k.PATTERN)
    if got is None:
        return None
    emits, secs, table = got
    c, peaks = ctx["cell"].config, ctx["peaks"]
    heads, dim = int(c["index_n_heads"]), int(c["index_head_dim"])
    chunk = int(c["engine"]["chunk_size"])
    least, pairs, rows = 0.0, 0, 0
    for ids in emits:
        f, b = k.least(int(ids["scored_keys"]), int(ids["indexed_rows"]),
                       heads, dim, chunk)
        least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
        pairs += int(ids["scored_keys"])
        rows += int(ids["indexed_rows"])
    print(f"[trace] dsa_index_roofline: {len(emits)} emitted steps inside "
          f"the traced stretch speak of {pairs} scored pairs of {rows} live "
          f"(row, layer) pairs, least {least * 1e3:.2f} ms; {k.PATTERN} and "
          f"{k.LEAF} took {secs * 1e3:.2f} ms over {table.programs()} step "
          f"programs",
          flush=True)
    return 100.0 * least / secs / ctx["chips"]
