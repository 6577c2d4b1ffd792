"""The learned indexer's scores (``paddle_tpu/ops/kernels/
sparse_latent_attention.py``: ``index_scores``, under ``self_attn/
pt.index``): ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` of a live
row against the index keys of its slot's context. On the chip the Pallas
kernel ``dsa_index_scores``, whose operations a component table
(``benchmark/harness/components.py``'s finer rows) files under the
kernel's own name, the innermost scope; in plain XLA the same work lies
under ``pt.index`` itself. The time the scores are held to is the device
time of both rows, so the share reads the same work whichever implements
it (the index projections and their rotation have scopes of their own
inside ``pt.index`` and are not in it)."""
#: the kernel's name in a trace, and the scope its plain form lies under
PATTERN = "dsa_index_scores"
LEAF = "pt.index"


def leaf_seconds(table, *leaves):
    """Seconds of the step programs' operations whose innermost scope is
    one of ``leaves`` in a component
    :class:`~benchmark.harness.components.Table` (0.0 where there is
    none: a program from before the scopes)."""
    return sum(secs for kind in table.step_kinds().values()
               for (_, name, _), (secs, _, _) in kind.rows.items()
               if name in leaves)


def least(pairs, rows, heads, dim, chunk, bytes_per_el=2):
    """(flops, bytes) of the least work for ``pairs`` scored (row, key)
    pairs of ``rows`` live (row, layer) pairs in ONE step: 2 flops a
    multiply-add over ``dim`` in each of ``heads`` index heads a pair (the
    ReLU and the weighted sum are not counted); every row's ``heads x
    dim`` index query read once; every index key of a live slot read once
    a step, counted from below as ``pairs / chunk`` keys (a slot is
    granted at most ``chunk`` rows a step, so its keys are at least its
    pairs over ``chunk``: exact for a full prefill chunk's history, an
    under-count for decode rows); a row's scores written once in
    float32 are the next stage's input and not counted. A floor."""
    flops = 2.0 * heads * dim * pairs
    els = rows * heads * dim + pairs / float(chunk) * dim
    return flops, float(els * bytes_per_el)
