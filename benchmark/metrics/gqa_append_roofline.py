"""Per-layer metric ``gqa_append_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). Trace time of the
paged K/V append kernel against the prompt chunks prefilled inside the
traced stretch, in a model whose K/V layers are the FEW among recurrent
ones and whose head size is a key of its own: each request's prefill
position at the stretch's two ends, as ``paged_append_roofline`` counts
them (that reader takes every layer as a K/V layer and the head size as
``hidden_size / heads``, which here is neither),
``benchmark/kernels/paged_attention_append.py``'s least work a chunk with
``num_attention_heads`` / ``num_key_value_heads`` heads, the head size
``head_dim`` and as many layers as ``gqa_layers`` names inside
``num_hidden_layers``. Decode rows (those that ride a mixed step) are left
out of the work and not of the time: the count is a lower bound, the
share an under-estimate. Nothing to read (None) where the trace has no
such kernel, or the configuration names no ``gqa_layers``."""
from benchmark.harness import loader
from benchmark.harness.readers import _roofline_pct
from benchmark.harness.trace import TraceError

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"


def gqa_depth(config):
    """K/V layers the program runs: those ``gqa_layers`` names (from 0)
    inside the depth; None for a configuration without the key."""
    if "gqa_layers" not in config:
        return None
    depth = int(config["num_hidden_layers"])
    return sum(1 for i in config["gqa_layers"] if int(i) < depth)


def read(ctx):
    tr, st = ctx.get("trace"), ctx.get("stretch")
    if tr is None or st is None or st.snap0 is None or st.snap1 is None:
        return None
    c = ctx["cell"].config
    layers = gqa_depth(c)
    if not layers:
        return None
    k = loader.module("kernels", "paged_attention_append")
    try:
        secs, count = tr.op_seconds(k.PATTERN)
    except TraceError:          # no such kernel in this trace
        return None
    if not count or secs <= 0:
        return None
    heads, kvh = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd, chunk = int(c["head_dim"]), int(c["engine"]["chunk_size"])
    pairs = []
    for r in ctx["records"]:
        rid, n = r.handle.request_id, r.n_prompt

        def pos(snap, t_end):
            if rid in snap:
                return min(snap[rid], n)
            return n if (r.t_first is not None and r.t_first <= t_end) else 0
        a, b = pos(st.snap0, st.t0), pos(st.snap1, st.t1)
        while a < b:
            e = min(b, (a // chunk + 1) * chunk)
            pairs.append(k.least(a, e, heads, kvh, hd, layers))
            a = e
    if not pairs:
        return None
    print(f"[trace] gqa_append_roofline: {count:.0f} calls of "
          f"{secs / count * 1e3:.3f} ms inside the traced stretch; "
          f"{len(pairs)} prompt chunks of <= {chunk} rows in {layers} K/V "
          f"layer(s)", flush=True)
    return _roofline_pct(pairs, secs, ctx["peaks"], ctx["chips"])
