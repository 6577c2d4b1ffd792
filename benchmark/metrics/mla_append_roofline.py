"""Per-layer metric ``mla_append_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). Trace time of the
latent-pool attention kernel against the prompt chunks prefilled inside
the traced stretch, in a model whose EVERY layer attends through the
latent pool (``latent_append_roofline`` counts the latent layers of a
model that names them in ``linear_attn_config``): each request's prefill
position at the stretch's two ends, as ``paged_append_roofline`` counts
them, ``benchmark/kernels/latent_attention_append.py``'s least work a
chunk with ``num_hidden_layers`` layers, ``num_attention_heads`` heads, a
key of ``kv_lora_rank + qk_rope_head_dim`` and values of ``kv_lora_rank``.
Decode rows (those that ride a mixed step and those of the all-decode
scans) are left out of the work and not of the time: the count is a lower
bound, the share an under-estimate. Nothing to read (None) where the trace
has no such kernel, or the configuration is not latent in every layer (no
``q_lora_rank``, or a ``linear_attn_config``)."""
from benchmark.harness import loader
from benchmark.harness.readers import _roofline_pct
from benchmark.harness.trace import TraceError

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"


def read(ctx):
    tr, st = ctx.get("trace"), ctx.get("stretch")
    if tr is None or st is None or st.snap0 is None or st.snap1 is None:
        return None
    c = ctx["cell"].config
    if "kv_lora_rank" not in c or "q_lora_rank" not in c \
            or c.get("linear_attn_config"):
        return None
    k = loader.module("kernels", "latent_attention_append")
    try:
        secs, count = tr.op_seconds(k.PATTERN)
    except TraceError:          # no such kernel in this trace
        return None
    if not count or secs <= 0:
        return None
    layers, heads = int(c["num_hidden_layers"]), int(c["num_attention_heads"])
    dv = int(c["kv_lora_rank"])
    width = dv + int(c["qk_rope_head_dim"])
    chunk = int(c["engine"]["chunk_size"])
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
            pairs.append(k.least(a, e, heads, width, dv, layers))
            a = e
    if not pairs:
        return None
    return _roofline_pct(pairs, secs, ctx["peaks"], ctx["chips"])
