"""Per-layer metric ``latent_append_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). Trace time of the
latent-pool attention kernel against the prompt chunks prefilled inside
the traced stretch (each request's prefill position at the stretch's two
ends, as ``paged_append_roofline`` counts them). Decode rows that ride a
mixed step are left out: the count is a lower bound, the share an
under-estimate. Nothing to read (None) where the trace has no such kernel
or the configuration no latent-attention layer."""
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
    lin = c.get("linear_attn_config")
    if not lin or "kv_lora_rank" not in c:
        return None
    k = loader.module("kernels", "latent_attention_append")
    try:
        secs, count = tr.op_seconds(k.PATTERN)
    except TraceError:          # no such kernel in this trace
        return None
    if not count or secs <= 0:
        return None
    layers = sum(1 for i in lin["full_attn_layers"]
                 if i <= int(c["num_hidden_layers"]))
    heads, dv = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
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
