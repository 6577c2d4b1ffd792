"""The arithmetic behind the per-layer metrics. Each metric has a small
file of its own under ``benchmark/metrics/`` that names its quantity here;
a quantity that finds nothing to read returns None and the harness leaves
the metric out of the line."""
from __future__ import annotations

from . import loader
from .trace import MODULES_LINE

SERVE_MODULES = r"^jit_(fused_step|multi_step|step)(\(|$)"
TRAIN_MODULES = r"^jit_step_fn(\(|$)"


def _delta(ctx, *keys):
    return sum(ctx["stats1"][k] - ctx["stats0"][k] for k in keys)


def host_ms_per_step(ctx):
    """Host seconds the engine spent admitting, dispatching and emitting,
    per engine step, over the window (``engine.stats``)."""
    if ctx["kind"] != "serve":
        return None
    steps = _delta(ctx, "steps")
    if steps <= 0:
        return None
    return 1e3 * _delta(ctx, "admit_time_s", "dispatch_time_s",
                        "emit_time_s") / steps


def tokens_per_step(ctx):
    if ctx["kind"] != "serve":
        return None
    steps = _delta(ctx, "steps")
    if steps <= 0:
        return None
    return _delta(ctx, "prefill_tokens", "tokens_generated") / steps


def step_device_ms(ctx):
    """Mean device duration of the step programs' module spans inside the
    traced stretch."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    pattern = SERVE_MODULES if ctx["kind"] == "serve" else TRAIN_MODULES
    secs, count = tr.op_seconds(pattern, MODULES_LINE)
    return 1e3 * secs / count


def device_idle_pct(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def _roofline_pct(least_pairs, seconds, peaks, chips):
    """100 * (the least time the chip could take) / (the time it took).
    Each call's least time is the larger of its operations over the peak
    rate and its bytes over the peak bandwidth; ``chips`` chips share the
    work evenly and ``seconds`` is their mean."""
    least = sum(max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
                for f, b in least_pairs) / chips
    return 100.0 * least / seconds


def _model(ctx):
    c = ctx["cell"].config
    heads = int(c["num_attention_heads"])
    return heads, int(c["num_key_value_heads"]), \
        int(c["hidden_size"]) // heads, ctx["layers_here"]


def paged_append_roofline(ctx):
    """Trace time of the append kernel against the prompt chunks that were
    prefilled inside the stretch: from each request's prefill position at
    the stretch's two ends (the engine's slots, read at both ends). Decode
    rows that ride a mixed step are left out: the count is a lower bound."""
    tr, st = ctx.get("trace"), ctx.get("stretch")
    if tr is None or st is None or st.snap0 is None or st.snap1 is None:
        return None
    app = loader.module("kernels", "paged_attention_append")
    secs, _ = tr.op_seconds(app.PATTERN)
    heads, kvh, hd, layers = _model(ctx)
    chunk = int(ctx["cell"].config["engine"]["chunk_size"])
    pairs = []
    for r in ctx["records"]:
        rid = r.handle.request_id
        n = r.n_prompt

        def pos(snap, t_end):
            if rid in snap:
                return min(snap[rid], n)
            return n if (r.t_first is not None and r.t_first <= t_end) else 0
        a, b = pos(st.snap0, st.t0), pos(st.snap1, st.t1)
        while a < b:
            e = min(b, (a // chunk + 1) * chunk)
            pairs.append(app.least(a, e, heads, kvh, hd, layers))
            a = e
    if not pairs:
        return None
    return _roofline_pct(pairs, secs, ctx["peaks"], ctx["chips"])


def flash_train_roofline(ctx):
    tr = ctx.get("trace")
    if tr is None or ctx["kind"] != "train":
        return None
    k = loader.module("kernels", "flash_attention")
    secs, _ = tr.op_seconds(k.PATTERN)
    heads, kvh, hd, layers = _model(ctx)
    f, b = k.least(ctx["batch"], ctx["seq"], heads, kvh, hd, layers)
    return _roofline_pct([(f * ctx["traced_steps"], b * ctx["traced_steps"])],
                         secs, ctx["peaks"], ctx["chips"])
