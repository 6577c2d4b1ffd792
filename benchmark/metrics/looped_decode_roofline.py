"""Per-layer metric ``looped_decode_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). Trace time of
``paged_attention_decode`` inside the traced stretch against its least
bytes and operations (``benchmark/kernels/paged_attention_decode.py``). The
kernel runs once a layer APPLICATION a decode iteration (``num_hidden_layers
x total_ut_steps`` times in a model whose layers run several times a
token), each time over the live slots' whole context; the reader counts
the calls in the trace, so it asks nothing of the family.

The harness gives a reader the engine's counters at the WINDOW's two ends
and only prefill positions at the stretch's, so everything that can be
read inside the stretch is: the calls and their seconds from the trace,
and the context a decoded row from the clients' records (the j-th token a
request was SEEN to gain inside the stretch was decoded over its prompt,
the tokens before it and itself). Only the live rows an iteration come
from the window's counters (``decode_rows`` over ``decode_iterations``,
booked for the iterations that ran through this kernel; a closed loop
keeps every slot full, so the stretch's is the window's). Least work =
calls x the bytes and operations of one call over that many rows at that
context. **What is left of the assumption**: a token is seen up to one
dispatch after it was decoded (a scan of ``readout_stride`` iterations),
so the records' context leads the traced calls' by at most that many
tokens, 1-2 % here; and the decode rows that rode a mixed step (through
the append kernel, 1 iteration in 40) are among the records at the same
contexts. The window's own mean (``decode_ctx_tokens`` over
``decode_rows``) is printed beside it and not used: the clients of a
closed loop can stay in step, and a stretch at a fixed place in the
window then sees one part of the sawtooth of their contexts (12.7 %
under the window's mean while PR 34's window held one cohort of eight,
1.1 % once it held 37 requests), and a faster kernel moves which part.
The share is of the bandwidth roof: a token's keys and values are read
once for 4 flops a byte pair. Nothing to read (None) where the trace has
no such kernel, the program keeps no such counters, or no token was seen
inside the stretch."""
from benchmark.harness import loader
from benchmark.harness.readers import _roofline_pct
from benchmark.harness.trace import TraceError

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
COUNTERS = ("decode_ctx_tokens", "decode_rows", "decode_iterations")


def _stretch_context(ctx):
    """(tokens held, rows) over the tokens the clients saw arrive inside
    the traced stretch: the j-th of ``new`` tokens seen after ``before``
    was decoded over ``n_prompt + before + j`` tokens."""
    st = ctx.get("stretch")
    held = rows = 0
    if st is None or st.t0 is None or st.t1 is None:
        return held, rows
    for r in ctx["records"]:
        for t, before, new in r.events:
            if st.t0 <= t < st.t1:
                rows += new
                held += new * (r.n_prompt + before) + new * (new + 1) // 2
    return held, rows


def read(ctx):
    tr, s0, s1 = ctx.get("trace"), ctx.get("stats0"), ctx.get("stats1")
    if tr is None or not s0 or not s1 \
            or any(key not in s for s in (s0, s1) for key in COUNTERS):
        return None
    k = loader.module("kernels", "paged_attention_decode")
    try:
        secs, calls = tr.op_seconds(k.PATTERN)
    except TraceError:          # no such kernel in this trace
        return None
    tokens, rows, iterations = (s1[key] - s0[key] for key in COUNTERS)
    held, seen = _stretch_context(ctx)
    if not calls or secs <= 0 or iterations <= 0 or not seen:
        return None
    print(f"[trace] looped_decode_roofline: mean context a decoded row "
          f"{held / seen:.1f} tokens inside the traced stretch (the "
          f"clients' records, {seen} tokens; used), "
          f"{tokens / max(rows, 1):.1f} over the window (engine counters)",
          flush=True)
    c = ctx["cell"].config
    heads = int(c["num_attention_heads"])
    hd = int(c.get("head_dim") or int(c["hidden_size"]) // heads)
    live = rows / iterations
    pair = k.least(live * held / seen, heads, int(c["num_key_value_heads"]),
                   hd, calls, seqs=live)
    return _roofline_pct([pair], secs, ctx["peaks"], ctx["chips"])
