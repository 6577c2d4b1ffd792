"""Per-layer metric ``window_append_roofline``: layer "kernels", moves
``serve_tok_s`` (better higher, source device_trace). Trace time of the
latent-pool attention kernel, which in a model whose other layers attend a
selected set runs in the WINDOW layers alone, against a least work that
counts the window's positions only (``benchmark/kernels/
window_latent_append.py``; ``swa_num_attention_heads`` heads, a key of
``swa_kv_lora_rank + swa_qk_rope_head_dim`` and values of
``swa_kv_lora_rank``). The pairs are the stretch's own, a step at a time,
from its ``pt:engine.emit`` spans: ``window_keys`` (a step's
``win_keys_live``: the sum of ``min(window, pos + 1)`` over live rows and
window layers); the window layers' live (row, layer) pairs are
``indexed_rows`` times the window layers a full layer inside the depth
(``layer_types``). Nothing to read (None) where the trace has no such
kernel, the configuration names no ``layer_types`` or the program writes
no such ids."""
from benchmark.harness import loader
from benchmark.harness.trace import TraceError

UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tok_s"
BETTER = "higher"
SOURCE = "device_trace"
IDS = ("window_keys", "indexed_rows")


def layer_counts(config):
    """(full layers, window layers) inside the depth, or None."""
    if "layer_types" not in config:
        return None
    kinds = config["layer_types"][:int(config["num_hidden_layers"])]
    return (sum(k == "full_attention" for k in kinds),
            sum(k == "sliding_attention" for k in kinds))


def read(ctx):
    tr = ctx.get("trace")
    counts = layer_counts(ctx["cell"].config)
    if tr is None or not counts or not all(counts):
        return None
    k = loader.module("kernels", "window_latent_append")
    try:
        secs, calls = tr.op_seconds(k.PATTERN)
    except TraceError:          # no such kernel in this trace
        return None
    emits = loader.module("metrics", "dsa_index_roofline").emitted(ctx, IDS)
    if not emits or not calls or secs <= 0:
        return None
    c, peaks = ctx["cell"].config, ctx["peaks"]
    heads, dv = int(c["swa_num_attention_heads"]), int(c["swa_kv_lora_rank"])
    width = dv + int(c["swa_qk_rope_head_dim"])
    chunk = int(c["engine"]["chunk_size"])
    full, window = counts
    least, pairs = 0.0, 0
    for ids in emits:
        f, b = k.least(int(ids["window_keys"]),
                       int(ids["indexed_rows"]) * window / full, heads,
                       width, dv, chunk)
        least += max(f / peaks["flops_per_s"], b / peaks["bytes_per_s"])
        pairs += int(ids["window_keys"])
    print(f"[trace] window_append_roofline: {calls:.0f} calls of "
          f"{secs / calls * 1e3:.3f} ms inside the traced stretch; its "
          f"{len(emits)} emitted steps speak of {pairs} (row, position) "
          f"pairs inside the rows' windows in {window} window layer(s), "
          f"least {least * 1e3:.2f} ms", flush=True)
    return 100.0 * least / secs / ctx["chips"]
