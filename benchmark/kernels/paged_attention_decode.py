"""Paged attention, decode form
(``paddle_tpu/ops/kernels/paged_attention.py:paged_attention_decode``): one
new token a sequence attends the ``ctx_tokens`` keys and values its block
table holds, itself included, and writes its own key and value."""
PATTERN = r"paged_attention_decode"


def least(ctx_tokens, heads, kv_heads, head_dim, applications, seqs=1,
          bytes_per_el=2):
    """(flops, bytes) of ``applications`` calls of the kernel (layers x
    loop steps of one decode iteration, or the calls counted in a trace),
    each over ``seqs`` sequences that hold ``ctx_tokens`` tokens between
    them, the new ones included: each query head meets every key once (2
    flops a multiply-add, for QK and for PV); keys and values of the
    context read once; q read and the output written once a sequence, its
    new key and value written once."""
    flops = 4.0 * heads * head_dim * ctx_tokens * applications
    els = (ctx_tokens * 2 * kv_heads + seqs * (2 * heads + 2 * kv_heads)) \
        * head_dim * applications
    return flops, float(els * bytes_per_el)
