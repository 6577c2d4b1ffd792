"""Disaggregated prefill/decode serving: cross-replica KV shipping.

Run: python examples/serve_disagg.py     # tiny demo model, CPU-friendly
Shows: a two-replica fleet where replica 0 ONLY prefills and replica 1
ONLY decodes. A generate request is submitted to the prefill replica as
a one-token leg with KV export staged at finish; the router ships the
staged entry over the transport (in-process loopback here — the PTKV
wire format is bytes-on-wire, so an RDMA/ICI transport is one class),
the decode replica imports it into its swap store, and the request
resumes there with the KV tier's one-token stitch: ONE prefill token
per migration, zero re-prefill, token-exact vs mixed placement (greedy
and seeded-sampled). Any ship failure falls back to plain re-prefill
with unchanged tokens. Also printed: ship counters, the
migration-latency histogram with its per-phase split, the fleet
explain_tail verdicts, and the per-replica kv_tier view. On exit the
router dumps its postmortem artifacts — the STITCHED cross-replica
Perfetto trace (one connected flow-linked chain per migrated request;
open at ui.perfetto.dev) and the fleet debug-bundle directory readable
by ``python -m paddle_tpu.profiler.bundle`` — under
``SERVE_DISAGG_OUT`` (default docs/artifacts/).
"""
import json
import os

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import FlightRecorder
from paddle_tpu.serving import AsyncLLMServer, ReplicaRouter
from paddle_tpu.serving.cluster import tp_engine


def build_engine(replica=0):
    """One replica's engine, pinned to its own device: a one-device
    ("tp",) mesh places the weights, the pools and the step programs
    there (replicas wrap when there are fewer devices than replicas)."""
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=256)
    model = LlamaForCausalLM(cfg).bfloat16()
    model.eval()
    # the ship path rides the KV tier's gather/scatter: paged + fused
    # are required on both ends (import_kv validates the geometry)
    devs = jax.devices()
    return tp_engine(model, tp=1, devices=[devs[replica % len(devs)]],
                     max_batch=4, max_seq_len=128, chunk_size=32,
                     cache_impl="paged", block_size=16, scheduler="fused",
                     sampling_seed=7)


def main():
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, size=(n,)).astype(np.int32)
               for n in (48, 33, 61)]

    # reference: the same prompts on ONE mixed engine — disaggregation
    # must not change a single token
    ref = [r.token_ids for r in
           build_engine().generate(prompts, max_new_tokens=12)]

    replicas = [AsyncLLMServer(build_engine(i), replica=i,
                               flight_recorder=FlightRecorder())
                for i in range(2)]
    with ReplicaRouter(replicas,
                       roles={"prefill": [0], "decode": [1]}) as router:
        handles = [router.submit(p, max_new_tokens=12) for p in prompts]
        for h, want in zip(handles, ref):
            res = h.result(timeout=300)
            ok = "token-exact" if res.token_ids == want else "MISMATCH"
            tc = res.trace_ctx
            print(f"req {res.request_id}: {res.token_ids[:6]}... "
                  f"({res.finish_reason}, {ok})  trace {tc.trace_id} "
                  f"hop {tc.hop} via {tc.via}")

        snap = router.snapshot()
        print(f"\nshipped {router.stats['kv_shipped']} requests "
              f"({snap['transport']['ship_bytes']} wire bytes), "
              f"{router.stats['kv_ship_fallback']} fallbacks")
        print("migration latency:", snap["migration_latency"])
        for phase, h in snap["migration_phases"].items():
            print(f"  kv_ship:{phase}: {h}")
        for e in router.explain_tail(0.0, top=3):
            print(f"  tail: req {e['request_id']} [{e.get('trace_id')}] "
                  f"gap {e['gap_s'] * 1e3:.1f}ms <- {e['cause']}")
        dec = snap["replicas"][1]
        print(f"decode replica prefill_tokens="
              f"{replicas[1].engine.stats['prefill_tokens']} "
              f"(= one stitch token per migration), kv_tier={dec['kv_tier']}")

        # postmortem artifacts: the stitched cross-replica trace (flow
        # events join the prefill and decode legs of each request into
        # one chain) + a fleet debug-bundle directory
        out = os.environ.get("SERVE_DISAGG_OUT",
                             os.path.join(os.path.dirname(__file__),
                                          "..", "docs", "artifacts"))
        trace_path = os.path.join(out, "serve_disagg_trace.json")
        router.export_merged_trace(trace_path)
        ev = json.load(open(trace_path))["traceEvents"]
        flows = sum(1 for e in ev if e.get("ph") == "s")
        print(f"\nstitched trace: {len(ev)} events, {flows} cross-replica "
              f"flows -> {trace_path}  (open at ui.perfetto.dev)")
        paths = router.dump_debug_bundle(
            os.path.join(out, "serve_disagg_bundle"))
        print(f"fleet debug bundle -> {os.path.dirname(paths['router'])}  "
              f"(read: python -m paddle_tpu.profiler.bundle "
              f"{paths['replicas'][0]})")
    for line in replicas[1].telemetry.prometheus_text().splitlines():
        if "kv_ship" in line and not line.startswith("#"):
            print(line)


if __name__ == "__main__":
    main()
