"""Driver benchmark — one JSON line per BASELINE workload config.

Default (`BENCH_MODEL` unset / `all`): runs every BASELINE.md config plus
the decode and serving benchmarks — resnet50, bert, vit, unet, llama_decode
(plus its int8/int4 weight-only rungs, re-baselining the quantized decode
ratios every run), llama_paged_decode (Pallas paged-attention kernel
on/off A/B), llama_serve (flight-recorder, supervision AND multi-step
readout-stride on/off A/Bs — the latter reports per-arm
rtt/dispatch/host-sync shares), llama_serve_fused (fused prefill+decode
scheduler on/off A/B), llama_serve_prefix_cache (automatic prefix caching
on/off A/B: shared-system-prompt hit-rate + zero-reuse overhead guard),
llama_serve_slo (multi-tenant SLO isolation: adversarial flood vs victim
tenant, per-tenant p99 TTFT + burn-rate alert fire/clear),
llama_serve_spec, then the flagship llama LAST — each in its own
subprocess, one JSON line each, so the tail line stays the llama MFU vs
the 45% north star (BASELINE.json).
`BENCH_MODEL=llama` (or any single name) prints exactly one line.

The flagship line measures the fused compiled training step (fwd+bwd+AdamW,
bf16 params + fp32 master weights, Pallas flash attention) of a Llama-family
decoder on one TPU chip. Model size is chosen to fill a single v5e chip
(16 GB HBM); on a pod slice the same code scales via the fleet
hybrid-parallel path (see __graft_entry__.py).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

# peak dense bf16 FLOPs/s per chip by TPU generation
_PEAK = {
    "v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12, "v4": 275e12,
    "v6 lite": 918e12, "v6e": 918e12, "v3": 123e12, "v2": 45e12,
}


def _peak_flops(device):
    """Peak FLOP/s of ``device`` from the table above, or None for a device
    that is not in it (a CPU, an unknown chip): a utilisation is computed
    from a table entry or not at all — never from an assumed peak."""
    kind = getattr(device, "device_kind", "").lower()
    for key, val in _PEAK.items():
        if key in kind:
            return val
    return None


def _pct_of_peak(flops_per_sec, peak):
    """``flops_per_sec`` as a percentage of ``peak``; None when the device
    has no table entry (see :func:`_peak_flops`)."""
    if peak is None:
        return None
    return round(flops_per_sec / peak * 100, 2)


def _time_train_step(step, args, steps):
    """Differential timing of a TrainStep (one warmup cycle, subtract one
    timed unit, sync via scalar loss fetch)."""
    loss = step(*args)
    float(np.asarray(loss._value))
    t0 = time.perf_counter()
    loss = step(*args)
    float(np.asarray(loss._value))
    d1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps + 1):
        loss = step(*args)
    final_loss = float(np.asarray(loss._value))
    dn = time.perf_counter() - t0
    return max(dn - d1, 1e-9) / steps, final_loss


def _forward_flops(model, arg_tensors):
    """Model FLOPs of one forward pass from XLA's cost model on the
    UNOPTIMIZED lowered HLO — i.e. the math as written, so grad-checkpoint
    recompute does not inflate the number. Returns None only when the
    backend's cost analysis carries no flop count; errors propagate."""
    import jax
    from paddle_tpu.core.tensor import Tensor, functional_mode
    from paddle_tpu.jit.functional_call import collect_state, bind_state

    _, params, _, buffers = collect_state(model)
    state = params + buffers

    def fwd(state_vals, arg_vals):
        with functional_mode(), bind_state(state, state_vals):
            out = model(*[Tensor(v) for v in arg_vals])
        leaves = jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: hasattr(x, "_value"))
        return [getattr(x, "_value", x) for x in leaves]

    lowered = jax.jit(fwd).lower([t._value for t in state],
                                 [t._value for t in arg_tensors])

    def norm(c):
        return c[0] if isinstance(c, (list, tuple)) else c

    cost = norm(lowered.cost_analysis())
    if cost is None or "flops" not in cost:
        # some backends only cost-analyze the COMPILED module;
        # forward-only, so remat can't inflate it
        cost = norm(lowered.compile().cost_analysis())
    if cost is None or "flops" not in cost:
        return None
    return float(cost["flops"])


def _artifact_dir():
    """Where serve benches persist their observability artifacts
    (telemetry snapshots, sample chrome traces): BENCH_ARTIFACT_DIR or
    docs/artifacts next to this file. Created on demand."""
    d = os.environ.get(
        "BENCH_ARTIFACT_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "docs", "artifacts"))
    os.makedirs(d, exist_ok=True)
    return d


def _serve_multi_step_ab(model, prompts, new_tokens, B, cap, stride,
                         rtt_s=0.0, chunk_size=256, pipeline_depth=2,
                         timeout=1800):
    """Multi-step on-device decode A/B: the same prompts served through
    TWO fused-scheduler engines — ``readout_stride=stride`` (the k-step
    compiled decode loop with in-graph early exit) vs ``stride=1`` (one
    host round-trip per decode step). Per arm, the host-tax split comes
    from the FLIGHT RECORDER's StepRecords (the engine-measured
    dispatch/sync wall splits, summed over the run):

    * ``host_sync_share``  — device→host token syncs / wall,
    * ``dispatch_share``   — host-side dispatch enqueue / wall,
    * ``rtt_share``        — rtt_s x host round-trips / wall (each
      StepRecord is one round-trip; the stride arm makes ~1/k as many),
    * ``host_tax_s`` / ``host_tax_ms_per_token`` — host_sync + dispatch
      in ABSOLUTE seconds (and per token). The arms serve the identical
      workload, so this is the fair cross-arm comparison everywhere: on
      CPU the dispatch timer absorbs blocked device compute (no real
      async enqueue), which inflates the FASTER arm's share-of-own-wall
      even as its absolute host tax drops; on TPU (true async dispatch)
      the share comparison agrees with the absolute one.

    Greedy streams must be token-exact across arms (asserted); the
    returned dict carries both arms plus ``multi_step_speedup``.
    Shared by the llama_serve bench and the tier-1 CPU smoke test."""
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.serving import AsyncLLMServer
    from paddle_tpu.profiler import FlightRecorder

    arms, streams = {}, {}
    for arm, s in (("off", 1), ("on", int(stride))):
        eng = LLMEngine(model, max_batch=B, max_seq_len=cap,
                        chunk_size=chunk_size, scheduler="fused",
                        readout_stride=s)
        eng.generate([prompts[0]], max_new_tokens=2)  # warm the programs
        eng.reset_stats()
        rec = FlightRecorder()
        srv = AsyncLLMServer(eng, max_queue_size=len(prompts) + 1,
                             flight_recorder=rec,
                             pipeline_depth=pipeline_depth)
        srv.start()
        t0 = time.perf_counter()
        hs = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
        outs = [h.result(timeout=timeout) for h in hs]
        wall = time.perf_counter() - t0
        srv.stop()
        toks = sum(len(o.token_ids) for o in outs)
        recs = rec.records()
        sync_s = sum(r.sync_s for r in recs)
        disp_s = sum(r.dispatch_s for r in recs)
        arms[arm] = {
            "readout_stride": s,
            "tokens_per_sec": round(toks / wall, 1),
            "host_round_trips": len(recs),
            "multi_steps": int(eng.stats["multi_steps"]),
            "host_sync_share": round(sync_s / wall, 4),
            "dispatch_share": round(disp_s / wall, 4),
            "rtt_share": round(rtt_s * len(recs) / wall, 4),
            "host_tax_s": round(sync_s + disp_s, 4),
            "host_tax_ms_per_token": round(
                (sync_s + disp_s) / max(toks, 1) * 1e3, 4),
            "pipeline_depth": srv.pipeline_depth,
        }
        streams[arm] = [o.token_ids for o in outs]
    token_parity = streams["on"] == streams["off"]
    assert token_parity, "multi-step decode changed a greedy stream"
    return {
        "multi_step_speedup": round(
            arms["on"]["tokens_per_sec"]
            / max(arms["off"]["tokens_per_sec"], 1e-9), 3),
        "readout_stride": int(stride),
        "token_parity": token_parity,
        "on": arms["on"], "off": arms["off"],
    }


def _bench_other(model_name):
    """Secondary BASELINE workloads (ResNet-50 / BERT-base MLM / ViT-L /
    SD-UNet) — same JSON contract, per-domain throughput metric. The driver
    default stays the flagship Llama config."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit.api import TrainStep

    if os.environ.get("BENCH_PRNG"):
        # 'rbg' = XLA's rng-bit-generator: hardware-rate random bits vs
        # threefry's VPU integer chains — the lever for dropout-mask cost
        # on elementwise dropout sites (distribution-identical, different
        # stream)
        jax.config.update("jax_default_prng_impl",
                          os.environ["BENCH_PRNG"])
    steps = int(os.environ.get("BENCH_STEPS", "8"))
    rng = np.random.default_rng(0)
    paddle.seed(0)
    peak = _peak_flops(jax.devices()[0])

    if model_name == "resnet50":
        from paddle_tpu.vision.models import resnet50
        B = int(os.environ.get("BENCH_BATCH", "128"))
        # NHWC end-to-end: the TPU-preferred conv layout (~1.5x the 3x3
        # stack vs NCHW, no transposes anywhere); BENCH_LAYOUT=NCHW for A/Bs
        layout = os.environ.get("BENCH_LAYOUT", "NHWC")
        model = resnet50(num_classes=1000, data_format=layout).bfloat16()
        optimizer = opt.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=model.parameters())
        step = TrainStep(model, lambda m, x, y: F.cross_entropy(m(x), y),
                         optimizer)
        shape = (B, 3, 224, 224) if layout == "NCHW" else (B, 224, 224, 3)
        x = paddle.to_tensor(rng.standard_normal(
            shape).astype(np.float32)).astype("bfloat16")
        y = paddle.to_tensor(rng.integers(0, 1000, B))
        # forward FLOPs from XLA's cost model (train = 3x fwd). The old
        # hand constant (3 * 4.1e9 * B) was GMACs, not FLOPs — it halved
        # the reported MFU; the per-instruction HLO count in
        # docs/artifacts/conv_roofline_proof.json confirms ~8.2 GFLOP/img
        fwd_flops = _forward_flops(model, (x,))
        dt, loss = _time_train_step(step, (x, y), steps)
        flops = 3 * (fwd_flops if fwd_flops is not None else 8.2e9 * B)
        return {"metric": "resnet50_1chip_train_imgs_per_sec",
                "value": round(B / dt, 1), "unit": "imgs/s",
                "vs_baseline": None, "mfu_pct": _pct_of_peak(flops / dt, peak),
                "step_time_s": round(dt, 4), "loss": loss}

    if model_name == "bert":
        from paddle_tpu.models import BertConfig, BertForMaskedLM
        # Round-5 sweep (24-step runs), all at rbg dropout masks (+2.6 MFU
        # over threefry — hardware rng-bit-generator vs VPU integer
        # chains): 48/42.0 STABLE, 64/37.0 (spilling schedule), 96/~52
        # WHEN it compiles — the no-remat B=96 program OOMs
        # nondeterministically under remote-compiler fusion variance, so
        # the bench LADDERS 96 -> 48 -> 24. The alternatives were
        # measured and rejected: full remat costs exactly the +1/3
        # recompute FLOPs on this compute-bound model (50.7 -> 38.0
        # dropout-free), dots_saveable remat still OOMs at 96 (keeps the
        # dot outputs) and only adds cost at 48 (30.9), and the chunked
        # fused head compiles B=96 DETERMINISTICALLY but its +23% head
        # FLOPs land at a stable 34.8 — worse than the 48-rung
        # (BENCH_CHUNKED_HEAD=1 to opt in; it remains the right tool for
        # larger-vocab models).
        if "BENCH_PRNG" not in os.environ:
            jax.config.update("jax_default_prng_impl", "rbg")
        B = int(os.environ.get("BENCH_BATCH", "96"))
        S = int(os.environ.get("BENCH_SEQ", "512"))
        cfg = BertConfig(
            max_position_embeddings=S,
            hidden_dropout_prob=float(os.environ.get("BENCH_DROPOUT", "0.1")),
            attention_probs_dropout_prob=float(
                os.environ.get("BENCH_ATTN_DROPOUT", "0.1")),
            # SELECTIVE remat: bert is compute-bound, so full remat costs
            # the whole +1/3 step FLOPs (measured 50.7 -> 38.0% MFU); a few
            # rematted layers shave just the compile-time temp peak that
            # made no-remat B=96 OOM nondeterministically
            use_recompute=os.environ.get("BENCH_REMAT", "0") == "1",
            recompute_layers=int(os.environ.get("BENCH_REMAT_LAYERS", "12")),
            recompute_policy=os.environ.get("BENCH_REMAT_POLICY") or None,
            fuse_mlm_head_ce=os.environ.get("BENCH_CHUNKED_HEAD",
                                            "0") == "1")
        if os.environ.get("BENCH_BF16_MOMENTS", "1") == "1":
            # same lever as the vit config: AdamW moment traffic in bf16
            from paddle_tpu.core.flags import set_flags
            set_flags({"adamw_bf16_moments": True})
        # rung choice is measured: 96/50.5 (when it compiles), 48/39.8-40.2,
        # 24/38.4 — and 64 is a trap (31.4%: the compiler picks a spilling
        # schedule there), so the ladder skips it
        ladder = [b for b in (B, 48, 24) if b <= B] or [B]
        last_err = None
        for B_try in ladder:
            paddle.seed(0)
            model = BertForMaskedLM(cfg).bfloat16()
            n_params = sum(int(np.prod(p.shape))
                           for p in model.parameters())
            optimizer = opt.AdamW(learning_rate=1e-4,
                                  parameters=model.parameters(),
                                  multi_precision=True)
            step = TrainStep(model,
                             lambda m, ids, lbl: m(ids, labels=lbl)[0],
                             optimizer)
            ids = paddle.to_tensor(
                rng.integers(0, cfg.vocab_size, (B_try, S)), dtype="int32")
            lbl = paddle.to_tensor(
                rng.integers(0, cfg.vocab_size, (B_try, S)), dtype="int32")
            try:
                dt, loss = _time_train_step(step, (ids, lbl), steps)
            except Exception as e:  # compile OOM at the edge config
                # only resource exhaustion ladders down — a genuine
                # regression (shape bug, import error) must fail loudly,
                # not silently demote the benchmark
                msg = str(e)
                if not any(t in msg.upper() for t in
                           ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED",
                            "OUT OF MEMORY", "OOM", "ALLOCAT")):
                    raise
                # keep only the message — the exception's traceback would
                # pin this rung's device buffers and OOM every later rung
                last_err = RuntimeError(f"bert B={B_try}: {msg[:300]}")
                del step, optimizer, model, ids, lbl
                import gc
                gc.collect()
                continue
            toks = B_try * S / dt
            return {"metric": "bert_base_mlm_1chip_tokens_per_sec",
                    "value": round(toks, 1), "unit": "tokens/s",
                    "vs_baseline": None,
                    "mfu_pct": _pct_of_peak(6 * n_params * toks, peak),
                    "step_time_s": round(dt, 4), "params": n_params,
                    "batch": B_try,
                    "prng": os.environ.get("BENCH_PRNG", "rbg"),
                    "loss": loss}
        raise last_err

    if model_name == "vit":
        from paddle_tpu.vision.models import vit_large_patch16
        # defaults = best measured config (round 4 sweep, 24-step runs):
        # B=40 + bf16 AdamW moments -> 45.4% MFU (was 38.0 at B=32 + fp32
        # moments). The gap was optimizer-state traffic (307M params x 8B
        # fp32 moments r/w per step) plus too little per-step compute to
        # amortize the weight+state streaming; B>=56 regresses again
        # (activation working set without remat). Curve: 32/38.0, 32+bf16m/
        # 39.0, 40/45.4, 48/44.1-44.5, 56/42.5, 64/43.1, 72/40.3, 96/36.5.
        B = int(os.environ.get("BENCH_BATCH", "40"))
        if os.environ.get("BENCH_BF16_MOMENTS", "1") == "1":
            from paddle_tpu.core.flags import set_flags
            set_flags({"adamw_bf16_moments": True})
        model = vit_large_patch16(num_classes=1000).bfloat16()
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        optimizer = opt.AdamW(learning_rate=3e-4,
                              parameters=model.parameters(),
                              multi_precision=True)
        step = TrainStep(model, lambda m, x, y: F.cross_entropy(m(x), y),
                         optimizer)
        x = paddle.to_tensor(rng.standard_normal(
            (B, 3, 224, 224)).astype(np.float32)).astype("bfloat16")
        y = paddle.to_tensor(rng.integers(0, 1000, B))
        dt, loss = _time_train_step(step, (x, y), steps)
        tokens_per_img = (224 // 16) ** 2 + 1
        return {"metric": "vit_large_1chip_train_imgs_per_sec",
                "value": round(B / dt, 1), "unit": "imgs/s",
                "vs_baseline": None,
                "mfu_pct": _pct_of_peak(
                    6 * n_params * tokens_per_img * B / dt, peak),
                "step_time_s": round(dt, 4), "params": n_params, "loss": loss}

    if model_name == "unet":
        from paddle_tpu.models import (UNetConfig, UNetModel, diffusion_loss)
        import jax.numpy as jnp
        B = int(os.environ.get("BENCH_BATCH", "4"))
        cfg = UNetConfig.sd_unet(
            use_recompute=os.environ.get("BENCH_REMAT", "1") == "1")
        model = UNetModel(cfg).bfloat16()
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        optimizer = opt.AdamW(learning_rate=1e-4,
                              parameters=model.parameters(),
                              multi_precision=True)
        alphas = paddle.to_tensor(np.linspace(0.999, 0.01, 1000)
                                  .astype(np.float32))

        def loss_fn(m, lat, t, ctx, noise):
            return diffusion_loss(m, lat, t, ctx, noise, alphas)

        step = TrainStep(model, loss_fn, optimizer)
        # NHWC: the TPU-native UNet is channels-last throughout (models/unet.py)
        lat = paddle.to_tensor(rng.standard_normal(
            (B, 64, 64, 4)).astype(np.float32)).astype("bfloat16")
        t = paddle.to_tensor(rng.integers(0, 1000, B))
        ctx = paddle.to_tensor(rng.standard_normal(
            (B, 77, 768)).astype(np.float32)).astype("bfloat16")
        noise = paddle.to_tensor(rng.standard_normal(
            (B, 64, 64, 4)).astype(np.float32)).astype("bfloat16")
        # forward FLOPs via XLA's cost model (train = 3x fwd); measured BEFORE
        # the timed steps so its trace never lands in a timing window
        fwd_flops = _forward_flops(model, (lat, t, ctx))
        dt, loss = _time_train_step(step, (lat, t, ctx, noise), steps)
        out = {"metric": "sd_unet_1chip_train_samples_per_sec",
               "value": round(B / dt, 2), "unit": "samples/s",
               "vs_baseline": None, "step_time_s": round(dt, 4),
               "params": n_params, "loss": loss}
        if fwd_flops is not None:
            out["mfu_pct"] = _pct_of_peak(3 * fwd_flops / dt, peak)
        return out

    if model_name == "llama_decode":
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.jit.functional_call import collect_state, read_values
        import jax.numpy as jnp
        B = int(os.environ.get("BENCH_BATCH", "8"))
        prompt = int(os.environ.get("BENCH_PROMPT", "512"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "128"))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=prompt + new_tokens)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        # logical param count, BEFORE any quantized re-packing
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        # weight-only quantized decode (BENCH_WEIGHT_DTYPE=int8|int4):
        # decode is weight-bandwidth-bound, so halving/quartering the
        # weight bytes per token-step is the serving-throughput lever
        weight_dtype = os.environ.get("BENCH_WEIGHT_DTYPE", "")
        if weight_dtype:
            from paddle_tpu.nn.quant import quantize_linears_for_inference
            quantize_linears_for_inference(model, weight_dtype=weight_dtype)
        ids_v = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, prompt)),
                            jnp.int32)
        # TWO-LENGTH DIFFERENTIAL (VERDICT r4 #7): time the full
        # prefill+decode pair at new_tokens and at a short control length,
        # and divide the time DELTA by the token delta. The old
        # pair-minus-prefill method subtracted a separately-timed prefill,
        # which under-subtracts fixed per-call costs (dispatch, donation
        # relayout, host round trip) and INFLATES absolute decode tok/s — the
        # builder's own int4 A/B already used this honest form.
        short = min(max(new_tokens // 8, 8), max(new_tokens // 2, 1))
        _, params, _, buffers = collect_state(model)
        state_vals = read_values(params + buffers)
        key = jax.random.PRNGKey(0)
        total = prompt + new_tokens

        def build_pair(n_new):
            prefill, decode = model._gen_programs(
                B, prompt, n_new, prompt + n_new, 0.0, 0, 1.0, None,
                "static", 64)

            def run_pair():
                l0, kb, vb = prefill(state_vals, ids_v)
                buf, n = decode(state_vals, kb, vb, l0, key,
                                jnp.float32(1.0), jnp.float32(1.0))
                int(np.asarray(n))
                return buf
            return prefill, run_pair

        prefill, run_long = build_pair(new_tokens)
        _, run_short = build_pair(short)

        def run_prefill():
            l0, kb, vb = prefill(state_vals, ids_v)
            float(np.asarray(l0[0, 0]))  # sync via scalar fetch

        # warm every program twice (donated-output relayout recompiles must
        # not land in a timing window)
        for f in (run_long, run_short):
            f()
            f()
        run_prefill()
        reps = int(os.environ.get("BENCH_STEPS", "8"))
        t0 = time.perf_counter()
        for _ in range(reps):
            run_prefill()
        t_prefill = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            run_short()
        t_short = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            run_long()
        t_long = (time.perf_counter() - t0) / reps
        t_decode = max(t_long - t_short, 1e-9)
        n_delta = new_tokens - short
        return {"metric": "llama_decode_tokens_per_sec",
                "value": round(B * n_delta / t_decode, 1),
                "unit": "tokens/s", "vs_baseline": None,
                "method": "two-length-differential",
                "decode_ms_per_token": round(
                    t_decode / n_delta * 1e3, 3),
                "new_tokens_long_short": [new_tokens, short],
                "prefill_tokens_per_sec": round(B * prompt / t_prefill, 1),
                "prefill_s": round(t_prefill, 4),
                "batch": B, "prompt_len": prompt, "new_tokens": new_tokens,
                "weight_dtype": weight_dtype or "bf16",
                "params": n_params}

    if model_name == "llama_paged_decode":
        # Paged-KV decode throughput with the Pallas paged-attention kernel
        # A/B'd against the dense-gather XLA fallback
        # (FLAGS_use_paged_attention) — the recorded number behind the
        # block-sparse-read claim. Two-length differential like
        # llama_decode; GQA by default (kv_heads = heads/4) since the
        # kernel is what unlocks cache_impl="paged" for GQA models.
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.jit.functional_call import collect_state, read_values
        from paddle_tpu.core.flags import set_flags
        import jax.numpy as jnp
        B = int(os.environ.get("BENCH_BATCH", "8"))
        prompt = int(os.environ.get("BENCH_PROMPT", "512"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "128"))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        kv_heads = int(os.environ.get("BENCH_KV_HEADS",
                                      str(max(heads // 4, 1))))
        block_size = int(os.environ.get("BENCH_BLOCK_SIZE", "64"))
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=kv_heads,
                          max_position_embeddings=prompt + new_tokens)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        ids_v = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, prompt)),
                            jnp.int32)
        short = min(max(new_tokens // 8, 8), max(new_tokens // 2, 1))
        _, params, _, buffers = collect_state(model)
        state_vals = read_values(params + buffers)
        key = jax.random.PRNGKey(0)
        reps = int(os.environ.get("BENCH_STEPS", "8"))

        def run_arm(kernel_on):
            # flag is read at trace time: flip it, then force a fresh trace
            # of the paged decode programs for this arm
            set_flags({"use_paged_attention": bool(kernel_on)})
            model._gen_cache = {}

            def build_pair(n_new):
                prefill, decode = model._gen_programs(
                    B, prompt, n_new, prompt + n_new, 0.0, 0, 1.0, None,
                    "paged", block_size)

                def run_pair():
                    l0, kb, vb = prefill(state_vals, ids_v)
                    buf, n = decode(state_vals, kb, vb, l0, key,
                                    jnp.float32(1.0), jnp.float32(1.0))
                    int(np.asarray(n))
                return run_pair

            run_long = build_pair(new_tokens)
            run_short = build_pair(short)
            for f in (run_long, run_short):  # warm twice (donation relayout)
                f()
                f()
            t0 = time.perf_counter()
            for _ in range(reps):
                run_short()
            t_short = (time.perf_counter() - t0) / reps
            t0 = time.perf_counter()
            for _ in range(reps):
                run_long()
            t_long = (time.perf_counter() - t0) / reps
            t_decode = max(t_long - t_short, 1e-9)
            return B * (new_tokens - short) / t_decode

        on_cpu = jax.default_backend() == "cpu"
        try:
            toks_on = run_arm(True)     # Pallas block-sparse kernel
            # on CPU both arms would trace the identical dense fallback
            # (the kernel is TPU-gated) — skip the redundant off arm
            toks_off = toks_on if on_cpu else run_arm(False)
        finally:
            set_flags({"use_paged_attention": True})
        return {"metric": "llama_paged_decode_tokens_per_sec",
                "value": round(toks_on, 1), "unit": "tokens/s",
                "vs_baseline": None, "method": "two-length-differential",
                "kernel_on_tokens_per_sec": round(toks_on, 1),
                "kernel_off_tokens_per_sec": round(toks_off, 1),
                # on CPU both arms run the dense fallback (the kernel is
                # TPU-gated) — the A/B is only meaningful on-chip
                "kernel_speedup": (round(toks_on / toks_off, 2)
                                   if not on_cpu else None),
                "decode_ms_per_token": round(
                    B * 1e3 / max(toks_on, 1e-9), 3),
                "new_tokens_long_short": [new_tokens, short],
                "batch": B, "prompt_len": prompt, "new_tokens": new_tokens,
                "block_size": block_size, "q_heads": heads,
                "kv_heads": kv_heads, "params": n_params}

    if model_name == "llama_serve_spec":
        # Batched speculative decoding THROUGH THE FUSED SCHEDULER
        # (ROADMAP item 2): verify-k grants ride the same token-budget
        # walk as prefill chunks and plain decode tokens, so speculation
        # now serves at FULL BATCH instead of the legacy batch-1 latency
        # demo (r05's 46.8 tok/s line — a different serving path, so
        # vs_baseline stays null). Main arm: B=8 spec on/off A/B
        # (speculation_speedup at batch, per-arm acceptance rate +
        # rtt_share); plus the classic batch-1 latency arm (the regime
        # where accepted drafts are nearly free because a k+1-row verify
        # window streams the same weights as a 1-token step).
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import AsyncLLMServer
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_req = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        cap = 512 + new_tokens
        spec_k = int(os.environ.get("BENCH_SPEC_K", "6"))
        stride = int(os.environ.get("BENCH_READOUT_STRIDE", "4"))
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        # repetition-heavy prompts: the workload where prompt-lookup
        # drafts actually accept (greedy continuations loop)
        prompts = []
        for i in range(n_req):
            base = rng.integers(0, cfg.vocab_size, (24,)).astype(np.int32)
            want = 256 + int(rng.integers(0, 128))
            reps = -(-want // len(base))  # tile past the target length
            prompts.append(np.tile(base, reps)[:want])

        rtt = None

        def serve_arm(k, batch, reqs):
            """One serve pass through a fused-scheduler engine at
            speculative_k=k; k=1 is the A/B control (bit-identical to
            the plain fused engine by construction)."""
            nonlocal rtt
            eng = LLMEngine(model, max_batch=batch, max_seq_len=cap,
                            chunk_size=256, scheduler="fused",
                            speculative_k=k, readout_stride=stride)
            eng.generate([prompts[0]], max_new_tokens=2)  # warm programs
            if rtt is None:
                rtts = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    float(np.asarray(eng._logits[0, 0]))
                    rtts.append(time.perf_counter() - t0)
                rtt = sorted(rtts)[len(rtts) // 2]
            eng.reset_stats()
            srv = AsyncLLMServer(eng, max_queue_size=reqs + 1)
            srv.start()
            t0 = time.perf_counter()
            hs = [srv.submit(p, max_new_tokens=new_tokens)
                  for p in prompts[:reqs]]
            outs = [h.result(timeout=1800) for h in hs]
            wall = time.perf_counter() - t0
            srv.stop()
            toks = sum(len(o.token_ids) for o in outs)
            steps = eng.stats["steps"]
            prop = eng.stats["spec_proposed_tokens"]
            acc = eng.stats["spec_accepted_tokens"]
            return {"tokens_per_sec": round(toks / wall, 1),
                    "batch": batch, "speculative_k": k,
                    "requests": reqs, "steps": steps,
                    "acceptance_rate": (round(acc / prop, 4)
                                        if prop else None),
                    "accepted_per_step": round(
                        eng.stats["spec_accepted_tokens"]
                        / max(steps, 1), 2),
                    # per-arm host-RTT share: speculation's win is
                    # FEWER host passes per token — this is the split
                    # that should drop on the spec arm
                    "rtt_share": round(rtt * steps / wall, 4),
                    "_outputs": [o.token_ids for o in outs]}

        b8_on = serve_arm(spec_k, B, n_req)
        b8_off = serve_arm(1, B, n_req)
        # greedy token parity across the A/B: speculation must never
        # change a stream (the coupled acceptance rule's contract)
        parity = b8_on.pop("_outputs") == b8_off.pop("_outputs")
        b1_n = min(3, n_req)
        b1_on = serve_arm(spec_k, 1, b1_n)
        b1_off = serve_arm(1, 1, b1_n)
        parity_b1 = b1_on.pop("_outputs") == b1_off.pop("_outputs")
        return {
            "metric": "llama_serve_spec_tokens_per_sec",
            "value": b8_on["tokens_per_sec"], "unit": "tokens/s",
            # r05's 46.8 was the legacy batch-1 latency demo — a
            # different serving path; the batched fused line has no
            # captured baseline to ratio against
            "vs_baseline": None,
            "scheduler": "fused", "readout_stride": stride,
            "speculative_k": spec_k, "slots": B,
            "new_tokens": new_tokens,
            "prompt_lens": f"{min(len(p) for p in prompts)}-"
                           f"{max(len(p) for p in prompts)}",
            "speculation_speedup": round(
                b8_on["tokens_per_sec"]
                / max(b8_off["tokens_per_sec"], 1e-9), 3),
            "speculation_speedup_b1": round(
                b1_on["tokens_per_sec"]
                / max(b1_off["tokens_per_sec"], 1e-9), 3),
            "token_parity": bool(parity and parity_b1),
            "spec_on": b8_on, "spec_off": b8_off,
            "latency_b1": {"spec_on": b1_on, "spec_off": b1_off},
            "rtt_est_ms": round(rtt * 1e3, 1),
            # r05 trend anchor: the LEGACY spec path's rtt share (0.324)
            # — the batched fused arm's rtt_share above is the number
            # that should sit far below it
            "rtt_share_r05_legacy": 0.324}

    if model_name == "llama_serve":
        # ASYNC serving subsystem (paddle_tpu/serving/ over
        # inference/llm_engine.py): mixed-length requests through fixed
        # slots, chunked prefill, per-step host transfer = one [B] token
        # vector — now driven by AsyncLLMServer's PIPELINED loop (step
        # N+1 dispatched before step N's token sync, so the host round trip
        # of the transfer overlaps the next step's device compute) with
        # per-stage telemetry attributing the serve wall (VERDICT r5 #4:
        # the old sync loop left ~76% of wall unexplained).
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import AsyncLLMServer
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_req = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        cap = 512 + new_tokens
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        weight_dtype = os.environ.get("BENCH_WEIGHT_DTYPE", "")
        if weight_dtype:
            from paddle_tpu.nn.quant import quantize_linears_for_inference
            quantize_linears_for_inference(model, weight_dtype=weight_dtype)
        # horizon 64 ~= one step per request generation (new_tokens=64):
        # each step() costs one host round trip, so tokens/s scales
        # ~linearly in horizon up to the point admissions coarsen
        horizon = int(os.environ.get("BENCH_HORIZON", "64"))
        eng = LLMEngine(model, max_batch=B, max_seq_len=cap, chunk_size=256,
                        horizon=horizon)
        lens = [256 + int(x) for x in
                rng.integers(0, 256, size=n_req)]  # mixed prompts
        prompts = [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in lens]
        # warm the programs (prefill + step) outside the timed window
        eng.generate([prompts[0]], max_new_tokens=2)
        # host round-trip estimate: a scalar fetch of resident device data
        # (VERDICT r4 #5). Under the pipelined loop the RTT of the token
        # transfer overlaps step N+1's compute, so this is reported as
        # context, not as an exclusive wall share.
        rtts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(np.asarray(eng._logits[0, 0]))
            rtts.append(time.perf_counter() - t0)
        rtt = sorted(rtts)[len(rtts) // 2]
        eng.reset_stats()
        server = AsyncLLMServer(eng, max_queue_size=n_req + 1)
        server.start()
        t0 = time.perf_counter()
        handles = [server.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        outs = [h.result(timeout=1800) for h in handles]
        wall = time.perf_counter() - t0
        server.stop()
        toks = sum(len(o.token_ids) for o in outs)
        steps = eng.stats["steps"]
        stats_off = dict(eng.stats)  # the A/B below keeps stepping eng
        snap = server.telemetry.snapshot(wall_s=wall)
        att = snap["attribution"]
        lat = snap["latency"]

        # flight-recorder A/B: the same prompts re-served with the
        # recorder ON (per-step StepRecords + per-request timelines).
        # Budget: <2% tok/s regression — the ring append + token stamps
        # must stay invisible next to device decode. A single sequential
        # pair would drown the 2% budget in serve-wall noise (ROUND4:
        # ±20% run-to-run on this metric), so the arms ALTERNATE
        # on/off/on/off/on/off and each side takes its median-of-3. The
        # recorded arm's telemetry snapshot and a sample chrome trace
        # persist next to the bench output so a slow-token question
        # ("why was THIS token slow?") can be answered from the
        # artifact, not a re-run.
        from paddle_tpu.profiler import FlightRecorder

        def serve_pass(rec, supervise=None, step_timeout_s=None,
                       metrics_store=None, trace_context=True):
            srv = AsyncLLMServer(eng, max_queue_size=n_req + 1,
                                 flight_recorder=rec, supervise=supervise,
                                 step_timeout_s=step_timeout_s,
                                 metrics_store=metrics_store,
                                 trace_context=trace_context)
            srv.start()
            t0 = time.perf_counter()
            hs = [srv.submit(p, max_new_tokens=new_tokens)
                  for p in prompts]
            outs = [h.result(timeout=1800) for h in hs]
            w = time.perf_counter() - t0
            srv.stop()
            return sum(len(o.token_ids) for o in outs) / w, srv, w

        on_tps, off_tps = [], [toks / wall]
        for _ in range(3):
            recorder = FlightRecorder()
            tps, server_on, wall_on = serve_pass(recorder)
            on_tps.append(tps)
            if len(off_tps) < 3:
                off_tps.append(serve_pass(None)[0])

        def median(xs):
            return sorted(xs)[len(xs) // 2]

        tps_off, tps_on = median(off_tps), median(on_tps)
        rec_overhead_pct = round((tps_off - tps_on) / tps_off * 100, 2)

        # supervision A/B (fault-tolerance satellite): the same prompts
        # re-served under supervise=RestartPolicy() with the watchdog
        # armed. Budget: <1% tok/s — the per-pass cost supervision adds
        # to the serve loop is ONE monotonic heartbeat read (the
        # watchdog is a separate mostly-sleeping thread, and the
        # restart machinery runs only on a crash). Supervision-OFF
        # overhead is 0 BY CONSTRUCTION: the unsupervised loop is the
        # very code the off arms above already timed — there is no
        # supervision branch on that path to pay for. Arms alternate,
        # median-of-3, same as the recorder A/B.
        from paddle_tpu.serving import RestartPolicy

        sup_on, sup_off = [], []
        for _ in range(3):
            sup_on.append(serve_pass(None, supervise=RestartPolicy(),
                                     step_timeout_s=300.0)[0])
            sup_off.append(serve_pass(None)[0])
        sup_overhead_pct = round(
            (median(sup_off) - median(sup_on)) / median(sup_off) * 100, 2)

        # metrics-store A/B (SLO sensor layer): the same prompts
        # re-served with the in-process time-series store attached —
        # the loop feeds every gauge/counter as monotonic-stamped
        # samples (interval-throttled) and the token hot path appends
        # per-tenant latency samples. Budget: <2% tok/s (the flight
        # recorder's budget — the off path is one detached-attribute
        # check per site). Arms alternate, median-of-3, same protocol
        # as the recorder A/B.
        ms_on, ms_off = [], []
        for _ in range(3):
            ms_on.append(serve_pass(None, metrics_store=True)[0])
            ms_off.append(serve_pass(None)[0])
        ms_overhead_pct = round(
            (median(ms_off) - median(ms_on)) / median(ms_off) * 100, 2)

        # trace-context A/B (distributed tracing): the same prompts
        # re-served with per-request TraceContext minting disabled.
        # The stamp is one uuid4 mint + a frozen dataclass per REQUEST
        # (nothing on the per-token path), so the honest budget is the
        # recorder's <2% tok/s with lots of headroom. Arms alternate,
        # median-of-3, same protocol as the recorder A/B.
        tc_on, tc_off = [], []
        for _ in range(3):
            tc_on.append(serve_pass(None)[0])
            tc_off.append(serve_pass(None, trace_context=False)[0])
        tc_overhead_pct = round(
            (median(tc_off) - median(tc_on)) / median(tc_off) * 100, 2)

        # multi-step on-device decode A/B (ROADMAP item 6): the same
        # prompts re-served through fused engines at readout_stride=k
        # vs 1, with per-arm rtt/dispatch/host-sync shares read off the
        # flight recorder — the host-tax split this PR exists to shrink.
        ms_stride = int(os.environ.get("BENCH_READOUT_STRIDE", "8"))
        multi_ab = _serve_multi_step_ab(
            model, prompts, new_tokens, B, cap, ms_stride, rtt_s=rtt)
        art_dir = _artifact_dir()
        stem = "llama_serve"
        trace_path = os.path.join(art_dir, f"{stem}_trace.json")
        recorder.export_chrome_trace(trace_path)
        tail_p99 = recorder.explain_tail(0.99, top=64)
        rec_snap = recorder.snapshot(tail=tail_p99)
        tel_path = os.path.join(art_dir, f"{stem}_telemetry.json")
        with open(tel_path, "w") as f:
            json.dump({
                "telemetry": server_on.telemetry.snapshot(wall_s=wall_on),
                "flight_recorder": rec_snap,
                "explain_tail_p99": tail_p99[:8],
            }, f, indent=1)
        # r05 sync-loop baseline (BENCH_r05.json): serve 1,158.9 tok/s —
        # comparable ONLY at the exact captured config (on-chip
        # defaults, bf16); any overridden knob makes the ratio
        # meaningless, so it degrades to null like the other bench lines
        at_r05_config = (
            B == 8 and new_tokens == 64
            and n_req == 16 and n_layers == 3
            and hidden == 4096 and ff == hidden * 11 // 4
            and horizon == 64 and not weight_dtype
            and jax.default_backend() != "cpu")
        base_toks = 1158.9
        out = {"metric": "llama_serve_tokens_per_sec",
               "value": round(toks / wall, 1), "unit": "tokens/s",
               "vs_baseline": (round(toks / wall / base_toks, 4)
                               if at_r05_config else None),
               "requests_per_sec": round(n_req / wall, 2),
               "steps_per_sec": round(steps / wall, 1),
               "requests": n_req, "slots": B,
               "prompt_lens": f"{min(len(p) for p in prompts)}-"
                              f"{max(len(p) for p in prompts)}",
               "new_tokens": new_tokens,
               "prefill_chunks": stats_off["prefill_chunks"],
               "horizon": horizon,
               "pipeline_depth": server.pipeline_depth,
               # recorder-on A/B (budget: < 2% tok/s regression) + the
               # persisted observability artifacts
               "flight_recorder_overhead_pct": rec_overhead_pct,
               "flight_recorder_on_tokens_per_sec": round(tps_on, 1),
               # supervision A/B (budget: < 1% tok/s — one heartbeat
               # read per loop pass; off-arm overhead is 0 by
               # construction). Restart-recovery wall time is measured
               # by tests/test_faults.py's chaos matrix and persisted
               # at the artifact path below.
               "supervision_overhead_pct": sup_overhead_pct,
               "supervision_on_tokens_per_sec": round(median(sup_on), 1),
               # metrics-store A/B (budget: < 2% tok/s — ring appends
               # + throttled gauge feeds; off path is one detached-
               # attribute check, same pattern as the recorder)
               "metrics_store_overhead_pct": ms_overhead_pct,
               "metrics_store_on_tokens_per_sec": round(
                   median(ms_on), 1),
               # trace-context A/B (budget: < 2% tok/s — one context
               # mint per request, nothing per token)
               "trace_context_overhead_pct": tc_overhead_pct,
               "trace_context_on_tokens_per_sec": round(
                   median(tc_on), 1),
               "restart_recovery_artifact": os.path.join(
                   art_dir, "restart_recovery.json"),
               "tail_causes_p99": rec_snap["tail_causes_p99"],
               "trace_artifact": trace_path,
               "telemetry_artifact": tel_path,
               # per-stage wall attribution from the serving telemetry —
               # replaces the one-scalar RTT split that left ~76% of r05
               # serve wall unexplained
               "attributed_share": att["attributed_share"],
               "stage_share": att["stage_share"],
               "ttft_p50_ms": round(lat["ttft"]["p50_s"] * 1e3, 1),
               "e2e_p50_ms": round(lat["e2e"]["p50_s"] * 1e3, 1),
               "rtt_est_ms": round(rtt * 1e3, 1),
               # host-RTT share of the serve wall (rtt x host passes /
               # wall) — the r05 tax this line tracks the TREND of:
               # llama_serve 0.233 at r05
               "rtt_share": round(rtt * steps / wall, 4),
               "rtt_share_r05": 0.233,
               "weight_dtype": weight_dtype or "bf16"}
        if multi_ab is not None:
            # the multi-step decode A/B: speedup + per-arm host-tax
            # split. The stride arm's host_sync + dispatch tax must sit
            # strictly below the stride-off arm's — tier-1's CPU smoke
            # asserts the structurally-stride-tied components (round
            # trips, rtt share, host_sync share); the dispatch-inclusive
            # comparison is meaningful where dispatch is a pure enqueue
            # (TPU), see _serve_multi_step_ab's docstring
            out["multi_step_speedup"] = multi_ab["multi_step_speedup"]
            out["multi_step"] = multi_ab
        return out

    if model_name == "llama_serve_fused":
        # Fused chunked-prefill + decode scheduling A/B: the SAME model /
        # prompts / server loop served by LLMEngine(scheduler="fused")
        # (Sarathi-style token-budget mixed steps — admission is slot
        # assignment, prefill chunks interleave INTO the decode batch,
        # one dispatch per engine step) vs the legacy admit-then-decode
        # scheduler whose prompt-long prefill trains stall every running
        # decode. Alongside throughput the line records the two numbers
        # the scheduler exists to move: admission_stall (queued-after-
        # free-slot time) and ramp-in dispatch counts.
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import AsyncLLMServer
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_req = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        cap = 512 + new_tokens
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        horizon = int(os.environ.get("BENCH_HORIZON", "64"))
        max_step_tokens = int(os.environ.get("BENCH_MAX_STEP_TOKENS", "0"))
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        lens = [256 + int(x) for x in rng.integers(0, 256, size=n_req)]
        prompts = [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in lens]

        arm_snapshots = {}

        def run_arm(scheduler):
            kw = dict(max_batch=B, max_seq_len=cap, chunk_size=chunk,
                      horizon=horizon, scheduler=scheduler)
            if scheduler == "fused" and max_step_tokens:
                kw["max_step_tokens"] = max_step_tokens
            eng = LLMEngine(model, **kw)
            eng.generate([prompts[0]], max_new_tokens=2)  # warm programs
            eng.reset_stats()
            server = AsyncLLMServer(eng, max_queue_size=n_req + 1)
            server.start()
            t0 = time.perf_counter()
            handles = [server.submit(p, max_new_tokens=new_tokens)
                       for p in prompts]
            outs = [h.result(timeout=1800) for h in handles]
            wall = time.perf_counter() - t0
            server.stop()
            toks = sum(len(o.token_ids) for o in outs)
            snap = server.telemetry.snapshot(wall_s=wall)
            arm_snapshots[scheduler] = snap
            stall = snap["latency"]["admission_stall"]
            return {
                "tokens_per_sec": toks / wall,
                "admission_stall_p50_ms": round(stall["p50_s"] * 1e3, 1),
                "admission_stall_p90_ms": round(stall["p90_s"] * 1e3, 1),
                "prefill_token_share": snap["prefill_token_share"],
                "ttft_p50_ms": round(
                    snap["latency"]["ttft"]["p50_s"] * 1e3, 1),
                "attributed_share": snap["attribution"]["attributed_share"],
                # ramp-in dispatch shape: legacy = prefill_chunks IS the
                # dispatch count (one serial dispatch per chunk inside
                # _admit, decodes stalled behind the train); fused = the
                # same chunk grants ride inside fused_steps MIXED
                # dispatches (1 per engine step, decodes riding along)
                "prefill_chunks": eng.stats["prefill_chunks"],
                "ramp_dispatches": (eng.stats["fused_steps"]
                                    if scheduler == "fused"
                                    else eng.stats["prefill_chunks"]),
                "fused_steps": eng.stats["fused_steps"],
                "engine_steps": eng.stats["steps"],
            }

        fused = run_arm("fused")
        legacy = run_arm("legacy")
        # persist the fused arm's full telemetry snapshot next to the
        # bench output (same artifact dir as the llama_serve recorder
        # dump) so stall/share regressions can be diffed without a re-run
        fused_tel_path = os.path.join(_artifact_dir(),
                                      "llama_serve_fused_telemetry.json")
        with open(fused_tel_path, "w") as f:
            json.dump({"fused": fused, "legacy": legacy,
                       "snapshots": arm_snapshots}, f, indent=1)
        at_r05_config = (
            B == 8 and new_tokens == 64 and n_req == 16 and n_layers == 3
            and hidden == 4096 and ff == hidden * 11 // 4
            and horizon == 64 and chunk == 256 and not max_step_tokens
            and jax.default_backend() != "cpu")
        return {"metric": "llama_serve_fused_tokens_per_sec",
                "value": round(fused["tokens_per_sec"], 1),
                "unit": "tokens/s",
                # r05 sync-loop serve baseline (BENCH_r05.json): 1,158.9
                # tok/s at this exact captured config
                "vs_baseline": (round(fused["tokens_per_sec"] / 1158.9, 4)
                                if at_r05_config else None),
                "scheduler_on": fused,
                "scheduler_off": legacy,
                "scheduler_speedup": round(
                    fused["tokens_per_sec"]
                    / max(legacy["tokens_per_sec"], 1e-9), 3),
                "requests": n_req, "slots": B, "new_tokens": new_tokens,
                "prompt_lens": f"{min(lens)}-{max(lens)}",
                "chunk": chunk, "horizon": horizon,
                "max_step_tokens": max_step_tokens or chunk + B - 1,
                "telemetry_artifact": fused_tel_path}

    if model_name == "llama_serve_prefix_cache":
        # Automatic prefix caching A/B: the SAME model / server served by
        # LLMEngine(cache_impl="paged", scheduler="fused") with
        # enable_prefix_cache on vs off, on TWO workloads:
        #   * shared — every prompt opens with the same system prompt
        #     (the template-heavy production shape): cache-on should
        #     report hit_rate > 0 and tokens/s >= cache-off, since the
        #     shared span admits as pure table writes + refcount bumps
        #     (zero prefill FLOPs);
        #   * zero-reuse — all-unique prompts: the overhead guard. The
        #     hash-chain probe, registration, and LRU bookkeeping ride
        #     the admission path, so cache-on must stay within 2% of
        #     cache-off here.
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import AsyncLLMServer
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_req = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        block = int(os.environ.get("BENCH_BLOCK", "64"))
        horizon = int(os.environ.get("BENCH_HORIZON", "64"))
        sys_len = int(os.environ.get("BENCH_SYS_PROMPT", "256"))
        tail_len = int(os.environ.get("BENCH_TAIL", "128"))
        # paged KV needs capacity % chunk == 0
        cap = -(-(sys_len + tail_len + new_tokens) // chunk) * chunk
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        V = cfg.vocab_size
        sys_prompt = rng.integers(0, V, (sys_len,)).astype(np.int32)
        tails = [rng.integers(0, V, (tail_len // 2 + int(x),)).astype(
            np.int32) for x in rng.integers(0, tail_len // 2, size=n_req)]
        shared = [np.concatenate([sys_prompt, t]) for t in tails]
        unique = [rng.integers(0, V, (sys_len + len(t),)).astype(np.int32)
                  for t in tails]

        def run_arm(prompts, cache_on):
            eng = LLMEngine(model, max_batch=B, max_seq_len=cap,
                            chunk_size=chunk, horizon=horizon,
                            cache_impl="paged", block_size=block,
                            scheduler="fused",
                            enable_prefix_cache=cache_on)
            # warm the compiled programs with a throwaway prompt that
            # shares nothing with the workload (must not seed the cache)
            warm = rng.integers(0, V, (3,)).astype(np.int32)
            eng.generate([warm], max_new_tokens=2)
            eng.reset_stats()
            server = AsyncLLMServer(eng, max_queue_size=n_req + 1)
            server.start()
            t0 = time.perf_counter()
            handles = [server.submit(p, max_new_tokens=new_tokens)
                       for p in prompts]
            outs = [h.result(timeout=1800) for h in handles]
            wall = time.perf_counter() - t0
            server.stop()
            toks = sum(len(o.token_ids) for o in outs)
            snap = server.telemetry.snapshot(wall_s=wall)
            hit = eng.stats["prefix_hit_tokens"]
            pre = eng.stats["prefill_tokens"]
            return {
                "tokens_per_sec": toks / wall,
                "hit_rate": round(hit / (hit + pre), 4) if hit + pre
                else 0.0,
                "prefix_hit_tokens": hit,
                "prefill_tokens": pre,
                "cow_blocks": eng.stats["prefix_cow_blocks"],
                "evicted_blocks": eng.stats["prefix_evicted_blocks"],
                "ttft_p50_ms": round(
                    snap["latency"]["ttft"]["p50_s"] * 1e3, 1),
                "attributed_share": snap["attribution"]["attributed_share"],
            }, [list(o.token_ids) for o in outs]

        shared_on, toks_on = run_arm(shared, True)
        shared_off, toks_off = run_arm(shared, False)
        unique_on, _ = run_arm(unique, True)
        unique_off, _ = run_arm(unique, False)
        overhead_pct = round(
            (1.0 - unique_on["tokens_per_sec"]
             / max(unique_off["tokens_per_sec"], 1e-9)) * 100, 2)
        art_path = os.path.join(_artifact_dir(),
                                "llama_serve_prefix_cache.json")
        with open(art_path, "w") as f:
            json.dump({"shared_on": shared_on, "shared_off": shared_off,
                       "unique_on": unique_on, "unique_off": unique_off},
                      f, indent=1)
        return {"metric": "llama_serve_prefix_cache_tokens_per_sec",
                "value": round(shared_on["tokens_per_sec"], 1),
                "unit": "tokens/s", "vs_baseline": None,
                "cache_on": shared_on, "cache_off": shared_off,
                "prefix_cache_speedup": round(
                    shared_on["tokens_per_sec"]
                    / max(shared_off["tokens_per_sec"], 1e-9), 3),
                # greedy serving: the A/B must be token-exact too
                "token_parity": toks_on == toks_off,
                "zero_reuse_on": unique_on, "zero_reuse_off": unique_off,
                "zero_reuse_overhead_pct": overhead_pct,
                "requests": n_req, "slots": B, "new_tokens": new_tokens,
                "sys_prompt_len": sys_len, "chunk": chunk,
                "block_size": block, "horizon": horizon,
                "telemetry_artifact": art_path}

    if model_name == "llama_serve_kv_quant":
        # Quantized-KV serving A/B: the SAME model/workload served by
        # LLMEngine(cache_impl="paged", scheduler="fused") with the pool
        # at bf16 vs int8 vs int4 — every arm's pool sized to the SAME
        # HBM BYTE BUDGET (the bf16 arm's oversubscribed pool bytes), so
        # the quantized arms hold ~2x/~4x the blocks. What the capacity
        # buys shows up as fewer preemptions / more resident slots /
        # higher tok/s on the memory-bound decode phase; what it costs
        # shows up in the greedy token-drift metric vs the bf16 arm
        # (exact-match prefix length + first divergence step per
        # request).
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import AsyncLLMServer
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_req = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        block = int(os.environ.get("BENCH_BLOCK", "64"))
        prompt_len = int(os.environ.get("BENCH_PROMPT", "256"))
        # the bf16 arm's pool covers this fraction of the full
        # (never-preempts) block demand — <1 = oversubscribed, so the
        # capacity lever has preemptions to convert into residency
        pool_frac = float(os.environ.get("BENCH_POOL_FRAC", "0.5"))
        cap = -(-(prompt_len + new_tokens) // chunk) * chunk
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        V = cfg.vocab_size
        prompts = [rng.integers(0, V, (prompt_len - 7 + int(x),)).astype(
            np.int32) for x in rng.integers(0, 15, size=n_req)]
        full_blocks = B * (cap // block)
        bf16_blocks = max(int(full_blocks * pool_frac), B + 1)

        _bpb_cache = {}

        def pool_blocks_for(dtype):
            # equal-HBM sizing through the engine's own byte arithmetic
            # (kv_bytes_per_block counts payload + scale arrays) — one
            # minimum-size probe engine per dtype, memoized
            if dtype not in _bpb_cache:
                probe = LLMEngine(model, max_batch=B, max_seq_len=cap,
                                  chunk_size=chunk, cache_impl="paged",
                                  block_size=block, scheduler="fused",
                                  kv_pool_blocks=B + 1,
                                  kv_cache_dtype=dtype)
                _bpb_cache[dtype] = probe.kv_bytes_per_block()
                del probe
            return _bpb_cache[dtype]

        budget = bf16_blocks * pool_blocks_for(None)

        def run_arm(dtype):
            n_blocks = min(budget // pool_blocks_for(dtype), full_blocks)
            eng = LLMEngine(model, max_batch=B, max_seq_len=cap,
                            chunk_size=chunk, cache_impl="paged",
                            block_size=block, scheduler="fused",
                            kv_pool_blocks=n_blocks, kv_cache_dtype=dtype)
            warm = rng.integers(0, V, (3,)).astype(np.int32)
            eng.generate([warm], max_new_tokens=2)
            eng.reset_stats()
            server = AsyncLLMServer(eng, max_queue_size=n_req + 1)
            server.start()
            t0 = time.perf_counter()
            handles = [server.submit(p, max_new_tokens=new_tokens)
                       for p in prompts]
            slot_samples = []
            outs = []
            # short result polls double as resident-slot samples; the
            # wall deadline keeps a pathological config (e.g. a pool
            # oversubscribed into ramp thrash) a loud failure, not a
            # hang
            deadline = t0 + 1800
            for h in handles:
                while True:
                    try:
                        outs.append(h.result(timeout=0.05))
                        break
                    except TimeoutError:
                        if time.perf_counter() > deadline:
                            raise
                        slot_samples.append(
                            sum(1 for s in eng.slots if s is not None))
            wall = time.perf_counter() - t0
            server.stop()
            toks = sum(len(o.token_ids) for o in outs)
            return {
                "kv_cache_dtype": dtype or "bf16",
                "tokens_per_sec": round(toks / wall, 1),
                "pool_blocks": n_blocks,
                "effective_blocks": eng.kv_pool_effective_blocks(),
                "pool_bytes": eng.kv_pool_nbytes(),
                "preemptions": eng.stats["preemptions"],
                "mean_resident_slots": round(
                    float(np.mean(slot_samples)) if slot_samples else
                    float(B), 2),
            }, [list(o.token_ids) for o in outs]

        def drift(ref_toks, arm_toks):
            # greedy drift vs the bf16 arm: exact-match prefix length and
            # the first divergence step, per request
            prefixes, first_div = [], None
            for ref, got in zip(ref_toks, arm_toks):
                n = 0
                for a, b2 in zip(ref, got):
                    if a != b2:
                        break
                    n += 1
                prefixes.append(n)
                if (n < min(len(ref), len(got)) or len(ref) != len(got)) \
                        and (first_div is None or n < first_div):
                    first_div = n
            return {"min_match_prefix": int(min(prefixes)),
                    "mean_match_prefix": round(float(np.mean(prefixes)), 1),
                    "first_divergence_step": first_div,
                    "token_parity": first_div is None}

        bf16_arm, bf16_toks = run_arm(None)
        int8_arm, int8_toks = run_arm("int8")
        int4_arm, int4_toks = run_arm("int4")
        int8_arm["drift_vs_bf16"] = drift(bf16_toks, int8_toks)
        int4_arm["drift_vs_bf16"] = drift(bf16_toks, int4_toks)
        art_path = os.path.join(_artifact_dir(), "llama_serve_kv_quant.json")
        with open(art_path, "w") as f:
            json.dump({"bf16": bf16_arm, "int8": int8_arm,
                       "int4": int4_arm}, f, indent=1)
        return {"metric": "llama_serve_kv_quant_tokens_per_sec",
                "value": int8_arm["tokens_per_sec"],
                "unit": "tokens/s", "vs_baseline": None,
                "bf16": bf16_arm, "int8": int8_arm, "int4": int4_arm,
                "int8_speedup": round(
                    int8_arm["tokens_per_sec"]
                    / max(bf16_arm["tokens_per_sec"], 1e-9), 3),
                "int4_speedup": round(
                    int4_arm["tokens_per_sec"]
                    / max(bf16_arm["tokens_per_sec"], 1e-9), 3),
                "requests": n_req, "slots": B, "new_tokens": new_tokens,
                "prompt_len": prompt_len, "chunk": chunk,
                "block_size": block, "pool_frac": pool_frac,
                "full_blocks": full_blocks,
                "telemetry_artifact": art_path}

    if model_name == "llama_serve_kv_tier":
        # Host KV-tier A/B: the SAME model/workload/pool served with the
        # tier OFF (preemption = full re-prefill, eviction = discard) vs
        # ON (kv_host_swap: preempted slots round-trip host RAM;
        # kv_host_spill_bytes: evicted prefix blocks spill + promote) at
        # EQUAL device-pool bytes — the tier spends host RAM and PCIe/DMA
        # bandwidth, never device HBM, so any tok/s win is pure recompute
        # avoided. The workload is the shape the tier serves in
        # production: TWO groups of requests each sharing a long system
        # prompt (BENCH_SYS_FRAC of the prompt) with unique tails,
        # interleaved so the groups CHURN each other's shared blocks out
        # of the pressured pool — the off arm recomputes the shared
        # prefix every time it cycles back, the on arm promotes it from
        # the host spill store (and preempted slots restore instead of
        # re-prefilling). What the tier buys shows up as re-prefill
        # tokens avoided and fewer prefill dispatches, what it costs as
        # the swap-stall share of serve wall. Streams must stay
        # TOKEN-EXACT across arms (the copies restore the bytes the pool
        # held). CPU-shape caveat: a toy-model serve is DISPATCH-bound
        # and decode-step-count invariant, so avoided prefill tokens
        # barely move tok/s there (expect ~parity inside the ±5% CPU
        # noise band, with the re-prefill reduction as the attributable
        # win); the tok/s gap opens on shapes where prefill FLOPs
        # dominate the copies — real model sizes on real accelerators.
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import AsyncLLMServer
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_req = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        block = int(os.environ.get("BENCH_BLOCK", "64"))
        prompt_len = int(os.environ.get("BENCH_PROMPT", "256"))
        pool_frac = float(os.environ.get("BENCH_POOL_FRAC", "0.5"))
        spill_mb = int(os.environ.get("BENCH_SPILL_MB", "256"))
        sys_frac = float(os.environ.get("BENCH_SYS_FRAC", "0.6"))
        cap = -(-(prompt_len + new_tokens) // chunk) * chunk
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        V = cfg.vocab_size
        sys_len = int(prompt_len * sys_frac)
        sys_prompts = [rng.integers(0, V, (sys_len,)).astype(np.int32)
                       for _ in range(2)]
        prompts = [np.concatenate([
            sys_prompts[i % 2],
            rng.integers(0, V, (prompt_len - sys_len - 7 + int(x),))
            .astype(np.int32)])
            for i, x in enumerate(rng.integers(0, 15, size=n_req))]
        full_blocks = B * (cap // block)
        n_blocks = max(int(full_blocks * pool_frac), B + 1)

        def run_arm(tier_on, pool_blocks=None):
            eng = LLMEngine(
                model, max_batch=B, max_seq_len=cap, chunk_size=chunk,
                cache_impl="paged", block_size=block, scheduler="fused",
                kv_pool_blocks=pool_blocks or n_blocks,
                enable_prefix_cache=True,
                kv_host_swap=tier_on,
                kv_host_spill_bytes=(spill_mb << 20) if tier_on else 0)
            warm = rng.integers(0, V, (3,)).astype(np.int32)
            eng.generate([warm], max_new_tokens=2)
            eng.reset()
            eng.reset_stats()
            server = AsyncLLMServer(eng, max_queue_size=n_req + 1)
            server.start()
            t0 = time.perf_counter()
            handles = [server.submit(p, max_new_tokens=new_tokens)
                       for p in prompts]
            outs = []
            deadline = t0 + 1800     # a thrashing config fails loudly
            for h in handles:
                while True:
                    try:
                        outs.append(h.result(timeout=0.05))
                        break
                    except TimeoutError:
                        if time.perf_counter() > deadline:
                            raise
            wall = time.perf_counter() - t0
            server.stop()
            toks = sum(len(o.token_ids) for o in outs)
            s = eng.stats
            swap_stall = s["swap_out_time_s"] + s["swap_in_time_s"]
            return {
                "tier": "on" if tier_on else "off",
                "tokens_per_sec": round(toks / wall, 1),
                "pool_blocks": pool_blocks or n_blocks,
                "preemptions": s["preemptions"],
                "prefill_tokens": s["prefill_tokens"],
                "prefix_hit_tokens": s["prefix_hit_tokens"],
                "kv_swap_out_blocks": s["kv_swap_out_blocks"],
                "kv_swap_in_blocks": s["kv_swap_in_blocks"],
                "kv_swap_saved_tokens": s["kv_swap_saved_tokens"],
                "kv_spill_blocks": s["kv_spill_blocks"],
                "kv_promote_blocks": s["kv_promote_blocks"],
                "swap_stall_share": round(swap_stall / max(wall, 1e-9), 4),
            }, [list(o.token_ids) for o in outs]

        # the FLOOR arm (full pool, tier off): the prefill tokens an
        # unpressured prefix-cached serve of this workload dispatches —
        # no preemptions, no evictions. Everything a pressured arm
        # dispatches beyond it is RE-prefill (recompute of KV the
        # engine already produced), which is exactly what the tier
        # exists to remove; the floor also anchors token parity.
        floor_arm, floor_toks = run_arm(False, pool_blocks=full_blocks)
        off_arm, off_toks = run_arm(False)
        on_arm, on_toks = run_arm(True)
        floor = floor_arm["prefill_tokens"]
        re_off = max(off_arm["prefill_tokens"] - floor, 0)
        re_on = max(on_arm["prefill_tokens"] - floor, 0)
        art_path = os.path.join(_artifact_dir(), "llama_serve_kv_tier.json")
        with open(art_path, "w") as f:
            json.dump({"floor": floor_arm, "tier_off": off_arm,
                       "tier_on": on_arm,
                       "reprefill_tokens_off": re_off,
                       "reprefill_tokens_on": re_on}, f, indent=1)
        return {"metric": "llama_serve_kv_tier_tokens_per_sec",
                "value": on_arm["tokens_per_sec"],
                "unit": "tokens/s", "vs_baseline": None,
                "floor": floor_arm, "tier_off": off_arm,
                "tier_on": on_arm,
                "tiering_speedup": round(
                    on_arm["tokens_per_sec"]
                    / max(off_arm["tokens_per_sec"], 1e-9), 3),
                "reprefill_tokens_off": re_off,
                "reprefill_tokens_on": re_on,
                "reprefill_reduction": round(
                    (re_off - re_on) / re_off, 3) if re_off else None,
                "token_parity": off_toks == on_toks == floor_toks,
                "requests": n_req, "slots": B, "new_tokens": new_tokens,
                "prompt_len": prompt_len, "sys_frac": sys_frac,
                "chunk": chunk,
                "block_size": block, "pool_frac": pool_frac,
                "spill_mb": spill_mb, "full_blocks": full_blocks,
                "telemetry_artifact": art_path}

    if model_name == "llama_serve_disagg":
        # Disaggregated prefill/decode A/B (DistServe/Splitwise): the
        # SAME two-replica fleet and workload served with role-split
        # routing (1 prefill + 1 decode replica; finished prefills SHIP
        # their staged KV to the decode replica and resume with the
        # one-token stitch — zero re-prefill) vs mixed placement (both
        # replicas take everything). The workload is the interference
        # shape disaggregation exists for: a PREFILL FLOOD of long-
        # prompt/short-output requests landing while a handful of
        # DECODE-TRICKLE streams are mid-generation. Mixed placement
        # lets the flood's chunk grants ride the tricklers' decode
        # steps (Sarathi interference on both replicas); the split arm
        # keeps the decode replica's steps prefill-free except the
        # stitch. What the split buys shows up as decode inter-token
        # p99 and TTFT p99 under flood; what it costs as shipped bytes
        # and the migration-latency histogram. Streams must stay
        # TOKEN-EXACT across arms (greedy: placement cannot change
        # tokens). An unflooded floor arm (same fleet, trickle only)
        # anchors the p99s. CPU-shape caveat: toy-model steps are
        # dispatch-bound, so the split's p99 win is muted vs real
        # accelerators where a long-prompt chunk occupies the device
        # for whole milliseconds.
        import threading
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import AsyncLLMServer, ReplicaRouter
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        flood_n = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        trickle_n = int(os.environ.get("BENCH_TRICKLE", "4"))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        block = int(os.environ.get("BENCH_BLOCK", "64"))
        prompt_len = int(os.environ.get("BENCH_PROMPT", "256"))
        cap = -(-(prompt_len + new_tokens) // chunk) * chunk
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        V = cfg.vocab_size
        flood_prompts = [rng.integers(0, V, (prompt_len - 7 + int(x),))
                         .astype(np.int32)
                         for x in rng.integers(0, 15, size=flood_n)]
        trickle_prompts = [rng.integers(0, V, (max(prompt_len // 4, 4),))
                           .astype(np.int32) for _ in range(trickle_n)]

        from paddle_tpu.profiler import FlightRecorder

        def run_arm(roles, flood=True, trace_path=None):
            servers = []
            for i in range(2):
                eng = LLMEngine(
                    model, max_batch=B, max_seq_len=cap,
                    chunk_size=chunk, cache_impl="paged",
                    block_size=block, scheduler="fused")
                warm = rng.integers(0, V, (3,)).astype(np.int32)
                eng.generate([warm], max_new_tokens=2)
                eng.reset()
                eng.reset_stats()
                servers.append(AsyncLLMServer(
                    eng, replica=i,
                    flight_recorder=(FlightRecorder()
                                     if trace_path else None),
                    max_queue_size=flood_n + trickle_n + 1))
            router = ReplicaRouter(servers, roles=roles)
            router.start()
            t0 = time.perf_counter()
            stamps = [[] for _ in range(trickle_n)]
            t_sub = [None] * trickle_n

            def consume(h, out):
                for tok in h:
                    out.append((time.perf_counter(), int(tok)))

            threads = []
            for i, p in enumerate(trickle_prompts):
                t_sub[i] = time.perf_counter()
                h = router.submit(p, max_new_tokens=new_tokens)
                th = threading.Thread(target=consume,
                                      args=(h, stamps[i]), daemon=True)
                th.start()
                threads.append(th)
            flood_handles = [router.submit(p, max_new_tokens=2)
                             for p in flood_prompts] if flood else []
            flood_toks = [list(h.result(timeout=1800).token_ids)
                          for h in flood_handles]
            for th in threads:
                th.join(timeout=1800)
            wall = time.perf_counter() - t0
            snap = router.snapshot()
            if trace_path:
                # the stitched cross-replica trace: every migrated
                # request's prefill and decode legs flow-linked into one
                # Perfetto chain, plus the router:migrations phase lane
                router.export_merged_trace(trace_path)
            router.stop(timeout=120)
            gaps = [b[0] - a[0] for s in stamps
                    for a, b in zip(s, s[1:])]
            ttfts = [s[0][0] - t for s, t in zip(stamps, t_sub) if s]
            toks = sum(len(s) for s in stamps) + \
                sum(len(t) for t in flood_toks)
            # re-prefill paid by DECODE-role steps: with roles, every
            # migrated request books exactly its one-token stitch on
            # the decode replica — anything beyond is fallback work
            migrated = router.stats["kv_shipped"] + \
                router.stats["kv_ship_fallback"]
            decode_prefill = servers[1].engine.stats["prefill_tokens"] \
                if roles else None
            out = {
                "arm": ("disagg" if roles else
                        "mixed" if flood else "floor"),
                "tokens_per_sec": round(toks / wall, 1),
                "decode_p99_ms": round(float(np.quantile(
                    gaps, 0.99)) * 1000, 3) if gaps else None,
                "decode_p50_ms": round(float(np.quantile(
                    gaps, 0.50)) * 1000, 3) if gaps else None,
                "ttft_p99_ms": round(float(np.quantile(
                    ttfts, 0.99)) * 1000, 3) if ttfts else None,
                "kv_shipped": router.stats["kv_shipped"],
                "kv_ship_fallback": router.stats["kv_ship_fallback"],
                "ship_bytes": snap["transport"]["ship_bytes"]
                if snap.get("transport") else 0,
                "migration_latency": snap.get("migration_latency"),
                "migration_phases": snap.get("migration_phases"),
                "decode_reprefill_tokens": (decode_prefill - migrated)
                if decode_prefill is not None else None,
            }
            return out, [[int(t) for _, t in s] for s in stamps], \
                flood_toks

        roles = {"prefill": [0], "decode": [1]}
        floor_arm, floor_trickle, _ = run_arm(None, flood=False)
        mixed_arm, mixed_trickle, mixed_flood = run_arm(None)
        trace_path = os.path.join(_artifact_dir(),
                                  "llama_serve_disagg_trace.json")
        dis_arm, dis_trickle, dis_flood = run_arm(roles,
                                                  trace_path=trace_path)
        parity = (dis_trickle == mixed_trickle == floor_trickle
                  and dis_flood == mixed_flood)
        # the phase sub-spans must ACCOUNT for the measured migration
        # latency: they nest inside the t0..t1 window (never exceed it
        # beyond timer noise) and explain at least half of it — the
        # un-phased residual is placement ranking + handle bookkeeping.
        # Only a clean ship run is comparable (a fallback books latency
        # with no phases and would dilute the histogram means).
        mp = dis_arm["migration_phases"] or {}
        phase_sum = sum(mp[p]["mean_s"]
                        for p in ("serialize", "transport", "import",
                                  "place") if p in mp)
        mig_mean = (dis_arm["migration_latency"] or {}).get("mean_s", 0)
        if dis_arm["kv_shipped"] and not dis_arm["kv_ship_fallback"]:
            assert 0.5 * mig_mean <= phase_sum <= 1.05 * mig_mean, \
                (phase_sum, mig_mean, mp)
        art_path = os.path.join(_artifact_dir(),
                                "llama_serve_disagg.json")
        with open(art_path, "w") as f:
            json.dump({"floor": floor_arm, "mixed": mixed_arm,
                       "disagg": dis_arm, "token_parity": parity,
                       "migration_phase_sum_s": round(phase_sum, 6),
                       "trace_artifact": trace_path},
                      f, indent=1)
        return {"metric": "llama_serve_disagg_decode_p99_ms",
                "value": dis_arm["decode_p99_ms"],
                "unit": "ms", "vs_baseline": None,
                "floor": floor_arm, "mixed": mixed_arm,
                "disagg": dis_arm,
                "disagg_p99_vs_mixed": round(
                    dis_arm["decode_p99_ms"]
                    / max(mixed_arm["decode_p99_ms"], 1e-9), 3),
                "token_parity": parity,
                "flood_requests": flood_n, "trickle_requests": trickle_n,
                "slots": B, "new_tokens": new_tokens,
                "prompt_len": prompt_len, "chunk": chunk,
                "block_size": block,
                "migration_phase_sum_s": round(phase_sum, 6),
                "trace_artifact": trace_path,
                "telemetry_artifact": art_path}

    if model_name == "llama_serve_slo":
        # Multi-tenant SLO isolation bench (the sensor half of ROADMAP
        # item 4): an ADVERSARIAL tenant floods the queue with long
        # prompts while a well-behaved VICTIM tenant keeps streaming
        # short requests. The new per-tenant latency histograms measure
        # the victim's p99 TTFT SEPARATELY from the adversary's (the
        # global histogram would blend them), a calibrated
        # SLO(metric="ttft_p99", tenant=victim) watches the victim from
        # the metrics store, and the Google-SRE multi-window burn-rate
        # alert must FIRE during the flood and CLEAR after it drains.
        # The final slo_report + the burn-rate trajectory persist to
        # docs/artifacts/slo_report.json — the evidence the PR-15+ SLO
        # controller will close its loop against.
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import (AdapterStore, AsyncLLMServer,
                                        random_lora_weights)
        from paddle_tpu.profiler import SLO, FlightRecorder
        B = int(os.environ.get("BENCH_BATCH", "4"))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        block = int(os.environ.get("BENCH_BLOCK", "64"))
        victim_prompt = int(os.environ.get("BENCH_VICTIM_PROMPT", "32"))
        victim_new = int(os.environ.get("BENCH_VICTIM_NEW_TOKENS", "12"))
        flood_prompt = int(os.environ.get("BENCH_FLOOD_PROMPT", "256"))
        flood_new = int(os.environ.get("BENCH_FLOOD_NEW_TOKENS", "48"))
        n_flood = int(os.environ.get("BENCH_FLOOD", "16"))
        n_warm = int(os.environ.get("BENCH_WARM", "6"))
        interval = float(os.environ.get("BENCH_VICTIM_INTERVAL_S", "0.05"))
        slow_w = float(os.environ.get("BENCH_SLO_WINDOW_S", "6.0"))
        fast_w = float(os.environ.get("BENCH_SLO_FAST_WINDOW_S", "1.5"))
        burn_thr = float(os.environ.get("BENCH_SLO_BURN", "2.0"))
        wall_deadline = float(os.environ.get("BENCH_DEADLINE_S", "900"))
        cap = -(-(max(flood_prompt, victim_prompt)
                  + max(flood_new, victim_new)) // chunk) * chunk
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        V = cfg.vocab_size
        # the adversary is a REGISTERED TENANT (adapter id) so the
        # tenant-keyed histograms and token counters split the traffic
        adapters = AdapterStore(cfg, rank=4)
        adversary = adapters.register(
            random_lora_weights(cfg, rank=4, seed=7, scale=0.02),
            alpha=1.0)
        victim = 0                      # base-model tenant
        eng = LLMEngine(model, max_batch=B, max_seq_len=cap,
                        chunk_size=chunk, cache_impl="paged",
                        block_size=block, scheduler="fused",
                        adapter_store=adapters, adapter_cache_slots=2)
        eng.generate([rng.integers(0, V, (3,)).astype(np.int32)],
                     max_new_tokens=2)          # warm the programs
        eng.reset_stats()

        def vprompt():
            return rng.integers(0, V, (victim_prompt,)).astype(np.int32)

        # -- phase 1: calibration — victim-only baseline TTFT sets the
        # SLO target (2x the observed median, floored) so the objective
        # is honest for whatever hardware runs this
        calib = AsyncLLMServer(eng, max_queue_size=n_warm + 1)
        calib.start()
        ttfts = []
        for _ in range(n_warm):
            h = calib.submit(vprompt(), max_new_tokens=victim_new)
            r = h.result(timeout=wall_deadline)
            ttfts.append(r.ttft_s)
        calib.stop()
        base_ttft = sorted(ttfts)[len(ttfts) // 2]
        target_s = max(2.0 * base_ttft, 0.02)
        slo = SLO("victim_ttft", "ttft_p99", tenant=victim,
                  target_s=target_s, window_s=slow_w,
                  fast_window_s=fast_w, burn_threshold=burn_thr)

        # -- phase 2: the flood — adversary dumps n_flood long prompts,
        # victim keeps a trickle of short requests flowing (bounded
        # outstanding so the run length stays the flood's, not ours)
        srv = AsyncLLMServer(eng, max_queue_size=n_flood + 64,
                             flight_recorder=FlightRecorder(),
                             metrics_store=True, slos=[slo],
                             metrics_interval_s=0.02, slo_interval_s=0.1)
        srv.start()
        t0 = time.monotonic()
        trajectory = []

        def poll(phase):
            (r,) = srv.slo_engine.evaluate()
            trajectory.append({
                "t_s": round(time.monotonic() - t0, 3), "phase": phase,
                "burn_rate_fast": r["burn_rate_fast"],
                "burn_rate_slow": r["burn_rate_slow"],
                "burning": r["burning"], "measured_s": r["measured_s"],
                "queue_depth": len(srv._queue)})
            return r

        flood = [srv.submit(
            rng.integers(0, V, (flood_prompt,)).astype(np.int32),
            max_new_tokens=flood_new, adapter_id=adversary)
            for _ in range(n_flood)]
        victims = []
        while any(not h.done for h in flood):
            if time.monotonic() - t0 > wall_deadline:
                raise RuntimeError(
                    f"llama_serve_slo: flood not drained after "
                    f"{wall_deadline}s — pathological config")
            if sum(1 for h in victims if not h.done) < 4:
                victims.append(srv.submit(vprompt(),
                                          max_new_tokens=victim_new))
            poll("flood")
            time.sleep(interval)
        for h in flood:
            h.result(timeout=wall_deadline)

        # -- phase 3: recovery — victim streams alone until the burn
        # alert CLEARS (bad samples age out of the fast window)
        recover_deadline = time.monotonic() + max(4 * fast_w + 10.0, 30.0)
        cleared_in_time = False
        while time.monotonic() < recover_deadline:
            h = srv.submit(vprompt(), max_new_tokens=victim_new)
            victims.append(h)
            h.result(timeout=wall_deadline)
            poll("recovery")
            burn_alerts = srv.metrics_store.alerts(kind="slo_burn")
            if burn_alerts and all(not a.active for a in burn_alerts):
                cleared_in_time = True
                break
            time.sleep(interval)
        for h in victims:
            h.result(timeout=wall_deadline)
        poll("final")
        report = srv.slo_report()
        burn_alerts = [a.to_dict()
                       for a in srv.metrics_store.alerts(kind="slo_burn")]
        srv.stop()

        fired = len(burn_alerts) > 0
        tl = report["tenant_latency"]
        vic_hist = tl[str(victim)]["ttft"]
        adv_hist = tl[str(adversary)]["ttft"]
        # the acceptance contract: the victim's p99 is measured PER
        # TENANT (its own histogram, not the blended global one — the
        # count is exactly the FLOOD SERVER's victim requests, each of
        # which streamed at least one token; the calibration server's
        # telemetry was separate), the burn alert fired under the
        # flood and cleared after it
        assert vic_hist["count"] == len(victims), \
            f"victim tenant histogram counted {vic_hist['count']} " \
            f"of {len(victims)} victim requests"
        assert adv_hist["count"] == n_flood, \
            "adversary tenant histogram miscounted the flood"
        assert fired, "burn-rate alert never fired under the flood"
        assert cleared_in_time, "burn-rate alert never cleared after"
        art_path = os.path.join(_artifact_dir(), "slo_report.json")
        with open(art_path, "w") as f:
            json.dump({
                "slo": {"name": slo.name, "metric": slo.metric,
                        "tenant": victim,
                        "target_s": round(target_s, 4),
                        "window_s": slow_w, "fast_window_s": fast_w,
                        "burn_threshold": burn_thr,
                        "calibration_ttft_p50_s": round(base_ttft, 4)},
                "report": report,
                "burn_alerts": burn_alerts,
                "trajectory": trajectory,
                "config": {"slots": B, "flood": n_flood,
                           "flood_prompt": flood_prompt,
                           "victim_prompt": victim_prompt,
                           "layers": n_layers, "hidden": hidden},
            }, f, indent=1)
        peak_burn = max(p["burn_rate_fast"] for p in trajectory)
        return {"metric": "llama_serve_slo_victim_ttft_p99_ms",
                "value": round(vic_hist["p99_s"] * 1e3, 1),
                "unit": "ms", "vs_baseline": None,
                "victim_ttft_p99_ms": round(vic_hist["p99_s"] * 1e3, 1),
                "victim_ttft_p50_ms": round(vic_hist["p50_s"] * 1e3, 1),
                "adversary_ttft_p99_ms": round(
                    adv_hist["p99_s"] * 1e3, 1),
                "target_ms": round(target_s * 1e3, 1),
                "burn_alert_fired": fired,
                "burn_alert_cleared": cleared_in_time,
                "peak_burn_rate_fast": round(peak_burn, 1),
                "trajectory_points": len(trajectory),
                "victim_requests": len(victims),
                "calibration_requests": n_warm,
                "flood_requests": n_flood,
                "pathologies_active": {k: v for k, v
                                       in report["pathologies"].items()
                                       if v},
                "slo_report_artifact": art_path}

    if model_name == "llama_serve_cluster":
        # Multichip serving A/B (paddle_tpu/serving/cluster.py): ONE
        # replica vs BENCH_REPLICAS replicas fronted by the prefix-
        # affinity ReplicaRouter, on a multi-tenant shared-system-prompt
        # workload (BENCH_TENANTS distinct system prompts, one per
        # routing_key). A third arm re-serves the cluster under RANDOM
        # routing — the affinity win (hit-rate + tok/s) is measured
        # against its own control, not inferred. BENCH_TP > 1
        # additionally shards each replica's engine over its own
        # ("tp",)-mesh device group (kv-head-sharded pools; needs
        # BENCH_REPLICAS * BENCH_TP local devices).
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import AsyncLLMServer, ReplicaRouter
        from paddle_tpu.serving.cluster import tp_engine
        R = int(os.environ.get("BENCH_REPLICAS", "2"))
        tp = int(os.environ.get("BENCH_TP", "1"))
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_req = int(os.environ.get("BENCH_REQUESTS", str(2 * B * R)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        block = int(os.environ.get("BENCH_BLOCK", "64"))
        horizon = int(os.environ.get("BENCH_HORIZON", "64"))
        sys_len = int(os.environ.get("BENCH_SYS_PROMPT", "256"))
        tail_len = int(os.environ.get("BENCH_TAIL", "128"))
        n_tenants = int(os.environ.get("BENCH_TENANTS", str(max(R, 2))))
        n_req = max(n_req, 2 * n_tenants)   # a timed wave must exist
        cap = -(-(sys_len + tail_len + new_tokens) // chunk) * chunk
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        V = cfg.vocab_size
        sys_prompts = [rng.integers(0, V, (sys_len,)).astype(np.int32)
                       for _ in range(n_tenants)]
        tails = [rng.integers(0, V, (tail_len // 2 + int(x),)).astype(
            np.int32) for x in rng.integers(0, tail_len // 2, size=n_req)]
        prompts = [np.concatenate([sys_prompts[i % n_tenants], t])
                   for i, t in enumerate(tails)]

        def build_model():
            # each replica materializes its own weight copy (same seed,
            # identical values) — under BENCH_TP each copy lays out on
            # its OWN replica mesh, which a shared model couldn't
            paddle.seed(0)
            m = LlamaForCausalLM(cfg).bfloat16()
            m.eval()
            return m

        def make_replica(i):
            kw = dict(max_batch=B, max_seq_len=cap, chunk_size=chunk,
                      horizon=horizon, cache_impl="paged",
                      block_size=block, scheduler="fused",
                      enable_prefix_cache=True)
            model = build_model()
            # replica i's weights, pools and step programs live on its OWN
            # device group (a one-device ("tp",) mesh pins a tp=1 replica
            # to its chip); with fewer groups than replicas they wrap
            groups = max(len(jax.devices()) // tp, 1)
            g = i % groups
            devs = jax.devices()[g * tp:(g + 1) * tp]
            eng = tp_engine(model, tp=tp, devices=devs, **kw)
            warm = rng.integers(0, V, (3,)).astype(np.int32)
            eng.generate([warm], max_new_tokens=2)
            eng.reset_stats()
            return AsyncLLMServer(eng, max_queue_size=n_req + 1, replica=i)

        def run_cluster(n_replicas, policy):
            replicas = [make_replica(i) for i in range(n_replicas)]
            router = ReplicaRouter(replicas, policy=policy)
            router.start()
            # SEED wave: one request per tenant primes the prefix caches
            # (and, under the affinity policy, spreads the tenants across
            # replicas — the router's outstanding-count load term places
            # simultaneous cold tenants on different replicas). The timed
            # MAIN wave below is the steady state the hit-rate and tok/s
            # numbers describe.
            seed_hs = [router.submit(prompts[i], max_new_tokens=new_tokens,
                                     routing_key=f"tenant{i % n_tenants}")
                       for i in range(n_tenants)]
            seed_outs = [h.result(timeout=1800) for h in seed_hs]
            for srv in replicas:
                srv.engine.reset_stats()
            t0 = time.perf_counter()
            hs = [router.submit(p, max_new_tokens=new_tokens,
                                routing_key=f"tenant{i % n_tenants}")
                  for i, p in enumerate(prompts[n_tenants:],
                                        start=n_tenants)]
            outs = [h.result(timeout=1800) for h in hs]
            wall = time.perf_counter() - t0
            router.stop()
            toks = sum(len(o.token_ids) for o in outs)
            per, hit_tok, pre_tok = [], 0, 0
            for i, srv in enumerate(replicas):
                st = srv.engine.stats
                per.append({
                    "replica": i, "tokens": st["tokens_generated"],
                    "tokens_per_sec": round(
                        st["tokens_generated"] / wall, 1),
                    "prefix_hit_tokens": st["prefix_hit_tokens"],
                    "placements": router.stats["placements"][i]})
                hit_tok += st["prefix_hit_tokens"]
                pre_tok += st["prefill_tokens"]
            return {
                "aggregate_tokens_per_sec": round(toks / wall, 1),
                "per_replica": per,
                "affinity_hit_rate": round(
                    hit_tok / (hit_tok + pre_tok), 4)
                if hit_tok + pre_tok else 0.0,
                "affinity_routed": router.stats["affinity_routed"],
                "resubmitted": router.stats["resubmitted"],
                "wall_s": round(wall, 3),
            }, [list(o.token_ids) for o in seed_outs + outs]

        single, toks_single = run_cluster(1, "affinity")
        cluster, toks_cluster = run_cluster(R, "affinity")
        random_arm, _ = run_cluster(R, "random")
        art_path = os.path.join(_artifact_dir(),
                                "llama_serve_cluster.json")
        with open(art_path, "w") as f:
            json.dump({"single": single, "cluster": cluster,
                       "cluster_random": random_arm}, f, indent=1)
        # r05's single-chip sync-loop serve line (1,158.9 tok/s): the
        # cluster aggregate is comparable only at the captured config on
        # chip — and is an R-replica number, so the ratio is the
        # capacity-scaling claim, not a same-hardware speedup
        at_r05_config = (
            B == 8 and new_tokens == 64 and n_layers == 3
            and hidden == 4096 and ff == hidden * 11 // 4
            and horizon == 64 and chunk == 256 and tp == 1
            and jax.default_backend() != "cpu")
        return {"metric": "llama_serve_cluster_tokens_per_sec",
                "value": cluster["aggregate_tokens_per_sec"],
                "unit": "tokens/s",
                "vs_baseline": (round(
                    cluster["aggregate_tokens_per_sec"] / 1158.9, 4)
                    if at_r05_config else None),
                "replicas": R, "tp": tp, "slots_per_replica": B,
                "single": single, "cluster": cluster,
                "cluster_random": random_arm,
                "cluster_speedup_vs_single": round(
                    cluster["aggregate_tokens_per_sec"]
                    / max(single["aggregate_tokens_per_sec"], 1e-9), 3),
                "affinity_hit_rate": cluster["affinity_hit_rate"],
                "random_hit_rate": random_arm["affinity_hit_rate"],
                # greedy serving: scaling out must not change one token
                "token_parity": toks_single == toks_cluster,
                "requests": n_req, "new_tokens": new_tokens,
                "tenants": n_tenants, "sys_prompt_len": sys_len,
                "chunk": chunk, "block_size": block, "horizon": horizon,
                "telemetry_artifact": art_path}

    if model_name == "llama_serve_lora":
        # Batched multi-LoRA A/B (paddle_tpu/serving/adapters.py): the
        # same base model served (a) WITHOUT an adapter store — the
        # pre-adapter compiled program, the overhead baseline — and (b)
        # with BENCH_ADAPTERS registered adapters and requests round-
        # robining across them through ONE fused paged engine, with an
        # adapter device cache of BENCH_ADAPTER_SLOTS slots (smaller
        # than the adapter count, so LRU swap-ins actually happen and
        # the swap rate is a real number). A per-adapter greedy PARITY
        # probe runs each adapter's stream against an offline
        # merged-weights reference engine.
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import (AsyncLLMServer, AdapterStore,
                                        apply_merged, random_lora_weights)
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_req = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        block = int(os.environ.get("BENCH_BLOCK", "64"))
        prompt_len = int(os.environ.get("BENCH_PROMPT", "128"))
        n_adapters = int(os.environ.get("BENCH_ADAPTERS", "8"))
        n_slots = int(os.environ.get("BENCH_ADAPTER_SLOTS", "4"))
        rank = int(os.environ.get("BENCH_RANK", "8"))
        n_parity = int(os.environ.get("BENCH_PARITY_ADAPTERS", "2"))
        cap = -(-(prompt_len + new_tokens) // chunk) * chunk
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        V = cfg.vocab_size
        prompts = [rng.integers(0, V, (prompt_len,)).astype(np.int32)
                   for _ in range(n_req)]
        store = AdapterStore(cfg, rank=rank)
        aids = [store.register(
            random_lora_weights(cfg, rank=rank, seed=100 + i, scale=0.02),
            alpha=2.0) for i in range(n_adapters)]

        def build_model():
            paddle.seed(0)
            m = LlamaForCausalLM(cfg).bfloat16()
            m.eval()
            return m

        def run_arm(adapter_ids, use_store):
            eng = LLMEngine(build_model(), max_batch=B, max_seq_len=cap,
                            chunk_size=chunk, cache_impl="paged",
                            block_size=block, scheduler="fused",
                            adapter_store=store if use_store else None,
                            adapter_cache_slots=n_slots)
            warm = rng.integers(0, V, (3,)).astype(np.int32)
            eng.generate([warm], max_new_tokens=2)
            eng.reset_stats()
            server = AsyncLLMServer(eng, max_queue_size=n_req + 1)
            server.start()
            t0 = time.perf_counter()
            hs = [server.submit(p, max_new_tokens=new_tokens,
                                adapter_id=aid)
                  for p, aid in zip(prompts, adapter_ids)]
            outs = [h.result(timeout=1800) for h in hs]
            wall = time.perf_counter() - t0
            server.stop()
            toks = sum(len(o.token_ids) for o in outs)
            st = eng.stats
            return {
                "tokens_per_sec": round(toks / wall, 1),
                "adapter_swaps": int(st["adapter_swaps"]),
                "adapter_cache_hits": int(st["adapter_cache_hits"]),
                "swap_rate": round(st["adapter_swaps"] / max(n_req, 1), 4),
                "wall_s": round(wall, 3),
            }

        base = run_arm([0] * n_req, use_store=False)
        mix = run_arm([aids[i % n_adapters] for i in range(n_req)],
                      use_store=True)
        # per-adapter greedy parity probe vs merged-weights references
        parity = True
        probe = prompts[0][:32]
        eng = LLMEngine(build_model(), max_batch=2, max_seq_len=cap,
                        chunk_size=chunk, cache_impl="paged",
                        block_size=block, scheduler="fused",
                        adapter_store=store, adapter_cache_slots=n_slots)
        for aid in aids[:n_parity]:
            rid = eng.add_request(probe, max_new_tokens=16, adapter_id=aid)
            while eng.has_unfinished():
                eng.step()
            got = eng.finished_outputs.pop(rid).token_ids
            merged = build_model()
            apply_merged(merged, store, aid)
            ref_eng = LLMEngine(merged, max_batch=2, max_seq_len=cap,
                                chunk_size=chunk, cache_impl="paged",
                                block_size=block, scheduler="fused")
            (ref,) = ref_eng.generate([probe], max_new_tokens=16)
            parity = parity and (got == ref.token_ids)
        return {"metric": "llama_serve_lora_tokens_per_sec",
                "value": mix["tokens_per_sec"],
                "unit": "tokens/s", "vs_baseline": None,
                "base": base, "adapter_mix": mix,
                "lora_overhead_pct": round(
                    (1.0 - mix["tokens_per_sec"]
                     / max(base["tokens_per_sec"], 1e-9)) * 100, 2),
                "swap_rate": mix["swap_rate"],
                "token_parity_vs_merged": parity,
                "adapters": n_adapters, "adapter_cache_slots": n_slots,
                "rank": rank, "requests": n_req, "slots": B,
                "new_tokens": new_tokens, "prompt_len": prompt_len,
                "chunk": chunk, "block_size": block}

    if model_name == "llama_serve_embed":
        # Mixed generate + PREFILL-ONLY embedding serving through one
        # fused engine (the multi-tenant scenario-diversity rung): a
        # generate-only arm is the control, then the same generate
        # workload re-runs with BENCH_EMBED embedding requests riding
        # the SAME token-budget walk — the mixed arm reports generation
        # tok/s (interference cost) plus embeds/s (the new capacity).
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference import LLMEngine
        from paddle_tpu.serving import AsyncLLMServer
        B = int(os.environ.get("BENCH_BATCH", "8"))
        new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "64"))
        n_gen = int(os.environ.get("BENCH_REQUESTS", str(2 * B)))
        n_emb = int(os.environ.get("BENCH_EMBED", str(n_gen)))
        n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
        ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
        heads = max(hidden // 128, 1)
        chunk = int(os.environ.get("BENCH_CHUNK", "256"))
        block = int(os.environ.get("BENCH_BLOCK", "64"))
        prompt_len = int(os.environ.get("BENCH_PROMPT", "128"))
        emb_len = int(os.environ.get("BENCH_EMBED_LEN", "256"))
        cap = -(-(max(prompt_len, emb_len) + new_tokens) // chunk) * chunk
        cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                          intermediate_size=ff, num_hidden_layers=n_layers,
                          num_attention_heads=heads,
                          num_key_value_heads=heads,
                          max_position_embeddings=cap)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg).bfloat16()
        model.eval()
        V = cfg.vocab_size
        gen_prompts = [rng.integers(0, V, (prompt_len,)).astype(np.int32)
                       for _ in range(n_gen)]
        emb_prompts = [rng.integers(0, V, (emb_len,)).astype(np.int32)
                       for _ in range(n_emb)]

        def run_arm(with_embed):
            eng = LLMEngine(model, max_batch=B, max_seq_len=cap,
                            chunk_size=chunk, cache_impl="paged",
                            block_size=block, scheduler="fused")
            warm = rng.integers(0, V, (3,)).astype(np.int32)
            eng.generate([warm], max_new_tokens=2)
            eng.reset_stats()
            server = AsyncLLMServer(
                eng, max_queue_size=n_gen + n_emb + 1)
            server.start()
            t0 = time.perf_counter()
            hs = [server.submit(p, max_new_tokens=new_tokens)
                  for p in gen_prompts]
            ehs = [server.submit_embed(p)
                   for p in emb_prompts] if with_embed else []
            outs = [h.result(timeout=1800) for h in hs]
            eouts = [h.result(timeout=1800) for h in ehs]
            wall = time.perf_counter() - t0
            server.stop()
            toks = sum(len(o.token_ids) for o in outs)
            assert all(o.embedding is not None for o in eouts)
            snap = server.telemetry.snapshot(wall_s=wall)
            return {
                "tokens_per_sec": round(toks / wall, 1),
                "embeds_per_sec": round(len(eouts) / wall, 2)
                if with_embed else 0.0,
                "embed_tokens_per_sec": round(
                    sum(len(p) for p in emb_prompts) / wall, 1)
                if with_embed else 0.0,
                "ttft_p50_ms": round(
                    snap["latency"]["ttft"]["p50_s"] * 1e3, 1),
                "wall_s": round(wall, 3),
            }, [list(o.token_ids) for o in outs]

        gen_only, toks_only = run_arm(False)
        mixed, toks_mixed = run_arm(True)
        return {"metric": "llama_serve_embed_mixed_tokens_per_sec",
                "value": mixed["tokens_per_sec"],
                "unit": "tokens/s", "vs_baseline": None,
                "generate_only": gen_only, "mixed": mixed,
                "embeds_per_sec": mixed["embeds_per_sec"],
                "generate_interference_pct": round(
                    (1.0 - mixed["tokens_per_sec"]
                     / max(gen_only["tokens_per_sec"], 1e-9)) * 100, 2),
                # greedy serving: embed traffic riding the same steps
                # must not change one generated token
                "token_parity": toks_only == toks_mixed,
                "gen_requests": n_gen, "embed_requests": n_emb,
                "slots": B, "new_tokens": new_tokens,
                "prompt_len": prompt_len, "embed_len": emb_len,
                "chunk": chunk, "block_size": block}

    if model_name == "conv_roofline":
        return _bench_conv_roofline()

    if model_name == "dispatch":
        return _bench_dispatch()

    if model_name == "memcheck":
        return _bench_memcheck()

    if model_name == "loss_parity":
        return _bench_loss_parity()

    raise ValueError(f"unknown BENCH_MODEL {model_name!r}")


def run_loss_parity(cfg_over=None, B=4, S=1024, steps=100, lr=3e-4):
    """Long-horizon loss-curve parity (VERDICT r3 #8): train the SAME llama
    config twice — bf16 params with fp32 AdamW masters (the production
    chain) vs an all-fp32 reference — with matched data order and RNG, and
    return the two trajectories + max relative divergence. Shared by the
    on-chip bench mode and the CPU CI test (tests/test_loss_parity.py)."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    base = dict(vocab_size=8192, hidden_size=2048, intermediate_size=5632,
                num_hidden_layers=2, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=S,
                use_recompute=True)
    base.update(cfg_over or {})
    cfg = LlamaConfig(**base)

    def run(bf16):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        if bf16:
            model = model.bfloat16()
        optimizer = opt.AdamW(learning_rate=lr,
                              parameters=model.parameters(),
                              weight_decay=0.01, multi_precision=bf16)

        def loss_fn(m, ids, labels):
            loss, _ = m(ids, labels=labels)
            return loss

        step = TrainStep(model, loss_fn, optimizer, donate=True)
        rng = np.random.default_rng(42)  # matched data order across runs
        losses = []
        for _ in range(steps):
            ids = paddle.to_tensor(
                rng.integers(0, cfg.vocab_size, (B, S)), dtype="int32")
            labels = paddle.to_tensor(
                rng.integers(0, cfg.vocab_size, (B, S)), dtype="int32")
            losses.append(float(np.asarray(step(ids, labels)._value)))
        return losses

    bf16 = run(True)
    ref = run(False)
    rel = [abs(a - b) / max(abs(b), 1e-9) for a, b in zip(bf16, ref)]
    return {"bf16": bf16, "fp32": ref,
            "max_rel_divergence": max(rel),
            "final_rel_divergence": rel[-1],
            "steps": steps}


def _bench_loss_parity():
    steps = int(os.environ.get("BENCH_PARITY_STEPS", "100"))
    B = int(os.environ.get("BENCH_BATCH", "4"))
    S = int(os.environ.get("BENCH_SEQ", "1024"))
    res = run_loss_parity(B=B, S=S, steps=steps)
    return {"metric": "llama_bf16_vs_fp32_loss_divergence_100step",
            "value": round(res["max_rel_divergence"] * 100, 3),
            "unit": "% max rel", "vs_baseline": None,
            "final_rel_pct": round(res["final_rel_divergence"] * 100, 3),
            "steps": steps,
            "loss_first_bf16": round(res["bf16"][0], 4),
            "loss_last_bf16": round(res["bf16"][-1], 4),
            "loss_last_fp32": round(res["fp32"][-1], 4)}


def _bench_memcheck():
    """Cross-validate the 7B-fit memory model against the REAL TPU compiler
    (VERDICT r3 weak #4/#5): AOT-compile the flagship bench config on this
    backend and compare predicted residency (compiled state bytes + the
    trace-level saved-residuals model that the virtual-mesh proofs rest on)
    with the compiler's own ``peak_memory_in_bytes``. The gap IS the
    in-segment transient — the number the 7B proof's "tens of MB" claim
    needs. Compile-only: no arrays are materialized."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.utils.memory_model import residual_bytes

    B = int(os.environ.get("BENCH_BATCH", "6"))
    S = int(os.environ.get("BENCH_SEQ", "2048"))
    n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
    hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
    ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
    heads = max(hidden // 128, 1)
    set_flags({"adamw_bf16_moments": True, "use_fused_adamw": False})
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=ff,
        num_hidden_layers=n_layers, num_attention_heads=heads,
        num_key_value_heads=heads, max_position_embeddings=S,
        use_recompute=True)
    paddle.seed(0)
    with paddle.LazyGuard():
        model = LlamaForCausalLM(cfg).bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          weight_decay=0.01, multi_precision=True)

    def loss_fn(m, ids, labels):
        loss, _ = m(ids, labels=labels)
        return loss

    step = TrainStep(model, loss_fn, optimizer, donate=True)
    ids = Tensor(jax.ShapeDtypeStruct((B, S), jnp.int32))
    compiled = step.aot_compile(ids, ids)
    m = compiled.memory_analysis()
    state = int(m.argument_size_in_bytes)
    peak = int(getattr(m, "peak_memory_in_bytes", 0))
    try:
        residuals = residual_bytes(step, (ids, ids), seq_len=S)
        resid_err = None
    except RuntimeError as e:
        residuals, resid_err = None, str(e)
    out = {"metric": "memcheck_7b_model_vs_compiler",
           "value": None, "unit": "pct", "vs_baseline": None,
           "params": n_params,
           "state_bytes_compiled": state,
           "residual_bytes_predicted": residuals,
           "peak_bytes_compiler": peak,
           "temp_bytes_compiler": int(getattr(m, "temp_size_in_bytes", 0)),
           "backend": jax.default_backend()}
    if residuals is not None and peak:
        predicted = state + residuals
        out["predicted_resident_bytes"] = predicted
        out["transient_bytes"] = peak - predicted
        out["value"] = round((peak - predicted) / peak * 100, 2)
    if resid_err:
        out["residual_model_error"] = resid_err[:200]
    return out


def _measured_stream_bw():
    """Measured HBM stream bandwidth (bytes/s) from the DEVICE-track
    duration of a large bf16 axpy fusion — the roofline denominator.
    Host-side timing includes the dispatch floor; the profiler's device
    track does not."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.utils.roofline import profile_device_events

    N = 128 * 1024 * 1024  # 256 MB per array
    x = jnp.ones((N,), jnp.bfloat16)
    y = jnp.ones((N,), jnp.bfloat16)
    axpy = jax.jit(lambda x, y: x * jnp.bfloat16(1.0001) + y)
    r = axpy(x, y)
    float(np.asarray(r[0]))

    def run(steps):
        for _ in range(steps):
            r = axpy(x, y)
        float(np.asarray(r[0]))

    ev, _ = profile_device_events(run, steps=8)
    # the only compute event is the axpy loop fusion: 2 reads + 1 write
    name, best = None, 0.0
    for n, d in ev.items():
        if d["total_us"] > best and not n.startswith("copy"):
            name, best = n, d["total_us"]
    per_step = best / 8 / 1e6
    return 3 * N * 2 / per_step


def _bench_conv_roofline():
    """Regenerate docs/artifacts/conv_roofline_proof.json (VERDICT r4 #1):
    per-fusion achieved FLOP/s + B/s vs each fusion's own roofline bound,
    for the resnet50 and unet bench steps, on the real chip. The reference
    counterpart is the cudnn conv stack with layout/algorithm autotuning
    (paddle/phi/kernels/gpudnn/conv_kernel.cu,
    phi/kernels/autotune/auto_tune_base.h); here the question "is XLA's
    conv lowering at the hardware ceiling" is answered per fusion."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.utils.roofline import (profile_device_events,
                                           roofline_table)

    steps = int(os.environ.get("BENCH_STEPS", "4"))
    rng = np.random.default_rng(0)
    peak = _peak_flops(jax.devices()[0])
    if peak is None:
        raise RuntimeError(
            f"conv_roofline needs a device in the _PEAK table, found "
            f"{jax.devices()[0].device_kind!r}")
    bw = _measured_stream_bw()
    models = {}

    def analyze(name, step, args):
        compiled = step.aot_compile(*args)
        hlo = compiled.as_text()
        for _ in range(2):  # donated-layout recompile must precede trace
            loss = step(*args)
        float(np.asarray(loss._value))

        def run(n):
            for _ in range(n):
                loss = step(*args)
            float(np.asarray(loss._value))

        ev, jit_total = profile_device_events(run, steps=steps)
        # self-calibrate the bandwidth roofline: the HIGHEST sustained HBM
        # rate demonstrated by any long-running fusion of this very step
        # (or the axpy probe) — the most self-critical denominator
        rows, _ = roofline_table(hlo, ev, steps, peak, bw)
        # capped at the chip's spec bandwidth: a fusion "demonstrating" more
        # than spec means residual byte overcount (aliased operands), not a
        # faster memory system
        bw_cal = min(max([bw] + [r["achieved_gbs"] * 1e9 for r in rows
                                 if r["time_us"] > 200
                                 and r["bytes"] > 32e6]),
                     819e9)
        rows, unmatched = roofline_table(hlo, ev, steps, peak, bw_cal)
        # module container events give the true device step time; leaf
        # events + unmatched is the fallback
        step_us = (jit_total / steps if jit_total
                   else sum(r["time_us"] for r in rows) + unmatched)
        conv = [r for r in rows if r["kind"] == "conv"]
        conv_us = sum(r["time_us"] for r in conv)
        conv_bound = sum(r["bound_us"] for r in conv)
        # "major" fusions: >=2% of step device time each
        major = [r for r in conv if r["time_us"] >= 0.02 * step_us]
        tot_bytes = sum(r["bytes"] for r in rows)
        tot_flops = sum(r["flops"] for r in rows)
        step_bound_us = max(tot_bytes / bw_cal, tot_flops / peak) * 1e6
        models[name] = {
            "step_device_us": round(step_us, 1),
            "hbm_bw_roofline_gbs": round(bw_cal / 1e9, 1),
            "total_hbm_gb_per_step": round(tot_bytes / 1e9, 2),
            "total_tflop_per_step": round(tot_flops / 1e12, 3),
            "aggregate_gbs": round(tot_bytes / step_us / 1e3, 1),
            "achieved_pct_of_peak_flops": round(
                tot_flops / (step_us / 1e6) / peak * 100, 2),
            # the whole step against ITS OWN roofline: the bound the
            # reference's tuned conv stack would also be subject to
            "step_bound_us": round(step_bound_us, 1),
            "step_roofline_eff": round(step_bound_us / step_us, 3),
            "step_bound_by": ("compute" if tot_flops / peak
                              >= tot_bytes / bw_cal else "memory"),
            "conv_time_share": round(conv_us / step_us, 3),
            "conv_weighted_roofline_eff": round(conv_bound / conv_us, 3),
            "major_conv_fusions": len(major),
            "major_conv_fusions_above_80pct": sum(
                1 for r in major if (r["roofline_eff"] or 0) >= 0.8),
            "unmatched_us_per_step": round(unmatched, 1),
            "rows": rows[:40],
        }

    # resnet50, exactly the bench config
    B = int(os.environ.get("BENCH_BATCH", "128"))
    paddle.seed(0)
    from paddle_tpu.vision.models import resnet50
    model = resnet50(num_classes=1000, data_format="NHWC").bfloat16()
    optimizer = opt.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())
    step = TrainStep(model, lambda m, x, y: F.cross_entropy(m(x), y),
                     optimizer)
    x = paddle.to_tensor(rng.standard_normal(
        (B, 224, 224, 3)).astype(np.float32)).astype("bfloat16")
    y = paddle.to_tensor(rng.integers(0, 1000, B))
    analyze("resnet50", step, (x, y))
    del model, optimizer, step, x, y
    import gc
    gc.collect()

    # unet, exactly the bench config
    from paddle_tpu.models import UNetConfig, UNetModel, diffusion_loss
    Bu = int(os.environ.get("BENCH_UNET_BATCH", "4"))
    paddle.seed(0)
    um = UNetModel(UNetConfig.sd_unet(use_recompute=True)).bfloat16()
    uopt = opt.AdamW(learning_rate=1e-4, parameters=um.parameters(),
                     multi_precision=True)
    alphas = paddle.to_tensor(np.linspace(0.999, 0.01, 1000)
                              .astype(np.float32))
    ustep = TrainStep(um, lambda m, lat, t, ctx, noise: diffusion_loss(
        m, lat, t, ctx, noise, alphas), uopt)
    lat = paddle.to_tensor(rng.standard_normal(
        (Bu, 64, 64, 4)).astype(np.float32)).astype("bfloat16")
    t = paddle.to_tensor(rng.integers(0, 1000, Bu))
    ctx = paddle.to_tensor(rng.standard_normal(
        (Bu, 77, 768)).astype(np.float32)).astype("bfloat16")
    noise = paddle.to_tensor(rng.standard_normal(
        (Bu, 64, 64, 4)).astype(np.float32)).astype("bfloat16")
    analyze("unet", ustep, (lat, t, ctx, noise))

    artifact = {
        "description": "Per-fusion roofline proof for the conv workloads "
                       "(resnet50 B=128, sd-unet B=4 train steps). "
                       "bound_us = max(flops/peak, bytes/bw); "
                       "roofline_eff = bound_us/time_us (1.0 = at the "
                       "roofline). flops are VALID-pair conv MACs x2 "
                       "(padding/dilation zeros excluded); bytes exclude "
                       "VMEM-prefetched (S(1)) operands. bw is "
                       "self-calibrated per model: max sustained HBM rate "
                       "demonstrated by any fusion of the same step.",
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
        "peak_bf16_flops": peak,
        "hbm_bw_axpy_probe_gbs": round(bw / 1e9, 1),
        "models": models,
        "attempt_ladder": [
            {"experiment": "layout NCHW vs NHWC end-to-end",
             "result": "EQUAL full-step throughput (XLA layout-assigns "
                       "convs; isolated microbenches misleadingly show "
                       "NHWC 1.5x)", "recorded": "round 3, PROGRESS + "
                       "BENCH_LAYOUT=NCHW knob in bench.py"},
            {"experiment": "resnet batch sweep B=128 vs 256",
             "result": "no change in imgs/s/chip — bandwidth-bound, "
                       "bigger batch scales bytes with flops",
             "recorded": "round 3"},
            {"experiment": "unet batch B=4 vs B=8",
             "result": "15.1 vs 15.2% MFU — batch-insensitive",
             "recorded": "round 4, PROGRESS unet_mfu_measured"},
            {"experiment": "FLOP accounting audit (this artifact)",
             "result": "bench.py used 4.1 GMACs/img as FLOPs — true "
                       "fwd is ~8.2 GFLOP/img (per-instruction HLO "
                       "count); resnet MFU restated ~2x higher",
             "recorded": "round 5, this file"},
            {"experiment": "unet attention: Pallas flash vs XLA einsum "
                           "A/B at every sd-unet shape (fwd+bwd, device-"
                           "track timed)",
             "result": "flash wins 2.6-20x everywhere: self 4096/d40 "
                       "5.06ms (einsum OOMs: 2GB logits buffers), cross "
                       "4096/77 0.73 vs 2.67ms, self 1024/d80 0.39 vs "
                       "8.60ms, cross 1024/77 0.14 vs 0.60ms, self 256/"
                       "d160 0.07 vs 0.45ms, cross 256/77 0.06 vs 0.15ms. "
                       "The 4096/d40 kernel runs AT the lane-padded MXU "
                       "bound (~4.8ms ideal for d=40 padded to 128 lanes) "
                       "— the 3.2x padding waste is inherent to head_dim "
                       "40 on a 128x128 systolic array, an SD architecture "
                       "choice, not a kernel deficiency",
             "recorded": "round 5, this file"},
            {"experiment": "per-fusion roofline (this artifact)",
             "result": "see models.*: conv fusions are MEMORY-bound on "
                       "resnet (weighted eff vs own bound in "
                       "conv_weighted_roofline_eff); the step as a whole "
                       "runs at step_roofline_eff of its bandwidth bound",
             "recorded": "round 5, this file"},
        ],
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "docs", "artifacts", "conv_roofline_proof.json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    return {"metric": "conv_roofline_weighted_eff",
            "value": models["resnet50"]["conv_weighted_roofline_eff"],
            "unit": "x of roofline", "vs_baseline": None,
            "unet_eff": models["unet"]["conv_weighted_roofline_eff"],
            "hbm_bw_measured_gbs": round(bw / 1e9, 1),
            "artifact": path}


def _bench_dispatch():
    """Eager op-dispatch microbenchmark (reference: the codegen'd allocation-
    free eager path, fluid/eager/auto_code_generator/generator/eager_gen.py).
    Measures forward ops/sec for small add/matmul/layer_norm with the
    compiled dispatch cache on vs off (grad recording enabled, so the cached
    path includes building the jitted vjp pair)."""
    import time
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core import tensor as T

    paddle.seed(0)
    x = paddle.randn([128, 128])
    x.stop_gradient = False
    y = paddle.randn([128, 128])
    w = paddle.randn([128])
    b = paddle.randn([128])

    import jax.numpy as jnp
    xv, yv, wv, bv = x._value, y._value, w._value, b._value
    jadd = jax.jit(lambda a, b2: a + b2)
    jmm = jax.jit(jnp.matmul)

    def jln(a, weight, bias):
        mu = jnp.mean(a, -1, keepdims=True)
        var = jnp.var(a, -1, keepdims=True)
        return (a - mu) / jnp.sqrt(var + 1e-5) * weight + bias

    jln = jax.jit(jln)

    cases = {
        "add": (lambda: x + y, lambda: jadd(xv, yv)),
        "matmul": (lambda: paddle.matmul(x, y), lambda: jmm(xv, yv)),
        "layer_norm": (lambda: F.layer_norm(x, [128], weight=w, bias=b),
                       lambda: jln(xv, wv, bv)),
    }

    def rate(f, n=300):
        f(); f()
        t0 = time.perf_counter()
        for _ in range(n):
            out = f()
        jax.block_until_ready(getattr(out, "_value", out))
        return n / (time.perf_counter() - t0)

    result = {}
    saved_max = T._DISPATCH_CACHE_MAX
    for label, (f, raw) in cases.items():
        T._DISPATCH_CACHE_MAX = saved_max
        fast = rate(f)
        T._DISPATCH_CACHE.clear()
        T._DISPATCH_CACHE_MAX = 0   # force the uncached path
        slow = rate(f, n=60)
        T._DISPATCH_CACHE_MAX = saved_max
        # absolute target: a pre-jitted raw-jax dispatch of the same compute
        # (no tape, no Tensor wrapper) — the residual overhead is tracked
        raw_rate = rate(raw)
        result[label] = {"cached_ops_per_sec": round(fast, 1),
                         "uncached_ops_per_sec": round(slow, 1),
                         "raw_jax_ops_per_sec": round(raw_rate, 1),
                         "speedup": round(fast / slow, 2),
                         "overhead_vs_raw_jax": round(raw_rate / fast, 2)}

    gmean = float(np.prod([v["speedup"] for v in result.values()])) ** (
        1.0 / len(result))
    over = float(np.prod([v["overhead_vs_raw_jax"]
                          for v in result.values()])) ** (1.0 / len(result))
    return {"metric": "eager_dispatch_speedup_geomean",
            "value": round(gmean, 2), "unit": "x", "vs_baseline": None,
            "overhead_vs_raw_jax_geomean": round(over, 2),
            "detail": result}


def _analysis_header():
    """The JSON header line before the workload ladder: the static-
    analysis state of the tree (paddle_tpu.analysis) so the trajectory
    records the baseline burn-down next to the perf numbers.
    ``analysis_findings`` = active (would-fail) findings — 0 on a clean
    tree; ``analysis_baselined`` = grandfathered debt still to burn.
    Runs as the ``analysis`` cell, in a child like every other cell."""
    from paddle_tpu.analysis import count_findings
    here = os.path.dirname(os.path.abspath(__file__))
    active, baselined, suppressed = count_findings(
        [os.path.join(here, "paddle_tpu")],
        baseline_path=os.path.join(here, "analysis_baseline.json"))
    return {"metric": "analysis_findings", "value": active,
            "unit": "findings", "vs_baseline": None,
            "analysis_baselined": baselined,
            "analysis_suppressed": suppressed}


def _run_all():
    """Default driver mode: the analysis header, then one JSON line per
    BASELINE config (1-5) plus llama_decode, with the flagship llama LAST
    so single-line tail parsing keeps working. Each cell runs in its own
    subprocess — flag settings and HBM stay isolated, and one cell failing
    doesn't take down the rest. This parent imports nothing that depends on
    jax: a chip belongs to one process, and the children need it. Returns
    the number of failed cells (the process exit code is non-zero when any
    failed)."""
    import subprocess
    import sys
    failed = 0
    # the int8/int4 rungs re-baseline the weight-only-quantized decode
    # ratios IN the ladder (same two-length-differential harness, same
    # subprocess isolation) — the retired 1.35x/1.67x numbers
    # regenerate here on every `all` run
    # instead of being re-quoted (compare their tokens/s against the
    # bf16 llama_decode line; each JSON line carries weight_dtype).
    for name, extra in [
            ("analysis", None),
            ("resnet50", None), ("bert", None), ("vit", None),
            ("unet", None), ("llama_decode", None),
            ("llama_decode_int8",
             {"BENCH_MODEL": "llama_decode", "BENCH_WEIGHT_DTYPE": "int8"}),
            ("llama_decode_int4",
             {"BENCH_MODEL": "llama_decode", "BENCH_WEIGHT_DTYPE": "int4"}),
            ("llama_paged_decode", None), ("llama_serve", None),
            ("llama_serve_fused", None), ("llama_serve_prefix_cache", None),
            ("llama_serve_kv_quant", None),
            ("llama_serve_kv_tier", None),
            ("llama_serve_disagg", None),
            ("llama_serve_slo", None),
            ("llama_serve_cluster", None), ("llama_serve_spec", None),
            ("llama_serve_lora", None), ("llama_serve_embed", None),
            ("llama", None)]:
        env = dict(os.environ, BENCH_MODEL=name)
        if extra:
            env.update(extra)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)], env=env,
                capture_output=True, text=True, timeout=1800)
            line = next((ln for ln in reversed(proc.stdout.splitlines())
                         if ln.startswith("{")), None)
            if proc.returncode != 0:
                line = None
            err = proc.stderr[-400:]
        except subprocess.TimeoutExpired:
            line, err = None, "timeout after 1800s"
        if line:
            print(line, flush=True)
        else:
            failed += 1
            print(json.dumps({"metric": f"{name}_bench_failed", "value": None,
                              "unit": "", "vs_baseline": None, "error": err}),
                  flush=True)
    return failed


def main():
    model_name = os.environ.get("BENCH_MODEL", "all")
    if model_name == "all":
        raise SystemExit(1 if _run_all() else 0)
    if model_name == "analysis":
        print(json.dumps(_analysis_header()))
        return

    import jax
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if model_name != "llama":
        out = _bench_other(model_name)
        out["device"] = getattr(jax.devices()[0], "device_kind", "unknown")
        print(json.dumps(out))
        return

    # defaults = best measured config at representative depth (>=3 of the
    # 7B-wide d=4096/ff=11264 decoder layers) and the 2k llama pretrain
    # context. Per-layer remat + flash attention lets B=6 fit beside the
    # 12.3GB of AdamW state for 879M params; the bigger batch amortizes the
    # optimizer/master-weight HBM traffic (the measured dominant overhead).
    # 24-step curve (2026-07-30): L3B6+remat 55.7%, L3B3+remat 53.4,
    # L3B8+remat 53.2, L2B3 no-remat 55.3 (old default), L3B12/L4 OOM
    # (L4 AdamW state alone is 15.2G of the 15.75G HBM).
    B = int(os.environ.get("BENCH_BATCH", "6"))
    S = int(os.environ.get("BENCH_SEQ", "2048"))
    n_layers = int(os.environ.get("BENCH_LAYERS", "3"))
    steps = int(os.environ.get("BENCH_STEPS", "12"))
    # bf16 moment storage (fp32 update math): -3.5GB optimizer HBM traffic,
    # measured +0.9 MFU at the default config (56.6 vs 55.7). Framework
    # default stays fp32 (reference-exact trajectories); the bench opts in
    # and reports the choice in its JSON line.
    bf16_moments = os.environ.get("BENCH_BF16_MOMENTS", "1") == "1"
    if bf16_moments:
        from paddle_tpu.core.flags import set_flags
        set_flags({"adamw_bf16_moments": True})
    hidden = int(os.environ.get("BENCH_HIDDEN", "4096"))
    ff = int(os.environ.get("BENCH_FF", str(hidden * 11 // 4)))
    heads = max(hidden // 128, 1)

    fused = os.environ.get("BENCH_FUSED", "0") == "1"
    remat = os.environ.get("BENCH_REMAT", "1") == "1"
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=ff,
        num_hidden_layers=n_layers, num_attention_heads=heads,
        num_key_value_heads=heads, max_position_embeddings=S,
        fuse_attention_qkv=fused, fuse_swiglu=fused,
        use_recompute=remat,
    )
    paddle.seed(0)
    model = LlamaForCausalLM(cfg).bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())

    bench_opt = os.environ.get("BENCH_OPT", "adamw")
    if bench_opt == "sgd":
        optimizer = opt.SGD(learning_rate=3e-4, parameters=model.parameters(),
                            multi_precision=False)
    elif bench_opt == "adamw_sr":
        # master-weight-FREE AdamW: bf16 params + moments + in-kernel
        # stochastic rounding — 6 B/param of optimizer state (vs 14 with
        # masters). Measured: throughput TIES the master chain on this chip
        # (optimizer traffic is latency-hidden); the win is the ~6.7 GB of
        # freed HBM at 7B scale (see tests/test_7b_scale.py SR footprint)
        from paddle_tpu.core.flags import set_flags
        set_flags({"adamw_stochastic_rounding": True,
                   "adamw_bf16_moments": True})
        optimizer = opt.AdamW(learning_rate=3e-4,
                              parameters=model.parameters(),
                              weight_decay=0.01, multi_precision=False)
    else:
        optimizer = opt.AdamW(learning_rate=3e-4,
                              parameters=model.parameters(),
                              weight_decay=0.01, multi_precision=True)

    def loss_fn(m, ids, labels):
        loss, _ = m(ids, labels=labels)
        return loss

    accum = int(os.environ.get("BENCH_ACCUM", "1"))
    step = TrainStep(model, loss_fn, optimizer, accumulate_steps=accum)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, size=(B, S)), dtype="int32")
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, size=(B, S)), dtype="int32")

    # warmup / compile TWO full accumulation cycles (sync via scalar host
    # fetch). Two, not one: paths
    # whose first call returns donated outputs in a different layout (e.g.
    # pallas-written params) trigger a one-time recompile on the SECOND
    # call, which must not land inside the d1 timing window.
    for _ in range(2 * accum):
        loss = step(ids, labels)
    final_loss = float(np.asarray(loss._value))

    # differential timing cancels the dispatch+fetch round-trip latency;
    # timed units are whole accumulation cycles so update cost amortizes
    t0 = time.perf_counter()
    for _ in range(accum):
        loss = step(ids, labels)
    np.asarray(loss._value)
    d1 = time.perf_counter() - t0

    cycles = max(steps // accum, 1)
    t0 = time.perf_counter()
    for _ in range((cycles + 1) * accum):
        loss = step(ids, labels)
    final_loss = float(np.asarray(loss._value))
    dn = time.perf_counter() - t0

    if os.environ.get("BENCH_DEBUG"):
        import sys
        print(f"[bench debug] d1={d1:.3f}s dn={dn:.3f}s cycles={cycles}",
              file=sys.stderr)
    dt = max(dn - d1, 1e-9)
    tokens_per_sec = cycles * accum * B * S / dt
    flops_per_token = model.flops_per_token(S)
    mfu_pct = _pct_of_peak(flops_per_token * tokens_per_sec,
                           _peak_flops(jax.devices()[0]))

    print(json.dumps({
        "metric": "llama_1chip_train_mfu",
        "value": mfu_pct,
        "unit": "% MFU",
        "vs_baseline": None if mfu_pct is None else round(mfu_pct / 45.0, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_s": round(dt / steps, 4),
        "params": n_params,
        "loss": final_loss,
        "bf16_moments": bf16_moments,
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
    }))


if __name__ == "__main__":
    main()
