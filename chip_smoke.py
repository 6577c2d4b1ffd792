"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip: train phase, then serve phase
    python chip_smoke.py multichip    one host, four chips: dryrun / TP train /
                                      four pinned replicas / TP serving

Drives the two main paths once through the entry points a user calls, at the
full width of the repo's flagship (hidden 4096, ff 11264, 32 heads x 128,
vocab 32,000, bf16; depth cut to 3 layers; weights random from a seed):

* train — ``LlamaForCausalLM`` + ``AdamW(multi_precision=True)`` under
  ``TrainStep`` (flash attention fwd+bwd, fused AdamW), a few steps on one
  repeated batch: loss finite at every step, lower at the last than the first;
* serve — ``LLMEngine(scheduler="fused", cache_impl="paged")`` behind
  ``AsyncLLMServer``: every request finishes on its length budget, streamed
  tokens equal the terminal result, pool fences clean; the same prompts
  through the default (legacy/dense) engine; and every greedy token either
  engine emitted must be the reference's argmax to within bf16 rounding
  (see :func:`greedy_margin_ulps` for the bar and why it is not equality);
* kernels — for each phase, the lowered programs that ran carry the Mosaic
  custom calls (flash fwd/bwd in the train step, paged decode/append in the
  serve programs): no interpret mode, no dense fallback.

It sets no platform and has no CPU mode: if jax finds no TPU it exits
non-zero, naming what it found. One process holds the chip(s) throughout.
The last line of stdout is one JSON object ``{"ok": true, "device": ...}``;
the exit code is 0 only if every phase passed — no phase failure is caught.

The phases are importable functions taking a config, so tests/
test_chip_smoke.py drives the same control flow at toy size on CPU; only
:func:`main` fixes the widths and demands the chip.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

#: the flagship widths: Llama-2-7B's hidden size and heads, 3 layers
FLAGSHIP = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11264,
                num_hidden_layers=3, num_attention_heads=32,
                num_key_value_heads=32)

#: the serving shape examples/serve_llama.py calls production
SERVE_ENGINE = dict(max_batch=8, scheduler="fused", cache_impl="paged",
                    block_size=64, chunk_size=256, readout_stride=4)

#: greedy-parity bar, in bf16 ulps of the reference's top logit
MARGIN_ULPS = 4.0

#: the Mosaic kernels each phase's programs must carry on the chip
TRAIN_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq", "fused_adamw")
SERVE_KERNELS = ("paged_attention_decode", "paged_attention_append")


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# kernel evidence: which Mosaic custom calls the lowered programs carry
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def lowered_programs():
    """Have jax dump the StableHLO of every program it lowers inside the
    block (cache hit or miss) and yield ``read() -> {module: text}``. A
    Pallas kernel compiled by Mosaic is a ``tpu_custom_call`` carrying its
    ``kernel_name``; under interpret mode or an XLA fallback there is
    none — so the text of the programs that RAN is the evidence."""
    import jax
    prev = jax.config.read("jax_dump_ir_to")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ir_") as d:
        jax.config.update("jax_dump_ir_to", d)

        def read():
            out = {}
            for path in glob.glob(os.path.join(d, "*.mlir")):
                with open(path) as f:
                    out[os.path.basename(path)] = f.read()
            return out
        try:
            yield read
        finally:
            jax.config.update("jax_dump_ir_to", prev)


def mosaic_calls(programs):
    """``{kernel_name: {module_file: count}}`` of the Mosaic custom calls
    in the dumped programs."""
    found = {}
    for module, text in programs.items():
        if "tpu_custom_call" not in text:
            continue
        for name in re.findall(r'kernel_name = "([^"]+)"', text):
            per = found.setdefault(name, {})
            per[module] = per.get(module, 0) + 1
    return found


def require_kernels(calls, names):
    """Print one line per kernel; raise if any is missing from the
    programs that ran."""
    for name in names:
        per = calls.get(name, {})
        log(f"kernel {name}: {sum(per.values())} Mosaic custom call(s) in "
            f"{len(per)} lowered program(s)")
        if not per:
            raise RuntimeError(
                f"kernel {name} is not in any lowered program of this "
                f"phase: it ran interpreted or an XLA fallback took its "
                f"place (Mosaic kernels seen: {sorted(calls)})")


# ---------------------------------------------------------------------------
# train phase
# ---------------------------------------------------------------------------

def build_model(cfg, seed=0):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    paddle.seed(seed)
    return LlamaForCausalLM(cfg).bfloat16()


def train_phase(cfg, batch, seq, steps=4, place=None):
    """``steps`` TrainStep iterations on one repeated seeded batch, each
    ended with ``block_until_ready``. ``place(model)`` (optional) lays the
    weights out before the optimizer is built (the multichip TP layout).
    Returns the losses; raises unless they are finite and falling."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.api import TrainStep

    t0 = time.perf_counter()
    model = build_model(cfg)
    if place is not None:
        place(model)
    optimizer = opt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          weight_decay=0.01, multi_precision=True)
    step = TrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl)[0],
                     optimizer)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)),
                           dtype="int32")
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)),
                              dtype="int32")
    losses, walls = [], []
    for i in range(steps):
        t = time.perf_counter()
        loss = step(ids, labels)
        jax.block_until_ready(loss._value)
        walls.append(time.perf_counter() - t)
        losses.append(float(np.asarray(loss._value)))
        log(f"train step {i}: loss {losses[-1]:.4f}")
        if not np.isfinite(losses[-1]):
            raise RuntimeError(f"train step {i}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train loss did not fall: {losses}")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # set-up = build + compile + first step; not a benchmark
    log(f"train ok: {n_params / 1e6:.0f}M params, B={batch} S={seq}, "
        f"{steps} steps, set-up {time.perf_counter() - t0:.1f}s "
        f"(first step {walls[0]:.1f}s)")
    return losses


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def make_prompts(vocab, n, lo, hi, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    return [rng.integers(1, vocab, size=(int(n_tok),)).astype(np.int32)
            for n_tok in lens]


def drain(handle, new_tokens):
    """Stream one request to its end and return its tokens: it must run
    to its length budget (no EOS id is set), and the streamed tokens must
    equal the terminal result."""
    streamed = list(handle)
    res = handle.result(timeout=600)
    if res.finish_reason != "length" or len(res.token_ids) != new_tokens:
        raise RuntimeError(
            f"request {res.request_id}: finish_reason="
            f"{res.finish_reason!r} with {len(res.token_ids)} tokens, "
            f"expected 'length' with {new_tokens}")
    if [int(t) for t in streamed] != [int(t) for t in res.token_ids]:
        raise RuntimeError(
            f"request {res.request_id}: streamed tokens differ from the "
            f"terminal result")
    return [int(t) for t in res.token_ids]


def check_pool_clean(engine):
    """Pool invariants after a serve: nothing fenced, nothing parked."""
    if engine._write_fence != {} or engine._quarantine != set():
        raise RuntimeError(
            f"pool not clean after serve: write_fence="
            f"{engine._write_fence} quarantine={engine._quarantine}")


def serve_through_server(engine, prompts, new_tokens, pipeline_depth=3):
    """Submit every prompt to an ``AsyncLLMServer`` over ``engine``, stream
    each handle to its end, and return the greedy token lists."""
    from paddle_tpu.serving import AsyncLLMServer

    with AsyncLLMServer(engine, max_queue_size=len(prompts) + 1,
                        pipeline_depth=pipeline_depth) as server:
        handles = [server.submit(p, max_new_tokens=new_tokens,
                                 temperature=0.0) for p in prompts]
        out = [drain(h, new_tokens) for h in handles]
    check_pool_clean(engine)
    return out


def greedy_margin_ulps(model, prompts, streams):
    """The greedy-parity bar. Teacher-force ``prompt ⊕ stream`` through the
    plain XLA forward of ``model`` (einsum attention, no Pallas, no cache)
    and return, per request, the worst margin by which an emitted token
    missed the reference's top logit, in bf16 ulps of that top logit.

    Why not token equality: the lm head runs in bf16, so logits near the
    top (|x| in [4, 8) at this vocab) sit on a 2^-5 grid and near-ties are
    common; two correct attention implementations (f32-accumulating kernel
    vs bf16 einsum) round differently, one flipped argmax changes every
    later token of that request, and equality then fails without a fault.
    A token that is the reference's argmax to within a few ulps is what
    greedy decoding can promise in bf16; a broken kernel (wrong block,
    wrong mask, garbage K/V) misses by hundreds of ulps. The margin is
    teacher-forced on the stream under test, so one flip cannot cascade."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.flags import flag_value, set_flags
    from paddle_tpu.core.tensor import Tensor, functional_mode, no_grad
    from paddle_tpu.jit.functional_call import (bind_state, collect_state,
                                                read_values)

    n_new = len(streams[0])
    width = max(len(p) for p in prompts) + n_new
    seqs = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        seqs[i, :len(p)] = p
        seqs[i, len(p):len(p) + n_new] = s
    # position t predicts token t+1: the generated tokens of request i are
    # predicted at positions len(p)-1 ... len(p)+n_new-2
    first = np.asarray([len(p) - 1 for p in prompts], np.int32)

    _, params, _, buffers = collect_state(model)
    state = params + buffers

    def margins(state_vals, ids, first):
        with functional_mode(), no_grad(), bind_state(state, state_vals):
            logits = model(Tensor(ids))._value.astype(jnp.float32)
        pos = first[:, None] + jnp.arange(n_new, dtype=jnp.int32)[None, :]
        rows = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
        nxt = jnp.take_along_axis(ids, pos + 1, axis=1)
        top = jnp.max(rows, axis=-1)
        got = jnp.take_along_axis(rows, nxt[:, :, None], axis=-1)[..., 0]
        # bf16: 8 significand bits -> ulp(x) = 2^(floor(log2|x|) - 7)
        ulp = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(top),
                                                      1e-30))) - 7.0)
        return jnp.max((top - got) / ulp, axis=1)

    was_training = model.training
    prev = flag_value("use_pallas_flash_attention")
    model.eval()
    set_flags({"use_pallas_flash_attention": False})
    try:
        out = jax.jit(margins)(read_values(state), jnp.asarray(seqs),
                               jnp.asarray(first))
        return [float(x) for x in np.asarray(out)]
    finally:
        set_flags({"use_pallas_flash_attention": prev})
        if was_training:
            model.train()


def require_greedy(label, model, prompts, streams):
    worst = max(greedy_margin_ulps(model, prompts, streams))
    log(f"{label}: worst greedy margin {worst:.2f} bf16 ulps of the "
        f"reference top logit (bar {MARGIN_ULPS})")
    if not worst <= MARGIN_ULPS:
        raise RuntimeError(
            f"{label}: an emitted token misses the reference argmax by "
            f"{worst:.1f} bf16 ulps (bar {MARGIN_ULPS})")


def agreement(a, b):
    """(requests with identical streams, tokens matched before the first
    divergence, total tokens) between two lists of greedy streams."""
    same = sum(x == y for x, y in zip(a, b))
    matched = sum(next((i for i, (p, q) in enumerate(zip(x, y)) if p != q),
                       len(x)) for x, y in zip(a, b))
    return same, matched, sum(len(x) for x in a)


def serve_phase(cfg, n_requests=12, prompt_lo=256, prompt_hi=512,
                new_tokens=64, engine_kw=SERVE_ENGINE, pipeline_depth=3):
    """Serve ``n_requests`` seeded prompts through the fused/paged engine
    behind AsyncLLMServer, then the same prompts through the default
    engine, and hold both to the greedy bar. Returns the two token sets."""
    from paddle_tpu.inference import LLMEngine

    t0 = time.perf_counter()
    model = build_model(cfg)
    model.eval()
    prompts = make_prompts(cfg.vocab_size, n_requests, prompt_lo, prompt_hi)
    engine = LLMEngine(model, **engine_kw)
    served = serve_through_server(engine, prompts, new_tokens,
                                  pipeline_depth)
    counts = {k: engine.stats[k] for k in (
        "steps", "fused_steps", "multi_steps", "prefill_tokens",
        "tokens_generated", "preemptions")}
    log(f"serve ok: {len(served)} requests x {new_tokens} tokens through "
        f"AsyncLLMServer(pipeline_depth={pipeline_depth}) over "
        f"{engine_kw}, set-up + serve {time.perf_counter() - t0:.1f}s; "
        f"engine counts {counts}")

    # the default engine (scheduler="legacy", cache_impl="dense"): still
    # the constructor default and the old headline cell
    default = LLMEngine(model, max_batch=engine_kw["max_batch"])
    ref = [[int(t) for t in r.token_ids]
           for r in default.generate(prompts, max_new_tokens=new_tokens)]
    same, matched, total = agreement(served, ref)
    log(f"fused/paged vs default engine: {same}/{len(prompts)} requests "
        f"token-identical, {matched}/{total} tokens before first "
        f"divergence")
    require_greedy("fused/paged engine", model, prompts, served)
    require_greedy("default engine", model, prompts, ref)
    return served, ref


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def release():
    """Collect what the finished phase left behind (its model, optimizer
    state and pools are locals of the phase function) and report what the
    first device still holds."""
    import jax
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    log(f"released: device 0 holds "
        f"{stats.get('bytes_in_use', 0) / 1e9:.2f} GB")


def replicas_phase(cfg, devices, prompts, new_tokens, engine_kw=SERVE_ENGINE):
    """One fused/paged replica per device behind ``ReplicaRouter``: each
    replica's weights and pools must live on its own device (asserted from
    the arrays), and every device must hold real bytes. Returns the greedy
    streams (the one-chip reference for the TP engine) and one replica's
    model (the reference forward for the greedy bar)."""
    import jax
    from paddle_tpu.device import tpu as tpu_dev
    from paddle_tpu.serving import AsyncLLMServer, ReplicaRouter
    from paddle_tpu.serving.cluster import tp_engine

    engines, models = [], []
    for dev in devices:
        model = build_model(cfg)
        model.eval()
        models.append(model)
        engines.append(tp_engine(model, tp=1, devices=[dev], **engine_kw))
    servers = [AsyncLLMServer(e, replica=i,
                              max_queue_size=len(prompts) + 1,
                              pipeline_depth=3)
               for i, e in enumerate(engines)]
    with ReplicaRouter(servers) as router:
        handles = [router.submit(p, max_new_tokens=new_tokens,
                                 temperature=0.0) for p in prompts]
        out = [drain(h, new_tokens) for h in handles]
        placed = [h.replica for h in handles]
    homes = set()
    for i, (eng, model, dev) in enumerate(zip(engines, models, devices)):
        check_pool_clean(eng)
        arrays = [p._value for p in model.parameters()] + \
            jax.tree_util.tree_leaves([eng._k, eng._v, eng._logits,
                                       eng._lens])
        on = set().union(*(a.devices() for a in arrays))
        if on != {dev}:
            raise RuntimeError(f"replica {i}: arrays on {on}, expected "
                               f"{{{dev}}}")
        homes |= on
        if dev.platform == "tpu":
            in_use = tpu_dev.memory_stats(dev).get("bytes_in_use", 0)
            log(f"replica {i}: {dev} holds {in_use / 1e9:.2f} GB, served "
                f"{placed.count(i)} request(s)")
            if in_use < 1e9:
                raise RuntimeError(
                    f"replica {i}: {dev} holds {in_use} bytes — its "
                    f"weights are somewhere else")
    if len(homes) != len(devices):
        raise RuntimeError(f"replicas share devices: {homes}")
    if len(set(placed)) != len(devices):
        raise RuntimeError(f"router left a replica idle: placements "
                           f"{placed}")
    log(f"replicas ok: {len(devices)} replicas on {len(homes)} distinct "
        f"devices, placements {placed}")
    return out, models[0]


def tp_train_place(mesh):
    """``place`` hook for :func:`train_phase`: Megatron TP layout of the
    llama weights over ``mesh`` (the examples/train_llama_tp.py shape)."""
    import jax
    from jax.sharding import NamedSharding
    from paddle_tpu.models.llama import llama_tp_spec

    def place(model):
        for name, p in model.named_parameters():
            p._value = jax.device_put(
                p._value, NamedSharding(mesh, llama_tp_spec(name)))
    return place


def multichip(cfg_kw=FLAGSHIP, n=4, batch=4, seq=2048, n_requests=8,
              prompt_lo=256, prompt_hi=512, new_tokens=64,
              engine_kw=SERVE_ENGINE):
    """Items (a)-(d) on ``n`` local devices, one process driving them all.
    Returns ``{item: "passed"}``; any failure raises."""
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.serving.cluster import tp_engine

    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(f"multichip needs {n} devices, found "
                           f"{len(jax.devices())}")
    results = {}

    # (a) the hybrid dp x pp x tp dryrun on the chips
    import __graft_entry__ as entry
    entry.dryrun_multichip(n)
    results["a_dryrun"] = "passed"

    # (d) the train phase, weights laid out by llama_tp_spec over n chips
    train_cfg = LlamaConfig(max_position_embeddings=seq, use_recompute=True,
                            **cfg_kw)
    with lowered_programs() as read:
        train_phase(train_cfg, batch, seq,
                    place=tp_train_place(Mesh(np.asarray(devices), ("mp",))))
        calls = mosaic_calls(read())
    if devices[0].platform == "tpu":
        require_kernels(calls, TRAIN_KERNELS)
    results["d_tp_train"] = "passed"
    release()

    # (c) n one-chip replicas behind the router, each on its own device
    serve_cfg = LlamaConfig(max_position_embeddings=1024, **cfg_kw)
    prompts = make_prompts(serve_cfg.vocab_size, n_requests, prompt_lo,
                           prompt_hi)
    one_chip, ref_model = replicas_phase(serve_cfg, devices, prompts,
                                         new_tokens, engine_kw)
    require_greedy("one-chip replicas", ref_model, prompts, one_chip)
    results["c_replicas"] = "passed"

    # (b) the serve phase through a TP engine over all n chips
    tp_model = build_model(serve_cfg)
    tp_model.eval()
    with lowered_programs() as read:
        eng = tp_engine(tp_model, tp=n, devices=devices, **engine_kw)
        tp_tokens = serve_through_server(eng, prompts, new_tokens)
        calls = mosaic_calls(read())
    if devices[0].platform == "tpu":
        require_kernels(calls, SERVE_KERNELS)
    same, matched, total = agreement(tp_tokens, one_chip)
    log(f"tp={n} engine vs one-chip engine: {same}/{len(prompts)} requests "
        f"token-identical, {matched}/{total} tokens before first "
        f"divergence")
    require_greedy(f"tp={n} engine", ref_model, prompts, tp_tokens)
    results["b_tp_serve"] = "passed"
    return results


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def one_chip():
    from paddle_tpu.models import LlamaConfig

    # B=6 x S=2048: the shape PR 22 brought up on the chip
    train_cfg = LlamaConfig(max_position_embeddings=2048, use_recompute=True,
                            **FLAGSHIP)
    with lowered_programs() as read:
        train_phase(train_cfg, batch=6, seq=2048, steps=4)
        calls = mosaic_calls(read())
    require_kernels(calls, TRAIN_KERNELS)
    # the ~12 GB of train state must be gone before the server builds
    release()

    serve_cfg = LlamaConfig(max_position_embeddings=1024, **FLAGSHIP)
    with lowered_programs() as read:
        serve_phase(serve_cfg)
        calls = mosaic_calls(read())
    require_kernels(calls, SERVE_KERNELS)


def main(argv):
    t0 = time.perf_counter()
    import jax
    import paddle_tpu  # noqa: F401  (places the compile cache)

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"jax {jax.__version__}, backend {jax.default_backend()}, device "
        f"{dev.device_kind} x{len(jax.devices())}, compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    if dev.platform != "tpu":
        log(f"no TPU: jax found platform {dev.platform!r} "
            f"({dev.device_kind}); this script has no CPU mode")
        return 2
    mode = argv[1] if len(argv) > 1 else "one_chip"
    if mode == "multichip":
        for item, outcome in multichip().items():
            log(f"multichip {item}: {outcome}")
    elif mode == "one_chip":
        one_chip()
    else:
        log(f"unknown mode {mode!r} (expected nothing or 'multichip')")
        return 2
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
