"""Runs one training cell: ``TrainStep`` over the seeded model, a fresh
seeded batch put on the device every step, the loss read every step. The
plain reference follows the first steps AFTER the window, once the
program's state is freed and its memory peak read, so it fits and the
peak stays the program's."""
from __future__ import annotations

import gc
import shutil
import statistics
import time

import numpy as np

from . import common, weights as W
from .trace import WINDOW

CHECK_STEPS = 3


def batch_for(seed, step, batch, seq, vocab):
    """Step ``step``'s packed rows: every row differs, every step differs.
    ids are the first ``seq`` tokens of each row of ``seq + 1``, labels the
    last ``seq``."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 7, step])
    rows = rng.integers(0, vocab, size=(batch, seq + 1)).astype(np.int32)
    return rows[:, :-1], rows[:, 1:]


def hyper(cfg):
    o = cfg["optimizer"]
    return (float(o["lr"]), float(o["beta1"]), float(o["beta2"]),
            float(o["eps"]), float(o["weight_decay"]))


def worst_leaf_gap(mine, ref):
    """Worst leaf by |program's norm - reference's norm|, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    floor = statistics.median(ref.values())
    worst, name = 0.0, None
    for k, r in ref.items():
        gap = abs(mine[k] - r) / max(r, floor)
        if gap > worst:
            worst, name = gap, k
    return worst, name


def compare(numbers, ref, limits, log):
    """The numbers compared, each beside its limit. Returns (checks,
    correct)."""
    checks = {}
    for i, (mine, theirs) in enumerate(zip(numbers["losses"],
                                           ref["losses"])):
        checks[f"loss_gap_step{i + 1}"] = abs(mine - theirs) / abs(theirs)
    checks["grad_norm_gap"], g_leaf = worst_leaf_gap(
        numbers["grad_norms"], ref["grad_norms"])
    checks["delta_norm_gap"], d_leaf = worst_leaf_gap(
        numbers["delta_norms"], ref["delta_norms"])
    correct = True
    for name, value in checks.items():
        key = "loss_gap" if name.startswith("loss_gap") else name
        limit = float(limits[key])
        ok = value <= limit and np.isfinite(value)
        correct = correct and bool(ok)
        log(f"[check] {name} = {value:.6f} (limit {limit})"
            + (f" worst leaf {g_leaf}" if name == "grad_norm_gap" else "")
            + (f" worst leaf {d_leaf}" if name == "delta_norm_gap" else ""))
    return checks, correct


def run(cell, seed, seconds, trace, t_start, devices, reference,
        control=None, log=print, tamper=None):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit.api import TrainStep

    cfg = cell.config
    clock = time.perf_counter
    counter = common.CompileCounter()
    tr = cfg["train"]
    batch, seq, vocab = int(tr["batch"]), int(tr["seq"]), \
        int(cfg["vocab_size"])
    hp = hyper(cfg)

    # ---- the program: one object, driven through its first steps and ----
    # ---- handed to the window ------------------------------------------
    model = common.build_model(cfg, seed, reference)
    model.train()
    params = dict(model.named_parameters())
    optimizer = opt.AdamW(learning_rate=hp[0], beta1=hp[1], beta2=hp[2],
                          epsilon=hp[3], parameters=model.parameters(),
                          weight_decay=hp[4], multi_precision=True)
    step = TrainStep(model, lambda m, ids, lbl: m(ids, labels=lbl)[0],
                     optimizer)
    if tamper is not None:
        step = tamper(step)

    def one(i):
        """The window's own call and feed: a fresh batch from the host,
        one step, the loss read back."""
        with jax.profiler.TraceAnnotation("bench:put_batch"):
            ids, labels = batch_for(seed, i, batch, seq, vocab)
            ids_t = paddle.to_tensor(ids, dtype="int32")
            lbl_t = paddle.to_tensor(labels, dtype="int32")
        with jax.profiler.TraceAnnotation("bench:step_and_read_loss"):
            loss = step(ids_t, lbl_t)
            return float(np.asarray(jax.block_until_ready(loss._value)))

    sq = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])
    names = list(params)
    numbers = {"losses": []}
    for i in range(CHECK_STEPS):
        numbers["losses"].append(one(i))
        slots = [optimizer._slots[id(params[n])] for n in names]
        if i == 0:
            # the first gradient as the optimizer got it: after one step
            # from zero moments, moment1 = (1 - beta1) * g
            m1 = [float(v) / (1 - hp[1])
                  for v in sq([s["moment1"] for s in slots])]
            numbers["grad_norms"] = dict(zip(names, m1))
    # the parameters' change after the checked steps: fp32 master weights
    # against the seeded start, made again leaf by leaf
    delta = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a - b.astype(jnp.float32)))))
    numbers["delta_norms"] = {}
    for n in names:
        start = W.make(seed, [(n, tuple(params[n]._value.shape))])[0]
        master = optimizer._slots[id(params[n])]["master_weight"]
        numbers["delta_norms"][n] = float(delta(master, start))
    del start
    warm = int(tr["warm_steps"])
    for i in range(CHECK_STEPS, CHECK_STEPS + warm):
        one(i)
    log(f"[train] built and warmed in {clock() - t_start:.1f}s")

    # ---- the window -----------------------------------------------------
    compiled0 = counter.snapshot()
    traced = None
    if trace:
        t_cfg = cell.file["trace"]
        traced = (int(t_cfg["start_step"]), int(t_cfg["steps"]))
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
    w0 = clock()
    setup_s = w0 - t_start
    n, losses, step_s, ann = 0, [], [], None
    i = CHECK_STEPS + warm
    while True:
        if traced and n == traced[0]:
            o = jax.profiler.ProfileOptions()
            o.python_tracer_level, o.host_tracer_level = 0, 1
            jax.profiler.start_trace(cell.trace_dir, profiler_options=o)
            ann = jax.profiler.TraceAnnotation(WINDOW)
            ann.__enter__()
        t_step = clock()
        losses.append(one(i))
        step_s.append(clock() - t_step)
        i, n = i + 1, n + 1
        if ann is not None and n == traced[0] + traced[1]:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            ann = None
        if clock() - w0 >= seconds and ann is None:
            break
    window = clock() - w0
    compiled1 = counter.snapshot()
    memory_peak = common.memory_peak_bytes(devices)
    tok_s = n * batch * seq / window / len(devices)
    lowered = compiled1[0] - compiled0[0]
    compile_s = compiled1[1] - compiled0[1]
    log(f"[train] window {window:.3f}s: {n} steps of {batch}x{seq}, "
        f"{tok_s:.1f} tokens/s per chip, {window / n * 1e3:.2f} ms per "
        f"step; loss first/last {losses[0]:.4f}/{losses[-1]:.4f}")
    # where a slow run lost its time: in one step, or in all of them
    mid = statistics.median(step_s)
    slow = [(k, 1e3 * s) for k, s in enumerate(step_s) if s > 1.2 * mid]
    log(f"[train] step median {mid * 1e3:.2f} ms, slowest "
        f"{max(step_s) * 1e3:.2f} ms; {len(slow)} steps over 1.2 x the "
        f"median: {[(k, round(ms)) for k, ms in slow[:8]]}")
    log(f"[train] programs lowered inside the window: {lowered} "
        f"({compile_s:.3f}s compiling)")
    if compile_s > 0.5:
        raise RuntimeError(f"{lowered} programs were compiled inside the "
                           f"measured window ({compile_s:.2f}s)")
    bad = [x for x in losses if not np.isfinite(x)]

    # ---- correct: the first steps under the plain reference, on a chip --
    # ---- the program has left ---------------------------------------------
    del step, optimizer, model, params, slots, master
    gc.collect()
    first = [batch_for(seed, k, batch, seq, vocab)
             for k in range(CHECK_STEPS)]
    t_ref = clock()
    ref = reference.train_steps(seed, cfg, first, hp)
    log(f"[check] reference followed {CHECK_STEPS} steps in "
        f"{clock() - t_ref:.1f}s: losses {ref['losses']}")
    limits = cell.file["check"]["limits"]
    checks, correct = compare(numbers, ref, limits, log)
    if control:
        ctl = reference.train_steps(seed, cfg, first, hp, control)
        cchecks, _ = compare(ctl, ref, limits, lambda s: log(
            s.replace("[check]", f"[check] {control} control:")))
        checks.update({f"control_{k}": v for k, v in cchecks.items()})
    correct = correct and not bad
    ctx = {"kind": "train", "cell": cell, "stretch": None,
           "traced_steps": traced[1] if traced else 0, "batch": batch,
           "seq": seq, "chips": len(devices), "window_s": window,
           "layers_here": int(cfg["num_hidden_layers"]), "steps": n}
    return {"correct": correct, "attempted": n, "failed": len(bad),
            "values": {"setup_s": setup_s, "train_tok_s": tok_s},
            "ctx": ctx, "memory_peak": memory_peak, "checks": checks}
