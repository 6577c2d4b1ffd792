"""Runs one serving cell: the engine behind ``AsyncLLMServer`` under an
open (scheduled arrivals) or a closed (waiting clients) loop, from one
client thread that submits and polls. Times are the client's: a token is
seen when the poll finds it on the handle."""
from __future__ import annotations

import gc
import time

import numpy as np

from . import common, traffic

POLL_S = 0.001


class Rec:
    """One request as the client saw it."""
    __slots__ = ("req", "handle", "t_submit", "seen", "t_first", "t_last",
                 "events", "done", "reason", "in_window")

    def __init__(self, req, handle, t_submit, in_window):
        self.req, self.handle, self.t_submit = req, handle, t_submit
        self.seen, self.t_first, self.t_last = 0, None, None
        #: [(time, tokens seen before, tokens newly seen)]
        self.events = []
        self.done, self.reason, self.in_window = False, None, in_window

    @property
    def n_prompt(self):
        return len(self.req.prompt)


class Ended:
    """What is kept of a handle once the server is gone."""
    __slots__ = ("request_id", "emitted")

    def __init__(self, request_id, emitted):
        self.request_id, self.emitted = request_id, emitted


class Client:
    """Submits and polls from the one thread that calls it."""

    def __init__(self, server, clock):
        self.server, self.clock = server, clock
        self.active, self.finished = [], []

    def submit(self, req, in_window):
        import jax
        with jax.profiler.TraceAnnotation("bench:submit"):
            t = self.clock()
            handle = self.server.submit(req.prompt,
                                        max_new_tokens=req.max_new,
                                        temperature=0.0, block=False)
        rec = Rec(req, handle, t, in_window)
        self.active.append(rec)
        return rec

    def poll(self):
        """Look at every open handle once; returns the records that ended."""
        ended = []
        now = self.clock()
        for rec in self.active:
            n = len(rec.handle.emitted)
            if n > rec.seen:
                if rec.t_first is None:
                    rec.t_first = now
                rec.t_last = now
                rec.events.append((now, rec.seen, n - rec.seen))
                rec.seen = n
            if rec.handle.done and len(rec.handle.emitted) == rec.seen:
                rec.done = True
                res = rec.handle.result_obj
                rec.reason = getattr(res, "finish_reason", None)
                ended.append(rec)
        if ended:
            self.active = [r for r in self.active if not r.done]
            self.finished.extend(ended)
        return ended

    def wait(self, until):
        import jax
        with jax.profiler.TraceAnnotation("bench:wait_for_token"):
            time.sleep(max(0.0, min(POLL_S, until - self.clock())))


def _window_tokens(records, w0, w1):
    """Prompt and output tokens the clients got inside [w0, w1): an output
    token when it is seen, a prompt's tokens evenly over the time from its
    submission to its first token (the stretch in which it was prefilled;
    credited whole at the first token, one document would move a window
    of some tens by several percent as it crosses the window's edge).
    Returns that count, the count with prompts whole at their first
    token, and the sorted times inside the window at which tokens were
    seen."""
    tokens, at_first, seen_at = 0.0, 0, []
    for r in records:
        for t, before, new in r.events:
            if w0 <= t < w1:
                tokens += new
                at_first += new + (r.n_prompt if before == 0 else 0)
                seen_at.append(t)
        if r.t_first is not None and r.t_first > r.t_submit:
            inside = min(r.t_first, w1) - max(r.t_submit, w0)
            tokens += r.n_prompt * max(0.0, inside) \
                / (r.t_first - r.t_submit)
    return tokens, at_first, sorted(seen_at)


def _engine(cfg, model, mesh):
    from paddle_tpu.inference import LLMEngine
    kw = dict(cfg["engine"])
    if mesh is not None:
        from paddle_tpu.serving.cluster import tp_engine
        return tp_engine(model, mesh=mesh, shard_weights=False, **kw)
    return LLMEngine(model, **kw)


def _prefill_positions(engine):
    """{request id: prompt tokens whose prefill has been dispatched} of
    the requests resident in a slot now."""
    out = {}
    for slot in list(engine.slots):
        if slot is not None:
            out[slot.req.request_id] = int(slot.prefill_pos)
    return out


def _warm(client, mix, vocab, capacity):
    """Every program the traffic will use, compiled before any clock that
    counts: a prompt of several chunks and a short one, decoded past a few
    readout strides, then the longest request the mix can send."""
    rng = np.random.default_rng(0)
    longest = min(int(mix["prompt"]["max"]),
                  capacity - int(mix["output"]["max"]))
    for n_p, n_o in ((3 * 64 + 5, 9), (17, 6), (longest, 5)):
        req = traffic.Request(-1, 0.0, rng.integers(
            1, vocab, size=n_p).astype(np.int32), n_o)
        client.submit(req, False)
    deadline = client.clock() + 1500
    while client.active:
        client.poll()
        if client.clock() > deadline:
            raise RuntimeError("warm-up did not finish")
        time.sleep(POLL_S)
    bad = [r.reason for r in client.finished if r.reason != "length"]
    if bad:
        raise RuntimeError(f"warm-up requests ended with {bad}")
    client.finished.clear()


class Session:
    """The system under test, built and warmed once: model, engine, server
    and the one client. ``drive`` runs one stretch of traffic over it."""

    def __init__(self, cell, seed, devices, reference, log=print):
        from paddle_tpu.serving import AsyncLLMServer

        cfg = cell.config
        self.cell, self.seed, self.devices, self.log = cell, seed, devices, log
        self.clock = time.perf_counter
        self.counter = common.CompileCounter()
        mesh = None
        if len(devices) > 1:
            from paddle_tpu.serving.cluster import tp_serving_mesh
            mesh = tp_serving_mesh(len(devices), list(devices))
        self.model = common.build_model(cfg, seed, reference, mesh)
        self.model.eval()
        self.engine = _engine(cfg, self.model, mesh)
        srv = cfg["server"]
        self.server = AsyncLLMServer(
            self.engine, max_queue_size=int(srv["max_queue_size"]),
            pipeline_depth=int(srv["pipeline_depth"]))
        self.server.start()
        self.client = Client(self.server, self.clock)
        self.vocab = int(cfg["vocab_size"])
        _warm(self.client, cell.traffic, self.vocab, self.engine.capacity)

    def close(self):
        self.server.stop(drain=False, timeout=60)
        self.server = self.engine = self.model = self.client = None
        gc.collect()

    def drive(self, mix, seed, seconds, trace_spec=None, t_start=None):
        """``warm_s`` seconds of the mix, then the window of ``seconds``,
        then the drain. Returns the window's measurements."""
        clock, client, engine, log = self.clock, self.client, self.engine, \
            self.log
        client.finished.clear()
        warm_s, drain_s = float(mix["warm_s"]), float(mix["drain_s"])
        t0 = clock()                      # the traffic's own zero
        w0, w1 = t0 + warm_s, t0 + warm_s + seconds
        stretch = None
        if trace_spec:
            stretch = common.TracedStretch(
                self.cell.trace_dir, w0 + float(trace_spec["start_s"]),
                min(float(trace_spec["seconds"]),
                    max(0.5, seconds - float(trace_spec["start_s"]))),
                clock, probe=lambda: _prefill_positions(engine))
            stretch.start()

        closed = mix["kind"] == "closed_clients"
        if closed:
            lengths = traffic.Lengths(mix, seed, self.vocab)
            pending = []
            for c in range(int(mix["clients"])):
                client.submit(lengths.next(clock() - t0, c), False)
        elif mix["kind"] == "open_poisson":
            pending = traffic.open_schedule(mix, seed, self.vocab,
                                            warm_s, seconds)
            pending.reverse()             # pop() takes the next due
        else:
            raise ValueError(f"serve runner: unknown traffic kind "
                             f"{mix['kind']!r}")

        stats0 = stats1 = None
        late = []
        while True:
            now = clock()
            if stats0 is None and now >= w0:
                stats0, compiled0 = dict(engine.stats), \
                    self.counter.snapshot()
                t_w0 = now
            if stats1 is None and now >= w1:
                stats1, compiled1 = dict(engine.stats), \
                    self.counter.snapshot()
                t_w1 = now
            while pending and t0 + pending[-1].due <= now:
                req = pending.pop()
                late.append(now - (t0 + req.due))
                client.submit(req, w0 <= t0 + req.due < w1)
            for rec in client.poll():
                if closed and clock() < w1:
                    t = clock()
                    client.submit(lengths.next(t - t0, rec.req.client),
                                  w0 <= t < w1)
            now = clock()
            if now >= w1 and not pending:
                if not any(r.in_window for r in client.active) \
                        or now >= w1 + drain_s:
                    break
            next_due = t0 + pending[-1].due if pending else now + POLL_S
            client.wait(min(next_due, now + POLL_S))
        if stats1 is None:
            raise RuntimeError("the window never closed")
        unfinished = [r for r in client.active if r.in_window]
        if stretch is not None:
            stretch.join()
        if closed:
            # the clients' last requests were sent before the window shut:
            # let them end, so that the next stretch starts from idle
            deadline = clock() + drain_s
            while client.active and clock() < deadline:
                client.poll()
                time.sleep(POLL_S)

        # ---- what the client saw ----------------------------------------
        sample = [r for r in client.finished if r.in_window]
        attempted = len(sample) + len(unfinished)
        failed_recs = [r for r in sample
                       if r.reason != "length" or r.seen != r.req.max_new]
        failed = len(failed_recs) + len(unfinished)
        limit_ms = (seconds + drain_s) * 1e3
        ok = [r for r in sample if r not in failed_recs]
        due = (lambda r: r.t_submit) if closed else \
            (lambda r: t0 + r.req.due)
        ttft = [(r.t_first - due(r)) * 1e3 for r in ok] + [limit_ms] * failed
        tpot = [(r.t_last - r.t_first) / (r.seen - 1) * 1e3
                for r in ok if r.seen > 1] + [limit_ms] * failed
        records = client.finished + client.active
        tokens, at_first, seen_at = _window_tokens(records, t_w0, t_w1)
        window = t_w1 - t_w0
        values = {"serve_tok_s": tokens / window}
        silence = max(np.diff(seen_at), default=0.0)
        if t_start is not None:
            values["setup_s"] = w0 - t_start
        for name, xs in (("ttft", ttft), ("tpot", tpot)):
            if xs:
                values[f"{name}_p95_ms"] = float(np.percentile(xs, 95))
                values[f"{name}_p50_ms"] = float(np.percentile(xs, 50))
        lowered = compiled1[0] - compiled0[0]
        compile_s = compiled1[1] - compiled0[1]
        nan = float("nan")
        log(f"[serve] window {window:.3f}s: {attempted} requests due, "
            f"{failed} failed ({len(unfinished)} unfinished at the drain "
            f"limit), {tokens:.1f} tokens ({at_first} with each prompt "
            f"counted whole at its first token); longest silence "
            f"{silence * 1e3:.0f} ms; ttft p50/p95 "
            f"{values.get('ttft_p50_ms', nan):.1f}/"
            f"{values.get('ttft_p95_ms', nan):.1f} ms, tpot p50/p95 "
            f"{values.get('tpot_p50_ms', nan):.2f}/"
            f"{values.get('tpot_p95_ms', nan):.2f} ms, "
            f"{values['serve_tok_s']:.1f} tok/s; generator late p95 "
            f"{(np.percentile(late, 95) * 1e3 if late else 0.0):.2f} ms; "
            f"engine steps {stats1['steps'] - stats0['steps']}, "
            f"preemptions {stats1['preemptions'] - stats0['preemptions']}")
        log(f"[serve] programs lowered inside the window: {lowered} "
            f"({compile_s:.3f}s compiling)")
        if compile_s > 0.5:
            raise RuntimeError(
                f"{lowered} programs were compiled inside the measured "
                f"window ({compile_s:.2f}s): the warm-up missed a shape")
        return {"values": values, "attempted": attempted, "failed": failed,
                "ok": ok, "bad": failed_recs, "records": records,
                "stats0": stats0, "stats1": stats1, "stretch": stretch,
                "window_s": window}


def run(cell, seed, seconds, trace, t_start, devices, reference,
        control=None, log=print, tamper=None):
    """Returns a dict: correct, attempted, failed, values (every number
    the run can give), ctx (for the per-layer readers), memory_peak,
    checks."""
    session = Session(cell, seed, devices, reference, log)
    log(f"[serve] built and warmed in {session.clock() - t_start:.1f}s; "
        f"pool {getattr(session.engine, 'n_blocks', None)} blocks of "
        f"{getattr(session.engine, 'block_size', None)}")
    m = session.drive(cell.traffic, seed, seconds,
                      cell.file["trace"] if trace else None, t_start)
    memory_peak = common.memory_peak_bytes(devices)
    ctx = {"kind": "serve", "cell": cell, "stats0": m["stats0"],
           "stats1": m["stats1"], "records": m["records"],
           "stretch": m["stretch"], "chips": len(devices),
           "layers_here": int(cell.config["num_hidden_layers"]),
           "window_s": m["window_s"]}
    for r in m["records"]:
        # the handle holds the server, the server the engine and its pools
        r.handle = Ended(r.handle.request_id, list(r.handle.emitted))
    served = [(r.req.prompt, np.asarray(r.handle.emitted, np.int32))
              for r in m["ok"]]
    session.close()
    del session
    gc.collect()
    left = (devices[0].memory_stats() or {}).get("bytes_in_use", 0)
    log(f"[serve] program freed: device 0 still holds {left / 1e9:.2f} GB")
    checks, correct = check(cell, seed, served, reference, control,
                            devices[0], log, tamper)
    correct = correct and not m["bad"]
    return {"correct": correct, "attempted": m["attempted"],
            "failed": m["failed"], "values": m["values"], "ctx": ctx,
            "memory_peak": memory_peak, "checks": checks}


def check(cell, seed, served, reference, control, device, log, tamper=None):
    """A sample of the finished requests, drawn from the seed, the longest
    among them, under the reference: the widest and the mean gap by which
    a served token's logit lies below the reference's best."""
    spec = cell.file["check"]
    if not served:
        log("[check] no finished request to compare: not correct")
        return {}, False
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 9])
    order = sorted(range(len(served)),
                   key=lambda i: -(len(served[i][0]) + len(served[i][1])))
    rest = order[1:]
    pick = [order[0]] + [rest[i] for i in rng.permutation(len(rest))
                         [:int(spec["sample"]) - 1]]
    sample = [served[i] for i in pick]
    if tamper is not None:
        sample = tamper(sample)
    t = time.perf_counter()
    out = reference.served_gaps(seed, cell.config, sample, control, device)
    gaps = np.concatenate(out["gaps"])
    numbers = {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}
    correct = True
    for name, limit in spec["limits"].items():   # the numbers compared
        value, limit = numbers[name], float(limit)
        correct = correct and value <= limit
        log(f"[check] {name} = {value:.6f} (limit {limit}) over "
            f"{len(gaps)} served tokens of {len(sample)} requests, "
            f"longest {len(sample[0][0])}+{len(sample[0][1])}; reference "
            f"logit std {out['logit_std']:.4f}")
    if out["control_gaps"] is not None:
        cg = np.concatenate(out["control_gaps"])
        numbers["control_gap_max"] = float(cg.max())
        numbers["control_gap_mean"] = float(cg.mean())
        log(f"[check] {control} control: gap_max = {cg.max():.6f}, gap_mean = "
            f"{cg.mean():.6f} (has to pass a limit to be caught)")
    log(f"[check] reference took {time.perf_counter() - t:.1f}s")
    return numbers, correct
