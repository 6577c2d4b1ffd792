"""Run a cell's sets the way the driver's check does: each run a process
of its own (this parent never touches jax, so the chip is the child's),
the seeds of one set each different, the sets with the same seeds. Prints
each run's result line and the compared numbers, then for each metric the
spread of each set (distance between the quartiles over the median, as
``statistics.quantiles(values, n=4)`` gives them) — the bound is about
five times the widest. Appends every run to
``chiprun_out/sets_<workload>.jsonl``.

    python3 benchmark/tools/sets.py --workload doc_batch \\
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 50 [--trace 1]
        [--control int8]
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one(workload, seed, seconds, trace, control):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    if control:
        cmd += ["--control", control]
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                       stderr=subprocess.STDOUT, text=True)
    lines = [x for x in p.stdout.splitlines() if x.strip()]
    rec = {"workload": workload, "seed": seed, "seconds": seconds,
           "trace": trace, "control": control, "rc": p.returncode,
           "wall_s": time.perf_counter() - t,
           "checks": [x for x in lines if x.startswith("[check]")],
           "notes": [x for x in lines if x.startswith(
               ("[serve]", "[train]", "[trace]"))]}
    try:
        rec["result"] = json.loads(lines[-1])
    except (ValueError, IndexError):
        rec["result"] = None
        rec["tail"] = lines[-25:]
    return rec


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", default="")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(ROOT, "chiprun_out", f"sets_{a.workload}.jsonl")
    sets = []
    for k in range(a.sets):
        runs = []
        for seed in seeds:
            rec = one(a.workload, seed, a.seconds, a.trace, a.control)
            rec["set"] = k
            with open(out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            for line in rec["checks"] + rec["notes"][-3:]:
                print("   ", line[:300])
            print(f"set {k} seed {seed} rc {rec['rc']} "
                  f"{rec['wall_s']:.0f}s:", json.dumps(rec["result"])[:1200]
                  if rec["result"] else rec.get("tail"), flush=True)
            runs.append(rec)
        sets.append(runs)
    names = sorted({n for runs in sets for r in runs if r["result"]
                    for n in r["result"]["metrics"]})
    for n in names:
        row = []
        for runs in sets:
            vals = [r["result"]["metrics"][n]["value"] for r in runs
                    if r["result"] and n in r["result"]["metrics"]]
            if n == "setup_s":
                vals = vals[1:]         # the first run of a side compiles
            if len(vals) >= 2:
                row.append((statistics.median(vals), spread(vals)))
        print(f"{n}: " + "; ".join(
            f"median {m:.6g} spread {100 * s:.2f}%" for m, s in row))
    bad = [r for runs in sets for r in runs
           if not r["result"] or not r["result"]["correct"]
           or r["result"]["failed"]]
    print(f"{len(bad)} run(s) without a result, not correct or with "
          f"failures")
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
