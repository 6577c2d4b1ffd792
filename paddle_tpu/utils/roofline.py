"""Per-fusion roofline analysis for compiled TPU programs.

The reference ships a tuned conv library with layout+algorithm autotuning
(paddle/phi/kernels/gpudnn/conv_kernel.cu, phi/kernels/autotune/
auto_tune_base.h); the TPU-native counterpart question is whether XLA's
conv fusions run at THIS chip's roofline. This module answers it with
measurement, not assertion:

  1. parse the optimized HLO of a compiled step — per entry-level
     instruction: FLOPs (dots/convs, recursively through fused
     computations) and HBM bytes (operand + result sizes);
  2. run the step under ``jax.profiler.trace`` and read the DEVICE-track
     durations per instruction (host-side timing includes dispatch;
     the device track is exact);
  3. join the two: each fusion's achieved FLOP/s and B/s against its own
     roofline bound  t_bound = max(flops/peak, bytes/bw_measured).

Used by ``BENCH_MODEL=conv_roofline`` (bench.py) to regenerate
``docs/artifacts/conv_roofline_proof.json`` and by
tests/test_roofline_tool.py for the parser contract.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import tempfile

__all__ = [
    "parse_hlo_costs", "profile_device_events", "roofline_table",
]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string; tuples sum their elements."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    return [int(d) for d in m.group(2).split(",") if d] if m else []


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*?)\)\s*->\s*(.+?)\s*\{\s*$")
_PARAM_RE = re.compile(r"%?([\w.\-]+)\s*:\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\]))")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_DTYPE_SHAPE_RE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")
_OP_OPEN_RE = re.compile(r"([\w\-]+)\(")


def _match_depth(s: str, i: int) -> int:
    """Index just past the bracket group opening at s[i] ('(' or '{'),
    counting nested brackets of both kinds (HLO layouts nest parens
    inside braces: bf16[8,...]{3,2,1,0:T(8,128)(2,1)S(1)})."""
    depth = 0
    opens, closes = "({", ")}"
    while i < len(s):
        if s[i] in opens:
            depth += 1
        elif s[i] in closes:
            depth -= 1
            if depth == 0:
                return i + 1
        i += 1
    return len(s)


def _split_instr(line: str):
    """'%name = TYPE op(operands), attrs' -> (name, type, op, rest).
    TYPE may be a tuple of layouted shapes — regexes can't match its
    nested brackets, which is exactly how multi-output fusions (conv+BN
    stats) went uncosted in the first cut of this parser."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%"):
        return None
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[1:eq]
    rest = s[eq + 3:]
    if not rest:
        return None
    if rest[0] == "(":
        i = _match_depth(rest, 0)
    else:
        m = _DTYPE_SHAPE_RE.match(rest)
        if not m:
            return None
        i = m.end()
    while i < len(rest) and rest[i] == "{":
        i = _match_depth(rest, i)
    type_str = rest[:i]
    tail = rest[i:].lstrip()
    m = _OP_OPEN_RE.match(tail)
    if not m:
        return None
    return name, type_str, m.group(1), tail[m.end():]


def _parse_computations(hlo_text: str):
    """-> {comp_name: {"params": {name: type}, "result": type,
    "instrs": [(name, type, op, rest)], "is_entry": bool}}"""
    comps = {}
    cur = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m and ("->" in line):
                name, params, result = m.group(1), m.group(2), m.group(3)
                cur = {"params": dict(
                            (n, t) for n, t in _PARAM_RE.findall(params)),
                       "result": result, "instrs": [],
                       "is_entry": line.startswith("ENTRY")}
                comps[name] = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        parsed = _split_instr(line)
        if parsed:
            cur["instrs"].append(parsed)
    return comps


def _win_attr(window: str, key: str, nd: int, default: int):
    m = re.search(rf"{key}=([0-9x_\-]+)", window)
    fallback = [(default, default)] * nd if key == "pad" else [default] * nd
    if not m:
        return fallback
    parts = m.group(1).split("x")
    if len(parts) != nd:
        return fallback
    if key == "pad":
        return [tuple(int(v) for v in p.split("_")) for p in parts]
    return [int(p) for p in parts]


def _conv_flops(type_str, rest, symtab):
    """Useful MACs of an HLO convolution: 2 x (non-spatial out dims) x
    rhs reduction features x per-dim VALID (output, window) pairs.

    Naive 2*prod(out)*prod(window)*C counts padding and dilation zeros as
    real math — a full-correlation filter-grad (window 56x56, pad 55)
    would read as 10 TFLOP of a 13 GFLOP op. Valid-pair counting per
    spatial dim makes the count match the model-level FLOP accounting the
    MFU numbers use."""
    out = _shape_dims(type_str)
    m = re.search(r"dim_labels=([\w]+)_([\w]+)->([\w]+)", rest)
    ops = _OPERAND_RE.findall(rest.split(", window=")[0])
    if not m or len(ops) < 2 or ops[0] not in symtab \
            or ops[1] not in symtab:
        return 0
    lhs_l, rhs_l, out_l = m.group(1), m.group(2), m.group(3)
    lhs = _shape_dims(symtab[ops[0]])
    rhs = _shape_dims(symtab[ops[1]])
    if len(rhs) != len(rhs_l) or len(lhs) != len(lhs_l) \
            or len(out) != len(out_l):
        return 0
    k_feat = rhs[rhs_l.index("i")]
    spatial = [ch for ch in out_l if ch.isdigit()]
    win = re.search(r"window=\{([^}]*)\}", rest)
    window = win.group(1) if win else ""
    nd = len(spatial)
    sizes = _win_attr(window, "size", nd, 1)
    strides = _win_attr(window, "stride", nd, 1)
    pads = _win_attr(window, "pad", nd, 0)
    ldil = _win_attr(window, "lhs_dilate", nd, 1)
    rdil = _win_attr(window, "rhs_dilate", nd, 1)
    # non-spatial output element count (batch x features)
    n = 1
    for i, ch in enumerate(out_l):
        if not ch.isdigit():
            n *= out[i]
    pairs = 1
    for d, ch in enumerate(spatial):
        O = out[out_l.index(ch)]
        K = sizes[d]
        L = lhs[lhs_l.index(ch)]
        span = (L - 1) * ldil[d] + 1  # dilated base extent
        valid = 0
        for o in range(O):
            base = o * strides[d] - pads[d][0]
            for kk in range(K):
                pos = base + kk * rdil[d]
                if 0 <= pos < span and pos % ldil[d] == 0:
                    valid += 1
        pairs *= valid
    return 2 * n * k_feat * pairs


def _dot_flops(type_str, rest, symtab):
    out = _shape_dims(type_str)
    n = 1
    for d in out:
        n *= d
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rest)
    ops = _OPERAND_RE.findall(rest.split(", lhs_")[0])
    if not m or not ops or ops[0] not in symtab:
        return 0
    lhs = _shape_dims(symtab[ops[0]])
    k = 1
    for i in (int(x) for x in m.group(1).split(",") if x):
        if i < len(lhs):
            k *= lhs[i]
    return 2 * n * k


def _comp_flops(comp_name, comps, memo):
    """Total dot/conv FLOPs of a computation, following nested fusion/call
    edges. Returns (flops, kinds) where kinds is a set like {"conv","dot"}."""
    if comp_name in memo:
        return memo[comp_name]
    comp = comps.get(comp_name)
    if comp is None:
        return 0, set()
    memo[comp_name] = (0, set())  # cycle guard
    symtab = dict(comp["params"])
    flops, kinds = 0, set()
    for name, type_str, op, rest in comp["instrs"]:
        symtab[name] = type_str
        if op == "convolution":
            f = _conv_flops(type_str, rest, symtab)
            flops += f
            if f:
                kinds.add("conv")
        elif op == "dot":
            f = _dot_flops(type_str, rest, symtab)
            flops += f
            if f:
                kinds.add("dot")
        elif op == "custom-call":
            kinds.add("custom")
        elif op in ("fusion", "call", "while", "conditional"):
            for callee in _CALLS_RE.findall(rest) or _operand_comps(op, rest):
                sub_f, sub_k = _comp_flops(callee, comps, memo)
                flops += sub_f
                kinds |= sub_k
    memo[comp_name] = (flops, kinds)
    return flops, kinds


def _operand_comps(op, rest):
    """while/conditional reference computations via body=/condition= etc."""
    if op == "while":
        return re.findall(r"(?:body|condition)=%?([\w.\-]+)", rest)
    if op == "conditional":
        return re.findall(r"\w+_computation=%?([\w.\-]+)", rest)
    return []


def parse_hlo_costs(hlo_text: str):
    """Per entry-level instruction: {"flops", "bytes", "kind", "op_name"}.

    bytes = operand bytes + result bytes (the fusion's HBM traffic bound,
    assuming perfect reuse inside the fusion); flops follow nested fusions.
    """
    comps = _parse_computations(hlo_text)
    entry = next((c for c in comps.values() if c["is_entry"]), None)
    if entry is None:
        return {}
    memo = {}
    symtab = dict(entry["params"])
    out = {}
    for name, type_str, op, rest in entry["instrs"]:
        symtab[name] = type_str
        res_bytes = _shape_bytes(type_str)
        opnames = _OPERAND_RE.findall(rest.split("metadata=")[0])
        # operands whose producing instruction lives in memory space S(1)
        # (VMEM, placed there by memory-space-assignment prefetch copies)
        # are NOT HBM traffic of this fusion — the copy-start/copy-done
        # that staged them is billed separately on the device track
        op_bytes, vmem_bytes = 0, 0
        for o in opnames:
            if o not in symtab:
                continue
            b = _shape_bytes(symtab[o])
            if "S(1)" in symtab[o]:
                vmem_bytes += b
            else:
                op_bytes += b
        if "S(1)" in type_str:
            vmem_bytes += res_bytes
            res_bytes = 0
        flops, kinds = 0, set()
        if op == "convolution":
            flops = _conv_flops(type_str, rest, symtab)
            kinds = {"conv"} if flops else set()
        elif op == "dot":
            flops = _dot_flops(type_str, rest, symtab)
            kinds = {"dot"} if flops else set()
        elif op in ("fusion", "call", "while", "conditional"):
            for callee in _CALLS_RE.findall(rest) or _operand_comps(op, rest):
                f, k = _comp_flops(callee, comps, memo)
                flops += f
                kinds |= k
        mname = re.search(r'op_name="([^"]*)"', rest)
        op_name = mname.group(1) if mname else ""
        if op == "custom-call" or "custom" in kinds \
                or "pallas_call" in op_name:
            # a Pallas kernel's FLOPs are invisible to HLO parsing — its
            # roofline must be argued from its OWN cost model, not this
            # table (kind="custom" keeps it out of the conv aggregates)
            kind = "custom"
        elif "conv" in kinds:
            kind = "conv"
        elif "dot" in kinds:
            kind = "dot"
        else:
            kind = "other"
        out[name] = {
            "flops": flops,
            "bytes": op_bytes + res_bytes,
            "vmem_bytes": vmem_bytes,
            "kind": kind,
            "op": op,
            "op_name": op_name,
        }
    return out


def profile_device_events(run_fn, steps: int = 4, trace_dir: str = None):
    """Run ``run_fn(steps)`` under jax.profiler.trace; return
    ({instr_name: {"count", "total_us"}}, device_total_us) from the
    device track. ``run_fn`` must sync before returning
    (``block_until_ready`` or a host fetch)."""
    import jax

    td = trace_dir or tempfile.mkdtemp(prefix="pt_roofline_")
    with jax.profiler.trace(td):
        run_fn(steps)
    paths = sorted(glob.glob(
        os.path.join(td, "**", "*.trace.json.gz"), recursive=True))
    if not paths:
        raise RuntimeError(f"no trace produced under {td}")
    events = json.loads(gzip.open(paths[-1]).read())["traceEvents"]
    # the dedupe-aware parse (module spans / per-op spans / bare-number
    # "Steps" markers each routed exactly once) lives in the profiler —
    # one regression-tested copy shared by every trace consumer
    from ..profiler import summarize_device_trace
    return summarize_device_trace(events)


def roofline_table(hlo_text: str, events, steps: int,
                   peak_flops: float, hbm_bw: float):
    """Join HLO costs with device durations -> per-instruction rows.

    Each row: achieved TFLOP/s and GB/s, its own roofline bound
    t_bound = max(flops/peak, bytes/bw), and efficiency = t_bound/t_meas
    (1.0 = running AT the roofline; small = leaving the machine idle).
    """
    costs = parse_hlo_costs(hlo_text)
    rows = []
    unmatched_us = 0.0
    for name, ev in events.items():
        us = ev["total_us"] / max(steps, 1)
        cost = costs.get(name)
        if cost is None or us <= 0:
            unmatched_us += us
            continue
        t = us / 1e6
        t_bound = max(cost["flops"] / peak_flops,
                      cost["bytes"] / hbm_bw) if (
                          cost["flops"] or cost["bytes"]) else 0.0
        rows.append({
            "name": name,
            "kind": cost["kind"],
            "op_name": cost["op_name"][:120],
            "time_us": round(us, 1),
            "flops": cost["flops"],
            "bytes": cost["bytes"],
            "achieved_tflops": round(cost["flops"] / t / 1e12, 2),
            "achieved_gbs": round(cost["bytes"] / t / 1e9, 1),
            "bound_us": round(t_bound * 1e6, 1),
            "bound_by": ("compute" if cost["flops"] / peak_flops
                         >= cost["bytes"] / hbm_bw else "memory"),
            "roofline_eff": round(t_bound / t, 3) if t_bound else None,
        })
    rows.sort(key=lambda r: -r["time_us"])
    return rows, unmatched_us  # already per-step (us was divided above)
