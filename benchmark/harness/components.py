"""Device time by component: the scope path every XLA operation of a step
program carries (``paddle_tpu.profiler.scope``: the model tree, written by
``Layer.__call__``, and the ``pt.<part>`` vocabulary) read out of a
profile into one table a program kind, and the per-layer metrics
``*_device_ms.*`` / ``device_named_pct.*`` from it.

For one chip, every event of the operations line inside the traced window
(containers left out, as ``Trace.top_ops`` does) is given: the program that
holds it (the ``XLA Modules`` span around it, by name, so a mixed step and a
decode scan are apart), its scope path, its direction (``transpose(`` in
the path is the backward pass) and, by ONE table here (:func:`component`),
its component.

**Where the scope comes from**: the HLO protos the profile file itself
carries (plane ``/host:metadata``: one event metadata a module that ran,
named ``<module>(<program id>)``, whose ``Hlo Proto`` stat is the
serialised ``HloProto``). An instruction's ``metadata.op_name`` is the
scope path; an operation event is joined to it by its module span's name
and its own instruction name. The file is read with a wire-format reader of
forty lines (:func:`fields`): ``jax.profiler.ProfileData`` shows that plane
without lines, and no protobuf schema for it ships with jax.

A program without scopes (a parent commit under these files; an executable
loaded from a compile cache that another tree filled: the cache's key
leaves metadata out) gives None with a printed reason, never a table of
``unnamed``: a step program's own operations carry ``jit(<program>)/...``
from jax itself, so "no path reaches past the program's name" is told
apart from "the file holds no HLO"."""
from __future__ import annotations

import bisect
import functools
import re
import time

from .readers import SERVE_MODULES, TRAIN_MODULES
from .trace import CONTAINERS, MODULES_LINE, OPS_LINE, family, newest_xplane

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def log(msg):
    print(msg, flush=True)


# -- the file -----------------------------------------------------------------
def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def fields(buf):
    """(field number, value) of one protobuf message's bytes: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt in (1, 5):
            ln = 8 if wt == 1 else 4
            v, i = buf[i:i + ln], i + ln
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, v


def _text(v):
    return bytes(v).decode("utf-8", "replace")


#: how far an operation without a path looks for one among its users
HOPS = 8
#: what such a look does not pass through: on the far side of a loop or of
#: a program's result, anything uses anything
BARRIERS = frozenset(("while", "conditional", "call", "tuple", "parameter"))
#: whose called computations' instructions run as operations of their own
CONTAINER_OPS = frozenset(("while", "conditional", "call", "async-start"))


def _packed(v):
    """A repeated int64 field's values (packed, or one value)."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _instructions(hlo_proto):
    """{instruction name: (op_name, own, opcode, event)} of one serialised
    ``HloProto``. ``event``: the instruction runs as an operation of its
    own (it is in the entry computation or in a loop's, a branch's or a
    call's, not inside a fusion, a reduction or a sort's comparison).
    ``own``: the instruction's own ``metadata.op_name`` names a scope
    (:func:`parse` finds a token). One that does not takes, in this order:
    its called computation's root's (a fusion is named by its root), else
    its first instruction's; the path of the nearest operation that USES
    its result, through at most :data:`HOPS` pathless operations and no
    :data:`BARRIERS` (what XLA makes for another operation serves it: a
    relayout copy, the slices of a weight's prefetch, a bitcast), into a
    loop's body where the result is the loop's k-th operand (XLA lifts a
    weight's prefetch out of the loop that reads it) and out of one where
    it is the body's k-th result; the same over its operands. Such a path
    is not ``own``."""
    comps, entry = {}, None     # computation id -> (root id, rows)
    for num, module in fields(hlo_proto):
        if num != 1:                                # HloProto.hlo_module
            continue
        for num, comp in fields(module):
            if num == 6:                            # .entry_computation_id
                entry = comp
            if num != 3:                            # .computations
                continue
            cid, root, rows = None, None, []
            for num, v in fields(comp):
                if num == 5:
                    cid = v
                elif num == 6:
                    root = v
                elif num == 2:                      # .instructions
                    row = {"name": None, "op": "", "id": None, "index": 0,
                           "opcode": "", "operands": [], "calls": []}
                    for num, w in fields(v):
                        if num == 1:
                            row["name"] = _text(w)
                        elif num == 2:
                            row["opcode"] = _text(w)
                        elif num == 13:
                            row["index"] = w        # .tuple_index
                        elif num == 35:
                            row["id"] = w
                        elif num == 7:              # .metadata.op_name
                            for num, x in fields(w):
                                if num == 2:
                                    row["op"] = _text(x)
                        elif num == 36:
                            row["operands"] += _packed(w)
                        elif num == 38:
                            row["calls"] += _packed(w)
                    rows.append(row)
            comps[cid] = (root, rows)

    def named(op):
        return bool(op) and bool(parse(op)[0])
    events, todo = set(), [entry]
    while todo:
        cid = todo.pop()
        if cid in events or cid not in comps:
            continue
        events.add(cid)
        todo += [c for r in comps[cid][1] if r["opcode"] in CONTAINER_OPS
                 for c in r["calls"]]
    nodes, users, operands = {}, {}, {}
    for cid, (root, rows) in comps.items():
        for row in rows:
            row["own"] = named(row["op"])
            for sub in row["calls"] if not row["own"] else ():
                r, inner = comps.get(sub, (None, []))
                ops = [i["op"] for i in inner if i["id"] == r] + \
                    [i["op"] for i in inner]
                op = next((o for o in ops if named(o)), "")
                if op:
                    row["op"], row["own"] = op, True
                    break
            key = (cid, row["id"])
            nodes[key] = row
            operands[key] = [(cid, o) for o in row["operands"]]
            for o in operands[key]:
                users.setdefault(o, []).append(key)
    # through a loop's boundary. In: the k-th operand of the tuple a
    # ``while`` takes is used by the ``get-tuple-element``s of index k on
    # its computations' parameter. Out: the k-th operand of its body's
    # root tuple is used by the ``get-tuple-element``s of index k on the
    # ``while`` itself (a loop XLA made, as for a batched slice, has no
    # path anywhere inside)
    for (cid, wid), row in list(nodes.items()):
        if row["opcode"] != "while" or not row["operands"]:
            continue
        carried = nodes.get((cid, row["operands"][0]))
        after = [nodes[u] for u in users.get((cid, wid), ())
                 if nodes[u]["opcode"] == "get-tuple-element"]
        for sub in row["calls"]:
            root, inner_rows = comps.get(sub, (None, []))
            for inner in inner_rows:
                if carried is not None and carried["opcode"] == "tuple" \
                        and inner["opcode"] == "get-tuple-element" \
                        and inner["index"] < len(carried["operands"]) \
                        and nodes.get((sub, inner["operands"][0]),
                                      {}).get("opcode") == "parameter":
                    users.setdefault(
                        (cid, carried["operands"][inner["index"]]),
                        []).append((sub, inner["id"]))
                if inner["id"] == root and inner["opcode"] == "tuple":
                    for gte in after:
                        if gte["index"] < len(inner["operands"]):
                            users.setdefault(
                                (sub, inner["operands"][gte["index"]]),
                                []).append((cid, gte["id"]))
    out = {}
    for key, row in nodes.items():
        op = row["op"]
        if not named(op):
            for edges in (users, operands):
                op = _nearest(key, edges, nodes, named)
                if op:
                    break
        out[row["name"]] = (op or row["op"], row["own"], row["opcode"],
                            key[0] in events)
    return out


def _nearest(start, edges, nodes, named):
    """The ``op`` of the nearest instruction along ``edges`` that ``named``
    accepts, breadth first, at most :data:`HOPS` away; "" if none."""
    seen, ring = {start}, [start]
    for _ in range(HOPS):
        nxt = []
        for i in ring:
            for j in edges.get(i, ()):
                if j in seen or j not in nodes \
                        or nodes[j]["opcode"] in BARRIERS:
                    continue
                seen.add(j)
                if named(nodes[j]["op"]):
                    return nodes[j]["op"]
                nxt.append(j)
        ring = nxt
    return ""


def module_scopes(module_proto):
    """:func:`_instructions` of one serialised ``HloModuleProto`` (what a
    compiled program hands out: ``compiled.runtime_executable()
    .hlo_modules()[0].as_serialized_hlo_module_proto()``), for reading a
    program's scopes with no profile taken."""
    n, size = len(module_proto), b""
    while True:
        size += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            break
    return _instructions(memoryview(b"\x0a" + size + bytes(module_proto)))


def hlo_scopes(path):
    """{module name as the modules line has it, ``jit_f(5)``: {instruction
    name: (op_name, own, opcode, event)}} of every module whose HLO the
    profile at ``path`` carries (:func:`_instructions`); {} where it carries
    none."""
    with open(path, "rb") as f:
        raw = memoryview(f.read())
    out = {}
    for num, plane in fields(raw):
        if num != 1:
            continue
        name, stat_names, metas = None, {}, []
        for num, v in fields(plane):
            if num == 2:
                name = _text(v)
            elif num == 5:                          # stat_metadata map
                d = dict(fields(v))
                if 2 in d:
                    m = dict(fields(d[2]))
                    stat_names[m.get(1)] = _text(m.get(2, b""))
            elif num == 4:                          # event_metadata map
                d = dict(fields(v))
                if 2 in d:
                    metas.append(d[2])
        if name != METADATA_PLANE:
            continue
        for meta in metas:
            module, proto = None, None
            for num, v in fields(meta):
                if num == 2:
                    module = _text(v)
                elif num == 5:                      # XStat
                    st = dict(fields(v))
                    if stat_names.get(st.get(1)) == HLO_STAT and 6 in st:
                        proto = st[6]
            if module and proto is not None:
                out[module] = _instructions(proto)
    return out


# -- the path -----------------------------------------------------------------
_JIT = re.compile(r"\b(?:jit|pjit|xla_call)\([^()]*\)")
_WRAP = re.compile(r"[A-Za-z_][\w.]*\(|\)")
#: tokens jax's own transforms and control flow put on a path
STRUCTURE = frozenset((
    "while", "body", "cond", "scan", "checkpoint", "remat", "rematted",
    "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "shard_map", "branch", "vmap", "jvp",
    "transpose", "pallas_call", "custom_lin"))


#: what every numeric token reads as
INDEX = "#"


@functools.lru_cache(maxsize=None)
def parse(op_name):
    """``(tokens, backward)`` of an ``op_name``: the scope tokens from the
    outermost in, with ``jit(...)`` wrappers, transform wrappers
    (``transpose(jvp(x))`` reads ``x``, backward), control-flow words,
    and the primitive at the end dropped, and a numeric token (a
    ``LayerList``'s index: which block) read as :data:`INDEX`.
    ``jit(f)/transpose(jvp(self_attn))/pt.core/mul`` gives
    ``(("self_attn", "pt.core"), True)``."""
    backward = "transpose(" in op_name
    parts = _WRAP.sub("", _JIT.sub("", op_name)).split("/")
    tokens = tuple(INDEX if t.isdigit() else t for t in parts[:-1]
                   if t and t not in STRUCTURE
                   and not t.startswith("branch_"))
    return tokens, backward


#: the parts of an expert layer, under ``ffn``
FFN_STAGES = ("pt.route", "pt.dispatch", "pt.experts", "pt.shared",
              "pt.combine")
#: a part outside the decoder's blocks -> its component
STEP_PARTS = {"pt.sample": "sample", "pt.pack": "pack",
              "pt.readout": "readout", "pt.exit": "exit"}
#: the components ``step_other_device_ms.batch`` adds up
STEP_OTHER = ("embed", "block", "head", "sample", "pack", "readout",
              "exit", "other")


def component(tokens):
    """THE table: scope tokens -> component. ``unnamed``: no scope at
    all; ``other``: a scope that no rule below knows."""
    if not tokens:
        return "unnamed"
    t = set(tokens)
    if "pt.clip" in t:
        return "clip"
    if "pt.optimizer" in t:
        return "optimizer"
    if "pt.loss" in t:
        return "loss"
    if "self_attn" in t:
        if "pt.core" in t:
            return "mixer.core"
        if any(x.endswith("_proj") for x in tokens):
            return "mixer.proj"
        return "mixer.other"
    if "mlp" in t:
        for stage in FFN_STAGES:
            if stage in t:
                return "ffn." + stage[3:]
        return "ffn"
    for part, comp in STEP_PARTS.items():
        if part in t:
            return comp
    if "embed_tokens" in t:
        return "embed"
    if INDEX in t or "layers" in t:
        return "block"                  # block-level norms and adds
    if "norm" in t or "lm_head" in t:
        return "head"
    return "other"


def leaf(tokens):
    """The innermost scope: the row of the finer table."""
    named = [t for t in tokens if t != INDEX]
    return named[-1] if named else "-"


# -- the table ----------------------------------------------------------------
class Kind:
    """One program kind's operations: ``calls`` module spans of ``secs``
    summed seconds; ``rows`` {(component, leaf, backward): [seconds,
    events, {hlo family: seconds}]}; ``inherited``: the seconds of
    operations whose path is a user's or an operand's, not their own."""

    def __init__(self):
        self.calls, self.secs, self.rows, self.inherited = 0, 0.0, {}, 0.0

    def add(self, comp, leaf_, backward, hlo, secs, own):
        row = self.rows.setdefault((comp, leaf_, backward), [0.0, 0, {}])
        row[0] += secs
        row[1] += 1
        row[2][hlo] = row[2].get(hlo, 0.0) + secs
        if not own and comp != "unnamed":
            self.inherited += secs

    def by_component(self):
        out = {}
        for (comp, _, _), (secs, n, _) in self.rows.items():
            a = out.setdefault(comp, [0.0, 0])
            a[0] += secs
            a[1] += n
        return out

    @property
    def op_secs(self):
        return sum(r[0] for r in self.rows.values())


class Table:
    """``kinds``: {module name without its program id: :class:`Kind`}."""

    def __init__(self, kinds, step_pattern):
        self.kinds = kinds
        self.step = re.compile(step_pattern)

    def step_kinds(self):
        return {k: v for k, v in self.kinds.items() if self.step.search(k)}

    def programs(self):
        return sum(k.calls for k in self.step_kinds().values())

    def seconds(self, pick=lambda comp, backward: True):
        """Summed seconds of the step programs' operations that ``pick``
        accepts."""
        return sum(secs for kind in self.step_kinds().values()
                   for (comp, _, bw), (secs, _, _) in kind.rows.items()
                   if pick(comp, bw))

    def ms_a_program(self, *names):
        """ms a step program of the components ``names`` (``"ffn"`` takes
        its stages ``ffn.*`` too)."""
        n = self.programs()
        if not n:
            return None
        return 1e3 * self.seconds(
            lambda comp, _: comp in names
            or comp.partition(".")[0] in names) / n

    def named_pct(self):
        total = self.seconds()
        if total <= 0:
            return None
        return 100.0 * self.seconds(lambda c, _: c != "unnamed") / total


def _kind(module):
    """A module span's name without its program id: the program kind."""
    return re.sub(r"\(\d+\)$", "", module)


def _module_of(spans, starts, s):
    """The name of the module span (sorted by start; a chip runs one at a
    time) that started last before ``s``: on a chip the one that holds
    the operation; in a CPU rehearsal, where the operations run on other
    threads once the call is back, the one that made it. None before the
    first."""
    i = bisect.bisect_right(starts, s) - 1
    return spans[i][2] if i >= 0 else None


def build(trace, scopes, step_pattern, chip=None):
    """The :class:`Table` of one chip's operations inside the window.
    ``scopes``: :func:`hlo_scopes`. An operation whose module carries no
    HLO, or whose instruction the HLO does not name, counts as
    ``unnamed``. A path is an operation's own or, for what XLA made to
    serve another operation, that one's (:func:`_instructions`)."""
    chip = min(trace.chips) if chip is None else chip
    lo, hi = trace.window
    mods = sorted(trace.chips[chip].get(MODULES_LINE, []))
    starts = [m[0] for m in mods]
    #: a module span's name with and without its program id
    by_name = {}
    for module, names in scopes.items():
        by_name[module] = names
        by_name.setdefault(_kind(module), names)
    kinds = {}
    for ms, me, name in mods:
        if ms >= lo and me <= hi:
            k = kinds.setdefault(_kind(name), Kind())
            k.calls += 1
            k.secs += (me - ms) / 1e9
    for s, e, name in trace.chips[chip][OPS_LINE]:
        if s < lo or e > hi or CONTAINERS.match(name):
            continue
        module = _module_of(mods, starts, s)
        names = by_name.get(module) if module else None
        op, own = names.get(name, ("", False))[:2] if names \
            else ("", False)
        tokens, backward = parse(op)
        kind = kinds.setdefault(_kind(module) if module else "(no module)",
                                Kind())
        kind.add(component(tokens), leaf(tokens), backward, family(name),
                 (e - s) / 1e9, own)
    return Table(kinds, step_pattern)


def show(table):
    """The log: a program kind's mean duration, then component x (ms a
    program, share, events a program), the finer rows under each, and the
    ten largest ``unnamed`` operations by HLO name."""
    for name, kind in sorted(table.kinds.items(),
                             key=lambda kv: -kv[1].op_secs):
        if not kind.calls or kind.op_secs <= 0:
            continue
        n, total = kind.calls, kind.op_secs
        log(f"[components] {name}: {n} programs of "
            f"{1e3 * kind.secs / n:.3f} ms; their operations sum to "
            f"{1e3 * total / n:.3f} ms a program, "
            f"{100 * kind.inherited / total:.1f} % of it under a path "
            f"taken from a user or an operand"
            + ("" if table.step.search(name) else " (not a step program)"))
        comps = kind.by_component()
        for comp, (secs, events) in sorted(comps.items(),
                                           key=lambda kv: -kv[1][0]):
            log(f"[components]   {comp:<12} {1e3 * secs / n:9.3f} ms "
                f"{100 * secs / total:5.1f} %  {events / n:8.1f} events")
            rows = sorted(((k, v) for k, v in kind.rows.items()
                           if k[0] == comp), key=lambda kv: -kv[1][0])
            for (_, leaf_, bw), (rs, _, hlo) in rows[:6]:
                if rs < 0.005 * total:
                    continue
                top = sorted(hlo.items(), key=lambda kv: -kv[1])[:3]
                log(f"[components]     {'bwd ' if bw else ''}{leaf_:<22} "
                    f"{1e3 * rs / n:9.3f} ms  "
                    + ", ".join(f"{h} {1e3 * t / n:.3f}" for h, t in top))
        unnamed = {}
        for (comp, _, _), (_, _, hlo) in kind.rows.items():
            if comp == "unnamed":
                for h, t in hlo.items():
                    unnamed[h] = unnamed.get(h, 0.0) + t
        for h, t in sorted(unnamed.items(), key=lambda kv: -kv[1])[:10]:
            log(f"[components]   unnamed: {h} {1e3 * t / n:.3f} ms a program")


def components(ctx):
    """The run's :class:`Table`, read once and logged; None, with the
    reason printed, where there is no trace, the profile carries no HLO,
    or no operation of a step program carries a scope past the program's
    own name (a program from before the scopes; an executable out of a
    compile cache that a tree without them filled)."""
    if "components" in ctx:
        return ctx["components"]
    ctx["components"] = None
    tr = ctx.get("trace")
    if tr is None:
        return None
    t0 = time.perf_counter()
    scopes = hlo_scopes(newest_xplane(ctx["cell"].trace_dir))
    if not scopes:
        log(f"[components] the profile carries no HLO ({METADATA_PLANE} "
            f"has no {HLO_STAT!r}): nothing to read")
        return None
    pattern = SERVE_MODULES if ctx["kind"] == "serve" else TRAIN_MODULES
    table = build(tr, scopes, pattern)
    took = time.perf_counter() - t0
    if not table.programs():
        log(f"[components] no step program ({pattern}) inside the window; "
            f"modules seen: {sorted(table.kinds)}")
        return None
    if table.seconds(lambda c, _: c not in ("unnamed", "other")) <= 0:
        log("[components] no operation of the step programs carries a "
            "scope the table knows (jax's own, an einsum's equation, are "
            "not the program's): the program was built without them (a "
            "commit before "
            "paddle_tpu.profiler.scope), or its executable came out of a "
            "compile cache that such a tree filled (the key leaves "
            "metadata out: clear the cache, or give this tree a cache "
            "directory of its own)")
        return None
    ctx["components"] = table
    show(table)
    n = table.programs()
    log(f"[components] the step programs' operations sum to "
        f"{1e3 * table.seconds() / n:.3f} ms a program over {n} programs "
        f"(all kinds together; step_device_ms.* is the module spans' mean: "
        f"asynchronous operations overlap others, idle gaps inside a "
        f"program belong to neither); {len(scopes)} modules' HLO read in "
        f"{took:.2f} s")
    return table


def device_ms(*names):
    """A metric's ``read``: the named components' ms a step program."""
    def read(ctx):
        table = components(ctx)
        return None if table is None else table.ms_a_program(*names)
    return read


def named_pct(ctx):
    table = components(ctx)
    return None if table is None else table.named_pct()


if __name__ == "__main__":
    # a profile read again, with no chip and no run:
    #   python3 -m benchmark.harness.components <file.xplane.pb> serve|train
    import sys

    from .trace import Trace
    _path, _cell_kind = sys.argv[1], sys.argv[2]
    show(build(Trace.from_file(_path), hlo_scopes(_path),
               SERVE_MODULES if _cell_kind == "serve" else TRAIN_MODULES))
