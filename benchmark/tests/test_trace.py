"""The reduction from a recorded profile to the per-layer numbers, on a
small ``XSpace`` written out by hand in the shape the v5e's own traces have
(looked at by hand on the first chip call of PR 24): device planes
``/device:TPU:<n>`` with lines "XLA Modules", "XLA Ops" (the loop bodies
nested inside their ``while``) and "Async XLA Ops"; event names are whole
HLO texts; the benchmark's own spans sit on host threads."""
import pytest
from jax.profiler import ProfileData

from benchmark.harness import readers, trace as T

NS = 1000  # picoseconds in a nanosecond


def plane(ordinal, ops, modules):
    meta, lines = {}, []

    def events(rows):
        out = []
        for name, start, dur in rows:
            mid = meta.setdefault(name, len(meta) + 1)
            out.append(f"events {{ metadata_id: {mid} offset_ps: "
                       f"{start * NS} duration_ps: {dur * NS} }}")
        return "\n".join(out)
    lines.append(f'lines {{ id: 1 name: "XLA Modules" {events(modules)} }}')
    lines.append(f'lines {{ id: 2 name: "XLA Ops" {events(ops)} }}')
    lines.append('lines { id: 3 name: "Async XLA Ops" '
                 + events([("%copy-start.1 = copy-start()", 0, 9000)]) + " }")
    metas = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in meta.items())
    return (f'planes {{ id: {ordinal + 1} name: "/device:TPU:{ordinal}"\n'
            f'{metas}\n' + "\n".join(lines) + " }")


def host(window, spans):
    names = {T.WINDOW: 1}
    rows = [f"events {{ metadata_id: 1 offset_ps: {window[0] * NS} "
            f"duration_ps: {(window[1] - window[0]) * NS} }}"]
    for name, s, e in spans:
        mid = names.setdefault(name, len(names) + 1)
        rows.append(f"events {{ metadata_id: {mid} offset_ps: {s * NS} "
                    f"duration_ps: {(e - s) * NS} }}")
    metas = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in names.items())
    return (f'planes {{ id: 99 name: "/host:CPU"\n{metas}\n'
            f'lines {{ id: 5 name: "python3" {" ".join(rows)} }} }}')


DEC = "%paged_attention_decode.7 = (bf16[8,8,4,128]) custom-call(%fusion.1)"
APP = "%paged_attention_append.2 = (bf16[8,8,1024,128]) custom-call(%x)"
FUS = "%fusion.1 = bf16[8,4096] fusion(%all-reduce.3, %while.6)"
ARD = "%all-reduce.3 = bf16[8,4096] all-reduce(%fusion.9)"
WHL = "%while.6 = (s32[], bf16[545,8,64,128]) while(%tuple.1)"
#: window 1000..5000 ns. The while spans its body's operations; an
#: operation straddles each end of the window.
OPS = [(WHL, 1000, 1500), (FUS, 1000, 400), (DEC, 1500, 300),
       (ARD, 1900, 100), (DEC, 2100, 300), (APP, 3000, 1000),
       (FUS, 800, 100), (FUS, 4900, 400)]
MODS = [("jit_multi_step(123)", 1000, 1500), ("jit_fused_step(9)", 3000, 1000),
        ("jit_set_len(5)", 4100, 10), ("jit_fused_step(9)", 4900, 400)]


def load(chips=1, ops=OPS):
    text = "\n".join([plane(c, ops, MODS) for c in range(chips)]
                     + [host((1000, 5000),
                             [("bench:wait_for_token", 900, 2950),
                              ("bench:submit", 2950, 3010)])])
    return T.Trace.from_profile(ProfileData.from_text_proto(text))


def test_op_name_is_the_hlo_name_alone():
    assert T.op_name(FUS) == "fusion.1"
    assert T.op_name("jit_fused_step(9)") == "jit_fused_step(9)"


def test_busy_is_the_union_clipped_to_the_window_not_a_sum():
    tr = load()
    # [1000,2500) from the while and its body, [3000,4000), [4900,5000)
    assert tr.busy_s() == pytest.approx(2600e-9)
    assert 0 < tr.busy_s() <= tr.window_s == pytest.approx(4000e-9)


def test_busy_is_the_mean_over_four_chips():
    tr = load(chips=4)
    assert sorted(tr.chips) == [0, 1, 2, 3]
    assert tr.busy_s() == pytest.approx(2600e-9)


def test_kernel_time_is_a_sum_per_name_inside_the_window():
    tr = load()
    assert tr.op_seconds(r"paged_attention_decode") == \
        (pytest.approx(600e-9), 2)
    # the fusion that names an all-reduce among its operands is no collective
    assert tr.op_seconds(r"all-reduce|all-gather") == (pytest.approx(100e-9), 1)
    # the module that straddles the window's end is left out
    assert tr.op_seconds(readers.SERVE_MODULES, T.MODULES_LINE) == \
        (pytest.approx(2500e-9), 2)


def test_a_missing_kernel_is_an_error_that_names_it_and_what_was_seen():
    with pytest.raises(T.TraceError) as e:
        load().op_seconds(r"flash_attention_fwd")
    assert "flash_attention_fwd" in str(e.value)
    assert "paged_attention_decode.7" in str(e.value)


def test_breakdown_leaves_out_containers_and_names_the_gaps():
    tr = load()
    ops = dict(tr.top_ops())
    assert "while.6" not in ops
    # a kernel's calls go under the kernel's name, fusions stay apart
    assert ops["paged_attention_append"] == pytest.approx(1000e-9)
    assert ops["paged_attention_decode"] == pytest.approx(600e-9)
    assert ops["fusion.1"] == pytest.approx(400e-9)
    assert T.family("convolution_bitcast_fusion.2") == \
        "convolution_bitcast_fusion.2"
    gaps = tr.idle_gaps()
    assert gaps[0] == ["host:unattributed", pytest.approx(900e-9)]
    assert ["bench:wait_for_token", pytest.approx(500e-9)] in gaps


def test_no_device_plane_or_no_window_is_an_error():
    text = host((0, 10), [])
    with pytest.raises(T.TraceError, match="device plane"):
        T.Trace.from_profile(ProfileData.from_text_proto(text))
    text = plane(0, OPS, MODS)
    with pytest.raises(T.TraceError, match="bench_window"):
        T.Trace.from_profile(ProfileData.from_text_proto(text))


class Rec:
    def __init__(self, rid, n_prompt, events, t_first):
        self.n_prompt, self.events, self.t_first = n_prompt, events, t_first
        self.handle = type("H", (), {"request_id": rid})()


class Stretch:
    t0, t1 = 0.0, 4.0
    snap0, snap1 = {1: 256}, {1: 700, 2: 512}


def ctx(tr, chips=1):
    from benchmark.harness import loader
    return {"kind": "serve", "trace": tr, "stretch": Stretch(),
            "cell": loader.Cell("doc_batch"), "layers_here": 16,
            "chips": chips,
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "records": [Rec(1, 700, [(1.0, 0, 1), (2.0, 1, 4)], 1.0),
                        Rec(2, 1024, [], None)]}


def test_roofline_shares_count_only_live_work():
    tr = load()
    c = ctx(tr)
    app = readers.paged_append_roofline(c)
    assert app is not None and app > 0
    # request 1 went 256 -> 700, request 2 0 -> 512: chunks at 256-borders
    from benchmark.kernels import paged_attention_append as K
    pieces = [(256, 512), (512, 700), (0, 256), (256, 512)]
    least = sum(max(f / 197e12, b / 819e9) for f, b in
                (K.least(a, b, 32, 8, 128, 16) for a, b in pieces))
    assert app == pytest.approx(100 * least / 1000e-9)


def test_a_reader_with_nothing_to_read_returns_nothing():
    c = ctx(load())
    c["trace"] = None
    assert readers.paged_append_roofline(c) is None
    assert readers.step_device_ms(c) is None
    assert readers.device_idle_pct(c) is None


def test_kernel_counts_from_shapes():
    from benchmark.kernels import flash_attention as F
    f, b = F.least(6, 2048, 32, 8, 128, 2)
    assert f == pytest.approx(12 * (2048 * 2049 / 2) * 128 * 32 * 6 * 2)
    assert b == 6 * 2048 * 128 * 2 * (5 * 32 + 4 * 8) * 2
