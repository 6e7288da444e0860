"""The trace reduction, on a trace recorded on a TPU v5e and on made-up
events.

`data/tws_v5e.xplane.pb` is 67 ms of the train-while-serve path on one
v5e: 40 blocks of 256 rows through `DRService.serve_and_update`, each
answer copied to the host, then a promote, with the benchmark's own
`bench.*` spans around each call.
"""

from __future__ import annotations

import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data" / "tws_v5e.xplane.pb"


@pytest.fixture(scope="module")
def planes():
    return trace.read_planes(str(DATA))


@pytest.fixture(scope="module")
def summary(planes):
    devices, host = planes
    return trace.reduce(devices, host, trace.window_of(devices, host), [0])


def _raw(name_prefix):
    """Durations (ns) of device-0 'XLA Ops' events whose HLO text starts
    with `name_prefix`, read straight from the profile."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA))
    plane = pd.find_plane_with_name("/device:TPU:0")
    out = []
    for line in plane.lines:
        if line.name == "XLA Ops":
            out += [e.duration_ns for e in line.events
                    if e.name.startswith(name_prefix)]
    return out


def test_union_merges_overlapping_and_touching_intervals():
    assert trace.union_ns([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == \
        [(0, 4), (5, 7), (10, 11)]


def test_instruction_name_of_an_hlo_op():
    assert trace.instruction(
        '%fused_transform.1 = f32[256,128]{1,0} custom-call(f32[256,128] '
        '%pad.11), custom_call_target="tpu_custom_call"') == "fused_transform"
    assert trace.instruction("%copy = f32[2]{0} copy(f32[2]{0} %x)") == "copy"
    assert trace.instruction("jit_fn(123)") is None


def test_gaps_are_named_by_the_host_spans_open_at_their_middle():
    E = trace.Event
    host = {"main#0": [E("bench.wait", 0, 100), E("other", 0, 100)],
            "t#1": [E("bench.submit", 40, 20),
                    E("PJRT_Client_Compile", 70, 30)]}
    gaps = [(0, 10), (45, 55), (80, 90), (200, 210)]
    got = trace.label_gaps(gaps, host)
    assert [g[0] for g in got] == ["bench.wait", "bench.submit+bench.wait",
                                   "bench.wait+compile", "none"]
    assert got[0][1] == pytest.approx(10e-9)


def test_busy_is_the_union_of_program_runs_on_the_device(planes, summary):
    devices, _ = planes
    runs = devices[0]["XLA Modules"]
    assert len(runs) == 40                       # one run per block
    merged = trace.union_ns((e.start_ns, e.end_ns) for e in runs)
    assert summary.busy_s == pytest.approx(
        sum(b - a for a, b in merged) * 1e-9)
    # the 40 runs do not overlap, and took 181,836 ns in all
    assert summary.busy_s == pytest.approx(181836e-9)
    assert 0 < summary.busy_s < summary.window_s
    idle = sum(s for _, s in summary.idle_gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s)


def test_kernel_time_is_summed_by_the_kernels_name(summary):
    ft = summary.kernel_events("fused_transform")
    ea = summary.kernel_events("easi_apply")
    assert len(ft) == 40 and len(ea) == 40
    assert summary.kernel_s("fused_transform") == pytest.approx(
        sum(_raw("%fused_transform")) * 1e-9)
    assert summary.kernel_s("easi_apply") == pytest.approx(
        sum(_raw("%easi_apply")) * 1e-9)
    # a name that is no kernel of this path finds nothing
    assert summary.kernel_events("flash_attention") == []


def test_idle_gaps_fall_in_the_benchmarks_own_spans(summary):
    labels = {label for label, _ in summary.idle_gaps}
    assert labels <= {"bench.serve_and_update", "bench.to_host",
                      "bench.promote", "none"}
    top = summary.breakdown()["idle_gaps"][0]
    assert top[0] == "bench.serve_and_update"     # host dispatch dominates
    ops = dict((k, v) for k, v in summary.breakdown()["device_ops"])
    assert ops["fused_transform"] == pytest.approx(
        summary.kernel_s("fused_transform"))
    assert len(summary.breakdown()["device_ops"]) <= 10


def test_lm_step_programs_split_decode_from_prefill():
    from bench.metrics import readers

    E = trace.Event
    mods = ([E("jit_fn(1)", 0, 2e6)] + [E("jit_fn(2)", 3e6 + i * 2e6, 1e6)
                                        for i in range(5)]
            + [E("jit__argmax(3)", 1, 1)])
    s = trace.Summary(window_s=1.0, busy_s=0.5, modules=mods, ops=[],
                      idle_gaps=[])
    decode, prefill = readers.lm_runs({"trace": s})
    assert len(decode) == 5 and [e.name for e in prefill] == ["jit_fn(1)"]
