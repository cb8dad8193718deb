"""The reduction from a profiler trace to busy time, program time and
host attribution: on hand-made events, and on a small trace recorded on
a TPU v5e (``record_trace.py``) with the host's record of its ticks."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import cost, devtrace, harness, spec
from bench.devtrace import Ev

DATA = Path(__file__).resolve().parent / "data"
CHIP = "smollm-135m.chat_poisson"
H, D = devtrace.HOST_PLANE, "/device:TPU:0"
OPS, MODS = devtrace.OPS_LINE, devtrace.MODULES_LINE


def test_union_and_cover():
    m = devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m == [(0, 3), (5, 8)]
    assert devtrace.covered(m, 2, 6) == 2
    assert devtrace.covered(m, 10, 20) == 0


def test_program_and_op_names():
    assert devtrace.program_name("jit_step_paged(1234)") == "step_paged"
    assert devtrace.program_name("jit_pstep_paged(9)") == "pstep_paged"
    assert devtrace.program_name("custom") == "custom"
    assert devtrace.op_label(
        "%fusion.1 = bf16[8,6912]{1,0:T(8,128)} fusion(...)") \
        == "%fusion.1 = bf16[8,6912]"


def synthetic():
    """Window [0, 100]: two ticks; the device runs a decode program
    (ops 12-30, with a loop op spanning two leaves) and a prefill
    program (ops 60-80), idles elsewhere."""

    return [
        Ev(H, "python", "bench.window", 0, 100),
        Ev(H, "python", "bench.tick", 10, 40),
        Ev(H, "python", "bench.tick", 50, 90),
        Ev(H, "python", "bench.idle", 40, 50),
        Ev(D, MODS, "jit_step_paged(1)", 12, 30),
        Ev(D, OPS, "%while.1 = (s32[])", 12, 30),
        Ev(D, OPS, "%fusion.2 = bf16[8]", 12, 20),
        Ev(D, OPS, "%fusion.3 = bf16[8]", 20, 30),
        Ev(D, MODS, "jit_pstep_paged(2)", 60, 80),
        Ev(D, OPS, "%fusion.4 = bf16[9]", 60, 80),
        Ev(D, OPS, "%outside = f32[1]", 150, 160),
    ]


def test_reduce_hand_made_events():
    r = devtrace.reduce(synthetic())
    assert r.window_ns == 100
    assert r.busy_ns == 18 + 20
    assert r.ticks == [(10, 40), (50, 90)]
    assert r.tick_busy_ns == [18, 20]
    assert r.programs == {"step_paged": 18, "pstep_paged": 20}
    assert r.tick_programs == [{"step_paged": 18}, {"pstep_paged": 20}]
    assert r.unattributed_ns(("step_paged", "pstep_paged")) == 0
    assert r.unattributed_ns(("step_paged",)) == 20
    # leaves only: the loop op is not counted beside its body
    assert dict(r.top_ops) == pytest.approx(
        {"%fusion.4 = bf16[9]": 20e-9, "%fusion.2 = bf16[8]": 8e-9,
         "%fusion.3 = bf16[8]": 10e-9})
    # idle gaps, named by the span over their middle: [0,12] none (the
    # harness loop),
    # [30,60] idle, [80,100] the second tick (its middle is its end)
    assert r.idle_by_span == pytest.approx(
        {"bench.loop": 12e-9, "bench.idle": 30e-9, "bench.tick": 20e-9})
    idle = reader("device_idle_share")(SimpleNamespace(red=r))
    assert idle == pytest.approx(62.0)
    host = reader("host_ms_per_tick")(SimpleNamespace(red=r))
    assert host == pytest.approx(((30 - 18) + (40 - 20)) / 2 * 1e-6)
    assert reader("host_ms_per_tick.open_loop")(SimpleNamespace(red=r)) \
        == host


def test_a_renamed_step_is_unattributed():
    """A refactor that renames the decode step leaves the readers
    nothing to read; its time shows as unattributed, not as zero."""

    named = harness.named_programs(spec.load_benchmark()["per_layer"])
    events = [e if e.name != "jit_step_paged(1)" else
              Ev(e.plane, e.line, "jit_step_fused(1)", e.start, e.end)
              for e in synthetic()]
    assert any(e.name == "jit_step_fused(1)" for e in events)
    r = devtrace.reduce(events)
    assert r.unattributed_ns(named) == 18
    assert devtrace.reduce(synthetic()).unattributed_ns(named) == 0


def test_reduce_needs_a_window_and_a_device():
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.reduce([e for e in synthetic()
                         if e.name != "bench.window"])
    with pytest.raises(ValueError, match="TPU"):
        devtrace.reduce([e for e in synthetic() if e.plane == H])


def reader(name):
    return spec.load_module("metrics", name).read


@pytest.fixture(scope="module")
def chip():
    events = devtrace.load(DATA / f"{CHIP}.xplane.pb.gz")
    rec = json.loads((DATA / f"{CHIP}.ticks.json").read_text())
    return events, devtrace.reduce(events), rec


def test_chip_trace_holds_what_the_reduction_reads(chip):
    events, red, rec = chip
    assert red.chips == ["/device:TPU:0"]
    assert {e.line for e in events if e.plane == D} == {OPS, MODS}
    assert len(red.ticks) == len(rec["ticks"]) >= 2
    assert 0 < red.busy_ns <= red.window_ns
    assert set(red.programs) >= {"step_paged", "pstep_paged"}
    # every busy nanosecond but the token read-back lies in the two
    # steps that the roofline readers name
    named = harness.named_programs(spec.load_benchmark()["per_layer"])
    assert named == {"step_paged", "pstep_paged"}
    assert 0 <= red.unattributed_ns(named) < 2e-2 * red.busy_ns
    # each tick's programs ran inside its span
    for (a, b), progs, busy in zip(red.ticks, red.tick_programs,
                                   red.tick_busy_ns):
        assert sum(progs.values()) <= (b - a)
        assert busy <= b - a


def test_chip_trace_metrics_match_the_recorded_run(chip):
    _, red, rec = chip
    ticks = [SimpleNamespace(decode=t["decode"],
                             prefill=[tuple(c) for c in t["prefill"]])
             for t in rec["ticks"]]
    cfg = json.loads((spec.BENCH / "configs" / "smollm-135m.json")
                     .read_text())
    peaks = spec.load_peaks(rec["device"]["kind"])
    run = SimpleNamespace(red=red, traced_ticks=ticks, peaks=peaks,
                          model=cost.Model.from_config(cfg))
    for name in ("decode_step_roofline", "prefill_step_roofline",
                 "device_idle_share", "host_ms_per_tick"):
        v = reader(name)(run)
        assert v == pytest.approx(rec["metrics"][name]["value"], rel=1e-9)
    for name in ("decode_step_roofline", "prefill_step_roofline"):
        assert 0 < reader(name)(run) <= 100


def test_roofline_reads_nothing_without_a_trace():
    run = SimpleNamespace(red=None, traced_ticks=[], peaks={})
    assert reader("decode_step_roofline")(run) is None
    assert reader("device_idle_share")(run) is None
