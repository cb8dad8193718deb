"""The program's own spans and scopes in a profiler trace
(``bench/spans.py``) and the per-layer metrics that read them: on
hand-made events, on the recorded trace of the first benchmark (a
program with neither, where every earlier reading stays as it was and
the new readers read nothing), and on small traces recorded on a TPU
v5e with the spans and scopes in place (``record_trace.py``)."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import devtrace, spans, spec
from bench.devtrace import Ev

DATA = Path(__file__).resolve().parent / "data"
OLD = "smollm-135m.chat_poisson"
H, D = devtrace.HOST_PLANE, "/device:TPU:0"
OPS, MODS = devtrace.OPS_LINE, devtrace.MODULES_LINE
NEW = ("host_prep_ms_per_tick", "host_commit_ms_per_tick",
       "pool_ms_per_decode")


def reader(name):
    return spec.load_module("metrics", name).read


def synthetic():
    """One tick [10, 100] whose serve.tick [12, 98] holds admission,
    backing, the decode step's pack, dispatch, read-back and commit;
    the device runs the decode step [28, 78] (three ops in three
    scopes) and the argmax [78.5, 79.5]."""

    return [
        Ev(H, "python", "bench.window", 0, 200),
        Ev(H, "python", "bench.tick", 10, 100),
        Ev(H, "python", "serve.tick", 12, 98),
        Ev(H, "python", "serve.admit", 13, 15),
        Ev(H, "python", "serve.back", 15, 20),
        Ev(H, "python", "serve.pack.decode", 20, 25),
        Ev(H, "python", "serve.dispatch.decode", 25, 30),
        Ev(H, "python", "serve.readback.decode", 30, 80),
        Ev(H, "python", "serve.commit.decode", 80, 95),
        Ev(D, MODS, "jit_step_paged(1)", 28, 78),
        Ev(D, OPS, "%while.1", 28, 78),
        Ev(D, OPS, "%a", 28, 50),
        Ev(D, OPS, "%b", 50, 60),
        Ev(D, OPS, "%c", 60, 78),
        Ev(D, MODS, "jit__argmax(2)", 78.5, 79.5),
        Ev(D, OPS, "%d", 78.5, 79.5),
    ]


PATHS = {D: {"%while.1": "jit(step_paged)/layers/while",
             "%a": "jit(step_paged)/layers/while/body/kv_write/eq",
             "%b": "jit(step_paged)/layers/while",
             "%c": "jit(step_paged)/layers/while/body/mlp/dot_general",
             "%d": "jit(_argmax)/reduce"}}


def test_scope_is_the_innermost_named_one():
    assert spans.scope_of("jit(s)/layers/while/body/attend/kv_read/x") \
        == "kv_read"
    assert spans.scope_of("jit(s)/layers/while") == "layers"
    assert spans.scope_of("jit(s)/jit(_take)/gather") == spans.UNSCOPED
    assert spans.scope_of("") == spans.UNSCOPED


def test_reduce_hand_made_events():
    r = spans.reduce(synthetic(), PATHS)
    assert r.ticks == [(10, 100)] and r.serve_spans == 7
    # idle: the tick 90 minus busy 50 + 1
    assert r.host_ns == [39]
    # admit 2 + back 5 + pack 5 + dispatch 3 (busy from 28)
    assert r.prep_ns == [15]
    # read-back 50 - 49 busy; commit 15
    assert r.commit_ns == [16]
    assert r.tick_self_ns == [1 + 3]
    assert r.outside_ns == [2 + 2]
    assert r.scopes == {"step_paged": {"kv_write": 22, "layers": 10,
                                       "mlp": 18},
                        "_argmax": {spans.UNSCOPED: 1}}
    assert r.calls == {"step_paged": 1, "_argmax": 1}
    assert r.idle_by_span == pytest.approx(
        {"serve.admit": 28e-9, "serve.readback.decode": 0.5e-9,
         "bench.loop": 120.5e-9})
    run = SimpleNamespace(red=devtrace.reduce(synthetic()), spans=r)
    assert reader("host_prep_ms_per_tick")(run) == pytest.approx(15e-6)
    assert reader("host_commit_ms_per_tick")(run) == pytest.approx(16e-6)
    assert reader("host_prep_ms_per_tick.open_loop")(run) \
        == reader("host_prep_ms_per_tick")(run)
    assert reader("host_commit_ms_per_tick.open_loop")(run) \
        == reader("host_commit_ms_per_tick")(run)
    assert reader("pool_ms_per_decode")(run) == pytest.approx(32e-6)


def test_overlapping_children_break_the_split():
    """The four parts add up to the tick's host time only while the
    children of serve.tick follow one another."""

    ev = synthetic() + [Ev(H, "python", "serve.pack.prefill", 20, 30)]
    r = spans.reduce(ev, PATHS)
    parts = r.prep_ns[0] + r.commit_ns[0] + r.tick_self_ns[0] \
        + r.outside_ns[0]
    assert parts != r.host_ns[0]


def test_readers_read_nothing_without_a_trace():
    run = SimpleNamespace(red=None)
    for name in NEW:
        assert reader(name)(run) is None


@pytest.fixture(scope="module")
def old():
    path = DATA / f"{OLD}.xplane.pb.gz"
    events = devtrace.load(path)
    rec = json.loads((DATA / f"{OLD}.ticks.json").read_text())
    return devtrace.reduce(events), spans.reduce(*spans.load(path)), rec


def test_old_trace_reads_as_before(old):
    """The first benchmark's trace, from a program with no serve.* span
    and no named scope: the reduction the accepted readers use reads
    exactly what it read when the trace was recorded."""

    red, _, rec = old
    assert red.top_ops == [
        ('%iota_reduce_fusion.2 = (pred[4096,16]', 0.059329370000000006),
        ('%fusion.190 = (f32[64,3,3,128]', 0.031019524000000003),
        ('%fusion.185 = bf16[65536,3,64]', 0.025526836),
        ('%fusion.187 = bf16[65536,3,64]', 0.02552679),
        ('%fusion.192 = bf16[64,3,3,128,64]', 0.024797686000000003),
        ('%fusion.184 = bf16[65536,3,64]', 0.024695869000000002),
        ('%fusion.182 = bf16[65536,3,64]', 0.024695652000000002),
        ('%fusion.191 = f32[64,3,3,128]', 0.024131759000000003),
        ('%bitcast_select_fusion.5 = (bf16[4096,3,16,64]', 0.02026149),
        ('%bitcast_select_fusion.4 = (bf16[4096,3,16,64]', 0.018751521)]
    assert red.programs == {"step_paged": 138254793.0,
                            "_argmax": 108868.0,
                            "pstep_paged": 295207088.0}
    assert red.busy_ns == 433568939.0
    for name in ("device_idle_share", "host_ms_per_tick"):
        assert reader(name)(SimpleNamespace(red=red)) \
            == rec["metrics"][name]["value"]


def test_old_trace_gives_the_new_readers_nothing(old):
    red, sr, _ = old
    assert sr.ticks == red.ticks and sr.serve_spans == 0
    # every op has its path, and none a scope
    assert set(sr.scopes) == {"step_paged", "pstep_paged", "_argmax"}
    assert all(set(t) == {spans.UNSCOPED} for t in sr.scopes.values())
    # the per-tick host time is the accepted reader's
    for (a, b), busy, host in zip(red.ticks, red.tick_busy_ns, sr.host_ns):
        assert (b - a) - busy == host
    run = SimpleNamespace(red=red, spans=sr)
    for name in NEW:
        assert reader(name)(run) is None


CELLS = ("qwen1.5-4b.decode_heavy", "smollm-135m.chat_poisson")


@pytest.fixture(scope="module", params=CELLS)
def chip(request):
    """A small trace of the cell recorded on a v5e with the program's
    spans and scopes in place, and the host's record of that run."""

    path = DATA / "spans" / f"{request.param}.xplane.pb.gz"
    events, paths = spans.load(path)
    rec = json.loads((DATA / "spans" / f"{request.param}.ticks.json")
                     .read_text())
    return (request.param, devtrace.reduce(devtrace.load(path)),
            spans.reduce(events, paths), paths, rec)


def test_host_time_splits_into_named_spans(chip):
    """Per tick: idle in the prep spans + in the commit spans + in
    serve.tick outside its children + in bench.tick outside serve.tick
    is the tick's host time as host_ms_per_tick reads it (to 1e-6 ms)."""

    _, red, sr, _, _ = chip
    assert sr.ticks == red.ticks and len(sr.ticks) >= 2
    assert sr.serve_spans >= 6 * len(sr.ticks)
    for (a, b), busy, p, c, s, o in zip(red.ticks, red.tick_busy_ns,
                                        sr.prep_ns, sr.commit_ns,
                                        sr.tick_self_ns, sr.outside_ns):
        assert abs((p + c + s + o) - ((b - a) - busy)) < 1.0


def test_the_engine_spans_name_the_idle_time(chip):
    _, _, sr, _, _ = chip
    unnamed = sum(sr.tick_self_ns) + sum(sr.outside_ns)
    assert unnamed <= 0.10 * sum(sr.host_ns)
    assert len(sr.idle_gaps) == 10
    assert all(n != "bench.tick" for n, _ in sr.idle_gaps)


def test_the_steps_are_scoped(chip):
    _, _, sr, _, _ = chip
    for prog in ("step_paged", "pstep_paged"):
        t = sr.scopes[prog]
        assert sr.calls[prog] >= 1
        assert t.get(spans.UNSCOPED, 0.0) <= 0.05 * sum(t.values())
        assert {"layers", "kv_write", "kv_read", "attend", "mlp"} <= set(t)


def test_pool_ops_fall_in_the_pool_scopes(chip):
    """Every decode-step op shaped like a layer's pool (or the stacked
    pools) lies in a scope ``pool_ms_per_decode`` counts: the scan's
    stacking, the float32 upcast and its copy among them."""

    name, _, _, paths, _ = chip
    cell = spec.load_cell(name)
    cfg, srv = cell.config, cell.server
    pool = (f"{srv['kv_pages']},{cfg['num_key_value_heads']},"
            f"{srv['page_size']},{cfg['head_dim']}]")
    pool_ops = {n: p for n, p in paths[D].items()
                if p.startswith("jit(step_paged)")
                and pool in devtrace.op_label(n)}
    assert any("dynamic-update-slice" in n for n in pool_ops)
    assert any(n.startswith("%convert") for n in pool_ops) \
        or name.startswith("smollm")
    POOL = spec.load_module("metrics", "pool_ms_per_decode").POOL
    assert all(spans.scope_of(p) in POOL for p in pool_ops.values())


def test_new_metrics_match_the_recorded_run(chip):
    name, red, sr, _, rec = chip
    run = SimpleNamespace(red=red, spans=sr)
    new = [m for m in rec["metrics"]
           if m.startswith(("host_prep", "host_commit", "pool_ms"))]
    assert len(new) == (3 if name.startswith("qwen") else 2)
    for m in new:
        v = reader(m)(run)
        assert v == pytest.approx(rec["metrics"][m]["value"], rel=1e-9)
        assert v > 0
