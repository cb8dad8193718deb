"""The engine's host spans on the profiler's clock: ``Server.tick``'s
fixed ``serve.*`` spans in a ``jax.profiler`` trace (read back with
``ProfileData``), their nesting, their copy on an attached recorder,
the requests' lifecycle stamps, and ``launch/serve --profile DIR``."""

from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import build_model
from repro.obs import Observability, parse_trace, spans_from_events
from repro.obs import validate_trace
from repro.runtime.serve import Server

HOST = "/host:CPU"


@pytest.fixture(scope="module")
def model():
    cfg = get_config("smollm-135m").reduced().replace(
        logits_dtype="float32")
    api = build_model(cfg)
    return api, api.init(jax.random.PRNGKey(0))


def _drain(api, params, obs=None, **kw):
    srv = Server(api, params, batch=2, context=48, prefill_chunk=8,
                 paged=True, page_size=4, obs=obs, **kw)
    reqs = [srv.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3][:n], max_new=m)
            for n, m in ((10, 4), (5, 6), (7, 3))]
    srv.run_until_drained()
    return srv, reqs


def _host_spans(trace_dir: Path) -> list[tuple[str, float, float]]:
    (xp,) = sorted(trace_dir.glob("**/*.xplane.pb"))
    pd = ProfileData.from_file(str(xp))
    out = []
    for plane in pd.planes:
        if plane.name != HOST:
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns) for e in line.events
                    if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _check_nesting(spans):
    """Every span lies inside one ``serve.tick``; inside a tick the
    children follow one another without overlap."""

    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert ticks
    for a, b in zip(ticks, ticks[1:]):
        assert a[2] <= b[1]
    for tk in ticks:
        kids = [s for s in spans if s[0] != "serve.tick"
                and tk[1] <= s[1] and s[2] <= tk[2]]
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1], (a, b)
    n_kids = sum(1 for s in spans if s[0] != "serve.tick")
    inside = sum(1 for s in spans if s[0] != "serve.tick"
                 and any(t[1] <= s[1] and s[2] <= t[2] for t in ticks))
    assert inside == n_kids


STEP_SPANS = ("pack", "dispatch", "readback", "commit")


@pytest.mark.parametrize("kind", ["plain", "speculative"])
def test_tick_spans_land_in_the_profiler_trace(model, tmp_path, kind):
    api, params = model
    kw = {}
    want = {"serve.tick", "serve.admit", "serve.back"}
    want |= {f"serve.{p}.{s}" for p in STEP_SPANS
             for s in ("decode", "prefill")}
    if kind == "speculative":
        # a window adds reclamation; the n-gram drafter adds the verify
        # and commit-prefill steps
        api = build_model(api.cfg.replace(window=8))
        kw = dict(speculate="ngram", spec_depth=2)
        want |= {"serve.draft", "serve.trim",
                 "serve.pack.verify", "serve.dispatch.verify",
                 "serve.readback.verify", "serve.commit.verify",
                 "serve.pack.accept", "serve.dispatch.accept",
                 "serve.commit.accept"}
    _drain(api, params, **kw)          # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        srv, _ = _drain(api, params, **kw)
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    if kind == "speculative":
        assert srv.spec_ticks > 0
    assert want <= names, sorted(want - names)
    assert names <= want | {"serve.draft", "serve.trim"}
    _check_nesting(spans)


def test_spans_on_the_recorder_keep_tokens_and_a_valid_trace(model):
    api, params = model
    _, plain = _drain(api, params)
    obs = Observability(trace=True, metrics=True, monitor=True)
    srv, traced = _drain(api, params, obs=obs)
    assert [r.out for r in traced] == [r.out for r in plain]
    doc = obs.export()
    assert validate_trace(doc) == []
    engine = [ev for ev in parse_trace(doc) if tuple(ev["track"]) ==
              ("engine",) and ev["ph"] in "BE"]
    roots = spans_from_events(engine)
    assert {r.name for r in roots} == {"serve.tick"}
    ticked = [r for r in roots if any(c.name == "tick" for c in r.children)]
    assert len(ticked) == srv.ticks
    for r in ticked:
        kids = [c.name for c in r.children]
        assert kids[0] == "serve.admit" and "serve.back" in kids
        (tick,) = [c for c in r.children if c.name == "tick"]
        steps = [c.name for c in tick.children]
        assert steps and all(n.startswith(("serve.pack.", "serve.dispatch.",
                                           "serve.readback.",
                                           "serve.commit."))
                             for n in steps)


def test_lifecycle_stamps(model):
    api, params = model
    _, reqs = _drain(api, params)
    for r in reqs:
        assert r.t_submitted is not None and r.t_admitted is not None
        assert r.t_submitted <= r.t_admitted
    # a preempted request keeps its first admission
    srv = Server(api, params, batch=1, context=48, paged=True, page_size=4,
                 prefill_chunk=8, scheduler="priority")
    rb = srv.submit(list(range(1, 17)), max_new=6, slo="batch")
    for _ in range(4):
        srv.tick()
    first = rb.t_admitted
    assert first is not None
    ri = srv.submit([7, 5, 3, 2], max_new=4, slo="interactive",
                    deadline=20.0)
    srv.run_until_drained()
    assert rb.preempted >= 1 and rb.t_admitted == first
    assert ri.t_submitted <= ri.t_admitted
    assert rb.t_submitted <= first < ri.t_submitted


def test_launch_serve_profile_writes_a_trace(tmp_path, monkeypatch):
    from repro.launch import serve as launch

    monkeypatch.setattr(launch, "use_compile_cache", lambda: None)
    out = tmp_path / "prof"
    srv = launch.main(["--arch", "smollm-135m", "--preset", "smoke",
                       "--requests", "2", "--batch", "2", "--context", "32",
                       "--max-new", "2", "--paged", "--profile", str(out)])
    assert len(srv.completed) == 2
    names = {s[0] for s in _host_spans(out)}
    assert "serve.tick" in names and "serve.dispatch.decode" in names
