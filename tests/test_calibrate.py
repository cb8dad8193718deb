"""Platform calibration subsystem: fits, artifact round trip, resolver,
cache-fingerprint keying, cost-model repricing, trajectory, CLI, and the
TuningPlan calibration gate."""

import json

import pytest

from repro.calibrate import (DEFAULT_SPEC, CalibrationError, PlatformSpec,
                             calibration_hash, device_fingerprint,
                             ensure_calibrated, fit_bandwidth,
                             fit_dispatch_us, fit_link_bw, fit_peak_flops,
                             gap_from_stats, get_platform_spec, load_spec,
                             load_trajectory, run_trajectory,
                             set_platform_spec)
from repro.calibrate.cli import main as cli_main
from repro.calibrate.spec import SPEC_KIND, calibrated_replace
from repro.calibrate.trajectory import append_run


@pytest.fixture
def restore_spec():
    """Restore the session's pinned spec after a test that installs its
    own (set_platform_spec or ensure_calibrated(install=True))."""

    prev = get_platform_spec()
    yield
    set_platform_spec(prev)


def cpu_like() -> PlatformSpec:
    """A synthetic calibrated spec with CPU-magnitude constants."""

    return calibrated_replace(DEFAULT_SPEC, peak_flops=150e9, hbm_bw=20e9,
                              dispatch_us=80.0, backend="cpu",
                              device_kind="cpu")


# -- fits: pure + deterministic on synthetic sweeps -------------------------


def test_fit_peak_flops_takes_best_rung():
    sweep = [{"n": 128, "flops": 4e6, "us": 100.0},    # 4e10 FLOP/s
             {"n": 256, "flops": 32e6, "us": 200.0}]   # 1.6e11 FLOP/s
    assert fit_peak_flops(sweep) == pytest.approx(1.6e11)
    assert fit_peak_flops(list(reversed(sweep))) == pytest.approx(1.6e11)


def test_fit_bandwidth_reads_largest_footprint_not_cache():
    # the small (cache-resident) point is FASTER per byte; the fit must
    # report the main-memory point anyway
    sweep = [{"footprint": 1e6, "bytes": 3e6, "us": 10.0},     # 3e11 B/s
             {"footprint": 64e6, "bytes": 192e6, "us": 2000.0}]  # 9.6e10
    assert fit_bandwidth(sweep) == pytest.approx(9.6e10)


def test_fit_dispatch_is_median():
    assert fit_dispatch_us([9.0, 3.0, 5.0]) == 5.0


def test_fit_link_bw_single_device_is_none():
    assert fit_link_bw([]) is None


def test_empty_sweeps_raise():
    with pytest.raises(CalibrationError):
        fit_peak_flops([])
    with pytest.raises(CalibrationError):
        fit_bandwidth([])
    with pytest.raises(CalibrationError):
        fit_dispatch_us([])


# -- PlatformSpec artifact: round trip, staleness, hashes -------------------


def test_spec_json_round_trip(tmp_path):
    spec = cpu_like()
    path = spec.save(tmp_path / "spec.json")
    loaded = load_spec(path)
    assert loaded == spec
    assert loaded.calibration_hash() == spec.calibration_hash()


def test_stale_schema_rejected(tmp_path):
    doc = cpu_like().to_json()
    doc["schema"] = 0
    p = tmp_path / "stale.json"
    p.write_text(json.dumps(doc))
    with pytest.raises(CalibrationError, match="stale"):
        load_spec(p)


def test_foreign_kind_rejected(tmp_path):
    p = tmp_path / "foreign.json"
    p.write_text(json.dumps({"schema": 1, "entries": {}}))
    with pytest.raises(CalibrationError, match="not a platform-spec"):
        load_spec(p)


def test_missing_artifact_is_oserror(tmp_path):
    with pytest.raises(OSError):
        load_spec(tmp_path / "nope.json")


def test_default_hash_is_literal_default():
    assert DEFAULT_SPEC.calibration_hash() == "default"
    assert cpu_like().calibration_hash() != "default"


def test_derived_properties():
    assert DEFAULT_SPEC.ici_bw == DEFAULT_SPEC.links * DEFAULT_SPEC.link_bw
    assert DEFAULT_SPEC.dispatch_s == pytest.approx(50e-6)


# -- resolver ----------------------------------------------------------------


def test_override_wins(restore_spec):
    spec = cpu_like()
    set_platform_spec(spec)
    assert get_platform_spec() is spec


def test_disk_artifact_resolves_when_device_matches(
        restore_spec, tmp_path, monkeypatch):
    dev = device_fingerprint()
    spec = calibrated_replace(DEFAULT_SPEC, peak_flops=1e11,
                              backend=dev["backend"],
                              device_kind=dev["device_kind"])
    spec.save(tmp_path / "spec.json")
    monkeypatch.setenv("REPRO_PLATFORM_SPEC", str(tmp_path / "spec.json"))
    set_platform_spec(None)            # re-enable disk resolution
    assert get_platform_spec().calibration_hash() == spec.calibration_hash()


def test_foreign_device_artifact_falls_back_to_default(
        restore_spec, tmp_path, monkeypatch):
    spec = calibrated_replace(DEFAULT_SPEC, peak_flops=1e11,
                              backend="not-a-backend",
                              device_kind="not-a-device")
    spec.save(tmp_path / "spec.json")
    monkeypatch.setenv("REPRO_PLATFORM_SPEC", str(tmp_path / "spec.json"))
    set_platform_spec(None)
    assert get_platform_spec() is DEFAULT_SPEC


def test_no_artifact_falls_back_to_default(
        restore_spec, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLATFORM_SPEC", str(tmp_path / "none.json"))
    set_platform_spec(None)
    assert get_platform_spec() is DEFAULT_SPEC


def _on_device(monkeypatch, tmp_path, backend, kind):
    monkeypatch.setattr("repro.calibrate.spec.device_fingerprint",
                        lambda: {"backend": backend, "device_kind": kind})
    monkeypatch.setenv("REPRO_PLATFORM_SPEC", str(tmp_path / "none.json"))
    set_platform_spec(None)


def test_v5e_kind_as_jax_reports_it_resolves_to_v5e_peaks(
        restore_spec, tmp_path, monkeypatch):
    """JAX names the v5e "TPU v5 lite"; the datasheet table is keyed by
    that string."""

    _on_device(monkeypatch, tmp_path, "tpu", "TPU v5 lite")
    spec = get_platform_spec()
    assert spec.device_kind == "TPU v5 lite"
    assert (spec.peak_flops, spec.hbm_bw) == (197e12, 819e9)


def test_unknown_tpu_kind_raises(restore_spec, tmp_path, monkeypatch):
    """An accelerator with no table entry is an error, never v5e numbers
    — and the tuning cache cannot key an entry to it either."""

    from repro.calibrate import UnknownDeviceError
    from repro.tune.cache import platform_fingerprint
    _on_device(monkeypatch, tmp_path, "tpu", "TPU v99")
    with pytest.raises(UnknownDeviceError, match="TPU v99"):
        get_platform_spec()
    with pytest.raises(UnknownDeviceError):
        platform_fingerprint()


def test_device_discovery_errors_propagate(monkeypatch):
    """A backend that fails to start is not reported as "unknown"."""

    from repro.tune.cache import platform_fingerprint

    def broken():
        raise RuntimeError("backend failed to initialize")
    monkeypatch.setattr("jax.devices", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        device_fingerprint()
    with pytest.raises(RuntimeError, match="failed to initialize"):
        platform_fingerprint()


# -- tuning-cache keying: calibrated never collides with default ------------


def test_cache_keys_differ_default_vs_calibrated(restore_spec):
    from repro.kernels.tuned_reduction.ops import ReductionTunable
    from repro.tune.cache import cache_key, platform_fingerprint

    tb = ReductionTunable(1024)
    set_platform_spec(DEFAULT_SPEC)
    k_default, doc_default = cache_key(tb, "grid")
    assert platform_fingerprint()["calibration"] == "default"

    set_platform_spec(cpu_like())
    k_cal, doc_cal = cache_key(tb, "grid")
    assert doc_cal["platform"]["calibration"] == \
        cpu_like().calibration_hash()
    assert k_default != k_cal


# -- cost-model repricing under a calibrated spec ---------------------------


def test_spec_depth_ranking_flips_under_cpu_constants(restore_spec):
    from repro.runtime.speculate import SpecDepthTunable
    tb = SpecDepthTunable(param_bytes=2_000_000_000, layers=24,
                          d_model=2048, kv_width=256, context=2048,
                          prompt_len=128, requests=32, mean_new=128,
                          batch=8, max_depth=8, drafters=("ngram",))
    set_platform_spec(DEFAULT_SPEC)
    pick_default = min(tb.space(), key=tb.cost)
    set_platform_spec(cpu_like())
    pick_cpu = min(tb.space(), key=tb.cost)
    # on v5e constants deep speculation pays; on CPU-magnitude
    # constants the extra verify FLOPs dominate and depth collapses
    assert pick_default["depth"] > pick_cpu["depth"]


def test_step_time_scales_with_spec():
    from repro.core.tpu_machine import TPUConfig, TPUWorkload, step_time
    w = TPUWorkload(params=10**9, active_params=10**9, layers=24,
                    d_model=2048, seq=1024, global_batch=64, vocab=32000)
    c = TPUConfig(dp=4, tp=2)
    fast = step_time(w, c, spec=DEFAULT_SPEC)
    slow = step_time(w, c, spec=cpu_like())
    assert slow["total"] > fast["total"]
    assert slow["compute"] == pytest.approx(
        fast["compute"] * DEFAULT_SPEC.peak_flops / cpu_like().peak_flops)


def test_gmt_from_spec_bridges_to_wave_model():
    from repro.core.wave_model import WaveParams, gmt_from_spec
    g_default = gmt_from_spec(DEFAULT_SPEC)
    assert g_default == round(DEFAULT_SPEC.peak_flops * 4
                              / DEFAULT_SPEC.hbm_bw)
    g_cpu = gmt_from_spec(cpu_like())
    assert g_cpu < g_default
    p = WaveParams.from_platform(64, spec=cpu_like())
    assert p.GMT == g_cpu


def test_roofline_analyze_with_spec():
    from repro.launch.roofline import analyze
    rec = {"arch": "smollm-135m", "shape": "train_4k", "mesh": "1x1",
           "status": "ok", "n_devices": 1,
           "cost": {"flops": 1e12, "bytes_accessed": 1e9},
           "collectives": {"total_bytes": 0}}
    fast = analyze(rec, spec=DEFAULT_SPEC)
    slow = analyze(rec, spec=cpu_like())
    assert slow.compute_s > fast.compute_s
    assert slow.memory_s > fast.memory_s


# -- ensure_calibrated: load-or-probe ---------------------------------------


TINY_PROBES = dict(matmul_sizes=(16,), footprints=(1 << 14,),
                   dispatch_reps=2)


def test_ensure_calibrated_probes_then_loads(restore_spec, tmp_path):
    path = tmp_path / "spec.json"
    spec1, probed1 = ensure_calibrated(path, **TINY_PROBES)
    assert probed1 and spec1.source == "calibrated"
    assert path.exists()
    # second call: pure artifact load, zero probes
    spec2, probed2 = ensure_calibrated(path, **TINY_PROBES)
    assert not probed2
    assert spec2.calibration_hash() == spec1.calibration_hash()
    # the loaded spec became the active one
    assert get_platform_spec().calibration_hash() == \
        spec1.calibration_hash()
    # fitted constants actually differ from the v5e datasheet
    assert spec1.peak_flops != DEFAULT_SPEC.peak_flops
    assert spec1.hbm_bw != DEFAULT_SPEC.hbm_bw


def test_ensure_calibrated_force_reprobes(restore_spec, tmp_path):
    path = tmp_path / "spec.json"
    ensure_calibrated(path, **TINY_PROBES)
    _, probed = ensure_calibrated(path, force=True, **TINY_PROBES)
    assert probed


# -- trajectory ---------------------------------------------------------------


def synthetic_stats(modeled_cfg, measured_cfg, model_us, best_us):
    return {"modeled_pick": {"config": modeled_cfg, "modeled": 1.0,
                             "measured": model_us},
            "measured_pick": {"config": measured_cfg, "modeled": 2.0,
                              "measured": best_us},
            "candidates": [{}, {}]}


def test_gap_from_stats():
    rec = gap_from_stats(synthetic_stats({"b": 1}, {"b": 2}, 150.0, 100.0))
    assert rec["gap"] == pytest.approx(1.5)
    assert not rec["agree"]
    agree = gap_from_stats(synthetic_stats({"b": 1}, {"b": 1}, 100.0, 100.0))
    assert agree["agree"] and agree["gap"] == 1.0


def test_gap_needs_measure_stats():
    with pytest.raises(CalibrationError):
        gap_from_stats({"evaluated": 3})


def test_append_run_accumulates(tmp_path):
    path = tmp_path / "BENCH_calibration.json"
    append_run([{"tunable": "a", "gap": 1.0}], path=path)
    append_run([{"tunable": "b", "gap": 1.2}], path=path)
    doc = load_trajectory(path)
    assert len(doc["runs"]) == 2
    assert doc["runs"][0]["tunables"][0]["tunable"] == "a"
    assert doc["runs"][1]["tunables"][0]["tunable"] == "b"
    assert doc["runs"][0]["calibration"] == "default"   # session pin


def test_trajectory_refuses_foreign_file(tmp_path):
    p = tmp_path / "BENCH_calibration.json"
    p.write_text(json.dumps({"kind": "something-else"}))
    with pytest.raises(CalibrationError):
        load_trajectory(p)


def test_run_trajectory_real_measure(tmp_path):
    from repro.kernels.tuned_reduction.ops import ReductionTunable
    path = tmp_path / "BENCH_calibration.json"
    run = run_trajectory([("reduce_4k", ReductionTunable(4096))],
                         path=path, top_k=1, repeats=1)
    assert run["tunables"][0]["tunable"] == "reduce_4k"
    assert run["tunables"][0]["gap"] >= 1.0
    assert len(load_trajectory(path)["runs"]) == 1


# -- CLI ----------------------------------------------------------------------


def test_cli_run_twice_is_pure_load(restore_spec, tmp_path, capsys,
                                    monkeypatch):
    path = tmp_path / "spec.json"
    monkeypatch.setattr(
        "repro.calibrate.probes.run_calibration",
        lambda quick=False, **kw: calibrated_replace(
            DEFAULT_SPEC, peak_flops=1e11, probes={"matmul": [1]},
            **device_fingerprint()))
    assert cli_main(["--spec", str(path), "run", "--json"]) == 0
    first = json.loads(capsys.readouterr().out)
    assert first["status"] == "calibrated"
    assert cli_main(["--spec", str(path), "run", "--json"]) == 0
    second = json.loads(capsys.readouterr().out)
    assert second["status"] == "loaded"
    assert second["probes_run"] == 0
    assert second["calibration"] == first["calibration"]


def test_cli_show_and_export(restore_spec, tmp_path, capsys):
    path = tmp_path / "spec.json"
    dev = device_fingerprint()
    calibrated_replace(DEFAULT_SPEC, peak_flops=1e11, **dev).save(path)
    assert cli_main(["--spec", str(path), "show", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["source"] == "calibrated"
    out = tmp_path / "exported.json"
    assert cli_main(["--spec", str(path), "export", str(out)]) == 0
    assert load_spec(out).calibration_hash() == doc["calibration"]


def test_cli_errors_are_exit_code_1(tmp_path, capsys):
    assert cli_main(["--spec", str(tmp_path / "no.json"),
                     "export", str(tmp_path / "out.json")]) == 1
    assert "error" in capsys.readouterr().err


# -- TuningPlan calibration gate --------------------------------------------


def test_plan_calibrate_gate_uses_artifact(restore_spec, tmp_path,
                                           monkeypatch):
    from repro.tune import TuningCache, TuningPlan

    dev = device_fingerprint()
    spec = calibrated_replace(DEFAULT_SPEC, peak_flops=1e11, hbm_bw=1e10,
                              **dev)
    spec.save(tmp_path / "spec.json")
    monkeypatch.setenv("REPRO_PLATFORM_SPEC", str(tmp_path / "spec.json"))

    plan = TuningPlan.from_spec({
        "name": "cal-gate", "calibrate": True,
        "jobs": [{"tunable": "kernels.tuned_reduction",
                  "params": {"n": 4096}, "engine": "grid"}]})
    assert plan.require_calibration

    lines: list[str] = []
    cache = TuningCache(tmp_path / "cache.json")
    report = plan.run(cache=cache, progress=lines.append, save=False)
    assert report.ok
    # the gate loaded the artifact (no probes) and installed it before
    # any job: the cached entry is keyed under the calibrated hash
    assert any("loaded" in ln for ln in lines)
    assert get_platform_spec().calibration_hash() == spec.calibration_hash()
    entry = next(iter(cache.entries.values()))
    assert entry["fingerprint"]["platform"]["calibration"] == \
        spec.calibration_hash()


def test_plan_without_calibrate_key_stays_ungated():
    from repro.tune import TuningPlan
    plan = TuningPlan.from_spec({"name": "plain", "jobs": []})
    assert not plan.require_calibration
