"""The benchmark refuses to measure without a TPU, and without the
program, and an unknown device kind has no peaks."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, spec

ROOT = spec.ROOT


def run_bench(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen1.5-4b.decode_heavy", "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_backend():
    p = run_bench(ROOT)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_check_device_raises_on_cpu():
    with pytest.raises(harness.NoDevice):
        harness.check_device(1)


def test_peaks_of_an_unknown_kind_are_an_error():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_s"] == 197e12
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v9 imaginary")


def test_every_cell_names_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.server["scheduler"] == "fcfs"
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert hasattr(spec.load_module("metrics", m["name"]), "read")
        ref = spec.load_module("references", cell.config["reference"])
        assert hasattr(ref, "run")
