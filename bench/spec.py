"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix, cell or
metric sits in a file of its own, found by name:

* ``bench/configs/<config>.json``   sizes, dtype, source (the ``file``
  that ``BENCHMARK.json`` lists for the configuration);
* ``bench/traffic/<traffic>.json``  parameters of the one generator;
* ``bench/cells/<workload>.json``   the cell's server settings, its
  correctness sample and limits;
* ``bench/metrics/<metric>.py``     a reader with ``read(run)``;
* ``bench/references/<name>.py``    a plain float32 reference forward;
* ``bench/costs/<name>.py``         a ``Model`` with ``from_config(cfg)``,
  ``decode(keys)`` and ``prefill(chunks)``: a configuration's count of
  operations and bytes, which the roofline and MFU readers read.

A configuration file may also hold these optional keys:

* ``sliding_window``  the attention window; ``harness.arch_config``
  sets ``ArchConfig.window`` from it (absent: the registry's);
* ``program``         ``ArchConfig`` field overrides, a dataclass field's
  as a dict of its fields (a chip share: experts held, a vocabulary
  slice); read by ``harness.arch_config``;
* ``init``            weight rules by leaf name, ``{"contract": [axes]}``
  or ``{"mean": m, "std": s}``; read by ``weights.make_params`` before
  its own;
* ``param_dtypes``    leaf name to dtype, for parameters not in
  ``dtype``; read by ``harness.check_dtypes`` in ``harness.build``;
* ``state_dtypes``    the same for decode-state leaves not in
  ``kv_dtype`` (an SSM's float32 recurrence); read there too;
* ``cost``            the name of its ``bench/costs/<name>.py``; read by
  ``harness.cost_model`` (absent: the dense decoder's ``bench/cost.py``).
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    server: dict
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        server=json.loads(
            (root / "bench" / "cells" / f"{workload}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def load_module(kind: str, name: str, root: Path = ROOT):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""

    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(kind: str) -> dict:
    """The peak table row for a device kind as JAX reports it; an
    unknown kind is an error, never a default."""

    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][kind]
