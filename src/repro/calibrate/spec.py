"""Measured platform specification — the constants every cost model
prices against.

The paper's premise is model-checking a *faithful* platform model; a
model is only as faithful as its constants.  :class:`PlatformSpec` is
the single source of those constants — peak FLOP/s, memory bandwidth,
interconnect bandwidth, dispatch latency — either the **defaults**
(TPU v5e datasheet numbers, exactly the values the repo hardcoded
before this subsystem) or **calibrated** values fitted from the
microbenchmark probes in :mod:`repro.calibrate.probes` and persisted as
a schema-versioned JSON artifact.

Not to be confused with :class:`repro.core.platform.PlatformSpec` (the
abstract Promela NP/GMT platform): that one parameterizes the *process
model*, this one carries the *physical device* numbers that the serving
and distributed cost models divide by.  :func:`~repro.core.wave_model.\
gmt_from_spec` bridges the two — it derives the abstract GMT ratio from
a measured spec.

Resolution order (:func:`get_platform_spec`):

1. an explicitly installed spec (:func:`set_platform_spec` — tests,
   benches, and the CLI use this),
2. a calibration artifact on disk (``$REPRO_PLATFORM_SPEC`` or
   ``~/.cache/repro/platform_spec.json``) whose schema is current and
   whose backend/device match the running process,
3. the datasheet peaks of the running device, looked up in
   :data:`DEVICE_SPECS` by the kind JAX reports — a TPU whose kind is
   not in the table is an :class:`UnknownDeviceError`, never another
   chip's numbers.  On the CPU, where nothing runs at the speed of a
   chip, the cost models keep modelling the target chip:
   :data:`DEFAULT_SPEC` (the TPU v5e constants).

:meth:`PlatformSpec.calibration_hash` is mixed into the tuning-cache
platform fingerprint (:func:`repro.tune.cache.platform_fingerprint`) so
configs tuned under calibrated constants never collide with
default-constant entries.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from dataclasses import asdict, dataclass, field, replace
from pathlib import Path
from typing import Any, Mapping

SPEC_SCHEMA = 1
SPEC_KIND = "repro.calibrate/platform-spec"
_ENV_VAR = "REPRO_PLATFORM_SPEC"
_DEFAULT_PATH = "~/.cache/repro/platform_spec.json"

# the constants that identify a calibration (everything a cost model
# divides by); probe metadata is provenance, not identity
_FITTED_FIELDS = ("peak_flops", "hbm_bw", "link_bw", "links", "dci_bw",
                  "dispatch_us")


class CalibrationError(ValueError):
    """The file is not a usable calibration artifact (wrong kind or a
    stale schema version)."""


class UnknownDeviceError(RuntimeError):
    """The running accelerator has no entry in :data:`DEVICE_SPECS`."""


def device_fingerprint() -> dict[str, str]:
    """Backend + chip generation of the running process (the device part
    of :func:`repro.tune.cache.platform_fingerprint`).  A backend that
    fails to start raises: no device is ever reported as unknown."""

    import jax
    return {"backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind}


@dataclass(frozen=True)
class PlatformSpec:
    """Fitted (or default) device constants + probe provenance."""

    peak_flops: float            # FLOP/s per device (bf16 on TPU)
    hbm_bw: float                # main-memory bytes/s per device
    link_bw: float = 50e9        # bytes/s per interconnect link
    links: int = 4               # usable links per device
    dci_bw: float = 25e9         # inter-pod bytes/s per device pair
    dispatch_us: float = 50.0    # per-dispatch host->device latency
    source: str = "default"      # "default" | "calibrated"
    backend: str = ""            # JAX backend the probes ran on
    device_kind: str = ""        # chip generation string
    created: float = 0.0         # unix time of the calibration run
    # raw probe sweeps + which constants were actually fitted (an
    # unfittable probe — e.g. the collective probe on one device —
    # leaves its constant at the default and is absent from "fitted")
    probes: Mapping[str, Any] = field(default_factory=dict)
    schema: int = SPEC_SCHEMA

    @property
    def ici_bw(self) -> float:
        """Aggregate interconnect bandwidth (links x per-link)."""

        return self.links * self.link_bw

    @property
    def dispatch_s(self) -> float:
        """Dispatch latency in seconds (cost models work in seconds)."""

        return self.dispatch_us * 1e-6

    def calibration_hash(self) -> str:
        """Short stable id of the fitted constants; the literal string
        ``"default"`` for the uncalibrated spec, so default-constant
        cache fingerprints stay byte-identical across hosts."""

        if self.source == "default":
            return "default"
        doc = {f: getattr(self, f) for f in _FITTED_FIELDS}
        doc["backend"] = self.backend
        doc["device_kind"] = self.device_kind
        blob = json.dumps(doc, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:12]

    def constants(self) -> dict[str, float]:
        """The fitted constants as a plain dict (CLI/bench reporting)."""

        return {f: getattr(self, f) for f in _FITTED_FIELDS}

    # -- JSON round-trip ----------------------------------------------------

    def to_json(self) -> dict[str, Any]:
        doc = asdict(self)
        doc["probes"] = dict(self.probes)
        doc["kind"] = SPEC_KIND
        return doc

    @classmethod
    def from_json(cls, doc: Mapping[str, Any]) -> "PlatformSpec":
        if doc.get("kind") != SPEC_KIND:
            raise CalibrationError(
                f"not a platform-spec artifact (kind="
                f"{doc.get('kind')!r}, want {SPEC_KIND!r})")
        if doc.get("schema") != SPEC_SCHEMA:
            raise CalibrationError(
                f"stale platform-spec schema {doc.get('schema')!r} "
                f"(current {SPEC_SCHEMA}); re-run "
                f"`python -m repro.calibrate run --force`")
        fields = {k: v for k, v in doc.items() if k != "kind"}
        return cls(**fields)

    def save(self, path: str | os.PathLike) -> Path:
        """Write the spec as a JSON artifact (atomic replace)."""

        path = Path(path).expanduser()
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                                   prefix=path.name, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(self.to_json(), f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path

    def matches_device(self) -> bool:
        """Does this calibration describe the running process's device?"""

        dev = device_fingerprint()
        return (self.backend == dev["backend"]
                and self.device_kind == dev["device_kind"])


def load_spec(path: str | os.PathLike) -> PlatformSpec:
    """Load a calibration artifact; :class:`CalibrationError` on a
    foreign file or a stale schema, ``OSError`` when missing."""

    text = Path(path).expanduser().read_text()
    try:
        doc = json.loads(text)
    except ValueError as e:
        raise CalibrationError(f"unparseable platform spec {path}: {e}")
    if not isinstance(doc, Mapping):
        raise CalibrationError(f"platform spec {path} is not a JSON object")
    return PlatformSpec.from_json(doc)


# the TPU v5e constants every cost model used before calibration existed
# (197 TFLOP/s bf16 MXU, 819 GB/s HBM, 4 x 50 GB/s ICI, 25 GB/s DCI,
# 50 us dispatch; Google Cloud documentation, "TPU v5e") — now stated
# exactly once, here.  JAX reports the v5e as "TPU v5 lite".
DEFAULT_SPEC = PlatformSpec(peak_flops=197e12, hbm_bw=819e9,
                            link_bw=50e9, links=4, dci_bw=25e9,
                            dispatch_us=50.0, source="default",
                            backend="tpu", device_kind="TPU v5 lite")

# datasheet peaks keyed by the device kind JAX reports
DEVICE_SPECS: Mapping[str, PlatformSpec] = {
    DEFAULT_SPEC.device_kind: DEFAULT_SPEC,
}


def datasheet_spec(dev: Mapping[str, str]) -> PlatformSpec:
    """The uncalibrated spec for ``dev``: its :data:`DEVICE_SPECS` entry
    on an accelerator, :data:`DEFAULT_SPEC` (the modelled target chip)
    on the CPU.  An accelerator not in the table raises."""

    if dev["backend"] == "cpu":
        return DEFAULT_SPEC
    spec = DEVICE_SPECS.get(dev["device_kind"])
    if spec is None:
        raise UnknownDeviceError(
            f"no datasheet spec for {dev['backend']} device "
            f"{dev['device_kind']!r} (known: {sorted(DEVICE_SPECS)}); add "
            f"it to repro.calibrate.spec.DEVICE_SPECS or install a "
            f"calibration with `python -m repro.calibrate run`")
    return spec


def spec_path(path: str | os.PathLike | None = None) -> Path:
    """The calibration-artifact location: explicit ``path``, else
    ``$REPRO_PLATFORM_SPEC``, else ``~/.cache/repro/platform_spec.json``."""

    if path is None:
        path = os.environ.get(_ENV_VAR, _DEFAULT_PATH)
    return Path(path).expanduser()


_active_spec: PlatformSpec | None = None
_loaded: tuple[Path, PlatformSpec] | None = None


def set_platform_spec(spec: PlatformSpec | None) -> PlatformSpec | None:
    """Install ``spec`` as the process-wide platform spec (``None``
    re-enables disk/default resolution); returns the previous override
    so callers can restore it."""

    global _active_spec, _loaded
    prev = _active_spec
    _active_spec = spec
    _loaded = None                    # force a re-read on next resolve
    return prev


def get_platform_spec() -> PlatformSpec:
    """Resolve the active platform spec (see module docstring for the
    order).  A disk artifact is only honored when its schema is current
    AND it was calibrated on this process's backend/device — a spec
    fitted on a TPU must not price CPU runs.  Raises
    :class:`UnknownDeviceError` on an accelerator with neither."""

    global _loaded
    if _active_spec is not None:
        return _active_spec
    path = spec_path()
    if _loaded is not None and _loaded[0] == path:
        return _loaded[1]
    dev = device_fingerprint()
    resolved: PlatformSpec | None = None
    try:
        spec = load_spec(path)
        if spec.matches_device():
            resolved = spec
    except (OSError, CalibrationError):
        resolved = None
    if resolved is None:
        resolved = datasheet_spec(dev)
    _loaded = (path, resolved)
    return resolved


def calibration_hash() -> str:
    """The active spec's calibration id (``"default"`` when running on
    defaults) — the value the tuning-cache platform fingerprint mixes
    in."""

    return get_platform_spec().calibration_hash()


def calibrated_replace(spec: PlatformSpec, **fitted: Any) -> PlatformSpec:
    """A copy of ``spec`` with fitted constants applied and the source
    flipped to ``"calibrated"`` (probe helpers build through here)."""

    return replace(spec, source="calibrated", created=time.time(), **fitted)


__all__ = ["PlatformSpec", "CalibrationError", "UnknownDeviceError",
           "DEFAULT_SPEC", "DEVICE_SPECS", "SPEC_SCHEMA", "SPEC_KIND",
           "load_spec", "spec_path", "get_platform_spec", "set_platform_spec",
           "calibration_hash", "calibrated_replace",
           "device_fingerprint"]
