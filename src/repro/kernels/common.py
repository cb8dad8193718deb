"""Shared kernel-wrapper helpers (deduplicated from the per-kernel
``ops.py`` files).

Every Pallas wrapper takes ``interpret: bool | None``; ``None`` means
"interpret mode iff no real accelerator" so the same call sites run on
CPU (interpret) and TPU (compiled) unchanged.

:func:`time_fn` is the one wall-clock discipline every Tunable's
``measure(cfg)`` uses: warmup calls absorb compilation, each timed call
blocks on its result, and the median survives scheduler noise.
"""

from __future__ import annotations

import time

import jax
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM every Pallas kernel here asks the TPU compiler for, and the
# budget each ``tuning_space`` counts its blocks against.  Stating it
# explicitly keeps the lattice independent of the compiler's default scope
# (which refused the larger blocks); a v5e TensorCore has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 32 * 2**20


def tpu_compiler_params() -> pltpu.CompilerParams:
    """Mosaic parameters shared by the kernels: the VMEM scope above."""

    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def fits_vmem(*, blocks: int, scratch: int = 0) -> bool:
    """Whether a kernel's VMEM footprint fits :data:`VMEM_LIMIT_BYTES`.

    ``blocks`` is the bytes of one copy of every pipelined input and
    output block: Pallas double-buffers each, so they count twice.
    ``scratch`` is what is allocated once (scratch shapes and the
    kernel body's large intermediates)."""

    return 2 * blocks + scratch <= VMEM_LIMIT_BYTES


def is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def median(samples) -> float:
    """True median: mean of the middle pair for even counts.
    ``sorted[n // 2]`` picked the upper-middle sample — with two
    samples that returned the *worse* time.  The one median every
    measurement path (``time_fn``, the measure engine) shares."""

    s = sorted(samples)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def resolve_interpret(interpret: bool | None) -> bool:
    """Default Pallas interpret mode: on for CPU, off on accelerators."""

    return is_cpu() if interpret is None else bool(interpret)


def time_fn(fn, *, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-clock microseconds of ``fn()``.

    ``fn`` returns a jax value (or pytree); each call is synchronized
    with ``jax.block_until_ready`` so dispatch-only time is never
    reported.  ``warmup`` un-timed calls run first (jit/Pallas
    compilation, cache warm)."""

    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append((time.perf_counter() - t0) * 1e6)
    return median(samples)


__all__ = ["VMEM_LIMIT_BYTES", "fits_vmem", "is_cpu", "median",
           "resolve_interpret", "time_fn", "tpu_compiler_params"]
