"""Logical-axis sharding: rules tables + mesh context.

Model code names tensor dims with *logical* axes ("batch", "embed",
"heads", "mlp", "vocab", "experts", ...).  A :class:`Rules` table maps
logical axes to mesh axes; :func:`logical_constraint` applies
``with_sharding_constraint`` when a mesh context is active and is a
no-op otherwise (CPU smoke tests).

The rules table is *the auto-tuner's action space* for distributed
configs: changing ``embed -> "data"`` turns on FSDP-style parameter
sharding, ``experts -> "model"`` turns on expert parallelism,
``seq -> "model"`` turns on sequence parallelism for long-context
decode, etc.  `launch/dryrun.py` re-lowers under mutated rules and the
roofline terms quantify the effect — the paper's "tune against the
machine model, not the hardware" loop.
"""

from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field
from typing import Any, Mapping

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class Rules:
    """logical axis -> mesh axis (str), tuple of mesh axes, or None."""

    table: tuple[tuple[str, Any], ...]

    @staticmethod
    def make(**kw) -> "Rules":
        return Rules(tuple(sorted(kw.items())))

    def get(self, name: str | None):
        if name is None:
            return None
        d = dict(self.table)
        return d.get(name)

    def replace(self, **kw) -> "Rules":
        d = dict(self.table)
        d.update(kw)
        return Rules(tuple(sorted(d.items())))

    def spec(self, axes: tuple[str | None, ...]) -> P:
        """Resolve logical axes to a PartitionSpec, dropping duplicate
        mesh-axis uses (first dim wins, like flax partitioning)."""

        used: set[str] = set()
        out = []
        for a in axes:
            m = self.get(a)
            if m is None:
                out.append(None)
                continue
            ms = (m,) if isinstance(m, str) else tuple(m)
            ms = tuple(x for x in ms if x not in used)
            used.update(ms)
            out.append(ms[0] if len(ms) == 1 else (ms if ms else None))
        return P(*out)


def default_rules(multi_pod: bool = False) -> Rules:
    batch = ("pod", "data") if multi_pod else ("data",)
    return Rules.make(
        batch=batch,        # data parallel over pod+data axes
        seq=None,           # sequence parallelism off by default
        embed=batch,        # FSDP: weights' embed dim sharded over dp axes
                            # (v5e 16 GB/chip demands it; weights are
                            # all-gathered per layer — ZeRO-3 semantics.
                            # Activations never pick this up: their batch
                            # dim claims the data axes first.)
        heads="model",      # tensor parallel attention
        kv_heads="model",
        mlp="model",        # tensor parallel MLP
        vocab="model",      # sharded embedding/logits
        experts="model",    # expert parallelism (MoE archs w/ many experts)
        expert_mlp=None,    # per-expert d_ff sharding (mixtral-style TP)
        state=None,         # SSM state dim
        cache_batch=batch,  # decode KV cache: shard over batch
        cache_seq="model",  # ... and over cache length when kv_heads can't
        head_dim=None,      # alternative cache TP dim (ring update stays
                            # local; tuner may prefer it over cache_seq)
        layers=None,
    )


@dataclass
class MeshCtx:
    mesh: Mesh
    rules: Rules


_CTX: contextvars.ContextVar[MeshCtx | None] = contextvars.ContextVar(
    "repro_mesh_ctx", default=None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Rules):
    token = _CTX.set(MeshCtx(mesh, rules))
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _CTX.reset(token)


def current_ctx() -> MeshCtx | None:
    return _CTX.get()


def logical_constraint(x: jax.Array, *axes: str | None) -> jax.Array:
    """Constrain an intermediate to its logical sharding (no-op without a
    mesh context)."""

    ctx = _CTX.get()
    if ctx is None:
        return x
    spec = ctx.rules.spec(tuple(axes))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def named_sharding(axes: tuple[str | None, ...]) -> NamedSharding | None:
    ctx = _CTX.get()
    if ctx is None:
        return None
    return NamedSharding(ctx.mesh, ctx.rules.spec(axes))


def tree_shardings(axes_tree, mesh: Mesh, rules: Rules):
    """PartitionSpec tree for a logical-axes tree (for pjit in/out)."""

    return jax.tree.map(
        lambda axes: NamedSharding(mesh, rules.spec(tuple(axes))),
        axes_tree, is_leaf=lambda t: isinstance(t, tuple) and
        all(isinstance(a, (str, type(None))) for a in t))


# Logical names a weight may fall back to for "model"-axis sharding when
# its canonical dim is not divisible by the mesh axis (e.g. 20 heads on a
# 16-way model axis -> shard the embed dim instead: row-parallel).
FALLBACK_NAMES = ("embed", "heads", "kv_heads", "mlp", "expert_mlp",
                  "vocab", "experts")


def arg_sharding(shape: tuple[int, ...], axes: tuple[str | None, ...],
                 mesh: Mesh, rules: Rules) -> NamedSharding:
    """Shape-aware sharding for *jit arguments* (which, unlike internal
    constraints, must divide evenly).

    Pass 1 applies the rules table where divisible; pass 2 guarantees
    weights still get a "model"-axis shard by falling back to the first
    divisible FALLBACK dim when the canonical one is not divisible."""

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def ax_size(m) -> int:
        ms = (m,) if isinstance(m, str) else tuple(m)
        n = 1
        for a in ms:
            n *= sizes[a]
        return n

    used: set[str] = set()
    out: list = [None] * len(axes)
    for i, name in enumerate(axes):
        m = rules.get(name)
        if m is None:
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(a for a in ms if a not in used)
        if not ms:
            continue
        if shape[i] % ax_size(ms) != 0:
            continue
        used.update(ms)
        out[i] = ms[0] if len(ms) == 1 else ms

    model_used = any(
        (o == "model") or (isinstance(o, tuple) and "model" in o)
        for o in out)
    if not model_used and "model" in sizes:
        for i, name in enumerate(axes):
            if out[i] is None and name in FALLBACK_NAMES and \
                    shape[i] % sizes["model"] == 0 and shape[i] > 1:
                out[i] = "model"
                break
    return NamedSharding(mesh, P(*out))


def shard_like(abstract_tree, axes_tree, mesh: Mesh, rules: Rules):
    """Shape-aware sharding tree for an abstract (ShapeDtypeStruct) tree
    + matching logical-axes tree."""

    is_axes_leaf = lambda t: isinstance(t, tuple) and all(
        isinstance(a, (str, type(None))) for a in t)
    return jax.tree.map(
        lambda leaf, axes: arg_sharding(tuple(leaf.shape), tuple(axes),
                                        mesh, rules),
        abstract_tree, axes_tree,
        is_leaf=lambda t: hasattr(t, "shape") and not isinstance(t, tuple))


__all__ = ["Rules", "default_rules", "use_mesh", "current_ctx",
           "logical_constraint", "named_sharding", "tree_shardings", "MeshCtx"]
