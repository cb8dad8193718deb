"""Random weights from ``--seed``, made by the benchmark.

The program declares the parameter tree (names, shapes, served dtype);
the values are the benchmark's own, drawn on the device in one jitted
call, so the plain reference reads weights the program did not make.

Each leaf's spread follows its name, looked up in this order:

1. the configuration's ``init`` table, which maps a leaf name to
   ``{"contract": [axes]}`` (normal with variance 1 / the product of
   those axes, counted from the end as in ``_CONTRACT``) or to
   ``{"mean": m, "std": s}``;
2. ``embed``: std 1 when the head is separate, 0.1 when tied (the
   logits then have a spread of about 0.1 * sqrt(d_model));
   ``unembed``: std 2.5 / sqrt(d_model), logits spread about 2.5;
   a projection of ``_CONTRACT``: variance 1 / (its contraction width);
3. norm weights (``ln*``, ``*_norm``): 1 + 0.05 * normal, so a dropped
   norm shows; the bias ``b<x>`` of a projection ``w<x>``: 0.1 * normal,
   so a dropped bias shows.

A leaf that none of these places is an error that names it.  The
program's own init scales are never read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""

    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) & 0x7FFFFFFF),
                              int(b) & 0x7FFFFFFF)


# contraction axes of each projection, counted from the end of the
# unstacked shape: wq/wk/wv (d, H, hd) contract d; wo (H, hd, d)
# contracts H*hd; wg/wu (d, f) contract d; wd (f, d) contracts f
_CONTRACT = {"wq": (-3,), "wk": (-3,), "wv": (-3,), "wo": (-3, -2),
             "wg": (-2,), "wu": (-2,), "wd": (-2,), "w1": (-2,), "w2": (-2,)}


def _contract_std(shape, axes) -> float:
    return 1.0 / np.sqrt(int(np.prod([shape[a] for a in axes])))


def _spread(name: str, shape, d_model: int, tied: bool, init: dict):
    """(mean, std) of leaf ``name``."""

    rule = init.get(name)
    if rule is not None:
        if "contract" in rule:
            return 0.0, _contract_std(shape, rule["contract"])
        return float(rule["mean"]), float(rule["std"])
    if name == "embed":
        return 0.0, 0.1 if tied else 1.0
    if name == "unembed":
        return 0.0, 2.5 / np.sqrt(d_model)
    if name in _CONTRACT:
        return 0.0, _contract_std(shape, _CONTRACT[name])
    if name.startswith("ln") or name.endswith("_norm"):
        return 1.0, 0.05
    if name.startswith("b") and "w" + name[1:] in _CONTRACT:
        return 0.0, 0.1
    raise KeyError(f"no init rule for parameter {name!r}; the "
                   f'configuration places it under "init"')


def named_leaves(tree) -> list[tuple[str, object]]:
    """``(name, leaf)`` in flattening order; a leaf's name is its last
    key (``wq``, ``router``), shared by every layer that holds it."""

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(str(getattr(path[-1], "key", path[-1])), x) for path, x in flat]


def make_params(abstract, seed: int, *, d_model: int, tied: bool,
                init: dict | None = None):
    """The tree ``abstract`` (ShapeDtypeStructs) filled from ``seed``,
    in one jitted call, in each leaf's declared dtype; ``init`` is the
    configuration's table of leaf rules."""

    init = init or {}
    leaves = named_leaves(abstract)
    unknown = sorted(set(init) - {n for n, _ in leaves})
    if unknown:
        raise KeyError(f'"init" names {unknown}, which are no parameters '
                       f"of the program")
    rules = [(s.shape, s.dtype, _spread(n, s.shape, d_model, tied, init))
             for n, s in leaves]
    treedef = jax.tree_util.tree_structure(abstract)

    def make(key):
        out = []
        for i, (shape, dtype, (mean, std)) in enumerate(rules):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((z * std + mean).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(seed_key(seed))
