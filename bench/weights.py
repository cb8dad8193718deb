"""Random weights from ``--seed``, made by the benchmark.

The program declares the parameter tree (names, shapes, served dtype);
the values are the benchmark's own, drawn on the device in one jitted
call, so the plain reference reads weights the program did not make.

Each leaf's spread follows its role, read from its name:

* a projection: normal with variance 1 / (its contraction width);
* ``embed``: std 1 when the head is separate, 0.1 when tied (the
  logits then have a spread of about 0.1 * sqrt(d_model));
* ``unembed``: std 2.5 / sqrt(d_model), logits spread about 2.5;
* norm weights: 1 + 0.05 * normal, so a dropped norm shows;
* biases: 0.1 * normal, so a dropped bias shows.
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


def _std(name: str, shape: tuple[int, ...], d_model: int, tied: bool):
    if name == "embed":
        return 0.1 if tied else 1.0
    if name == "unembed":
        return 2.5 / np.sqrt(d_model)
    if name in _CONTRACT:
        width = int(np.prod([shape[a] for a in _CONTRACT[name]]))
        return 1.0 / np.sqrt(width)
    raise KeyError(f"no init rule for parameter {name!r}")


def _leaf(name: str, shape, dtype, key, d_model: int, tied: bool):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("ln") or name.endswith("_norm"):
        return (1.0 + 0.05 * z).astype(dtype)
    if name.startswith("b"):
        return (0.1 * z).astype(dtype)
    return (z * _std(name, shape, d_model, tied)).astype(dtype)


def make_params(abstract, seed: int, *, d_model: int, tied: bool):
    """The tree ``abstract`` (ShapeDtypeStructs) filled from ``seed``,
    in one jitted call, in each leaf's declared dtype."""

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    specs = [(s.shape, s.dtype) for _, s in flat]

    def make(key):
        leaves = [_leaf(n, sh, dt, jax.random.fold_in(key, i), d_model, tied)
                  for i, (n, (sh, dt)) in enumerate(zip(names, specs))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)(seed_key(seed))
