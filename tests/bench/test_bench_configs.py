"""A configuration's file alone decides what the harness builds: the
program's ``ArchConfig`` (published keys, then ``program`` overrides),
the weights (the ``init`` table before the benchmark's own rules), the
dtypes a parameter may have (``param_dtypes``) and the count of
operations and bytes (``cost``).  The two dense files build what they
built before these keys existed; a non-dense registry model builds and
serves from a configuration dict alone."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from small_cell import small_cell

from bench import cost, harness, spec
from bench.weights import make_params, named_leaves, seed_key

DENSE = ["qwen1.5-4b", "smollm-135m"]


def config(name):
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


def parent_arch_config(cfg):
    """The mapping before ``program`` and ``sliding_window``: every
    dense key required, the window forced off."""

    from repro.configs import get_config

    return get_config(cfg["arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], window=None)


_PARENT_CONTRACT = {"wq": (-3,), "wk": (-3,), "wv": (-3,), "wo": (-3, -2),
                    "wg": (-2,), "wu": (-2,), "wd": (-2,), "w1": (-2,),
                    "w2": (-2,)}


def _parent_leaf(name, shape, dtype, key, d_model, tied):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("ln") or name.endswith("_norm"):
        return (1.0 + 0.05 * z).astype(dtype)
    if name.startswith("b"):
        return (0.1 * z).astype(dtype)
    if name == "embed":
        std = 0.1 if tied else 1.0
    elif name == "unembed":
        std = 2.5 / np.sqrt(d_model)
    else:
        width = int(np.prod([shape[a] for a in _PARENT_CONTRACT[name]]))
        std = 1.0 / np.sqrt(width)
    return (z * std).astype(dtype)


def parent_make_params(abstract, seed, *, d_model, tied):
    """The weights before the ``init`` table: a closed table of names
    and the ``ln``/``b`` prefix rules."""

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]

    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _parent_leaf(n, s.shape, s.dtype, jax.random.fold_in(key, i),
                         d_model, tied)
            for i, (n, (_, s)) in enumerate(zip(names, flat))])

    return jax.jit(make)(seed_key(seed))


def abstract_of(cfg):
    from repro.models.api import build_model

    api = build_model(harness.arch_config(cfg))
    return jax.eval_shape(api.init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", DENSE)
def test_dense_arch_config_is_the_parents(name):
    cfg = config(name)
    assert harness.arch_config(cfg) == parent_arch_config(cfg)


@pytest.mark.parametrize("name", DENSE)
def test_dense_weights_are_the_parents_bit_for_bit(name):
    cfg = small_cell(64, 2, 256, 0.6, config=name).config
    abstract = abstract_of(cfg)
    harness.check_dtypes(abstract, cfg["dtype"], "param_dtypes", cfg)
    kw = dict(d_model=cfg["hidden_size"], tied=cfg["tie_word_embeddings"])
    got = make_params(abstract, 2**33 + 5, init=cfg.get("init", {}), **kw)
    want = parent_make_params(abstract, 2**33 + 5, **kw)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))


@pytest.mark.parametrize("name", DENSE)
def test_dense_count_is_the_parents(name):
    cfg = config(name)
    assert "cost" not in cfg
    assert harness.cost_model(cfg) == cost.Model.from_config(cfg)


def reduced_file(arch: str, **keys) -> dict:
    """A configuration file for a registry model at ``.reduced()``
    widths, as a PR that adds one would write it."""

    from repro.configs import get_config

    r = get_config(arch).reduced()
    cfg = {"arch": arch, "reference": "dense_decoder", "dtype": "bfloat16",
           "kv_dtype": "bfloat16", "hidden_size": r.d_model,
           "intermediate_size": r.d_ff, "num_hidden_layers": r.n_layers,
           "num_attention_heads": r.n_heads,
           "num_key_value_heads": r.n_kv_heads, "head_dim": r.hd,
           "vocab_size": r.vocab, "qkv_bias": r.qkv_bias,
           "tie_word_embeddings": r.tie_embeddings,
           "rope_theta": r.rope_theta}
    cfg.update(keys)
    return cfg


MIXTRAL = reduced_file(
    "mixtral-8x22b", sliding_window=8,
    program={"moe": {"num_experts": 4}},
    init={"router": {"contract": [-2]}},
    param_dtypes={"router": "float32"})

MAMBA2 = reduced_file(
    "mamba2-2.7b",
    program={"ssm": {"state": 8, "headdim": 8, "chunk": 8}},
    init={"D": {"mean": 1.0, "std": 0.05},
          "a_log": {"mean": 0.0, "std": 0.1},
          "dt_bias": {"mean": 0.0, "std": 0.1},
          "conv_w": {"contract": [-2]},
          "conv_b": {"mean": 0.0, "std": 0.1},
          "w_in": {"contract": [-2]}, "w_out": {"contract": [-2]}},
    state_dtypes={"h": "float32"})


@pytest.mark.parametrize("cfg", [MIXTRAL, MAMBA2],
                         ids=["mixtral-8x22b", "mamba2-2.7b"])
def test_non_dense_model_builds_and_serves_from_its_file(run_small, cfg):
    from repro.configs import get_config

    r = get_config(cfg["arch"]).reduced()
    arch = harness.arch_config(cfg)
    assert (arch.window, arch.moe, arch.ssm) == (r.window, r.moe, r.ssm)
    cell = _cell(cfg)
    _, params, server = harness.build(cell, 2**33 + 7)
    for tree, key in ((params, "param_dtypes"),
                      (server.state, "state_dtypes")):
        held = dict(named_leaves(tree))
        for name, dtype in cfg.get(key, {}).items():
            assert held[name].dtype == jnp.dtype(dtype)
    assert server.api.cfg.window == r.window
    res = run_small(cell, 2**33 + 7, check=False)
    assert res["attempted"] > 0
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_the_window_is_the_files():
    cfg = dict(MIXTRAL)
    del cfg["sliding_window"]
    assert harness.arch_config(cfg).window == 4096   # the registry's
    assert harness.arch_config(dict(cfg, sliding_window=None)).window is None


@pytest.mark.parametrize("fault", ["unplaced_leaf", "undeclared_dtype",
                                   "unknown_override", "undeclared_state",
                                   "rule_for_no_leaf"])
def test_a_fault_of_the_file_is_an_error_that_names_it(fault):
    cfg = dict(MIXTRAL)
    if fault == "unplaced_leaf":
        cfg["init"] = {}
        with pytest.raises(KeyError, match=r"\brouter\b"):
            harness.build(_cell(cfg), 1)
    elif fault == "undeclared_dtype":
        del cfg["param_dtypes"]
        with pytest.raises(ValueError, match=r"\brouter\b.*float32"):
            harness.build(_cell(cfg), 1)
    elif fault == "unknown_override":
        cfg["program"] = dict(cfg["program"], n_experts_held=2)
        with pytest.raises(KeyError, match=r"\bn_experts_held\b"):
            harness.arch_config(cfg)
    elif fault == "undeclared_state":
        cfg = dict(MAMBA2)
        del cfg["state_dtypes"]
        with pytest.raises(ValueError, match=r"'h' in float32"):
            harness.build(_cell(cfg), 1)
    else:
        cfg["init"] = dict(cfg["init"], gate={"mean": 0.0, "std": 1.0})
        with pytest.raises(KeyError, match=r"\bgate\b"):
            harness.build(_cell(cfg), 1)


def _cell(cfg):
    cell = small_cell(64, 2, 256, 0.6)
    cell.config = cfg
    return cell


PROBE_COST = '''
class Model:
    def __init__(self, cfg):
        self.cfg = cfg

    @classmethod
    def from_config(cls, cfg):
        return cls(cfg)

    def decode(self, keys):
        return 1.0, 2.0

    def prefill(self, chunks):
        return 3.0, 4.0
'''

PROBE_METRIC = '''
SEEN = []


def read(run):
    SEEN.append(run.model)
    return 1.0
'''


def test_a_file_that_names_a_count_gets_it_as_run_model(run_small, tmp_path,
                                                        monkeypatch):
    """``"cost": "<name>"`` builds ``run.model`` from
    ``bench/costs/<name>.py``; a probe reader sees it in a whole run."""

    for kind, name, text in (("costs", "probe", PROBE_COST),
                             ("metrics", "probe_model", PROBE_METRIC)):
        (tmp_path / "bench" / kind).mkdir(parents=True, exist_ok=True)
        (tmp_path / "bench" / kind / f"{name}.py").write_text(text)
    load = spec.load_module
    loaded = {}

    def load_once(kind, name):
        if (kind, name) not in loaded:
            loaded[kind, name] = load(kind, name, root=tmp_path)
        return loaded[kind, name]

    monkeypatch.setattr(spec, "load_module", load_once)
    cell = _cell(dict(small_cell(64, 2, 256, 0.6).config, cost="probe"))
    cell.end_to_end = [{"name": "probe_model", "unit": "1"}]
    res = run_small(cell, 2**33 + 9, check=False)
    assert res["metrics"] == {"probe_model": {"value": 1.0, "unit": "1"}}
    (model,) = loaded["metrics", "probe_model"].SEEN
    assert type(model).__module__ == loaded["costs", "probe"].__name__
    assert model.cfg["cost"] == "probe"
    assert model.decode([5]) == (1.0, 2.0)
