"""The plain float32 reference and the program's full-sequence forward
are the same model: at ``.reduced()`` widths, with the benchmark's
weights in float32, their logits agree to float32 rounding.  The fp8
control departs from both by far more."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec
from bench.weights import make_params


def reduced(name):
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    from repro.configs import get_config

    r = get_config(cfg["arch"]).reduced()
    return dict(cfg, hidden_size=r.d_model, intermediate_size=r.d_ff,
                num_hidden_layers=r.n_layers, num_attention_heads=r.n_heads,
                num_key_value_heads=r.n_kv_heads, head_dim=r.hd,
                vocab_size=r.vocab)


@pytest.mark.parametrize("name", ["qwen1.5-4b", "smollm-135m"])
def test_reference_matches_the_program_forward(name):
    from repro.models.api import build_model

    cfg = reduced(name)
    api = build_model(harness.arch_config(cfg))
    abstract = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), abstract)
    params = make_params(abstract, 2**33 + 3, d_model=cfg["hidden_size"],
                         tied=cfg["tie_word_embeddings"])
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                              cfg["vocab_size"])
    ref = spec.load_module("references", cfg["reference"])
    want = np.asarray(ref.logits(params, cfg, toks))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(api.forward(params, {"tokens": toks}))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2e-5 * scale
    # the widest-gap arithmetic on the same logits
    score = np.asarray(got.argmax(-1), np.int32)
    gap, top = ref.run(params, cfg, toks, jnp.asarray(score))
    assert np.asarray(gap).max() <= 4e-5 * scale
    assert (np.asarray(top) == want.argmax(-1)).all()
    # the control rounds through fp8: far outside float32 rounding
    q = np.asarray(ref.logits(params, cfg, toks, quant="fp8"))
    assert np.abs(q - want).max() > 1e-2 * scale
