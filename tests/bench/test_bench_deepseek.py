"""deepseek-v2-lite from its configuration file: the file builds the
published model at one chip's expert share, the paged server's logits
agree with the plain float32 reference (``bench/references/
deepseek_v2.py``) at ``.reduced()`` sizes, a reduced file serves
through a whole run, its count (``bench/costs/deepseek_v2.py``) is the
hand count, and the readers of its new layers read nothing where the
step has no such scopes."""

import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from small_cell import small_cell

from bench import cost, harness, spec
from bench.weights import make_params

PEAKS = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}
NEW = ("expert_ms_per_decode", "expert_roofline", "latent_attend_roofline")


def config():
    return json.loads((spec.BENCH / "configs" / "deepseek-v2-lite.json")
                      .read_text())


def reduced_file() -> dict:
    """The file at ``.reduced()`` widths, holding 4 of 8 experts (4..7):
    what a chip of an expert-parallel 2 deployment holds."""

    cfg = config()
    cfg.update(hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_hidden_layers=3,
               num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, n_routed_experts=4, num_experts_per_tok=3,
               vocab_size=256, published={"n_routed_experts": 8},
               program={"moe": {"num_experts": 8, "top_k": 3,
                                "d_expert": 32, "held": 4, "held_from": 4},
                        "mla": {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
                                "qk_rope_head_dim": 8, "v_head_dim": 16}})
    return cfg


def test_the_file_builds_the_published_model_at_a_chip_share():
    from repro.configs import get_config
    from repro.models.api import build_model

    cfg = config()
    arch = harness.arch_config(cfg)
    pub = get_config("deepseek-v2-lite")
    assert arch == pub.replace(moe=pub.moe.__class__(
        **{**pub.moe.__dict__, "held": 16, "held_from": 0}))
    assert (arch.moe.num_experts, arch.moe.top_k, arch.moe.n_held) == (64, 6,
                                                                       16)
    # per chip: 26 expert layers of 13.77 M attention (+ norms), 0.13 M
    # router, 17.30 M shared and 16 x 8.65 M held experts; layer 0 with
    # its 67.24 M dense MLP; embedding and head 2 x 209.7 M
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert attn == 13_762_560
    norms = 512 + 2 * 2048
    moe = 2048 * 64 + 3 * 2048 * 2816 + 16 * 3 * 2048 * 1408
    total = (26 * (attn + norms + moe) + attn + norms + 3 * 2048 * 10944
             + 2048 + 2 * 2048 * 102_400)
    assert build_model(arch).param_count() == total
    assert round(total / 1e9, 2) == 4.91


def _f32_params(cfg, seed):
    from repro.models.api import build_model

    api = build_model(harness.arch_config(cfg))
    abstract = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), abstract)
    return api, make_params(abstract, seed, d_model=cfg["hidden_size"],
                            tied=False, init=cfg["init"])


def test_reference_matches_the_program_forward():
    """The reference (decompressed, every held expert on every token)
    and the program's full forward are the same model: float32
    rounding apart.  The fp8 control departs by far more."""

    cfg = reduced_file()
    api, params = _f32_params(cfg, 2**33 + 3)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)
    ref = spec.load_module("references", cfg["reference"])
    want = np.asarray(ref.logits(params, cfg, toks))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(api.forward(params, {"tokens": toks}))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2e-5 * scale
    q = np.asarray(ref.logits(params, cfg, toks, quant="fp8"))
    assert np.abs(q - want).max() > 1e-2 * scale


def test_server_logits_match_the_reference():
    """Chunked prefill, then decode, through the paged latent pool: each
    served token's logits against the reference's full forward over the
    prompt and the served tokens.  Float32 weights and pool, so the two
    differ by rounding alone (absorbed against decompressed, grouped
    against dense expert matmuls, other summation orders).  On the CPU
    the served logits read 7e-7 of the logits' scale off the reference
    and the fp8 control 0.13; the bound, 1e-5, leaves the rounding ten
    times its room and sits four orders below a lower precision."""

    from repro.runtime.serve import Server

    cfg = reduced_file()
    api, params = _f32_params(cfg, 2**33 + 5)
    srv = Server(api, params, batch=2, context=64, prefill_chunk=8,
                 paged=True, page_size=8, kv_pages=16, scheduler="fcfs")
    seen = []
    for name in ("_step", "_prefill_step"):
        step = getattr(srv, name)

        def record(*a, _step=step, _kind=name):
            logits, state = _step(*a)
            seen.append((_kind, np.asarray(logits)))
            return logits, state

        setattr(srv, name, record)
    prompt = np.random.default_rng(7).integers(0, 256, 21).tolist()
    req = srv.submit(prompt, max_new=6)
    srv.run_until_drained()
    assert len(req.out) == 6
    # the last prefill call emits token 0, each decode call the next
    first = max(i for i, (k, _) in enumerate(seen) if k == "_prefill_step")
    rows = [seen[first][1][0]] + [lg[0] for k, lg in seen[first + 1:]
                                  if k == "_step"][:5]
    ref = spec.load_module("references", cfg["reference"])
    toks = jnp.asarray([prompt + req.out[:-1]])
    want = np.asarray(ref.logits(params, cfg, toks))[0, len(prompt) - 1:]
    got = np.stack(rows)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert list(got.argmax(-1)) == req.out


def test_reduced_file_builds_and_serves_from_its_dict(run_small):
    cell = small_cell(64, 2, 256, 0.6)
    cell.config = reduced_file()
    _, params, server = harness.build(cell, 2**33 + 13)
    assert params["blocks"]["0_moe"]["ffn"]["router"].dtype == jnp.float32
    assert params["blocks"]["0_moe"]["ffn"]["wg"].shape[1] == 4
    assert set(server.state["blocks"]["0_moe"]["kv"]) == {"ckv"}
    res = run_small(cell, 2**33 + 13)
    assert res["attempted"] > 0
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["correct"], res["checks"]


def model():
    return harness.cost_model(config())


def test_count_by_hand():
    m = model()
    assert type(m).__module__.endswith("deepseek_v2")
    assert (m.E, m.held, m.k, m.lead, m.n_moe) == (64, 16, 6, 1, 26)
    assert m.row_bytes == 1152 and 27 * m.row_bytes == 31_104
    keys = [300, 700]
    fl, by = m.decode(keys)
    # per token: projections, the absorbed attention over its keys, the
    # dense layer, router + shared + 6/64 of 16 held experts, the head
    proj = 2 * (2048 * 16 * 192 + 2048 * 576)
    per_key = 2 * 16 * 576 + 2 * 16 * 512
    fixed = 2 * 16 * 128 * 512 * 2 + 2 * 16 * 128 * 2048
    moe = (2 * 2048 * 64 + 2 * 3 * 2048 * 2816
           + 6 * 16 / 64 * 2 * 3 * 2048 * 1408)
    want = sum(27 * (proj + fixed + per_key * k) + 2 * 3 * 2048 * 10944
               + 26 * moe + 2 * 2048 * 102_400 for k in keys)
    assert fl == pytest.approx(want, rel=1e-12)
    hit = 16 * (1 - (1 - 6 / 64) ** 2)
    weights = (27 * (13_762_560 + 512 + 4096) + 3 * 2048 * 10944
               + 26 * (3 * 2048 * 2816 + hit * 3 * 2048 * 1408)
               + 2048 + 2048 * 102_400)
    assert by == pytest.approx(2 * weights + 26 * 2048 * 64 * 4
                               + 31_104 * (1000 + 2), rel=1e-12)


def test_a_full_decode_step_is_bound_by_the_weights():
    """48 slots with ~1.1 k live keys: every held expert is hit (16 x
    0.991), 7.2 GB of experts, 1.6 GB of latent rows and 2.2 GB of the
    rest: about 11 GB, 13.4 ms at 819 GB/s."""

    m = model()
    fl, by = m.decode([1100] * 48)
    assert m.hit(48) == pytest.approx(16 * 0.99109, rel=1e-4)
    assert 10.8e9 < by < 11.2e9
    t = cost.least_time(fl, by, PEAKS)
    assert t == pytest.approx(by / 819e9)
    ef, eb = m.experts_decode(48)
    assert eb == pytest.approx(26 * m.hit(48) * 3 * 2048 * 1408 * 2)
    assert ef == pytest.approx(26 * 48 * 6 * 16 / 64 * 2 * 3 * 2048 * 1408)
    af, ab = m.attend_decode([1100] * 48)
    assert ab == pytest.approx(27 * (1152 * 1101 * 48 + 2 * (
        512 * 16 * 256 + 16 * 128 * 2048)))
    assert m.decode([]) == m.prefill([]) == m.experts_decode(0) == (0.0, 0.0)


def test_prefill_counts_its_chunks():
    m = model()
    fl, by = m.prefill([(0, 128, False), (128, 64, True)])
    attend = sum(t + 1 for t in range(128)) + sum(128 + t + 1
                                                  for t in range(64))
    per_tok = m.token_flops(0, False)
    assert fl == pytest.approx(192 * per_tok + 27 * 2 * 16 * 1088 * attend
                               + 2 * 2048 * 102_400, rel=1e-12)
    assert by == pytest.approx(m.weight_bytes(192, True)
                               + 31_104 * (128 + 192 + 192), rel=1e-12)


def test_new_readers_read_nothing_without_their_scopes():
    from bench import devtrace
    from bench.devtrace import Ev
    from bench.metrics import _subscopes

    for name in NEW:
        assert spec.load_module("metrics", name).read(
            SimpleNamespace(red=None, model=model(), peaks=PEAKS)) is None
    H, D = devtrace.HOST_PLANE, "/device:TPU:0"
    events = [Ev(H, "python", "bench.window", 0, 100),
              Ev(D, devtrace.MODULES_LINE, "jit_step_paged(1)", 10, 60),
              Ev(D, devtrace.OPS_LINE, "%a", 10, 20),
              Ev(D, devtrace.OPS_LINE, "%b", 20, 35),
              Ev(D, devtrace.OPS_LINE, "%c", 35, 60),
              Ev(D, devtrace.OPS_LINE, "%ragged-dot-none.1", 60, 64)]
    paths = {D: {"%a": "jit(step_paged)/layers/while/body/mlp/route/top_k",
                 "%b": "jit(step_paged)/layers/while/body/mlp/experts/mul",
                 "%c": "jit(step_paged)/layers/while/body/attend/absorb/dot",
                 "%ragged-dot-none.1": "ragged-dot-none"}}
    events[1] = Ev(D, devtrace.MODULES_LINE, "jit_step_paged(1)", 10, 64)
    r = _subscopes.reduce(events, paths, (0, 100), "step_paged")
    # XLA's grouped-matmul kernels carry no scope path: they are experts
    assert r.calls == 1 and r.ns == {"route": 10, "experts": 19,
                                     "absorb": 25}
    assert r.ms_per_call("route", "experts") == pytest.approx(29e-6)
    bare = {D: {k: v.replace("/route", "").replace("/experts", "")
                .replace("/absorb", "") for k, v in paths[D].items()
                if not v.startswith("ragged")}}
    r = _subscopes.reduce(events, bare, (0, 100), "step_paged")
    assert r.ms_per_call("route", "experts") is None
