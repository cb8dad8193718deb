"""Compile-only checks for a described TPU v5e: the kernels' modeled block
picks, smollm-135m's paged decode step at published widths, and the
server's donated paged steps at qwen1.5-4b's serving shapes.

Nothing here runs on a chip.  The TPU compiler (libtpu) compiles for a
``v5e:2x2`` topology described in a fixture, and refuses what the chip
would refuse: blocks that overflow the scoped VMEM, unaligned slices,
programs larger than device memory.  This is the only test file that
describes the chip; the description happens inside a fixture, so every
worker collects the same tests and only the one given this file loads the
library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _argmin(tunable):
    return min(tunable.space(), key=tunable.cost)


def _matmul(chip):
    from repro.kernels.matmul_tuned import ops
    cfg = _argmin(ops.MatmulTunable(4096, 4096, 4096))
    a = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=chip)
    return jax.jit(lambda a, b: ops._matmul_call(
        a, b, interpret=False, **cfg)).lower(a, a)


def _reduction(chip):
    from repro.kernels.tuned_reduction import ops
    cfg = _argmin(ops.ReductionTunable(2**26))
    x = jax.ShapeDtypeStruct((2**26,), jnp.float32, sharding=chip)
    return ops.reduce_1d.__wrapped__.lower(x, interpret=False, **cfg)


def _flash(chip):
    from repro.kernels.flash_attention import ops
    cfg = _argmin(ops.FlashAttentionTunable(S=4096, D=128, BH=20))
    q = jax.ShapeDtypeStruct((1, 20, 4096, 128), jnp.bfloat16, sharding=chip)
    return ops._flash_call.lower(q, q, q, causal=True, window=None,
                                 interpret=False, **cfg)


def _sweep(chip):
    from repro.core.wave_model import WaveParams
    from repro.kernels.sweep_eval import ops
    n = 2**15
    cfg = _argmin(ops.SweepEvalTunable(n))
    p = WaveParams(size=2**20, NP=128, GMT=16, kind="minimum", NU=15)
    x = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip)
    return ops.sweep_eval.__wrapped__.lower(x, x, p, interpret=False, **cfg)


@pytest.mark.parametrize("lower", [_matmul, _reduction, _flash, _sweep],
                         ids=["matmul_4096", "reduction_2p26",
                              "flash_qwen15_4b", "sweep_eval"])
def test_modeled_argmin_compiles(one_chip, lower):
    """The cost model's pick from each kernel's lattice fits the chip's
    scoped VMEM and compiles to a Mosaic kernel."""

    compiled = lower(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_paged_decode_step_compiles(one_chip):
    """smollm-135m at published widths: the server's paged decode step
    (8 slots, context 2048, page 16) compiles and fits one chip."""

    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.kv import PagedKVSpec

    api = build_model(get_config("smollm-135m"))
    batch, context = 8, 2048
    spec = PagedKVSpec.for_server(context=context, page_size=16,
                                  batch=batch)
    params = _on(one_chip, api.abstract())
    state = _on(one_chip, jax.eval_shape(
        lambda: api.init_decode_state(batch, context, spec,
                                      dtype=jnp.bfloat16)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    active = jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=one_chip)
    step = jax.jit(lambda p, s, t, pos, pt, act: api.decode_step(
        p, s, t, pos, pt, act))
    compiled = step.lower(params, state, i32(batch, 1), i32(batch),
                          i32(batch, spec.pages_per_slot), active).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2**30


@pytest.mark.parametrize("arch,batch,n_pages,step", [
    ("qwen1.5-4b", 8, 512, "decode"),
    ("qwen1.5-4b", 8, 512, "prefill"),
    ("smollm-135m", 64, 4096, "decode"),
], ids=["qwen15_4b-decode", "qwen15_4b-prefill", "smollm_135m-decode"])
def test_paged_server_steps_write_the_pool_in_place(one_chip, arch, batch,
                                                    n_pages, step):
    """The server's jitted paged decode and prefill steps at the serving
    cells' shapes (context 1024, page 16, chunk 128; qwen1.5-4b 8 slots
    and 512 pages, smollm-135m 64 and 4,096) donate the state, so the
    compiled program aliases the whole KV pool from input to output,
    and no temporary comes near a copy of the stacked pool.  smollm's
    64-wide heads are the case where a pool laid out by head size made
    the TPU relayout the whole pool; its prefill is left out, its
    8,192-query attention scores alone being larger than the bound."""

    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.kv import PagedKVSpec
    from repro.runtime.serve import server_steps

    api = build_model(get_config(arch))
    context, chunk = 1024, 128
    spec = PagedKVSpec.for_server(context=context, page_size=16,
                                  n_pages=n_pages, batch=batch)
    params = _on(one_chip, api.abstract())
    state = _on(one_chip, jax.eval_shape(
        lambda: api.init_decode_state(batch, context, spec,
                                      dtype=jnp.bfloat16)))
    pool = [a for e in state["blocks"].values()
            for a in jax.tree.leaves(e["kv"])]
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pool)
    layer_bytes = pool_bytes // pool[0].shape[0]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    table = i32(batch, spec.pages_per_slot)
    decode, prefill, _ = server_steps(api, paged=True)
    if step == "decode":
        active = jax.ShapeDtypeStruct((batch,), jnp.bool_,
                                      sharding=one_chip)
        lowered = decode.lower(params, state, i32(batch, 1), i32(batch),
                               active, table)
    else:
        lowered = prefill.lower(params, state, i32(batch, chunk),
                                i32(batch), i32(batch), table)
    mem = lowered.compile().memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # the read path's float32 copy of a layer's gathered pages is one
    # layer's K and V in size here (slots x pages a slot = the pool's
    # pages), so the bound is two layers: a stacked pool copy is 15 to
    # 20 times more
    assert mem.temp_size_in_bytes < 2 * layer_bytes


def test_deepseek_paged_decode_step_fits_its_cell(one_chip):
    """deepseek-v2-lite at its file's chip share (16 of 64 experts held)
    and the long_decode cell's shapes (48 slots, context 2048, page 16,
    6,144 pages): the server's donated decode step compiles, its latent
    pool of all 27 layers is aliased in place, and the 9.8 GB of weights,
    the 3.1 GB pool and the step's temporaries fit one chip with room
    for the prefill step's (1.2 GB when this test was written)."""

    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.kv import PagedKVSpec
    from repro.runtime.serve import server_steps

    cfg = get_config("deepseek-v2-lite")
    api = build_model(cfg.replace(moe=dataclasses.replace(cfg.moe, held=16)))
    batch, context = 48, 2048
    spec = PagedKVSpec.for_server(context=context, page_size=16,
                                  n_pages=6144, batch=batch)
    params = _on(one_chip, jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    state = _on(one_chip, jax.eval_shape(
        lambda: api.init_decode_state(batch, context, spec,
                                      dtype=jnp.bfloat16)))
    (pool,) = jax.tree.leaves(state["blocks"]["0_moe"]["kv"])
    assert pool.shape[0] == 27
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    active = jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=one_chip)
    decode, _, _ = server_steps(api, paged=True)
    mem = decode.lower(params, state, i32(batch, 1), i32(batch), active,
                       i32(batch, spec.pages_per_slot)).compile(
                       ).memory_analysis()
    assert mem.alias_size_in_bytes >= pool.size * pool.dtype.itemsize
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 14 * 10**9)
