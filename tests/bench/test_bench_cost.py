"""Operations and bytes of one tick, against counts made by hand from
each configuration's published widths."""

import json

import pytest

from bench import cost, spec

PEAKS = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}


def model(name):
    return cost.Model.from_config(
        json.loads((spec.BENCH / "configs" / f"{name}.json").read_text()))


def test_qwen_layer_and_cache_sizes():
    m = model("qwen1.5-4b")
    # q, k, v, o: 4 * 2560 * 2560 (MHA); MLP 3 * 2560 * 6912
    assert m.layer_weights == 4 * 2560 * 2560 + 3 * 2560 * 6912 == 79_298_560
    assert m.layer_other == 2 * 2560 + 3 * 2560
    assert m.kv_token_bytes == 409_600
    # 40 layers, final norm, head: the bf16 weights one decode reads
    assert m.stack_bytes(True) == 2 * (40 * (79_298_560 + 12_800) + 2560
                                       + 2560 * 151_936)


def test_smollm_layer_and_cache_sizes():
    m = model("smollm-135m")
    # q, o: 576 * 576; k, v: 576 * 3 * 64 each; MLP 3 * 576 * 1536
    assert m.layer_weights == (2 * 576 * 576 + 2 * 576 * 192
                               + 3 * 576 * 1536) == 3_538_944
    assert m.layer_other == 2 * 576
    assert m.kv_token_bytes == 23_040


@pytest.mark.parametrize("name", ["qwen1.5-4b", "smollm-135m"])
def test_weights_match_the_program(name):
    from bench import harness
    from repro.models.api import build_model

    m = model(name)
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    api = build_model(harness.arch_config(cfg))
    embed = m.V * m.d
    total = m.L * (m.layer_weights + m.layer_other) + m.d + embed
    if not cfg["tie_word_embeddings"]:
        total += m.head_weights
    assert total == api.param_count()


def test_one_qwen_decode_tick():
    m = model("qwen1.5-4b")
    keys = [300, 700]          # two slots, their own new key included
    fl, by = m.decode(keys)
    layer = 2 * 79_298_560
    attn = 4 * 20 * 128
    want_fl = sum(40 * (layer + attn * k) + 2 * 2560 * 151_936
                  for k in keys)
    assert fl == want_fl
    assert by == m.stack_bytes(True) + 409_600 * (1000 + 2)
    # bound by memory: 7.12 GB of weights (the input embedding is only
    # gathered) and 0.41 GB of cache at 819 GB/s
    t = cost.least_time(fl, by, PEAKS)
    assert t == pytest.approx(by / 819e9)
    assert 9.1e-3 < t < 9.3e-3


def test_one_smollm_prefill_tick():
    m = model("smollm-135m")
    chunks = [(0, 128, False), (128, 64, True)]
    fl, by = m.prefill(chunks)
    attn = 0
    for start, n, _ in chunks:
        attn += sum(start + t + 1 for t in range(n))
    want = 30 * (2 * 3_538_944 * 192 + 4 * 9 * 64 * attn) \
        + 2 * 576 * 49_152
    assert fl == want
    assert by == m.stack_bytes(True) + 23_040 * ((128 + 192) + 192)


def test_padding_and_idle_slots_are_no_work():
    m = model("smollm-135m")
    assert m.decode([]) == (0.0, 0.0)
    assert m.prefill([]) == (0.0, 0.0)
    # a chunk whose logits are not taken reads no head
    _, with_head = m.prefill([(0, 128, True)])
    _, no_head = m.prefill([(0, 128, False)])
    assert with_head - no_head == 2 * 576 * 49_152


def test_least_time_takes_the_larger_bound():
    assert cost.least_time(197e12, 1.0, PEAKS) == pytest.approx(1.0)
    assert cost.least_time(1.0, 819e9, PEAKS) == pytest.approx(1.0)
