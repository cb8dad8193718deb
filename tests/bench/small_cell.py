"""A cell of the benchmark's kind at sizes the CPU runs in seconds."""

import json


def small_cell(d: int, layers: int, vocab: int, limit: float):
    """A cell of the benchmark's kind at a size the CPU runs in seconds:
    smollm-135m's file with its widths cut, a closed loop of 4 clients
    on 4 slots, fcfs, paged bf16."""

    from bench import spec

    cfg = json.loads((spec.BENCH / "configs" / "smollm-135m.json")
                     .read_text())
    heads = max(4, d // 64)
    cfg.update(hidden_size=d, intermediate_size=4 * d,
               num_hidden_layers=layers,
               num_attention_heads=heads, num_key_value_heads=heads // 2,
               head_dim=d // heads, vocab_size=vocab)
    mix = {"loop": "closed", "clients": 4, "requests": 16,
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 80},
           "output": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 64}}
    srv = {"slots": 4, "context": 160, "page_size": 16, "kv_pages": 40,
           "prefill_chunk": 32, "scheduler": "fcfs",
           "sample": {"requests": 8}, "limits": {"max_gap_logits": limit}}
    bench = spec.load_benchmark()
    return spec.Cell(name="small", chips=1, config_name="small", config=cfg,
                     traffic_name="small", traffic=mix, server=srv,
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])


def cell_limit() -> float:
    """The tightest correctness limit of the benchmark's cells."""

    from bench import spec

    return min(spec.load_cell(w["name"]).server["limits"]["max_gap_logits"]
               for w in spec.load_benchmark()["workloads"])
