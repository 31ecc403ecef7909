"""A cell at a size the CPU holds: the phi3 config file's architecture
cut to two layers of width 64, in float32, under a small chat mix."""
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.spec import Cell  # noqa: E402

SEED = 2 ** 33 + 7   # wider than 32 bits, as a run's seed may be


def tiny_cell(name: str = "phi3-chat", limit: float = 1e-3, chips: int = 1,
              heads: tuple = (4, 2)) -> Cell:
    """``heads``: (query heads, KV heads), at head_dim 16."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "phi3-mini-3.8b.json")) as f:
        conf = json.load(f)
    conf.update(hidden_size=64, num_hidden_layers=2,
                num_attention_heads=heads[0], num_key_value_heads=heads[1],
                head_dim=16, intermediate_size=128,
                vocab_size=128, torch_dtype="float32",
                flux={"sink": 4, "local": 28, "pool_size": 8},
                serving={"prefill_chunk": 16, "block_k": 16,
                         "decode_steps_per_tick": 1,
                         "prefill_chunks_per_tick": 1})
    mix = {"set_seed": 1, "system_prompt": 8,
           "prompt": {"counts": "user", "median": 20, "sigma": 0.5,
                      "min": 4, "max": 40},
           "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 10},
           "arrivals": {"process": "gamma", "cv": 2.0}}
    point = {"rate_per_s": 8.0, "slots": 3, "max_len": 64,
             "check": {"served_tokens": 30, "max_requests": 3},
             "limits": {"logit_gap": limit, "pattern_mismatch_layers": 0}}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    per_layer = [m for m in bm["per_layer"]
                 if "workloads" not in m or name in m["workloads"]]
    return Cell(name, chips, conf, mix, point,
                copy.deepcopy(bm["end_to_end"]), per_layer)
