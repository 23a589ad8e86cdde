"""What every cell shares: finding a cell's files by name, building the
program's model configuration from a configuration file, seeds, and the
rule that decides ``correct``."""
from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# configuration-file key -> the program's ModelConfig field
_ARCH_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "hidden_act": "mlp_act",
    "head_dim": "head_dim",
}


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def cell(name: str, root: str = ROOT) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file) of one cell."""
    bench = benchmark(root)
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    return (work,) + cell_files(work["config"], work["traffic"], root)


def cell_files(config: str, mix: str, root: str = ROOT) -> tuple[dict, dict]:
    """(configuration file, traffic file) by their names."""
    conf_entry = next(c for c in benchmark(root)["configs"]
                      if c["name"] == config)
    return (load_json(root, conf_entry["file"]),
            load_json(BENCH_DIR, "traffic", mix + ".json"))


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry with every architecture number of the file laid over it, so the
    model runs as the file states, whatever the registry holds."""
    from repro.configs import registry
    arch = conf["architecture"]
    fields = {_ARCH_FIELDS[k]: v for k, v in arch.items()}
    fields["head_dim"] = arch.get("head_dim") or (
        arch["hidden_size"] // arch["num_attention_heads"])
    fields["compute_dtype"] = conf["compute_dtype"]
    return dataclasses.replace(registry.get(conf["registry"]), **fields)


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of up to 62 bits as two non-negative 31-bit words."""
    if seed < 0 or seed >= 1 << 62:
        raise SystemExit(f"--seed must be in [0, 2**62): {seed}")
    return seed & 0x7FFFFFFF, seed >> 31


def judge(numbers: dict, limits: dict) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether every one is at
    or under it (a number with no limit, or not a number, fails)."""
    compared = {k: {"value": v, "limit": limits.get(k)}
                for k, v in numbers.items()}
    return compared, all(c["limit"] is not None and c["value"] <= c["limit"]
                         for c in compared.values())
