"""Rehearsals of the benchmark off the chip: each cell at the program's
CPU-sized widths and a few tokens, driven through ``bench/run.py``'s own
``run`` with the look for a chip and the calibration stood in for."""
from __future__ import annotations

import argparse
import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import common  # noqa: E402
import run as bench_run  # noqa: E402

# Per kind, the mix's sizes at rehearsal scale, and the limits of
# ``correct`` at that scale.  The cells' own limits are set from readings at
# their own sizes on the chip; these from CPU readings of the rehearsals
# (sound runs: worst gradient leaf 7e-4..1.6e-3, mean 2.1e-4..3.5e-4, update
# 4e-4..6.5e-3, logit gaps 0..0.04; the float8 control: worst gradient leaf
# 7.9e-3..1.9e-2, mean 3.5e-3..6e-3, logit gaps 0.17..0.62; half of the
# batch: mean 1.2e-2..4.8e-2, update 8e-3..4.1e-2).
SMALL = {
    "train": {"batch": 2, "seq": 32, "data_pool": 4,
              "limits": {"grad_gap": 5e-3, "grad_gap_mean": 1.5e-3,
                         "update_gap": 1e-2}},
    "forward": {"batch": 1, "seq": 64, "limits": {"logit_gap": 0.1}},
}


class StubService:
    """Answers every predictor question with a fixed time: the rehearsals
    exercise the harness, not the predictor."""

    def latency_train(self, *a, **k):
        return argparse.Namespace(seconds=0.01)

    latency_query = latency_train


def reduced_conf(conf: dict) -> dict:
    """The configuration at the program's CPU-sized widths
    (``registry.reduced``: same head ratio, biases and tying) and two layers,
    for rehearsals off the chip."""
    from repro.configs import registry
    r = registry.reduced(conf["registry"])
    arch = dict(conf["architecture"], hidden_size=r.d_model,
                intermediate_size=r.d_ff, num_attention_heads=r.n_heads,
                num_key_value_heads=r.n_kv_heads, head_dim=r.head_dim,
                num_hidden_layers=2, vocab_size=r.vocab_size)
    return dict(conf, architecture=arch)


def names() -> list[str]:
    """Every cell of BENCHMARK.json."""
    return [w["name"] for w in common.benchmark()["workloads"]]


def small_cell(name: str):
    config, mix_name = name.rsplit(".", 1)
    conf, mix = common.cell_files(config, mix_name)
    work = {"name": name, "config": config, "traffic": mix_name, "chips": 1}
    mix = copy.deepcopy(mix)
    mix.update(SMALL[mix["kind"]])
    return work, reduced_conf(conf), mix


def rehearse(name: str, seed: int = 12345, seconds: float = 0.4,
             cell_mod=None, monkeypatch=None) -> dict:
    """One run of ``name`` at rehearsal scale through ``run.run``."""
    cell = small_cell(name)
    monkeypatch.setattr(common, "cell", lambda n, root=None: cell)
    monkeypatch.setattr(bench_run, "find_chips", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    # the process's compile cache stays as the test run set it
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(bench_run, "persistent_cache", lambda on: None)
    monkeypatch.setattr(bench_run, "calibrate",
                        lambda dtype: (StubService(), 0.01))
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=0)
    return bench_run.run(args, cell_mod=cell_mod)
