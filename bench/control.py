"""The readings that the limits of ``correct`` are set from, at a cell's own
size, on several seeds, in one process (set-up is long; this skips the
predictor's calibration and queries, which ``correct`` does not read).

  python bench/control.py --workload <cell> --seeds 11 12 13 [--steps 8]

For each seed it prints one JSON line with three sets of numbers, each
judged against the mix's limits by the rule a benchmark run uses
(``common.judge``), with its ``correct``:

- ``program``: the cell's set-up and a short window of its own steps, then
  the comparison with the float32 reference, as a benchmark run makes it;
- ``control``: the reference computed in float8 put in the program's place
  (``Cell.control``), which has to come out not correct;
- ``half_batch`` (training cells): the fault that leaves half of each batch
  out of the step and takes the mean over the rest, also not correct.

``BENCHMARK.json``'s runs never run this; it is for setting and checking the
limits in ``bench/traffic/<mix>.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

# libtpu logs to a fixed directory under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import common  # noqa: E402
import run as bench_run  # noqa: E402


def half_batch(kind_mod):
    """The training kind with each step given half of its batch."""
    class HalfBatch(kind_mod.Cell):
        def make_step(self, step_fn):
            def half(params, opt_state, batch):
                return step_fn(params, opt_state, {
                    k: v[: v.shape[0] // 2] for k, v in batch.items()})
            return super().make_step(half)
    return HalfBatch


def readings(cell_cls, cfg, conf, mix, seed, reference, steps, control):
    cell = cell_cls(cfg, conf, mix, seed, reference)
    with cell.context():
        cell.setup()
        cell.run_steps(steps)
    cell.release()
    gc.collect()
    numbers, notes = cell.check()
    out = {"numbers": numbers, "notes": notes}
    if control:
        out["control"] = cell.control()
    del cell
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args(argv)
    work, conf, mix = common.cell(args.workload)
    bench_run.find_chips(work["chips"])
    from repro.launch import compile_cache
    compile_cache.enable_compile_cache()
    cfg = common.model_config(conf)
    reference = bench_run.load("reference", conf["reference"])
    kind = bench_run.load("kinds", mix["kind"])
    for seed in args.seeds:
        line = {"workload": args.workload, "seed": seed}
        got = readings(kind.Cell, cfg, conf, mix, seed, reference, args.steps,
                       control=True)
        sets = {"program": got["numbers"], "control": got["control"]}
        if mix["kind"] == "train":
            sets["half_batch"] = readings(half_batch(kind), cfg, conf, mix, seed,
                                          reference, args.steps,
                                          control=False)["numbers"]
        for label, numbers in sets.items():
            compared, correct = common.judge(numbers, mix["limits"])
            line[label] = {"correct": correct, "compared": compared}
        line["notes"] = got["notes"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
