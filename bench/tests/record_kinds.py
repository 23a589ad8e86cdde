"""Records the small chip trace that ``test_bench_kinds.py`` reads: the
rehearsal-sized training cell (qwen2-0.5b at the program's CPU-sized widths,
two layers, 2 x 32; ``rehearse.small_cell``), three steps traced under
``bench.steps``, beside the step's compiled HLO text.

  python bench/tests/record_kinds.py <out dir>

Needs a TPU; writes ``v5e_kinds.xplane.pb`` and ``v5e_kinds.hlo.gz``.
"""
from __future__ import annotations

import gzip
import os
import shutil
import sys
import tempfile

import rehearse  # noqa: F401 - puts bench/ on the path

import common  # noqa: E402
import device_trace  # noqa: E402
import run as bench_run  # noqa: E402
import split  # noqa: E402

CELL = "qwen2-0.5b.train-b8-s1024"


def main(out: str) -> int:
    import jax
    work, conf, mix = rehearse.small_cell(CELL)
    bench_run.find_chips(work["chips"])
    split.metadata_in_cache_key()
    cfg = common.model_config(conf)
    cell = bench_run.load("kinds", mix["kind"]).Cell(
        cfg, conf, mix, 2**31 + 13, bench_run.load("reference",
                                                   conf["reference"]))
    trace_dir = tempfile.mkdtemp(prefix="bench_kinds_")
    with cell.context():
        cell.setup()
        hlo = split.window_hlo(cell, mix["kind"])
        split.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.steps"):
            cell.run_steps(3)
        jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    shutil.copy(device_trace.xplane_file(trace_dir),
                os.path.join(out, "v5e_kinds.xplane.pb"))
    with gzip.open(os.path.join(out, "v5e_kinds.hlo.gz"), "wt") as f:
        f.write(hlo)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
