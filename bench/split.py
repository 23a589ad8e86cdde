"""One cell with both phases traced: the prediction's error split by op
family, and the query phase's compile share and count.

  python bench/split.py --workload <cell> --seed <n> --seconds <s>

Set-up is ``run.py``'s (calibration, weights, warm-up).  Then the profiler
records the step phase under ``bench.steps`` and the query phase under
``bench.queries``, and the trace is read (``op_kinds.py``):

- ``matmul_err_ms``, ``attention_err_ms``, ``memory_err_ms``: per family,
  |predicted - measured| ms per step.  Predicted is the family's part of
  the answer ``pred_err_pct`` compares (``kind_seconds`` of
  ``latency_train`` / ``latency_query`` at the cell's shape); measured is
  the device time of the window program's ops of that family.
- ``query_compile_pct``: the part of the time spent answering
  (``latency.*`` spans) that went to compiling memory snippets
  (``predict.snippet_compile`` spans).
- ``query_compiles_mean``: compile spans over queries asked.

Standard error carries each family's predicted and measured ms and signed
relative error, and the checks: the families' sum against the busy time,
the share of device time no instruction of the window program explains,
and the compile spans against ``LatencyService.stats``.  The last line of
standard output is one JSON object.  The step and query times it prints
are taken with the profiler on; ``run.py --trace 0`` gives them without.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import run as bench_run  # noqa: E402 - sets the path and libtpu's log dir

import common  # noqa: E402
import device_trace  # noqa: E402
import op_kinds  # noqa: E402
import traffic  # noqa: E402


def window_hlo(cell, kind: str) -> str:
    """The optimized HLO text of the cell's window program, lowered with
    the window's own arguments (inside the cell's context)."""
    if kind == "train":
        params, opt_state = cell.state
        lowered = cell.step.lower(params, opt_state, cell.batches[0])
    else:
        lowered = cell.fwd.lower(cell.params, cell.tokens)
    return lowered.compile().as_text()


def own_answer(svc, cell, mix: dict):
    """The predictor's answer at the cell's own shape (``run.py`` compares
    its ``seconds`` with the step)."""
    endpoint = mix["queries"]["endpoint"]
    return getattr(svc, endpoint)(cell.cfg, cell.batch, cell.seq,
                                  dtype=cell.cfg.compute_dtype)


def metadata_in_cache_key():
    """Key JAX's persistent compile cache on the programs' metadata too.
    By default the key leaves it out, so a program compiled before the
    ``attention`` scope existed answers for one that has it, and its text
    names no scope: ``attention`` would read nothing."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def start_trace(trace_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # host spans only: no per-call tracing
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run(args) -> dict:
    import jax
    from repro.launch import compile_cache
    work, conf, mix = common.cell(args.workload)
    device = bench_run.find_chips(work["chips"])
    compile_cache.enable_compile_cache()
    metadata_in_cache_key()
    cfg = common.model_config(conf)
    reference = bench_run.load("reference", conf["reference"])
    cell = bench_run.load("kinds", mix["kind"]).Cell(cfg, conf, mix,
                                                     args.seed, reference)
    svc, _ = bench_run.calibrate(cfg.compute_dtype)
    trace_dir = tempfile.mkdtemp(prefix="bench_split_")
    with cell.context():
        cell.setup()
        answer = own_answer(svc, cell, mix)
        hlo = window_hlo(cell, mix["kind"])
        n = max(2, round(args.seconds / 2.0 / cell.step_estimate_s))
        start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.steps"):
            step_phase_s = cell.run_steps(n)
    bench_run.persistent_cache(False)
    compiles_before = svc.stats["snippet_compiles"]
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.queries"):
        lat, bad = bench_run.ask_queries(svc, cfg, mix["queries"], args.seed,
                                         exclude=[traffic.own_point(mix)])
    query_phase_s = time.perf_counter() - t
    compiles = svc.stats["snippet_compiles"] - compiles_before
    jax.profiler.stop_trace()
    bench_run.persistent_cache(True)

    trace_file = device_trace.xplane_file(trace_dir)
    kinds = op_kinds.kind_times(trace_file, "bench.steps", hlo)
    spans = op_kinds.query_spans(trace_file, "bench.queries")
    predicted = answer.kind_seconds
    metrics = op_kinds.metrics(kinds, n, predicted, spans, len(lat))
    leaf_s = sum(kinds["kind_s"].values())
    checks = {
        "kinds_over_busy": leaf_s / kinds["busy_s"],
        "unmapped_pct": 100.0 * kinds["unmapped_s"] / kinds["busy_s"],
        "compile_spans_match_stats": spans["compiles"] == compiles,
        "trace_bytes": os.path.getsize(trace_file),
    }
    log = bench_run.log
    for k in op_kinds.KINDS:
        p, m = predicted[k] * 1e3, kinds["kind_s"][k] / n * 1e3
        log(f"[split] {k} predicted_ms={p!r} measured_ms={m!r} "
            f"rel_err={(p - m) / m if m else float('nan')!r}")
    log(f"[split] steps={n} step_ms={step_phase_s / n * 1e3!r} "
        f"queries={len(lat)} bad={bad} "
        f"query_ms_mean={query_phase_s / len(lat) * 1e3!r} "
        f"snippet_compiles={compiles} unmapped_ops={kinds['unmapped_ops']}")
    log(f"[split] checks {checks}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {"workload": args.workload, "seed": args.seed, "device": device,
            "metrics": metrics, "steps": n,
            "step_ms": step_phase_s / n * 1e3,
            "query_ms_mean": query_phase_s / len(lat) * 1e3,
            "queries": len(lat), "bad_answers": bad,
            "snippet_compiles": compiles,
            "predicted_ms": {k: v * 1e3 for k, v in predicted.items()},
            "measured_ms": {k: v / n * 1e3 for k, v in kinds["kind_s"].items()},
            "busy_ms": kinds["busy_s"] / n * 1e3, "spans": spans,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    print(json.dumps(run(ap.parse_args(argv))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
