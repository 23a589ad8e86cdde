"""PM2Lat's chip benchmark: one cell, one run.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``, whose ``kind`` picks ``bench/kinds/<kind>.py``).
A run:

1. Set-up: finds a TPU with the chips the cell asks for (and exits non-zero
   with no result otherwise), keeps JAX's compile cache inside the checkout,
   calibrates the predictor fresh on the chip in the cell's type, makes the
   weights and inputs on the device from ``--seed``, and warms every shape
   the window uses.
2. Window: the step phase runs the cell's jitted step back to back for half
   of ``--seconds``; the query phase then asks the predictor
   (``LatencyService``) the mix's fixed set of distinct questions, one after
   another, in the seed's order (the set is sized to take about the other
   half at today's speed).
3. Check: frees the program's state, runs the plain reference, and compares.

The last line of standard output is one JSON object.  With ``--trace 0`` its
metrics are the cell's end-to-end metrics; with ``--trace 1`` the step half
of the window is traced and its metrics are the per-layer ones, each read by
``bench/metrics/<metric>.py``.  The numbers compared for ``correct`` are
printed beside their limits as the last lines of standard error and under
the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

# libtpu logs to a fixed directory under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import common  # noqa: E402
import traffic  # noqa: E402


def load(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def find_chips(chips: int) -> dict:
    """The device record; exits non-zero when JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; jax found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; jax found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def persistent_cache(on: bool):
    """Turn JAX's persistent compile cache on or off from here on."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", on)
    cc.reset_cache()


def calibrate(dtype: str):
    """A fresh calibration in the cell's type; the predictor's default
    (framework) kernels read no Pallas table, so none is calibrated."""
    from repro.core import calibrate as cal
    from repro.serving.latency_service import LatencyService
    t = time.perf_counter()
    store = cal.calibrate_host(dtypes=(dtype,), pallas=False, verbose=False)
    return LatencyService(store, cal.device_name()), time.perf_counter() - t


def ask_queries(svc, cfg, spec, seed, exclude):
    """One client asks the stream's points one after another: each answer's
    latency, and how many answers were bad (a query that raised, or
    answered other than a positive finite time)."""
    lat, bad = [], 0
    for point in traffic.query_points(spec, seed, exclude=exclude):
        t = time.perf_counter()
        try:
            ans = traffic.ask(svc, cfg, spec, point, cfg.compute_dtype)
            ok = math.isfinite(ans) and ans > 0
        except Exception:  # noqa: BLE001 - a failed query is counted, not fatal
            if not bad:
                log(traceback.format_exc())
            ok = False
        lat.append(time.perf_counter() - t)
        bad += not ok
    return lat, bad


def run(args, cell_mod=None) -> dict:
    """One run; returns the result object.  ``cell_mod`` stands in for the
    kind's module (tests drive the harness with a broken timed path)."""
    work, conf, mix = common.cell(args.workload)
    device = find_chips(work["chips"])
    # JAX's persistent cache at a fixed path inside the checkout (or where
    # JAX_COMPILATION_CACHE_DIR says), as the program's entry points keep it
    from repro.launch import compile_cache
    compile_cache.enable_compile_cache()
    import jax
    cfg = common.model_config(conf)
    reference = load("reference", conf["reference"])
    cell = (cell_mod or load("kinds", mix["kind"])).Cell(
        cfg, conf, mix, args.seed, reference)
    rec = {"device_kind": device["kind"]}

    svc, rec["calib_s"] = calibrate(cfg.compute_dtype)
    half = args.seconds / 2.0
    with cell.context():
        cell.setup()
        predicted = cell.predicted_step_s(svc)   # also warms the query path
        n = max(2, round(half / cell.step_estimate_s))
        setup_s = time.perf_counter() - T_START

        trace_dir = None
        if args.trace:
            import tempfile
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # host spans only: no per-call tracing
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.steps"):
            rec["step_phase_s"] = cell.run_steps(n)
        if args.trace:
            jax.profiler.stop_trace()
    rec["steps"] = n
    # Each new query shape makes the predictor compile; the stream's points
    # are new to it, and must not be answered by the compiles of earlier
    # runs, so the persistent cache is off while it runs.
    persistent_cache(False)
    t_queries = time.perf_counter()
    lat, bad = ask_queries(svc, cfg, mix["queries"], args.seed,
                           exclude=[traffic.own_point(mix)])
    rec["query_phase_s"] = time.perf_counter() - t_queries
    persistent_cache(True)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)

    cell.release()
    gc.collect()
    rec.update(cell.work())
    step_s = rec["step_phase_s"] / n

    numbers, notes = cell.check()
    numbers["bad_answers"] = bad
    compared, correct = common.judge(numbers, dict(mix["limits"],
                                                   bad_answers=0))

    log(f"[bench] {args.workload} seed={args.seed} steps={n} "
        f"step_s={step_s!r} predicted_s={predicted!r} queries={len(lat)} "
        f"bad={bad} calib_s={rec['calib_s']!r} setup_s={setup_s!r} "
        f"peak_bytes={peak}")
    for note in notes:
        log(f"[check] {note}")

    result = {"correct": correct, "attempted": n + len(lat), "failed": bad,
              "metrics": {}, "device": dict(device, memory_peak_bytes=peak)}
    if args.trace:
        import device_trace as tracemod
        rec["trace"] = tracemod.reduce(trace_dir, "bench.steps")
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"].update(busy_s=rec["trace"]["busy_s"],
                                window_s=rec["trace"]["window_s"])
        result["breakdown"] = rec["trace"]["breakdown"]
        for m in common.benchmark()["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            value = load("metrics", m["name"]).read(rec)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"step_ms": step_s * 1e3,
                  "pred_err_pct": abs(predicted - step_s) / step_s * 100.0,
                  "query_ms_mean": rec["query_phase_s"] / len(lat) * 1e3,
                  "setup_s": setup_s}
        log(f"[queries] n={len(lat)} mean_ms={values['query_ms_mean']!r} "
            f"p50_ms={statistics.median(lat) * 1e3!r} "
            f"max_ms={max(lat) * 1e3!r}")
        units = {m["name"]: m["unit"] for m in common.benchmark()["end_to_end"]}
        for name, unit in units.items():
            if values.get(name) is not None:
                result["metrics"][name] = {"value": values[name], "unit": unit}
    result["compared"] = compared
    for k, c in compared.items():
        log(f"compared {k}={c['value']!r} limit={c['limit']!r}")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    result = run(parse_args(argv))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
