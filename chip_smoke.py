"""Smoke run of PM2Lat's main path on a TPU, through the normal entry points.

  python chip_smoke.py             # one chip: calibrate, train, serve, predict
  python chip_smoke.py --chips 4   # train on a 2x2 mesh vs the same steps on
                                   # one of the four chips, and predict both
  python chip_smoke.py --save-calibration DIR   # also keep the tables

The model is full-width qwen2-0.5b with random weights from a seed, computing
in bfloat16.  Each phase is a function that also runs at a reduced config
(``tests/test_chip_smoke.py`` runs them on the CPU).  Nothing is caught: a
failed phase exits non-zero.  Without a TPU the script exits non-zero before
any phase.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen2-0.5b"
DTYPE = "bfloat16"
BATCH, SEQ, STEPS = 8, 1024, 5
# 2x2 mesh vs one chip, per step: bf16 compute sums in a different order
# once the matmuls are sharded (bf16 epsilon is 2**-8 ~ 3.9e-3)
LOSS_RTOL = 1e-2


def phase_device(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; jax found {d.platform!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} chips; jax found {len(devs)}")
    ver = importlib.metadata.version
    print(f"[device] kind={d.device_kind!r} count={len(devs)} "
          f"jax={ver('jax')} jaxlib={ver('jaxlib')} libtpu={ver('libtpu')}",
          flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def phase_calibrate(save_dir: str | None = None):
    """A fresh on-device calibration, never a stored one; saved under
    ``save_dir`` when one is given."""
    from repro.core import calibrate
    store = calibrate.calibrate_host(dtypes=(DTYPE,), pallas=True,
                                     verbose=False)
    print(f"[calibrate] device={store.meta['device']} "
          f"seconds={store.meta['seconds']:.1f} tables={len(store.tables)} "
          f"memory_model_train_rel_err="
          f"{store.memory_model['train_rel_err']:.4f}", flush=True)
    if save_dir:
        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir,
                            f"calibration_{calibrate.device_name()}.json")
        store.save(path)
        print(f"[calibrate] saved -> {path}", flush=True)
    return store


def phase_train(arch: str = ARCH, *, mesh: str = "1x1", steps: int = STEPS,
                batch: int = BATCH, seq: int = SEQ) -> dict:
    """``launch/train.run`` (remat on) from a fresh checkpoint directory;
    step 0 compiles and checkpoints, so step times are reported after it."""
    from repro.launch import train
    with tempfile.TemporaryDirectory() as ckpt:
        res = train.run(train.parse_args([
            "--arch", arch, "--compute-dtype", DTYPE, "--mesh", mesh,
            "--steps", str(steps), "--batch", str(batch), "--seq", str(seq),
            "--ckpt-dir", ckpt]))
    losses = res["losses"]
    assert len(losses) == steps, losses
    assert all(math.isfinite(x) for x in losses), losses
    assert res["restarts"] == 0, res["restarts"]
    step_s = res["step_s"][1:]
    print(f"[train] mesh={mesh} arch={arch} batch={batch} seq={seq} "
          f"losses={losses} step_s_after_0={step_s} "
          f"median_step_s={float(np.median(step_s)):.6f}", flush=True)
    return res


def phase_serve(arch: str = ARCH, *, requests: int = 8, prompt_len: int = 128,
                max_new: int = 32, max_batch: int = 8) -> dict:
    from repro.launch import serve
    out = serve.run(serve.parse_args([
        "--arch", arch, "--compute-dtype", DTYPE,
        "--requests", str(requests), "--prompt-len", str(prompt_len),
        "--max-new", str(max_new), "--max-batch", str(max_batch)]))
    assert out["tokens_out"] == requests * max_new, out
    print(f"[serve] arch={arch} tokens_out={out['tokens_out']} "
          f"decode_steps={out['decode_steps']} "
          f"throughput_tok_s={out['throughput_tok_s']:.1f}", flush=True)
    return out


def phase_mesh(arch: str = ARCH, **kw) -> tuple:
    """Training on a 2x2 mesh (data=2, model=2) against the same steps on
    one chip; the loss curves must agree within ``LOSS_RTOL``."""
    mesh = phase_train(arch, mesh="2x2", **kw)
    one = phase_train(arch, mesh="1x1", **kw)
    rel = (np.abs(np.subtract(mesh["losses"], one["losses"]))
           / np.abs(one["losses"]))
    print(f"[mesh] 2x2 vs 1x1 loss rel diff per step={rel.tolist()} "
          f"rtol={LOSS_RTOL}", flush=True)
    assert np.all(rel <= LOSS_RTOL), rel
    return mesh, one


def measure_forward(cfg, batch: int, seq: int) -> float:
    """Seconds per jitted forward (the repo's measurement protocol)."""
    from repro.core import profiler
    from repro.models import registry as mr
    model = mr.build(cfg)
    params = model.init(jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)
    return profiler.measure(jax.jit(model.forward), params, tokens)


def phase_predict(store, step_s, arch: str = ARCH, *, batch: int = BATCH,
                  seq: int = SEQ, dp: int = 1, tp: int = 1,
                  forward: bool = True) -> dict:
    """Predictions from the calibrated tables beside measured times: the
    forward (measured here) and the training step (measured by
    ``phase_train``, ``step_s`` after step 0).  No error bound yet."""
    from repro.configs import registry as cr
    from repro.core import calibrate
    from repro.serving.latency_service import LatencyService
    cfg = dataclasses.replace(cr.get_any(arch), compute_dtype=DTYPE)
    svc = LatencyService(store, calibrate.device_name())
    out = {}
    if forward:
        q = svc.latency_query(cfg, batch, seq, dtype=DTYPE)
        out["forward"] = (q.seconds, measure_forward(cfg, batch, seq))
    t = svc.latency_train(cfg, batch, seq, dp=dp, tp=tp, dtype=DTYPE)
    out[f"train_dp{dp}_tp{tp}"] = (t.seconds, float(np.median(step_s)))
    for what, (pred, meas) in out.items():
        assert math.isfinite(pred) and pred > 0, (what, pred)
        print(f"[predict] {what} {arch} batch={batch} seq={seq} {DTYPE}: "
              f"predicted_s={pred:.6f} measured_s={meas:.6f} "
              f"ratio={pred / meas:.3f}", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--save-calibration", metavar="DIR",
                    help="also save the calibrated tables under DIR")
    args = ap.parse_args(argv)
    device = phase_device(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    store = phase_calibrate(args.save_calibration)
    if args.chips == 1:
        res = phase_train()
        phase_serve()
        phase_predict(store, res["step_s"][1:])
    else:
        mesh, one = phase_mesh()
        phase_predict(store, mesh["step_s"][1:], dp=2, tp=2, forward=False)
        phase_predict(store, one["step_s"][1:], forward=False)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
