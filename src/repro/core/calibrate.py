"""Device calibration: run the PM2Lat data-collection pass on THIS device and
persist the throughput tables + memory model (paper §III-C protocol).

The paper's stance is per-device profiling ("for newer devices we rerun the
full data-collection on the target hardware").  The same code runs on the
CPU host (Pallas kernels in interpret mode) and on a TPU (kernels compiled
by Mosaic); ``device_name`` keys the tables by the device that ran them.

Collected kernel families (each a selection-oracle candidate, core/oracle.py):
  - matmul|xla_default@<m0>x<n0>      (the framework's jnp/einsum path, one
                                       table per reference grid), fp32 + bf16
  - bmm|xla_default@<b0>x<m0>x<n0>    (batched, one table per reference grid)
  - attention|fa_jnp                  (the model stack's flash-attention path)
  - matmul|mm_<cfg>                   (Pallas kernels - Table VI)
  - attention|fa_<cfg>                (Pallas flash attention, per dtype)
  - memory model                      (utility ops, linear regression)
"""
from __future__ import annotations

import os
import time
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import devices as D
from repro.core import memory_model as mm
from repro.core import profiler
from repro.core.table import KernelKey, TableStore, ThroughputTable
from repro.kernels import flash_attention as fkern
from repro.kernels import matmul as mkern
from repro.kernels.ops import interpret_default
from repro.models import attention as A

DEFAULT_K_ANCHORS = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)


def device_name() -> str:
    """``cpu_host`` on the CPU; on an accelerator, its ``device_kind`` as a
    name (``tpu_v5_lite`` on a v5e), which must have a registered profile."""
    if jax.default_backend() == "cpu":
        return D.CPU_HOST
    return D.accelerator_name(jax.devices()[0].device_kind)


def _table_from_measurements(key, anchors_dur, m0, n0, batch=1,
                             ref_tiles=1) -> ThroughputTable:
    anchors = {k: 2.0 * batch * m0 * n0 * k / d for k, d in anchors_dur.items()}
    k_max = max(anchors_dur)
    return ThroughputTable(key=key, anchors=anchors,
                           org_dur=anchors_dur[k_max], k_max=k_max,
                           ref_grid=(m0, n0), ref_tiles=ref_tiles,
                           ref_batch=batch)


REF_GRIDS = ((64, 256), (256, 256), (512, 512), (1024, 1024))

# bmm reference grids (B0, M0, N0): like the matmul grids, each regime the
# batched-GEMM lowering treats differently (many small mats, few large mats,
# skinny per-batch planes) is its own kernel with its own table — the
# selection oracle picks the nearest by (log-area, log-aspect) with the
# batch folded into the area.
BMM_REF_GRIDS = ((8, 256, 256), (32, 64, 64), (2, 512, 512))


def calibrate_matmul(store: TableStore, *, dtype=jnp.float32,
                     grids=REF_GRIDS,
                     k_anchors: Iterable[int] = DEFAULT_K_ANCHORS,
                     verbose=False):
    """One table per reference (M0,N0) grid: XLA picks different kernels for
    skinny vs square GEMMs, so each grid regime is its own PM2Lat kernel."""
    dt = jnp.dtype(dtype)
    f = jax.jit(lambda a, b: a @ b)
    for m0, n0 in grids:
        durs = {}
        for k in k_anchors:
            a = jnp.ones((m0, k), dt)
            b = jnp.ones((k, n0), dt)
            durs[k] = profiler.measure(f, a, b)
            if verbose:
                print(f"  matmul {dt.name} {m0}x{n0} K={k}: {durs[k]*1e3:.3f} ms")
        key = KernelKey("matmul", f"xla_default@{m0}x{n0}", dt.name,
                        device_name())
        store.add(_table_from_measurements(key, durs, m0, n0))


def calibrate_bmm(store: TableStore, *, dtype=jnp.float32,
                  grids=BMM_REF_GRIDS,
                  k_anchors=(32, 64, 128, 256, 512, 1024, 2048, 4096),
                  verbose=False):
    """One table per (B0, M0, N0) reference grid; the profiled batch is
    recorded as ``ref_batch`` (oracle metadata) instead of being folded into
    the grid, so aspect scoring sees the true per-batch plane."""
    dt = jnp.dtype(dtype)
    f = jax.jit(lambda a, b: jnp.einsum("bmk,bkn->bmn", a, b))
    for b0, m0, n0 in grids:
        durs = {}
        for k in k_anchors:
            a = jnp.ones((b0, m0, k), dt)
            b = jnp.ones((b0, k, n0), dt)
            durs[k] = profiler.measure(f, a, b)
            if verbose:
                print(f"  bmm {dt.name} {b0}x{m0}x{n0} K={k}: "
                      f"{durs[k]*1e3:.3f} ms")
        key = KernelKey("bmm", f"xla_default@{b0}x{m0}x{n0}", dt.name,
                        device_name())
        store.add(_table_from_measurements(key, durs, m0, n0, batch=b0))


def calibrate_attention(store: TableStore, *, dtype=jnp.float32, b0=2, h0=4,
                        hd0=64, s_anchors=(128, 256, 512, 1024, 2048, 4096),
                        verbose=False):
    """The framework's jnp flash-attention path; swept dim = sequence length
    (the attention analogue of the paper's K sweep)."""
    dt = jnp.dtype(dtype)
    spec = A.AttnSpec(causal=True, kv_block=128)
    f = jax.jit(lambda q, k, v: A.flash_attention(q, k, v, spec=spec))
    durs, anchors = {}, {}
    for s in s_anchors:
        q = jnp.ones((b0, s, h0, hd0), dt)
        durs[s] = profiler.measure(f, q, q, q)
        anchors[s] = 4.0 * b0 * h0 * s * s * hd0 / durs[s]
        if verbose:
            print(f"  fa_jnp S={s}: {durs[s]*1e3:.3f} ms")
    s_max = max(durs)
    key = KernelKey("attention", "fa_jnp", dt.name, device_name())
    store.add(ThroughputTable(key=key, anchors=anchors, org_dur=durs[s_max],
                              k_max=s_max, ref_grid=(b0 * h0 * s_max, s_max),
                              ref_tiles=1, ref_head_dim=hd0))


def calibrate_pallas_matmul(store: TableStore, configs=None, *,
                            dtype=jnp.float32,
                            k_anchors=(128, 256, 512, 1024, 2048),
                            verbose=False):
    """Pallas kernels: each BlockSpec config is its own
    kernel with its own table (kernel differentiation, Table VI).  The
    reference grid is PROPORTIONAL to the block config (2x2 tiles), so the
    selection oracle's nearest-grid rule can tell the configs apart — a
    shared fixed grid would make every ``mm_<cfg>`` score identically."""
    dt = jnp.dtype(dtype)
    configs = configs or (mkern.MatmulConfig(128, 128, 128),
                          mkern.MatmulConfig(256, 256, 256))
    for cfg in configs:
        m0 = 2 * cfg.bm
        n0 = 2 * cfg.bn
        f = jax.jit(lambda a, b: mkern.matmul_kernel(
            a, b, cfg, interpret=interpret_default()))
        durs = {}
        for k in k_anchors:
            kk = max(k, cfg.bk)
            kk = (kk // cfg.bk) * cfg.bk
            a = jnp.ones((m0, kk), dt)
            b = jnp.ones((kk, n0), dt)
            durs[kk] = profiler.measure(f, a, b, min_reps=3, min_total_s=0.01)
            if verbose:
                print(f"  {cfg.name} K={kk}: {durs[kk]*1e3:.3f} ms")
        key = KernelKey("matmul", cfg.name, dt.name, device_name())
        tiles = (m0 // cfg.bm) * (n0 // cfg.bn)
        t = _table_from_measurements(key, durs, m0, n0, ref_tiles=tiles)
        store.add(t)


def calibrate_pallas_attention(store: TableStore, configs=None, *,
                               dtypes=(jnp.float32,),
                               s_anchors=(128, 256, 512, 1024), verbose=False):
    """Each (bq, bk) BlockSpec config is its own PM2Lat kernel (Table VI),
    swept per dtype: the selection oracle differentiates ``fa_<cfg>`` tables
    by dtype exactly as it does the framework paths."""
    configs = configs or (fkern.FlashConfig(128, 128),)
    for dtype in dtypes:
        dt = jnp.dtype(dtype)
        for cfg in configs:
            f = jax.jit(lambda q, k, v: fkern.flash_attention_kernel(
                q, k, v, cfg, causal=True, interpret=interpret_default()))
            durs, anchors = {}, {}
            bh, hd = 4, 64
            for s in s_anchors:
                ss = max(s, cfg.bq, cfg.bk)
                q = jnp.ones((bh, ss, hd), dt)
                durs[ss] = profiler.measure(f, q, q, q, min_reps=3,
                                            min_total_s=0.01)
                anchors[ss] = 4.0 * bh * ss * ss * hd / durs[ss]
                if verbose:
                    print(f"  {cfg.name} {dt.name} S={ss}: "
                          f"{durs[ss]*1e3:.3f} ms")
            s_max = max(durs)
            key = KernelKey("attention", cfg.name, dt.name, device_name())
            store.add(ThroughputTable(key=key, anchors=anchors,
                                      org_dur=durs[s_max], k_max=s_max,
                                      ref_grid=(bh * s_max, s_max),
                                      ref_tiles=1, ref_head_dim=hd))


def calibrate_memory_model(store: TableStore, verbose=False):
    samples = mm.collect_utility_samples()
    model = mm.fit_memory_model(samples)
    store.memory_model = model.to_json()
    if verbose:
        print(f"  memory model: train rel err {model.train_rel_err:.3f}, "
              f"coef={model.coef}")
    return model


def calibrate_host(path: Optional[str] = None, *, dtypes=("float32",),
                   pallas: bool = True, verbose: bool = True) -> TableStore:
    """Full calibration pass; ~2-4 min on this host with default budget."""
    t0 = time.time()
    store = TableStore()
    for dt in dtypes:
        if verbose:
            print(f"[calibrate] matmul/bmm/attention dtype={dt}")
        calibrate_matmul(store, dtype=dt, verbose=verbose)
        calibrate_bmm(store, dtype=dt)
        calibrate_attention(store, dtype=dt, verbose=verbose)
    if pallas:
        if verbose:
            print("[calibrate] pallas kernels")
        calibrate_pallas_matmul(store, verbose=verbose)
        calibrate_pallas_attention(store, dtypes=dtypes, verbose=verbose)
    if verbose:
        print("[calibrate] memory model")
    calibrate_memory_model(store, verbose=verbose)
    store.meta = {"device": device_name(), "seconds": time.time() - t0}
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        store.save(path)
    if verbose:
        print(f"[calibrate] done in {store.meta['seconds']:.1f}s -> {path}")
    return store


def default_store_path() -> str:
    root = os.environ.get("REPRO_ARTIFACTS",
                          os.path.join(os.path.dirname(__file__), "..", "..",
                                       "..", "artifacts"))
    return os.path.abspath(os.path.join(root, f"calibration_{device_name()}.json"))


def load_or_calibrate(path: Optional[str] = None, **kw) -> TableStore:
    path = path or default_store_path()
    if os.path.exists(path):
        return TableStore.load(path)
    return calibrate_host(path, **kw)
