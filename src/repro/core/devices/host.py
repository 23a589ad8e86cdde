"""Empirical DeviceProfile for the calibrated device.

Cross-device transfer needs a SOURCE roofline to divide out of the measured
throughputs (``core/transfer.py``).  For the calibrated device that roofline
is derived from the calibration itself — the same stance as
``baselines/roofline.py``: peak := best observed matmul throughput per dtype,
bandwidth := the inverse bytes-coefficient of the memory model.  Deriving
both from the store keeps the profile consistent with the tables it anchors,
so calibrated->calibrated transfer is the identity by construction.

The calibrated device is either the CPU host (``cpu_host``) or an
accelerator named after its ``device_kind`` (``calibrate.device_name``).  An
accelerator takes what a calibration cannot measure — kind, memory sizes,
interconnect — from the datasheet profile of its chip.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional

from repro.core.collectives import DEFAULT_INTERCONNECT
from repro.core.devices.profiles import TPU_V5E, GiB, KiB, MiB, DeviceProfile
from repro.core.table import TableStore

CPU_HOST = "cpu_host"

_FALLBACK_BW = 2e10          # bytes/s, matches core/device.host_device_model
_FALLBACK_PEAK = 5e10

# ``device_kind`` as jax reports it -> the datasheet profile of that chip
ACCELERATOR_KINDS: Dict[str, DeviceProfile] = {"TPU v5 lite": TPU_V5E}


def _slug(device_kind: str) -> str:
    return device_kind.lower().replace(" ", "_")


_CHIPS = {_slug(k): p for k, p in ACCELERATOR_KINDS.items()}


def accelerator_name(device_kind: str) -> str:
    """The calibrated device's name for an accelerator ``device_kind``
    ('TPU v5 lite' -> 'tpu_v5_lite'); raises for a chip with no profile."""
    if device_kind not in ACCELERATOR_KINDS:
        raise KeyError(f"no device profile for accelerator {device_kind!r}; "
                       f"known: {sorted(ACCELERATOR_KINDS)}")
    return _slug(device_kind)


def chip_profile(name: str) -> Optional[DeviceProfile]:
    """The datasheet profile behind a calibrated accelerator name, or None
    for a name that is not one."""
    return _CHIPS.get(name)


def host_profile_from_store(store: TableStore,
                            name: Optional[str] = None) -> DeviceProfile:
    """Derive the calibrated device's analytical profile from its tables."""
    name = name or (store.meta or {}).get("device") or CPU_HOST
    chip = chip_profile(name)
    if chip is None and name != CPU_HOST:
        raise ValueError(f"{name!r} is not a calibrated device name "
                         f"({CPU_HOST!r} or one of {sorted(_CHIPS)})")
    peaks: Dict[str, float] = {}
    for t in store.tables.values():
        if t.key.op != "matmul" or t.key.device != name:
            continue
        peaks[t.key.dtype] = max(peaks.get(t.key.dtype, 0.0),
                                 max(t.anchors.values()))
    mm = store.memory_model
    coef = (mm["coef"] if isinstance(mm, dict)
            else (mm.coef if mm is not None else None))
    bw = 1.0 / coef[0] if coef is not None and coef[0] > 0 else None
    if chip is not None:
        if not peaks or bw is None:
            raise ValueError(f"store for {name!r} lacks matmul tables or a "
                             f"memory model; recalibrate on the device")
        return dataclasses.replace(
            chip, name=name, peak_flops=peaks, hbm_bw=bw,
            notes=f"empirical peaks and bw on {chip.name}; sizes and "
                  f"interconnect from its datasheet")
    return DeviceProfile(
        name=name, kind="cpu",
        peak_flops=peaks or {"float32": _FALLBACK_PEAK},
        hbm_bw=bw or _FALLBACK_BW,
        hbm_bytes=32 * GiB, l2_bytes=32 * MiB, smem_bytes=64 * KiB,
        sm_count=os.cpu_count() or 1,
        link_bw=1e9,
        # exactly the unregistered-device default, so collective predictions
        # for the host are identical whether or not the lazy registration in
        # BatchPredictor.host_profile() has run yet
        interconnect=DEFAULT_INTERCONNECT,
        notes="empirical: peaks from matmul anchors, bw from memory-model "
              "bytes coefficient")
