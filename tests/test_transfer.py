"""Roofline-ratio transfer invariants (core/transfer.py), the device
registry, the calibrated device's description, and the
DeviceModel/DeviceProfile strict-dtype peak lookup.  All synthetic — no
calibration artifact."""
import dataclasses
import os

import numpy as np
import pytest

from repro.core import device as dev
from repro.core import devices as D
from repro.core.devices.profiles import DeviceProfile
from repro.core.table import KernelKey, TableStore, ThroughputTable
from repro.core.transfer import (arithmetic_intensity, transfer_memory_model,
                                 transfer_store, transfer_table)


def profile(name, peak, bw):
    return DeviceProfile(name=name, kind="gpu",
                         peak_flops={"float32": peak}, hbm_bw=bw,
                         hbm_bytes=2 ** 34, l2_bytes=2 ** 22,
                         smem_bytes=2 ** 16, sm_count=4)


def mm_table(device="src", ref=(256, 256), anchors=None):
    anchors = anchors or {64: 4e11, 256: 5e11, 1024: 6e11}
    kmax = max(anchors)
    return ThroughputTable(
        key=KernelKey("matmul", f"xla_default@{ref[0]}x{ref[1]}", "float32",
                      device),
        anchors=dict(anchors), org_dur=1e-3, k_max=kmax, ref_grid=ref,
        ref_tiles=1)


# ---------------------------------------------------------------------------
# transfer invariants
# ---------------------------------------------------------------------------

def test_identity_transfer_is_exact():
    src = profile("src", 1e12, 1e11)
    t = mm_table()
    out = transfer_table(t, src, src)
    assert out.anchors == t.anchors
    assert out.org_dur == t.org_dur
    assert out.key == t.key  # same device name -> same key


def test_compute_bound_scales_by_peak_ratio():
    """Every anchor's AI sits above BOTH ridges -> pure peak-FLOPs ratio."""
    src = profile("src", 1e12, 1e12)      # ridge 1 FLOP/B
    dst = profile("dst", 3e12, 1e12)      # ridge 3 FLOP/B
    t = mm_table()                        # AI(64) ~ 21, AI(1024) ~ 57
    for k in t.anchors:
        assert arithmetic_intensity(t, k) > 3
    out = transfer_table(t, src, dst)
    for k in t.anchors:
        assert out.anchors[k] == pytest.approx(3.0 * t.anchors[k], rel=1e-12)
    # duration shrinks by the same factor
    assert out.org_dur == pytest.approx(t.org_dur / 3.0, rel=1e-12)


def test_memory_bound_scales_by_bandwidth_ratio():
    """Every anchor's AI sits below BOTH ridges -> pure bandwidth ratio."""
    src = profile("src", 1e15, 1e10)      # ridge 1e5
    dst = profile("dst", 1e15, 5e10)      # ridge 2e4; AI ~ tens
    t = mm_table(anchors={64: 1e10, 256: 2e10, 1024: 3e10})
    out = transfer_table(t, src, dst)
    for k in t.anchors:
        assert out.anchors[k] == pytest.approx(5.0 * t.anchors[k], rel=1e-12)


def test_knee_rederived_on_target():
    """Compute-bound on the source but memory-bound on the target: the
    transferred anchor is clamped by the TARGET's bandwidth leg, not scaled
    by the peak ratio."""
    src = profile("src", 1e12, 1e12)        # ridge 1 -> compute-bound
    dst = profile("dst", 100e12, 1e9)       # ridge 1e5 -> memory-bound
    t = mm_table()
    out = transfer_table(t, src, dst)
    for k in t.anchors:
        ai = arithmetic_intensity(t, k)
        eff = t.anchors[k] / src.peak_flops["float32"]
        want = eff * ai * dst.hbm_bw        # dst roofline: bandwidth leg
        assert out.anchors[k] == pytest.approx(want, rel=1e-12)
        # never above the target roofline scaled by source efficiency
        assert out.anchors[k] < 100e12


def test_transferred_anchor_never_exceeds_target_roofline():
    # src roofline sits above every anchor (efficiency < 1), as calibration
    # guarantees for a profile derived from the same store
    src = profile("src", 1e12, 2.2e10)
    for peak, bw in ((19.5e12, 2e12), (67e12, 3.35e12), (30e12, 3e11)):
        dst = profile("d", peak, bw)
        out = transfer_table(mm_table(), src, dst)
        for k, thr in out.anchors.items():
            assert thr <= dst.roofline_throughput(
                arithmetic_intensity(out, k), "float32") * (1 + 1e-12)


def test_transfer_preserves_oracle_metadata():
    """Re-anchoring must carry the selection-oracle candidate metadata
    (ref_grid/ref_batch/ref_head_dim) so a transferred store still selects
    kernels exactly like the source calibration."""
    src = profile("src", 1e12, 1e11)
    dst = profile("dst", 3e12, 2e11)
    t = ThroughputTable(
        key=KernelKey("bmm", "xla_default@8x256x256", "float32", "src"),
        anchors={64: 4e11, 1024: 6e11}, org_dur=1e-3, k_max=1024,
        ref_grid=(256, 256), ref_tiles=1, ref_batch=8)
    out = transfer_table(t, src, dst)
    assert (out.ref_grid, out.ref_batch) == ((256, 256), 8)
    fa = ThroughputTable(
        key=KernelKey("attention", "fa_128x128", "float32", "src"),
        anchors={128: 1e10, 512: 2e10}, org_dur=1e-3, k_max=512,
        ref_grid=(2048, 512), ref_tiles=1, ref_head_dim=64)
    assert transfer_table(fa, src, dst).ref_head_dim == 64


def test_bmm_intensity_is_per_batch_plane():
    """ref_batch repeats every operand: arithmetic intensity equals the
    single-GEMM value of the unfolded (M0, N0) plane."""
    single = ThroughputTable(
        key=KernelKey("bmm", "a", "float32", "src"),
        anchors={64: 1e10}, org_dur=1e-3, k_max=64,
        ref_grid=(256, 256), ref_tiles=1)
    batched = ThroughputTable(
        key=KernelKey("bmm", "b", "float32", "src"),
        anchors={64: 1e10}, org_dur=1e-3, k_max=64,
        ref_grid=(256, 256), ref_tiles=1, ref_batch=16)
    assert arithmetic_intensity(batched, 64) == pytest.approx(
        arithmetic_intensity(single, 64))


def test_attention_intensity_is_seq_linear():
    t = ThroughputTable(
        key=KernelKey("attention", "fa_jnp", "float32", "src"),
        anchors={128: 1e10, 512: 2e10}, org_dur=1e-3, k_max=512,
        ref_grid=(2048, 512), ref_tiles=1)
    assert arithmetic_intensity(t, 128) == pytest.approx(32.0)
    assert arithmetic_intensity(t, 512) == pytest.approx(128.0)


def test_memory_model_transfer_scales_bytes_and_flops_not_intercept():
    src = profile("src", 1e12, 1e10)
    dst = profile("dst", 4e12, 5e10)        # 4x compute, 5x bandwidth
    mm = {"coef": [1e-10, 2e-12, 3e-12, 1e-5], "train_rel_err": 0.1,
          "class_coef": {"pointwise": [2e-10, 0.0, 0.0, 2e-5]}}
    out = transfer_memory_model(mm, src, dst)
    assert out["coef"][0] == pytest.approx(1e-10 / 5)   # bytes ~ 1/bw
    assert out["coef"][1] == pytest.approx(2e-12 / 4)   # flops ~ 1/peak
    assert out["coef"][2] == pytest.approx(3e-12 / 4)
    assert out["coef"][3] == 1e-5                       # launch overhead
    assert out["class_coef"]["pointwise"][0] == pytest.approx(2e-10 / 5)
    assert out["class_coef"]["pointwise"][3] == 2e-5
    # source dict untouched
    assert mm["coef"][0] == 1e-10


def test_memory_model_ratio_uses_shared_dtype_not_fallback(recwarn):
    """A host calibrated only for bf16 must scale compute coefficients by a
    dtype BOTH profiles genuinely quote — never by one side's silent
    max-peak fallback against the other's real fp32 peak."""
    src = dataclasses.replace(profile("src", 0.0, 1e10),
                              peak_flops={"bfloat16": 2e12})
    dst = dataclasses.replace(profile("dst", 0.0, 1e10),
                              peak_flops={"float32": 67e12,
                                          "bfloat16": 8e12})
    mm = {"coef": [0.0, 4e-12, 0.0, 1e-5], "train_rel_err": 0.0,
          "class_coef": {}}
    out = transfer_memory_model(mm, src, dst)
    assert out["coef"][1] == pytest.approx(4e-12 * 2e12 / 8e12)   # bf16 ratio
    assert not recwarn.list                     # no peak-fallback warning


def test_tpu_v5e_profile_mirrors_device_model():
    """The v5e datasheet lives once, in core/device.TPU_V5E; the fleet
    profile must track it."""
    p, m = D.get_profile("tpu_v5e"), dev.TPU_V5E
    assert p.peak_flops == m.peak_flops
    assert (p.hbm_bw, p.hbm_bytes, p.smem_bytes, p.link_bw) == \
        (m.hbm_bw, m.hbm_bytes, m.vmem_bytes, m.ici_bw)


# ---------------------------------------------------------------------------
# the calibrated device's description
# ---------------------------------------------------------------------------

def calibrated_store(device):
    st = TableStore()
    st.add(mm_table(device))
    st.memory_model = {"coef": [1e-11, 0.0, 0.0, 1e-6], "train_rel_err": 0.0,
                       "class_coef": {}}
    st.meta = {"device": device}
    return st


def test_device_name_is_cpu_host_on_cpu():
    from repro.core import calibrate
    assert calibrate.device_name() == "cpu_host"


def test_calibrated_v5e_takes_sizes_and_interconnect_from_its_chip():
    from repro.core.collectives import interconnect_for
    name = D.accelerator_name("TPU v5 lite")
    chip = D.get_profile("tpu_v5e")
    # collectives price over the chip's ICI even before registration
    assert name not in D.REGISTRY
    assert interconnect_for(name) == chip.interconnect
    p = D.host_profile_from_store(calibrated_store(name))
    assert p.name == name and p.kind == "tpu"
    assert p.hbm_bytes == 16 * 1024 ** 3 and p.smem_bytes == chip.smem_bytes
    assert p.interconnect == chip.interconnect
    # what the calibration measured is kept
    assert p.peak_flops == {"float32": 6e11}
    assert p.hbm_bw == pytest.approx(1e11)


def test_uncalibrated_or_unknown_accelerator_raises():
    with pytest.raises(KeyError, match="no device profile"):
        D.accelerator_name("TPU v99")
    with pytest.raises(ValueError, match="not a calibrated device"):
        D.host_profile_from_store(calibrated_store("gpu_host"))
    # an accelerator never falls back to assumed peaks or bandwidth
    empty = TableStore()
    empty.meta = {"device": D.accelerator_name("TPU v5 lite")}
    with pytest.raises(ValueError, match="recalibrate"):
        D.host_profile_from_store(empty)
    # the CPU host keeps its fallback
    assert D.host_profile_from_store(TableStore()).kind == "cpu"


def test_compile_cache_follows_env_else_fixed_in_tree_path(monkeypatch):
    import jax
    from repro.launch import compile_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv(cc.CACHE_ENV, "/elsewhere")
        assert cc.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev[keys[0]]
        monkeypatch.delenv(cc.CACHE_ENV)
        first = cc.enable_compile_cache()
        assert first == cc.enable_compile_cache() == cc.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == first
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(root, ".jax_compilation_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)


def test_transfer_store_rekeys_and_drops_foreign_tables():
    src, dst = profile("src", 1e12, 1e11), profile("dst", 2e12, 2e11)
    st = TableStore()
    st.add(mm_table("src"))
    st.add(mm_table("other"))               # different device: must not move
    st.memory_model = {"coef": [1e-10, 0.0, 0.0, 1e-5], "train_rel_err": 0.0,
                       "class_coef": {}}
    st.meta = {"device": "src"}
    out = transfer_store(st, src, dst)
    assert len(out.tables) == 1
    (t,) = out.tables.values()
    assert t.key.device == "dst"
    assert out.meta["device"] == "dst"
    assert out.meta["transferred_from"] == "src"
    assert out.memory_model["coef"][0] != st.memory_model["coef"][0]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_has_the_fleet_and_helpful_errors():
    for name in ("a100_80g", "h100_sxm", "v100", "rtx_4090", "l4", "tpu_v5e"):
        p = D.get_profile(name)
        assert p.hbm_bw > 0 and p.peak("float32") > 0 and p.sm_count > 0
    with pytest.raises(KeyError, match="registered"):
        D.get_profile("a100-80gb")          # near-miss name lists the fleet


def test_register_rejects_conflict_allows_idempotent():
    p = profile("tmp_dev", 1e12, 1e11)
    D.register(p)
    D.register(p)                           # identical re-register: no-op
    with pytest.raises(ValueError):
        D.register(profile("tmp_dev", 9e12, 1e11))
    D.register(profile("tmp_dev", 9e12, 1e11), overwrite=True)
    del D.REGISTRY["tmp_dev"]


def test_ridge_and_roofline_throughput():
    p = profile("p", 8e12, 2e12)
    assert p.ridge("float32") == pytest.approx(4.0)
    assert p.roofline_throughput(2.0, "float32") == pytest.approx(4e12)
    assert p.roofline_throughput(100.0, "float32") == pytest.approx(8e12)


# ---------------------------------------------------------------------------
# strict/warning peak lookup (DeviceModel + DeviceProfile)
# ---------------------------------------------------------------------------

def test_device_model_peak_warns_on_unknown_dtype():
    with pytest.warns(UserWarning, match="float16"):
        got = dev.TPU_V5E.peak("float16")
    assert got == max(dev.TPU_V5E.peak_flops.values())


def test_device_model_peak_known_dtype_no_warning(recwarn):
    assert dev.TPU_V5E.peak("bfloat16") == 197e12
    assert not recwarn.list


def test_peak_strict_flag_raises():
    with pytest.raises(KeyError, match="no peak-FLOPs entry"):
        dev.TPU_V5E.peak("floa32", strict=True)
    with pytest.raises(KeyError):
        D.get_profile("a100_80g").peak("f32", strict=True)


def test_peak_strict_env(monkeypatch):
    monkeypatch.setenv(dev.STRICT_DTYPE_ENV, "1")
    with pytest.raises(KeyError):
        dev.TPU_V5E.peak("float16")
    monkeypatch.setenv(dev.STRICT_DTYPE_ENV, "0")
    with pytest.warns(UserWarning):
        dev.TPU_V5E.peak("float16")
