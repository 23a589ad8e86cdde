"""Ahead-of-time compiles of the calibrated Pallas kernels for a described
TPU v5e (no chip attached): Mosaic refuses here what it would refuse on
the chip — misaligned blocks, too much VMEM.  The topology is described
inside a fixture, never at import; keep every such compile in this file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fk
from repro.kernels import matmul as mk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bm,bk,bn,dtype", [
    (128, 128, 128, "float32"), (128, 128, 128, "bfloat16"),
    (256, 256, 256, "float32"), (256, 256, 256, "bfloat16"),
    (8, 128, 128, "bfloat16"),
])
def test_pallas_matmul_compiles_for_v5e(one_chip, bm, bk, bn, dtype):
    cfg = mk.MatmulConfig(bm, bk, bn)
    # the calibration's reference grid: 2x2 tiles, K a multiple of bk
    m, n, k = 2 * bm, 2 * bn, 4 * bk
    a = jax.ShapeDtypeStruct((m, k), jnp.dtype(dtype), sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.dtype(dtype), sharding=one_chip)
    _compile(lambda a, b: mk.matmul_kernel(a, b, cfg), a, b)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_pallas_flash_attention_compiles_for_v5e(one_chip, head_dim):
    cfg = fk.FlashConfig(128, 128)
    q = jax.ShapeDtypeStruct((4, 512, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v: fk.flash_attention_kernel(q, k, v, cfg,
                                                       causal=True), q, q, q)


def test_attention_scope_reaches_the_chips_products(one_chip):
    """The forward cell's program at one sequence (batch 1, where the CPU's
    compiler drops the products' metadata): compiled for the chip, every
    product of the attention core (named by its einsum) carries the
    ``attention`` scope, and no projection's does."""
    import dataclasses
    import re
    from repro.configs import registry as cr
    from repro.models import attention as A
    from repro.models import registry as mr
    cfg = dataclasses.replace(cr.reduced("qwen2-0.5b"),
                              compute_dtype="bfloat16")
    model = mr.build(cfg)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(model.init, jax.random.key(0)))
    text = jax.jit(model.forward).lower(params, jax.ShapeDtypeStruct(
        (1, 512), jnp.int32, sharding=one_chip)).compile().as_text()
    core, other = [], []
    for line in text.splitlines():
        m = re.search(r' (?:dot|convolution)\(.*op_name="([^"]*)"', line)
        if m:
            path = m.group(1).split("/")
            scoped = A.SCOPE in path
            (core if "->" in path[-2] else other).append(scoped)
    assert core and other
    assert all(core) and not any(other)
