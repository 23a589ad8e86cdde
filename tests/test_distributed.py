"""Distributed behavior on fake devices (subprocesses own the XLA flag —
the main test process must keep its single real device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code: str, devices: int = 8, timeout: int = 520) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_train_on_2x4_mesh_matches_single_device():
    """3 steps on a (2,4) data x model mesh == 3 steps on 1 device."""
    code = """
    import jax, json
    import jax.numpy as jnp
    from repro.configs import registry as cr
    from repro.models import registry as mr
    from repro.distributed import sharding as sh, specs as sp
    from repro.training import optimizer as opt, step as tstep
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    import dataclasses

    cfg = dataclasses.replace(cr.reduced("qwen2-0.5b", n_layers=2),
                              compute_dtype="float32")
    model = mr.build(cfg)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8, seed=0))
    adamw = opt.AdamWConfig(lr=1e-3)

    def run(mesh_shape):
        mesh = make_mesh(mesh_shape, ("data", "model"))
        with sh.mesh_context(mesh):
            params = model.init(jax.random.key(0))
            o = opt.init_opt_state(params)
            p_specs = sp.params_specs(params)
            ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s),
                tree, is_leaf=lambda s: isinstance(s, P))
            params = jax.device_put(params, ns(p_specs))
            o = jax.device_put(o, ns(sp.opt_specs(o, p_specs)))
            step = jax.jit(tstep.build_train_step(model, adamw))
            losses = []
            for s in range(3):
                params, o, m = step(params, o, data.batch_at(s))
                losses.append(float(m["loss"]))
        return losses

    l_mesh = run((2, 4))
    l_single = run((1, 1))
    print(json.dumps({"mesh": l_mesh, "single": l_single}))
    """
    out = json.loads(_run(code).strip().splitlines()[-1])
    for a, b in zip(out["mesh"], out["single"]):
        assert abs(a - b) / abs(b) < 2e-4, out


@pytest.mark.slow
def test_compressed_psum_across_8_devices():
    code = """
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    from repro.distributed import compression as comp
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 512)), jnp.float32)
    f = shard_map(lambda s: comp.compressed_psum(s[0], "dp"), mesh=mesh,
                  in_specs=P("dp"), out_specs=P())
    y = f(x)
    true = np.asarray(x).sum(0)
    rel = np.abs(np.asarray(y) - true) / (np.abs(true) + 1e-3)
    print("REL", float(rel.mean()))
    assert float(rel.mean()) < 0.05
    """
    out = _run(code)
    assert "REL" in out


@pytest.mark.slow
def test_elastic_reshard_8_to_6_devices():
    code = """
    import jax, numpy as np, jax.numpy as jnp
    from repro.ft import elastic
    from jax.sharding import NamedSharding, PartitionSpec as P
    devs = jax.devices()
    mesh8 = elastic.make_elastic_mesh(devs, 4, 2)
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh8, P("data", "model")))
    plan = elastic.plan_elastic_mesh(6, model_degree=2, global_batch=8)
    assert plan == (2, 2) or plan == (3, 2), plan
    d, m = plan
    mesh_new = elastic.make_elastic_mesh(devs, d, m)
    y = jax.device_put(x, NamedSharding(mesh_new, P("data", "model")))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    print("OK")
    """
    assert "OK" in _run(code)


@pytest.mark.slow
def test_sharded_decode_step_lowered_on_mesh():
    """decode_step lowers+compiles with KV cache sharded over a (2,4) mesh."""
    code = """
    import jax, jax.numpy as jnp, dataclasses
    from repro.configs import registry as cr
    from repro.models import registry as mr
    from repro.distributed import sharding as sh, specs as sp
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    cfg = dataclasses.replace(cr.reduced("yi-6b", n_layers=2), compute_dtype="float32")
    model = mr.build(cfg)
    mesh = make_mesh((2, 4), ("data", "model"))
    with sh.mesh_context(mesh):
        params = model.abstract_params()
        cache = model.abstract_cache(8, 64, dtype=jnp.float32)
        p_specs = sp.params_specs(params)
        c_specs = sp.cache_specs(cache, cfg)
        ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda s: isinstance(s, P))
        f = jax.jit(model.decode_step,
                    in_shardings=(ns(p_specs),
                                  NamedSharding(mesh, P("data")), ns(c_specs)))
        lowered = f.lower(params, jax.ShapeDtypeStruct((8,), jnp.int32), cache)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        print("COMPILED", ca["flops"] > 0)
    """
    assert "COMPILED True" in _run(code)
