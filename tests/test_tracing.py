"""The program's own marks for the profiler: the ``attention`` name scope on
the compiled step's attention ops, the ``latency.*`` and
``predict.snippet_compile`` and ``predict.snippet_batch`` spans with the
counters behind ``LatencyService.stats``, and the per-family split of each
answer."""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry as cr
from repro.core import opgraph as og
from repro.models import attention as A
from repro.models import registry as mr
from repro.serving.latency_service import LatencyService

DOT = re.compile(r"^\s*(?:ROOT )?%(\S+) = \S+ (?:dot|convolution)\((.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _qwen2():
    return dataclasses.replace(cr.reduced("qwen2-0.5b"),
                               compute_dtype="bfloat16")


def _dots(hlo: str) -> list[tuple[str, bool, str]]:
    """(name, batched, op_name) of every dot of a compiled module.  The
    attention core's products are the model's only batched ones (over
    batch and key-value heads); projections, MLP and loss contract plain
    matrices."""
    out = []
    for line in hlo.splitlines():
        m = DOT.match(line)
        if m:
            name = OP_NAME.search(line)
            out.append((m.group(1), "lhs_batch_dims" in m.group(2),
                        name.group(1) if name else ""))
    return out


def _in_scope(op_name: str) -> bool:
    return A.SCOPE in op_name.split("/")


def _train_hlo(cfg):
    from repro.distributed import sharding as sh
    from repro.launch.train import build_mesh
    from repro.training import optimizer as opt
    from repro.training import step as tstep
    model = mr.build(cfg)
    with sh.mesh_context(build_mesh("1x1"), act_mode="tp", remat=True):
        params = jax.eval_shape(model.init, jax.random.key(0))
        state = jax.eval_shape(opt.init_opt_state, params)
        step = jax.jit(tstep.build_train_step(
            model, opt.AdamWConfig(lr=1e-3), num_microbatches=1,
            block_skip=False, fused_ce=True), donate_argnums=(0, 1))
        batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
                 for k in ("tokens", "labels")}
        return step.lower(params, state, batch).compile().as_text()


def _forward_hlo(cfg):
    model = mr.build(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    return jax.jit(model.forward).lower(
        params, jax.ShapeDtypeStruct((2, 64), jnp.int32)).compile().as_text()


@pytest.mark.parametrize("program", ["train", "forward"])
def test_attention_scope_marks_the_attention_dots_only(program):
    hlo = (_train_hlo if program == "train" else _forward_hlo)(_qwen2())
    dots = _dots(hlo)
    attn = [d for d in dots if d[1]]
    other = [d for d in dots if not d[1]]
    assert attn and other
    assert all(_in_scope(n) for _, _, n in attn), attn
    assert not any(_in_scope(n) for _, _, n in other), other
    backward = [n for _, _, n in attn if "transpose(" in n]
    forward = [n for _, _, n in attn if "transpose(" not in n]
    assert forward
    if program == "train":
        # the gradient's products (dq, dk, dv and the recomputed scores)
        assert len(backward) >= 4, backward
    else:
        assert not backward


def _host_events(trace_dir, names):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats)) for e in line.events
                        if e.name in names]
    return out


def test_query_spans_and_compile_counter(calibration_store, tmp_path):
    cfg = _qwen2()
    og._snippet_features.cache_clear()      # every snippet shape is new
    svc = LatencyService(calibration_store, "cpu_host")
    before = svc.stats["snippet_compiles"]
    with jax.profiler.trace(str(tmp_path)):
        first = svc.latency_train(cfg, 2, 96, dtype="bfloat16")
        new = svc.stats["snippet_compiles"]
        again = svc.latency_train(cfg, 2, 96, dtype="bfloat16")
        other = LatencyService(calibration_store, "cpu_host").latency_train(
            cfg, 2, 96, dtype="bfloat16")
        repeat = svc.stats["snippet_compiles"]
    assert new > before and repeat == new
    assert not first.cached and again.cached and not other.cached

    events = _host_events(tmp_path, {"latency.latency_train",
                                     "predict.snippet_compile",
                                     "predict.snippet_batch"})
    spans = [s for n, s in events if n == "latency.latency_train"]
    compiles = [s for n, s in events if n == "predict.snippet_compile"]
    batches = [s for n, s in events if n == "predict.snippet_batch"]
    assert len(compiles) == new - before
    # one batch per cold service, each compiling what it lacked together
    assert [s["n"] for s in batches] == [new - before, new - before]
    assert svc.stats["snippet_compile_batches"] == 1
    # a one-dimensional shape reads back as a number
    assert all(s["snippet"] in og.SNIPPETS
               and re.fullmatch(r"\d+(x\d+)*", str(s["shape"]))
               for s in compiles)
    assert sorted((s["query_id"], s["cached"]) for s in spans) == [
        (1, 0), (1, 0), (2, 1)]
    assert all(s["batch"] == 2 and s["seq"] == 96 for s in spans)

    plain = LatencyService(calibration_store, "cpu_host").latency_train(
        cfg, 2, 96, dtype="bfloat16")
    assert plain.to_json() == first.to_json() == other.to_json()


@pytest.mark.parametrize("model,batch,seq", [
    ("qwen2-0.5b", 2, 64), ("qwen2-0.5b", 8, 512),
    ("moonshot-v1-16b-a3b", 4, 128)])
@pytest.mark.parametrize("endpoint", ["latency_query", "latency_train"])
def test_kind_split_sums_to_the_answer(calibration_store, endpoint, model,
                                       batch, seq):
    cfg = dataclasses.replace(cr.reduced(model), compute_dtype="bfloat16")
    svc = LatencyService(calibration_store, "cpu_host")
    r = getattr(svc, endpoint)(cfg, batch, seq, dtype="bfloat16")
    split = r.kind_seconds
    assert set(split) == {"matmul", "attention", "memory"}
    assert all(v > 0 for v in split.values())
    assert sum(split.values()) == pytest.approx(r.seconds, rel=1e-9)
    hit = getattr(svc, endpoint)(cfg, batch, seq, dtype="bfloat16")
    assert hit.cached
    assert hit.kind_seconds == (split if endpoint == "latency_train" else None)


def test_kind_split_of_a_layout_sums_to_its_work(calibration_store):
    """Across chips the split counts collectives too and sums to the work,
    which overlap can make longer than the step."""
    svc = LatencyService(calibration_store, "cpu_host")
    r = svc.latency_train(_qwen2(), 4, 128, dp=2, tp=2, dtype="bfloat16")
    split = r.kind_seconds
    assert split["collective"] == pytest.approx(r.comm_seconds, rel=1e-12)
    assert sum(split.values()) == pytest.approx(
        r.fwd_seconds + r.bwd_seconds + r.optimizer_seconds
        + r.comm_seconds, rel=1e-9)
    assert sum(split.values()) >= r.seconds * (1 - 1e-9)
