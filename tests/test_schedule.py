"""Schedule-aware op IR + two-stream list-schedule simulator:
no-overlap bit-identity with the sequential sum, makespan bounds
(max busy <= makespan <= sequential sum) across swept configs, emergent
pipeline bubble shrinking with microbatches, bucketed gradient-comm
overlap in the training step, MoE all-to-all payloads, spec-keyed
prediction caching, and the docs/parallelism.md overlap worked example."""
import dataclasses
import functools
import math
import operator

import numpy as np
import pytest

from repro.configs import registry as cr
from repro.core import calibrate
from repro.core import collectives as CC
from repro.core import opgraph as og
from repro.core import schedule as S
from repro.core.batch_predict import BatchPredictor, PredictionCache
from repro.core.partition import plan_stages_model
from repro.core.predictor import PM2Lat


@pytest.fixture(scope="module")
def bp(calibration_store):
    return BatchPredictor(calibration_store, calibrate.device_name())


# ---------------------------------------------------------------------------
# the IR
# ---------------------------------------------------------------------------

def test_op_union_and_streams():
    mm = og.MatmulOp("x", m=8, n=8, k=8)
    co = CC.CollectiveOp("c", "all_reduce", 1.0, 2)
    assert isinstance(mm, og.OP_TYPES) and isinstance(co, og.OP_TYPES)
    assert og.stream_of(mm) == og.COMPUTE_STREAM
    assert og.stream_of(co) == og.COMM_STREAM


def test_opgraph_chain_and_deps():
    ops = [og.MatmulOp(f"m{i}", m=8, n=8, k=8) for i in range(3)]
    g = og.OpGraph.chain(ops)
    assert g.ops() == ops and len(g) == 3
    assert [n.deps for n in g.nodes] == [(), (0,), (1,)]
    with pytest.raises(AssertionError):
        g.add(ops[0], deps=(99,))           # forward reference rejected
    i = g.add(CC.CollectiveOp("c", "p2p", 1.0, 2), deps=g.tail())
    assert g.nodes[i].stream == og.COMM_STREAM


def test_enumerate_graph_is_the_flat_list():
    cfg = cr.get_any("qwen3-mini")
    g = og.enumerate_graph(cfg, 4, 128)
    assert g.ops() == og.enumerate_ops(cfg, 4, 128)
    assert all(n.stream == og.COMPUTE_STREAM for n in g.nodes)


def test_spec_microbatches_validation_and_tag():
    with pytest.raises(ValueError, match="microbatches"):
        og.ParallelismSpec(microbatches=0)
    # default microbatches leave the historical tag untouched
    assert og.ParallelismSpec(dp=2, tp=4, pp=2, act_mode="sp").tag() \
        == "dp2.tp4.pp2.sp"
    assert og.ParallelismSpec(pp=2, microbatches=4).tag() \
        == "dp1.tp1.pp2.tp.mb4"


def test_training_spec_validation_and_tag():
    with pytest.raises(ValueError, match="optimizer"):
        S.TrainingStepSpec(optimizer="lion")
    with pytest.raises(ValueError, match="invalid"):
        S.TrainingStepSpec(bucket_mb=0.0)
    assert S.TrainingStepSpec().tag() == "adamw.bkt25"
    assert S.TrainingStepSpec("sgd", bucket_mb=1.5).tag() == "sgd.bkt1.5"


# ---------------------------------------------------------------------------
# the simulator
# ---------------------------------------------------------------------------

def _fold(values):
    """Left-to-right float addition.  Python's ``sum`` compensates its
    rounding since 3.12, so it is not the sequence of additions a chain
    schedule makes."""
    return functools.reduce(operator.add, values, 0.0)


def test_simulate_chain_is_bitwise_sum():
    durs = [0.1, 0.0301, 7e-5, 0.42, 1e-9]
    streams = ["compute"] * 5
    deps = [()] + [(i,) for i in range(4)]
    _, ends, makespan = S.simulate(durs, streams, deps)
    assert makespan == _fold(durs)          # same additions, same order
    assert float(ends[-1]) == makespan


def test_simulate_two_stream_overlap():
    # compute 3+3 chained; comm 5 depends only on the first compute op
    durs = [3.0, 5.0, 3.0]
    streams = ["compute", "comm", "compute"]
    deps = [(), (0,), (0,)]
    starts, ends, makespan = S.simulate(durs, streams, deps)
    assert makespan == 8.0                  # comm hidden behind compute tail
    assert float(starts[1]) == float(starts[2]) == 3.0


def test_simulate_dep_beats_stream_availability():
    durs = [1.0, 4.0, 1.0]
    streams = ["compute", "comm", "compute"]
    deps = [(), (0,), (1,)]                 # second compute WAITS for comm
    _, ends, makespan = S.simulate(durs, streams, deps)
    assert makespan == 6.0                  # 1 + 4 + 1, comm exposed


# ---------------------------------------------------------------------------
# no-overlap golden: schedule == the historical sequential sum
# ---------------------------------------------------------------------------

def test_trivial_spec_schedule_bit_identical(bp):
    cfg = cr.reduced("qwen2-0.5b")
    want, _ = bp.predict_model(cfg, 2, 32)
    sched = bp.schedule_parallel(cfg, 2, 32, og.ParallelismSpec())
    assert sched.makespan == want           # bitwise, not approx
    assert sched.makespan == sched.sequential_seconds
    assert sched.comm_seconds == 0.0 and sched.exposed_comm_seconds == 0.0


def test_no_overlap_schedule_equals_sequential_sum(bp):
    """mb=1 schedules are serialized chains: makespan == sum of the very
    rows the pre-schedule predict_parallel returned — bit-identical."""
    cfg = cr.reduced("qwen2-0.5b")
    scalar = PM2Lat(bp.store, bp.device)
    for spec in (og.ParallelismSpec(tp=4), og.ParallelismSpec(pp=2),
                 og.ParallelismSpec(dp=2, tp=2, pp=2, act_mode="sp")):
        total, rows = bp.predict_parallel(cfg, 4, 32, spec)
        assert total == _fold(r.seconds for r in rows)
        flat = og.enumerate_parallel_ops(cfg, 4, 32, spec)
        assert [r.name for r in rows] == [o.name for o in flat]
        s_total, s_rows = scalar.predict_parallel(cfg, 4, 32, spec)
        assert s_total == _fold(r.seconds for r in s_rows)


def test_makespan_bounds_across_swept_configs(bp):
    """Acceptance invariant: for EVERY swept config,
    max(per-stream busy) <= makespan <= sequential sum."""
    cfg = cr.reduced("qwen2-0.5b")
    specs = [og.ParallelismSpec(), og.ParallelismSpec(tp=4),
             og.ParallelismSpec(pp=2), og.ParallelismSpec(pp=4),
             og.ParallelismSpec(pp=2, microbatches=4),
             og.ParallelismSpec(tp=2, pp=2, microbatches=2),
             og.ParallelismSpec(dp=2, microbatches=2),
             og.ParallelismSpec(dp=2, tp=2, pp=2, act_mode="sp",
                                microbatches=4)]
    for spec in specs:
        sched = bp.schedule_parallel(cfg, 8, 32, spec)
        busiest = max(sched.busy().values())
        assert busiest <= sched.makespan * (1 + 1e-9), spec
        assert sched.makespan <= sched.sequential_seconds * (1 + 1e-9), spec
        assert sched.bounds_ok(), spec
    for spec in (og.ParallelismSpec(dp=4),
                 og.ParallelismSpec(dp=2, pp=2, microbatches=4)):
        sched = bp.schedule_step(cfg, 8, 32, spec=spec,
                                 train=S.TrainingStepSpec(bucket_mb=1.0))
        assert sched.bounds_ok(), spec


def test_pipeline_bubble_shrinks_with_microbatches(bp):
    cfg = cr.reduced("qwen2-0.5b")
    shares = []
    for mb in (2, 4, 8):
        sched = bp.schedule_parallel(
            cfg, 16, 32, og.ParallelismSpec(pp=4, microbatches=mb))
        # overlap is real: the grid beats its own serialization
        assert sched.makespan < sched.sequential_seconds
        shares.append(sched.bubble_share)
    assert shares[0] > shares[1] > shares[2], shares


def test_pipeline_stage_count_matches_grid(bp):
    cfg = cr.reduced("qwen2-0.5b", n_layers=4)
    sched = bp.schedule_parallel(cfg, 8, 32,
                                 og.ParallelismSpec(pp=2, microbatches=2))
    stage_streams = {s for s in sched.streams if s.startswith("compute.s")}
    assert stage_streams == {"compute.s0", "compute.s1"}
    p2p = [r for r in sched.rows if r.name.startswith("pp.act_p2p")]
    assert len(p2p) == 2                    # (pp-1) hand-offs per microbatch


# ---------------------------------------------------------------------------
# training step
# ---------------------------------------------------------------------------

def test_training_step_structure(bp):
    cfg = cr.reduced("qwen2-0.5b")
    fwd_total, _ = bp.predict_model(cfg, 4, 32)
    total, rows = bp.predict_step(cfg, 4, 32)
    names = [r.name for r in rows]
    assert any(n.startswith("bwd.") for n in names)
    assert names[-1] == "opt.update"
    fwd = sum(r.seconds for r in rows
              if r.kind != "collective" and not r.name.startswith(("bwd.",
                                                                   "opt.")))
    bwd = sum(r.seconds for r in rows if r.name.startswith("bwd.")
              and r.kind != "collective")
    assert fwd == pytest.approx(fwd_total, rel=1e-12)
    # backward compute = bwd_fwd_ratio x forward compute (counts scale)
    assert bwd == pytest.approx(2.0 * fwd, rel=1e-9)
    assert total == pytest.approx(sum(r.seconds for r in rows), rel=1e-12)


def test_training_dp_buckets_overlap_backward(bp):
    cfg = cr.reduced("qwen2-0.5b")
    grad_bytes = cfg.param_count() * 4       # fp32 grads, tp=1
    small = bp.schedule_step(cfg, 8, 32, spec=og.ParallelismSpec(dp=4),
                             train=S.TrainingStepSpec(bucket_mb=0.25))
    one = bp.schedule_step(cfg, 8, 32, spec=og.ParallelismSpec(dp=4),
                           train=S.TrainingStepSpec(bucket_mb=1e6))
    n_small = sum(1 for r in small.rows if r.name.startswith("grad.bucket"))
    n_one = sum(1 for r in one.rows if r.name.startswith("grad.bucket"))
    assert n_one == 1
    assert n_small == math.ceil(grad_bytes / (0.25 * 2 ** 20))
    # bucket payloads sum to the full gradient volume
    tot = sum(o.nbytes for o in
              S.build_training_graph(cfg, 8, 32, og.ParallelismSpec(dp=4),
                                     S.TrainingStepSpec(bucket_mb=0.25)
                                     ).ops()
              if getattr(o, "name", "").startswith("grad.bucket"))
    assert tot == pytest.approx(grad_bytes)
    # bucketing hides comm behind backward; a single flush bucket cannot
    assert small.exposed_comm_seconds < small.comm_seconds
    assert one.exposed_comm_seconds == pytest.approx(one.comm_seconds,
                                                     rel=1e-6)


def test_training_optimizer_priced_by_memory_model(bp):
    cfg = cr.reduced("qwen2-0.5b")
    adamw, _ = [r for r in bp.predict_step(cfg, 2, 32)[1]
                if r.name == "opt.update"], None
    sgd = [r for r in bp.predict_step(
        cfg, 2, 32, train=S.TrainingStepSpec(optimizer="sgd"))[1]
        if r.name == "opt.update"]
    assert adamw[0].seconds > 0 and adamw[0].kernel == "linreg"
    assert sgd[0].seconds < adamw[0].seconds  # fewer state streams
    # tp shards the parameter update
    tp = [r for r in bp.predict_step(cfg, 2, 32,
                                     spec=og.ParallelismSpec(tp=4))[1]
          if r.name == "opt.update"]
    assert tp[0].seconds < adamw[0].seconds


def test_training_scalar_batch_agree(bp):
    cfg = cr.reduced("qwen2-0.5b")
    scalar = PM2Lat(bp.store, bp.device)
    spec = og.ParallelismSpec(dp=2, tp=2)
    train = S.TrainingStepSpec(bucket_mb=1.0)
    t_b, rows_b = bp.predict_step(cfg, 4, 32, spec=spec, train=train)
    t_s, rows_s = scalar.predict_step(cfg, 4, 32, spec=spec, train=train)
    assert t_b == pytest.approx(t_s, rel=1e-9)
    assert [r.name for r in rows_b] == [r.name for r in rows_s]


# ---------------------------------------------------------------------------
# MoE all-to-all
# ---------------------------------------------------------------------------

def test_moe_all_to_all_emitted_with_capacity_payload():
    cfg = cr.get_any("moonshot-v1-16b-a3b-reduced")
    assert cfg.moe is not None
    ops = og.enumerate_parallel_ops(cfg, 2, 64, og.ParallelismSpec(tp=4))
    a2a = [o for o in ops if isinstance(o, CC.CollectiveOp)
           and o.coll == "all_to_all"]
    assert {o.name for o in a2a} == {"moe.dispatch.all_to_all",
                                     "moe.combine.all_to_all"}
    n_moe = sum(1 for k in cfg.layer_kinds if k in og._FFN_KINDS)
    assert all(o.world == 4 and o.count == n_moe for o in a2a)
    assert a2a[0].nbytes == og.moe_routed_bytes(cfg, 2, 64, "float32")
    # payload grows with the capacity factor
    fat = dataclasses.replace(
        cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=2 * cfg.moe.capacity_factor))
    assert og.moe_routed_bytes(fat, 2, 64, "float32") > a2a[0].nbytes
    # dense models emit none
    dense = og.enumerate_parallel_ops(cr.get_any("qwen3-mini"), 2, 64,
                                      og.ParallelismSpec(tp=4))
    assert not any(getattr(o, "coll", "") == "all_to_all" for o in dense)


def test_all_to_all_alpha_beta_costs():
    ic = CC.Interconnect("nvlink-mesh", link_bw=25e9, link_latency=2e-6,
                         links_per_gpu=12)
    t, algo = CC.collective_time("all_to_all", 1e3, 8, ic)
    assert str(algo) == "tree"              # latency-bound: Bruck wins
    t, algo = CC.collective_time("all_to_all", 1e9, 8, ic)
    assert str(algo) == "ring"              # bandwidth-bound: pairwise wins
    # pairwise all-to-all moves the same per-rank volume as an all-gather
    ring_a2a = CC.collective_time("all_to_all", 1e8, 8, ic,
                                  algorithm="ring")[0]
    ring_ag = CC.collective_time("all_gather", 1e8, 8, ic,
                                 algorithm="ring")[0]
    assert float(ring_a2a) == pytest.approx(float(ring_ag), rel=1e-12)


# ---------------------------------------------------------------------------
# serving cache (spec-keyed) + partition makespan
# ---------------------------------------------------------------------------

def test_make_key_spec_suffix():
    base = PredictionCache.make_key("m", "dev", None, 2, 64)
    tagged = PredictionCache.make_key("m", "dev", None, 2, 64,
                                      spec="dp1.tp4.pp1.tp")
    assert tagged == base + "|dp1.tp4.pp1.tp" and base != tagged


def test_cache_accepts_dict_values(tmp_path):
    path = str(tmp_path / "c.json")
    cache = PredictionCache(maxsize=8, path=path)
    cache.put("k1", 1e-3)
    cache.put("k2", {"seconds": 2e-3, "comm_seconds": 1e-4})
    cache.save()
    back = PredictionCache(maxsize=8, path=path)
    assert back.get("k1") == pytest.approx(1e-3)
    assert back.get("k2") == {"seconds": 2e-3, "comm_seconds": 1e-4}


def test_latency_parallel_and_train_cached(bp):
    from repro.serving.latency_service import LatencyService
    svc = LatencyService(bp.store, bp.device)
    p1 = svc.latency_parallel("qwen3-mini", 4, 64, tp=2, device="a100_80g")
    p2 = svc.latency_parallel("qwen3-mini", 4, 64, tp=2, device="a100_80g")
    assert not p1.cached and p2.cached
    assert (p2.seconds, p2.compute_seconds, p2.comm_seconds) \
        == (p1.seconds, p1.compute_seconds, p1.comm_seconds)
    # microbatches are part of the key
    p3 = svc.latency_parallel("qwen3-mini", 4, 64, tp=2, pp=2,
                              microbatches=4, device="a100_80g")
    assert not p3.cached
    t1 = svc.latency_train("qwen3-mini", 4, 64, dp=2, bucket_mb=4.0,
                           device="a100_80g")
    t2 = svc.latency_train("qwen3-mini", 4, 64, dp=2, bucket_mb=4.0,
                           device="a100_80g")
    assert not t1.cached and t2.cached and t2.seconds == t1.seconds
    # bucket size is part of the key
    t3 = svc.latency_train("qwen3-mini", 4, 64, dp=2, bucket_mb=8.0,
                           device="a100_80g")
    assert not t3.cached
    assert t1.to_json()["comm_share"] == pytest.approx(t1.comm_share)


def test_malformed_cache_dict_is_a_miss_not_a_crash(bp):
    from repro.core.batch_predict import config_key
    from repro.serving.latency_service import LatencyService
    svc = LatencyService(bp.store, bp.device)
    cfg = cr.get_any("qwen3-mini")
    spec = og.ParallelismSpec(tp=2)
    key = PredictionCache.make_key(config_key(cfg), "a100_80g", None, 4, 64,
                                   spec=spec.tag())
    svc.cache.put(key, {"sec": 1.0})        # foreign/truncated entry
    p = svc.latency_parallel("qwen3-mini", 4, 64, tp=2, device="a100_80g")
    assert not p.cached and p.seconds > 0   # recomputed, entry replaced
    assert svc.latency_parallel("qwen3-mini", 4, 64, tp=2,
                                device="a100_80g").cached


def test_bubble_share_ignores_non_stage_compute(bp):
    """The optimizer's bare 'compute' stream must not count as an extra
    pipeline executor."""
    cfg = cr.reduced("qwen2-0.5b")
    sched = bp.schedule_step(cfg, 8, 32,
                             spec=og.ParallelismSpec(pp=2, microbatches=2))
    busy = sched.busy()
    stage = {s: b for s, b in busy.items() if s.startswith("compute.s")}
    assert "compute" in busy and len(stage) == 2
    want = 1.0 - sum(stage.values()) / (2 * sched.makespan)
    assert sched.bubble_share == pytest.approx(want, rel=1e-12)


def test_bubble_share_schedule_kind_aware(bp):
    """Regression: ``Schedule.bubble_share`` used to hard-code the GPipe
    executor-column rule (idle / (k · makespan)) for EVERY graph.  A
    1F1B-wired schedule must instead report idle over ideal compute —
    the convention whose balanced-pipeline value is ``(pp-1)/mb`` — so
    the same timeline yields two different (documented) shares."""
    rows = [S.PredictionRow(f"stage{i}", "compute", 1.0, "t")
            for i in range(2)]
    streams = ["compute.s0", "compute.s1"]
    st = np.array([0.0, 0.5])
    sched = S.Schedule(rows, streams, st, st + 1.0, makespan=1.5)
    assert sched.kind == "gpipe"
    assert sched.bubble_share == pytest.approx(1.0 / 3.0, rel=1e-12)
    as_1f1b = dataclasses.replace(sched, kind="1f1b")
    assert as_1f1b.bubble_share == pytest.approx(0.5, rel=1e-12)
    # and the builders thread the kind: a 1f1b spec's scalar schedule
    # reports the ideal-relative share, its gpipe twin the makespan one
    cfg = cr.reduced("qwen2-0.5b")
    one = bp.schedule_step(cfg, 8, 32,
                           spec=og.ParallelismSpec(pp=2, microbatches=4,
                                                   schedule="1f1b"))
    gp = bp.schedule_step(cfg, 8, 32,
                          spec=og.ParallelismSpec(pp=2, microbatches=4))
    assert one.kind == "1f1b" and gp.kind == "gpipe"
    busy = one.busy()
    comp = sum(b for s, b in busy.items() if s.startswith("compute.s"))
    assert one.bubble_share == pytest.approx(
        (2 * one.makespan - comp) / comp, rel=1e-9)
    busy_g = gp.busy()
    comp_g = sum(b for s, b in busy_g.items() if s.startswith("compute.s"))
    assert gp.bubble_share == pytest.approx(
        (2 * gp.makespan - comp_g) / (2 * gp.makespan), rel=1e-9)


def test_latency_train_splits_consistent(bp):
    from repro.serving.latency_service import LatencyService
    svc = LatencyService(bp.store, bp.device)
    t = svc.latency_train("qwen3-mini", 4, 64, dp=4, microbatches=2,
                          bucket_mb=1.0, device="a100_80g")
    assert t.bwd_seconds == pytest.approx(2.0 * t.fwd_seconds, rel=1e-9)
    assert t.optimizer_seconds > 0
    assert 0 <= t.exposed_comm_seconds <= t.comm_seconds * (1 + 1e-9)
    assert t.seconds <= (t.fwd_seconds + t.bwd_seconds + t.comm_seconds
                         + t.optimizer_seconds) * (1 + 1e-9)


def test_plan_stages_model_schedule_makespan(bp):
    cfg = cr.reduced("qwen2-0.5b", n_layers=4)
    plans = {}
    for mb in (1, 2, 4):
        plan, _ = plan_stages_model(bp, cfg, 2, 32, 2, device="h100_sxm",
                                    microbatches=mb)
        assert plan.makespan is not None and plan.microbatches == mb
        plans[mb] = plan
    # same boundaries, pipelining shortens the end-to-end makespan
    assert plans[1].boundaries == plans[2].boundaries
    assert plans[1].makespan > plans[2].makespan > plans[4].makespan
    # mb=1 pipeline: sum of pure stages + one hand-off
    from repro.core.partition import activation_comm_cost
    comm = activation_comm_cost(cfg, 2, 32, device_a="h100_sxm",
                                device_b="h100_sxm")
    pure = sum(plans[1].stage_times) - comm  # stage_times charge hand-offs
    assert plans[1].makespan == pytest.approx(pure + comm, rel=1e-9)


# ---------------------------------------------------------------------------
# docs worked example (parallelism.md "Overlap & training step")
# ---------------------------------------------------------------------------

def test_overlap_worked_example_numbers():
    """Pin the exact numbers docs/parallelism.md walks through by hand:
    two 10 ms stages, 1 ms PER-MICROBATCH hand-off."""
    mk = lambda mb: S.pipeline_stage_schedule([10e-3, 10e-3], 1e-3,
                                              microbatches=mb)
    assert mk(1).makespan == pytest.approx(21e-3, rel=1e-12)
    two = mk(2)
    assert two.makespan == pytest.approx(16e-3, rel=1e-12)
    assert two.sequential_seconds == pytest.approx(22e-3, rel=1e-12)
    assert two.bubble_share == pytest.approx(1 - 20e-3 / (2 * 16e-3),
                                             rel=1e-9)
    assert mk(4).makespan == pytest.approx(13.5e-3, rel=1e-12)
    # the hand-off is charged once per microbatch per link: the α latency
    # term never vanishes with deeper microbatching
    assert mk(4).comm_seconds == pytest.approx(4e-3, rel=1e-12)


def test_planner_handoff_keeps_alpha_term(bp):
    """plan_stages_model prices the per-microbatch hand-off at the
    microbatch batch via the α–β model: on a latency-dominated link the
    planner must NOT report latency shrinking to zero with huge mb."""
    from repro.core.partition import _mb_handoff, activation_comm_cost
    cfg = cr.reduced("qwen2-0.5b", n_layers=4)
    full = activation_comm_cost(cfg, 8, 64, device_a="l4", device_b="l4")
    per_mb = _mb_handoff(cfg, 8, 64, 8, derived=True, comm_cost=full,
                         dtype=None, device_a="l4", device_b="l4")
    from repro.core.collectives import interconnect_for
    alpha = interconnect_for("l4").link_latency
    assert per_mb >= alpha and per_mb > full / 8
    # explicit overrides are opaque scalars: split evenly
    assert _mb_handoff(cfg, 8, 64, 8, derived=False, comm_cost=8.0,
                       dtype=None, device_a=None, device_b=None) == 1.0
