"""The split of a trace by op family (``op_kinds.py``): the rule on HLO text
by hand, the query phase's spans on a trace recorded here, and a small
training trace recorded on a TPU v5e beside its compiled HLO text
(``record_kinds.py``)."""
import gzip
import os

import jax
import pytest

import rehearse  # noqa: F401 - puts bench/ on the path
import op_kinds as ok

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "v5e_kinds.xplane.pb")
RECORDED_HLO = os.path.join(DATA, "v5e_kinds.hlo.gz")

HLO = """HloModule jit_step, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%fused_inner (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  ROOT %dot.1 = f32[8,8]{1,0} dot(%p, %p), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%fused_outer (q: f32[8,8]) -> f32[8,8] {
  %q = f32[8,8]{1,0} parameter(0)
  ROOT %fusion.9 = f32[8,8]{1,0} fusion(%q), kind=kOutput, calls=%fused_inner
}

%fused_add (r: f32[8,8]) -> f32[8,8] {
  %r = f32[8,8]{1,0} parameter(0)
  %c = f32[] constant(1), metadata={op_name="jit(step)/attention/add"}
  %b = f32[8,8]{1,0} broadcast(%c), dimensions={}
  ROOT %add.2 = f32[8,8]{1,0} add(%r, %b)
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %fusion.1 = f32[8,8]{1,0} fusion(%x), kind=kOutput, calls=%fused_outer, metadata={op_name="jit(step)/while/body/dot_general"}
  %fusion.2 = f32[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_add, metadata={op_name="jit(step)/convert_element_type"}
  %fusion.3 = f32[8,8]{1,0} fusion(%fusion.2), kind=kOutput, calls=%fused_outer, metadata={op_name="transpose(jvp())/checkpoint/attention/attention/bqhgd,bkhd->bqhgk/dot_general"}
  %copy-start = (f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]) copy-start(%fusion.3)
  %copy-done = f32[8,8]{1,0} copy-done(%copy-start)
  %exp.4 = f32[8,8]{1,0} exponential(%copy-done), metadata={op_name="jit(step)/rematted_computation/attention/exp"}
  ROOT %dot.5 = f32[8,8]{1,0} dot(%exp.4, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/attentions/dot_general"}
}
"""


def test_kind_rule_on_hlo_text():
    module, kinds = ok.instruction_kinds(HLO)
    assert module == "jit_step"
    assert kinds == {
        "x": "memory",
        "fusion.1": "matmul",      # a dot two fusions down
        "fusion.2": "memory",      # scope on a body constant only: no
        "fusion.3": "attention",   # the backward's name stack
        "copy-start": "memory", "copy-done": "memory",
        "exp.4": "attention",      # the recomputation's
        "dot.5": "matmul",         # "attentions" is another name
    }


def test_instruction_of_event_name():
    assert ok.instruction_of(
        "%fusion.3 = (f32[8]{0}, bf16[2,4]{1,0}) fusion(%a), calls=%f"
    ) == "fusion.3"
    assert ok.instruction_of("%copy-start.7 = f32[2]{0} copy-start(%x)") \
        == "copy-start.7"


def _compile():
    with jax.profiler.TraceAnnotation(ok.COMPILE_SPAN):
        jax.jit(lambda x: x + 1).lower(1.0).compile()


def _spans(tmp_path):
    """A query phase as the service marks it: two answers, the first with
    two compiles, the second with none; one more compile in the phase but
    outside an answer, and one before the phase."""
    with jax.profiler.trace(str(tmp_path)):
        _compile()
        with jax.profiler.TraceAnnotation("bench.queries"):
            with jax.profiler.TraceAnnotation("latency.latency_train",
                                              query_id=1):
                _compile()
                _compile()
            _compile()
            with jax.profiler.TraceAnnotation("latency.latency_train",
                                              query_id=2):
                pass
    return ok.query_spans(str(tmp_path), "bench.queries")


def test_query_spans_and_metrics(tmp_path):
    spans = _spans(tmp_path)
    assert spans["answers"] == 2 and spans["compiles"] == 3
    assert 0 < spans["compile_s"] < spans["answer_s"]
    kinds = {"kind_s": {"matmul": 2.0, "attention": 1.0, "memory": 0.5}}
    m = ok.metrics(kinds, 10, {"matmul": 0.5, "attention": 0.05,
                               "memory": 0.05}, spans, 2)
    assert m["matmul_err_ms"] == pytest.approx(300.0)
    assert m["attention_err_ms"] == pytest.approx(50.0)
    assert m["memory_err_ms"] == pytest.approx(0.0, abs=1e-9)
    assert m["query_compiles_mean"] == 1.5
    assert m["query_compile_pct"] == pytest.approx(
        100 * spans["compile_s"] / spans["answer_s"])


def test_own_times_partition_nested_events():
    events = [(0, 100, "loop"), (10, 30, "a"), (30, 60, "b"), (40, 50, "c"),
              (120, 130, "d")]
    own = {name: ns for _, _, name, ns in ok.own_times(events, 0, 1000)}
    assert own == {"loop": 50, "a": 20, "b": 20, "c": 10, "d": 10}
    clipped = {name: ns for _, _, name, ns in ok.own_times(events, 45, 125)}
    assert clipped == {"loop": 40, "b": 10, "c": 5, "d": 5}
    assert sum(clipped.values()) == 125 - 45 - (120 - 100)


def test_recorded_chip_trace_partitions_by_kind():
    with gzip.open(RECORDED_HLO, "rt") as f:
        hlo = f.read()
    r = ok.kind_times(RECORDED, "bench.steps", hlo)
    kind_s = r["kind_s"]
    assert all(kind_s[k] > 0 for k in ok.KINDS)
    # no window op lacks an instruction of the window program
    assert r["unmapped_s"] == 0 and r["unmapped_ops"] == []
    # the families partition the busy time
    assert sum(kind_s.values()) == pytest.approx(r["busy_s"], rel=1e-6)
