"""PM2Lat predictor: kernel-differentiated throughput interpolation for
compute ops + linear proxy-metric regression for memory-bound ops, aggregated
sequentially over the op graph (paper §III-C).

Kernel selection — which profiled table answers for an op — lives in
``core/oracle.py`` (``KernelOracle``), shared with the vectorized
``BatchPredictor`` so the two paths can never disagree on which kernel the
library would run.  ``PredictionRow.kernel`` reports the kernel id the
oracle actually selected (e.g. ``xla_default@1024x1024``), not the family
default.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from repro.configs import base as C
from repro.core import opgraph as og
from repro.core.memory_model import MemoryModel
from repro.core.oracle import KernelOracle
from repro.core.table import TableStore, ThroughputTable


@dataclasses.dataclass
class PredictionRow:
    name: str
    kind: str
    seconds: float
    kernel: str


def seconds_by_kind(rows) -> dict:
    """Predicted seconds per op family, each summed in row order:
    ``matmul`` (matmul and bmm rows: the throughput tables), ``attention``
    (the attention tables; decode attention the memory model), ``memory``
    (the memory model) and ``collective`` (the interconnect model), the
    last only when there are collectives."""
    out = {"matmul": 0.0, "attention": 0.0, "memory": 0.0}
    for r in rows:
        kind = "matmul" if r.kind == "bmm" else r.kind
        out[kind] = out.get(kind, 0.0) + r.seconds
    return out


class PM2Lat:
    def __init__(self, store: TableStore, device: str):
        self.store = store
        self.device = device
        self.oracle = KernelOracle(store, device)
        mm = store.memory_model
        self.memory_model = MemoryModel.from_json(mm) if isinstance(mm, dict) else mm
        # Measured L2 correction (comm_calibrate artifact): scales the
        # memory model's bytes term.  None without a calibration artifact —
        # the bit-identical datasheet path.
        if self.memory_model is not None and self.memory_model.cache is None:
            from repro.core.comm_calibrate import cache_correction_for
            cc = cache_correction_for(device)
            if cc is not None:
                self.memory_model = dataclasses.replace(self.memory_model,
                                                        cache=cc)

    @property
    def interconnect(self):
        """This device's α–β interconnect spec (collective-op prediction):
        the measured fit when a comm-calibration artifact carries one
        (``core/comm_calibrate.py``), else the registered datasheet profile,
        else ``collectives.DEFAULT_INTERCONNECT``."""
        from repro.core.comm_calibrate import calibrated_interconnect
        return calibrated_interconnect(self.device)

    # ----- per-op -----
    def _matmul_table(self, op: og.MatmulOp,
                      kernel: Optional[str]) -> ThroughputTable:
        if kernel is not None:
            return self.oracle.lookup(op.kind, kernel, op.dtype)
        return self.oracle.select_matmul(op.kind, op.dtype, op.m, op.n,
                                         batch=op.batch)

    def _attention_table(self, op: og.AttentionOp,
                         kernel: Optional[str]) -> ThroughputTable:
        if kernel is not None:
            return self.oracle.lookup("attention", kernel, op.dtype)
        return self.oracle.select_attention(op.dtype, op.skv,
                                            head_dim=op.hd)

    def predict_matmul(self, op: og.MatmulOp, kernel: str = None) -> float:
        t = self._matmul_table(op, kernel)
        return t.predict(op.m, op.n, op.k, batch=op.batch) * op.count

    def predict_attention(self, op: og.AttentionOp,
                          kernel: Optional[str] = None) -> float:
        if op.phase == og.DECODE:
            return self.predict_decode_attention(op)
        t = self._attention_table(op, kernel)
        thr = t.interpolate_throughput(op.skv)
        return op.flops / thr

    def predict_decode_attention(self, op: og.AttentionOp) -> float:
        """Decode-phase attention (sq=1): the kernel streams the KV cache, so
        the op is memory-bound and flops-based table pricing collapses — price
        it with the memory model over the analytic KV-read traffic instead
        (class ``softmax``: same reduce-then-scale access pattern)."""
        return self.memory_model.predict(og.decode_attention_features(op),
                                         "softmax")

    def predict_memory(self, op: og.MemoryOp) -> float:
        from repro.core.memory_model import class_of
        return self.memory_model.predict(op.features(),
                                         class_of(op.snippet)) * op.count

    def predict_collective(self, op) -> Tuple[float, str]:
        """Seconds (incl. count) + selected ring/tree algorithm for one
        ``CollectiveOp`` under this device's interconnect."""
        from repro.core.collectives import predict_collective
        return predict_collective(op, self.interconnect)

    def predict_op(self, op) -> PredictionRow:
        if op.kind in ("matmul", "bmm"):
            t = self._matmul_table(op, None)
            sec = t.predict(op.m, op.n, op.k, batch=op.batch) * op.count
            return PredictionRow(op.name, op.kind, sec, t.key.kernel)
        if op.kind == "attention":
            if op.phase == og.DECODE:
                sec = self.predict_decode_attention(op)
                gqa = max(1, op.heads // max(1, op.kv_heads))
                return PredictionRow(op.name, "attention", sec,
                                     f"kv_read@gqa{gqa}")
            t = self._attention_table(op, None)
            sec = op.flops / t.interpolate_throughput(op.skv)
            return PredictionRow(op.name, "attention", sec, t.key.kernel)
        if op.kind == "collective":
            sec, algo = self.predict_collective(op)
            return PredictionRow(op.name, "collective", sec, algo)
        return PredictionRow(op.name, "memory", self.predict_memory(op), "linreg")

    # ----- model level -----
    def predict_ops(self, ops: List) -> Tuple[float, List[PredictionRow]]:
        rows = [self.predict_op(op) for op in ops]
        return sum(r.seconds for r in rows), rows

    def predict_model(self, cfg: C.ModelConfig, batch: int, seq: int,
                      dtype: Optional[str] = None):
        ops = og.enumerate_ops(cfg, batch, seq, dtype=dtype)
        return self.predict_ops(ops)

    def predict_parallel(self, cfg: C.ModelConfig, batch: int, seq: int,
                         spec: "og.ParallelismSpec",
                         dtype: Optional[str] = None):
        """Schedule-aware end-to-end prediction under a ``ParallelismSpec``:
        the makespan of the two-stream list schedule (``core/schedule.py``)
        over the sharded compute ops + induced collectives.  With
        ``microbatches == 1`` the schedule is a serialized chain, so the
        answer is bit-identical to the historical sequential sum (and a
        trivial spec is the plain ``predict_model`` path, op for op)."""
        sched = self.schedule_parallel(cfg, batch, seq, spec, dtype=dtype)
        return sched.makespan, sched.rows

    def schedule_parallel(self, cfg: C.ModelConfig, batch: int, seq: int,
                          spec: "og.ParallelismSpec",
                          dtype: Optional[str] = None):
        """The full ``Schedule`` (timeline + busy/exposed splits) behind
        ``predict_parallel``."""
        from repro.core import schedule as S
        return S.schedule_parallel(self, cfg, batch, seq, spec, dtype=dtype)

    def predict_step(self, cfg: C.ModelConfig, batch: int, seq: int,
                     spec: "og.ParallelismSpec" = None, train=None,
                     dtype: Optional[str] = None):
        """One TRAINING step (fwd + bwd + gradient comm + optimizer update)
        under a ``ParallelismSpec`` + ``schedule.TrainingStepSpec``, priced
        as the schedule makespan."""
        sched = self.schedule_step(cfg, batch, seq, spec=spec, train=train,
                                   dtype=dtype)
        return sched.makespan, sched.rows

    def schedule_step(self, cfg: C.ModelConfig, batch: int, seq: int,
                      spec: "og.ParallelismSpec" = None, train=None,
                      dtype: Optional[str] = None):
        """The full training-step ``Schedule`` behind ``predict_step``."""
        from repro.core import schedule as S
        return S.schedule_step(self, cfg, batch, seq, spec=spec, train=train,
                               dtype=dtype)

    def predict_blocks(self, cfg: C.ModelConfig, batch: int, seq: int,
                       dtype: Optional[str] = None) -> List[float]:
        """Per-transformer-block latency (for the partition planner)."""
        per_layer = []
        for li, kind in enumerate(cfg.layer_kinds):
            one = dataclasses.replace(cfg, n_layers=len(cfg.block_pattern),
                                      block_pattern=(kind,))
            ops = og.enumerate_ops(
                dataclasses.replace(one, n_layers=1), batch, seq, dtype=dtype)
            # strip embed/unembed/final-norm (not per-block)
            ops = [o for o in ops
                   if o.name not in ("embed", "unembed", "final_norm")]
            total, _ = self.predict_ops(ops)
            per_layer.append(total)
        return per_layer

# The former VectorizedMatmulPredictor (numpy Eq(1)/(2) over one anchor
# table) grew into the all-op-family engine in core/batch_predict.py —
# use BatchPredictor.predict_matmul_batch, which adds the vectorized
# kernel-selection oracle and matches this module's scalar path exactly.
