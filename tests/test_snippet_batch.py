"""A query's missing memory-snippet features compile side by side
(``BatchPredictor._feature_rows``): the answers, the feature rows and the
compile count are the sequential loop's, each distinct key compiles once,
``stats["snippet_compile_batches"]`` counts the batches, and a worker's
exception reaches the caller."""
import dataclasses
import sys
import threading
from collections import Counter

import numpy as np
import pytest

from repro.configs import registry as cr
from repro.core import opgraph as og
from repro.core.batch_predict import BatchPredictor
from repro.core.memory_model import feature_vector
from repro.serving.latency_service import LatencyService


def _qwen2():
    return dataclasses.replace(cr.reduced("qwen2-0.5b"),
                               compute_dtype="bfloat16")


def _sequential_rows(self, keys):
    """The reference: one compile after another, in order."""
    for key in keys:
        if key not in self._feat_cache:
            self._feat_cache[key] = feature_vector(og._snippet_features(*key))


def _answers(calibration_store):
    """Every answer a fresh service gives from cold caches, its feature rows
    and the compiles it took."""
    og._snippet_features.cache_clear()
    svc = LatencyService(calibration_store, "cpu_host")
    cfg = _qwen2()
    before = svc.stats["snippet_compiles"]
    query = svc.latency_query(cfg, 3, 160, dtype="bfloat16")
    train = svc.latency_train(cfg, 4, 96, dp=2, dtype="bfloat16")
    grid = svc.latency_grid(cfg, [1, 2], [224, 288], dtype="bfloat16")
    return {"query": query.to_json(), "train": train.to_json(),
            "grid": grid.tolist(),
            "rows": {k: v.tolist()
                     for k, v in svc.predictor._feat_cache.items()},
            "compiles": svc.stats["snippet_compiles"] - before,
            "batches": svc.predictor._feat_batches}


def test_concurrent_rows_match_the_sequential_loop(calibration_store,
                                                   monkeypatch):
    concurrent = _answers(calibration_store)
    with monkeypatch.context() as m:
        m.setattr(BatchPredictor, "_feature_rows", _sequential_rows)
        sequential = _answers(calibration_store)
    assert max(concurrent.pop("batches")) > 1     # threads did the work
    assert not sequential.pop("batches")
    assert concurrent["query"]["kind_seconds"] is not None
    assert concurrent["train"]["kind_seconds"] is not None
    assert concurrent == sequential               # bit for bit
    assert concurrent["compiles"] == len(concurrent["rows"])


def test_a_repeated_key_compiles_once(calibration_store):
    og._snippet_features.cache_clear()
    bp = BatchPredictor(calibration_store, "cpu_host")
    ops = [og.MemoryOp(f"res{i}", "add", (40, 24), count=i + 1)
           for i in range(3)]
    secs = bp.predict_memory_batch(ops)
    assert og._snippet_features.cache_info().misses == 1
    assert bp._feat_batches == Counter({1: 1})
    assert np.array_equal(secs, secs[0] * np.array([1.0, 2.0, 3.0]))
    ops += [og.MemoryOp("ln", "rmsnorm", (40, 24)),
            og.MemoryOp("ln2", "rmsnorm", (40, 24))]
    bp.predict_memory_batch(ops)
    assert og._snippet_features.cache_info().misses == 2
    assert bp._feat_batches == Counter({1: 2})


def test_one_batch_per_query_and_none_on_repeat(calibration_store):
    og._snippet_features.cache_clear()
    svc = LatencyService(calibration_store, "cpu_host")
    cfg = _qwen2()
    svc.latency_query(cfg, 5, 352, dtype="bfloat16")
    k = svc.stats["snippet_compiles"]
    assert k > 1
    assert svc.stats["snippet_compile_batches"] == 1
    assert svc.predictor._feat_batches == Counter({k: 1})
    # answered from the prediction cache, then priced again from the rows
    assert svc.latency_query(cfg, 5, 352, dtype="bfloat16").cached
    svc.predictor.predict_model(cfg, 5, 352, dtype="bfloat16")
    assert svc.stats["snippet_compiles"] == k
    assert svc.stats["snippet_compile_batches"] == 1


def test_a_workers_exception_reaches_the_caller(calibration_store,
                                                monkeypatch):
    error = RuntimeError("compiler refused the snippet")
    raised_in = []
    real = og._snippet_features

    def features(snippet, shape, dtype):
        if snippet == "silu_mul":
            raised_in.append(threading.current_thread())
            raise error
        return real(snippet, shape, dtype)

    monkeypatch.setattr(og, "_snippet_features", features)
    bp = BatchPredictor(calibration_store, "cpu_host")
    ops = [og.MemoryOp("ln", "rmsnorm", (48, 24)),
           og.MemoryOp("act", "silu_mul", (48, 48)),
           og.MemoryOp("res", "add", (48, 24))]
    with pytest.raises(RuntimeError) as got:
        bp.predict_memory_batch(ops)
    assert got.value is error
    assert raised_in and raised_in[0] is not threading.main_thread()


def test_many_keys_on_few_cores_compile_each_once(calibration_store):
    """Sixteen keys, each twice, on threads that trade the interpreter
    every microsecond: each key compiles once and lands in its own row."""
    og._snippet_features.cache_clear()
    bp = BatchPredictor(calibration_store, "cpu_host")
    keys = [("add", (8 * (i + 1), 16), "float32") for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        bp._feature_rows(keys + keys[::-1])
    finally:
        sys.setswitchinterval(interval)
    assert og._snippet_features.cache_info().misses == len(keys)
    assert bp._feat_batches == Counter({len(keys): 1})
    for key in keys:
        want = feature_vector(og._snippet_features(*key))
        assert np.array_equal(bp._feat_cache[key], want)
