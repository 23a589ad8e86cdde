"""Each cell of BENCHMARK.json rehearsed off the chip: its kind's set-up,
window and check at the program's CPU-sized widths, through ``run.run``."""
import math

import pytest

import rehearse  # noqa: I001 - puts bench/ on the path first
import common

CELLS = rehearse.names()


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name, monkeypatch):
    result = rehearse.rehearse(name, monkeypatch=monkeypatch)
    assert result["correct"] is True, result["compared"]
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "compared"
    assert result["failed"] == 0
    m = result["metrics"]
    assert set(m) == {e["name"] for e in common.benchmark()["end_to_end"]}
    assert all(math.isfinite(v["value"]) and v["value"] > 0 for v in m.values())
    for name_, c in result["compared"].items():
        assert math.isfinite(c["value"]), name_
