"""The control of each cell, at a size a test run can hold (the rehearsal's
sizes and limits, ``rehearse.SMALL``): the plain reference computed in
float8 in the program's place has to come out as not correct, where the
program itself comes out correct, by the rule a benchmark run uses."""
import pytest

import rehearse
import common
import control
import run as bench_run

CELLS = rehearse.names()


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    _, conf, mix = rehearse.small_cell(name)
    cfg = common.model_config(conf)
    reference = bench_run.load("reference", conf["reference"])
    kind = bench_run.load("kinds", mix["kind"])
    got = control.readings(kind.Cell, cfg, conf, mix, 2147483647, reference,
                           steps=3, control=True)
    assert common.judge(got["numbers"], mix["limits"])[1] is True, got
    assert common.judge(got["control"], mix["limits"])[1] is False, got
