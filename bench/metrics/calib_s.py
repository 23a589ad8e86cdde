"""Seconds the predictor's calibration took in set-up: the host's clock
around ``core/calibrate.calibrate_host``."""


def read(rec: dict):
    return rec.get("calib_s")
