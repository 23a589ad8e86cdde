"""Share of the traced step phase in which no operation ran on the chip:
1 - (union of the device's op intervals) / window, from the profiler's
trace."""


def read(rec: dict):
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
