"""Device: 1 - (union of the device-op intervals / traced sub-window), from
the profiler trace (``trace_reduce.py``)."""


def read(facts: dict):
    trace = facts["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
