"""Median device time of one execution of the step program, from the
trace's ``XLA Modules`` line."""
import statistics


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["step_device_s"]:
        return None
    return statistics.median(tr["step_device_s"]) * 1e3
