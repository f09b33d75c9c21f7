"""90th percentile, over the window's chunk tasks, of the wait from the
predecessor task's end to the task's start (the CWS's own records): how long
a ready task waits for the scheduler and the executor to hand it the chip.
Needs at least ten samples above the percentile."""
import statistics


def read(rec):
    gaps = rec["gaps_s"]
    if len(gaps) < 100:
        return None
    return statistics.quantiles(gaps, n=10)[8] * 1e3
