"""Median, over the window's chunk tasks, of the wait from the predecessor
task's end to the task's start: the CWS round and the executor's hand-off."""
import statistics


def read(rec):
    gaps = rec["gaps_s"]
    return statistics.median(gaps) * 1e3 if gaps else None
