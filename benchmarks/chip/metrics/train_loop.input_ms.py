"""Median, over the traced steps, of the host time that makes a step's
batch and puts it on the device: the program's ``train.batch`` +
``train.put`` spans (``spans.py``)."""
import statistics


def read(rec):
    sp = rec.get("spans")
    if not sp or not sp["input_s"]:
        return None
    return statistics.median(sp["input_s"]) * 1e3
