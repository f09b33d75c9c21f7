"""Median, over consecutive chunk tasks in the traced window, of the time
from the end of one task's ``task.body`` span to the start of the next
one's: ``TaskFinished``, the round, ``launch``, the thread hop,
``TaskStarted`` and every engine-lock wait between them (``spans.py``)."""
import statistics


def read(rec):
    sp = rec.get("spans")
    if not sp or not sp["handoff_s"]:
        return None
    return statistics.median(sp["handoff_s"]) * 1e3
