"""Scheduling rounds per chunk task in the traced window: the ``cws.round``
spans that start between the first and the last complete ``task.body``
start, over the task periods between them (``spans.py``). The executor's
forced poll and the round each finish asks for both count."""


def read(rec):
    sp = rec.get("spans")
    if not sp or not sp["periods"]:
        return None
    return sp["rounds"] / sp["periods"]
