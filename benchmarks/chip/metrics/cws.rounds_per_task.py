"""Scheduling rounds per chunk task in the traced window that the
scheduler's own events ask for: the ``cws.round`` spans with ``forced`` 0
that start between the first and the last complete ``task.body`` start,
over the task periods between them (``spans.py``). The driver loop's timed
poll (``forced`` 1, one every ``poll_s``) is left out: its count follows
the task's length, not the scheduler's work."""


def read(rec):
    sp = rec.get("spans")
    if not sp or not sp["periods"]:
        return None
    return sp["asked_rounds"] / sp["periods"]
