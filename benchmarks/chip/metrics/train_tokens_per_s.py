"""Tokens of every step in the window over the window's seconds. The window
runs from the first timed chunk task's start to the last one's end, as the
CWS recorded them, so host work, syncs and hand-offs between steps count."""


def read(rec):
    if rec["window_s"] <= 0:
        return None
    return rec["window_steps"] * rec["tokens_per_step"] / rec["window_s"]
