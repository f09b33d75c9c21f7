"""Share of the traced window in which no operation ran on the device:
one less the union of the operations' intervals over the window."""


def read(rec):
    tr = rec["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
