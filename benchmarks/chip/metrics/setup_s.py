"""Seconds from the process's start to the window's start: imports, the
weights made on the device, compilation (from the cache after a cell's first
run), and the chunks that carry the checked first steps."""


def read(rec):
    return rec["setup_s"]
