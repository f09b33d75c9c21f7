"""Host time per step inside the chunk tasks: each task's duration less the
host-clock time of its steps (``run_training``'s ``step_seconds``, each
ending in ``block_until_ready``), over the steps. Batch building, the
batch's transfer and the per-chunk host reads are what is left."""


def read(rec):
    if not rec["chunk_steps"]:
        return None
    host = sum(rec["chunk_s"]) - sum(rec["chunk_step_s"])
    return host / rec["chunk_steps"] * 1e3
