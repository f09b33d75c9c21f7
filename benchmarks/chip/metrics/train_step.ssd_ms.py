"""Device self time, a step, of the step program's operations whose HLO
``op_name`` holds the ``ssd`` named scope (the chunked SSD scan: forward,
recompute and backward), median over the traced executions
(``spans.py``)."""


def read(rec):
    sp = rec.get("spans")
    scope = (sp or {}).get("scope_s") or {}
    if "ssd" not in scope:
        return None
    return scope["ssd"] * 1e3
