"""Device self time, a step, of the step program's operations whose HLO
``op_name`` holds the ``optimizer`` named scope (forward, recompute and
backward), median over the traced executions (``spans.py``)."""


def read(rec):
    sp = rec.get("spans")
    scope = (sp or {}).get("scope_s") or {}
    if "optimizer" not in scope:
        return None
    return scope["optimizer"] * 1e3
