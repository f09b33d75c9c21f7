"""Device self time, a step, of the step program's operations whose HLO
``op_name`` holds the ``mlp`` named scope (forward, recompute and
backward), median over the traced executions (``spans.py``)."""


def read(rec):
    sp = rec.get("spans")
    scope = (sp or {}).get("scope_s") or {}
    if "mlp" not in scope:
        return None
    return scope["mlp"] * 1e3
