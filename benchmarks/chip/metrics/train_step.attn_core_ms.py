"""Device self time, a step, of the step program's operations whose HLO
``op_name`` holds the ``attn_core`` named scope (forward, recompute and
backward), median over the traced executions (``spans.py``)."""


def read(rec):
    sp = rec.get("spans")
    scope = (sp or {}).get("scope_s") or {}
    if "attn_core" not in scope:
        return None
    return scope["attn_core"] * 1e3
