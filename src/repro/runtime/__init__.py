# Distributed runtime: sharding rules, the train step factory, continuous
# batching, the CWS-driven orchestrator, and fault handling.
from .sharding import (  # noqa: F401
    base_rules,
    input_axes,
    shardings_for_tree,
    spec_for,
    train_rules,
)
