"""Prediction plugins (paper §5): Lotaru-style runtime prediction and
feedback memory prediction."""
import math

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property-based suite needs hypothesis (pip install -r requirements-dev.txt)",
)
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.predict import (
    BayesianLinReg,
    FeedbackMemoryPredictor,
    LotaruPredictor,
    NodeProfile,
)
from repro.core.provenance import ProvenanceStore, TaskTrace

GiB = 1 << 30
RNG = np.random.default_rng(7)


def test_lotaru_learns_linear_runtime():
    """runtime = 20 + 15·GB with 5% noise → <20% relative error after
    a handful of observations (the cold-start regime Lotaru targets)."""
    pred = LotaruPredictor()
    for _ in range(12):
        gb = float(RNG.uniform(0.5, 16))
        rt = (20 + 15 * gb) * float(RNG.lognormal(0, 0.05))
        pred.observe("align", int(gb * GiB), rt)
    errs = []
    for gb in (1.0, 4.0, 12.0):
        mu, _ = pred.predict("align", int(gb * GiB))
        truth = 20 + 15 * gb
        errs.append(abs(mu - truth) / truth)
    assert np.median(errs) < 0.2, errs


def test_lotaru_node_speed_normalisation():
    """Observations from a slow node must transfer to a fast node."""
    pred = LotaruPredictor()
    pred.register_node_bench(NodeProfile("slow", 0.5))
    pred.register_node_bench(NodeProfile("fast", 2.0))
    # ground truth on the reference node: 100 s → 200 s on `slow`
    for _ in range(8):
        pred.observe("task", GiB, 200.0 * float(RNG.lognormal(0, 0.03)),
                     node="slow")
    mu_fast, _ = pred.predict("task", GiB, node="fast")
    assert 35 < mu_fast < 70, mu_fast          # ≈ 100/2


def test_lotaru_from_provenance_store():
    store = ProvenanceStore()
    for i in range(10):
        gb = float(RNG.uniform(1, 8))
        store.record_task(TaskTrace(
            workflow_id="w", task_id=f"t{i}", name="sort", attempt=0,
            node=None, start_time=0.0, end_time=10 + 5 * gb,
            state="SUCCEEDED", input_size=int(gb * GiB)))
    pred = LotaruPredictor()
    assert pred.train_from_provenance(store) == 10
    mu, _ = pred.predict("sort", 4 * GiB)
    assert abs(mu - 30) / 30 < 0.3


def test_memory_predictor_reduces_wastage_without_failures():
    """Compared to a fixed 16 GiB request, the learned allocation must cut
    wastage while (almost) never under-provisioning."""
    pred = FeedbackMemoryPredictor(sigma_margin=2.0)
    truth = lambda gb: (1.0 + 0.5 * gb) * GiB  # noqa: E731
    for _ in range(30):
        gb = float(RNG.uniform(0.5, 10))
        pred.observe("assemble", int(gb * GiB),
                     int(truth(gb) * RNG.lognormal(0, 0.05)))
    fixed = learned = fails = 0
    for _ in range(50):
        gb = float(RNG.uniform(0.5, 10))
        need = truth(gb) * RNG.lognormal(0, 0.05)
        alloc = pred.allocate("assemble", int(gb * GiB), 16 * GiB, attempt=0)
        if alloc < need:
            fails += 1
        fixed += 16 * GiB - need
        learned += max(alloc - need, 0)
    assert fails <= 5
    assert learned < 0.5 * fixed


def test_memory_predictor_retry_doubles():
    pred = FeedbackMemoryPredictor()
    a0 = pred.allocate("x", GiB, 2 * GiB, attempt=0)
    a1 = pred.allocate("x", GiB, 2 * GiB, attempt=1)
    a2 = pred.allocate("x", GiB, 2 * GiB, attempt=2)
    assert a1 == 2 * a0 and a2 == 4 * a0


@settings(max_examples=20, deadline=None)
@given(w0=st.floats(1.0, 50.0), w1=st.floats(0.1, 30.0),
       seed=st.integers(0, 1000))
def test_bayes_linreg_recovers_weights(w0, w1, seed):
    rng = np.random.default_rng(seed)
    m = BayesianLinReg()
    for _ in range(40):
        x = float(rng.uniform(0.0, 8.0))
        m.update(np.array([1.0, x]), w0 + w1 * x + rng.normal(0, 0.1))
    mu, std = m.predict(np.array([1.0, 4.0]))
    assert abs(mu - (w0 + 4 * w1)) < 1.0 + 0.1 * (w0 + 4 * w1)
