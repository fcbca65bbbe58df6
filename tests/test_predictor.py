"""Predictor stack: GBDT learning, metric correctness, the two-phase
Maestro-Pred pipeline + its baselines/ablations. Property-based companions
(isotonic monotonicity) live in test_properties.py, which skips itself when
hypothesis is unavailable."""
import numpy as np
import pytest

from repro.core.predictor import (GBDT, GBDTConfig, IsotonicCalibrator,
                                  LinearBaseline, MaestroPred,
                                  PredictorConfig, classification_metrics,
                                  regression_metrics)
from repro.data.tracegen import generate_trace, stratified_temporal_split

RNG = np.random.default_rng(0)


def test_gbdt_regression_learns():
    X = RNG.normal(size=(3000, 6)).astype(np.float32)
    y = 2 * X[:, 0] - np.abs(X[:, 1]) + 0.05 * RNG.normal(size=3000)
    m = GBDT(GBDTConfig(n_trees=60, max_leaves=15)).fit(
        X[:2400], y[:2400], X[2400:], y[2400:])
    r2 = regression_metrics(y[2400:], m.predict(X[2400:]))["r2"]
    assert r2 > 0.9


def test_gbdt_classifier_calibrated_range():
    X = RNG.normal(size=(2000, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(float)
    m = GBDT(GBDTConfig(n_trees=40, max_leaves=7, objective="logloss")).fit(
        X[:1500], y[:1500], X[1500:], y[1500:])
    p = m.predict(X[1500:])
    assert np.all((p >= 0) & (p <= 1))
    assert classification_metrics(y[1500:], p)["auc"] > 0.95


def test_gbdt_early_stopping():
    X = RNG.normal(size=(800, 3)).astype(np.float32)
    y = RNG.normal(size=800)   # pure noise: must stop early
    m = GBDT(GBDTConfig(n_trees=200, early_stopping=5)).fit(
        X[:600], y[:600], X[600:], y[600:])
    assert len(m.trees) < 200


def test_isotonic_monotone_fixed_grid():
    """Deterministic spot-check of the property in test_properties.py."""
    rng = np.random.default_rng(0)
    scores = rng.uniform(0, 1, 100)
    labels = (scores + rng.normal(0, 0.3, 100) > 0.5).astype(float)
    iso = IsotonicCalibrator().fit(scores, labels)
    out = iso.transform(np.linspace(0, 1, 64))
    assert np.all(np.diff(out) >= -1e-9)
    assert np.all((out >= 0) & (out <= 1))


def test_classification_metrics_perfect_and_random():
    y = np.array([0, 0, 1, 1, 1, 0, 1, 0], float)
    perfect = classification_metrics(y, y * 0.98 + 0.01)
    assert perfect["auc"] == pytest.approx(1.0)
    assert perfect["acc"] == 1.0
    rnd = classification_metrics(y, np.full(8, 0.5))
    assert 0.4 <= rnd["auc"] <= 0.6


@pytest.fixture(scope="module")
def small_trace():
    jobs = generate_trace(250, rate=1.0, seed=3)
    return stratified_temporal_split(jobs)


def _fit_kwargs(train):
    return dict(
        observations=[s.obs for s in train],
        lengths=np.array([s.true_len for s in train], float),
        tool_labels=np.array([float(s.tool_call) for s in train]))


FAST = PredictorConfig(
    cls=GBDTConfig(objective="logloss", n_trees=30, max_leaves=7),
    reg=GBDTConfig(n_trees=40, max_leaves=15))


def test_maestro_pred_end_to_end(small_trace):
    train, test = small_trace
    mp = MaestroPred(FAST).fit(**_fit_kwargs(train))
    out = mp.predict([s.obs for s in test])
    assert np.all(out["length"] >= 1)
    assert np.all((out["p_tool"] >= 0) & (out["p_tool"] <= 1))
    m = regression_metrics([s.true_len for s in test], out["length"])
    lin = LinearBaseline().fit(**_fit_kwargs(train))
    ml = regression_metrics([s.true_len for s in test],
                            lin.predict([s.obs for s in test])["length"])
    assert m["mae"] < ml["mae"]          # beats prompt-length-only OLS

    # p_tool gates: stages with no tools available get exactly 0
    no_tools = [s.obs for s in test if s.obs.tools_available == 0]
    if no_tools:
        assert np.all(mp.predict(no_tools)["p_tool"] == 0.0)


def test_ablation_direction(small_trace):
    """w/o semantic features must not beat the full model (Table VII)."""
    train, test = small_trace
    full = MaestroPred(FAST).fit(**_fit_kwargs(train))
    import dataclasses
    no_sem = MaestroPred(dataclasses.replace(FAST, use_semantic=False)).fit(
        **_fit_kwargs(train))
    y = [s.true_len for s in test]
    mae_full = regression_metrics(
        y, full.predict([s.obs for s in test])["length"])["mae"]
    mae_nosem = regression_metrics(
        y, no_sem.predict([s.obs for s in test])["length"])["mae"]
    assert mae_full <= mae_nosem * 1.05


def test_hardware_spec_is_keyed_by_device_kind():
    """Peaks come from the device's kind; the CPU keeps the default spec
    (virtual-clock baselines do not move); an unknown TPU kind raises."""
    import types

    import jax

    from repro.core.predictor.cost_model import (DEVICE_SPECS, HardwareSpec,
                                                 UnknownDeviceError,
                                                 hardware_spec)
    assert hardware_spec(jax.devices("cpu")[0]) == HardwareSpec()
    v5e = hardware_spec(types.SimpleNamespace(platform="tpu",
                                              device_kind="TPU v5 lite"))
    assert v5e is DEVICE_SPECS["TPU v5 lite"]
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(UnknownDeviceError, match="TPU v99"):
        hardware_spec(types.SimpleNamespace(platform="tpu",
                                            device_kind="TPU v99"))
