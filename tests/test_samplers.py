"""Sampler distribution laws (paper §2.5).

The closed-form inverse CDFs must reproduce their target categorical
distributions exactly (up to Monte-Carlo noise), and the weight-based
samplers must match softmax/linear weights over real timestamps.
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import SamplerConfig
from repro.core.samplers import (
    BIAS_EXPONENTIAL,
    index_exponential,
    index_linear,
    index_pick_lanes,
    index_uniform,
    pick_start_edges,
    weighted_pick_exp,
    weighted_pick_linear,
)

NDRAWS = 200_000

# upper α=1e-3 standard-normal quantile: the false-positive rate per MC
# test. Seeds are pinned (PRNGKey constants below), so in practice each
# gate is deterministic — α bounds how unlucky a pinned seed can be.
_Z_ALPHA = 3.0902
CHI2_ALPHA = 1e-3


def chi2_crit(dof: int, z: float = _Z_ALPHA) -> float:
    """Upper critical value of χ²(dof) via the Wilson–Hilferty cube
    approximation (accurate to ~1% for dof >= 3; conservative below)."""
    dof = max(dof, 1)
    h = 2.0 / (9.0 * dof)
    return dof * (1.0 - h + z * np.sqrt(h)) ** 3


def _hist(picks, n):
    return np.bincount(np.asarray(picks), minlength=n)[:n] / len(picks)


def _chi2_ok(observed, expected, ndraws):
    """Pearson χ² goodness-of-fit at fixed (dof, α): buckets with an
    expected count <= 5 are pooled out (the standard validity rule),
    dof = kept buckets − 1, gate = Wilson–Hilferty critical value."""
    exp_counts = expected * ndraws
    mask = exp_counts > 5
    chi2 = np.sum((observed[mask] * ndraws - exp_counts[mask]) ** 2
                  / exp_counts[mask])
    dof = int(mask.sum())
    return chi2 < chi2_crit(max(dof - 1, 1))


@pytest.mark.parametrize("n", [1, 2, 7, 64])
@pytest.mark.statistical
def test_index_uniform_law(n):
    u = jax.random.uniform(jax.random.PRNGKey(0), (NDRAWS,))
    picks = index_uniform(u, jnp.full((NDRAWS,), n, jnp.int32))
    h = _hist(picks, n)
    assert _chi2_ok(h, np.full(n, 1.0 / n), NDRAWS)


@pytest.mark.parametrize("n", [1, 2, 7, 64])
@pytest.mark.statistical
def test_index_linear_law(n):
    u = jax.random.uniform(jax.random.PRNGKey(1), (NDRAWS,))
    picks = index_linear(u, jnp.full((NDRAWS,), n, jnp.int32))
    w = np.arange(1, n + 1, dtype=np.float64)
    assert _chi2_ok(_hist(picks, n), w / w.sum(), NDRAWS)


@pytest.mark.parametrize("n", [1, 2, 7, 20])
@pytest.mark.statistical
def test_index_exponential_law(n):
    u = jax.random.uniform(jax.random.PRNGKey(2), (NDRAWS,))
    picks = index_exponential(u, jnp.full((NDRAWS,), n, jnp.int32))
    w = np.exp(np.arange(n, dtype=np.float64) - n)
    assert _chi2_ok(_hist(picks, n), w / w.sum(), NDRAWS)


# candidate counts far past float32's exact integers: a start-edge draw over
# a 2^25-edge window has n ≈ 3·10^7, a hub's neighbourhood millions
LARGE_N = [81, 2 ** 20 + 1, 2 ** 22 + 1, 2 ** 24, 30_000_000, 2 ** 31 - 1]
LARGE_DRAWS = 1 << 20
OFFSET_BINS = 16


def _offset_law(n: int) -> np.ndarray:
    """P(offset k from the newest) for k < OFFSET_BINS, then the tail."""
    k = np.arange(OFFSET_BINS, dtype=np.float64)
    head = np.exp(-k) * -np.expm1(-1.0) / -np.expm1(-float(n))
    return np.append(head, max(1.0 - head.sum(), 0.0))


def _offset_hist(picks, n: int) -> np.ndarray:
    off = n - 1 - np.asarray(picks, np.int64)
    return np.bincount(np.minimum(off, OFFSET_BINS),
                       minlength=OFFSET_BINS + 1) / off.size


def _large_u():
    return jax.random.uniform(jax.random.PRNGKey(3), (LARGE_DRAWS,))


@pytest.mark.parametrize("n", LARGE_N)
@pytest.mark.statistical
def test_index_exponential_exact_at_large_n(n):
    """The exact law at every n: a chi-square over the offsets 0..15 from
    the newest candidate and a tail bin, against
    e^{−k}(1 − e^{−1})/(1 − e^{−n}). A form that adds log u to n in
    float32 rounds the sum from n ≈ 2^20 on and skips candidates from
    2^24 on: it fails every case here from 2^20 + 1 up."""
    picks = np.asarray(index_exponential(
        _large_u(), jnp.full((LARGE_DRAWS,), n, jnp.int32)))
    assert picks.min() >= 0 and picks.max() <= n - 1
    assert _chi2_ok(_offset_hist(picks, n), _offset_law(n), LARGE_DRAWS)


@pytest.mark.parametrize("n", LARGE_N)
def test_exponential_callers_follow_index_exponential(n):
    """The per-lane dispatch and the start-edge draw give the same picks
    as ``index_exponential`` (the fused and tiled kernels and the sharded
    start draw call the per-lane dispatch)."""
    u = _large_u()[:4096]
    nn = jnp.full(u.shape, n, jnp.int32)
    want = np.asarray(index_exponential(u, nn))
    code = jnp.full(u.shape, BIAS_EXPONENTIAL, jnp.int32)
    np.testing.assert_array_equal(np.asarray(index_pick_lanes(code, u, nn)),
                                  want)
    index = SimpleNamespace(num_edges=jnp.asarray(n, jnp.int32))
    cfg = SamplerConfig(mode="index", start_bias="exponential")
    np.testing.assert_array_equal(np.asarray(pick_start_edges(index, cfg, u)),
                                  want)


@pytest.mark.statistical
def test_weighted_exp_matches_softmax():
    ts = jnp.asarray([0, 5, 5, 8, 9], jnp.int32)
    tref = int(ts.max())
    w = jnp.exp((ts - tref).astype(jnp.float32))
    pexp = jnp.concatenate([jnp.zeros(1), jnp.cumsum(w)])
    u = jax.random.uniform(jax.random.PRNGKey(4), (NDRAWS,))
    c = jnp.zeros((NDRAWS,), jnp.int32)
    b = jnp.full((NDRAWS,), 5, jnp.int32)
    picks = weighted_pick_exp(pexp, c, b, u)
    target = np.asarray(w / w.sum(), np.float64)
    assert _chi2_ok(_hist(picks, 5), target, NDRAWS)


@pytest.mark.statistical
def test_weighted_exp_suffix_neighborhood():
    """Sampling from a suffix [c, b) uses the same global prefix array."""
    ts = jnp.asarray([0, 5, 5, 8, 9], jnp.int32)
    w = jnp.exp((ts - 9).astype(jnp.float32))
    pexp = jnp.concatenate([jnp.zeros(1), jnp.cumsum(w)])
    u = jax.random.uniform(jax.random.PRNGKey(5), (NDRAWS,))
    c = jnp.full((NDRAWS,), 2, jnp.int32)
    b = jnp.full((NDRAWS,), 5, jnp.int32)
    picks = np.asarray(weighted_pick_exp(pexp, c, b, u)) - 2
    wn = np.asarray(w)[2:]
    assert _chi2_ok(_hist(picks, 3), wn / wn.sum(), NDRAWS)


@pytest.mark.statistical
def test_weighted_linear_matches_weights():
    ts = jnp.asarray([2, 4, 4, 10], jnp.int32)
    tbase = 2
    elem = (ts - tbase + 1).astype(jnp.float32)
    plin = jnp.concatenate([jnp.zeros(1), jnp.cumsum(elem)])
    u = jax.random.uniform(jax.random.PRNGKey(6), (NDRAWS,))
    c = jnp.zeros((NDRAWS,), jnp.int32)
    b = jnp.full((NDRAWS,), 4, jnp.int32)
    tb = jnp.full((NDRAWS,), tbase, jnp.int32)
    picks = weighted_pick_linear(plin, ts, tb, c, b, u)
    # w_i = ts_i - ts_c + 1 with ts_c = 2
    w = np.asarray(ts, np.float64) - 2 + 1
    assert _chi2_ok(_hist(picks, 4), w / w.sum(), NDRAWS)


@settings(max_examples=50, deadline=None)
@given(st.floats(0.0, 1.0, exclude_max=True), st.integers(1, 10_000))
def test_index_samplers_in_range(u, n):
    uu = jnp.asarray([u], jnp.float32)
    nn = jnp.asarray([n], jnp.int32)
    for f in (index_uniform, index_linear, index_exponential):
        i = int(f(uu, nn)[0])
        assert 0 <= i < n


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 100), min_size=1, max_size=30),
       st.floats(0.0, 1.0, exclude_max=True))
def test_weighted_exp_exact_inverse_cdf(ts_list, u):
    """Property: the returned k is the minimal index whose cumulative
    normalized weight reaches u."""
    ts = np.sort(np.asarray(ts_list, np.int32))
    w = np.exp((ts - ts.max()).astype(np.float64))
    pexp = jnp.concatenate([jnp.zeros(1), jnp.cumsum(jnp.asarray(w, jnp.float32))])
    n = len(ts)
    k = int(weighted_pick_exp(pexp, jnp.asarray([0], jnp.int32),
                              jnp.asarray([n], jnp.int32),
                              jnp.asarray([u], jnp.float32))[0])
    cdf = np.cumsum(w) / w.sum()
    expected = int(np.searchsorted(cdf, u, side="right"))
    # float32 rounding at bucket boundaries may move the pick by one bucket
    assert abs(k - min(expected, n - 1)) <= 1
