"""The plain TPE reference against a loop over its formula, and the rank
gap that the check compares."""
import math

import numpy as np
import pytest

import reference


def _loop_log_mixture(x, obs, bw):
    total = 0.0
    for o in obs:
        total += math.prod(math.exp(-0.5 * ((xd - od) / b) ** 2)
                           / (b * math.sqrt(2 * math.pi))
                           for xd, od, b in zip(x, o, bw))
    total += math.prod(math.exp(-0.5 * (xd - 0.5) ** 2) / math.sqrt(2 * math.pi)
                       for xd in x)
    return math.log(total / (len(obs) + 1))


def test_acquisition_matches_the_formula_written_as_loops():
    rng = np.random.default_rng(0)
    xg, xb = rng.uniform(size=(8, 3)), rng.uniform(size=(16, 3))
    mg = np.r_[np.ones(5), np.zeros(3)]
    mb = np.r_[np.ones(11), np.zeros(5)]
    cands = rng.uniform(size=(6, 3))
    got = reference.acquisition(cands, xg, mg, xb, mb)
    bg = reference.bandwidth(xg, mg, 0.05, 0.5)
    bb = reference.bandwidth(xb, mb, 0.08, 0.7)
    for c, g in zip(cands, got):
        want = (_loop_log_mixture(c, xg[:5], bg)
                - _loop_log_mixture(c, xb[:11], bb))
        assert g == pytest.approx(want, rel=1e-12, abs=1e-12)
    # the bandwidth is the clipped n^(-1/(d+4)) scaled deviation
    std = xg[:5].std(0)
    assert np.allclose(bg, np.clip(std * 5 ** (-1 / 7), 0.05, 0.5))


def test_rank_gap_is_zero_for_the_reference_order_and_grows_with_swaps():
    s = np.array([3.0, 2.5, 1.0, -4.0])
    assert reference.rank_gap(s) == 0.0
    assert reference.rank_gap(s[[1, 0, 2, 3]]) == pytest.approx(0.5)
    assert reference.rank_gap(s[::-1]) == pytest.approx(7.0)


def _run(n_obs=190, n_pending=6, d=4, seed=1):
    """A consistent call: observations of known trials, in-flight rows of
    served trials at the mean, the split the reference makes, candidates
    in the reference's order."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(size=(n_obs + n_pending, d))
    y = rng.uniform(size=n_obs)
    y = np.r_[y, np.full(n_pending, np.sum(y) / n_obs)]
    known = {v: X[i] for i, v in enumerate(y[:n_obs].tolist())}
    history = set(y[:50].tolist())
    good, bad = reference.split(X, y)
    xg = np.zeros((32, d), np.float32)
    xg[:len(good)] = good
    mg = (np.arange(32) < len(good)).astype(float)
    mb = (np.arange(256) < len(bad)).astype(float)
    cands = rng.uniform(size=(64, d)).astype(np.float32)
    order = np.argsort(-reference.acquisition(
        cands, good.astype(np.float32), np.ones(len(good)),
        bad.astype(np.float32), np.ones(len(bad))))
    call = {"X": X, "y": y, "n_obs": n_obs, "xg": xg, "mg": mg, "mb": mb,
            "out": cands[order]}
    return call, known, history, X[n_obs:]


def test_split_check_follows_optunas_default_gamma():
    assert [reference.n_good(n) for n in (5, 20, 100, 250, 4200)] == \
        [2, 2, 10, 25, 25]
    call, known, history, served = _run()
    ok = reference.check_calls([call], known, history, served)
    assert ok == {"rank_gap": 0.0, "split_violations": 0, "liar_gap": 0.0,
                  "rows_wrong": 0}
    # one good row too few on the chip, or a bad row in the good set
    short = dict(call, mg=np.r_[call["mg"][:19] * 0 + 1, np.zeros(13)])
    assert reference.check_calls([short], known, history,
                                 served)["split_violations"] == 1
    swapped = dict(call, xg=call["xg"].copy())
    order = np.argsort(call["y"])
    swapped["xg"][0] = call["X"][order[-1]]
    assert reference.check_calls([swapped], known, history,
                                 served)["split_violations"] == 1


def test_in_flight_rows_must_sit_at_the_mean_of_the_observed():
    call, known, history, served = _run()
    y = call["y"].copy()
    y[-3:] = y[:190].min()
    got = reference.check_calls([dict(call, y=y)], known, history, served)
    assert got["liar_gap"] > 0.1


def test_every_row_must_be_a_trial_the_run_holds():
    call, known, history, served = _run()
    X = call["X"].copy()
    X[105, 0] += 1e-6                     # an observed point moved
    X[-1, 1] += 1e-6                      # an in-flight point moved
    y = call["y"].copy()
    y[107] += 1.0                         # a value no trial has
    assert reference.rows_wrong(X, y, 190, known, history, served) == 3
    # a row taken twice counts, and so does the history trial it hides
    y2, X2 = call["y"].copy(), call["X"].copy()
    y2[3], X2[3] = y2[4], X2[4]
    assert reference.rows_wrong(X2, y2, 190, known, history, served) == 2
