"""Pruner semantics: early termination of non-promising trials (sec. 2)."""
import struct

import numpy as np
import pytest

from repro.core.pruners import PercentilePruner, make_pruner
from repro.core.pruners.median import percentile_of_others
from repro.core.storage import InMemoryStorage
from repro.core.types import (Direction, StepView, Study, StudyConfig, Trial,
                              TrialState)


def study_with_history(curves, direction=Direction.MINIMIZE, states=None):
    """curves: list of per-trial loss curves already 'reported'."""
    cfg = StudyConfig(name="p", properties={}, direction=direction)
    trials = []
    for i, curve in enumerate(curves):
        t = Trial(trial_id=i, uid=f"p:{i}", study_key="p", params={},
                  state=(states[i] if states else TrialState.COMPLETED),
                  value=curve[-1],
                  intermediates={s: v for s, v in enumerate(curve)})
        trials.append(t)
    return Study(config=cfg, trials=trials)


def running_trial(curve, tid=99):
    return Trial(trial_id=tid, uid=f"p:{tid}", study_key="p", params={},
                 state=TrialState.RUNNING,
                 intermediates={s: v for s, v in enumerate(curve)})


def test_median_prunes_bad_trial():
    good = [[10 - s for s in range(10)] for _ in range(5)]     # reach ~1
    study = study_with_history(good)
    bad = running_trial([100.0, 99.0, 98.0])
    study.trials.append(bad)
    pruner = make_pruner({"name": "median", "n_startup_trials": 3})
    assert pruner.should_prune(study, bad, 2)


def test_median_keeps_good_trial():
    good = [[10 - s for s in range(10)] for _ in range(5)]
    study = study_with_history(good)
    better = running_trial([8.0, 6.5, 5.0])
    study.trials.append(better)
    pruner = make_pruner({"name": "median", "n_startup_trials": 3})
    assert not pruner.should_prune(study, better, 2)


def test_median_respects_startup_and_warmup():
    study = study_with_history([[1.0, 1.0]])
    bad = running_trial([100.0, 100.0])
    study.trials.append(bad)
    pruner = make_pruner({"name": "median", "n_startup_trials": 4})
    assert not pruner.should_prune(study, bad, 1)     # not enough history
    pruner2 = make_pruner({"name": "median", "n_startup_trials": 0,
                           "n_warmup_steps": 5})
    assert not pruner2.should_prune(study, bad, 1)    # still warming up


def test_median_maximize_direction():
    good = [[s * 1.0 for s in range(10)] for _ in range(5)]    # rising = good
    study = study_with_history(good, direction=Direction.MAXIMIZE)
    bad = running_trial([0.0, 0.0, 0.0])
    study.trials.append(bad)
    pruner = make_pruner({"name": "median", "n_startup_trials": 3})
    assert pruner.should_prune(study, bad, 2)


def test_percentile_is_laxer_than_median():
    curves = [[float(v)] * 3 for v in (1, 2, 3, 4, 5, 6, 7, 8, 9)]
    study = study_with_history(curves)
    mid = running_trial([5.5, 5.5, 5.5])
    study.trials.append(mid)
    assert make_pruner({"name": "median", "n_startup_trials": 3}
                       ).should_prune(study, mid, 2)
    assert not make_pruner({"name": "percentile", "percentile": 90.0,
                            "n_startup_trials": 3}).should_prune(study, mid, 2)


def test_sha_rungs():
    pruner = make_pruner({"name": "sha", "min_resource": 2, "reduction_factor": 3})
    assert pruner.rung_of(0) is None
    assert pruner.rung_of(1) == 0           # resource 2
    assert pruner.rung_of(5) == 1           # resource 6
    assert pruner.rung_resource(0) == 2 and pruner.rung_resource(1) == 6


def test_sha_prunes_bottom_of_rung():
    curves = [[float(v)] * 4 for v in (1, 2, 3, 4, 5, 6, 7, 8)]
    study = study_with_history(curves)
    worst = running_trial([9.0, 9.0])
    study.trials.append(worst)
    pruner = make_pruner({"name": "sha", "min_resource": 2, "reduction_factor": 3})
    assert pruner.should_prune(study, worst, 1)
    best = running_trial([0.5, 0.5], tid=98)
    study.trials.append(best)
    assert not pruner.should_prune(study, best, 1)


def test_hyperband_brackets_deterministic():
    pruner = make_pruner({"name": "hyperband", "min_resource": 1,
                          "max_resource": 27, "reduction_factor": 3})
    assert len(pruner.brackets) == 4
    t = running_trial([1.0])
    assert pruner.bracket_of(t) is pruner.bracket_of(t)


def test_patient_prunes_plateau():
    study = study_with_history([[1.0]])
    plateau = running_trial([5.0, 4.0] + [4.0] * 10)
    study.trials.append(plateau)
    pruner = make_pruner({"name": "patient", "patience": 4})
    assert pruner.should_prune(study, plateau, 11)
    improving = running_trial([5.0 - 0.3 * s for s in range(12)], tid=98)
    study.trials.append(improving)
    assert not pruner.should_prune(study, improving, 11)


def test_none_pruner_never_prunes():
    study = study_with_history([[0.0] * 5] * 10)
    bad = running_trial([1e9] * 5)
    study.trials.append(bad)
    assert not make_pruner({"name": "none"}).should_prune(study, bad, 4)


def test_unknown_specs_raise():
    with pytest.raises(ValueError):
        make_pruner({"name": "nope"})
    from repro.core.samplers import make_sampler
    with pytest.raises(ValueError):
        make_sampler({"name": "nope"})


def test_pruning_saves_compute_end_to_end():
    """Integration: a median-pruned campaign spends fewer total steps than
    an unpruned one while finding the same optimum region."""
    from repro.core import (Client, ClientStudy, DirectTransport, HopaasServer,
                            suggestions)

    def run(pruner):
        srv = HopaasServer(seed=1)
        cl = Client(DirectTransport(srv), srv.tokens.issue("t"))
        study = ClientStudy(name="c", client=cl,
                            properties={"x": suggestions.uniform(0, 4)},
                            sampler={"name": "random"}, pruner=pruner)
        total_steps = 0
        for _ in range(24):
            with study.trial() as tr:
                # loss curve converges to x^2: bad x is visible early
                target = tr.x ** 2
                for step in range(16):
                    total_steps += 1
                    val = target + (16 - step) * 0.05
                    if tr.should_prune(step, val):
                        break
                tr.loss = target
        (s,) = [x for x in cl.studies() if x["name"] == "c"]
        return total_steps, s["best_value"], s["n_pruned"]

    steps_none, best_none, _ = run({"name": "none"})
    steps_med, best_med, pruned = run({"name": "median", "n_startup_trials": 4})
    assert pruned > 0
    assert steps_med < steps_none * 0.9
    assert best_med < 1.0 and best_none < 1.0


# --------------------------------------------------------------------------- #
# the percentile pruner's sorted per-step view against the NumPy reference
# --------------------------------------------------------------------------- #
def reference_percentile(pruner, study, trial, step):
    """The list-and-``np.percentile`` pruner the sorted view replaced, as
    ``(threshold, verdict)``; threshold None where no percentile is taken."""
    if step < pruner.n_warmup_steps:
        return None, False
    if (step - pruner.n_warmup_steps) % pruner.interval_steps != 0:
        return None, False
    sign = pruner._sign(study)
    others = [sign * v for uid, v in study.reports_at(step).items()
              if uid != trial.uid]
    if len(others) < pruner.n_startup_trials:
        return None, False
    if not others:
        # np.percentile raised IndexError on an empty set; the view
        # answers "no others, no verdict"
        return None, False
    threshold = float(np.percentile(others, pruner.percentile))
    mine = min(sign * v for s, v in trial.intermediates.items() if s <= step)
    return threshold, mine > threshold


def _bits(x):
    return struct.pack("<d", x)


class _ManagedDriver:
    """A study owned by storage: every report goes through update_trial."""

    def __init__(self, direction):
        self.storage = InMemoryStorage()
        self.study, _ = self.storage.get_or_create_study(
            StudyConfig(name="eq", properties={}, direction=direction))

    def new_trial(self):
        return self.storage.add_trial(self.study.key, {}, None, None)

    def report(self, trial, step, value):
        self.storage.update_trial(trial.uid, intermediate=(step, value))


class _HandBuiltDriver:
    """An unmanaged study whose trials' intermediates are mutated directly."""

    def __init__(self, direction):
        self.study = Study(config=StudyConfig(name="eq", properties={},
                                              direction=direction))

    def new_trial(self):
        n = len(self.study.trials)
        t = Trial(trial_id=n, uid=f"eq:{n}", study_key="eq", params={})
        self.study.trials.append(t)
        return t

    def report(self, trial, step, value):
        trial.intermediates[step] = value


@pytest.mark.parametrize("mode", ["managed", "hand_built"])
@pytest.mark.parametrize("percentile", [50.0, 25.0, 90.0, 12.5, 33.3])
@pytest.mark.parametrize("direction", [Direction.MINIMIZE, Direction.MAXIMIZE])
def test_percentile_pruner_matches_numpy_reference(direction, percentile, mode):
    """Thresholds bit-identical to ``np.percentile`` and equal verdicts, from
    0 to 3,000 reporters at a step, with exact ties (the probe's own value
    among them), re-reports, the probe absent from the step, and warm-up
    and interval gating."""
    rng = np.random.default_rng(
        [int(percentile * 10), direction == Direction.MAXIMIZE,
         mode == "managed"])
    drv = (_ManagedDriver if mode == "managed" else _HandBuiltDriver)(direction)
    study = drv.study
    sign = 1.0 if direction == Direction.MINIMIZE else -1.0
    pruners = [PercentilePruner(percentile, n_startup_trials=0),
               PercentilePruner(percentile, n_startup_trials=4),
               PercentilePruner(percentile, n_startup_trials=1,
                                n_warmup_steps=1, interval_steps=2)]

    def value():
        # a coarse grid half the time, so exact ties are common
        if rng.random() < 0.5:
            return float(rng.integers(0, 12)) * 0.25
        return float(rng.normal(1.5, 1.0))

    compared = []

    def check(trial, step):
        for pruner in pruners:
            threshold, verdict = reference_percentile(pruner, study, trial,
                                                      step)
            assert pruner.should_prune(study, trial, step) == verdict
            if threshold is not None:
                view, own = study.step_view(step, sign, trial.uid)
                got = percentile_of_others(view, own, percentile)
                assert _bits(got) == _bits(threshold), (step, got, threshold)
                compared.append(len(view) - (own is not None))

    trials = []
    for size in (0, 1, 2, 3, 4, 5, 9, 40, 3000):
        while len(trials) < size:
            t = drv.new_trial()
            trials.append(t)
            for step in range(int(rng.integers(1, 5))):
                drv.report(t, step, value())
        # a probe reports steps 0..3, tying an existing value where there
        # is one, and is judged after each report as the service does
        probe = drv.new_trial()
        for step in range(4):
            peers = [t for t in trials if step in t.intermediates]
            tie = peers[int(rng.integers(len(peers)))] if peers else None
            drv.report(probe, step,
                       tie.intermediates[step] if tie else value())
            check(probe, step)
        # re-reports (client retries) replace values, the probe's own too
        for t in [probe] + [trials[int(i)] for i in
                            rng.integers(0, max(len(trials), 1),
                                         size=min(len(trials), 3))]:
            step = int(rng.choice(sorted(t.intermediates)))
            drv.report(t, step, value())
            check(probe, step)
        # a trial judged at steps where it has not reported
        absent = drv.new_trial()
        drv.report(absent, 0, value())
        for step in range(4):
            check(absent, step)
        trials += [probe, absent]
    assert min(compared) == 1 and max(compared) >= 3000


def test_percentile_of_others_nan_and_range():
    """A NaN among the others makes the threshold NaN (never a prune), as
    NumPy's does; the probe's own NaN is left out; a percentile outside
    [0, 100] raises as NumPy does."""
    view = StepView([1.0, float("nan"), 3.0, 2.0])
    assert len(view) == 4 and view.nans == 1
    assert np.isnan(percentile_of_others(view, None, 50.0))
    assert np.isnan(np.percentile([1.0, float("nan"), 3.0, 2.0], 50.0))
    assert percentile_of_others(view, float("nan"), 50.0) == \
        np.percentile([1.0, 3.0, 2.0], 50.0)
    with pytest.raises(ValueError):
        percentile_of_others(view, None, 100.5)


def test_percentile_pruner_without_others_keeps_the_trial():
    """With ``n_startup_trials=0`` and nobody else at the step the verdict
    is "keep" (``np.percentile`` raised IndexError on the empty set)."""
    study = study_with_history([])
    lone = running_trial([3.0, 1.0])
    study.trials.append(lone)
    pruner = make_pruner({"name": "percentile", "percentile": 50.0,
                          "n_startup_trials": 0})
    assert not pruner.should_prune(study, lone, 1)
    assert not pruner.should_prune(study, lone, 2)    # not reported there
    with pytest.raises(IndexError):
        np.percentile([], 50.0)


def test_report_view_counters_one_build_per_step_and_sign():
    """N reports, each judged, on managed studies: one view built per
    (step, sign), N queries, summed over the shards in storage_stats; the
    kept view equals a fresh sort after every report and re-report."""
    rng = np.random.default_rng(7)
    storage = InMemoryStorage()
    studies = [storage.get_or_create_study(
        StudyConfig(name=f"c{d.value}", properties={}, direction=d))[0]
        for d in (Direction.MINIMIZE, Direction.MAXIMIZE)]
    pruner = make_pruner({"name": "median"})
    n_reports = 0
    for study in studies:
        sign = pruner._sign(study)
        uids = [storage.add_trial(study.key, {}, None, None).uid
                for _ in range(30)]
        for step in range(4):
            for uid in uids:
                for _ in range(1 + (rng.random() < 0.2)):   # some re-report
                    storage.update_trial(
                        uid, intermediate=(step, float(rng.integers(0, 6))))
                    pruner.should_prune(study, storage.get_trial(uid), step)
                    n_reports += 1
                    view, _ = study.step_view(step, sign, uid)
                    assert view.values == sorted(
                        sign * v for v in study.reports_at(step).values())
        assert study.report_view_builds == 4
    stats = storage.storage_stats()
    assert stats["report_view_builds"] == 2 * 4
    # every report was judged once and its view read once more above
    assert stats["report_view_queries"] == 2 * n_reports
    assert stats["trial_scans"] == 0


def _drive_reports(storage, study, script, pruner):
    """Apply ``(trial_id, step, value)`` reports, judging each; verdicts."""
    verdicts = []
    for tid, step, value in script:
        uid = f"{study.key}:{tid}"
        storage.update_trial(uid, intermediate=(step, value))
        verdicts.append(pruner.should_prune(study, storage.get_trial(uid),
                                            step))
    return verdicts


@pytest.mark.parametrize("recovery", ["journal", "snapshot", "restore_shard"])
def test_recovered_study_prunes_like_the_live_one(tmp_path, recovery):
    """A study replayed from the journal, loaded from a snapshot, or
    restored with ``_restore_shard`` rebuilds its views lazily and gives the
    live study's verdicts on the same next reports."""
    from repro.core.durable import DurableStorage
    rng = np.random.default_rng(11)
    config = StudyConfig(name="rec", properties={},
                         direction=Direction.MAXIMIZE)
    pruner = make_pruner({"name": "median", "n_startup_trials": 2})
    n_trials = 40
    prefix = [(int(rng.integers(n_trials)), int(rng.integers(4)),
               float(rng.normal())) for _ in range(300)]
    tail = [(int(rng.integers(n_trials)), int(rng.integers(4)),
             float(rng.normal())) for _ in range(200)]

    def build(storage):
        study, _ = storage.get_or_create_study(config)
        for _ in range(n_trials):
            storage.add_trial(study.key, {}, None, None)
        _drive_reports(storage, study, prefix, pruner)
        return study

    live = InMemoryStorage()
    live_study = build(live)
    expected = _drive_reports(live, live_study, tail, pruner)
    assert any(expected) and not all(expected)

    if recovery == "restore_shard":
        source = InMemoryStorage()
        build(source)
        recovered = InMemoryStorage()
        recovered._restore_shard(source.shard_record(config.key()))
    else:
        root = str(tmp_path / "wal")
        st = DurableStorage(root, fsync="always", auto_compact=False,
                            segment_bytes=2000)
        build(st)
        if recovery == "snapshot":
            assert st.compact(min_segments=1) > 0
        st.close()
        recovered = DurableStorage(root, fsync="off", auto_compact=False)
        assert (recovered.last_recovery["snapshot_covers"] > 0) == \
            (recovery == "snapshot")
    study = recovered.get_study(config.key())
    assert study.report_view_builds == 0
    assert _drive_reports(recovered, study, tail, pruner) == expected
    assert study.report_view_builds == 4
    recovered.close()
