"""The admission controller decides as it did before it learned to say
why (keto_tpu/driver/admission.py): a fixed script of ``observe_round`` /
``tick`` calls, the window after each pinned from the parent commit's
code. ``test_admission_replay_decides_exactly_as_the_parent_did`` uses
nothing the parent lacks and passes on its admission.py unchanged; the
tests after it read what this PR added."""

import random

import pytest

from keto_tpu.driver.admission import AdmissionController
from keto_tpu.x.metrics import MetricsRegistry


class FakeStats:
    def __init__(self):
        self._vals = []

    def feed(self, *ms):
        self._vals.extend(ms)

    def tail(self, n):
        if n <= 0:
            return [], len(self._vals)
        return self._vals[-n:], len(self._vals)


def _replay(ctrl_kw=None):
    """A serving daemon's defaults (budget 4 x 40 = 160 ms, window
    64..32768, ticks 0.25 s apart) driven by a fixed script: silence with
    a deep queue, a steady closed loop at 80-90% of the budget, one slow
    round in four, slow slices over a short queue, recovery, both signals
    at once. Every seventh call comes too soon and is not evaluated."""
    rng = random.Random(25)
    stats = FakeStats()
    ctrl = AdmissionController(
        stats=stats, target_ms=40.0, min_window=64, max_window=32768, **(ctrl_kw or {})
    )
    now, trajectory = 100.0, []
    for step in range(120):
        now += 0.25 if step % 7 else 0.1
        phase = step // 20
        if phase == 0:
            backlog = 40000 if 8 <= step < 11 else 1000
        elif phase == 1:
            ctrl.observe_round(1024, 1024 / 205000.0)
            stats.feed(*(rng.uniform(0.8, 1.6) for _ in range(12)))
            backlog = rng.randrange(26000, 30000)
        elif phase == 2:
            ctrl.observe_round(1024, (4 if step % 4 == 0 else 1) * 1024 / 205000.0)
            stats.feed(*(rng.uniform(0.8, 1.6) for _ in range(12)))
            backlog = rng.randrange(26000, 30000)
        elif phase == 3:
            ctrl.observe_round(1024, 0.02)
            stats.feed(*(rng.uniform(150.0, 400.0) for _ in range(3)))
            backlog = 512
        elif phase == 4:
            ctrl.observe_round(1024, 0.005)
            stats.feed(1.0)
            backlog = 2048
        else:
            ctrl.observe_round(512, 0.05)
            stats.feed(500.0)
            backlog = 30000
        ctrl.tick(backlog=backlog, now=now)
        trajectory.append(ctrl.window)
    return ctrl, trajectory


#: the window after each of the 120 calls, recorded from the PARENT's
#: admission.py (commit a07774f): this test runs unchanged on it
PINNED_WINDOWS = [
    32768, 32768, 32768, 32768, 32768, 32768, 32768, 32768, 16384, 8192, 4096, 4608, 5120,
    5632, 5632, 6144, 6656, 7168, 7680, 8192, 8704, 8704, 9216, 9728, 10240, 10752, 11264,
    11776, 11776, 12288, 12800, 13312, 13824, 14336, 14848, 14848, 15360, 15872, 16384,
    16896, 17408, 17920, 17920, 18432, 9216, 4608, 5120, 5632, 2816, 2816, 1408, 704, 352,
    176, 88, 64, 64, 576, 288, 800, 400, 200, 100, 100, 64, 64, 64, 64, 64, 64, 64, 64, 64,
    64, 64, 64, 64, 64, 64, 64, 576, 1088, 1600, 2112, 2112, 2624, 3136, 3648, 4160, 4672,
    5184, 5184, 5696, 6208, 6720, 7232, 7744, 8256, 8256, 8768, 4384, 2192, 1096, 548, 274,
    274, 137, 68, 64, 64, 64, 64, 64, 64, 64, 64, 64, 64, 64, 64,
]


def test_admission_replay_decides_exactly_as_the_parent_did():
    ctrl, trajectory = _replay()
    assert trajectory == PINNED_WINDOWS
    assert (ctrl.decreases, ctrl.increases) == (47, 56)


def test_admission_decreases_name_the_signal_that_tripped():
    from keto_tpu.driver.admission import SIGNALS

    ctrl, _ = _replay()
    assert tuple(ctrl.decreases_by_signal) == SIGNALS
    # the deep silent queue; the slow rounds of the closed loop; the slow
    # slices — and where both are over, the slices are asked first
    assert ctrl.decreases_by_signal == {"slice_p99": 34, "queue_delay": 10, "stall": 3}
    assert sum(ctrl.decreases_by_signal.values()) == ctrl.decreases


def test_admission_queue_delay_histogram_sees_each_evaluated_tick_once():
    hist = MetricsRegistry().histogram("keto_admission_queue_delay_seconds", "test")
    ctrl = AdmissionController(min_window=64, max_window=32768)
    ctrl.attach_queue_delay_histogram(hist)
    ctrl.tick(backlog=1000, now=10.0)  # no rate yet: no estimate, nothing observed
    ctrl.observe_round(1000, 0.01)  # 100k tuples/s
    assert ctrl.rate_tuples_per_s == pytest.approx(100000.0)
    ctrl.tick(backlog=8000, now=11.0)  # 80 ms
    ctrl.tick(backlog=9000, now=11.1)  # too soon: not evaluated
    ctrl.tick(backlog=4000, now=12.0)  # 40 ms
    samples = {name: v for name, _, _, v, _ in hist.samples() if not name.endswith("_bucket")}
    assert samples["keto_admission_queue_delay_seconds_count"] == 2
    assert samples["keto_admission_queue_delay_seconds_sum"] == pytest.approx(0.12)
