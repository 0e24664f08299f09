"""The harness's own arithmetic: which calls a window holds, when a call
counts as failed, and what ``peak_hbm`` is made of."""

import time
import types

import numpy as np

from benchmark import run


class SleepySession:
    cohort = 2

    def __init__(self, call_s):
        self.call_s, self.calls = call_s, 0

    def call(self):
        time.sleep(self.call_s)
        self.calls += 1
        return 1, {"count": np.array([8.0]), "participants": np.array([2])}


def test_a_window_holds_only_calls_that_start_and_end_inside_it():
    session = SleepySession(0.05)
    t0 = time.perf_counter()
    calls = run.measure(session, 0.32)
    # no call is started that the median call so far would not finish
    assert 1 <= len(calls) <= 6 and len(calls) == session.calls
    assert all(t0 <= c[0] <= c[1] and c[0] + 0.05 <= t0 + 0.32 + 0.01
               for c in calls)
    assert sum(c[2] for c in calls) == len(calls)


def test_a_call_fails_on_a_short_cohort_or_a_value_that_is_not_finite():
    good = {"count": np.array([8.0]), "participants": np.array([2])}
    assert run.call_ok(good, 2)
    assert not run.call_ok(good, 3)
    assert not run.call_ok({**good, "loss_sum": np.array([np.nan])}, 2)


def chip(**stats):
    return types.SimpleNamespace(memory_stats=lambda: stats)


def test_peak_hbm_is_the_fullest_chips_live_peak_plus_reserved_peak():
    # the numbers of gpt2l_silo_fused on the v5e (PR 22) and an emptier chip
    full = chip(bytes_in_use=931290112, peak_bytes_in_use=1823542272,
                bytes_reserved=13248724992, peak_bytes_reserved=13248724992)
    other = chip(bytes_in_use=1, peak_bytes_in_use=2 * 2**30,
                 bytes_reserved=0, peak_bytes_reserved=0)
    stats = run.memory_stats([other, full])
    assert stats["peak_bytes"] == 1823542272 + 13248724992 == 15072267264
    assert stats["bytes_in_use"] == 931290112  # the fullest chip's own
    assert run.memory_stats([types.SimpleNamespace(
        memory_stats=lambda: None)])["peak_bytes"] == 0  # the CPU has none


def test_the_sum_is_an_upper_bound_that_a_set_up_transient_raises():
    # a transient of 8.21 GB before any call (the reference's fp32 copies in
    # this PR's first chip call) is in the sum though the chip never held
    # both: what set-up left is reported beside it so that it shows
    live_before = run.memory_stats([chip(peak_bytes_in_use=8210000000,
                                         peak_bytes_reserved=0)])
    after = run.memory_stats([chip(peak_bytes_in_use=8210000000,
                                   peak_bytes_reserved=13248724992)])
    assert after["peak_bytes"] > 16909336064  # more than the chip has
    assert after["peak_bytes_in_use"] == live_before["peak_bytes_in_use"]
