"""Fault-tolerance runtime: heartbeat detection, restart policy, elastic
planning, straggler reslicing, chaos-injected detector behavior."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.runtime import (ChaosSchedule, ChaosWorker, ElasticPlan,
                           FailureDetector, FaultEvent, HeartbeatTracker,
                           RestartPolicy, ResliceAction, plan_reslice)
from repro.train.monitors import StepTimeMonitor


def test_heartbeat_detects_dead_host():
    hb = HeartbeatTracker(n_hosts=4, timeout_s=10.0)
    now = 1000.0
    for h in range(4):
        hb.beat(h, now)
    hb.beat(2, now + 100)
    assert hb.dead_hosts(now + 105) == [0, 1, 3]
    assert hb.dead_hosts(now + 5) == []


def test_restart_policy_backoff_and_budget():
    rp = RestartPolicy(max_restarts=3, base_backoff_s=1.0,
                       max_backoff_s=10.0, jitter=None)
    bs = [rp.next_backoff() for _ in range(4)]
    assert bs[0] == 1.0 and bs[1] == 2.0 and bs[2] == 4.0
    assert bs[3] is None            # budget exhausted


@given(st.integers(0, 10_000), st.integers(1, 20))
@settings(max_examples=30, deadline=None)
def test_restart_policy_jitter_properties(seed, max_restarts):
    """Decorrelated jitter: every draw lands in [base, max], the budget
    exhausts to None exactly after max_restarts, and two policies with the
    same seed replay identically."""
    base, cap = 1.5, 12.0
    rp = RestartPolicy(max_restarts=max_restarts, base_backoff_s=base,
                       max_backoff_s=cap, seed=seed)
    draws = [rp.next_backoff() for _ in range(max_restarts + 3)]
    good, exhausted = draws[:max_restarts], draws[max_restarts:]
    assert all(b is not None and base <= b <= cap for b in good)
    assert all(b is None for b in exhausted)
    twin = RestartPolicy(max_restarts=max_restarts, base_backoff_s=base,
                         max_backoff_s=cap, seed=seed)
    assert [twin.next_backoff() for _ in range(max_restarts)] == good


def test_restart_policy_rejects_bad_config():
    with pytest.raises(ValueError, match="jitter"):
        RestartPolicy(jitter="bogus")
    with pytest.raises(ValueError, match="backoff"):
        RestartPolicy(base_backoff_s=5.0, max_backoff_s=1.0)


def test_elastic_plan_shrinks_data_axis():
    plan = ElasticPlan.plan(surviving_hosts=6, chips_per_host=4,
                            model_parallel=8, resume_step=120)
    assert plan.mesh_shape == (3, 8)    # 24 chips / tp8
    assert plan.resume_step == 120


def test_failure_detector_combines_signals():
    det = FailureDetector(n_hosts=4, timeout_s=60.0,
                          straggler_threshold=1.4)
    rng = np.random.default_rng(0)
    for step in range(10):
        t = 1.0 + rng.normal(0, 0.02, 4)
        t[1] = 2.5
        det.observe_step(step, t, now=1000.0 + step)
    v = det.verdict(10, now=1010.0)
    assert v["stragglers"] == [1]
    assert v["dead"] == []
    assert not v["healthy"]


# --------------------------------------------------------------- reslicing
def _monitor_with_levels(levels, steps=6):
    mon = StepTimeMonitor(len(levels), decay=0.5)
    for s in range(steps):
        mon.observe(s, np.asarray(levels, float))
    return mon, steps - 1


def test_plan_reslice_shrinks_slow_host_share():
    mon, step = _monitor_with_levels([1.0, 1.0, 4.0, 1.0])
    act = plan_reslice(mon, step, global_batch=64, min_share=2)
    assert isinstance(act, ResliceAction)
    assert act.total == 64
    assert all(s >= 2 for s in act.shares)
    assert act.shares[2] == min(act.shares)    # slow host gets least work


def test_plan_reslice_raises_when_batch_below_floor():
    mon, step = _monitor_with_levels([1.0, 1.0, 1.0, 1.0])
    with pytest.raises(ValueError, match="min_share"):
        plan_reslice(mon, step, global_batch=7, min_share=2)


def test_plan_reslice_min_share_clamp_converges_multipass():
    """One extreme straggler among many hosts: the min_share clamp
    overshoots the batch by more than one unit per host, forcing the
    shrink loop through several passes — the single-pass bug returned
    shares summing past the global batch here."""
    mon, step = _monitor_with_levels([1.0, 1000.0, 1000.0, 1000.0])
    # raw ≈ [8.97, .009, .009, .009] → floor+clamp = [8, 2, 2, 2] = 14,
    # five units over the batch of 9: the fast host must shed 5, one per
    # pass, so the loop runs five times before converging to [3, 2, 2, 2]
    act = plan_reslice(mon, step, global_batch=9, min_share=2)
    assert act.total == 9
    assert all(s >= 2 for s in act.shares)
    assert act.shares[0] == 3
    # exactly at the floor: every host gets min_share, nothing else fits
    act = plan_reslice(mon, step, global_batch=8, min_share=2)
    assert act.shares == (2, 2, 2, 2)


# ------------------------------------------------- chaos-injected detection
def _tick_worker(events):
    """A no-op mailbox worker under a chaos schedule."""
    class _Inner:
        def process(self, msg, tick):
            return []

        def reset(self):
            pass

    return ChaosWorker(_Inner(), 0, events)


def test_failure_detector_flags_chaos_heartbeat_loss():
    """A chaos crash stops the worker's heartbeats; the detector must
    call it dead after the timeout — on the injected virtual clock, no
    wall sleeps anywhere."""
    wk = _tick_worker((FaultEvent(5, 0, "crash"),))
    det = FailureDetector(n_hosts=1, timeout_s=3.0)
    deaths = []
    for tick in range(1, 12):
        wk.begin_tick(tick)
        if wk.alive:
            det.hb.beat(0, float(tick))
        v = det.verdict(tick, now=float(tick))
        if v["dead"]:
            deaths.append(tick)
    # alive through tick 4, beats stop at 5, timeout_s=3 → dead from 8 on
    assert deaths == [8, 9, 10, 11]


def test_failure_detector_flags_chaos_persistent_straggler():
    """A chaos stall shows up as inflated observed step times; the fitted
    verdict must flag that worker and ElasticPlan must replan without a
    restart."""
    wk = _tick_worker((FaultEvent(4, 0, "stall", 100),))
    det = FailureDetector(n_hosts=3, timeout_s=50.0,
                          straggler_threshold=1.5)
    step = 0
    for tick in range(1, 20):
        wk.begin_tick(tick)
        times = np.asarray([5.0 if wk.stalled(tick) else 1.0, 1.0, 1.0])
        det.observe_step(step, times, now=float(tick))
        step += 1
    v = det.verdict(step, now=19.0)
    assert v["stragglers"] == [0]
    assert v["dead"] == []        # stalled, not dead: it still heartbeats
    # evict the straggler and replan the mesh around the survivors
    survivors = [h for h in range(3) if h not in v["stragglers"]]
    plan = ElasticPlan.plan(surviving_hosts=len(survivors),
                            chips_per_host=4, model_parallel=4,
                            resume_step=7)
    assert plan.mesh_shape == (2, 4)
    assert plan.resume_step == 7
