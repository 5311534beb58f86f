"""RetryPolicy tier-1 pins: deterministic under a fixed seed, gives up
at the deadline, never fires on success."""
import pytest

from autodist_tpu.runtime.retry import (RetryError, RetryPolicy,
                                        backoff_delay)


def test_backoff_delay_capped_exponential():
    assert [backoff_delay(a, 5.0, 60.0) for a in range(1, 6)] == \
        [5.0, 10.0, 20.0, 40.0, 60.0]


def test_delays_deterministic_under_fixed_seed():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.1, cap_delay_s=2.0,
                    seed=42)
    assert p.delays() == p.delays()
    assert len(p.delays()) == 4
    # a different seed gives a different jitter draw
    q = RetryPolicy(max_attempts=5, base_delay_s=0.1, cap_delay_s=2.0,
                    seed=43)
    assert p.delays() != q.delays()
    # jitter stays within +/- the configured fraction of the base curve
    for a, d in enumerate(p.delays(), start=1):
        base = p.delay_s(a)
        assert base * 0.5 <= d <= base * 1.5


def test_never_fires_on_success():
    slept = []
    p = RetryPolicy(max_attempts=5, base_delay_s=1.0, seed=0)
    calls = []

    def ok():
        calls.append(1)
        return 99

    assert p.call(ok, sleep=slept.append) == 99
    assert len(calls) == 1 and slept == []


def test_retries_then_succeeds_with_seeded_schedule():
    slept = []
    p = RetryPolicy(max_attempts=4, base_delay_s=0.1, cap_delay_s=1.0,
                    seed=7)
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] < 3:
            raise OSError("transient")
        return "done"

    assert p.call(flaky, sleep=slept.append) == "done"
    assert state["n"] == 3
    assert slept == p.delays()[:2]   # the exact seeded schedule


def test_gives_up_after_attempt_budget():
    p = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0)
    calls = []

    def always():
        calls.append(1)
        raise OSError("down")

    with pytest.raises(RetryError) as ei:
        p.call(always, sleep=lambda s: None)
    assert len(calls) == 3
    assert ei.value.attempts == 3
    assert isinstance(ei.value.last, OSError)


def test_gives_up_at_the_deadline():
    # fake clock: each attempt "takes" 10s; deadline 15s -> the second
    # retry would land past the deadline and must not run.
    t = {"now": 0.0}

    def clock():
        return t["now"]

    def sleep(s):
        t["now"] += s

    calls = []

    def always():
        calls.append(1)
        t["now"] += 10.0
        raise OSError("down")

    p = RetryPolicy(max_attempts=10, base_delay_s=1.0, jitter=0.0,
                    deadline_s=15.0)
    with pytest.raises(RetryError, match="deadline"):
        p.call(always, sleep=sleep, clock=clock)
    assert len(calls) == 2   # attempt 1 (10s) + retry (11s) > 15s stops


def test_non_retryable_propagates_unwrapped():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.0,
                    retryable=(OSError,))
    with pytest.raises(ValueError, match="bug"):
        p.call(lambda: (_ for _ in ()).throw(ValueError("bug")),
               sleep=lambda s: None)


def test_predicate_classification():
    p = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter=0.0,
                    retryable=lambda e: "retry-me" in str(e))
    with pytest.raises(RetryError):
        p.call(lambda: (_ for _ in ()).throw(OSError("retry-me")),
               sleep=lambda s: None)
    with pytest.raises(OSError, match="not-this"):
        p.call(lambda: (_ for _ in ()).throw(OSError("not-this")),
               sleep=lambda s: None)


def test_max_total_delay_is_the_lint_bound():
    p = RetryPolicy(max_attempts=3, base_delay_s=1.0, cap_delay_s=10.0,
                    jitter=0.5)
    # retries after attempts 1 and 2: (1 + 2) * 1.5 worst case
    assert p.max_total_delay_s() == pytest.approx(4.5)
