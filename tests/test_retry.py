"""Unit tests for the deadline/backoff/breaker layer (utils/retry).

Everything runs on fake clocks and recorded sleeps — no wall-clock
dependence, so bounds are exact rather than flaky."""

import asyncio
import random

import pytest

from dds_tpu.utils.retry import (
    CircuitBreaker,
    Deadline,
    DeadlineExceededError,
    RetryPolicy,
    retry,
    retry_deadline,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def run(coro):
    return asyncio.run(coro)


# ----------------------------------------------------------------- deadline


def test_deadline_accounting_on_fake_clock():
    clock = FakeClock()
    dl = Deadline(5.0, clock=clock)
    assert dl.remaining() == 5.0 and not dl.expired
    clock.advance(3.0)
    assert dl.remaining() == 2.0 and dl.elapsed() == 3.0
    assert dl.timeout(10.0) == 2.0  # per-attempt clipped to the remainder
    assert dl.timeout(0.5) == 0.5
    clock.advance(3.0)
    assert dl.expired and dl.timeout(1.0) == 0.0


# ------------------------------------------------- exponential backoff bounds


def test_full_jitter_backoff_within_exponential_envelope():
    policy = RetryPolicy(base=0.1, multiplier=2.0, max_delay=1.0)
    rng = random.Random(7)
    for attempt in range(8):
        cap = min(1.0, 0.1 * 2.0 ** attempt)
        for _ in range(50):
            d = policy.backoff(attempt, rng)
            assert 0.0 <= d <= cap, (attempt, d, cap)


def test_backoff_without_jitter_is_deterministic_exponential():
    policy = RetryPolicy(base=0.1, multiplier=2.0, max_delay=0.5, jitter=False)
    rng = random.Random(0)
    assert [policy.backoff(a, rng) for a in range(4)] == [
        0.1, 0.2, 0.4, 0.5  # capped at max_delay
    ]


def test_retry_deadline_sleeps_follow_the_policy():
    clock = FakeClock()
    sleeps = []

    async def fake_sleep(d):
        sleeps.append(d)
        clock.advance(d)

    calls = {"n": 0}

    async def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise ConnectionError("nope")
        return "ok"

    async def go():
        policy = RetryPolicy(base=0.1, multiplier=2.0, max_delay=10.0,
                             jitter=False)
        out = await retry_deadline(
            flaky, Deadline(60.0, clock=clock), policy, sleep=fake_sleep
        )
        assert out == "ok"
        assert sleeps == [0.1, 0.2, 0.4]  # exact exponential ladder

    run(go())


# --------------------------------------------------------- deadline exhaustion


def test_deadline_exhaustion_raises_typed_error_with_context():
    clock = FakeClock()

    async def fake_sleep(d):
        clock.advance(d)

    async def always_down():
        clock.advance(0.05)  # each attempt costs time too
        raise ConnectionError("partitioned")

    async def go():
        policy = RetryPolicy(base=0.2, multiplier=2.0, max_delay=5.0,
                             jitter=False)
        with pytest.raises(DeadlineExceededError) as ei:
            await retry_deadline(
                always_down, Deadline(1.0, clock=clock), policy,
                sleep=fake_sleep,
            )
        err = ei.value
        assert err.attempts >= 1
        assert isinstance(err.last_error, ConnectionError)
        assert err.elapsed <= 1.0 + 1e-9  # degraded WITHIN budget, no overrun
        assert clock.t <= 1.0 + 1e-9     # never slept past the deadline

    run(go())


def test_retry_deadline_does_not_retry_unlisted_exceptions():
    async def boom():
        raise ValueError("a bug, not a blip")

    async def go():
        with pytest.raises(ValueError):
            await retry_deadline(
                boom, Deadline(10.0), retry_on=(ConnectionError,)
            )

    run(go())


def test_retry_deadline_attempt_cap_propagates_real_error():
    calls = {"n": 0}

    async def always_down():
        calls["n"] += 1
        raise ConnectionError("down")

    async def go():
        policy = RetryPolicy(base=0.0, max_attempts=3, jitter=False)
        with pytest.raises(ConnectionError):
            await retry_deadline(always_down, Deadline(10.0), policy)
        assert calls["n"] == 3

    run(go())


def test_legacy_fixed_backoff_retry_still_works():
    calls = {"n": 0}

    async def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("x")
        return 42

    assert run(retry(flaky, 0.0, 5)) == 42
    assert calls["n"] == 3


# ------------------------------------------------------------ circuit breaker


def test_breaker_opens_after_threshold_and_half_opens_after_reset():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=3, reset_timeout=2.0, clock=clock)
    assert b.state == CircuitBreaker.CLOSED and b.allow()
    b.record_failure()
    b.record_failure()
    assert b.state == CircuitBreaker.CLOSED  # below threshold
    b.record_failure()
    assert b.state == CircuitBreaker.OPEN and not b.allow()
    clock.advance(1.9)
    assert not b.allow()  # still open before reset_timeout
    clock.advance(0.2)
    assert b.allow()      # probe admitted
    assert b.state == CircuitBreaker.HALF_OPEN


def test_breaker_half_open_probe_success_closes():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout=1.0, clock=clock)
    b.record_failure()
    assert b.state == CircuitBreaker.OPEN
    clock.advance(1.0)
    assert b.allow() and b.state == CircuitBreaker.HALF_OPEN
    b.record_success()
    assert b.state == CircuitBreaker.CLOSED and b.allow()


def test_breaker_half_open_probe_failure_reopens_with_fresh_timer():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=2, reset_timeout=1.0, clock=clock)
    b.record_failure()
    b.record_failure()
    clock.advance(1.0)
    assert b.state == CircuitBreaker.HALF_OPEN
    b.record_failure()  # ONE failed probe re-opens (no threshold grace)
    assert b.state == CircuitBreaker.OPEN and not b.allow()
    clock.advance(0.5)
    assert not b.allow()  # the reset timer restarted at the failed probe
    clock.advance(0.5)
    assert b.allow()


def test_breaker_success_resets_consecutive_failure_count():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=3, reset_timeout=1.0, clock=clock)
    for _ in range(4):
        b.record_failure()
        b.record_success()  # CONSECUTIVE failures trip, interleaved don't
    assert b.state == CircuitBreaker.CLOSED


# ------------------------------- half-open has an owner (core/quorum_client)


@pytest.mark.parametrize("state", ["closed", "open", "half_open"])
def test_breaker_is_settled_only_when_closed(state):
    """`allow()` is what the degraded try asks (half-open admits it);
    `settled` is what routing asks while another target stands: open or
    half-open, the target is its owner's probe's to resolve."""
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=1, reset_timeout=1.0, clock=clock)
    if state != "closed":
        b.record_failure()
    if state == "half_open":
        clock.advance(1.0)
    assert b.state == state
    assert b.settled is (state == "closed")
    assert b.allow() is (state != "open")
    assert (b.half_open_eta() > 0) is (state == "open")


@pytest.mark.parametrize("answers", [[True], [False, True],
                                     [False, False, False, True]])
def test_an_owners_probe_walks_the_legal_transitions_only(answers):
    """The owner's loop as `AbdClient._probe_loop` runs it, on a fake
    clock: wait out `half_open_eta`, probe, record. Every transition is one
    `obs/watchtower.breaker_legality` allows, a failed probe restarts the
    timer, the first verified answer closes."""
    from dds_tpu.utils.trace import tracer

    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=3, reset_timeout=2.0, clock=clock,
                       name="replica-9")
    moves = []

    def on(rec):
        if rec.name.startswith("breaker.") and rec.meta["target"] == b.name:
            moves.append(rec.name.split(".", 1)[1])

    tracer.subscribe(on)
    try:
        for _ in range(3):
            b.record_failure()
        waited = []
        for ok in answers:
            assert not b.settled
            eta = b.half_open_eta()
            waited.append(eta)
            clock.advance(eta)
            assert b.half_open_eta() == 0 and b.state == b.HALF_OPEN
            clock.advance(1.0)               # the probe's own wait
            b.record_success() if ok else b.record_failure()
        assert b.settled
    finally:
        tracer.unsubscribe(on)
    assert waited == [2.0] * len(answers)    # a fresh timer each time
    want = ["open", "half_open"] * len(answers) + ["closed"]
    assert moves == want
    legal = {"closed": {"open"}, "open": {"half_open", "closed"},
             "half_open": {"open", "closed"}}
    state = "closed"
    for to in moves:
        assert to in legal[state]
        state = to
