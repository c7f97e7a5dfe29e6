"""Time as a dependency — virtual for tests/simulation, wall for serving.

Every serving policy in this package (token-bucket refill, queue age,
deadline-aware dispatch, degrade hysteresis) is a function of *time*, and
a policy that can only be exercised by actually sleeping is untestable in
CI.  The tier therefore never calls ``time`` directly: it asks an
injected :class:`Clock`, and the two implementations make the same loop
either a deterministic discrete-event simulation (:class:`VirtualClock` —
``sleep_until`` jumps, ``advance`` charges modeled service time) or a
real paced service (:class:`WallClock` — ``sleep_until`` sleeps,
``advance`` is a no-op because wall time already passed during the work).
"""

from __future__ import annotations

import time

__all__ = ["Clock", "VirtualClock", "WallClock"]


class Clock:
    """The time interface the serving tier programs against."""

    def now(self) -> float:
        """Current time in seconds (monotone)."""
        raise NotImplementedError

    def advance(self, dt: float) -> None:
        """Charge ``dt`` seconds of service time (virtual time only)."""
        raise NotImplementedError

    def sleep_until(self, t: float) -> None:
        """Block (or jump) until ``now() >= t``."""
        raise NotImplementedError


class VirtualClock(Clock):
    """Deterministic simulated time: nothing moves unless told to.

    ``advance`` models work being done (the service charges each batch's
    modeled duration); ``sleep_until`` models idling until the next event
    (arrival or deadline trigger).  Time never goes backwards.
    """

    def __init__(self, start: float = 0.0):
        self._now = float(start)

    def now(self) -> float:
        return self._now

    def advance(self, dt: float) -> None:
        if dt < 0:
            raise ValueError(f"cannot advance time by {dt!r} (negative)")
        self._now += float(dt)

    def sleep_until(self, t: float) -> None:
        self._now = max(self._now, float(t))


class WallClock(Clock):
    """Real time via ``time.perf_counter`` (zeroed at construction).

    ``advance`` is a no-op: wall time already elapsed while the engine
    ran the batch.  ``sleep_until`` actually sleeps, which is what paces
    an open-loop arrival schedule at its offered QPS.
    """

    def __init__(self):
        self._t0 = time.perf_counter()

    def restart(self) -> None:
        """Zero the clock again: the served window starts now."""
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def advance(self, dt: float) -> None:
        pass  # the work itself consumed the time

    def sleep_until(self, t: float) -> None:
        dt = float(t) - self.now()
        if dt > 0:
            time.sleep(dt)
