"""Circuit breakers over the kernel degradation ladder.

``core.resilience.run_with_degradation`` steps pallas -> xla and
xla-fused -> xla-unfused *within one solve* when a kernel faults.  A service replays
that discovery on every request: a rung that is persistently broken (a
driver wedged, VMEM exhausted by a cotenant) keeps faulting, and each
fault costs a failed chunk launch before the ladder steps down.  The
breaker remembers: a rung that trips ``threshold`` times inside
``window`` seconds is *open* — skipped outright at chunk entry for
``cooldown`` seconds, after which a single probe (*half-open*) is let
through; success closes the breaker, another failure re-opens it.

``BreakerBoard`` holds one breaker per ladder rung and answers the only
question the service loop asks: *given the configured entry rung, which
rung should this chunk actually run on right now?*
"""

from __future__ import annotations

from ..core import resilience as _res

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half-open"


class CircuitBreaker:
    """Failure-count breaker with a sliding window and cooldown probe."""

    def __init__(self, *, threshold: int = 3, window: float = 60.0,
                 cooldown: float = 30.0, clock=None):
        import time
        self.threshold = threshold
        self.window = window
        self.cooldown = cooldown
        self._clock = clock if clock is not None else time.monotonic
        self._failures: list[float] = []   # timestamps inside the window
        self._opened_at: float | None = None
        self._probing = False
        self.trips = 0

    @property
    def state(self) -> str:
        if self._opened_at is None:
            return CLOSED
        if self._clock() - self._opened_at >= self.cooldown:
            return HALF_OPEN
        return OPEN

    def allows(self) -> bool:
        """May a call go through right now?

        In half-open, the first caller becomes the probe; concurrent
        callers are still refused until the probe reports back.
        """
        st = self.state
        if st == CLOSED:
            return True
        if st == HALF_OPEN and not self._probing:
            self._probing = True
            return True
        return False

    def record_success(self) -> None:
        self._failures.clear()
        self._opened_at = None
        self._probing = False

    def record_failure(self) -> None:
        now = self._clock()
        if self._opened_at is not None:
            # a failed half-open probe: restart the cooldown
            self._opened_at = now
            self._probing = False
            return
        self._failures = [t for t in self._failures
                          if now - t < self.window] + [now]
        if len(self._failures) >= self.threshold:
            self._opened_at = now
            self._probing = False
            self.trips += 1


class BreakerBoard:
    """One ``CircuitBreaker`` per kernel-ladder rung."""

    def __init__(self, *, threshold: int = 3, window: float = 60.0,
                 cooldown: float = 30.0, clock=None):
        self._breakers = {
            rung: CircuitBreaker(threshold=threshold, window=window,
                                 cooldown=cooldown, clock=clock)
            for rung in _res.KERNEL_LADDER
        }

    def __getitem__(self, rung: str) -> CircuitBreaker:
        return self._breakers[rung]

    def entry_config(self, cfg):
        """Walk ``cfg`` down the ladder past rungs whose breaker refuses.

        Returns ``(entry_cfg, skips)`` where ``skips`` counts the open
        rungs stepped over.  The bottom rung always runs (a fully-open
        board must not deadlock the service — the last rung's failures
        surface as request faults, which is the honest outcome).
        """
        skips = 0
        while True:
            rung = _res.config_rung(cfg)
            down = _res.degrade_config(cfg)
            if down is None or self._breakers[rung].allows():
                return cfg, skips
            skips += 1
            cfg = down

    def record(self, rung: str, ok: bool) -> None:
        br = self._breakers[rung]
        br.record_success() if ok else br.record_failure()

    @property
    def trips(self) -> int:
        return sum(b.trips for b in self._breakers.values())

    def snapshot(self) -> dict[str, str]:
        return {rung: b.state for rung, b in self._breakers.items()}


__all__ = ["BreakerBoard", "CircuitBreaker", "CLOSED", "HALF_OPEN", "OPEN"]
