"""Bounded suspect-then-confirm health policy for a live resource.

The serving fleet (:mod:`distmlip_tpu.fleet`) watches N live engine
replicas with :class:`ReprobePolicy`: a failed heartbeat marks a replica
SUSPECT (not dead), and bounded re-probes with backoff either clear the
suspicion or confirm the wedge.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field


@dataclass
class ReprobePolicy:
    """Bounded suspicion-then-confirm discipline for a LIVE resource.

    A failed probe marks the resource SUSPECT rather than dead; the policy
    then requires ``max_reprobes`` FURTHER consecutive failures, each at
    least ``backoff_s`` apart (backing off between looks instead of
    hammering a struggling replica), before confirming the wedge. Any
    successful probe clears the suspicion entirely.

    Drive it with :meth:`observe`; it returns ``"healthy"``,
    ``"suspect"`` or ``"wedged"``. ``clock`` is injectable so tests step
    time deterministically.
    """

    max_reprobes: int = 1
    backoff_s: float = 1.0
    clock: object = time.monotonic

    failures: int = field(default=0, init=False)
    _last_look: float = field(default=float("-inf"), init=False)

    def observe(self, healthy: bool) -> str:
        now = self.clock()
        if healthy:
            self.failures = 0
            self._last_look = now
            return "healthy"
        if self.failures > 0 and now - self._last_look < self.backoff_s:
            # inside the backoff window: the previous verdict stands —
            # a rapid poll loop must not burn re-probes faster than the
            # resource could plausibly recover
            return "suspect" if self.failures <= self.max_reprobes \
                else "wedged"
        self.failures += 1
        self._last_look = now
        return "suspect" if self.failures <= self.max_reprobes else "wedged"

    def reset(self) -> None:
        self.failures = 0
        self._last_look = float("-inf")
