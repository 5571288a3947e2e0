"""The arithmetic of the end-to-end metrics, kept apart so it can be tested
on a made-up reply log."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float | None:
    """The ``p``-th percentile (0-100) by linear interpolation between the
    closest ranks; ``None`` of no values. The 95th of twenty values lies
    between the 19th and the 20th smallest, not at the largest."""
    xs = sorted(values)
    if not xs:
        return None
    at = (len(xs) - 1) * p / 100.0
    lo = math.floor(at)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (at - lo)


def mean(values) -> float | None:
    xs = list(values)
    return sum(xs) / len(xs) if xs else None


class ReplyLog:
    """Replies of one window: ``(cls, kind, t_send, t_done, ok)`` with times
    in seconds on one monotonic clock. The window opens at ``t_open``; it
    closes at the last completion, so a rate divides by the time that really
    passed and a stall counts in it."""

    def __init__(self, t_open: float):
        self.t_open = t_open
        self.rows: list[tuple] = []

    def add(self, cls: str, kind: str, t_send: float, t_done: float,
            ok: bool) -> None:
        self.rows.append((cls, kind, t_send, t_done, ok))

    @property
    def attempted(self) -> int:
        return len(self.rows)

    @property
    def failed(self) -> int:
        return sum(1 for r in self.rows if not r[4])

    @property
    def elapsed_s(self) -> float:
        return max((r[3] for r in self.rows), default=self.t_open) - self.t_open

    def rate(self, wrong: int = 0) -> float | None:
        """Correct replies a second: failed ones and those the check found
        wrong are not counted, the time they took is."""
        good = self.attempted - self.failed - wrong
        return good / self.elapsed_s if self.elapsed_s > 0 else None

    def latencies_ms(self, kind: str | None = None, cls: str | None = None):
        """Send to reply of the replies that succeeded; a failed reply is in
        ``failed`` and in no latency."""
        return [(r[3] - r[2]) * 1e3 for r in self.rows if r[4]
                and (kind is None or r[1] == kind)
                and (cls is None or r[0] == cls)]
