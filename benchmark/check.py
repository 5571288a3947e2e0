"""The comparison that decides ``correct``.

Once the window has closed, a sample of the replies it finished — drawn from
the seed, with the slowest reply of every class in it — is compared row for
row with what the plain reference owes the same text. The configuration's
guarantee is "every reply exact and complete", so the comparison is exact and
every limit is 0: a reply that failed, a reply whose rows differ, a reply the
system answered on a degraded path. Each number is printed beside its limit.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import Reference, sorted_rows
from benchmark.traffic import STREAM_SAMPLE, rng_for


def sample(replies, seed: int, size: int):
    """Replies to compare: all of them if they are few, else ``size`` drawn
    from the seed, and always the slowest of each class."""
    done = [r for r in replies if r.ok]
    if len(done) <= size:
        return done
    pick = set(rng_for(seed, STREAM_SAMPLE).choice(
        len(done), size=size, replace=False).tolist())
    slowest: dict[str, int] = {}
    for i, r in enumerate(done):
        j = slowest.get(r.req.cls)
        if j is None or r.t_done - r.t_send > done[j].t_done - done[j].t_send:
            slowest[r.req.cls] = i
    return [done[i] for i in sorted(pick | set(slowest.values()))]


def compare(ref: Reference, replies) -> tuple[int, int, list[str]]:
    """-> (replies compared, replies wrong, a few words on the first wrong
    ones). The reference answers each distinct text once."""
    owed: dict[str, np.ndarray] = {}
    wrong, notes = 0, []
    for r in replies:
        want = owed.get(r.req.text)
        if want is None:
            want = owed[r.req.text] = ref.evaluate(r.req.text)
        got = sorted_rows(r.rows())
        if got.shape != want.shape or not np.array_equal(got, want):
            wrong += 1
            if len(notes) < 5:
                notes.append(f"{r.req.cls}#{r.req.idx}: {len(got)} rows, "
                             f"the reference has {len(want)}")
    return len(replies), wrong, notes


def decide(ref: Reference, replies, seed: int, size: int,
           degraded: dict[str, int]) -> dict:
    """-> {"correct", "wrong", "notes", "checks": {name: {value, limit}}}.
    ``degraded``: counts of what the program did instead of its device path
    (fallback events, host steps, CPU-engine executions, broken probes)."""
    failed = sum(1 for r in replies if not r.ok)
    compared, wrong, notes = compare(ref, sample(replies, seed, size))
    for r in replies:
        if not r.ok and len(notes) < 8:
            notes.append(f"{r.req.cls}#{r.req.idx} failed: {r.status}")
    checks = {
        "replies_compared": {"value": compared, "limit": 1, "rule": ">="},
        "wrong_replies": {"value": wrong, "limit": 0, "rule": "<="},
        "failed_replies": {"value": failed, "limit": 0, "rule": "<="},
        "degraded": {"value": sum(degraded.values()), "limit": 0,
                     "rule": "<="},
    }
    ok = all(c["value"] >= c["limit"] if c["rule"] == ">="
             else c["value"] <= c["limit"] for c in checks.values())
    return {"correct": ok, "wrong": wrong, "notes": notes, "checks": checks,
            "degraded": {k: v for k, v in degraded.items() if v}}
