"""The batched k-NN operator (the query half of the hybrid subsystem).

One scoring seam — :func:`scores` — written against a swappable array
module ``xp`` (the ``join/kernels.py`` posture): cosine / dot / L2 are a
batched matmul plus elementwise fixups, so the SAME function body runs
as plain NumPy on the host and traces into a jitted XLA batched-matmul +
``jax.lax.top_k`` scan on the device (candidates padded to a power-of-two
capacity class, dead/padding slots masked to ``-inf``). L2 ranks by
NEGATIVE squared distance so "higher score = nearer" holds across all
three metrics.

Composition with BGPs happens in the engine
(``CPUEngine._knn_seed`` / ``_knn_rank``): this module only ranks.
Ranking is deterministic — ties break by ``(score desc, vid asc)`` — so
the pattern-then-rank and rank-then-pattern replies are byte-identical
between routes whenever score gaps exceed float error (exact cross-route
score ties at the k boundary may differ: XLA and NumPy matmuls round
differently).

Wide scans split into slice ranges across the engine pool
(:func:`sliced_topk`) with the ``join/dist.py`` heavy-lane shape:
claim-once slices, a gather barrier, one inline per-slice retry, and
per-slice device->host fallback. Per-element scores are row-independent,
so the sliced merge is exactly the single-scan answer.
"""

from __future__ import annotations

import threading

import numpy as np

from wukong_tpu.analysis.lockdep import declare_leaf, make_lock
from wukong_tpu.utils.errors import ErrorCode, WukongError
from wukong_tpu.utils.timer import get_usec

#: the metric names behind the one kernel seam (knn_metric knob values)
KNN_METRICS = ("cosine", "dot", "l2")

#: device capacity-class floor (join/kernels.py PAD_FLOOR discipline)
PAD_FLOOR = 1024

# the slice claim lock guards one bool — innermost by construction,
# exactly join.slice
declare_leaf("vector.slice")

# chaos/bench seam: when set, the device scan path calls it before
# dispatch (raise to simulate a device failure; the measured-demotion
# drill and BENCH_GRAPHRAG's demotion check drive this)
_DEVICE_FAIL_HOOK = None


def _metrics():
    from wukong_tpu.obs.metrics import get_registry

    reg = get_registry()
    return (
        reg.histogram("wukong_vector_scan_us",
                      "k-NN scan latency (usec) by executed route",
                      labels=("route",)),
        reg.counter("wukong_vector_scan_slices_total",
                    "Wide k-NN scan slice-range dispatches"),
    )


_M_SCAN_US, _M_SLICES = _metrics()


def pad_pow2(n: int, floor: int = PAD_FLOOR) -> int:
    """Smallest power of two >= max(n, floor) — the device path's
    capacity class, so the jitted scan compiles a bounded set of shape
    variants instead of one per store size."""
    c = max(int(n), int(floor), 1)
    return 1 << (c - 1).bit_length()


def scores(base, queries, metric: str, xp=np):
    """``[B, N]`` similarity scores of ``queries [B, d]`` against
    ``base [N, d]`` — THE kernel seam (higher = nearer for every
    metric). Pure xp ops: traces under jit unchanged."""
    if metric == "dot":
        return queries @ base.T
    if metric == "cosine":
        qn = queries / xp.clip(
            xp.linalg.norm(queries, axis=1, keepdims=True), 1e-12, None)
        bn = base / xp.clip(
            xp.linalg.norm(base, axis=1, keepdims=True), 1e-12, None)
        return qn @ bn.T
    if metric == "l2":
        qq = xp.sum(queries * queries, axis=1, keepdims=True)  # [B, 1]
        bb = xp.sum(base * base, axis=1)  # [N]
        return -(qq - 2.0 * (queries @ base.T) + bb[None, :])
    raise WukongError(ErrorCode.UNSUPPORTED_SHAPE,
                      f"knn_metric must be one of {KNN_METRICS}, "
                      f"got {metric!r}")


def topk_host(vids, vecs, alive, anchor, k: int, metric: str):
    """NumPy brute-force top-k over live slots; the oracle every other
    route must match. Ties break ``(score desc, vid asc)``."""
    anchor = np.asarray(anchor, dtype=np.float32)
    if len(vids) == 0 or k <= 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.float32))
    s = np.asarray(scores(vecs, anchor[None, :], metric, np)[0],
                   dtype=np.float32)
    s = np.where(alive, s, -np.inf)
    order = np.lexsort((vids, -s))
    order = order[np.isfinite(s[order])]
    sel = order[:int(k)]
    return vids[sel].copy(), s[sel].copy()


# jitted scan variants keyed on (metric, k); candidate shapes are
# handled by pad_pow2 bucketing, so the cache stays small
_SCAN_JIT_CACHE: dict = {}


def _jit_scan(metric: str, k: int):
    fn = _SCAN_JIT_CACHE.get((metric, k))
    if fn is None:
        import jax
        import jax.numpy as jnp

        def wk_knn_scan(base, mask, anchor):
            s = scores(base, anchor[None, :], metric, jnp)[0]
            s = jnp.where(mask, s, -jnp.inf)
            return jax.lax.top_k(s, k)

        fn = _SCAN_JIT_CACHE[(metric, k)] = jax.jit(wk_knn_scan)
    return fn


def topk_device(vids, vecs, alive, anchor, k: int, metric: str):
    """The jitted XLA scan: pad candidates to a capacity class, mask
    dead/padding slots, ``lax.top_k``, then re-order the k winners on
    the host by the canonical ``(score desc, vid asc)`` tie policy."""
    if _DEVICE_FAIL_HOOK is not None:
        _DEVICE_FAIL_HOOK()
    import jax.numpy as jnp

    anchor = np.asarray(anchor, dtype=np.float32)
    n = int(len(vids))
    if n == 0 or k <= 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.float32))
    cap = pad_pow2(n)
    base = np.zeros((cap, vecs.shape[1]), dtype=np.float32)
    base[:n] = vecs
    mask = np.zeros(cap, dtype=bool)
    mask[:n] = alive
    kk = int(min(k, cap))
    t0 = get_usec()
    top_s, top_i = _jit_scan(metric, kk)(
        jnp.asarray(base), jnp.asarray(mask), jnp.asarray(anchor))
    top_s = np.asarray(top_s, dtype=np.float32)  # blocking D2H sync
    top_i = np.asarray(top_i)
    from wukong_tpu.obs.device import maybe_device_dispatch

    maybe_device_dispatch(
        "knn.scan", template=f"{metric}:k{kk}", live=n, capacity=cap,
        wall_us=get_usec() - t0,
        nbytes=int(base.nbytes) + int(mask.nbytes) + int(anchor.nbytes)
        + 8 * kk)
    ok = np.isfinite(top_s) & (top_i < n)
    sel_v = np.asarray(vids)[top_i[ok]]
    sel_s = top_s[ok]
    order = np.lexsort((sel_v, -sel_s))[:int(k)]
    return sel_v[order].copy(), sel_s[order].copy()


def scan_topk(vstore, anchor, k: int, metric: str, route: str = "host",
              shard: int | None = None):
    """One full-store scan through the route seam. Returns
    ``(top_vids, top_scores, demoted_reason | None)`` — a device-path
    failure degrades to the host kernels with the answer intact and the
    reason latched for the proxy's measured-demotion feedback
    (``JOIN_ROUTES`` posture). Charges the partition's heat accountant
    (one charge per scan, never per row)."""
    vids, vecs, alive, _ver = vstore.snapshot()
    t0 = get_usec()
    demoted = None
    used = "host"
    if route == "device":
        try:
            out = topk_device(vids, vecs, alive, anchor, k, metric)
            used = "device"
        except Exception as e:  # degrade, never fail the query
            demoted = (e.code.name if isinstance(e, WukongError)
                       else type(e).__name__)
            out = topk_host(vids, vecs, alive, anchor, k, metric)
    else:
        out = topk_host(vids, vecs, alive, anchor, k, metric)
    dur = get_usec() - t0
    _M_SCAN_US.labels(route=used).observe(dur)
    if shard is None:
        shard = getattr(vstore, "sid", 0)
    from wukong_tpu.obs.heat import get_heat

    get_heat().charge(int(shard), "vector", rows=int(len(vids)),
                      nbytes=int(vecs.nbytes), dur_us=int(dur))
    return out[0], out[1], demoted


def rank_candidates(vstore, cand_vids, anchor, k: int, metric: str,
                    route: str = "host"):
    """Top-k over an explicit candidate id set (pattern-then-rank: the
    BGP's binding set). Candidates missing from the store or tombstoned
    simply don't rank. Same return contract as :func:`scan_topk`."""
    cand = np.unique(np.asarray(cand_vids, dtype=np.int64))
    vids, vecs, alive, _ver = vstore.snapshot()
    if len(vids) == 0 or cand.size == 0 or k <= 0:
        return (np.empty(0, dtype=np.int64),
                np.empty(0, dtype=np.float32), None)
    slots = np.asarray([vstore.slot_of.get(int(v), -1) for v in cand],
                       dtype=np.int64)
    hit = slots >= 0
    cand, slots = cand[hit], slots[hit]
    sub_vecs = vecs[slots] if len(slots) else vecs[:0]
    sub_alive = alive[slots] if len(slots) else alive[:0]
    t0 = get_usec()
    demoted = None
    used = "host"
    if route == "device":
        try:
            out = topk_device(cand, sub_vecs, sub_alive, anchor, k, metric)
            used = "device"
        except Exception as e:
            demoted = (e.code.name if isinstance(e, WukongError)
                       else type(e).__name__)
            out = topk_host(cand, sub_vecs, sub_alive, anchor, k, metric)
    else:
        out = topk_host(cand, sub_vecs, sub_alive, anchor, k, metric)
    _M_SCAN_US.labels(route=used).observe(get_usec() - t0)
    return out[0], out[1], demoted


def resolve_anchor(vstore, clause) -> np.ndarray:
    """The clause's anchor as a ``[dim]`` float32 vector: a literal
    vector must match the store's fixed ``vector_dim``; a vertex anchor
    must have a live embedding."""
    if clause.anchor_vec is not None:
        vec = np.asarray(clause.anchor_vec, dtype=np.float32).ravel()
        if vstore is not None and len(vec) != vstore.dim:
            raise WukongError(
                ErrorCode.UNSUPPORTED_SHAPE,
                f"knn literal vector has dim {len(vec)}, store has "
                f"{vstore.dim}")
        return vec
    if vstore is None:
        raise WukongError(ErrorCode.UNSUPPORTED_SHAPE,
                          "knn() anchor needs an attached vector store")
    vec = vstore.get(int(clause.anchor_vid))
    if vec is None:
        raise WukongError(
            ErrorCode.VERTEX_INVALID,
            f"knn() anchor vertex {clause.anchor_vid} has no live "
            "embedding")
    return np.asarray(vec, dtype=np.float32)


def classify_knn_mode(q) -> str:
    """The composition direction (EXPLAIN shows it):

    - ``scan`` — no graph patterns: a pure ranked scan;
    - ``rank_then_pattern`` — the chain STARTS at the knn variable:
      the scan seeds the chain (a seeded walk);
    - ``pattern_then_rank`` — anything else: the BGP runs first and
      the scan ranks its binding set.

    The parser stamps the direction from the TEXTUAL pattern order
    (``KNNClause.mode``) — preferred here, because a planner reorder
    after parse must not flip the semantics. The shape-derived fallback
    covers hand-built queries."""
    mode = getattr(q.knn, "mode", "")
    if mode:
        return mode
    pg = q.pattern_group
    if not pg.patterns:
        return "scan"
    if pg.patterns[0].subject == q.knn.var:
        return "rank_then_pattern"
    return "pattern_then_rank"


# ---------------------------------------------------------------------------
# wide-scan slice split (join/dist.py heavy-lane shape)
# ---------------------------------------------------------------------------


class _KnnSlice:
    """One slot-range slice of a wide scan: a fire-and-forget heavy-lane
    pool item claimable exactly once; engine-thread death reaches
    :meth:`fail_all` via the scheduler's death handler, so the gather
    barrier always wakes."""

    lane = "heavy"

    __slots__ = ("vids", "vecs", "alive", "anchor", "k", "metric",
                 "route", "result", "demoted", "event", "error",
                 "_claim_lock", "_claimed")

    def __init__(self, vids, vecs, alive, anchor, k, metric, route):
        self.vids = vids
        self.vecs = vecs
        self.alive = alive
        self.anchor = anchor
        self.k = k
        self.metric = metric
        self.route = route
        self.result = None
        self.demoted: str | None = None
        self.event = threading.Event()
        self.error: BaseException | None = None
        self._claim_lock = make_lock("vector.slice")
        self._claimed = False  # guarded by: _claim_lock

    def claim(self) -> bool:
        with self._claim_lock:
            if self._claimed:
                return False
            self._claimed = True
            return True

    def run(self, engine=None) -> None:
        if not self.claim():
            return
        self._execute()

    def _execute(self) -> None:
        ok = False
        try:
            if self.route == "device":
                try:
                    self.result = topk_device(self.vids, self.vecs,
                                              self.alive, self.anchor,
                                              self.k, self.metric)
                except Exception as e:
                    # per-slice fallback: this slice degrades to host,
                    # the others keep their route
                    self.demoted = (e.code.name if isinstance(e, WukongError)
                                    else type(e).__name__)
                    self.result = topk_host(self.vids, self.vecs,
                                            self.alive, self.anchor,
                                            self.k, self.metric)
            else:
                self.result = topk_host(self.vids, self.vecs, self.alive,
                                        self.anchor, self.k, self.metric)
            ok = True
        except BaseException as e:
            self.error = e
        finally:
            if not ok and self.error is None:
                self.error = RuntimeError("knn slice aborted")
            self.event.set()

    def retry_inline(self) -> None:
        self.error = None
        self._execute()

    def fail_all(self, exc: BaseException) -> None:
        """Scheduler death-handler / dead-pool contract."""
        if not self.event.is_set():
            self.error = exc
            self.event.set()


def sliced_topk(pool, vstore, anchor, k: int, metric: str,
                route: str, parts: int):
    """Wide-scan fan-out: split the slot range into ``parts`` slices
    across the engine pool's heavy lane, each computing its local
    top-k; the gather thread works slice 0 itself, claims stragglers
    inline, retries a failed slice once, and merges by the canonical
    ``(score desc, vid asc)`` order — exactly the single-scan answer,
    since per-element scores are row-independent. Returns
    ``(top_vids, top_scores, demoted_reason | None)``."""
    from wukong_tpu.runtime.batcher import (
        HEAVY_GATHER_WAIT_S,
        SLICE_CLAIM_GRACE_S,
    )

    vids, vecs, alive, _ver = vstore.snapshot()
    n = int(len(vids))
    parts = max(min(int(parts), max(n, 1)), 1)
    if parts <= 1 or pool is None:
        return scan_topk(vstore, anchor, k, metric, route=route)
    t0 = get_usec()
    bounds = np.linspace(0, n, parts + 1).astype(np.int64)
    slices = [
        _KnnSlice(vids[bounds[i]:bounds[i + 1]],
                  vecs[bounds[i]:bounds[i + 1]],
                  alive[bounds[i]:bounds[i + 1]],
                  anchor, k, metric, route)
        for i in range(parts)]
    _M_SLICES.inc(len(slices))
    for s in slices[1:]:
        try:
            pool.submit(s, lane="heavy")
        except Exception:
            pass  # claimed and run inline below
    slices[0].run(None)  # the gather thread works its own share first
    for s in slices[1:]:
        if not s.event.wait(SLICE_CLAIM_GRACE_S):
            if s.claim():  # not started yet: run the straggler inline
                s._execute()
            elif not s.event.wait(HEAVY_GATHER_WAIT_S):
                raise WukongError(
                    ErrorCode.UNKNOWN_PATTERN,
                    "knn gather barrier timed out on a claimed slice")
    demoted = None
    for s in slices:
        if s.error is not None:
            # one inline retry on the gather thread; a second failure
            # surfaces to the caller (the engine degrades the scan to
            # its own single-threaded host path)
            s.retry_inline()
            if s.error is not None:
                raise s.error
        if s.demoted is not None:
            demoted = s.demoted
    all_v = np.concatenate([s.result[0] for s in slices])
    all_s = np.concatenate([s.result[1] for s in slices])
    order = np.lexsort((all_v, -all_s))[:int(k)]
    dur = get_usec() - t0
    _M_SCAN_US.labels(
        route="device" if route == "device" and demoted is None
        else "host").observe(dur)
    from wukong_tpu.obs.heat import get_heat

    get_heat().charge(int(getattr(vstore, "sid", 0)), "vector",
                      rows=n, nbytes=int(vecs.nbytes), dur_us=int(dur))
    return all_v[order].copy(), all_s[order].copy(), demoted
