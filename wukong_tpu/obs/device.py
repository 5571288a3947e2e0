"""Device-cost observatory: dispatch accounting, compile ledger, residency.

ROADMAP item 8 ("compile the template, not the step") wants whole-plan
fused XLA programs routed by measured feedback — but nothing measures the
device side today: jit dispatch wall time, compile cost, pad_pow2 padding
waste, and device-resident table bytes are all invisible. This module is
the compiled-template control plane's decision substrate, built one PR
ahead of the actuator (the PR 7→8 / 11→12 / 13→14 move).

Three planes, all observe-only (no dispatch is ever re-routed here):

- :class:`DispatchLedger` — charged at every jitted call site's sync
  point through the single :func:`maybe_device_dispatch` seam: per
  (site, template, capacity class) dispatch counts, device wall time,
  live rows vs padded capacity (padding efficiency = the pad_pow2
  discipline's measured waste), and bytes moved device<->host.
- :class:`CompileLedger` — cold-vs-warm dispatch split by first-call
  detection per (site, template, capacity) jit variant, per-site
  shape-variant counts, and a **variant-storm sentinel**: a site minting
  more than ``device_variant_limit`` variants inside one
  ``device_storm_cooldown_s`` window journals a ``device.variant_storm``
  ClusterEvent and force-dumps the trace ring via FlightRecorder — the
  capacity-class discipline finally gets a regression tripwire. The
  persistent XLA compile cache (utils/compilecache.py) reports its
  availability through :func:`note_compile_cache`.
- :class:`ResidencyLedger` — device-resident bytes per kind
  (``join_table`` = JoinTableCache device tables, ``segment`` /
  ``index`` = engine/device_store.py stagings, ``knn`` = vector scan
  blocks) against the ``device_budget_mb`` ceiling (HBM_BUDGET.md's
  numbers as live telemetry), with fills/evictions/invalidations
  counted per store-version edge.

``DEVICE_INPUTS`` literally maps every signal item 8's route chooser may
read to the registered metric that backs it (the ``PLACEMENT_INPUTS`` /
``ADMISSION_INPUTS`` / ``CACHE_INPUTS`` contract; the ``device-telemetry``
analysis gate keeps the map honest and every jitted call site seamed).
Surfaced as ``GET /device`` + ``/device.json`` on obs/httpd.py, the
``device`` console verb, a Monitor ``Device[...]`` rolling-report line,
and tsdb trend windows. Everything gates on ``enable_device_obs``
(default ON; the hot serving path carries no device dispatch, so the
hook cost is one knob check).
"""

from __future__ import annotations

import time
from collections import deque

from wukong_tpu.analysis.lockdep import declare_leaf, make_lock
from wukong_tpu.config import Global
from wukong_tpu.obs.metrics import get_registry
from wukong_tpu.utils.timer import get_usec

#: every signal ROADMAP item 8's compiled-template route chooser may
#: read, mapped to the registered metric that backs it (scrape-able
#: truth for each number the actuator will consume). The
#: device-telemetry analysis gate verifies each named metric is actually
#: registered in code, and that every tsdb trend read in this module
#: stays inside this map.
DEVICE_INPUTS = {
    "dispatches": "wukong_device_dispatch_total",
    "dispatch_wall": "wukong_device_dispatch_us",
    "padding_efficiency": "wukong_device_padding_efficiency",
    "padded_rows": "wukong_device_rows_total",
    "bytes_moved": "wukong_device_bytes_moved_total",
    "variants": "wukong_device_variants",
    "variant_storms": "wukong_device_variant_storms_total",
    "resident_bytes": "wukong_device_resident_bytes",
    "residency_events": "wukong_device_residency_total",
    "residency_high_water": "wukong_device_resident_high_water_bytes",
    "compile_cache": "wukong_device_compile_cache_total",
    "feedback": "wukong_device_feedback_total",
}

#: device-resident byte kinds the residency ledger totals (the stores
#: HBM_BUDGET.md budgets): join/wcoj.py JoinTableCache device tables,
#: engine/device_store.py segment + index-list stagings, vector/knn.py
#: padded scan blocks, and engine/template_compile.py's cached
#: whole-plan compiled programs with their staged operand estimates
RESIDENT_KINDS = ("join_table", "segment", "index", "knn", "template")

#: residency edge events counted per (kind, event)
RESIDENCY_EVENTS = ("fill", "evict", "invalidate")

#: bounded-cardinality catch-all template label (the reuse-observatory
#: posture: unbounded template shapes must not mint unbounded series)
OVERFLOW_TEMPLATE = "__overflow__"
_TEMPLATES_CAP = 512

#: jit-minting modules under engine//join//vector that legitimately do
#: NOT call the dispatch seam themselves, each with the justification
#: the device-telemetry gate displays. The rule: a kernel DEFINITION
#: module may skip the seam only when every site that INVOKES its
#: kernels charges it — the charge belongs at the sync point (where
#: wall time and live-row counts exist), never inside traced code.
DEVICE_DISPATCH_ALLOWLIST = {
    "engine/tpu_kernels.py": (
        "kernel definitions only; every dispatch syncs and charges in "
        "engine/tpu.py (_charge_chain) or engine/tpu_merge.py "
        "(_charge_merge)"),
    "engine/tpu_stream.py": (
        "streaming chain kernel definition; dispatched and charged at "
        "the batch-chain sync seam in engine/tpu.py"),
    "join/kernels.py": (
        "jit minters (jit_kernels/jit_level_ranges/jit_level_probe/"
        "jit_seed_masks); "
        "invocation sites join/wcoj.py and stream/continuous.py charge "
        "the seam at their blocking device_get"),
}

# every lock here guards dict/deque/int updates only — innermost by
# construction, like reuse.ledger/heat.shard (charges fire from engine
# sync points and store staging paths, outside every other tracked
# lock; the device.variant_storm event + recorder dump are emitted
# AFTER the compile lock releases, since events.ring is itself a leaf)
declare_leaf("device.dispatch")
declare_leaf("device.compile")
declare_leaf("device.residency")

_M_DISPATCH = get_registry().counter(
    "wukong_device_dispatch_total",
    "Jitted device dispatches charged at the sync point, by site",
    labels=("site",))
_M_DISPATCH_US = get_registry().histogram(
    "wukong_device_dispatch_us",
    "Device dispatch wall time (usec) by site and cold/warm temperature "
    "(cold = first call of a jit variant, compile included)",
    labels=("site", "temp"))
_M_ROWS = get_registry().counter(
    "wukong_device_rows_total",
    "Rows through jitted dispatches by site: live vs padded capacity "
    "(live/padded = the padding efficiency the pad_pow2 classes cost)",
    labels=("site", "kind"))
_M_BYTES = get_registry().counter(
    "wukong_device_bytes_moved_total",
    "Bytes moved across the host<->device boundary per dispatch site",
    labels=("site",))
_M_STORMS = get_registry().counter(
    "wukong_device_variant_storms_total",
    "Variant-storm sentinel trips (a site minted more than "
    "device_variant_limit jit variants in one window)",
    labels=("site",))
_M_RESIDENCY = get_registry().counter(
    "wukong_device_residency_total",
    "Device-residency edges by kind and event (fill/evict/invalidate)",
    labels=("kind", "event"))
_M_COMPILE_CACHE = get_registry().counter(
    "wukong_device_compile_cache_total",
    "Persistent XLA compile-cache outcomes by site (utils/"
    "compilecache.py boot setup; engine/template_compile.py "
    "whole-plan program cache hits/misses/evictions)",
    labels=("outcome", "site"))
_M_FEEDBACK = get_registry().counter(
    "wukong_device_feedback_total",
    "Measured-feedback route decisions charged through the observatory "
    "(proxy demotions + heavy-split choices, correlated with device cost)",
    labels=("kind", "reason"))


def _budget_bytes() -> int:
    return max(int(Global.device_budget_mb), 1) * (1 << 20)


# ---------------------------------------------------------------------------
# the dispatch ledger
# ---------------------------------------------------------------------------

class _SiteStat:
    """One (site, template, capacity) dispatch record (mutated under the
    dispatch lock)."""

    __slots__ = ("count", "live", "padded", "wall_us", "nbytes", "cold")

    def __init__(self):
        self.count = 0
        self.live = 0
        self.padded = 0
        self.wall_us = 0
        self.nbytes = 0
        self.cold = 0


class DispatchLedger:
    """Per (site, template, capacity class) dispatch accounting: counts,
    device wall time, live rows vs padded capacity, bytes moved."""

    def __init__(self, max_keys: int | None = None):
        self._max = max_keys or _TEMPLATES_CAP
        self._lock = make_lock("device.dispatch")
        # (site, template, capacity) -> _SiteStat
        self._stats: dict[tuple, _SiteStat] = {}  # guarded by: _lock

    def charge(self, site: str, template: str, capacity: int, live: int,
               wall_us: int, nbytes: int, cold: bool, count: int) -> str:
        """Account ``count`` dispatches; returns the bounded template
        label actually charged (``__overflow__`` past the key cap)."""
        key = (site, template, int(capacity))
        with self._lock:
            st = self._stats.get(key)
            if st is None:
                if len(self._stats) >= self._max:
                    key = (site, OVERFLOW_TEMPLATE, int(capacity))
                    st = self._stats.get(key)
                if st is None:
                    st = self._stats[key] = _SiteStat()
            st.count += count
            st.live += int(live)
            st.padded += int(capacity) * count
            st.wall_us += int(wall_us)
            st.nbytes += int(nbytes)
            if cold:
                st.cold += 1
        return key[1]

    # ------------------------------------------------------------------
    def padding_efficiency(self, site: str | None = None) -> float | None:
        """live / padded over every charged dispatch (optionally one
        site's) — None before any dispatch carried capacity."""
        with self._lock:
            live = padded = 0
            for (s, _t, _c), st in self._stats.items():
                if site is not None and s != site:
                    continue
                live += st.live
                padded += st.padded
        return (live / padded) if padded else None

    def site_efficiencies(self) -> dict[str, float]:
        """{site: live/padded} for the callback gauge (sites with no
        padded rows yet are absent, not 0 — absent series drop)."""
        agg: dict[str, list] = {}
        with self._lock:
            for (s, _t, _c), st in self._stats.items():
                a = agg.setdefault(s, [0, 0])
                a[0] += st.live
                a[1] += st.padded
        return {s: v[0] / v[1] for s, v in agg.items() if v[1]}

    def dispatch_counts(self, site: str | None = None) -> dict:
        """{count, cold, warm, wall_us} totals (optionally one site's) —
        the route chooser's dispatch-amortization read."""
        with self._lock:
            count = cold = wall = 0
            for (s, _t, _c), st in self._stats.items():
                if site is not None and s != site:
                    continue
                count += st.count
                cold += st.cold
                wall += st.wall_us
        return {"count": count, "cold": cold, "warm": count - cold,
                "wall_us": wall}

    def report(self, k: int | None = None) -> list[dict]:
        """Per (site, template, capacity) rows ranked by wall time. ONE
        lock acquisition snapshots everything."""
        with self._lock:
            snap = [((s, t, c), st.count, st.live, st.padded, st.wall_us,
                     st.nbytes, st.cold)
                    for (s, t, c), st in self._stats.items()]
        rows = []
        for (s, t, c), count, live, padded, wall, nbytes, cold in snap:
            rows.append({
                "site": s, "template": t, "capacity": c,
                "dispatches": count,
                "live_rows": live, "padded_rows": padded,
                "padding_efficiency": (round(live / padded, 4)
                                       if padded else None),
                "wall_us": wall, "bytes_moved": nbytes,
                "cold": cold, "warm": count - cold,
            })
        rows.sort(key=lambda r: (-r["wall_us"], r["site"], r["capacity"]))
        kk = k if k is not None else max(int(Global.top_k), 1)
        return rows[:kk]

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()


# ---------------------------------------------------------------------------
# the compile ledger + variant-storm sentinel
# ---------------------------------------------------------------------------

class _SiteVariants:
    """One site's minted jit variants (mutated under the compile lock)."""

    __slots__ = ("variants", "mints_us", "last_trip_us")

    def __init__(self):
        self.variants: set = set()  # caller holds: device.compile (the compile lock)
        self.mints_us: deque = deque(maxlen=4096)  # caller holds: device.compile (the compile lock)
        self.last_trip_us = None  # the storm cooldown cursor: never yet


class CompileLedger:
    """First-call (cold) detection per (site, template, capacity) jit
    variant, per-site variant counts, and the variant-storm sentinel."""

    def __init__(self, limit: int | None = None,
                 cooldown_s: float | None = None):
        self._limit = limit
        self._cooldown_s = cooldown_s
        self._lock = make_lock("device.compile")
        self._sites: dict[str, _SiteVariants] = {}  # guarded by: _lock

    def _lim(self) -> int:
        return self._limit or max(int(Global.device_variant_limit), 1)

    def _cool_us(self) -> int:
        s = (self._cooldown_s if self._cooldown_s is not None
             else float(Global.device_storm_cooldown_s))
        return int(max(s, 0.001) * 1e6)

    def note(self, site: str, template: str, capacity: int) -> tuple:
        """Record one dispatch of a (template, capacity) variant at
        ``site``. Returns ``(cold, storm_minted | None)`` — cold is True
        on the variant's first call; storm_minted is the in-window mint
        count when the sentinel just tripped (the caller journals the
        event OUTSIDE this lock)."""
        now = get_usec()
        cool = self._cool_us()
        storm = None
        with self._lock:
            sv = self._sites.get(site)
            if sv is None:
                sv = self._sites[site] = _SiteVariants()
            cold = (template, int(capacity)) not in sv.variants
            if cold:
                sv.variants.add((template, int(capacity)))
                sv.mints_us.append(now)
                while sv.mints_us and now - sv.mints_us[0] > cool:
                    sv.mints_us.popleft()
                if (len(sv.mints_us) > self._lim()
                        and (sv.last_trip_us is None
                             or now - sv.last_trip_us >= cool)):
                    sv.last_trip_us = now
                    storm = len(sv.mints_us)
        return cold, storm

    def variant_counts(self) -> dict[str, int]:
        with self._lock:
            return {s: len(sv.variants) for s, sv in self._sites.items()}

    def reset(self) -> None:
        with self._lock:
            self._sites.clear()


# ---------------------------------------------------------------------------
# the residency ledger
# ---------------------------------------------------------------------------

class ResidencyLedger:
    """Device-resident bytes per kind against the ``device_budget_mb``
    ceiling, with fill/evict/invalidate edges counted per store-version
    edge (an invalidation clearing N entries is ONE edge)."""

    def __init__(self):
        self._lock = make_lock("device.residency")
        self._bytes: dict[str, int] = {}  # guarded by: _lock
        self._high_water = 0  # guarded by: _lock
        self._versions: dict[str, int] = {}  # guarded by: _lock

    def fill(self, kind: str, nbytes: int) -> None:
        with self._lock:
            self._bytes[kind] = self._bytes.get(kind, 0) + int(nbytes)
            total = sum(self._bytes.values())
            if total > self._high_water:
                self._high_water = total
        _M_RESIDENCY.labels(kind=kind, event="fill").inc()

    def evict(self, kind: str, nbytes: int) -> None:
        with self._lock:
            self._bytes[kind] = max(
                self._bytes.get(kind, 0) - int(nbytes), 0)
        _M_RESIDENCY.labels(kind=kind, event="evict").inc()

    def invalidate(self, kind: str, nbytes: int | None = None,
                   version: int | None = None) -> bool:
        """One store-version edge dropped ``nbytes`` (None = everything
        of ``kind``). Returns False when the same version edge was
        already counted for this kind — a store bump that clears three
        caches is still ONE invalidation edge per kind."""
        with self._lock:
            if version is not None:
                if self._versions.get(kind) == int(version):
                    # the byte drop still applies; the edge was counted
                    if nbytes is None:
                        self._bytes[kind] = 0
                    else:
                        self._bytes[kind] = max(
                            self._bytes.get(kind, 0) - int(nbytes), 0)
                    return False
                self._versions[kind] = int(version)
            if nbytes is None:
                self._bytes[kind] = 0
            else:
                self._bytes[kind] = max(
                    self._bytes.get(kind, 0) - int(nbytes), 0)
        _M_RESIDENCY.labels(kind=kind, event="invalidate").inc()
        return True

    # ------------------------------------------------------------------
    def totals(self) -> dict[str, int]:
        with self._lock:
            return dict(self._bytes)

    def total_bytes(self) -> int:
        with self._lock:
            return sum(self._bytes.values())

    def high_water(self) -> int:
        with self._lock:
            return self._high_water

    def stats(self) -> dict:
        with self._lock:
            total = sum(self._bytes.values())
            return {"by_kind": dict(self._bytes), "total_bytes": total,
                    "high_water_bytes": self._high_water,
                    "budget_bytes": _budget_bytes(),
                    "over_budget": total > _budget_bytes()}

    def reset(self) -> None:
        with self._lock:
            self._bytes.clear()
            self._versions.clear()
            self._high_water = 0


# ---------------------------------------------------------------------------
# the observatory facade
# ---------------------------------------------------------------------------

class DeviceObservatory:
    """Dispatch + compile + residency ledgers behind the single
    :func:`maybe_device_dispatch` / :func:`maybe_device_resident`
    seams."""

    def __init__(self, variant_limit: int | None = None,
                 cooldown_s: float | None = None):
        self.dispatch_ledger = DispatchLedger()
        self.compile_ledger = CompileLedger(limit=variant_limit,
                                            cooldown_s=cooldown_s)
        self.residency = ResidencyLedger()

    # ------------------------------------------------------------------
    def dispatch(self, site: str, template: str = "", live: int = 0,
                 capacity: int = 0, wall_us: int = 0, nbytes: int = 0,
                 count: int = 1) -> dict:
        """Charge one sync point: ``count`` dispatches at ``site`` of the
        ``(template, capacity)`` jit variant that carried ``live`` rows
        against ``capacity``-row padded tensors and took ``wall_us`` on
        the device. Returns the per-step record EXPLAIN ANALYZE's device
        table consumes. Metrics and the storm journal run OUTSIDE the
        ledger leaves (events.ring is itself a leaf)."""
        cold, storm = self.compile_ledger.note(site, template, capacity)
        tmpl = self.dispatch_ledger.charge(site, template, capacity, live,
                                           wall_us, nbytes, cold, count)
        temp = "cold" if cold else "warm"
        _M_DISPATCH.labels(site=site).inc(count)
        _M_DISPATCH_US.labels(site=site, temp=temp).observe(wall_us)
        if capacity:
            _M_ROWS.labels(site=site, kind="live").inc(live)
            _M_ROWS.labels(site=site, kind="padded").inc(capacity * count)
        if nbytes:
            _M_BYTES.labels(site=site).inc(nbytes)
        if storm is not None:
            self._journal_storm(site, storm)
        return {"site": site, "template": tmpl, "capacity": int(capacity),
                "live": int(live), "dispatches": int(count),
                "wall_us": int(wall_us), "temp": temp,
                "padding_efficiency": (round(live / (capacity * count), 4)
                                       if capacity and count else None)}

    def _journal_storm(self, site: str, minted: int) -> None:
        """Journal the sentinel trip and force-dump the trace ring (the
        LatencyAttributor regression posture: event first, dump carries
        its id)."""
        _M_STORMS.labels(site=site).inc()
        from wukong_tpu.obs.events import emit_event
        from wukong_tpu.obs.recorder import get_recorder

        eid = emit_event("device.variant_storm", site=site,
                         minted_in_window=minted,
                         limit=max(int(Global.device_variant_limit), 1),
                         variants_total=self.compile_ledger.
                         variant_counts().get(site, 0))
        rec = get_recorder()
        recent = rec.last(1)
        if recent:
            # the storm fires mid-dispatch, before its own query's trace
            # completes — the newest ring entry is the closest witness
            rec.dump(recent[-1], "DEVICE_VARIANT_STORM", event_id=eid)

    # ------------------------------------------------------------------
    def report(self, k: int | None = None) -> dict:
        counts = self.dispatch_ledger.dispatch_counts()
        return {
            "enabled": bool(Global.enable_device_obs),
            "dispatches": counts,
            "padding_efficiency": self.dispatch_ledger.padding_efficiency(),
            "by_site_efficiency": {
                s: round(v, 4) for s, v in
                sorted(self.dispatch_ledger.site_efficiencies().items())},
            "variants": self.compile_ledger.variant_counts(),
            "ranked": self.dispatch_ledger.report(k),
            "residency": self.residency.stats(),
            "inputs": dict(DEVICE_INPUTS),
        }

    def reset(self) -> None:
        self.dispatch_ledger.reset()
        self.compile_ledger.reset()
        self.residency.reset()


# process-wide observatory (the engine seams, /device, and Monitor share it)
_observatory = DeviceObservatory()

get_registry().gauge(
    "wukong_device_padding_efficiency",
    "Live rows / padded capacity over charged dispatches, by site "
    "(1.0 = zero padding waste)",
    labels=("site",),
).set_function(
    lambda: {(s,): v
             for s, v in _observatory.dispatch_ledger
             .site_efficiencies().items()})
get_registry().gauge(
    "wukong_device_variants",
    "Distinct (template, capacity) jit variants minted per dispatch site",
    labels=("site",),
).set_function(
    lambda: {(s,): float(n)
             for s, n in _observatory.compile_ledger
             .variant_counts().items()})
get_registry().gauge(
    "wukong_device_resident_bytes",
    "Device-resident bytes by kind (join tables / segment stagings / "
    "index lists / knn blocks)",
    labels=("kind",),
).set_function(
    lambda: {(k,): float(v)
             for k, v in _observatory.residency.totals().items()})
get_registry().gauge(
    "wukong_device_resident_high_water_bytes",
    "High-water total of device-resident bytes since process start "
    "(compare against device_budget_mb)",
).set_function(lambda: float(_observatory.residency.high_water()))


def get_device_obs() -> DeviceObservatory:
    return _observatory


def maybe_device_dispatch(site: str, template: str = "", live: int = 0,
                          capacity: int = 0, wall_us: int = 0,
                          nbytes: int = 0, count: int = 1) -> dict | None:
    """THE jitted-dispatch instrumentation seam (device-telemetry gate
    contract: every jax.jit call site in engine/join/vector charges here
    or justifies itself in DEVICE_DISPATCH_ALLOWLIST). One knob check
    when the observatory is off. Returns the per-step record (None when
    off) — call sites append it to ``q.device_steps`` for EXPLAIN
    ANALYZE's device table."""
    if not Global.enable_device_obs:
        return None
    return _observatory.dispatch(site, template=template, live=live,
                                 capacity=capacity, wall_us=wall_us,
                                 nbytes=nbytes, count=count)


def maybe_device_resident(event: str, kind: str, nbytes: int | None = None,
                          version: int | None = None) -> None:
    """THE residency seam: stores charge ``fill`` / ``evict`` /
    ``invalidate`` edges with the nbytes they staged or dropped. One
    knob check when the observatory is off."""
    if not Global.enable_device_obs:
        return
    if event == "fill":
        _observatory.residency.fill(kind, int(nbytes or 0))
    elif event == "evict":
        _observatory.residency.evict(kind, int(nbytes or 0))
    else:
        _observatory.residency.invalidate(kind, nbytes, version=version)


def note_feedback(kind: str, reason: str) -> None:
    """The measured-feedback records (`_record_route_feedback`, the knn
    demotion latch, the heavy-split decision) charge their decisions
    here so item 8's chooser can correlate route demotions with the
    device cost that motivated them — the decision logic itself stays in
    runtime/proxy.py untouched."""
    if not Global.enable_device_obs:
        return
    _M_FEEDBACK.labels(kind=kind, reason=reason).inc()


def note_compile_cache(outcome: str, site: str = "boot") -> None:
    """Compile-cache outcomes by site: utils/compilecache.py reports
    persistent-cache setup (``available``, site
    ``boot``) and engine/template_compile.py charges its whole-plan
    program cache (``hit`` / ``miss`` / ``evict``, site ``template``)
    — a storm of whole-plan variants is visible to the same counter
    the compile ledger's amortization claim reads."""
    _M_COMPILE_CACHE.labels(outcome=outcome, site=site).inc()


def read_device_input(signal: str, site: str | None = None):
    """Item 8's ONLY read path into the observatory: every number the
    compiled-template route chooser consumes is read here by its
    ``DEVICE_INPUTS`` name, so the map stays the literal truth about
    what the actuator depends on."""
    if signal not in DEVICE_INPUTS:
        raise KeyError(f"{signal!r} is not a declared device input "
                       f"(see {sorted(DEVICE_INPUTS)})")
    if signal == "padding_efficiency":
        return _observatory.dispatch_ledger.padding_efficiency(site)
    if signal == "dispatches":
        return _observatory.dispatch_ledger.dispatch_counts(site)
    if signal == "variants":
        counts = _observatory.compile_ledger.variant_counts()
        return counts.get(site) if site is not None else counts
    if signal == "resident_bytes":
        return _observatory.residency.totals()
    if signal == "residency_high_water":
        return _observatory.residency.high_water()
    raise KeyError(f"device input {signal!r} has no live read path here "
                   "— scrape its backing metric "
                   f"{DEVICE_INPUTS[signal]!r} instead")


def device_trend(window_s: float | None = None) -> dict:
    """Dispatch / storm / residency-edge rates over the tsdb trend
    window. Every metric literal read here is declared in DEVICE_INPUTS
    (gate-enforced); reads go through rate_by_label, not rate(), for
    the cold-start-window reason reuse_trend documents."""
    from wukong_tpu.obs.tsdb import get_tsdb

    ts = get_tsdb()
    by_site = ts.rate_by_label("wukong_device_dispatch_total", "site",
                               window_s)
    if not by_site:
        return {}
    out = {"dispatches_per_s": round(sum(by_site.values()), 2)}
    storms = ts.rate_by_label("wukong_device_variant_storms_total",
                              "site", window_s)
    if storms:
        out["storms_per_s"] = round(sum(storms.values()), 3)
    edges = ts.rate_by_label("wukong_device_residency_total", "kind",
                             window_s)
    if edges:
        out["residency_edges_per_s"] = round(sum(edges.values()), 2)
    return out


# ---------------------------------------------------------------------------
# the /device report (endpoint + console verb + Monitor line)
# ---------------------------------------------------------------------------

def render_device(k: int | None = None) -> tuple[str, dict]:
    """(plain-text table, JSON dict) for the /device endpoint and the
    ``device`` console verb: dispatch totals + padding efficiency on
    top, the per-(site, template, capacity) ranking under it, variants
    and the residency ledger against the budget below."""
    rep = _observatory.report(k)
    trend = device_trend()
    js = {**rep, "trend": trend,
          "knobs": {"device_budget_mb": int(Global.device_budget_mb),
                    "device_variant_limit":
                        int(Global.device_variant_limit),
                    "xla_cache_dir": str(Global.xla_cache_dir),
                    "xprof_dir": str(Global.xprof_dir)}}
    d = rep["dispatches"]
    eff = rep["padding_efficiency"]
    res = rep["residency"]

    lines = ["wukong-device  (XLA dispatch / compile / residency "
             "observatory)", ""]
    lines.append(
        f"DISPATCH count {d['count']:,}  cold {d['cold']:,}  "
        f"warm {d['warm']:,}  wall {d['wall_us'] / 1e3:,.1f}ms  "
        f"pad_eff {'-' if eff is None else format(eff, '.1%')}")
    if not rep["enabled"]:
        lines.append("  (enable_device_obs is OFF — nothing is being "
                     "observed)")
    lines.append("")
    lines.append(f"{'site':<18} {'template':<12} {'cap':>9} {'disp':>7} "
                 f"{'eff':>6} {'cold':>5} {'wall_ms':>9} {'moved':>10}")
    for r in rep["ranked"]:
        e = r["padding_efficiency"]
        lines.append(
            f"{r['site']:<18.18} {r['template']:<12.12} "
            f"{r['capacity']:>9,} {r['dispatches']:>7,} "
            f"{'-' if e is None else format(e, '.0%'):>6} "
            f"{r['cold']:>5,} {r['wall_us'] / 1e3:>9,.1f} "
            f"{r['bytes_moved']:>10,}")
    if not rep["ranked"]:
        lines.append("  (no dispatches charged — device routes idle?)")
    lines.append("")
    if rep["variants"]:
        lines.append("VARIANTS  " + "  ".join(
            f"{s}:{n}" for s, n in sorted(rep["variants"].items()))
            + f"  (limit {Global.device_variant_limit}/window)")
    # compiled-template demotion latches (engine/template_compile.py):
    # a failed/losing whole-plan compile is diagnosable from /device
    # without a trace dump. Lazy import — the observatory must render
    # even when the engine package is not loaded.
    try:
        from wukong_tpu.engine.template_compile import demotion_report

        demoted = demotion_report()
    except Exception:
        demoted = {}
    if demoted:
        js["template_demotions"] = dict(demoted)
        lines.append("TEMPLATE  demoted  " + "  ".join(
            f"{t[:16]}:{r}" for t, r in sorted(demoted.items())))
    # which Pallas kernel variant the capability probe selected, or why
    # none (engine/tpu_stream.py) — a dropped kernel must not go unseen
    from wukong_tpu.engine.tpu_stream import stream_report

    kern = stream_report()
    js["kernels"] = [kern]
    lines.append(
        f"KERNEL    {kern['kernel']}  "
        f"{'live ' + kern['variant'] if kern['live'] else 'not live'}"
        + (f"  ({kern['reason'][:120]})" if kern["reason"] else ""))
    lines.append(
        f"RESIDENT  total {res['total_bytes']:,}B  "
        f"high-water {res['high_water_bytes']:,}B  "
        f"budget {res['budget_bytes']:,}B"
        + ("  OVER BUDGET" if res["over_budget"] else ""))
    if res["by_kind"]:
        lines.append("  by kind  " + "  ".join(
            f"{kk}:{v:,}B" for kk, v in sorted(res["by_kind"].items())))
    if trend:
        lines.append("TREND   " + "  ".join(
            f"{k2} {v:,.2f}" for k2, v in sorted(trend.items())))
    return "\n".join(lines) + "\n", js
