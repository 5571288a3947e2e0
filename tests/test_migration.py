"""Elastic data plane (ISSUE 12): the live shard-migration actuator.

Acceptance surface: `MigrationExecutor` drives the advisor's
MigrationPlans through the crash-safe clone -> catch-up -> cutover ->
retire state machine; every query served during a migration is
byte-identical to an unmigrated oracle and `complete=True`; injected
faults (and a kill) at each of clone, catch-up, and cutover either
resume to completion or abort with the donor-side `gstore_digest`
unchanged and ZERO lost mutations — writes issued during every phase
are present after recovery; the `migration_enable` knob off leaves the
serving path and advisor posture exactly at the PR 11 observe-only
behavior; phase transitions journal `shard.migrate.*` events with shard
correlation keys (`/events -K migrate` selects the timeline); in-flight
state rides `/plan`, `/healthz` (degraded-not-dead), and the Monitor's
`Migration[...]` line; and the migration-safety analysis gate holds the
invariants statically. The whole module runs fully lockdep-checked.
"""

import os

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.obs.events import get_journal, render_events
from wukong_tpu.obs.heat import get_heat
from wukong_tpu.obs.metrics import get_registry
from wukong_tpu.obs.placement import (
    MigrationPlan,
    get_advisor,
    get_lineage,
    render_plan,
)
from wukong_tpu.obs.tsdb import get_tsdb
from wukong_tpu.parallel.sharded_store import ShardedDeviceStore
from wukong_tpu.runtime import faults
from wukong_tpu.runtime.emulator import Emulator, _probe_read
from wukong_tpu.runtime.faults import FaultPlan, FaultSpec
from wukong_tpu.runtime.migration import (
    MIGRATION_PHASES,
    MigrationExecutor,
    get_migrator,
    maybe_start_migration,
)
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.store.dynamic import insert_batch_into, insert_triples
from wukong_tpu.store.gstore import build_partition, hash_mod
from wukong_tpu.store.persist import gstore_digest
from wukong_tpu.utils.errors import WukongError
from wukong_tpu.utils.timer import get_usec

pytestmark = pytest.mark.chaos

N_SHARDS = 4
DONOR = 3
RECIPIENT = 2


@pytest.fixture(autouse=True, scope="module")
def _lockdep_checked():
    """The migration suite runs fully lockdep-checked (the chaos-suite
    posture): the cutover/state locks are declared leaves, so any
    acquisition under them — or any cycle through the WAL mutation
    lock — fails the module teardown."""
    from wukong_tpu.analysis import lockdep

    lockdep.install(True)
    yield
    try:
        assert lockdep.cycles() == [], lockdep.cycles()
        assert lockdep.leaf_violations() == [], lockdep.leaf_violations()
    finally:
        lockdep.install(False)


@pytest.fixture(scope="module")
def world():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    return {"g": g, "ss": ss, "triples": triples}


@pytest.fixture(scope="module")
def proxy(world):
    return Proxy(world["g"], world["ss"],
                 CPUEngine(world["g"], world["ss"]))


@pytest.fixture(autouse=True)
def _hygiene(monkeypatch):
    """Knobs at defaults (migration DISARMED — each test arms
    explicitly), every process-wide singleton clean, no fault plan or
    WAL leaking across tests."""
    monkeypatch.setattr(Global, "migration_enable", False)
    monkeypatch.setattr(Global, "migration_rotate_reads", True)
    monkeypatch.setattr(Global, "placement_interval_s", 0)
    monkeypatch.setattr(Global, "wal_dir", "")
    monkeypatch.setattr(Global, "enable_events", True)
    monkeypatch.setattr(Global, "enable_tsdb", True)
    get_migrator().reset()
    get_advisor().reset()
    get_lineage().reset()
    get_journal().clear()
    get_heat().reset()
    get_tsdb().reset()
    faults.clear()
    yield
    faults.clear()
    get_migrator().reset()


class _Mesh:
    devices = np.empty(N_SHARDS, dtype=object)


def _sstore(world):
    stores = [build_partition(world["triples"], i, N_SHARDS)
              for i in range(N_SHARDS)]
    return ShardedDeviceStore(stores, _Mesh(), replication_factor=1)


def _plan(donor=DONOR, recipient=RECIPIENT) -> MigrationPlan:
    return MigrationPlan(
        plan_id="mp-test", t_us=get_usec(), donor_shard=donor,
        recipient_host=recipient, predicted_move_bytes=1 << 20,
        bytes_source="estimate", donor_rate_per_s=4.0,
        mean_rate_per_s=1.0, imbalance_before=2.5, imbalance_after=1.5,
        window_s=60.0, inputs={}, reason="test")


def _edges(k: int, shard: int = DONOR, base: int = 100000) -> np.ndarray:
    """k synthetic edges whose subjects hash onto ``shard``."""
    out = []
    s = base
    while len(out) < k:
        if hash_mod(np.array([s]), N_SHARDS)[0] == shard:
            out.append((s, 17, s))
        s += 1
    return np.asarray(out, dtype=np.int64)


def _fetch(sstore, shard=DONOR):
    return sstore._fetch_shard(shard, _probe_read, "migtest")


def _arm(mig, sstore, monkeypatch, proxy=None):
    monkeypatch.setattr(Global, "migration_enable", True)
    mig.attach(sstore=sstore, owner=proxy)


# ---------------------------------------------------------------------------
# the off-knob posture: PR 11's observe-only behavior, pinned
# ---------------------------------------------------------------------------

def test_disabled_executor_refuses_and_posture_unchanged(world):
    sstore = _sstore(world)
    mig = get_migrator()
    mig.attach(sstore=sstore)
    with pytest.raises(WukongError, match="migration_enable is off"):
        mig.run_plan(_plan())
    # nothing moved, nothing journaled, nothing enrolled: the serving
    # path is exactly the static-hash PR 11 world
    assert sstore.placement == {} and sstore.rotation == {}
    assert get_journal().last(kind="shard.migrate") == []
    assert mig.status()["in_flight"] is False
    # and the boot helper refuses to start the actuator loop
    assert maybe_start_migration(sstore) is None


def test_disabled_advisor_stays_observe_only(world):
    """With the knob off the advisor still emits plans but the store
    stays bit-untouched — `run_hotspot`'s observe-only proof."""
    sstore = _sstore(world)
    fp = [(id(g), gstore_digest(g)) for g in sstore.stores]
    adv = get_advisor()
    adv.attach_store(sstore)
    adv.advise_once()  # whatever it decides, it must only *say* it
    assert [(id(g), gstore_digest(g)) for g in sstore.stores] == fp
    assert sstore.placement == {}


# ---------------------------------------------------------------------------
# the happy path
# ---------------------------------------------------------------------------

def test_full_migration_happy_path(world, monkeypatch):
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    donor_store = sstore.stores[DONOR]
    d0 = gstore_digest(donor_store)
    before = get_registry().counter(
        "wukong_migrations_total",
        labels=("outcome",)).value(outcome="completed")
    job = mig.run_plan(_plan())
    assert job.phase == "done" and job.attempts == 1
    # read path swapped: new primary object, placement notes the host,
    # the donor copy demoted to a read-rotation replica on its old host
    assert sstore.stores[DONOR] is not donor_store
    assert sstore.placement == {DONOR: RECIPIENT}
    assert [h for h, _g in sstore.rotation[DONOR]] == [DONOR]
    # the copy is byte-identical and the donor was never written
    assert gstore_digest(sstore.stores[DONOR]) == d0
    assert gstore_digest(donor_store) == d0
    # post-move lineage observed immediately at cutover
    rec = get_lineage().report()[DONOR]
    assert rec["primary_host"] == RECIPIENT
    assert rec["rotation_hosts"] == [DONOR]
    # completion metrics
    reg = get_registry()
    assert reg.counter("wukong_migrations_total", labels=("outcome",)
                       ).value(outcome="completed") == before + 1
    assert job.bytes_moved > 0
    # every phase journaled, shard-correlated, cross-linked from the job
    kinds = [e.kind for e in get_journal().last(kind="shard.migrate",
                                                shard=DONOR)]
    assert kinds == ["shard.migrate.start", "shard.migrate.catchup",
                     "shard.migrate.cutover", "shard.migrate.retire"]
    assert len(job.event_ids) == 4
    assert all(get_journal().find(ev) is not None for ev in job.event_ids)


def test_rotate_off_retires_donor_outright(world, monkeypatch):
    sstore = _sstore(world)
    monkeypatch.setattr(Global, "migration_rotate_reads", False)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    job = mig.run_plan(_plan())
    assert job.phase == "done" and job.rotated is False
    assert sstore.rotation == {}
    assert sstore.placement == {DONOR: RECIPIENT}


def test_serving_byte_identical_through_every_phase(world, monkeypatch):
    """The tentpole's serving contract: a probe through the normal
    resilience fetch path after every phase returns bytes equal to the
    pre-migration oracle, complete=True throughout."""
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    oracle, ok = _fetch(sstore)
    assert ok
    seen = {}

    def hook(phase, _job):
        out, complete = _fetch(sstore)
        seen[phase] = bool(complete) and np.array_equal(out, oracle)

    job = mig.run_plan(_plan(), phase_hook=hook)
    assert job.phase == "done"
    assert set(seen) == set(MIGRATION_PHASES)
    assert all(seen.values()), seen
    # and after the move settles, both rotation turns stay identical
    for _ in range(2 * len(sstore.rotation.get(DONOR, ())) + 2):
        out, complete = _fetch(sstore)
        assert complete and np.array_equal(out, oracle)


def test_wal_catchup_replays_tail_and_dual_writes(world, monkeypatch,
                                                  tmp_path):
    """Writes landing between snapshot and catch-up arrive via WAL-tail
    replay; writes landing after catch-up arrive via the dual-write
    sink — the recipient ends exactly one-application equal to an
    oracle partition."""
    monkeypatch.setattr(Global, "wal_dir", str(tmp_path / "wal"))
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    w_clone, w_catchup = _edges(1, base=100000), _edges(1, base=101000)

    def hook(phase, _job):
        if phase == "clone":  # in the WAL tail the catch-up must replay
            insert_batch_into(list(sstore.stores), w_clone)
        elif phase == "catchup":  # dual-write window
            insert_batch_into(list(sstore.stores), w_catchup)

    job = mig.run_plan(_plan(), phase_hook=hook)
    assert job.phase == "done"
    # seq_clone is the WAL high-water mark at the snapshot (-1 on a
    # fresh log); exactly the one post-snapshot batch replays
    assert job.replayed == 1
    oracle = build_partition(world["triples"], DONOR, N_SHARDS)
    insert_triples(oracle, w_clone, check_ids=False)
    insert_triples(oracle, w_catchup, check_ids=False)
    assert gstore_digest(sstore.stores[DONOR]) == gstore_digest(oracle)
    # the rotation copy (the old donor) saw both writes too — rotated
    # reads must never serve stale data
    (_h, rot), = sstore.rotation[DONOR]
    assert gstore_digest(rot) == gstore_digest(oracle)


def test_stream_epoch_dual_applies_during_window(world, monkeypatch,
                                                 tmp_path):
    """A stream epoch committed during the dual-write window reaches the
    recipient through `migration_sinks()` (no epoch lost), exercising
    the ingest path's fan-out rather than `insert_batch_into`'s."""
    from wukong_tpu.store.dynamic import (
        deroll_migration_sink,
        enroll_migration_sink,
        migration_sinks,
    )
    from wukong_tpu.store.persist import clone_gstore
    from wukong_tpu.store.wal import mutation_lock
    from wukong_tpu.stream.ingest import StreamIngestor

    sstore = _sstore(world)
    recipient = clone_gstore(sstore.stores[DONOR])
    with mutation_lock():
        enroll_migration_sink(("migrate", DONOR), recipient)
    try:
        ing = StreamIngestor(list(sstore.stores))
        batch = _edges(2, base=102000)
        rec = ing.commit_epoch(batch)
        # the sink is a transient mirror of a store already counted:
        # n_inserted reports each edge once, not once-per-copy
        assert rec.n_inserted == len(batch)
        with mutation_lock():
            assert migration_sinks() == [recipient]
    finally:
        with mutation_lock():
            deroll_migration_sink(("migrate", DONOR))
    oracle = build_partition(world["triples"], DONOR, N_SHARDS)
    insert_triples(oracle, batch, check_ids=False)
    assert gstore_digest(recipient) == gstore_digest(oracle)


# ---------------------------------------------------------------------------
# chaos: injected faults at each phase abort cleanly back to the donor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site,kind", [
    ("migration.clone", "transient"),
    ("migration.catchup", "transient"),
    ("migration.cutover", "shard_down"),
])
def test_fault_at_each_phase_aborts_with_donor_untouched(
        world, monkeypatch, site, kind):
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    donor_store = sstore.stores[DONOR]
    d0 = gstore_digest(donor_store)
    aborts0 = get_registry().counter(
        "wukong_migration_aborts_total",
        labels=("cause",)).value(cause="injected_fault")
    faults.install(FaultPlan([FaultSpec(site, kind)], seed=0))
    with pytest.raises((faults.TransientFault, faults.ShardDown)):
        mig.run_plan(_plan())
    faults.clear()
    job = mig.job()
    assert job.phase == "aborted" and job.abort_cause == "injected_fault"
    # rolled back to the donor: same primary object, digest unchanged,
    # no placement/rotation residue, no dual sink leaked
    from wukong_tpu.store.dynamic import migration_sinks
    from wukong_tpu.store.wal import mutation_lock

    assert sstore.stores[DONOR] is donor_store
    assert gstore_digest(donor_store) == d0
    assert sstore.placement == {} and sstore.rotation == {}
    with mutation_lock():
        assert migration_sinks() == []
    # the abort journaled with its phase, and the metric names the cause
    (ev,) = get_journal().last(kind="shard.migrate.abort")
    assert ev.shard == DONOR
    assert ev.attrs["at_phase"] == site.split(".")[1]
    assert get_registry().counter(
        "wukong_migration_aborts_total", labels=("cause",)
    ).value(cause="injected_fault") == aborts0 + 1
    # serving still complete and byte-identical after the abort
    out, complete = _fetch(sstore)
    assert complete and np.array_equal(out, _probe_read(donor_store))


def test_fault_mid_flight_write_survives_abort(world, monkeypatch,
                                               tmp_path):
    """Zero lost mutations on the ABORT path: a write issued after the
    snapshot is in the donor (the only copy that matters once the
    migration rolls back)."""
    monkeypatch.setattr(Global, "wal_dir", str(tmp_path / "wal"))
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    w = _edges(1, base=103000)
    faults.install(FaultPlan(
        [FaultSpec("migration.catchup", "transient")], seed=0))
    with pytest.raises(faults.TransientFault):
        mig.run_plan(_plan(),
                     phase_hook=lambda ph, _j: insert_batch_into(
                         list(sstore.stores), w) if ph == "clone" else None)
    faults.clear()
    oracle = build_partition(world["triples"], DONOR, N_SHARDS)
    insert_triples(oracle, w, check_ids=False)
    assert gstore_digest(sstore.stores[DONOR]) == gstore_digest(oracle)


def test_abort_after_published_cutover_swaps_back(world, monkeypatch):
    """A failure AFTER the read path swapped (here: a crashing phase
    hook) rolls the publication back: donor primary restored, rotation
    dropped, fan-out rebound — the full abort-and-rollback contract."""
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    donor_store = sstore.stores[DONOR]
    d0 = gstore_digest(donor_store)

    def hook(phase, _job):
        if phase == "cutover":
            raise RuntimeError("operator pulled the plug")

    with pytest.raises(RuntimeError):
        mig.run_plan(_plan(), phase_hook=hook)
    job = mig.job()
    assert job.phase == "aborted"
    assert sstore.stores[DONOR] is donor_store
    assert gstore_digest(donor_store) == d0
    assert sstore.placement.get(DONOR, DONOR) == DONOR
    assert sstore.rotation == {}
    (ev,) = get_journal().last(kind="shard.migrate.abort")
    assert ev.attrs["swapped_back"] is True
    out, complete = _fetch(sstore)
    assert complete and np.array_equal(out, _probe_read(donor_store))


def test_concurrent_abort_stops_the_driver(world, monkeypatch):
    """`migrate -abort` landing while the driver is mid-flight: the
    state machine must never roll forward past the abort — no cutover
    publishes, the job lands in history exactly once, and the driver
    surfaces the abort instead of completing the migration."""
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    donor_store = sstore.stores[DONOR]

    def hook(phase, _job):
        if phase == "clone":  # the operator wins the race
            assert mig.abort(cause="operator").phase == "aborted"

    with pytest.raises(WukongError, match="aborted"):
        mig.run_plan(_plan(), phase_hook=hook)
    job = mig.job()
    assert job.phase == "aborted" and job.abort_cause == "operator"
    assert sstore.stores[DONOR] is donor_store
    assert sstore.placement == {} and sstore.rotation == {}
    with mig._lock:
        assert sum(1 for j in mig._history if j is job) == 1


def test_abort_after_retire_keeps_recipient_serving(world, monkeypatch):
    """An abort landing after retire already released the donor (rotate
    off) has nothing to roll back TO: the recipient must stay primary —
    never a None primary — and the shard keeps serving identically."""
    monkeypatch.setattr(Global, "migration_rotate_reads", False)
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    oracle, _ok = _fetch(sstore)

    def hook(phase, _job):
        if phase == "retire":
            raise RuntimeError("late failure")

    with pytest.raises(RuntimeError):
        mig.run_plan(_plan(), phase_hook=hook)
    assert mig.job().phase == "aborted"
    assert sstore.stores[DONOR] is not None
    assert sstore.placement == {DONOR: RECIPIENT}
    out, complete = _fetch(sstore)
    assert complete and np.array_equal(out, oracle)


def test_remigration_grows_the_rotation_set(world, monkeypatch):
    """A second migration of an already-rotated shard APPENDS to the
    rotation (serving set k -> k+1, exactly the advisor's predicted-
    balance model), and aborting a third move restores the second's
    serving set — earlier rotation copies are never silently dropped."""
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    oracle, _ok = _fetch(sstore)
    mig.run_plan(_plan())                           # 3 -> host 2
    mig.run_plan(_plan(donor=DONOR, recipient=1))   # 3 -> host 1
    assert sstore.placement == {DONOR: 1}
    assert [h for h, _g in sstore.rotation[DONOR]] == [DONOR, RECIPIENT]

    def hook(phase, _job):
        if phase == "cutover":
            raise RuntimeError("boom")

    with pytest.raises(RuntimeError):               # 3 -> host 0, aborted
        mig.run_plan(_plan(donor=DONOR, recipient=0), phase_hook=hook)
    assert sstore.placement == {DONOR: 1}
    assert [h for h, _g in sstore.rotation[DONOR]] == [DONOR, RECIPIENT]
    for _ in range(6):  # every rotation turn serves identical bytes
        out, complete = _fetch(sstore)
        assert complete and np.array_equal(out, oracle)


def test_operator_abort_via_executor(world, monkeypatch):
    """`migrate -abort` semantics: abort with nothing in flight is a
    clean no-op; a second abort after an abort is too."""
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    assert mig.abort(cause="operator") is None
    job = mig.run_plan(_plan())
    assert job.phase == "done"
    assert mig.abort(cause="operator") is None  # done: nothing to abort


# ---------------------------------------------------------------------------
# the kill drill: crash (no rollback) at each phase, then resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site", ["migration.clone", "migration.catchup",
                                  "migration.cutover"])
def test_kill_at_each_phase_resumes_with_zero_lost_writes(
        world, monkeypatch, tmp_path, site):
    """The crash-safety drill: a kill at any phase leaves a resumable
    job; writes issued before the crash AND between crash and resume
    are all present exactly once after roll-forward (dedup off, so a
    double-application would change the digest)."""
    monkeypatch.setattr(Global, "wal_dir", str(tmp_path / "wal"))
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    writes = [_edges(1, base=104000), _edges(1, base=105000)]
    faults.install(FaultPlan([FaultSpec(site, "transient")], seed=0))
    with pytest.raises(faults.TransientFault):
        mig.run_plan(_plan(), rollback=False,
                     phase_hook=lambda ph, _j: insert_batch_into(
                         list(sstore.stores), writes[0],
                         dedup=False) if ph == "clone" else None)
    faults.clear()
    job = mig.job()
    assert job.phase == site.split(".")[1]  # crashed in place, resumable
    # a write lands while the migration is down
    insert_batch_into(list(sstore.stores), writes[1], dedup=False)
    job = mig.resume(phase_hook=lambda ph, _j: None)
    assert job.phase == "done" and job.attempts == 2
    oracle = build_partition(world["triples"], DONOR, N_SHARDS)
    for w in (writes if site != "migration.clone" else writes[1:]):
        # a clone-phase crash happens BEFORE the hook ever fired, so
        # only the while-down write exists in that schedule
        insert_triples(oracle, w, dedup=False, check_ids=False)
    assert gstore_digest(sstore.stores[DONOR]) == gstore_digest(oracle)
    (_h, rot), = sstore.rotation[DONOR]
    assert gstore_digest(rot) == gstore_digest(oracle)
    assert sstore.placement == {DONOR: RECIPIENT}


def test_resume_guards(world, monkeypatch):
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    with pytest.raises(WukongError, match="no crashed migration"):
        mig.resume()
    mig.run_plan(_plan())
    with pytest.raises(WukongError, match="no crashed migration"):
        mig.resume()  # done jobs don't resume


def test_second_plan_refused_while_in_flight(world, monkeypatch):
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    hits = []

    def hook(phase, _job):
        if phase == "clone" and not hits:
            hits.append(phase)
            with pytest.raises(WukongError, match="already in flight"):
                mig.run_plan(_plan(donor=1, recipient=0))

    job = mig.run_plan(_plan(), phase_hook=hook)
    assert hits and job.phase == "done"
    assert sstore.placement == {DONOR: RECIPIENT}  # only the first plan ran


def test_plan_validation(world, monkeypatch):
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    with pytest.raises(WukongError, match="donor shard"):
        mig.run_plan(_plan(donor=99))
    with pytest.raises(WukongError, match="recipient host"):
        mig.run_plan(_plan(recipient=99))
    detached = MigrationExecutor()
    with pytest.raises(WukongError, match="no live sharded store"):
        monkeypatch.setattr(Global, "migration_enable", True)
        detached.run_plan(_plan())


# ---------------------------------------------------------------------------
# surfaces: events filter, /plan, /healthz, Monitor, metrics, console
# ---------------------------------------------------------------------------

def test_events_migrate_filter(world, monkeypatch):
    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    mig.run_plan(_plan())
    # `/events -K migrate`: the dotted-segment filter selects the whole
    # shard.migrate.* timeline (so does the full `-K shard.migrate`
    # prefix and an exact `-K shard.migrate.cutover`)
    _text, js = render_events(kind="migrate")
    assert set(js["counts"]) == {
        "shard.migrate.start", "shard.migrate.catchup",
        "shard.migrate.cutover", "shard.migrate.retire"}
    assert all(e["shard"] == DONOR for e in js["events"])
    assert [e.kind for e in get_journal().last(kind="shard.migrate")] == \
        [e["kind"] for e in js["events"]]
    (cut,) = get_journal().last(kind="shard.migrate.cutover")
    assert cut.attrs["recipient_host"] == RECIPIENT
    assert cut.attrs["pause_us"] >= 0
    # unrelated kinds stay out of the filtered view
    assert "shard.migrate.abort" not in js["counts"]


def test_plan_surface_healthz_and_monitor_mid_flight(world, monkeypatch):
    """Mid-migration: /plan shows IN FLIGHT, /healthz reports the shard
    degraded-not-dead, Monitor prints a Migration[...] line; all three
    go quiet once the migration settles."""
    from wukong_tpu.obs.httpd import health_report
    from wukong_tpu.runtime.monitor import Monitor

    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    mon = Monitor()
    seen = {}

    def hook(phase, _job):
        if phase != "cutover":
            return
        text, js = render_plan(advise=False)
        rep = health_report()
        seen["plan"] = "migration IN FLIGHT" in text
        seen["plan_js"] = js["migration"]["in_flight"]
        seen["healthz_live"] = rep["live"]
        seen["healthz"] = rep["degraded"].get("migration")
        seen["monitor"] = mon.migration_lines()

    mig.run_plan(_plan(), phase_hook=hook)
    assert seen["plan"] and seen["plan_js"]
    assert seen["healthz_live"] is True  # degraded, never dead
    assert seen["healthz"] == {"shard": DONOR, "phase": "cutover",
                               "recipient_host": RECIPIENT}
    assert seen["monitor"] and "Migration[" in seen["monitor"][0]
    # settled: every surface quiet again
    text, js = render_plan(advise=False)
    assert "IN FLIGHT" not in text and js["migration"]["in_flight"] is False
    assert js["migration"]["last"]["phase"] == "done"
    assert "migration" not in health_report()["degraded"]
    assert mon.migration_lines() == []


def test_phase_gauge_tracks_the_state_machine(world, monkeypatch):
    from wukong_tpu.runtime.migration import _phase_gauge

    sstore = _sstore(world)
    mig = get_migrator()
    _arm(mig, sstore, monkeypatch)
    assert _phase_gauge() == 0.0
    gauges = {}
    mig.run_plan(_plan(), phase_hook=lambda ph, _j: gauges.setdefault(
        ph, _phase_gauge()))
    # the hook fires with the phase still current: 1-based phase index
    assert gauges == {ph: float(i + 1)
                      for i, ph in enumerate(MIGRATION_PHASES)}
    assert _phase_gauge() == 0.0


def test_console_migrate_verb_surfaces(proxy, capsys, monkeypatch):
    """The operator verbs stay safe with no dist world attached: status
    prints, abort is a no-op, a sweep reports no plan, and the armed-off
    posture surfaces the refusal as a console error, not a crash."""
    from wukong_tpu.runtime.console import Console

    con = Console(proxy)
    assert con.run_command("migrate -s -j") is True
    out = capsys.readouterr().out
    assert '"in_flight": false' in out
    assert con.run_command("migrate -abort") is True  # no flight: no-op
    assert con.run_command("migrate") is True  # no advisor data -> no plan
    monkeypatch.setattr(Global, "migration_enable", True)
    assert con.run_command("migrate") is True  # still no plan; no crash


def test_actuator_loop_start_stop(world, monkeypatch):
    """`maybe_start_migration` arms the background loop only when both
    knobs ask for it, and supersedes the observe-only advisor loop (one
    sweeper, not two)."""
    sstore = _sstore(world)
    monkeypatch.setattr(Global, "migration_enable", True)
    monkeypatch.setattr(Global, "placement_interval_s", 60)
    mig = maybe_start_migration(sstore)
    try:
        assert mig is not None and mig._thread is not None
        assert get_advisor()._thread is None  # the advisor loop yielded
    finally:
        get_migrator().stop()
    assert get_migrator()._thread is None


# ---------------------------------------------------------------------------
# the executed rebalance drill (ROADMAP item 3 acceptance, armed)
# ---------------------------------------------------------------------------

def test_rebalance_drill_executes_and_rebalances(world, proxy,
                                                 monkeypatch):
    """The hot-spot drill flipped from observe-only to executed: the
    actuator migrates the advisor's donor shard, every probe during the
    migration is byte-identical, and the post-move host imbalance lands
    under placement_imbalance_x."""
    monkeypatch.setattr(Global, "migration_enable", True)
    sstore = _sstore(world)
    emu = Emulator(proxy)
    rep = emu.run_rebalance(n_ops=900, zipf_a=1.6, seed=7, sstore=sstore)
    assert rep["executed"] and rep["plan_donor_is_hot"]
    assert rep["queries_identical"], rep["probes"]
    assert set(rep["probes"]) == set(MIGRATION_PHASES) | {"post"}
    assert rep["rebalanced"] and rep["decision_after"] == "balanced"
    assert rep["imbalance_after"] < rep["imbalance_before"]
    assert rep["rebalance_gain"] > 1.0
    assert rep["job"]["phase"] == "done"
    assert rep["job"]["bytes_moved"] > 0
    assert rep["store_untouched"] is False  # the drill MOVED the store
    assert sstore.placement == {rep["hot"]: rep["plan"]["recipient_host"]}


def test_rebalance_drill_refuses_when_disarmed(world, proxy):
    """migration_enable off: the drill raises at run_plan — the
    observe-only posture holds even through the drill's entry point."""
    sstore = _sstore(world)
    emu = Emulator(proxy)
    with pytest.raises(WukongError, match="migration_enable is off"):
        emu.run_rebalance(n_ops=600, zipf_a=1.6, seed=7, sstore=sstore)
    assert sstore.placement == {}


# ---------------------------------------------------------------------------
# the migration-safety analysis gate (pos/neg fixtures + repo clean)
# ---------------------------------------------------------------------------

def test_migration_gate_fixtures(tmp_path):
    from wukong_tpu.analysis import run_analysis

    def write(tree: dict) -> str:
        root = tmp_path / "pkg"
        for rel, src in tree.items():
            p = root / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(src)
        return str(root)

    bad = write({
        "runtime/migration.py": (
            "MIGRATION_PHASES = ('clone', 'cutover')\n"
            "def _phase_cutover(job):\n"
            "    emit_event('shard.migrate.cutover', shard=1)\n"
            "    swap()\n"
            "lock = make_lock('migration.state')\n"),
        "parallel/sharded_store.py": (
            "def cutover_shard(i, store):\n"
            "    stores[i] = store\n")})
    msgs = "\n".join(str(v) for v in run_analysis(
        bad, plugins=["migration-safety"]))
    assert "shard.migrate.start" in msgs      # unjournaled transition
    assert "shard.migrate.abort" in msgs
    assert "_phase_cutover" in msgs           # unguarded cutover path
    assert "cutover_shard" in msgs
    assert "migration.state" in msgs          # undeclared leaf lock

    good = write({
        "runtime/migration.py": (
            "MIGRATION_PHASES = ('clone', 'catchup', 'cutover', 'retire')\n"
            "declare_leaf('migration.state')\n"
            "lock = make_lock('migration.state')\n"
            "def run(job):\n"
            "    emit_event('shard.migrate.start', shard=1)\n"
            "    emit_event('shard.migrate.catchup', shard=1)\n"
            "    emit_event('shard.migrate.retire', shard=1)\n"
            "    emit_event('shard.migrate.abort', shard=1)\n"
            "def _phase_cutover(job):  # guarded by: the migration lock\n"
            "    emit_event('shard.migrate.cutover', shard=1)\n"),
        "parallel/sharded_store.py": (
            "def cutover_shard(self, i, store):\n"
            "    with self._migration_lock:\n"
            "        self.stores[i] = store\n")})
    assert run_analysis(good, plugins=["migration-safety"]) == []
    # a tree without an actuator is out of the gate's scope
    empty = str(tmp_path / "empty")
    os.makedirs(empty, exist_ok=True)
    assert run_analysis(empty, plugins=["migration-safety"]) == []


def test_repo_migration_gate_clean():
    from wukong_tpu.analysis import run_analysis

    pkg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "wukong_tpu")
    assert run_analysis(pkg, plugins=["migration-safety"]) == []
