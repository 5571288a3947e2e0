"""Multi-process data plane tests (PR 20).

Three layers, cheapest first:

- **Framing goldens** — the wire protocol's frame codec and message
  registry, exercised as pure functions (no sockets): roundtrip, the
  torn-tail contract (only the unacknowledged trailing message drops),
  structured FRAME_TOO_LARGE / TRANSPORT_CORRUPT errors.
- **Wire serving in-process** — a real TCP exchange against the worker's
  serve loop run in a thread (deterministic chaos on the
  transport.connect/send/recv fault sites, structured error propagation,
  byte-identity of every op vs its loopback execution).
- **Process supervision** — real spawn-context workers: checkpoint boot,
  WAL-tail replay, digest-gated peering, SIGKILL + restart recovery, the
  heartbeat failure detector, and the emulator's kill-a-process drill.
"""

import random
import socket
import threading
import time

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.runtime import faults
from wukong_tpu.runtime.faults import FaultPlan, FaultSpec
from wukong_tpu.runtime.transport import (
    FRAME_MAGIC,
    MESSAGE_REGISTRY,
    OP_HANDLERS,
    FrameDecoder,
    LoopbackTransport,
    SocketTransport,
    decode_frames,
    encode_frame,
    make_transport,
    pack_error,
    pack_message,
    pack_reply,
    run_op,
    unpack_message,
    unpack_reply,
)
from wukong_tpu.store.gstore import build_partition
from wukong_tpu.store.persist import gstore_digest
from wukong_tpu.types import IN, OUT
from wukong_tpu.utils.errors import (
    ErrorCode,
    FrameTooLarge,
    RetryExhausted,
    TransportCorrupt,
    WukongError,
)

pytestmark = pytest.mark.proc


@pytest.fixture(autouse=True, scope="module")
def _lockdep_checked():
    """The whole multi-process suite runs under the lockdep runtime
    checker: transport per-connection locks and the supervisor/worker
    state locks are declared leaves — teardown asserts no order cycles
    and no leaf inversions were recorded by any drill."""
    from wukong_tpu.analysis import lockdep

    lockdep.install(True)
    yield
    try:
        assert lockdep.cycles() == [], lockdep.cycles()
        assert lockdep.leaf_violations() == [], lockdep.leaf_violations()
    finally:
        lockdep.install(False)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# a tiny partitioned world (numpy-only — workers must not need jax)
# ---------------------------------------------------------------------------

D = 4


def _triples():
    rng = np.random.default_rng(7)
    n = 400
    s = rng.integers(1000, 1400, size=n)
    p = rng.integers(2, 6, size=n)
    o = rng.integers(1000, 1400, size=n)
    return np.stack([s, p, o], axis=1).astype(np.int64)


@pytest.fixture(scope="module")
def stores():
    t = _triples()
    return [build_partition(t, i, D) for i in range(D)]


@pytest.fixture(scope="module")
def g0(stores):
    return stores[0]


# ---------------------------------------------------------------------------
# framing goldens (pure functions, no sockets)
# ---------------------------------------------------------------------------

def test_frame_roundtrip():
    payloads = [b"", b"x", b"hello wire" * 100]
    buf = b"".join(encode_frame(p) for p in payloads)
    out, consumed = decode_frames(buf)
    assert out == payloads
    assert consumed == len(buf)


def test_torn_frame_drops_only_unacknowledged_message():
    f1, f2 = encode_frame(b"first"), encode_frame(b"second-message")
    for cut in range(1, len(f2)):
        out, consumed = decode_frames(f1 + f2[:cut])
        assert out == [b"first"]  # every byte before the tear parses
        assert consumed == len(f1)  # ... and the torn tail stays buffered
    # completing the tail recovers the message: nothing acknowledged lost
    dec = FrameDecoder()
    assert dec.feed(f1 + f2[:5]) == [b"first"]
    assert dec.feed(f2[5:]) == [b"second-message"]


def test_frame_decoder_byte_at_a_time():
    frames = [encode_frame(b"a" * 37), encode_frame(b""), encode_frame(b"z")]
    dec = FrameDecoder()
    got = []
    for b in b"".join(frames):
        got += dec.feed(bytes([b]))
    assert got == [b"a" * 37, b"", b"z"]


def test_bad_magic_is_structured_corruption():
    with pytest.raises(TransportCorrupt) as ei:
        decode_frames(b"XXXX" + encode_frame(b"p")[4:])
    assert ei.value.code == ErrorCode.TRANSPORT_CORRUPT


def test_crc_mismatch_is_structured_corruption():
    f = bytearray(encode_frame(b"payload-bytes"))
    f[-1] ^= 0xFF  # flip one payload byte of a COMPLETE frame
    with pytest.raises(TransportCorrupt):
        decode_frames(bytes(f))


def test_oversized_frame_raises_structured_error_naming_the_limit():
    # encode side: the sender refuses what the receiver would refuse
    with pytest.raises(FrameTooLarge) as ei:
        encode_frame(b"x" * 100, max_bytes=64)
    assert ei.value.code == ErrorCode.FRAME_TOO_LARGE
    assert "transport_max_frame_mb" in str(ei.value)
    # decode side: a hostile/corrupt declared length is refused up front
    frame = encode_frame(b"y" * 100)
    with pytest.raises(FrameTooLarge) as ei:
        decode_frames(frame, max_bytes=64)
    assert "transport_max_frame_mb" in str(ei.value)
    # the knob is the default limit for both sides
    old = Global.transport_max_frame_mb
    Global.transport_max_frame_mb = 0
    try:
        with pytest.raises(FrameTooLarge):
            encode_frame(b"over the knob")
    finally:
        Global.transport_max_frame_mb = old


def test_frame_magic_is_stable():
    # the wire format is a compatibility surface: changing it silently
    # partitions old/new processes mid-upgrade
    assert FRAME_MAGIC == b"WKTX"
    assert encode_frame(b"q")[:4] == b"WKTX"


# ---------------------------------------------------------------------------
# message registry: every declared op roundtrips both sides
# ---------------------------------------------------------------------------

#: sample request args per op (plain ints by schema design)
_SAMPLE_ARGS = {
    "ping": (7,),
    "segment": (3, OUT),
    "versatile": (IN,),
    "index": (2, IN),
    "digest": (),
    "sync": (5,),
    "snapshot": (),
}


def test_registry_and_handlers_cover_the_same_ops():
    assert set(MESSAGE_REGISTRY) == set(OP_HANDLERS)
    assert set(MESSAGE_REGISTRY) == set(_SAMPLE_ARGS)


@pytest.mark.parametrize("op", sorted(MESSAGE_REGISTRY))
def test_pack_unpack_roundtrip_every_message_type(op):
    args = _SAMPLE_ARGS[op]
    pack, unpack = MESSAGE_REGISTRY[op]
    assert unpack(pack(args)) == tuple(int(a) for a in args)
    # and through the full request envelope + frame codec
    frame = encode_frame(pack_message(op, 3, args))
    (payload,), _ = decode_frames(frame)
    got_op, got_sid, got_args = unpack_message(payload)
    assert (got_op, got_sid) == (op, 3)
    assert got_args == tuple(int(a) for a in args)


def test_unpack_message_rejects_malformed_payloads():
    with pytest.raises(TransportCorrupt):
        unpack_message(b"\x00not-a-pickle")
    with pytest.raises(TransportCorrupt):
        unpack_message(pack_reply("wrong-shape"))
    with pytest.raises(TransportCorrupt):  # undeclared op
        unpack_message(pack_message("segment", 0, (1, 0))
                       .replace(b"segment", b"zegment"))


def test_reply_envelope_ok_err_unknown():
    assert unpack_reply(pack_reply({"a": 1})) == {"a": 1}
    with pytest.raises(WukongError) as ei:
        unpack_reply(pack_error(int(ErrorCode.SHARD_UNAVAILABLE), "gone"))
    assert ei.value.code == ErrorCode.SHARD_UNAVAILABLE
    with pytest.raises(TransportCorrupt):
        unpack_reply(b"\x80\x04N.")  # pickled None: unknown reply kind


def test_run_op_executes_every_declared_op(g0):
    keys, offs, edges = run_op("segment", g0, 3, OUT)
    assert len(offs) == len(keys) + 1 and len(edges) == offs[-1]
    missing = run_op("segment", g0, 999, OUT)  # absent segment: empty CSR
    assert len(missing[0]) == 0 and list(missing[1]) == [0]
    idx = run_op("index", g0, 3, IN)
    assert idx.dtype == np.int32
    vkeys, _voffs, _vedges, _vpred = run_op("versatile", g0, OUT)
    assert vkeys is not None
    assert run_op("digest", g0) == int(gstore_digest(g0))
    pong = run_op("ping", g0, 42)
    assert pong == {"sid": 0, "version": int(getattr(g0, "version", 0)),
                    "seq": 42}
    assert run_op("sync", g0, 5) == 0  # loopback: nothing to catch up
    from wukong_tpu.store.persist import gstore_from_bytes

    blob = run_op("snapshot", g0)
    assert gstore_digest(gstore_from_bytes(blob)) == gstore_digest(g0)
    with pytest.raises(WukongError):
        run_op("no-such-op", g0)


# ---------------------------------------------------------------------------
# transports: loopback default, socket local-fallback, mode knob
# ---------------------------------------------------------------------------

def test_make_transport_mode_knob():
    assert make_transport().mode == "loopback"  # the zero-touch default
    old = Global.transport_mode
    try:
        Global.transport_mode = "socket"
        assert isinstance(make_transport(), SocketTransport)
        Global.transport_mode = "carrier-pigeon"
        with pytest.raises(WukongError) as ei:
            make_transport()
        assert ei.value.code == ErrorCode.UNSUPPORTED_SHAPE
    finally:
        Global.transport_mode = old


def test_loopback_fetch_is_direct_execution(g0):
    lo = LoopbackTransport()
    a = lo.fetch(0, g0, "segment", (3, OUT))
    b = run_op("segment", g0, 3, OUT)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert lo.dispatch(lambda u, v: u + v, 2, 3) == 5


def test_loopback_snapshot_is_an_independent_clone(g0):
    snap = LoopbackTransport().snapshot(0, g0)
    assert snap is not g0
    assert gstore_digest(snap) == gstore_digest(g0)


def test_peerless_socket_transport_serves_locally(g0):
    """Flipping transport_mode=socket with no workers up must stay
    byte-identical: the parent's copy is authoritative."""
    tr = SocketTransport()
    try:
        a = tr.fetch(0, g0, "segment", (3, OUT))
        b = run_op("segment", g0, 3, OUT)
        for x, y in zip(a, b):
            assert np.array_equal(x, y)
        assert gstore_digest(tr.snapshot(0, g0)) == gstore_digest(g0)
    finally:
        tr.close()


# ---------------------------------------------------------------------------
# wire serving against the worker loop, in-process (threaded server)
# ---------------------------------------------------------------------------

@pytest.fixture()
def wire(g0):
    """A real TCP server speaking the framed protocol, serving shard 0
    from a thread — the worker's serve loop without the process."""
    from wukong_tpu.runtime.procs import _serve_connection, _WorkerState

    state = _WorkerState({0: g0}, applied_seq=-1, wal_dir="")
    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    server.bind(("127.0.0.1", 0))
    server.listen(8)

    def accept_loop():
        while True:
            try:
                cli, _ = server.accept()
            except OSError:
                return
            threading.Thread(target=_serve_connection, args=(cli, state),
                             daemon=True).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    tr = SocketTransport()
    addr = ("127.0.0.1", server.getsockname()[1])
    tr.register_peer(0, addr)
    yield tr, addr
    tr.close()
    server.close()


def test_wire_fetch_matches_loopback_byte_for_byte(wire, g0):
    tr, _addr = wire
    for op, args in (("segment", (3, OUT)), ("segment", (4, IN)),
                     ("index", (2, IN)), ("versatile", (OUT,))):
        remote = tr.fetch(0, g0, op, args)
        local = run_op(op, g0, *args)
        if isinstance(local, tuple):
            for x, y in zip(remote, local):
                assert np.array_equal(np.asarray(x), np.asarray(y))
        else:
            assert np.array_equal(np.asarray(remote), np.asarray(local))
    assert tr.fetch(0, g0, "digest", ()) == int(gstore_digest(g0))
    assert gstore_digest(tr.snapshot(0, g0)) == gstore_digest(g0)


def test_wire_error_propagates_structured(wire, g0):
    tr, addr = wire
    tr.register_peer(5, addr)  # the worker does not own shard 5
    with pytest.raises(WukongError) as ei:
        tr._retry_call(5, "digest", ())
    assert ei.value.code == ErrorCode.SHARD_UNAVAILABLE
    assert "shard 5" in str(ei.value)


def test_transport_connect_fault_retries_through(wire, g0):
    plan = FaultPlan([FaultSpec("transport.connect", "transient", count=1)],
                     seed=0)
    faults.install(plan)
    assert tr_fetch_digest(wire, g0)  # first connect faulted, retry wins
    assert ("transport.connect", None, "transient") in plan.history


def test_transport_send_fault_drops_connection_and_retries(wire, g0):
    tr, _ = wire
    tr.fetch(0, g0, "digest", ())  # warm the connection
    plan = FaultPlan([FaultSpec("transport.send", "transient", count=1)],
                     seed=0)
    faults.install(plan)
    assert tr_fetch_digest(wire, g0)
    assert plan.history and plan.history[0][0] == "transport.send"


def test_transport_recv_fault_drops_connection_and_retries(wire, g0):
    plan = FaultPlan([FaultSpec("transport.recv", "transient", count=1)],
                     seed=0)
    faults.install(plan)
    assert tr_fetch_digest(wire, g0)
    assert plan.history and plan.history[0][0] == "transport.recv"


def tr_fetch_digest(wire, g0) -> bool:
    tr, _ = wire
    return tr.fetch(0, g0, "digest", ()) == int(gstore_digest(g0))


def test_dead_peer_exhausts_retries_with_transient_faults(g0, monkeypatch):
    """A peer that is simply gone (connection refused) must surface as
    retry exhaustion — the sharded store's resilience ladder then owns
    rotation/failover, exactly as for an in-proc shard fault."""
    monkeypatch.setattr(Global, "retry_base_ms", 1)
    monkeypatch.setattr(Global, "retry_max_ms", 2)
    sink = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sink.bind(("127.0.0.1", 0))
    dead = ("127.0.0.1", sink.getsockname()[1])
    sink.close()  # nothing listens here any more
    tr = SocketTransport(connect_timeout_ms=200)
    tr.register_peer(0, dead)
    try:
        with pytest.raises(RetryExhausted):
            tr._retry_call(0, "digest", ())
    finally:
        tr.close()


# ---------------------------------------------------------------------------
# retry_call audit: no sleep after the final attempt
# ---------------------------------------------------------------------------

def test_retry_call_never_sleeps_after_the_final_attempt():
    """attempts=N means exactly N calls and N-1 backoffs: sleeping after
    the last failure would add a full backoff window of dead latency to
    every exhausted retry (and stall the caller's failover)."""
    from wukong_tpu.runtime.faults import TransientFault
    from wukong_tpu.runtime.resilience import retry_call

    calls, sleeps = [], []

    def boom():
        calls.append(1)
        raise TransientFault("always down")

    with pytest.raises(RetryExhausted):
        retry_call(boom, site="test.audit", attempts=4, base_ms=1, max_ms=2,
                   rng=random.Random(0), sleep=sleeps.append)
    assert len(calls) == 4
    assert len(sleeps) == 3  # N-1: no backoff after the last failure


# ---------------------------------------------------------------------------
# process supervision: spawn, WAL-tail sync, kill, restart, heartbeat
# ---------------------------------------------------------------------------

def _mk_sstore(stores):
    from wukong_tpu.parallel.sharded_store import ShardedDeviceStore

    class _Mesh:
        devices = np.empty(D, dtype=object)

    return ShardedDeviceStore(list(stores), _Mesh(), replication_factor=1)


@pytest.fixture()
def proc_world(tmp_path, monkeypatch):
    """A supervisor-ready world: fresh partitions (module stores stay
    pristine), an active WAL, and a slow heartbeat so tests drive
    kill/restart deterministically."""
    from wukong_tpu.store.wal import reset_wal

    monkeypatch.setattr(Global, "proc_workers", 2)
    monkeypatch.setattr(Global, "proc_heartbeat_ms", 60_000)
    monkeypatch.setattr(Global, "proc_restart_backoff_ms", 1)
    monkeypatch.setattr(Global, "wal_dir", str(tmp_path / "wal"))
    t = _triples()
    stores = [build_partition(t, i, D) for i in range(D)]
    ss = _mk_sstore(stores)
    yield ss, str(tmp_path / "ckpt")
    Global.wal_dir = ""
    reset_wal()


def test_supervisor_spawn_serve_sync_kill_restart(proc_world):
    from wukong_tpu.runtime.procs import ProcSupervisor
    from wukong_tpu.store.dynamic import insert_batch_into

    ss, ckpt_dir = proc_world
    sup = ProcSupervisor(ss, ckpt_dir)
    sup.start()
    try:
        # every shard recovered digest-identical from the checkpoint and
        # got peered; the sstore now speaks the socket transport
        assert ss.transport is sup.transport
        assert all(sup.transport.peer_for(s) is not None for s in range(D))
        assert sorted(sup.groups) == [0, 1]
        # workers stay off jax: the parent may hold the only chip
        assert sup.worker_jax_loaded is False
        # wire fetches are byte-identical to the parent's local execution
        for sid in range(D):
            a = ss.transport.fetch(sid, ss.stores[sid], "segment", (3, OUT))
            b = run_op("segment", ss.stores[sid], 3, OUT)
            for x, y in zip(a, b):
                assert np.array_equal(x, y)
        # WAL is the mutation transport: a durable insert after boot
        # reaches every worker via the sync op, proven by digests
        batch = np.array([[2000, 3, 2001], [2002, 4, 2003]], dtype=np.int64)
        insert_batch_into(list(ss.stores), batch, dedup=False)
        sup.sync()
        for gid in sup.groups:
            want = {sid: int(gstore_digest(ss.stores[sid]))
                    for sid in sorted(sup.groups[gid].serving)}
            assert sup.worker_digests(gid) == want
        # SIGKILL one worker: its shards fall back to the parent through
        # the resilience ladder (peers deregister only on restart)
        gid = sup.group_of(0)
        dead_pid = sup.kill(gid)
        assert dead_pid > 0
        # restart = the full crash-recovery path: newest checkpoint +
        # WAL-tail replay (the post-boot insert!), digest-gated rejoin
        assert sup.restart(gid) is True
        want = {sid: int(gstore_digest(ss.stores[sid]))
                for sid in sorted(sup.groups[gid].serving)}
        assert sup.worker_digests(gid) == want
        assert all(sup.transport.peer_for(s) is not None
                   for s in sup.groups[gid].shard_ids)
    finally:
        sup.stop()
    # stop() restores the loopback transport: zero-touch both ways
    assert ss.transport.mode == "loopback"


def test_heartbeat_detects_death_and_restarts(proc_world, monkeypatch):
    from wukong_tpu.obs.metrics import get_registry
    from wukong_tpu.runtime.procs import ProcSupervisor

    ss, ckpt_dir = proc_world
    monkeypatch.setattr(Global, "proc_workers", 1)
    monkeypatch.setattr(Global, "proc_heartbeat_ms", 50)
    monkeypatch.setattr(Global, "proc_heartbeat_misses", 2)
    reg = get_registry()
    m_restarts = reg.counter("wukong_proc_restarts_total",
                             "Worker processes restarted by the supervisor",
                             labels=("group",))
    r0 = m_restarts.value(group="0")
    sup = ProcSupervisor(ss, ckpt_dir)
    sup.start()
    try:
        pid0 = sup.groups[0].proc.pid
        sup.kill(0)
        deadline = time.time() + 30
        while time.time() < deadline:
            grp = sup.groups[0]
            if (grp.proc is not None and grp.proc.pid != pid0
                    and grp.serving):
                break
            time.sleep(0.05)
        else:
            pytest.fail("heartbeat never restarted the killed worker")
        assert m_restarts.value(group="0") - r0 >= 1
        assert sup.worker_digests(0) == {
            sid: int(gstore_digest(ss.stores[sid]))
            for sid in sorted(sup.groups[0].serving)}
    finally:
        sup.stop()


# ---------------------------------------------------------------------------
# the kill-a-process drill, end to end (emulator + replicated dist world)
# ---------------------------------------------------------------------------

@pytest.mark.recovery
def test_kill_a_process_drill(tmp_path, monkeypatch, eight_cpu_devices):
    """ISSUE 20 acceptance: SIGKILL a worker mid-query-stream — every
    reply stays complete=True and byte-identical to the loopback oracle
    via replica failover; the restarted worker rejoins after checkpoint +
    WAL-tail replay, digest-identical; stop() restores loopback."""
    from wukong_tpu.engine.cpu import CPUEngine
    from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
    from wukong_tpu.parallel.dist_engine import DistEngine
    from wukong_tpu.parallel.mesh import make_mesh
    from wukong_tpu.runtime.emulator import Emulator
    from wukong_tpu.runtime.proxy import Proxy
    from wukong_tpu.store.gstore import build_all_partitions
    from wukong_tpu.store.wal import reset_wal

    monkeypatch.setattr(Global, "enable_tpu", False)
    monkeypatch.setattr(Global, "enable_dist_inplace", False)
    monkeypatch.setattr(Global, "replication_factor", 2)
    monkeypatch.setattr(Global, "proc_workers", 2)
    # the drill drives kill/restart itself: keep the heartbeat out of it
    monkeypatch.setattr(Global, "proc_heartbeat_ms", 60_000)
    monkeypatch.setattr(Global, "proc_restart_backoff_ms", 1)
    monkeypatch.setattr(Global, "retry_base_ms", 1)
    monkeypatch.setattr(Global, "retry_max_ms", 4)
    monkeypatch.setattr(Global, "wal_dir", str(tmp_path / "wal"))
    triples, _ = generate_lubm(1, seed=42)
    ss = VirtualLubmStrings(1, seed=42)
    dist = DistEngine(build_all_partitions(triples, 8), ss, make_mesh(8))
    assert dist.sstore.replication_factor == 2
    g = build_partition(triples, 0, 1)
    proxy = Proxy(g, ss, CPUEngine(g, ss), None, dist)
    try:
        report = Emulator(proxy).run_proc_drill(str(tmp_path / "ckpt"),
                                                rounds=2)
        assert report["proc_identical"] is True
        assert report["outage"]["complete"] is True
        assert report["outage"]["identical"] is True
        assert report["outage"]["failovers"] > 0
        assert report["rejoin"]["ok"] is True
        assert report["rejoin"]["wal_replayed"] is True
        assert report["rejoin"]["digests_match"] is True
        assert report["rejoin"]["repeered"] is True
        assert report["rejoin"]["restarts"] >= 1
        assert report["recovered"]["complete"] is True
        assert report["recovered"]["identical"] is True
        assert report["loopback_restored"]["mode"] == "loopback"
        assert report["loopback_restored"]["identical"] is True
    finally:
        proxy.recovery().stop()
        Global.wal_dir = ""
        reset_wal()
