"""Process supervision: per-shard-group worker pools behind the proxy.

The reference runs one process per server under MPI with one-sided RDMA
reads (PAPER.md §L2/L3); until PR 20 every "distributed" guarantee here
was really a threading guarantee inside one interpreter. This module puts
real process boundaries under the transport seam (runtime/transport.py):

- **Spawn.** :class:`ProcSupervisor` splits the sharded store's D
  partitions into ``proc_workers`` contiguous groups and spawns one
  worker process per group (``multiprocessing`` *spawn* context — no
  forked JAX runtime state; workers are numpy-only by construction and
  report whether jax leaked into them). A worker boots exactly like a
  crashed server recovering: it loads its partitions from the NEWEST
  checkpoint bundle and replays the WAL tail through the normal PR 5
  mutation paths (``insert_triples`` / ``apply_vector_record``) before
  serving a byte, then proves itself with a per-shard content digest the
  parent checks against its own stores.
- **Serve.** Each worker listens on a loopback TCP socket and answers the
  framed transport ops (segment/versatile/index fetches, digest probes,
  WAL-tail syncs, migration snapshots). The parent's SocketTransport gets
  one peer registration per shard; shards whose worker is down (or whose
  digest did not match) stay parent-served.
- **Supervise.** A heartbeat thread pings every group at
  ``proc_heartbeat_ms``; ``proc_heartbeat_misses`` consecutive misses
  declare the worker dead (counted in
  ``wukong_proc_heartbeat_misses_total``) and trigger a restart with
  capped-exponential backoff (``proc_restart_backoff_ms`` doubling up to
  ``proc_restart_backoff_max_ms``), counted in
  ``wukong_proc_restarts_total`` and journaled as ``proc.restart``. While
  the worker is down its shards' fetches flow through the existing
  resilience ladder: peers deregister → retries → breaker → replica
  failover (``wukong_failover_total``) — results stay ``complete=True``
  and byte-identical while any replica lives, which is exactly what the
  kill-a-process drill (runtime/emulator.py ``run_proc_drill``) asserts.

The WAL is the mutation transport: workers share the parent's WAL
*directory* read-only (store/wal.py ``replay_dir`` — they must never
construct a ``WriteAheadLog`` on it, whose constructor repairs torn tails
in place) and catch up via the ``sync`` op, which heartbeats piggyback.
"""

from __future__ import annotations

import json
import multiprocessing
import os
import signal
import socket
import sys
import threading
import time

from wukong_tpu.analysis.lockdep import declare_leaf, make_lock
from wukong_tpu.config import Global
from wukong_tpu.utils.logger import log_info, log_warn

# supervisor group-state lock: guards the group table and per-group
# restart bookkeeping (plain dict/int writes); innermost by construction —
# spawning, transport calls, and events all happen OUTSIDE it
declare_leaf("procs.state")
# worker-side serve-state lock: guards applied_seq during WAL syncs
declare_leaf("procs.worker.state")

#: knobs a spawn-context worker inherits from the parent (spawn starts a
#: fresh interpreter, so Global resets to defaults there)
_INHERITED_KNOBS = ("transport_max_frame_mb", "wal_dir")


# ---------------------------------------------------------------------------
# worker side (runs in the child process — keep this numpy-only: no jax,
# no engine/parallel imports beyond device_store's numpy helpers)
# ---------------------------------------------------------------------------

def _newest_bundle(ckpt_dir: str):
    """(path, manifest) of the newest valid checkpoint bundle, or None.
    Mirrors RecoveryManager._checkpoints without importing the recovery
    manager (that would drag proxy-side modules into the worker)."""
    try:
        names = sorted((n for n in os.listdir(ckpt_dir)
                        if n.startswith("ckpt-")), reverse=True)
    except FileNotFoundError:
        return None
    for name in names:
        path = os.path.join(ckpt_dir, name)
        mpath = os.path.join(path, "MANIFEST.json")
        try:
            with open(mpath, encoding="utf-8") as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        return path, manifest
    return None


class _WorkerState:
    """One worker process's serving state: its owned partitions and the
    WAL replay high-water mark."""

    def __init__(self, stores: dict, applied_seq: int, wal_dir: str):
        self.stores = stores  # sid -> GStore (owned partitions)
        self.applied_seq = applied_seq
        self.wal_dir = wal_dir
        self.lock = make_lock("procs.worker.state")

    def sync(self, upto_seq: int) -> int:
        """Replay the parent WAL tail (read-only) through the normal
        mutation paths; returns the new high-water mark. Cheap no-op when
        the parent has committed nothing new."""
        from wukong_tpu.store.dynamic import insert_triples
        from wukong_tpu.store.wal import replay_dir
        from wukong_tpu.vector.vstore import apply_vector_record

        with self.lock:
            if not self.wal_dir or upto_seq <= self.applied_seq:
                return self.applied_seq
            for rec in replay_dir(self.wal_dir,
                                  after_seq=self.applied_seq):
                if rec.kind == "vector":
                    for g in self.stores.values():
                        apply_vector_record(g, rec.payload)
                else:
                    # plain insert — or an epoch without stream context
                    # (recovery.py's no-stream branch): the data must not
                    # be lost; insert_triples filters to each partition
                    for g in self.stores.values():
                        insert_triples(g, rec.payload["triples"],
                                       dedup=rec.payload.get("dedup", True),
                                       check_ids=False)
                self.applied_seq = rec.seq
            return self.applied_seq


def _serve_connection(sock, state: _WorkerState) -> None:
    from wukong_tpu.runtime.transport import (
        FrameDecoder,
        encode_frame,
        pack_error,
        pack_reply,
        run_op,
        unpack_message,
    )
    from wukong_tpu.utils.errors import ErrorCode, WukongError

    dec = FrameDecoder()
    try:
        while True:
            chunk = sock.recv(1 << 20)
            if not chunk:
                return
            for payload in dec.feed(chunk):
                try:
                    op, sid, args = unpack_message(payload)
                    if op == "sync":
                        result = state.sync(args[0])
                    elif op == "ping":
                        # piggyback the parent's committed seq: a worker
                        # answering a heartbeat is also caught up
                        state.sync(args[0])
                        g = state.stores.get(sid)
                        if g is None:
                            g = state.stores[min(state.stores)]
                        result = run_op(op, g, *args)
                    else:
                        g = state.stores.get(sid)
                        if g is None:
                            raise WukongError(
                                ErrorCode.SHARD_UNAVAILABLE,
                                f"worker does not own shard {sid}")
                        result = run_op(op, g, *args)
                    reply = encode_frame(pack_reply(result))
                except WukongError as e:
                    reply = encode_frame(pack_error(int(e.code), e.detail))
                except Exception as e:  # noqa: BLE001 — a handler crash
                    # must answer (the parent fails over); it must not
                    # kill the serve thread
                    reply = encode_frame(pack_error(
                        int(ErrorCode.SHARD_UNAVAILABLE),
                        f"worker op failed: {e!r:.200}"))
                sock.sendall(reply)
    except OSError:
        return  # peer went away; the parent reconnects
    finally:
        try:
            sock.close()
        except OSError:
            pass


def worker_main(conn, group_id: int, shard_ids: list, num_shards: int,
                ckpt_dir: str, wal_dir: str, knobs: dict) -> None:
    """Entry point of one worker process (spawn context): recover the
    owned partitions (newest checkpoint + WAL tail — the normal PR 5
    paths), then serve transport ops on a loopback socket forever."""
    # a chip belongs to one process and the parent may hold it: a worker is
    # numpy-only, and should jax ever load here it must not ask for the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    from wukong_tpu.store.dynamic import insert_triples
    from wukong_tpu.store.persist import (
        checkpoint_part_path,
        gstore_digest,
        load_gstore,
    )
    from wukong_tpu.store.wal import replay_dir
    from wukong_tpu.utils.errors import CheckpointCorrupt
    from wukong_tpu.vector.vstore import apply_vector_record

    try:
        for k, v in knobs.items():
            try:
                Global.set(k, v)
            except Exception:  # noqa: BLE001 — immutable/renamed knob
                pass
        found = _newest_bundle(ckpt_dir)
        if found is None:
            conn.send(("error", f"no checkpoint bundle in {ckpt_dir}"))
            return
        path, manifest = found
        wal_seq = int(manifest.get("wal_seq", -1))
        stores: dict = {}
        for sid in shard_ids:
            idx = next((j for j, p in enumerate(manifest.get("parts", []))
                        if int(p.get("sid", -1)) == int(sid)
                        and int(p.get("num_workers", 0)) == num_shards),
                       None)
            if idx is None:
                conn.send(("error",
                           f"bundle {path} has no part for shard {sid}"))
                return
            stores[int(sid)] = load_gstore(checkpoint_part_path(path, idx))
        # WAL tail replay with recovery.py's contiguity rule: a gap means
        # acknowledged records were truncated away behind some OTHER
        # checkpoint — applying the rest would silently skip mutations
        prev_seq = wal_seq
        if wal_dir:
            for rec in replay_dir(wal_dir, after_seq=wal_seq):
                if rec.seq != prev_seq + 1:
                    raise CheckpointCorrupt(
                        f"WAL gap: record {rec.seq} follows {prev_seq}",
                        path=wal_dir)
                prev_seq = rec.seq
                if rec.kind == "vector":
                    for g in stores.values():
                        apply_vector_record(g, rec.payload)
                else:
                    for g in stores.values():
                        insert_triples(g, rec.payload["triples"],
                                       dedup=rec.payload.get("dedup", True),
                                       check_ids=False)
        state = _WorkerState(stores, prev_seq, wal_dir)
        server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        server.bind(("127.0.0.1", 0))
        server.listen(16)
        digests = {sid: int(gstore_digest(g)) for sid, g in stores.items()}
        conn.send(("ready", server.getsockname()[1], digests,
                   int(prev_seq), "jax" in sys.modules))
    except Exception as e:  # noqa: BLE001 — boot failure must reach the
        # supervisor as a message, not a silent exit code
        try:
            conn.send(("error", f"{type(e).__name__}: {e}"))
        except OSError:
            pass
        return
    while True:
        try:
            cli, _addr = server.accept()
        except OSError:
            return
        cli.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        t = threading.Thread(target=_serve_connection, args=(cli, state),
                             daemon=True)
        t.start()


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------

class _Group:
    """Supervisor bookkeeping for one worker process."""

    def __init__(self, gid: int, shard_ids: list):
        self.gid = gid
        self.shard_ids = list(shard_ids)
        self.proc = None
        self.addr = None
        self.misses = 0
        self.restarts = 0  # consecutive failed/backed-off restarts
        self.serving: set = set()  # shards whose digest matched (peered)


def _metrics():
    from wukong_tpu.obs.metrics import get_registry

    reg = get_registry()
    return (
        reg.counter("wukong_proc_restarts_total",
                    "Worker processes restarted by the supervisor",
                    labels=("group",)),
        reg.counter("wukong_proc_heartbeat_misses_total",
                    "Supervisor heartbeats a worker failed to answer",
                    labels=("group",)),
    )


class ProcSupervisor:
    """Own the worker pool for one sharded store: spawn, heartbeat,
    restart-with-recovery, and the SocketTransport peer registry.

    Lifecycle: ``start()`` checkpoints the current stores (workers boot
    from it), spawns the pool, installs a SocketTransport on the sstore;
    ``stop()`` tears the pool down and restores the previous transport.
    ``kill()`` SIGKILLs one worker — the chaos drill's hammer."""

    def __init__(self, sstore, ckpt_dir: str, wal_dir: str | None = None,
                 recovery=None):
        from wukong_tpu.runtime.transport import SocketTransport
        from wukong_tpu.store.wal import active_wal

        self.sstore = sstore
        self.ckpt_dir = ckpt_dir
        wal = active_wal()
        self.wal_dir = (wal_dir if wal_dir is not None
                        else (wal.dir if wal is not None else ""))
        self._recovery = recovery  # optional RecoveryManager for checkpoints
        self.transport = SocketTransport()
        self._prev_transport = None
        self._lock = make_lock("procs.state")
        # table shape changes (start/stop) hold _lock; readers iterate a
        # live dict (CPython-atomic) and _Group fields are single-writer
        self.groups: dict[int, _Group] = {}  # lock-free: single-writer table; per-group fields owned by heartbeat thread
        self._ctx = multiprocessing.get_context("spawn")
        self._hb_thread = None
        self._hb_stop = threading.Event()
        self._m_restarts, self._m_misses = _metrics()
        self.worker_jax_loaded: bool | None = None  # drill/test probe

    # -- lifecycle -------------------------------------------------------
    def _checkpoint(self) -> None:
        if self._recovery is not None:
            self._recovery.checkpoint()
            return
        from wukong_tpu.runtime.recovery import RecoveryManager

        rm = RecoveryManager(lambda: list(self.sstore.stores),
                             sstore=self.sstore, ckpt_dir=self.ckpt_dir)
        rm.checkpoint()

    def start(self, checkpoint: bool = True) -> None:
        from wukong_tpu.obs.events import emit_event

        if checkpoint:
            self._checkpoint()
        D = self.sstore.D
        W = max(1, min(int(Global.proc_workers), D))
        # contiguous split: shard i -> group i * W // D
        with self._lock:
            for gid in range(W):
                shard_ids = [i for i in range(D) if i * W // D == gid]
                self.groups[gid] = _Group(gid, shard_ids)
        for grp in self.groups.values():
            self._spawn(grp)
        self._prev_transport = self.sstore.transport
        self.sstore.transport = self.transport
        self._hb_stop.clear()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           name="proc-heartbeat",
                                           daemon=True)
        self._hb_thread.start()
        emit_event("proc.pool.start", workers=W,
                   shards=D, ckpt_dir=self.ckpt_dir)

    def stop(self) -> None:
        from wukong_tpu.obs.events import emit_event

        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5)
            self._hb_thread = None
        if self._prev_transport is not None:
            self.sstore.transport = self._prev_transport
            self._prev_transport = None
        self.transport.close()
        with self._lock:
            groups, self.groups = dict(self.groups), {}
        for grp in groups.values():
            if grp.proc is not None and grp.proc.is_alive():
                grp.proc.terminate()
                grp.proc.join(timeout=5)
        emit_event("proc.pool.stop", workers=len(groups))

    # -- spawn / restart -------------------------------------------------
    def _spawn(self, grp: _Group, timeout_s: float = 60.0) -> bool:
        """Spawn (or respawn) one group's worker and wait for its
        recovery to finish: checkpoint load + WAL-tail replay, proven by
        a per-shard digest match against the parent's live stores. Only
        matching shards get peered; a mismatch stays parent-served."""
        from wukong_tpu.obs.events import emit_event
        from wukong_tpu.store.persist import gstore_digest

        knobs = {k: getattr(Global, k) for k in _INHERITED_KNOBS}
        knobs["wal_dir"] = ""  # workers never append; replay is read-only
        parent_conn, child_conn = self._ctx.Pipe()
        proc = self._ctx.Process(
            target=worker_main,
            args=(child_conn, grp.gid, grp.shard_ids, self.sstore.D,
                  self.ckpt_dir, self.wal_dir, knobs),
            daemon=True, name=f"wukong-worker-{grp.gid}")
        proc.start()
        child_conn.close()
        if not parent_conn.poll(timeout_s):
            log_warn(f"proc group {grp.gid}: worker did not report within "
                     f"{timeout_s}s; leaving shards parent-served")
            proc.terminate()
            return False
        try:
            msg = parent_conn.recv()
        except (EOFError, OSError):
            log_warn(f"proc group {grp.gid}: worker died before reporting; "
                     "leaving shards parent-served")
            proc.join(timeout=5)
            return False
        if msg[0] != "ready":
            log_warn(f"proc group {grp.gid}: worker boot failed: {msg[1]}")
            proc.join(timeout=5)
            return False
        _tag, port, digests, applied_seq, jax_loaded = msg
        self.worker_jax_loaded = bool(jax_loaded)
        grp.proc = proc
        grp.addr = ("127.0.0.1", int(port))
        grp.misses = 0
        grp.serving = set()
        for sid in grp.shard_ids:
            want = int(gstore_digest(self.sstore.stores[sid]))
            got = int(digests.get(sid, -1))
            if got != want:
                log_warn(f"proc group {grp.gid}: shard {sid} digest "
                         f"mismatch after recovery (worker {got:#x}, "
                         f"parent {want:#x}); keeping it parent-served")
                continue
            grp.serving.add(sid)
            self.transport.register_peer(sid, grp.addr)
            # the outage is over for this shard: close its breaker so the
            # next fetch goes straight back to the (new) primary path
            self.sstore.breaker.record_success(sid)
        log_info(f"proc group {grp.gid}: worker pid={proc.pid} serving "
                 f"{sorted(grp.serving)} on port {port} "
                 f"(wal seq {applied_seq})")
        emit_event("proc.worker.ready", group=grp.gid, pid=proc.pid,
                   shards=sorted(grp.serving), wal_seq=int(applied_seq))
        return bool(grp.serving)

    def _deregister(self, grp: _Group) -> None:
        for sid in list(grp.serving):
            self.transport.deregister_peer(sid)
        grp.serving = set()

    def kill(self, gid: int) -> int:
        """SIGKILL one worker (the drill's mid-stream hammer); returns the
        dead pid. Peers stay registered on purpose: in-flight and
        subsequent fetches must discover the death the hard way (connect
        refused → retries → breaker → replica failover) exactly like a
        real crash, until the heartbeat notices and restarts."""
        grp = self.groups[gid]
        pid = grp.proc.pid
        os.kill(pid, signal.SIGKILL)
        grp.proc.join(timeout=10)
        return pid

    def restart(self, gid: int) -> bool:
        """Restart one group's worker through the full recovery path,
        with capped-exponential backoff between consecutive attempts."""
        from wukong_tpu.obs.events import emit_event

        grp = self.groups[gid]
        self._deregister(grp)
        if grp.proc is not None and grp.proc.is_alive():
            grp.proc.terminate()
        if grp.proc is not None:
            grp.proc.join(timeout=10)
        backoff_ms = min(
            int(Global.proc_restart_backoff_ms) * (2 ** grp.restarts),
            int(Global.proc_restart_backoff_max_ms))
        if grp.restarts > 0 or backoff_ms > 0:
            time.sleep(backoff_ms / 1000.0)
        ok = self._spawn(grp)
        if ok:
            grp.restarts = 0
        else:
            grp.restarts += 1
        self._m_restarts.labels(group=str(gid)).inc()
        emit_event("proc.restart", group=gid, ok=ok,
                   backoff_ms=int(backoff_ms))
        return ok

    # -- heartbeat -------------------------------------------------------
    def _committed_seq(self) -> int:
        from wukong_tpu.store.wal import active_wal

        wal = active_wal()
        return (wal.next_seq - 1) if wal is not None else -1

    def _ping(self, grp: _Group) -> bool:
        if grp.addr is None or not grp.serving:
            return False
        sid = min(grp.serving)
        try:
            out = self.transport.call(grp.addr, "ping", sid,
                                      (self._committed_seq(),))
        except Exception:  # noqa: BLE001 — any failure shape is a miss;
            # classification is the restart's job
            return False
        return int(out.get("sid", -1)) == sid

    def _heartbeat_loop(self) -> None:
        period = max(int(Global.proc_heartbeat_ms), 10) / 1000.0
        misses_allowed = max(int(Global.proc_heartbeat_misses), 1)
        while not self._hb_stop.wait(period):
            with self._lock:
                groups = list(self.groups.values())
            for grp in groups:
                if self._hb_stop.is_set():
                    return
                if grp.proc is None:
                    continue
                if self._ping(grp):
                    grp.misses = 0
                    continue
                grp.misses += 1
                self._m_misses.labels(group=str(grp.gid)).inc()
                if grp.misses >= misses_allowed:
                    log_warn(f"proc group {grp.gid}: "
                             f"{grp.misses} consecutive heartbeat misses; "
                             "restarting the worker")
                    grp.misses = 0
                    self.restart(grp.gid)

    # -- drill / test helpers -------------------------------------------
    def sync(self) -> None:
        """Push the WAL tail to every live worker (the heartbeat does this
        continuously; drills call it for a deterministic barrier)."""
        seq = self._committed_seq()
        for grp in self.groups.values():
            if grp.serving:
                self.transport._retry_call(min(grp.serving), "sync", (seq,))

    def worker_digests(self, gid: int) -> dict:
        """Per-shard content digests served by one live worker."""
        grp = self.groups[gid]
        return {sid: int(self.transport._retry_call(sid, "digest", ()))
                for sid in sorted(grp.serving)}

    def group_of(self, sid: int) -> int:
        for gid, grp in self.groups.items():
            if sid in grp.shard_ids:
                return gid
        raise KeyError(sid)
