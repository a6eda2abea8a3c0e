"""Host-side object plane.

TPU-native replacement for the reference's pickled-MPI object transport
(reference: chainermn/communicators/mpi_communicator_base.py — object ops
``bcast_obj``/``gather_obj``/``send_obj``/``recv_obj`` built on mpi4py's
pickle-based messaging; module path per SURVEY.md §2.1, reference mount empty).

Here the object world is the set of JAX *processes* (hosts), matching the
reference's node-level object plane. Transport:

* single process — trivial identity paths (the common single-controller case);
* multi-process — pickled payloads ride ``jax.experimental.multihost_utils``
  (uint8 tensors over the DCN collective fabric) for collectives, and the
  ``jax.distributed`` coordinator's KV store for point-to-point, chunked to
  bound coordinator message sizes (the analog of the reference's 256 MB
  ``max_buf_len`` chunking in scatter_dataset).
"""

from __future__ import annotations

import pickle
import threading
import time
from typing import Any, List, Optional

import numpy as np

import jax

from chainermn_tpu.resilience import chaos as _chaos
from chainermn_tpu.resilience.policy import policy as _rpc_policy

# KV-store chunk bound: coordinator values are strings; keep chunks modest.
_KV_CHUNK = 4 * 1024 * 1024

# Every deadline below derives from ONE policy (resilience/policy.py):
# the total per-operation budget (CHAINERMN_TPU_RPC_TIMEOUT_MS, default
# 600 s — the historical scattered constant), the fail-fast probe slice
# (CHAINERMN_TPU_RPC_PROBE_MS, default 10 s) that bounds how long a dead
# coordinator goes unnoticed, and the jittered-exponential retry ladder.

# seeded by every ObjectPlane at construction; read by the liveness probes
_ALIVE_KEY = "og/liveness/seed"

# set by post_abort (the global except hook's MPI_Abort analog); checked by
# every liveness probe so peers of a crashed rank raise within one probe
# interval instead of waiting out their collective budgets.
_ABORT_KEY = "og/abort"
_ABORT_FLAG = _ABORT_KEY + "/flag"


class JobAbortedError(RuntimeError):
    """Another process declared the job dead (global except hook)."""


def post_abort(reason: str) -> None:
    """Mark the job aborted for every peer (best-effort, bounded).

    The crashing process may be the coordinator host, where a graceful
    ``jax.distributed.shutdown()`` can block forever waiting for peers that
    are themselves stuck in collectives — so this posts a poison key with a
    short thread-guarded budget and swallows every failure (if the
    coordinator is already gone, peers fail fast via the liveness probe
    instead)."""
    client = _client()
    if client is None:
        return
    try:
        _guard_rpc(lambda: client.key_value_set(
            _ABORT_FLAG, reason[:512]), budget_ms=5_000)
    except Exception:
        pass


def _read_abort(client) -> Optional[str]:
    """The posted abort reason, or None — without ever blocking
    (``key_value_try_get``). A blocking get is NOT an option here: this
    runs on every probe slice of every guarded wait, and a missing key
    would stall it for the full get deadline."""
    try:
        return client.key_value_try_get(_ABORT_FLAG)
    except Exception:  # NotFound: nobody aborted (or coordinator gone —
        return None    # the liveness probe owns that case)


def _client():
    """The jax.distributed coordinator client, or None."""
    try:
        from jax._src import distributed  # noqa: internal, only path to KV store

        return distributed.global_state.client
    except Exception:
        return None


class ObjectPlane:
    """Process-plane object collectives.

    Sequence counters are CLASS-level: every instance in a process shares
    them, because all instances share the coordinator's one key namespace —
    per-instance counters would collide (e.g. a user-made plane and the
    communicator's internal one both starting at seq 0). SPMD discipline
    (every process runs the same program, hence the same call order) keeps
    the counters aligned across processes, exactly like MPI collectives.
    The counters are scoped to the coordinator client: re-initializing
    jax.distributed gives a fresh KV namespace, so planes created after that
    must restart at seq 0 or they desync from peers that start fresh.
    """

    _seq: dict = {}
    # strong ref to the coordinator client the counters belong to; `is`
    # comparison is unambiguous (an id() would be reusable after free)
    _seq_client: Any = None

    def __init__(self) -> None:
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        client = _client()
        if client is not ObjectPlane._seq_client:
            ObjectPlane._seq_client = client
            ObjectPlane._seq.clear()
        self._p2p_seq = ObjectPlane._seq
        if client is not None and self.process_count > 1:
            # seed the liveness key the fail-fast probes read: a get on it
            # returns instantly while the coordinator lives, so any error
            # (incl. client-side deadline) means the coordinator is gone
            try:
                client.key_value_set(_ALIVE_KEY, "1", allow_overwrite=True)
            except TypeError:  # older client without allow_overwrite
                try:
                    client.key_value_set(_ALIVE_KEY, "1")
                except Exception:
                    pass
            except Exception:
                pass

    # -- collectives ----------------------------------------------------

    def bcast_obj(self, obj: Any, root: int = 0) -> Any:
        if self.process_count == 1:
            return obj
        from jax.experimental import multihost_utils

        payload = pickle.dumps(obj) if self.process_index == root else b""
        # Ship (length, data) as uint8; broadcast_one_to_all roots at process 0,
        # so first hop payloads to process 0 over the KV store if root differs.
        # The relay key carries a sequence number like every other KV channel:
        # the coordinator rejects duplicate keys, and a reused key would hand
        # process 0 the previous bcast's stale payload.
        if root != 0:
            seq = self._next_seq(f"bcast_root/{root}")
            if self.process_index == root:
                self._kv_put(f"bcast_root/{root}/{seq}", payload)
            if self.process_index == 0:
                payload = self._kv_get(f"bcast_root/{root}/{seq}")
        n = np.array([len(payload)], dtype=np.int64)
        n = multihost_utils.broadcast_one_to_all(n)
        buf = np.zeros(int(n[0]), dtype=np.uint8)
        if self.process_index == 0 and payload:
            buf = np.frombuffer(payload, dtype=np.uint8).copy()
        buf = multihost_utils.broadcast_one_to_all(buf)
        return pickle.loads(buf.tobytes())

    def allgather_obj(self, obj: Any) -> List[Any]:
        if self.process_count == 1:
            return [obj]
        # KV-store allgather: every process publishes, barriers, reads all.
        seq = self._next_seq("allgather")
        key = f"og/ag/{seq}"
        self._kv_put(f"{key}/{self.process_index}", pickle.dumps(obj))
        self._barrier(f"{key}/barrier", _rpc_policy().barrier_ms())
        return [
            pickle.loads(self._kv_get(f"{key}/{i}"))
            for i in range(self.process_count)
        ]

    def gather_obj(self, obj: Any, root: int = 0) -> Optional[List[Any]]:
        if self.process_count == 1:
            return [obj]
        # like allgather, but only root pays the N reads
        seq = self._next_seq("gather")
        key = f"og/g/{seq}"
        self._kv_put(f"{key}/{self.process_index}", pickle.dumps(obj))
        self._barrier(f"{key}/barrier", _rpc_policy().timeout_ms)
        if self.process_index != root:
            return None
        return [
            pickle.loads(self._kv_get(f"{key}/{i}"))
            for i in range(self.process_count)
        ]

    def scatter_obj(self, objs: Optional[List[Any]], root: int = 0) -> Any:
        if self.process_count == 1:
            assert objs is not None
            return objs[0]
        seq = self._next_seq("scatter")
        key = f"og/sc/{seq}"
        if self.process_index == root:
            assert objs is not None and len(objs) == self.process_count
            for i, o in enumerate(objs):
                if i != root:
                    self._kv_put(f"{key}/{i}", pickle.dumps(o))
        self._barrier(f"{key}/barrier", _rpc_policy().timeout_ms)
        if self.process_index == root:
            return objs[self.process_index]
        return pickle.loads(self._kv_get(f"{key}/{self.process_index}"))

    # -- point-to-point -------------------------------------------------

    def send_obj(self, obj: Any, dest: int, tag: int = 0) -> None:
        if self.process_count == 1:
            raise RuntimeError("send_obj with a single process has no peer")
        seq = self._next_seq(f"p2p/{self.process_index}/{dest}/{tag}")
        self._kv_put(
            f"og/p2p/{self.process_index}/{dest}/{tag}/{seq}", pickle.dumps(obj)
        )

    def recv_obj(self, src: int, tag: int = 0) -> Any:
        if self.process_count == 1:
            raise RuntimeError("recv_obj with a single process has no peer")
        seq = self._next_seq(f"p2p/{src}/{self.process_index}/{tag}")
        data = self._kv_get(
            f"og/p2p/{src}/{self.process_index}/{tag}/{seq}"
        )
        return pickle.loads(data)

    def try_recv_obj(self, src: int, tag: int = 0,
                     timeout_ms: Optional[int] = None) -> Any:
        """Bounded receive that leaves the channel position intact on
        timeout. ``recv_obj`` increments the channel sequence *before*
        the blocking get, so a timed-out wait would permanently desync
        the channel (the next recv skips the object that eventually
        lands). Pollers — ``fleet/transport.py`` ack/data loops — need
        to come back later, so here the sequence is committed only when
        the get succeeds; a miss raises ``TimeoutError`` and the next
        call retries the SAME slot."""
        if self.process_count == 1:
            raise RuntimeError(
                "try_recv_obj with a single process has no peer")
        channel = f"p2p/{src}/{self.process_index}/{tag}"
        seq = self._p2p_seq.get(channel, 0)
        data = self._kv_get(
            f"og/p2p/{src}/{self.process_index}/{tag}/{seq}",
            timeout_ms=timeout_ms)
        self._p2p_seq[channel] = seq + 1
        return pickle.loads(data)

    # -- host barrier ----------------------------------------------------

    def barrier(self, timeout_ms: Optional[int] = None) -> None:
        """Coordinator-backed host barrier across processes.

        Unlike a device-collective barrier (``sync_global_devices``) this
        rides the KV store: it needs no cross-process device computation
        support and every wait is guarded — a dead peer or coordinator
        turns into a bounded ``JobAbortedError``/``TimeoutError`` instead
        of an infinite rendezvous (the watchdog contract)."""
        if self.process_count == 1:
            return
        seq = self._next_seq("host_barrier")
        self._barrier(f"og/hb_barrier/{seq}",
                      timeout_ms if timeout_ms is not None
                      else _rpc_policy().barrier_ms())

    # -- kv helpers (chunked; coordinator values are bounded strings) ----

    def _next_seq(self, channel: str) -> int:
        n = self._p2p_seq.get(channel, 0)
        self._p2p_seq[channel] = n + 1
        return n

    def _kv_put(self, key: str, data: bytes) -> None:
        _chaos.on_rpc("kv_put")
        client = _client()
        nchunks = max(1, (len(data) + _KV_CHUNK - 1) // _KV_CHUNK)

        def put_all():
            # ONE guard thread for the whole put (not one per chunk RPC):
            # large scatters would otherwise spawn hundreds of short-lived
            # threads; the liveness probe still fires every probe slice
            client.key_value_set(f"{key}/n", str(nchunks))
            for c in range(nchunks):
                client.key_value_set_bytes(
                    f"{key}/{c}", data[c * _KV_CHUNK:(c + 1) * _KV_CHUNK])

        # budget scales with payload so multi-GB scatters aren't cut off
        _guard_rpc(put_all, budget_ms=_rpc_policy().put_budget_ms(nchunks))

    def _kv_get(self, key: str, timeout_ms: Optional[int] = None) -> bytes:
        if timeout_ms is None:
            timeout_ms = _rpc_policy().timeout_ms
        nchunks = int(_sliced_get(f"{key}/n", timeout_ms))
        parts = []
        for c in range(nchunks):
            parts.append(_sliced_get(f"{key}/{c}", timeout_ms, raw=True))
        return b"".join(parts)

    def _barrier(self, name: str, timeout_ms: int) -> None:
        _chaos.on_rpc("barrier")
        client = _client()
        # barriers cannot be sliced (a timed-out barrier id is poisoned for
        # every participant), so guard the single long wait with probes
        _guard_rpc(lambda: client.wait_at_barrier(name, timeout_ms),
                   budget_ms=timeout_ms + _rpc_policy().probe_ms)


def _coordinator_alive() -> None:
    """Raise if the job is aborted or the coordinator is unreachable.

    Two checks: (1) the poison key posted by a crashing rank's except hook
    or the watchdog (non-blocking read; missing key = healthy); (2) a
    short get on the
    liveness key every ObjectPlane seeds at construction — it returns
    instantly while the coordinator lives, so ANY error (including a
    client-side deadline against a dead endpoint) means the coordinator is
    gone."""
    client = _client()
    reason = _read_abort(client)
    if reason is not None:
        raise JobAbortedError(
            f"job aborted by a crashed peer: {reason}")
    last = None
    pol = _rpc_policy()
    ladder = pol.liveness_ladder_ms()
    for attempt, attempt_ms in enumerate(ladder):
        # retry ladder: a loaded coordinator may miss one short deadline;
        # back off (jittered) between attempts so N stuck ranks don't
        # hammer a struggling coordinator in lockstep
        try:
            client.blocking_key_value_get(_ALIVE_KEY, attempt_ms)
            return
        except Exception as e:  # noqa: BLE001
            last = e
            if attempt + 1 < len(ladder):
                time.sleep(pol.backoff_ms(attempt) / 1000.0)
    raise RuntimeError(
        f"jax.distributed coordinator unreachable — aborting instead "
        f"of waiting out the full collective timeout: {last}") from last


def _guard_rpc(fn, budget_ms: Optional[int] = None):
    """Run a coordinator RPC that has no deadline of its own on a worker
    thread; while it blocks, probe coordinator liveness every policy probe
    slice and raise promptly if the coordinator is gone (the abandoned
    daemon thread is moot — the caller is about to tear the process
    down)."""
    pol = _rpc_policy()
    if budget_ms is None:
        budget_ms = pol.timeout_ms
    result: dict = {}

    def run():
        try:
            result["v"] = fn()
        except BaseException as e:  # noqa: BLE001 — surfaced to caller
            result["e"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    waited = 0
    while True:
        slice_ms = min(pol.probe_ms, budget_ms - waited)
        th.join(max(slice_ms, 1) / 1000)
        waited += slice_ms
        if not th.is_alive():
            break
        if waited >= budget_ms:
            raise TimeoutError(
                f"coordinator RPC exceeded its {budget_ms} ms budget")
        _coordinator_alive()
    if "e" in result:
        raise result["e"]
    return result.get("v")


def _is_deadline_error(e: Exception) -> bool:
    """Timed-out-waiting-for-key vs transport failure.

    Prefer a structured gRPC status when the client exposes one (``code()``
    on grpc-style errors); fall back to the canonical status NAME in the
    message (jaxlib's XlaRuntimeError stringifies as
    'DEADLINE_EXCEEDED: ...'), and only then to loose wording — gRPC/jaxlib
    phrasing varies across versions and a misclassified transport error
    would be retried while a misclassified deadline aborts the collective.
    """
    code = getattr(e, "code", None)
    if callable(code):
        try:
            name = getattr(code(), "name", "")
            if name:
                return name.upper() == "DEADLINE_EXCEEDED"
        except Exception:
            pass
    # No structured status: accept only the canonical status token and
    # jaxlib's exact key-wait phrasing. Looser matching ("timeout",
    # "timed out" anywhere) classified CONNECTION-timeout transport
    # failures as key-wait deadlines, retrying against a dead coordinator
    # instead of failing fast (bounded by _coordinator_alive, but it
    # delayed abort by whole probe windows).
    msg = str(e).lower()
    return ("deadline_exceeded" in msg
            or "timed out waiting for key" in msg)


def _sliced_get(key: str, timeout_ms: int, raw: bool = False):
    """blocking_key_value_get with the budget sliced into short attempts,
    probing coordinator liveness between slices (fail-fast)."""
    _chaos.on_rpc("kv_get")
    client = _client()
    get = (client.blocking_key_value_get_bytes if raw
           else client.blocking_key_value_get)
    waited = 0
    while True:
        slice_ms = min(_rpc_policy().probe_ms, timeout_ms - waited)
        if slice_ms <= 0:
            raise TimeoutError(
                f"key {key!r} not published within {timeout_ms} ms")
        try:
            return get(key, slice_ms)
        except Exception as e:  # noqa: BLE001
            if not _is_deadline_error(e):
                raise  # transport error: coordinator gone — fail fast
            waited += slice_ms
            _coordinator_alive()


class FsObjectPlane:
    """File-backed point-to-point object plane for supervised fleets.

    The jax.distributed coordinator cannot re-admit a rank after SIGKILL
    (the service pins membership at init), which rules the KV store out
    as the wire for the supervised-restart drill: the whole point is
    that a killed prefill host comes back under
    :class:`~chainermn_tpu.resilience.supervisor.Supervisor` and keeps
    shipping handoffs. This plane keeps the exact ``send_obj`` /
    ``recv_obj`` / ``try_recv_obj`` surface but rides a shared
    directory instead:

    * one subdirectory per directed channel ``(src, dst, tag)``, one
      file per message, named by sequence number;
    * writes are atomic (tmp + ``os.replace``) so a reader can never
      observe a torn message — a SIGKILL mid-write leaves only a tmp
      file the reader ignores;
    * the sender derives its next sequence from the files already on
      disk, so a restarted incarnation continues the channel instead of
      overwriting it; when :meth:`gc` has pruned every consumed file,
      the per-channel ``HWM`` high-water mark supplies the floor, so a
      reborn sender still never reuses a sequence number;
    * the receiver may :meth:`gc` a channel after resolving frames:
      the high-water mark is committed atomically BEFORE any file is
      unlinked, and a reborn receiver seeds its position from it — a
      crash between the two steps at worst re-deletes, never re-reads;
    * every receive is deadline-sliced exactly like the KV-store path
      (``TimeoutError`` on a miss; ``try_recv_obj`` commits the reader
      position only on success).

    Single-host scope: this is the test/drill wire for processes
    sharing a filesystem, not a datacenter transport — the production
    path is :class:`ObjectPlane` over the coordinator.
    """

    def __init__(self, root: str, index: int, count: int) -> None:
        import os as _os

        self.root = root
        self.process_index = int(index)
        self.process_count = int(count)
        self._recv_pos: dict = {}
        _os.makedirs(root, exist_ok=True)

    def _chan_dir(self, src: int, dst: int, tag: int) -> str:
        import os as _os

        return _os.path.join(self.root, f"p2p_{src}_{dst}_{tag}")

    @staticmethod
    def _read_hwm(chan_dir: str) -> int:
        """The channel's GC high-water mark: every seq below it has
        been consumed and pruned (0 when the channel was never GCed)."""
        import os as _os

        try:
            with open(_os.path.join(chan_dir, "HWM")) as f:
                return int(f.read().strip() or 0)
        except (FileNotFoundError, ValueError):
            return 0

    @classmethod
    def _next_seq(cls, chan_dir: str) -> int:
        """Next unused sequence on a channel (restart-safe): one past
        the highest frame still on disk, falling back to the GC
        high-water mark when every consumed frame has been pruned —
        counting files would re-issue seqs after a :meth:`gc`."""
        import os as _os

        try:
            names = _os.listdir(chan_dir)
        except FileNotFoundError:
            return 0
        seqs = [int(n[:-4]) for n in names if n.endswith(".obj")]
        if seqs:
            return max(seqs) + 1
        return cls._read_hwm(chan_dir)

    def send_obj(self, obj: Any, dest: int, tag: int = 0) -> None:
        import os as _os
        import tempfile

        chan = self._chan_dir(self.process_index, dest, tag)
        _os.makedirs(chan, exist_ok=True)
        seq = self._next_seq(chan)
        fd, tmp = tempfile.mkstemp(dir=chan, suffix=".tmp")
        try:
            with _os.fdopen(fd, "wb") as f:
                f.write(pickle.dumps(obj))
                f.flush()
                _os.fsync(f.fileno())
            _os.replace(tmp, _os.path.join(chan, f"{seq:08d}.obj"))
        except BaseException:
            try:
                _os.unlink(tmp)
            except OSError:
                pass
            raise

    def _read_at(self, src: int, tag: int, seq: int,
                 timeout_ms: Optional[int]) -> bytes:
        import os as _os

        pol = _rpc_policy()
        if timeout_ms is None:
            timeout_ms = pol.timeout_ms
        path = _os.path.join(self._chan_dir(src, self.process_index, tag),
                             f"{seq:08d}.obj")
        deadline = time.monotonic() + timeout_ms / 1000.0
        while True:
            try:
                with open(path, "rb") as f:
                    return f.read()
            except FileNotFoundError:
                pass
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(
                    f"object {path!r} not published within {timeout_ms} ms")
            # poll fast: the drill ships small frames on localhost, and a
            # probe-sliced sleep would add whole probe windows of latency
            time.sleep(min(left, 0.005))

    def _pos(self, src: int, tag: int) -> int:
        """Current reader position, seeded from the channel's GC
        high-water mark on first access — a reborn receiver must not
        wait on frames :meth:`gc` already unlinked."""
        chan = (src, tag)
        if chan not in self._recv_pos:
            self._recv_pos[chan] = self._read_hwm(
                self._chan_dir(src, self.process_index, tag))
        return self._recv_pos[chan]

    def recv_obj(self, src: int, tag: int = 0) -> Any:
        seq = self._pos(src, tag)
        self._recv_pos[(src, tag)] = seq + 1
        return pickle.loads(self._read_at(src, tag, seq, None))

    def try_recv_obj(self, src: int, tag: int = 0,
                     timeout_ms: Optional[int] = None) -> Any:
        """Bounded receive; the reader position advances only on
        success, so a timed-out poll retries the same slot later."""
        seq = self._pos(src, tag)
        data = self._read_at(src, tag, seq, timeout_ms)
        self._recv_pos[(src, tag)] = seq + 1
        return pickle.loads(data)

    def gc(self, src: int, tag: int = 0) -> int:
        """Prune this receiver's consumed frames on channel
        ``src → self``. Commits ``HWM = position`` atomically FIRST,
        then unlinks every ``.obj`` below it; returns the number
        pruned. Crash-safe in both orders: a crash before the mark
        leaves extra files (re-GCed later), a crash after it leaves a
        mark that only covers already-consumed frames. Unconsumed
        frames (seq >= position) are never touched, so a sender
        mid-flight loses nothing."""
        import os as _os
        import tempfile

        chan_dir = self._chan_dir(src, self.process_index, tag)
        pos = self._pos(src, tag)
        _os.makedirs(chan_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=chan_dir, suffix=".tmp")
        try:
            with _os.fdopen(fd, "w") as f:
                f.write(str(pos))
                f.flush()
                _os.fsync(f.fileno())
            _os.replace(tmp, _os.path.join(chan_dir, "HWM"))
        except BaseException:
            try:
                _os.unlink(tmp)
            except OSError:
                pass
            raise
        pruned = 0
        for name in _os.listdir(chan_dir):
            if name.endswith(".obj") and int(name[:-4]) < pos:
                try:
                    _os.unlink(_os.path.join(chan_dir, name))
                    pruned += 1
                except OSError:
                    pass                # concurrent GC: already gone
        return pruned
