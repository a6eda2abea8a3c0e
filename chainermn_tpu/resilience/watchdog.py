"""Heartbeat/watchdog: convert a dead peer into a bounded error.

A single wedged or killed host is the nastiest pod failure mode: every
symmetric collective blocks on the missing rank, the survivors sit in a
rendezvous with no deadline, and nobody notices until a human does. The
reference had MPI_Abort semantics for *crashes* (global except hook); a
SIGKILL leaves no hook to run.

This watchdog closes the gap at the host plane. Every process runs a
daemon thread that (1) bumps its own heartbeat key in the coordinator KV
store every ``interval_ms`` and (2) watches every peer's key; a peer
whose heartbeat stops advancing for ``timeout_ms`` is declared dead, the
abort poison key is posted (``object_plane.post_abort``), and every
process blocked in a guarded host-plane operation raises
:class:`~chainermn_tpu.comm.object_plane.JobAbortedError` within one
probe interval — an infinite hang becomes a bounded, catchable error
that restart orchestration can act on.

Configuration (env):

* ``CHAINERMN_TPU_HEARTBEAT_MS`` — beat/check cadence (default 5000);
* ``CHAINERMN_TPU_HEARTBEAT_TIMEOUT_MS`` — staleness threshold before a
  peer is declared dead (default 6 × the cadence);
* ``CHAINERMN_TPU_WATCHDOG=1`` — lets :func:`maybe_start_watchdog`
  (called by the Trainer) start it without code changes.

Device-plane collectives (XLA rendezvous) cannot be interrupted from
Python; the watchdog bounds every *host-plane* wait and makes the death
visible to the step loop between dispatches — the documented contract
(docs/fault_tolerance.md).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

_ENV_INTERVAL = "CHAINERMN_TPU_HEARTBEAT_MS"
_ENV_TIMEOUT = "CHAINERMN_TPU_HEARTBEAT_TIMEOUT_MS"
_ENV_ENABLE = "CHAINERMN_TPU_WATCHDOG"

_HB_PREFIX = "og/hb"


def _env_ms(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        return default
    return v if v > 0 else default


class Watchdog:
    """One process's heartbeat publisher + peer monitor.

    ``client`` duck-types the jax.distributed coordinator client
    (``key_value_set``, ``key_value_try_get``)
    so tests can drive it with a fake; production passes None and the
    real client is resolved lazily.
    """

    def __init__(self, rank: int, world: int,
                 client=None,
                 interval_ms: Optional[int] = None,
                 timeout_ms: Optional[int] = None,
                 on_dead=None):
        self.rank = rank
        self.world = world
        self._client_override = client
        self.interval_ms = interval_ms if interval_ms is not None else (
            _env_ms(_ENV_INTERVAL, 5_000))
        self.timeout_ms = timeout_ms if timeout_ms is not None else (
            _env_ms(_ENV_TIMEOUT, 6 * self.interval_ms))
        self._on_dead = on_dead
        self._beat = 0
        self._overwrite_ok: Optional[bool] = None
        # peer -> (last seen value, monotonic time it last advanced)
        self._seen: Dict[int, tuple] = {}
        self.dead_peer: Optional[int] = None
        self.dead_reason: Optional[str] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- kv access -------------------------------------------------------

    def _client(self):
        if self._client_override is not None:
            return self._client_override
        from chainermn_tpu.comm import object_plane

        return object_plane._client()

    def _publish(self, client) -> None:
        self._beat += 1
        key = f"{_HB_PREFIX}/{self.rank}"
        if self._overwrite_ok is not False:
            try:
                client.key_value_set(key, str(self._beat),
                                     allow_overwrite=True)
                self._overwrite_ok = True
                return
            except TypeError:  # older client: no allow_overwrite kwarg
                self._overwrite_ok = False
            except Exception:
                return  # coordinator trouble: peers' probes handle it
        # no-overwrite fallback: versioned keys; readers scan forward
        try:
            client.key_value_set(f"{key}/{self._beat}", "1")
        except Exception:
            pass

    def _read_peer(self, client, peer: int) -> Optional[str]:
        key = f"{_HB_PREFIX}/{peer}"
        if self._overwrite_ok is not False:
            val = self._try_get(client, key)
            if val is not None:
                return val
        # versioned-key fallback: has the peer advanced past what we saw?
        last = self._seen.get(peer, (None, 0.0))[0]
        nxt = int(last) + 1 if str(last).isdigit() else 1
        if self._try_get(client, f"{key}/{nxt}") is not None:
            return str(nxt)
        # a peer we have never actually read stays None — the startup
        # grace in _check_peers owns that case
        return str(last) if last is not None else None

    @staticmethod
    def _try_get(client, key: str) -> Optional[str]:
        try:
            return client.key_value_try_get(key)
        except Exception:  # NotFound
            return None

    # -- monitoring ------------------------------------------------------

    def _check_peers(self, client) -> None:
        now = time.monotonic()
        for peer in range(self.world):
            if peer == self.rank:
                continue
            val = self._read_peer(client, peer)
            if val is None:
                # never seen: startup grace — start the staleness clock
                self._seen.setdefault(peer, (None, now))
                val, since = self._seen[peer]
                if val is None and (now - since) * 1000 > 2 * self.timeout_ms:
                    self._declare_dead(peer, "never published a heartbeat")
                continue
            prev = self._seen.get(peer)
            if prev is None or prev[0] != val:
                self._seen[peer] = (val, now)
            elif (now - prev[1]) * 1000 > self.timeout_ms:
                self._declare_dead(
                    peer, f"heartbeat stalled at beat {val} for "
                          f"{int((now - prev[1]) * 1000)} ms")

    def _declare_dead(self, peer: int, why: str) -> None:
        if self.dead_peer is not None:
            return
        self.dead_peer = peer
        self.dead_reason = f"watchdog(rank {self.rank}): peer {peer} {why}"
        try:
            from chainermn_tpu.comm.object_plane import post_abort

            post_abort(self.dead_reason)
        except Exception:
            pass
        if self._on_dead is not None:
            try:
                self._on_dead(peer, self.dead_reason)
            except Exception:
                pass

    def check(self) -> None:
        """Raise JobAbortedError if this watchdog declared a peer dead —
        the step loop's cheap per-iteration poll."""
        if self.dead_peer is not None:
            from chainermn_tpu.comm.object_plane import JobAbortedError

            raise JobAbortedError(self.dead_reason)

    # -- thread lifecycle ------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            client = self._client()
            if client is not None:
                try:
                    self._publish(client)
                    self._check_peers(client)
                except Exception:
                    pass  # transient coordinator trouble: retry next beat
            if self.dead_peer is not None:
                return  # job is aborted; nothing further to monitor
            self._stop.wait(self.interval_ms / 1000.0)

    def start(self) -> "Watchdog":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name=f"chainermn-watchdog-{self.rank}",
                daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


_watchdog: Optional[Watchdog] = None


def start_watchdog(interval_ms: Optional[int] = None,
                   timeout_ms: Optional[int] = None) -> Optional[Watchdog]:
    """Start the process-wide watchdog (idempotent). Returns None in a
    single-process job — there is no peer to watch."""
    global _watchdog
    import jax

    if jax.process_count() <= 1:
        return None
    if _watchdog is None:
        _watchdog = Watchdog(jax.process_index(), jax.process_count(),
                             interval_ms=interval_ms,
                             timeout_ms=timeout_ms)
    return _watchdog.start()


def maybe_start_watchdog() -> Optional[Watchdog]:
    """Start the watchdog iff $CHAINERMN_TPU_WATCHDOG is truthy — the
    Trainer's opt-in hook."""
    if os.environ.get(_ENV_ENABLE, "").lower() in ("", "0", "false"):
        return None
    return start_watchdog()


def stop_watchdog() -> None:
    global _watchdog
    if _watchdog is not None:
        _watchdog.stop()
        _watchdog = None


def current_watchdog() -> Optional[Watchdog]:
    return _watchdog
