"""Elastic training manager.

(reference: python/paddle/distributed/fleet/elastic/manager.py:126 —
ElasticManager registers nodes in etcd with TTL leases, watches for
scale in/out, and signals the launcher to restart the job with the new
world. The etcd dependency is replaced by the native TCPStore
(csrc/tcp_store.cpp): heartbeats are timestamped keys, the watcher
thread ages them.)
"""
from __future__ import annotations

import enum
import logging
import threading
import time
from typing import Callable, List, Optional

__all__ = ["ElasticManager", "ElasticStatus"]

logger = logging.getLogger("paddle_tpu.elastic")


class ElasticStatus(enum.Enum):
    COMPLETED = 0
    ERROR = 1
    HOLD = 2
    RESTART = 3
    EXIT = 4


class ElasticManager:
    """Node registry + heartbeat watcher over a TCPStore.

    Each node writes ``/elastic/<job>/nodes/<rank>`` = timestamp every
    ``heartbeat_interval``; the watcher marks the world changed when a
    node's heartbeat ages past ``node_timeout`` (scale-in) or a new rank
    appears (scale-out) and invokes ``on_world_change(alive_ranks)``.
    """

    def __init__(self, store, job_id: str = "default", rank: int = 0,
                 np_: int = 1, heartbeat_interval: float = 1.0,
                 node_timeout: float = 5.0,
                 on_world_change: Optional[Callable] = None):
        self.store = store
        self.job = job_id
        self.rank = rank
        self.np = np_
        self.heartbeat_interval = heartbeat_interval
        self.node_timeout = node_timeout
        self.on_world_change = on_world_change
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._last_world: Optional[tuple] = None
        # guards status: written by the heartbeat/watcher threads and
        # the driver (register/ack/exit) concurrently
        self._state_lock = threading.Lock()
        self.status = ElasticStatus.HOLD

    def _set_status(self, status: "ElasticStatus") -> None:
        with self._state_lock:
            self.status = status

    # -- registration / heartbeat --------------------------------------
    def _node_key(self, rank: int) -> str:
        return f"/elastic/{self.job}/nodes/{rank}"

    def register(self):
        self.store.set(self._node_key(self.rank), str(time.time()))
        t = threading.Thread(target=self._heartbeat_loop, daemon=True)
        t.start()
        self._threads.append(t)
        w = threading.Thread(target=self._watch_loop, daemon=True)
        w.start()
        self._threads.append(w)
        self._set_status(ElasticStatus.HOLD)

    def _heartbeat_loop(self):
        while not self._stop.wait(self.heartbeat_interval):
            try:
                self.store.set(self._node_key(self.rank),
                               str(time.time()))
            except Exception as e:
                # a dead store means THIS node now looks dead to every
                # peer while still running — surface it loudly (status
                # ERROR flips restart_needed) instead of silently
                # letting the pod split-brain
                if not self._stop.is_set():
                    # the reason first: a thread that polls the status
                    # reads the log once it sees ERROR
                    logger.error(
                        "elastic heartbeat for rank %d failed (%s: %s); "
                        "peers will see this node as dead — flagging "
                        "ERROR for the recovery loop", self.rank,
                        type(e).__name__, e)
                    self._set_status(ElasticStatus.ERROR)
                return

    # -- watching -------------------------------------------------------
    def alive_ranks(self) -> List[int]:
        now = time.time()
        alive = []
        for r in range(self.np):
            try:
                if not self.store.check(self._node_key(r)):
                    continue
                # short timeout: the key may vanish between check and get
                ts = float(self.store.get(self._node_key(r), timeout=0.2))
            except Exception:
                continue
            if now - ts <= self.node_timeout:
                alive.append(r)
        return alive

    def _watch_loop(self):
        while not self._stop.wait(self.heartbeat_interval):
            world = tuple(self.alive_ranks())
            if self._last_world is None:
                self._last_world = world
                continue
            if world != self._last_world:
                logger.warning("elastic world changed: %s -> %s",
                               self._last_world, world)
                self._last_world = world
                self._set_status(ElasticStatus.RESTART)
                if self.on_world_change:
                    self.on_world_change(list(world))

    @property
    def restart_needed(self) -> bool:
        """True when recovery must run: a peer changed the world
        (RESTART) or this node's own heartbeat died (ERROR — peers
        already consider us gone)."""
        with self._state_lock:
            return self.status in (ElasticStatus.RESTART,
                                   ElasticStatus.ERROR)

    def ack_world_change(self):
        """Acknowledge a handled RESTART so the manager is reusable
        (e.g. the driver decided the new world is acceptable and
        continues instead of relaunching); the watcher keeps comparing
        against the latest world. ERROR is sticky — a node whose own
        heartbeat died cannot talk itself back to health."""
        with self._state_lock:
            # atomic check-and-set: a concurrent watcher ERROR between
            # the read and the write must not be overwritten to HOLD
            if self.status == ElasticStatus.RESTART:
                self.status = ElasticStatus.HOLD

    def wait_restart(self, timeout: float = 60.0) -> bool:
        """Block until the watcher flags a world change (survivor-side
        recovery gate: stop stepping, checkpoint is already on disk,
        exit for the launcher to relaunch — see resume.py)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.restart_needed:
                return True
            time.sleep(self.heartbeat_interval / 2)
        return False

    def wait_world(self, n: int, timeout: float = 60.0) -> bool:
        """Block until ``n`` live ranks are registered (job start gate —
        the reference's pod-ready barrier)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            if len(self.alive_ranks()) >= n:
                return True
            time.sleep(self.heartbeat_interval / 2)
        return False

    def exit(self, completed: bool = True):
        self._set_status(ElasticStatus.COMPLETED if completed
                         else ElasticStatus.ERROR)
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2)
        try:
            self.store.delete_key(self._node_key(self.rank))
        except Exception:
            pass
