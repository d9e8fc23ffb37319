"""Process-backed replica pool for the asyncio pricing gateway.

:class:`ProcessReplica` satisfies the same ``price_chunk(ChunkSpec) ->
ChunkResult`` protocol as ``serve/replica.py::LocalReplica`` but executes
every chunk in a **spawned worker process** — one process per replica, so
replicas stop sharing a GIL and a jit cache, and a replica "crash" is a
real ``kill -9``, not an injected exception.  The paper's §4.2 workers
are exactly this shape: independent processes with explicit
synchronisation, reassigned work when one falls behind.

Lifecycle (see ``docs/SERVING.md`` for the operator's guide)::

    spawn ──► warmup chunk (compiles the bucket's program) ──► ready
      │            │                                            │
      │            │ never acks within warmup_timeout_s         │ price_chunk
      │            ▼                                            ▼
      │        SIGKILL + ReplicaCrash                   send ChunkSpec.to_wire()
      │                                                         │
      │     ┌── deadline (call_timeout_s) ── SIGKILL ──┐        │
      └─────┤                                          ├◄───────┤
            └── pipe EOF / worker exit ── ReplicaCrash ┘        ▼
                                                     recv ChunkResult.from_wire()

Everything crossing the pipe is the versioned wire schema of
``serve/core.py`` (``to_wire``/``from_wire``) — plain scalars, tuples and
numpy arrays, never a live mesh or a callable.  The worker resolves the
chunk's ``devices=`` *count* against its own jax runtime, so a pool can
in principle span heterogeneous hosts.

Fault semantics match the gateway's thread-pool contract exactly:

* a **hung** worker (no reply within ``call_timeout_s``) is killed with
  SIGKILL and :class:`~repro.serve.replica.ReplicaCrash` raised — the
  gateway marks the slot dead, re-queues the in-flight chunk, and (with
  ``restart_s``) respawns a fresh process through the same factory;
* a **dead** worker is detected by pipe EOF or the process sentinel
  (exitcode), again surfacing as :class:`ReplicaCrash`;
* a **request** error (e.g. a PWL capacity ``OverflowError``) is
  re-raised under its own type — the worker stays alive and healthy.

Process replicas run on the CPU only.  The gateway parent has already
imported JAX, and on an accelerator host it holds the chip, so a worker
could not reach it: off the CPU, starting a :class:`ProcessReplica`
raises (the thread pool is the served path there).

:class:`ReplicaPool` is the factory the gateway consumes via
``pool={"thread","process"}``: ``factory(i)`` builds replica ``i`` and is
also what ``restart_s`` respawn calls, so a killed process is replaced by
a *new* process, warmup and all.
"""
from __future__ import annotations

import contextlib
import multiprocessing
import multiprocessing.connection
import os
import signal
import threading
import time
from typing import Dict, Optional

from ..core.platform import active_platform
from .core import ChunkResult, ChunkSpec, _Pending
from .replica import LocalReplica, ReplicaCrash

__all__ = ["ProcessReplica", "ReplicaPool", "warmup_chunk"]


def warmup_chunk(*, n_steps: int = 8, backend: str = "jnp",
                 capacity: int = 16, engine: str = "notc",
                 interpret: Optional[bool] = None,
                 n_paths: int = 256, n_assets: int = 1,
                 exercise_steps: Optional[tuple] = None) -> dict:
    """Wire dict for a 1-row chunk a worker prices on start.

    Pricing it imports jax, sets the platform policy and compiles the
    (padded=1) program for the pool's default bucket — the first real
    request then hits a warm process.  ``rid=-1`` marks it synthetic;
    the result is discarded, only the ack matters.
    """
    key = (100.0, 0.2, 0.1, 0.25, 0.0, "put", 100.0, 110.0,
           n_steps, n_assets, exercise_steps)
    chunk = ChunkSpec(
        bucket=(n_steps, engine), requests=[_Pending(-1, key, 0.0)],
        n_steps=n_steps, engine=engine, capacity=capacity, backend=backend,
        padded=1,
        cols=((100.0,), (0.2,), (0.1,), (0.25,), (0.0,), ("put",),
              (100.0,), (110.0,)),
        n_assets=n_assets, exercise_steps=exercise_steps,
        n_paths=n_paths, interpret=interpret)
    return chunk.to_wire()


def _worker_main(conn, cfg: dict) -> None:
    """Worker process entry point (module-level so spawn can pickle it).

    A strict request/reply loop over ``conn``: every message is a tuple
    whose first element is the op.  Engine execution goes through the
    same ``execute_chunk`` as every other transport — importing it pulls
    in ``repro.core`` whose package init sets the x64 policy, so a spawn
    worker prices bit-identically to the parent.

    ``cfg["faults"]`` maps the worker-local chunk index to a fault kind
    (``"sigkill"`` | ``"exit"`` | ``"hang"``) and ``cfg["hang_warmup"]``
    wedges the warmup ack — the real-process analogue of
    ``FaultyReplica``, used by the fault suite and the kill-injection
    bench.  Faults are *real*: ``sigkill`` is ``os.kill(…, SIGKILL)`` on
    itself, not an exception.
    """
    from .core import execute_chunk      # late: after spawn bootstraps
    faults = {int(k): v for k, v in (cfg.get("faults") or {}).items()}
    calls = 0
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            return                       # parent closed its end / died
        op = msg[0]
        if op == "stop":
            return
        if op == "warmup":
            if cfg.get("hang_warmup"):
                time.sleep(3600.0)       # never acks; parent SIGKILLs us
            t0 = time.perf_counter()
            execute_chunk(ChunkSpec.from_wire(msg[1]))
            conn.send(("ready", os.getpid(), time.perf_counter() - t0))
            continue
        if op == "chunk":
            i, calls = calls, calls + 1
            fault = faults.get(i)
            if fault == "sigkill":
                os.kill(os.getpid(), signal.SIGKILL)
            if fault == "exit":
                # pipe EOF on the result read: close our end, then die
                # without flushing anything
                conn.close()
                os._exit(3)
            if fault == "hang":
                time.sleep(3600.0)       # parent's deadline SIGKILLs us
            try:
                res = execute_chunk(ChunkSpec.from_wire(msg[1]))
            except BaseException as e:   # noqa: BLE001 — forwarded whole
                conn.send(("err", type(e).__name__, str(e)))
            else:
                conn.send(("ok", res.to_wire()))
            continue
        conn.send(("err", "ValueError", f"unknown op {op!r}"))


def _require_cpu_host() -> None:
    """Refuse process replicas where the parent holds an accelerator."""
    platform = active_platform()
    if platform != "cpu":
        raise RuntimeError(
            f"process replicas need the CPU platform, not {platform!r}: "
            "this process already holds the accelerator, so a spawned "
            "worker cannot reach it; use pool='thread'")


class ProcessReplica:
    """A replica that prices chunks in its own spawned process.

    Satisfies the gateway's replica protocol (``name``, ``calls``,
    ``price_chunk``) and adds ``pid``/``alive``/``close()``.  All
    infrastructure failures — deadline exceeded (worker SIGKILLed),
    pipe EOF, worker exit — raise :class:`ReplicaCrash`; once dead the
    replica stays dead (the gateway respawns through the pool factory).

    ``price_chunk`` is serialized by a lock (the gateway runs one call
    in flight per replica anyway); ``close()`` deliberately does *not*
    take it, so killing the process unblocks a concurrent call via the
    process sentinel.
    """

    def __init__(self, name: str = "proc", *, warmup: Optional[dict] = None,
                 call_timeout_s: Optional[float] = None,
                 warmup_timeout_s: float = 120.0,
                 faults: Optional[Dict[int, str]] = None,
                 hang_warmup: bool = False, start: bool = True):
        self.name = name
        self.calls = 0
        self.call_timeout_s = call_timeout_s
        self.warmup_timeout_s = float(warmup_timeout_s)
        self._warmup = warmup
        self._cfg = {"faults": dict(faults or {}),
                     "hang_warmup": bool(hang_warmup)}
        self._lock = threading.Lock()
        self._dead: Optional[str] = None
        self._ready = False
        self._warmup_deadline: Optional[float] = None
        self._conn = None
        self._proc = None
        if start:
            self.start()

    # price_chunk (and everything it calls) runs under _lock; close()
    # is deliberately lock-free — see the class docstring and the
    # reasoned waivers in tools/analysis_waivers.toml.
    GUARDED_BY = {
        "_dead": "_lock", "_ready": "_lock", "calls": "_lock",
        "warmup_seconds": "_lock", "_conn": "_lock", "_proc": "_lock",
        "_warmup_deadline": "_lock",
    }

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #
    def start(self) -> None:
        _require_cpu_host()
        ctx = multiprocessing.get_context("spawn")
        parent, child = ctx.Pipe()
        self._proc = ctx.Process(target=_worker_main, args=(child, self._cfg),
                                 name=self.name, daemon=True)
        self._proc.start()
        child.close()                    # child's end lives in the child
        self._conn = parent
        if self._warmup is None:
            self._ready = True
        else:
            self._conn.send(("warmup", self._warmup))
            self._warmup_deadline = (time.monotonic()
                                     + self.warmup_timeout_s)

    @property
    def pid(self) -> Optional[int]:
        return None if self._proc is None else self._proc.pid

    @property
    def alive(self) -> bool:
        return (self._dead is None and self._proc is not None
                and self._proc.is_alive())

    def close(self) -> None:
        """Kill the worker and release the pipe (idempotent; called by
        the gateway's slot teardown).  Lock-free by design — a blocked
        ``price_chunk`` wakes up via the process sentinel."""
        self._dead = self._dead or "closed"
        self._kill()
        if self._conn is not None:
            with contextlib.suppress(OSError):
                self._conn.close()

    def _kill(self) -> None:
        if self._proc is not None and self._proc.is_alive():
            self._proc.kill()            # SIGKILL — no cooperation needed
            self._proc.join(timeout=10.0)

    def _exitcode(self):
        """The worker's exitcode for diagnostics (joins briefly so a
        just-died process settles to its real code, e.g. -9)."""
        if self._proc is None:
            return None
        self._proc.join(timeout=1.0)
        return self._proc.exitcode

    def _die(self, reason: str) -> ReplicaCrash:  # locked: _lock
        """Mark dead and build (not raise) the crash for the caller.
        Called only from under ``price_chunk``'s lock."""
        self._dead = reason
        if self._conn is not None:
            with contextlib.suppress(OSError):
                self._conn.close()
        return ReplicaCrash(f"{self.name}: {reason}")

    # ------------------------------------------------------------------ #
    # wire I/O
    # ------------------------------------------------------------------ #
    def _recv(self, timeout: Optional[float], what: str):  # locked: _lock
        """One reply off the pipe, racing the worker's death sentinel.

        ``timeout`` None = wait forever (modulo the sentinel).  On
        deadline the worker is SIGKILLed first — a wedged engine call
        holds the jax runtime, so the only safe recovery is a fresh
        process — then :class:`ReplicaCrash` raises.
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            try:
                ready = multiprocessing.connection.wait(
                    [self._conn, self._proc.sentinel], timeout=remaining)
            except OSError:
                raise self._die(f"pipe failed waiting for {what}") from None
            if self._conn in ready:
                try:
                    return self._conn.recv()
                except (EOFError, OSError):
                    raise self._die(
                        f"pipe EOF reading {what} "
                        f"(exitcode {self._exitcode()})") from None
            if ready:                    # sentinel fired: worker exited
                if self._conn.poll(0.1):  # drain a result racing the exit
                    with contextlib.suppress(EOFError, OSError):
                        return self._conn.recv()
                raise self._die(f"worker exited before {what} "
                                f"(exitcode {self._exitcode()})")
            self._kill()                 # timeout: SIGKILL, then report
            raise self._die(
                f"no {what} within {timeout:.3g}s deadline "
                "(worker SIGKILLed)")

    def _ensure_ready(self) -> None:  # locked: _lock
        if self._ready:
            return
        remaining = self._warmup_deadline - time.monotonic()
        if remaining <= 0:
            self._kill()
            raise self._die("never acked the warmup chunk "
                            f"(worker SIGKILLed, pid {self.pid})")
        msg = self._recv(remaining, "warmup ack")
        if msg[0] != "ready":
            raise self._die(f"bad warmup ack {msg[0]!r}")
        self._ready = True
        self.warmup_seconds = float(msg[2])

    # ------------------------------------------------------------------ #
    # replica protocol
    # ------------------------------------------------------------------ #
    def price_chunk(self, chunk: ChunkSpec) -> ChunkResult:
        with self._lock:
            if self._dead is not None:
                raise ReplicaCrash(f"{self.name}: dead ({self._dead})")
            self._ensure_ready()
            self.calls += 1
            try:
                self._conn.send(("chunk", chunk.to_wire()))
            except (BrokenPipeError, OSError):
                raise self._die(
                    f"pipe broke sending chunk "
                    f"(exitcode {self._exitcode()})") from None
            msg = self._recv(self.call_timeout_s, "chunk result")
            if msg[0] == "ok":
                return ChunkResult.from_wire(msg[1])
            if msg[0] == "err":
                _, kind, text = msg
                # request errors come back under their own type so the
                # gateway's healthy-replica retry semantics hold
                if kind == "OverflowError":
                    raise OverflowError(f"{self.name}: {text}")
                raise RuntimeError(f"{self.name}: {kind}: {text}")
            raise self._die(f"bad reply op {msg[0]!r}")


class ReplicaPool:
    """Replica factory the gateway consumes (``pool="thread"|"process"``).

    ``factory(i)`` builds replica ``i``; the gateway calls it both at
    startup and on ``restart_s`` respawn, so a SIGKILLed process replica
    is replaced by a *fresh* process (new pid, new warmup).  The thread
    kind builds :class:`~repro.serve.replica.LocalReplica` — exactly the
    pre-pool behaviour.
    """

    KINDS = ("thread", "process")

    def __init__(self, kind: str = "thread", *,
                 warmup: Optional[dict] = None,
                 call_timeout_s: Optional[float] = None,
                 warmup_timeout_s: float = 120.0,
                 name_prefix: str = "replica"):
        if kind not in self.KINDS:
            raise ValueError(f"pool kind must be one of {self.KINDS}, "
                             f"got {kind!r}")
        self.kind = kind
        self.warmup = warmup
        self.call_timeout_s = call_timeout_s
        self.warmup_timeout_s = warmup_timeout_s
        self.name_prefix = name_prefix

    def factory(self, i: int):
        name = f"{self.name_prefix}-{i}"
        if self.kind == "thread":
            return LocalReplica(name)
        return ProcessReplica(name, warmup=self.warmup,
                              call_timeout_s=self.call_timeout_s,
                              warmup_timeout_s=self.warmup_timeout_s)

    def build(self, n: int) -> list:
        return [self.factory(i) for i in range(n)]
