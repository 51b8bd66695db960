"""Host-level fleet collectives over the JAX coordination service.

The multi-host miner needs exactly one cross-process primitive inside the
level loop — summing per-process partial popcounts — plus a handful of
control-plane exchanges (watermark agreement, candidate-pool unions, result
digests). On TPU/GPU pods those could ride the DCN all-reduce, but the CPU
backend does not implement cross-process XLA computations at all
(``Multiprocess computations aren't implemented on the CPU backend``), and
the control-plane exchanges are host-side anyway. So the fleet speaks a
single transport that works on every backend `jax.distributed.initialize`
supports: the coordination-service **key-value store** that already carries
JAX's own bootstrap traffic.

Protocol
--------

Every collective is one *round*. At round ``n`` each process

1. deletes its own round ``n-2`` key (safe: completing round ``n-1`` is a
   rendezvous, so every peer has already read the ``n-2`` keys — see the
   inline proof on :meth:`FleetCollective._gc`),
2. publishes its payload under ``<ns>/<n>/<pid>``,
3. blocking-reads the other ``P-1`` keys.

Rounds are strictly ordered per process and every process must execute the
*same sequence* of collectives — the fleet placement and coordinator are
built so that all collective call sites are driven by globally-identical
state (global counts, fanned-out commands). A peer that dies mid-round
surfaces as :class:`FleetTimeout` on the survivors, which the coordinator
maps to its single-host degradation path.

:class:`LoopbackCollective` is the ``P == 1`` implementation (no
coordination service, zero overhead): it lets every fleet code path run —
and be property-tested — in a single ordinary process.
"""

from __future__ import annotations

import pickle
import threading
import time

import numpy as np

__all__ = [
    "Collective",
    "FleetCollective",
    "FleetDesyncError",
    "FleetTimeout",
    "LoopbackCollective",
]


class FleetTimeout(RuntimeError):
    """A peer failed to publish its round payload within the deadline —
    the fleet-level analogue of a device loss; the coordinator degrades."""


class FleetDesyncError(RuntimeError):
    """Processes disagreed on a value that must be replicated (version
    watermarks, result digests). Always a bug or corruption, never retried."""


class Collective:
    """Interface shared by the loopback and multi-process implementations.

    ``pid`` / ``nproc`` identify this process; :meth:`allgather` is the one
    primitive, everything else derives from it.
    """

    pid: int = 0
    nproc: int = 1

    # cumulative accounting (the fleet's /stats reads these)
    rounds: int = 0
    seconds: float = 0.0
    payload_bytes: int = 0

    def allgather(self, payload: bytes) -> list[bytes]:
        raise NotImplementedError

    def allreduce_sum(self, arr: np.ndarray) -> np.ndarray:
        """Element-wise sum of one equal-shape int64 array per process."""
        arr = np.ascontiguousarray(arr, dtype=np.int64)
        if self.nproc == 1:
            self.rounds += 1
            return arr.copy()
        parts = self.allgather(arr.tobytes())
        out = np.zeros_like(arr)
        for raw in parts:
            out += np.frombuffer(raw, dtype=np.int64).reshape(arr.shape)
        return out

    def allgather_obj(self, obj) -> list:
        """All-gather arbitrary (trusted, in-fleet) python payloads."""
        if self.nproc == 1:
            self.rounds += 1
            return [obj]
        return [pickle.loads(raw) for raw in self.allgather(pickle.dumps(obj))]

    def agree(self, value: bytes, what: str = "value") -> bytes:
        """Assert every process holds the same ``value`` (watermarks,
        digests); returns it. Divergence raises :class:`FleetDesyncError`."""
        if self.nproc == 1:
            self.rounds += 1
            return value
        parts = self.allgather(value)
        for pid, other in enumerate(parts):
            if other != value:
                raise FleetDesyncError(
                    f"{what} diverged: p{self.pid}={value!r} p{pid}={other!r}"
                )
        return value

    def barrier(self, name: str = "sync") -> None:
        self.allgather(name.encode())

    def stats(self) -> dict:
        return {
            "nproc": self.nproc,
            "pid": self.pid,
            "rounds": self.rounds,
            "seconds": round(self.seconds, 6),
            "payload_bytes": self.payload_bytes,
        }


class LoopbackCollective(Collective):
    """Single-process fleet: every collective is the identity."""

    def __init__(self):
        self.pid = 0
        self.nproc = 1
        self.rounds = 0
        self.seconds = 0.0
        self.payload_bytes = 0

    def allgather(self, payload: bytes) -> list[bytes]:
        self.rounds += 1
        self.payload_bytes += len(payload)
        return [payload]

    def __repr__(self) -> str:
        return "LoopbackCollective()"


class FleetCollective(Collective):
    """Key-value-store collectives over ``jax.distributed``'s coordination
    client. Requires ``jax.distributed.initialize`` to have run; one
    instance per process, shared by the store, placement and coordinator
    (rounds are a single global sequence, guarded by a lock so service
    worker threads cannot interleave two collectives)."""

    def __init__(self, *, timeout_s: float = 60.0, namespace: str = "fleet"):
        import jax
        from jax._src import distributed as _dist

        client = _dist.global_state.client
        if client is None:
            raise RuntimeError(
                "FleetCollective needs jax.distributed.initialize() first"
            )
        self._client = client
        self.pid = int(jax.process_index())
        self.nproc = int(jax.process_count())
        self.timeout_s = float(timeout_s)
        self._ns = namespace
        self._round = 0
        self._lock = threading.Lock()
        self.rounds = 0
        self.seconds = 0.0
        self.payload_bytes = 0

    def _gc(self, n: int) -> None:
        # Deleting our round n-2 key at the start of round n is race-free:
        # a blocking read is a rendezvous, so finishing round n-1 implies
        # every peer *started* n-1, which implies every peer *finished* n-2
        # — and finishing n-2 means it read all n-2 keys, ours included.
        if n >= 2:
            try:
                self._client.key_value_delete(f"{self._ns}/{n - 2}/{self.pid}")
            except Exception:
                pass  # GC best-effort; stale keys only cost coordinator RAM

    def allgather(self, payload: bytes) -> list[bytes]:
        if self.nproc == 1:
            self.rounds += 1
            self.payload_bytes += len(payload)
            return [payload]
        t0 = time.perf_counter()
        with self._lock:
            n = self._round
            self._round += 1
            self._gc(n)
            self._client.key_value_set_bytes(f"{self._ns}/{n}/{self.pid}", payload)
            out: list[bytes] = []
            timeout_ms = max(1, int(self.timeout_s * 1000))
            for pid in range(self.nproc):
                if pid == self.pid:
                    out.append(payload)
                    continue
                try:
                    out.append(
                        self._client.blocking_key_value_get_bytes(
                            f"{self._ns}/{n}/{pid}", timeout_ms
                        )
                    )
                except Exception as exc:
                    raise FleetTimeout(
                        f"peer p{pid} missed round {n} within "
                        f"{self.timeout_s:.1f}s: {exc}"
                    ) from exc
            self.rounds += 1
            self.payload_bytes += sum(len(b) for b in out)
            self.seconds += time.perf_counter() - t0
        return out

    def __repr__(self) -> str:
        return f"FleetCollective(pid={self.pid}, nproc={self.nproc})"
