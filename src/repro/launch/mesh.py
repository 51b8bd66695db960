"""Meshes and multi-host launch plumbing for the mining service.

Multi-host specs add a leading **dcn** axis over processes:
``mesh_from_spec("2x4x1")`` is 2-way DCN data parallelism x 4-way in-host
pair sharding x 1-way word sharding. ``distributed_init`` wires the
process into the fleet (`jax.distributed.initialize`), and ``is_main`` is
the HomebrewNLP-Jax-style coordinator gate (only process 0 binds HTTP /
owns artifact writes).
"""

from __future__ import annotations

import jax

__all__ = [
    "make_host_mesh",
    "mesh_from_spec",
    "distributed_init",
    "is_main",
]


def mesh_from_spec(spec: str) -> jax.sharding.Mesh:
    """Parse a ``--mesh`` CLI spec into a mesh.

    ``"4x2"`` -> 4-way pair sharding x 2-way word sharding over axes
    ``(data, model)``; a bare ``"8"`` means pure word sharding ``(1, 8)`` —
    the row-parallel layout for tables whose bitset rows exceed one device.
    A three-part spec ``"2x4x1"`` adds the leading **dcn** axis over
    processes — axes ``(dcn, data, model)`` — for hybrid DCN x ICI fleets:
    pair batches shard over ``(dcn, data)``, words over ``model``
    (``jax.make_mesh`` orders devices process-major, so the dcn axis falls
    on the slow inter-host links exactly like MaxText's DCN data axis).
    """
    raw = spec.lower().replace("×", "x").split("x")
    if not all(p.isdigit() for p in raw):  # '4x' must error, not flip axes
        raise ValueError(
            f"--mesh spec must be 'MODEL', 'DATAxMODEL' or 'DCNxDATAxMODEL', got {spec!r}"
        )
    parts = [int(p) for p in raw]
    if len(parts) == 1:
        parts = [1, parts[0]]
    if len(parts) == 2:
        axes = ("data", "model")
    elif len(parts) == 3:
        axes = ("dcn", "data", "model")
    else:
        raise ValueError(
            f"--mesh spec must be 'MODEL', 'DATAxMODEL' or 'DCNxDATAxMODEL', got {spec!r}"
        )
    if any(p <= 0 for p in parts):
        raise ValueError(
            f"--mesh spec must be 'MODEL', 'DATAxMODEL' or 'DCNxDATAxMODEL', got {spec!r}"
        )
    return jax.make_mesh(tuple(parts), axes)


def make_host_mesh(data: int = 4, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    if data * model > n:
        data, model = max(1, n // 2), min(2, n) if n > 1 else 1
        if data * model > n:
            data, model = n, 1
    return jax.make_mesh(
        (data, model), ("data", "model"),
    )


def distributed_init(
    coordinator_address: str | None,
    num_processes: int,
    process_id: int,
) -> tuple[int, int]:
    """Join the mining fleet: ``jax.distributed.initialize`` on the given
    coordinator rendezvous. A ``num_processes <= 1`` launch is a no-op (the
    single-host path never pays distributed bootstrap); returns the
    effective ``(process_id, num_processes)`` either way."""
    if num_processes <= 1:
        return 0, 1
    if not coordinator_address:
        raise ValueError("--num-processes > 1 requires --coordinator-address")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return int(jax.process_index()), int(jax.process_count())


def is_main() -> bool:
    """Coordinator gate (HomebrewNLP-Jax ``is_main()`` discipline): exactly
    one process — index 0 — binds the HTTP listener, owns artifact writes
    and merges fleet answers; everyone else runs the peer command loop."""
    return int(jax.process_index()) == 0

