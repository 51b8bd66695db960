"""Chaos tests: the fault-injection harness driving the service's
robustness machinery.

Every scenario asserts convergence, not just survival: a killed/restarted
or degraded service must end up serving the same answer an undisturbed
cold ``mine()`` produces.
"""

import threading
import time

import numpy as np
import pytest

from repro.core import KyivConfig, mine
from repro.service import (
    DeadlineExceeded,
    DeviceFault,
    FaultInjector,
    KillPoint,
    MiningService,
    ResilienceConfig,
    placement_faults,
)


def _rand(seed, n, m, dom=4):
    return np.random.default_rng(seed).integers(0, dom, size=(n, m))


def _sets(result):
    return result.canonical_set()


FAST = ResilienceConfig(
    max_retries=2, backoff_s=0.001, failure_threshold=3, cooldown_s=60.0
)


# ---------------------------------------------------------------------------
# FaultInjector mechanics
# ---------------------------------------------------------------------------


def test_injector_times_and_after():
    inj = FaultInjector()
    inj.arm("site", action="raise", exc=DeviceFault("x"), times=2, after=1)
    inj.check("site")  # hit 1: skipped by after
    with pytest.raises(DeviceFault):
        inj.check("site")
    with pytest.raises(DeviceFault):
        inj.check("site")
    inj.check("site")  # fired out
    assert inj.hits("site") == 4 and inj.fired("site") == 2


def test_null_injector_refuses_arming():
    from repro.service.faults import NULL_INJECTOR

    with pytest.raises(RuntimeError):
        NULL_INJECTOR.arm("site")
    assert NULL_INJECTOR.check("anything") is None


# ---------------------------------------------------------------------------
# Kill mid-mine -> resume from level checkpoint
# ---------------------------------------------------------------------------


def test_kill_mid_mine_resumes_from_checkpoint(tmp_path):
    data = _rand(0, 150, 6, 4)
    cfg = dict(tau=2, kmax=4)
    undisturbed = mine(data, KyivConfig(**cfg))

    d = str(tmp_path / "wal")
    inj = FaultInjector()
    svc = MiningService(engine="numpy", wal_dir=d, fault_injector=inj)
    svc.append(data)
    # die at the second level boundary — after its checkpoint was saved
    inj.arm("mine.level_end", action="raise", exc=KillPoint("mid-mine"), after=1)
    with pytest.raises(KillPoint):
        svc.mine(**cfg)
    svc.close()

    # "restart": a fresh process over the same directory resumes the job
    svc2 = MiningService(engine="numpy", wal_dir=d)
    assert svc2.stats()["durability"]["resumed_jobs"] == 1
    r = svc2.mine(**cfg)  # coalesces onto the resumed run
    assert r.info.get("resumed_from_level", 0) >= 3
    assert _sets(r.result) == _sets(undisturbed)
    svc2.close()


def test_completed_job_leaves_no_checkpoints(tmp_path):
    import os

    d = str(tmp_path / "wal")
    svc = MiningService(engine="numpy", wal_dir=d)
    svc.append(_rand(0, 80, 5, 4))
    svc.mine(tau=2, kmax=3)
    jobs = os.path.join(d, "jobs")
    assert not os.path.isdir(jobs) or os.listdir(jobs) == []
    svc.close()


# ---------------------------------------------------------------------------
# Flaky / dead device -> retry, degrade, recover
# ---------------------------------------------------------------------------


def test_flaky_device_retries_then_succeeds():
    data = _rand(1, 100, 5, 4)
    inj = FaultInjector()
    svc = MiningService.from_dataset(
        data, engine="jnp", interpret=True, fault_injector=inj, resilience=FAST
    )
    with placement_faults(inj):
        inj.arm("placement.dispatch", exc=DeviceFault("transient"), times=1)
        r = svc.mine(tau=2, kmax=3)
    assert svc.device_retries == 1 and svc.degraded_mines == 0
    assert svc.breaker.state == "closed"
    assert _sets(r.result) == _sets(mine(data, KyivConfig(tau=2, kmax=3, engine="numpy")))
    svc.close()


def test_dead_device_degrades_to_host_and_breaker_opens():
    data = _rand(2, 100, 5, 4)
    inj = FaultInjector()
    svc = MiningService.from_dataset(
        data, engine="jnp", interpret=True, fault_injector=inj, resilience=FAST
    )
    with placement_faults(inj):
        inj.arm("placement.dispatch", exc=DeviceFault("dead"), times=10_000)
        r = svc.mine(tau=2, kmax=3)
        assert r.info.get("degraded") == "host"
        assert svc.breaker.state == "open"
        assert svc.readiness() == (False, "circuit_breaker_open")
        # with the breaker open, further requests go straight to the host
        # path without touching the device
        hits_before = inj.hits("placement.dispatch")
        r2 = svc.mine(tau=2, kmax=4)
        assert inj.hits("placement.dispatch") == hits_before
        assert r2.info.get("degraded") == "host"
    cold = mine(data, KyivConfig(tau=2, kmax=4, engine="numpy"))
    assert _sets(r2.result) == _sets(cold)
    stats = svc.stats()["resilience"]
    assert stats["state"] == "open" and stats["degraded_mines"] == 2
    svc.close()


def test_breaker_cooldown_allows_device_recovery():
    data = _rand(3, 90, 5, 4)
    inj = FaultInjector()
    res = ResilienceConfig(
        max_retries=1, backoff_s=0.001, failure_threshold=2, cooldown_s=0.05
    )
    svc = MiningService.from_dataset(
        data, engine="jnp", interpret=True, fault_injector=inj, resilience=res
    )
    with placement_faults(inj):
        inj.arm("placement.dispatch", exc=DeviceFault("dead"), times=10_000)
        svc.mine(tau=2, kmax=3)
        assert svc.breaker.state == "open"
        inj.disarm("placement.dispatch")  # the device "comes back"
        time.sleep(0.06)
        assert svc.breaker.state == "half_open"
        svc.cache.clear()
        r = svc.mine(tau=2, kmax=3)  # the probe: runs on-device, closes
    assert svc.breaker.state == "closed"
    assert r.info.get("degraded") is None
    assert svc.readiness() == (True, "ok")
    svc.close()


# ---------------------------------------------------------------------------
# Compile refusals are bugs: they propagate, they never degrade
# ---------------------------------------------------------------------------

# the chip compiler's refusal of an SMEM-overflowing scalar-prefetch table,
# as jaxlib raises it (tests/test_tpu_compile.py provokes the real one)
_SMEM_REFUSAL = (
    "RESOURCE_EXHAUSTED: Allocation (size=2097152) would exceed memory "
    "(size=1048576) :: #allocation3 [shape = 'u8[2097152]{0}', space=smem, "
    "size = 0x200000, tag = 'prefetched SMEM operand 0'] :: intersect_count_indexed.1"
)


def test_compile_refusal_propagates_and_never_degrades():
    import jax

    data = _rand(5, 100, 5, 4)
    inj = FaultInjector()
    svc = MiningService.from_dataset(
        data, engine="jnp", fault_injector=inj, resilience=FAST
    )
    with placement_faults(inj):
        inj.arm(
            "placement.dispatch",
            exc=jax.errors.JaxRuntimeError(_SMEM_REFUSAL),
            times=10_000,
        )
        with pytest.raises(jax.errors.JaxRuntimeError, match="space=smem"):
            svc.mine(tau=2, kmax=3)
        # raised on the first dispatch: no retry, no host answer
        assert inj.fired("placement.dispatch") == 1
    assert svc.device_retries == 0 and svc.degraded_mines == 0
    assert svc.breaker.state == "closed"
    assert svc.stats()["resilience"]["degraded_mines"] == 0
    svc.close()


def test_runtime_error_type_still_degrades():
    """A real runtime fault (jaxlib's JaxRuntimeError, out of memory while
    running) keeps the retry -> host degradation path."""
    import jax

    data = _rand(6, 100, 5, 4)
    inj = FaultInjector()
    svc = MiningService.from_dataset(
        data, engine="jnp", fault_injector=inj, resilience=FAST
    )
    oom = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
        "allocate 1.00G. That was not possible. There are 512.00M free."
    )
    with placement_faults(inj):
        inj.arm("placement.dispatch", exc=oom, times=10_000)
        r = svc.mine(tau=2, kmax=3)
    assert r.info.get("degraded") == "host"
    assert svc.device_retries == FAST.max_retries and svc.degraded_mines == 1
    assert _sets(r.result) == _sets(mine(data, KyivConfig(tau=2, kmax=3, engine="numpy")))
    svc.close()


@pytest.mark.parametrize(
    "exc,device_failure",
    [
        (DeviceFault("injected"), True),
        ("runtime-oom", True),
        ("device-lost", True),
        ("smem-refusal", False),
        ("mosaic-refusal", False),
        ("hbm-compile-refusal", False),
        (ValueError("The Pallas TPU lowering currently requires that the last two "
                    "dimensions of your block shape are divisible by 8 and 128"), False),
        (KeyError("bug"), False),
    ],
    ids=["injected", "runtime-oom", "device-lost", "smem-refusal",
         "mosaic-refusal", "hbm-compile-refusal", "lowering-refusal", "bug"],
)
def test_failure_classification(exc, device_failure):
    import jax

    from repro.core.placement import is_device_failure

    real = {
        "runtime-oom": "RESOURCE_EXHAUSTED: Out of memory while trying to allocate 8.00G.",
        "device-lost": "INTERNAL: DEVICE_LOST: TPU chip 0 halted",
        "smem-refusal": _SMEM_REFUSAL,
        "mosaic-refusal": "INTERNAL: Mosaic failed to compile TPU kernel: Slice shape "
        "along dimension 0 must be aligned to tiling (8), but is 1.",
        "hbm-compile-refusal": "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
        "Ran out of memory in memory space hbm. Used 17.2G of 15.75G hbm.",
    }
    if isinstance(exc, str):
        exc = jax.errors.JaxRuntimeError(real[exc])
    assert is_device_failure(exc) is device_failure


# ---------------------------------------------------------------------------
# Deadlines and cancellation
# ---------------------------------------------------------------------------


def test_deadline_returns_partial_and_does_not_wedge(tmp_path):
    data = _rand(4, 120, 6, 4)
    inj = FaultInjector()
    svc = MiningService(
        engine="numpy", wal_dir=str(tmp_path / "wal"), fault_injector=inj
    )
    svc.append(data)
    # each level boundary stalls 0.25s; a 0.1s deadline trips at the first
    # batch/level check after it expires
    inj.arm("mine.level_end", action="sleep", seconds=0.25, times=100)
    t0 = time.monotonic()
    r = svc.mine(tau=1, kmax=5, deadline_s=0.1)
    elapsed = time.monotonic() - t0
    assert r.source == "partial"
    assert r.info["interrupted"] == "deadline"
    assert not r.result.completed
    assert elapsed < 2.0  # deadline + one stalled boundary, not the full run
    # partial answers are never cached and the scheduler is not wedged
    inj.reset()
    r2 = svc.mine(tau=1, kmax=5)
    assert r2.source == "cold" and r2.result.completed
    undisturbed = mine(data, KyivConfig(tau=1, kmax=5))
    assert _sets(r2.result) == _sets(undisturbed)
    svc.close()


def test_cancel_stops_inflight_run(tmp_path):
    data = _rand(5, 120, 6, 4)
    inj = FaultInjector()
    svc = MiningService(
        engine="numpy", wal_dir=str(tmp_path / "wal"), fault_injector=inj
    )
    svc.append(data)
    inj.arm("mine.level_end", action="sleep", seconds=0.25, times=100)
    out = {}

    def run():
        out["resp"] = svc.mine(tau=1, kmax=5)

    t = threading.Thread(target=run)
    t.start()
    time.sleep(0.1)  # let the run reach its first stalled boundary
    assert svc.cancel(1, 5)["cancelled"] == 1
    t.join(timeout=10)
    assert out["resp"].source == "partial"
    assert out["resp"].info["interrupted"] == "cancelled"
    svc.close()


def test_coalesced_waiter_deadline(tmp_path):
    """A deadline-free initiator keeps its run; a coalesced waiter with a
    deadline gets DeadlineExceeded instead of blocking on the shared run."""
    data = _rand(6, 120, 6, 4)
    inj = FaultInjector()
    svc = MiningService(
        engine="numpy",
        wal_dir=str(tmp_path / "wal"),
        fault_injector=inj,
        deadline_grace_s=0.05,
    )
    svc.append(data)
    inj.arm("mine.level_end", action="sleep", seconds=0.4, times=3)
    out = {}

    def initiator():
        out["resp"] = svc.mine(tau=1, kmax=5)

    t = threading.Thread(target=initiator)
    t.start()
    time.sleep(0.1)
    with pytest.raises(DeadlineExceeded):
        svc.mine(tau=1, kmax=5, deadline_s=0.05)
    t.join(timeout=30)
    assert out["resp"].result.completed  # the initiator was unaffected
    svc.close()


def test_kill_mid_mine_then_recovery_converges_with_appends(tmp_path):
    """Full chaos loop: append, die mid-mine, restart, append more, mine —
    the final answer matches an undisturbed cold run over all the rows."""
    a, b = _rand(7, 100, 5, 4), _rand(8, 40, 5, 4)
    d = str(tmp_path / "wal")
    inj = FaultInjector()
    svc = MiningService(engine="numpy", wal_dir=d, fault_injector=inj)
    svc.append(a)
    inj.arm("mine.level_end", action="raise", exc=KillPoint("die"), after=1)
    with pytest.raises(KillPoint):
        svc.mine(tau=2, kmax=4)
    svc.close()

    svc2 = MiningService(engine="numpy", wal_dir=d)
    svc2.append(b)  # moves past the dead job's version
    r = svc2.mine(tau=2, kmax=4)
    undisturbed = mine(np.concatenate([a, b]), KyivConfig(tau=2, kmax=4))
    assert _sets(r.result) == _sets(undisturbed)
    svc2.close()
