"""Telemetry substrate: metrics registry, trace spans, logs, profiling.

Covers the observability contracts:

* registry semantics (types, labels, conflicts, collectors) and the
  Prometheus text exposition (linted by the same validator CI uses),
* span trees — nesting, sampling, the ring buffer, contextvar propagation
  across the scheduler's worker-thread hop,
* span timings agreeing with the per-level ``LevelStats`` clocks on the
  host and device paths (and on a forced 8-device mesh, in a subprocess),
* the un-tearable ``/stats``/scrape snapshot with a mine in flight,
* HTTP: ``/metrics`` (>= 20 families, lint-clean), ``X-Trace-Id``
  correlation, ``GET /trace``, JSON logs carrying the trace id,
* the opt-in profiling hook,
* host waits on the device in ``device.wait`` and nowhere else, emission in
  ``level.emit``, the reply's decode / encode / write, compiles as
  ``jax.compile`` spans and cost-envelope counts, and the span tree on the
  profiler's own trace.
"""

import io
import json
import logging
import os
import re
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.obs import logs as obs_logs
from repro.obs import metrics as om
from repro.obs.metrics import MetricsRegistry, lint_exposition
from repro.obs.trace import TRACER, Tracer, current_trace_id

def _rand(seed, n, m, dom=5):
    return np.random.default_rng(seed).integers(0, dom, size=(n, m))


@pytest.fixture()
def tracer_reset():
    """Restore the process-wide tracer's config + ring buffer after a test."""
    yield TRACER
    TRACER.configure(max_traces=64, sample_every=1)
    TRACER.reset()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "req", ("route",))
    c.inc(route="/mine")
    c.inc(2, route="/mine")
    c.inc(route="/stats")
    assert c.value(route="/mine") == 3
    assert c.value(route="/stats") == 1
    assert c.value(route="/never") == 0
    with pytest.raises(ValueError):
        c.inc(-1, route="/mine")
    with pytest.raises(ValueError):
        c.inc(path="/mine")  # wrong label name

    g = reg.gauge("t_depth", "depth")
    g.set(4)
    g.add(-1.5)
    assert g.value() == 2.5

    h = reg.histogram("t_latency_seconds", "lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.series()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(56.05)
    # cumulative per le: 0.1 -> 1, 1.0 -> 3, 10.0 -> 4, +Inf -> 5
    assert [c for _, c in s["buckets"]] == [1, 3, 4, 5]


def test_registry_rejects_conflicting_reregistration():
    reg = MetricsRegistry()
    reg.counter("t_x_total", "x")
    with pytest.raises(ValueError):
        reg.gauge("t_x_total", "x")  # same name, different type
    with pytest.raises(ValueError):
        reg.counter("t_x_total", "x", ("route",))  # different labels
    # identical re-registration returns the same family object
    assert reg.counter("t_x_total", "x") is reg.counter("t_x_total", "x")
    with pytest.raises(ValueError):
        reg.counter("0bad name", "x")


def test_render_is_lint_clean_and_snapshot_agrees():
    reg = MetricsRegistry()
    reg.counter("t_served_total", "served", ("route",)).inc(route="/mine")
    reg.gauge("t_ready", "ready").set(1)
    h = reg.histogram("t_wall_seconds", "wall", buckets=(0.01, 1.0))
    h.observe(0.5)
    text = reg.render()
    assert lint_exposition(text) == []
    assert '# TYPE t_served_total counter' in text
    assert 't_wall_seconds_bucket{le="+Inf"} 1' in text
    snap = reg.snapshot()
    assert snap["t_served_total"]["values"]["/mine"] == 1
    assert snap["t_wall_seconds"]["values"][""]["count"] == 1


def test_lint_catches_bad_expositions():
    assert lint_exposition("# TYPE bad_counter counter\nbad_counter 3\n")
    assert lint_exposition("orphan_sample 1\n")  # sample before TYPE
    assert lint_exposition(
        "# TYPE h histogram\n"
        'h_bucket{le="0.1"} 5\nh_bucket{le="+Inf"} 3\n'  # decreasing
    )


def test_named_collectors_replace_and_owner_checked_unregister():
    reg = MetricsRegistry()
    g = reg.gauge("t_mirror", "mirrored")
    calls = []

    def c1():
        calls.append("c1")
        g.set(1)

    def c2():
        calls.append("c2")
        g.set(2)

    reg.register_collector("svc", c1)
    reg.render()
    assert calls == ["c1"]
    reg.register_collector("svc", c2)  # replacement takes over the slot
    reg.render()
    assert calls == ["c1", "c2"]
    reg.unregister_collector("svc", c1)  # stale owner: must NOT evict c2
    reg.render()
    assert calls[-1] == "c2"
    reg.unregister_collector("svc", c2)
    calls.clear()
    reg.render()
    assert calls == []


def test_broken_collector_never_fails_the_scrape():
    reg = MetricsRegistry()

    def boom():
        raise RuntimeError("collector bug")

    reg.register_collector("bad", boom)
    assert lint_exposition(reg.render()) == []
    assert reg.collector_errors == 1


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_nesting_parent_ids_and_tree():
    tr = Tracer(max_traces=4)
    with tr.start("req") as root:
        with tr.span("outer", k=2) as outer:
            with tr.span("inner"):
                pass
        assert current_trace_id() == root.trace_id
    trace = tr.last(1)[0]
    outer_sp = trace.find("outer")[0]
    inner_sp = trace.find("inner")[0]
    assert outer_sp.parent_id == trace.root.span_id
    assert inner_sp.parent_id == outer_sp.span_id
    assert outer_sp.attrs == {"k": 2}
    d = trace.to_dict()
    assert d["spans"][0]["name"] == "req"
    assert d["spans"][0]["children"][0]["name"] == "outer"
    assert d["spans"][0]["children"][0]["children"][0]["name"] == "inner"
    assert tr.get(root.trace_id) is trace
    assert tr.get("nope") is None


def test_nested_start_trace_joins_the_outer_trace():
    tr = Tracer()
    with tr.start("outer"):
        with tr.start("inner") as sp:  # nests, does not mint a second trace
            sp.set(tag=1)
    assert len(tr.last(10)) == 1
    trace = tr.last(1)[0]
    assert [s.name for s in trace.find("inner")] == ["inner"]
    assert trace.find("inner")[0].parent_id == trace.root.span_id


def test_sampling_and_ring_buffer():
    tr = Tracer(max_traces=3, sample_every=2)
    for _ in range(8):
        with tr.start("req"):
            with tr.span("work"):
                pass
    st = tr.stats()
    assert st["started"] == 8 and st["sampled_out"] == 4
    assert st["stored"] == 3  # ring buffer keeps only the newest 3


def test_ring_overflow_counted_and_paging_never_duplicates():
    """Satellite regression: evictions are observable (``dropped``) and the
    seq-keyed pages of ``GET /trace`` neither overlap nor skip entries."""
    tr = Tracer(max_traces=4, sample_every=1)
    for i in range(10):
        with tr.start("req", meta={"i": i}):
            pass
    st = tr.stats()
    assert st["appended"] == 10 and st["dropped"] == 6 and st["stored"] == 4

    # newest-first pages keyed by seq: churn between page fetches must not
    # re-serve an already-seen trace
    page1, cursor = tr.page(2)
    assert [t.seq for t in page1] == [9, 8] and cursor == 8
    with tr.start("req"):  # churn evicts seq 6 between pages
        pass
    page2, cursor2 = tr.page(2, before=cursor)
    assert [t.seq for t in page2] == [7], [t.seq for t in page2]
    assert cursor2 is None  # ring exhausted: no further page
    seen = {t.seq for t in page1} | {t.seq for t in page2}
    assert len(seen) == 3  # no duplicates across pages

    tr.reset()
    assert tr.stats()["dropped"] == 0 and tr.stats()["appended"] == 0


def test_trace_dropped_counter_in_stats_and_scrape(tracer_reset):
    from repro.service import MiningService

    svc = MiningService.from_dataset(_rand(0, 60, 3))
    try:
        TRACER.configure(max_traces=2)
        for tau in (1, 2, 3, 1, 2):
            svc.mine(tau=tau, kmax=2)
        assert svc.stats()["obs"]["traces"]["dropped"] >= 3
        text = om.REGISTRY.render()
        assert lint_exposition(text) == []
        m = re.search(r"^repro_trace_dropped_total (\d+)", text, re.M)
        assert m and int(m.group(1)) >= 3
    finally:
        svc.close()


def test_span_is_noop_without_active_trace():
    tr = Tracer()
    assert current_trace_id() is None
    with tr.span("orphan") as sp:
        sp.set(ignored=True)  # must not raise
    assert tr.last(10) == []
    assert current_trace_id() is None


def test_scheduler_propagates_trace_context(tracer_reset):
    """The worker-thread hop must carry the active span (copy_context)."""
    from repro.service import RequestScheduler

    sched = RequestScheduler()
    try:
        with TRACER.start("req") as root:
            seen = sched.submit("k", lambda: current_trace_id()).result()
        assert seen == root.trace_id
        # outside any trace the worker sees none either
        assert sched.submit("k2", lambda: current_trace_id()).result() is None
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# span tree vs LevelStats clocks (host + device paths)
# ---------------------------------------------------------------------------


def _mine_traced(engine):
    from repro.core import KyivConfig, mine

    D = _rand(3, 300, 6)
    with TRACER.start("test.mine"):
        result = mine(D, KyivConfig(tau=1, kmax=3, engine=engine))
    return result, TRACER.last(1)[0]


def _check_spans_against_stats(result, trace):
    mine_span = trace.find("mine")[0]
    # the span tree must account for >=95% of the mine's wall time
    assert trace.coverage(mine_span) >= 0.95
    level_spans = sorted(trace.find("mine.level"), key=lambda s: s.t0)
    # level-1 singletons are classified during seeding (the "mine.seed"
    # span); every looped level k>=2 gets its own "mine.level" span
    stats_by_k = {ls.k: ls for ls in result.stats}
    looped = []
    for sp in level_spans:
        ls = stats_by_k[sp.attrs["k"]]
        looped.append(ls)
        # the span wraps the whole level iteration, including the LevelStats
        # bookkeeping itself, so it can only be >= the level's own clock
        assert sp.duration >= ls.time_total * 0.999
        # and it must stay in the same ballpark (not leak another level in)
        assert sp.duration <= ls.time_total * 1.35 + 0.15
    assert {ls.k for ls in looped} == {k for k in stats_by_k if k >= 2}
    # stage spans wrap exactly the regions the stage clocks time
    by_stage = {
        "frontier.candidates": sum(
            s.duration for s in trace.find("frontier.candidates")
        ),
        "intersect": sum(
            s.duration
            for s in trace.find("intersect.dispatch") + trace.find("device.wait")
        ),
        "classify": sum(s.duration for s in trace.find("level.classify")),
    }
    clocks = {
        "frontier.candidates": sum(ls.time_candidates for ls in looped),
        "intersect": sum(ls.time_intersect for ls in looped),
        "classify": sum(ls.time_classify for ls in looped),
    }
    for stage, spanned in by_stage.items():
        assert spanned >= clocks[stage] * 0.95 - 0.01, (stage, spanned, clocks)
        assert spanned <= clocks[stage] * 1.35 + 0.15, (stage, spanned, clocks)


def test_span_tree_matches_level_stats_host(tracer_reset):
    result, trace = _mine_traced("numpy")
    assert len(result.itemsets) > 0
    _check_spans_against_stats(result, trace)


def test_span_tree_matches_level_stats_device(tracer_reset):
    result, trace = _mine_traced("jnp")
    _check_spans_against_stats(result, trace)


_MESH_OBS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import numpy as np
import jax
from repro.core import KyivConfig, MeshPlacement, mine
from repro.obs.trace import TRACER

rng = np.random.default_rng(13)
D = rng.integers(0, 5, size=(200, 7))
ref = mine(D, KyivConfig(tau=2, kmax=4, engine="numpy"))
mesh = jax.make_mesh((2, 4), ("data", "model"))
p = MeshPlacement(mesh, pair_axes=("data",), word_axis="model")
with TRACER.start("mesh.mine"):
    got = mine(D, KyivConfig(tau=2, kmax=4, placement=p))
assert sorted(got.itemsets) == sorted(ref.itemsets)
trace = TRACER.last(1)[0]
mine_span = trace.find("mine")[0]
assert trace.coverage(mine_span) >= 0.95, trace.coverage(mine_span)
levels = trace.find("mine.level")
by_k = {ls.k: ls for ls in got.stats}
assert {sp.attrs["k"] for sp in levels} == {k for k in by_k if k >= 2}
for sp in levels:
    assert sp.duration >= by_k[sp.attrs["k"]].time_total * 0.999
from repro.obs import metrics as om
assert om.REGISTRY.counter(
    "repro_placement_dispatch_total", "", ("site", "kind")
).value(site="dispatch", kind="mesh") > 0
print("MESH_OBS_OK")
"""


@pytest.mark.slow
def test_mesh_span_tree_8dev():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_OBS_SCRIPT, src],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_OBS_OK" in proc.stdout


# ---------------------------------------------------------------------------
# torn-counter regression: scrape with a mine in flight
# ---------------------------------------------------------------------------


def _hist_count_agrees(text):
    """Every histogram series' +Inf cumulative bucket equals its _count —
    the invariant a torn (unlocked) scrape breaks."""
    inf = {}
    counts = {}
    for line in text.splitlines():
        m = re.match(r"(\w+)_bucket\{(.*)le=\"\+Inf\"\}\s+(\d+)", line)
        if m:
            inf[(m.group(1), re.sub(r'le="[^"]*",?', "", m.group(2)))] = int(
                m.group(3)
            )
        m = re.match(r"(\w+)_count(\{.*\})?\s+(\d+)", line)
        if m:
            labels = (m.group(2) or "{}").strip("{}")
            counts[(m.group(1), labels + ("," if labels else ""))] = int(
                m.group(3)
            )
    assert inf, "no histogram series rendered"
    for key, v in inf.items():
        name, labels = key
        ck = (name, labels)
        assert ck in counts and counts[ck] == v, (key, v, counts.get(ck))


def test_stats_and_scrape_are_not_torn_with_mine_in_flight(tracer_reset):
    from repro.service import MiningService

    svc = MiningService.from_dataset(_rand(0, 400, 5))
    stop = threading.Event()
    errors = []

    def churn():
        tau = 1
        try:
            while not stop.is_set():
                svc.mine(tau=tau, kmax=2 + (tau % 2))
                tau = tau % 3 + 1
        except Exception as e:  # pragma: no cover - surfaced via errors
            errors.append(e)

    t = threading.Thread(target=churn)
    t.start()
    try:
        prev_runs = -1.0
        for _ in range(30):
            stats = svc.stats()
            assert "obs" in stats and "metrics" in stats["obs"]
            runs = sum(
                stats["obs"]["metrics"]["repro_mine_runs_total"]["values"].values()
            )
            assert runs >= prev_runs  # counters never go backwards
            prev_runs = runs
            text = om.REGISTRY.render()
            assert lint_exposition(text) == []
            _hist_count_agrees(text)
    finally:
        stop.set()
        t.join(timeout=30)
        svc.close()
    assert not errors, errors


# ---------------------------------------------------------------------------
# HTTP: /metrics, /trace, request correlation, /stats compatibility
# ---------------------------------------------------------------------------


@pytest.fixture()
def obs_http_service(tracer_reset):
    from repro.launch.serve_miner import make_server
    from repro.service import MiningService

    svc = MiningService.from_dataset(_rand(0, 200, 4))
    server = make_server(svc, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield svc, server.server_address[1]
    server.shutdown()
    server.server_close()
    svc.close()


def _req(port, path, payload=None, headers=None):
    url = f"http://127.0.0.1:{port}{path}"
    data = json.dumps(payload).encode() if payload is not None else None
    resp = urllib.request.urlopen(
        urllib.request.Request(url, data=data, headers=headers or {}), timeout=60
    )
    return resp, resp.read()


def test_http_metrics_exposition(obs_http_service):
    _, port = obs_http_service
    _req(port, "/mine", {"tau": 1, "kmax": 3})  # populate mining families
    resp, body = _req(port, "/metrics")
    assert resp.headers["Content-Type"].startswith("text/plain; version=0.0.4")
    text = body.decode()
    assert lint_exposition(text) == []
    families = {
        line.split()[2] for line in text.splitlines() if line.startswith("# TYPE")
    }
    assert len(families) >= 20, sorted(families)
    for required in (
        "repro_mine_wall_seconds",
        "repro_mine_level_seconds",
        "repro_placement_dispatch_total",
        "repro_service_mine_requests_total",
        "repro_http_requests_total",
        "repro_exec_cache_hits_total",
        "repro_result_cache_entries",
    ):
        assert required in families, required


def test_http_trace_correlation_and_retrieval(obs_http_service):
    _, port = obs_http_service
    resp, body = _req(port, "/mine", {"tau": 1, "kmax": 3})
    j = json.loads(body)
    tid = j["trace_id"]
    assert resp.headers["X-Trace-Id"] == tid

    # a client-supplied id is honoured and echoed
    resp2, body2 = _req(
        port, "/mine", {"tau": 1, "kmax": 3}, headers={"X-Trace-Id": "cafe0123"}
    )
    assert json.loads(body2)["trace_id"] == "cafe0123"
    assert resp2.headers["X-Trace-Id"] == "cafe0123"

    # the cold mine's span tree is retrievable and accounts for the request
    _, tb = _req(port, f"/trace?id={tid}")
    tree = json.loads(tb)["trace"]
    assert tree["trace_id"] == tid
    assert tree["coverage"] >= 0.95
    names = set()

    def walk(node):
        names.add(node["name"])
        for c in node["children"]:
            walk(c)

    for root in tree["spans"]:
        walk(root)
    assert {"http /mine", "service.mine", "mine.cold", "mine",
            "mine.level"} <= names, names

    _, lb = _req(port, "/trace?n=5")
    listing = json.loads(lb)
    assert len(listing["traces"]) >= 2
    assert listing["tracer"]["started"] >= 2

    with pytest.raises(urllib.error.HTTPError) as e:
        _req(port, "/trace?id=doesnotexist")
    assert e.value.code == 404


def test_http_stats_shape_backward_compatible(obs_http_service):
    _, port = obs_http_service
    _req(port, "/mine", {"tau": 1, "kmax": 2})
    _, body = _req(port, "/stats")
    stats = json.loads(body)
    # pre-existing sections consumed by dashboards / older clients
    for section in ("store", "cache", "scheduler", "placement", "served",
                    "executables", "resilience", "http"):
        assert section in stats, section
    assert stats["store"]["n_rows"] == 200
    # new obs fold-in rides alongside, not instead
    assert "metrics" in stats["obs"] and "traces" in stats["obs"]
    assert stats["obs"]["traces"]["started"] >= 1


def test_metrics_exempt_from_backpressure_but_auth_gated():
    from repro.launch.serve_miner import make_server
    from repro.service import MiningService

    svc = MiningService.from_dataset(_rand(0, 60, 3))
    server = make_server(svc, port=0, auth_token="tok", max_inflight=1)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    port = server.server_address[1]
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _req(port, "/metrics")
        assert e.value.code == 401
        resp, body = _req(
            port, "/metrics", headers={"Authorization": "Bearer tok"}
        )
        assert resp.status == 200 and b"# TYPE" in body
    finally:
        server.shutdown()
        server.server_close()
        svc.close()


# ---------------------------------------------------------------------------
# structured logs
# ---------------------------------------------------------------------------


@pytest.fixture()
def clean_repro_logger():
    logger = logging.getLogger("repro")
    had = list(logger.handlers)
    yield logger
    for h in list(logger.handlers):
        logger.removeHandler(h)
    for h in had:
        logger.addHandler(h)
    logger.propagate = True
    logger.setLevel(logging.NOTSET)


def test_json_logs_carry_trace_id(tracer_reset, clean_repro_logger):
    buf = io.StringIO()
    log = obs_logs.setup(level="info", json_mode=True, stream=buf)
    with TRACER.start("req") as root:
        log.info("access", extra={"route": "/mine", "code": 200})
    log.warning("later")  # outside the trace: no trace_id field
    lines = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert lines[0]["msg"] == "access"
    assert lines[0]["trace_id"] == root.trace_id
    assert lines[0]["route"] == "/mine" and lines[0]["code"] == 200
    assert lines[0]["level"] == "info"
    assert "trace_id" not in lines[1]


def test_text_logs_carry_trace_id(tracer_reset, clean_repro_logger):
    buf = io.StringIO()
    log = obs_logs.setup(level="debug", json_mode=False, stream=buf)
    with TRACER.start("req") as root:
        log.debug("hello", extra={"k": 3})
    line = buf.getvalue().strip()
    assert f"trace_id={root.trace_id}" in line and "k=3" in line


# ---------------------------------------------------------------------------
# profiling hook
# ---------------------------------------------------------------------------


def test_profile_records_gauges_and_cache_delta(tmp_path):
    from repro.core import KyivConfig, mine
    from repro.obs import profile as obs_profile

    reg = MetricsRegistry()
    with obs_profile.profile(str(tmp_path / "xplane"), registry=reg) as prof:
        result = mine(_rand(1, 200, 5), KyivConfig(tau=1, kmax=3, engine="jnp"))
        prof.set_result(result)
    assert prof.wall_s is not None and prof.wall_s > 0
    assert set(prof.exec_cache_delta) == {"hits", "misses", "entries"}
    assert reg.gauge("repro_profile_last_wall_seconds", "").value() == pytest.approx(
        prof.wall_s
    )
    assert reg.gauge("repro_profile_levels_retired", "").value() == len(result.stats)
    runs = reg.counter("repro_profile_runs_total", "", ("profiler",))
    assert runs.value(profiler="xplane") + runs.value(profiler="off") == 1


def test_profile_without_dump_dir_is_gauges_only():
    from repro.obs import profile as obs_profile

    reg = MetricsRegistry()
    with obs_profile.profile(registry=reg) as prof:
        pass
    assert prof.profiler_active is False
    assert prof.wall_s is not None
    assert reg.counter(
        "repro_profile_runs_total", "", ("profiler",)
    ).value(profiler="off") == 1


# ---------------------------------------------------------------------------
# device waits, emission, the reply's cost, compiles, profiler annotations
# ---------------------------------------------------------------------------

_DELAY_S = 0.08


class _SlowFetch:
    """A device value whose host read (``np.asarray`` / ``int``) sleeps
    first: a stand-in for a kernel the host has to wait for."""

    def __init__(self, value, fetches):
        self.value = value
        self.fetches = fetches

    def _wait(self):
        import time

        self.fetches.append(1)
        time.sleep(_DELAY_S)

    def __array__(self, dtype=None, copy=None):
        self._wait()
        return np.asarray(self.value, dtype=dtype)

    def __int__(self):
        self._wait()
        return int(self.value)


def _unwrap(v):
    return v.value if isinstance(v, _SlowFetch) else v


def _slow_device_placement(fetches):
    """A jnp placement whose frontier outputs are read back slowly."""
    from repro.core.placement import DevicePlacement

    p = DevicePlacement("jnp")
    dispatch, mask, partition = p.frontier_dispatch, p.frontier_mask, p.frontier_partition

    def frontier_dispatch(*a, **kw):
        pairs_d, ok_d = dispatch(*a, **kw)
        return _SlowFetch(pairs_d, fetches), _SlowFetch(ok_d, fetches)

    def frontier_mask(fstate, pairs_d, ok_d):
        cpairs, n_ok = mask(fstate, _unwrap(pairs_d), _unwrap(ok_d))
        return cpairs, _SlowFetch(n_ok, fetches)

    def frontier_partition(cls_d):
        order, n_emit, n_store = partition(cls_d)
        return _SlowFetch(order, fetches), n_emit, n_store

    p.frontier_dispatch = frontier_dispatch
    p.frontier_mask = frontier_mask
    p.frontier_partition = frontier_partition
    return p


def _slow_host_pipelines(fetches):
    """Numpy level pipelines whose batch results are handed over slowly."""
    from repro.core.placement import HostPlacement
    from repro.kernels.intersect.ops import LevelPipeline

    class SlowHandle:
        def __init__(self, handle):
            self.handle = handle

        def result(self):
            import time

            fetches.append(1)
            time.sleep(_DELAY_S)
            return self.handle.result()

    class SlowPipeline(LevelPipeline):
        def submit(self, pairs, write_children):
            return SlowHandle(super().submit(pairs, write_children))

    placement = HostPlacement()
    return lambda bits, counts, tau: SlowPipeline(
        bits, counts, tau=tau, placement=placement, fused_classify=True
    )


@pytest.mark.parametrize("path", ["device", "device_bounds", "host"])
def test_injected_device_delay_lands_under_device_wait(tracer_reset, path):
    from repro.core import KyivConfig, mine, mine_preprocessed
    from repro.core.kyiv import prepare

    fetches = []
    D = _rand(3, 300, 6)
    kw = {}
    if path == "host":
        cfg = KyivConfig(tau=1, kmax=3, engine="numpy")
        kw["pipeline_factory"] = _slow_host_pipelines(fetches)
    else:
        cfg = KyivConfig(tau=1, kmax=3, use_bounds=path == "device_bounds",
                         placement=_slow_device_placement(fetches))
    prep = prepare(D, cfg)
    mine_preprocessed(prep, cfg, **kw)  # compiles: they would land in the stages
    fetches.clear()
    with TRACER.start("test.mine"):
        result = mine_preprocessed(prep, cfg, **kw)
    trace = TRACER.last(1)[0]
    want = mine(D, KyivConfig(tau=1, kmax=3, engine="numpy"))
    assert sorted(result.itemsets) == sorted(want.itemsets)

    def total(name):
        return sum(s.duration for s in trace.find(name))

    assert len(fetches) >= 2
    injected = _DELAY_S * len(fetches)
    assert total("device.wait") >= injected
    # the stage spans hold host work only: not one injected delay
    assert total("level.classify") < _DELAY_S
    assert total("frontier.candidates") < _DELAY_S
    assert sum(ls.time_intersect for ls in result.stats) >= injected
    assert sum(ls.time_classify + ls.time_candidates for ls in result.stats) < _DELAY_S
    if path == "device_bounds":
        assert {"ok", "pairs", "result"} <= {s.attrs["site"] for s in trace.find("device.wait")}
    # every emitted batch is spanned, and emission is out of the level's self time
    by_id = {s.span_id: s for s in trace.spans}
    emits = trace.find("level.emit")
    assert emits and all(by_id[s.parent_id].name == "mine.level" for s in emits)
    _check_spans_against_stats(result, trace)


def _finished_trace(trace_id, timeout_s=10.0):
    """The request's trace once its root span has closed: the client holds
    the reply before the handler thread leaves the root span."""
    import time

    deadline = time.monotonic() + timeout_s
    while (trace := TRACER.get(trace_id)) is None and time.monotonic() < deadline:
        time.sleep(0.005)
    assert trace is not None, trace_id
    return trace


def test_mine_reply_spans_decode_encode_write(obs_http_service):
    import time

    _, port = obs_http_service
    t0 = time.perf_counter()
    _, body = _req(port, "/mine", {"tau": 1, "kmax": 3})
    client_s = time.perf_counter() - t0
    trace = _finished_trace(json.loads(body)["trace_id"])
    (respond,) = trace.find("http.respond")
    kids = {s.name: s for s in trace.children_of(respond)}
    assert set(kids) == {"http.decode", "http.encode", "http.write"}
    assert all(s.duration > 0 for s in kids.values())
    assert kids["http.decode"].t1 <= kids["http.encode"].t0 <= kids["http.write"].t0
    assert sum(s.duration for s in kids.values()) <= min(respond.duration, client_s)
    # the reply is built from arrays: what was encoded, in how many runs
    reply = json.loads(body)
    assert kids["http.decode"].attrs["itemsets"] == len(reply["itemsets"]) > 0
    assert 1 <= kids["http.decode"].attrs["runs"] <= reply["kmax"]
    assert kids["http.encode"].attrs["bytes"] == len(body)


def _host_event_names(xplane_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(xplane_dir, "**", "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def test_spans_lie_on_the_profiler_trace(tracer_reset, tmp_path):
    import jax

    from repro.launch.serve_miner import make_server
    from repro.service import MiningService

    dev = MiningService.from_dataset(_rand(0, 200, 4), engine="jnp")
    server = make_server(dev, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _, body = _req(server.server_address[1], "/mine", {"tau": 1, "kmax": 3})
            _finished_trace(json.loads(body)["trace_id"])  # the root span's annotation too
        finally:
            jax.profiler.stop_trace()
    finally:
        server.shutdown()
        server.server_close()
        dev.close()
    names = _host_event_names(str(tmp_path))
    assert {"mine.level", "device.wait", "http.encode", "http /mine"} <= names, names


def test_no_annotation_without_a_profiler_session(tracer_reset, monkeypatch):
    import jax  # the annotation is only looked up once jax is loaded

    from repro.obs import trace as obs_trace

    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(obs_trace, "_ANNOTATION", Counting)
    with TRACER.start("t"):
        with obs_trace.span("a"):
            pass
    assert not Counting.is_enabled() and made == []


def test_compile_is_a_span_and_counted_in_the_cost_envelope(tracer_reset):
    from repro.service import MiningService

    first, wider = _rand(5, 300, 6), _rand(6, 4000, 6)
    svc = MiningService.from_dataset(first, engine="jnp")
    # fed as svc is, so that its store grows to svc's width
    twin = MiningService.from_dataset(first, engine="jnp")
    twin.append(wider)
    try:
        svc.mine(tau=2, kmax=3)
        # a wider table: the level loop's executables meet new shapes
        svc.append(wider)
        r = svc.mine(tau=2, kmax=3)
        trace = TRACER.get(r.info["cost"]["trace_id"])
        compiles = trace.find("jax.compile")
        assert compiles and r.info["cost"]["executables_compiled"] == len(compiles)
        assert all(s.t0 <= s.t1 and s.attrs["fun_name"] for s in compiles)
        by_id = {s.span_id: s for s in trace.spans}

        def in_level(sp):
            while sp.parent_id is not None:
                sp = by_id[sp.parent_id]
                if sp.name == "mine.level":
                    return True
            return False

        # a compile shows under the level that paid for it
        assert any(in_level(s) for s in compiles)
        # the same mine on the same shapes compiles nothing
        r = twin.mine(tau=2, kmax=3)
        assert r.info["cost"]["executables_compiled"] == 0
        assert not TRACER.get(r.info["cost"]["trace_id"]).find("jax.compile")
    finally:
        svc.close()
        twin.close()
