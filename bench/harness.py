"""Run one benchmark cell once: set up, measure a window, check every answer.

One process holds the chip. It builds the table from the seed, loads it into
a ``MiningService`` (the configuration's engine) and serves that service
through ``repro.launch.serve_miner.make_server`` on a thread. The client, in
this process too, sends the mix's answers over HTTP, one after another, for
``seconds``; each answer is timed from sending its first request to holding
its last reply parsed. Answers started before the window closes all count;
the last one is awaited.

Set-up warms every executable the window will use on a second service
instance with the same table, which the window never asks, so every answer
in the window stays cold: it replays the window's own answers, in order,
until three in a row create no executable, or the mix ends, or it has
covered more than the window's length of answers (see :func:`_warm`).

Every answer of the window is checked against ``bench/reference.py``, which
imports nothing of the program, after the window has closed and the
program's device state is freed. An answer also fails when its reply carries
another ``source`` than the mix expects, was degraded to the host, or when
the service retried a device call.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import sys
import tempfile
import threading
import time
import urllib.request
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import reference
import traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# executables created while jitted code first meets a shape: compiled, or
# loaded from the persistent cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SetupError(RuntimeError):
    """The run cannot measure: no chip, an interpreted placement, or a
    program that cannot be imported. No result is printed."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    with open(spec_path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in {spec_path.name}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(ROOT / conf["file"]) as f:
        config = json.load(f)

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(
        name=name,
        config=config,
        mix=traffic.load_mix(w["traffic"]),
        chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
    )


def make_rows(config: dict, n_rows: int, rng: np.random.Generator) -> np.ndarray:
    table = config["table"]
    gen = load_module(BENCH / "tables" / f"{table['kind']}.py")
    return gen.generate(table, n_rows, rng)


class CompileCounter:
    """Counts the executables JAX creates, from its own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.created = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event == _COMPILE_EVENT:
            with self._lock:
                self.created += 1
                self.seconds += float(duration)

    def _on_event(self, event, **_kw):
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> tuple[int, int, float]:
        with self._lock:
            return self.created, self.cache_hits, self.seconds


@dataclass
class Answer:
    """One answer of the window, as the client saw it."""

    requests: list
    t_send: float
    t_done: float = 0.0
    replies: list = field(default_factory=list)  # raw reply bytes, per request
    latency_s: float = 0.0  # sum of the replies' own latency_s
    trace_ids: list = field(default_factory=list)
    errors: list = field(default_factory=list)
    stats: list = field(default_factory=list)  # level stats of each /mine

    @property
    def client_s(self) -> float:
        return self.t_done - self.t_send


@dataclass
class RunRecord:
    """What the per-layer metric readers read (``bench/metrics/*.py``)."""

    answers: list
    spans: dict  # trace id -> [{"name", "t0", "t1", "span_id", "parent_id"}]
    window: tuple  # (start, end) on the host clock
    n_words: int  # bitset words per item as the placement holds them
    device_ops: list = field(default_factory=list)  # [(name, start, end)] host clock
    device_kind: str = ""


def _post(port: int, route: str, body: dict, timeout: float) -> bytes:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=json.dumps(body).encode(),
        method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read()


def _to_jsonable(body: dict) -> dict:
    return {k: (v.tolist() if isinstance(v, np.ndarray) else v) for k, v in body.items()}


def _serve_answer(port: int, requests: list, timeout: float) -> Answer:
    ans = Answer(requests=requests, t_send=time.perf_counter())
    for req in requests:
        try:
            raw = _post(port, req["route"], _to_jsonable(req["body"]), timeout)
        except OSError as e:  # HTTPError, URLError, timeouts
            ans.errors.append(f"{req['route']}: {type(e).__name__}: {e}")
            break
        reply = json.loads(raw)
        ans.replies.append(raw)
        ans.latency_s += float(reply.get("latency_s", 0.0))
        ans.trace_ids.append(reply.get("trace_id"))
        if req["source"] is not None and reply.get("source") != req["source"]:
            ans.errors.append(
                f"{req['route']} served {reply.get('source')!r}, expected {req['source']!r}"
            )
        if reply.get("info", {}).get("degraded"):
            ans.errors.append(f"{req['route']} degraded to {reply['info']['degraded']}")
    ans.t_done = time.perf_counter()
    return ans


def _apply_direct(service, req: dict) -> None:
    """One request of the mix straight into the service (warm-up)."""
    body = req["body"]
    if req["route"] == "/mine":
        service.mine(
            tau=int(body["tau"]),
            kmax=int(body["kmax"]),
            mode=str(body.get("mode", "exact")),
            epsilon=body.get("epsilon"),
        )
    elif req["route"] == "/append":
        service.append(np.asarray(body["rows"]))
    else:
        raise ValueError(f"warm-up cannot replay {req['route']}")


def _warm(service, answer_iter, seconds: float, counter: CompileCounter, log) -> list:
    """Replay the window's answers on ``service`` until three in a row
    create no executable, or the mix ends, or the replayed answers' time
    without compiles (the first answer aside) passes 1.25 x ``seconds``: a
    window answer runs the same mine and more, so the window cannot reach
    an answer the warm-up did not. Returns the answers replayed."""
    replayed, quiet, work = [], 0, 0.0
    for requests in answer_iter:
        c0, _, s0 = counter.snapshot()
        t0 = time.perf_counter()
        for req in requests:
            _apply_direct(service, req)
        dt = time.perf_counter() - t0
        c1, _, s1 = counter.snapshot()
        replayed.append(requests)
        quiet = quiet + 1 if c1 == c0 else 0
        if replayed[1:]:
            work += max(0.0, dt - (s1 - s0))
        log(f"warm answer {len(replayed)}: {dt:.3f} s, {c1 - c0} executables")
        if quiet >= 3 or work >= 1.25 * seconds:
            break
    return replayed


def _rows(m: np.ndarray) -> np.ndarray:
    """Each row of an integer matrix as one comparable value."""
    m = np.ascontiguousarray(m, dtype=np.int64)
    return m.view(np.dtype((np.void, 8 * m.shape[1]))).ravel()


def _wrong_itemsets(ref: reference.Reference, tau: int, reply: dict) -> int:
    """Itemsets, with their supports, in the symmetric difference of the
    served and the reference answer; a duplicate or an unknown item in the
    served answer counts too."""
    want = ref.answer(tau)
    sets = reply["itemsets"]
    sizes = np.fromiter((len(s["items"]) for s in sets), dtype=np.int64, count=len(sets))
    counts = np.fromiter((s["count"] for s in sets), dtype=np.int64, count=len(sets))
    ids = ref.ids_of([it for s in sets for it in s["items"]])
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
    unknown = np.add.reduceat(ids < 0, starts) if len(sets) else np.zeros(0, bool)
    wrong = int((unknown > 0).sum())
    for k in set(want) | set(sizes.tolist()):
        w_ids, w_sup = want.get(k, (np.zeros((0, k), np.int64), np.zeros(0, np.int64)))
        sel = np.flatnonzero((sizes == k) & (unknown == 0))
        g_ids = np.sort(ids[starts[sel][:, None] + np.arange(k)], axis=1)
        served, n = np.unique(_rows(np.column_stack([g_ids, counts[sel]])), return_counts=True)
        expected = _rows(np.column_stack([w_ids, w_sup]))
        found = np.isin(served, expected)
        wrong += int((n - 1).sum()) + int((~found).sum()) + len(expected) - int(found.sum())
    return wrong


def _check_answers(cell: Cell, table: np.ndarray, answers: list, tau_lo: int, log) -> dict:
    """Compare every /mine reply with the reference. Returns counts."""
    kmax = int(cell.config["kmax"])
    refs: dict[int, reference.Reference] = {}
    wrong = checked = 0
    rows = table
    t0 = time.perf_counter()
    for ans in answers:
        for req, raw in zip(ans.requests, ans.replies):
            if req["route"] == "/append":
                rows = np.concatenate([rows, np.asarray(req["body"]["rows"])])
                continue
            if req["route"] != "/mine":
                continue
            if len(rows) not in refs:
                refs[len(rows)] = reference.Reference(rows, kmax, tau_lo)
            wrong += _wrong_itemsets(refs[len(rows)], int(req["body"]["tau"]), json.loads(raw))
            checked += 1
    log(f"reference built and {checked} answers compared in {time.perf_counter() - t0:.3f} s")
    return {"answers_checked": checked, "itemsets_wrong": wrong}


def _spans_of(trace_ids: list) -> dict:
    from repro.obs.trace import TRACER

    out = {}
    for tid in trace_ids:
        tr = TRACER.get(tid) if tid else None
        if tr is None:
            continue
        out[tid] = [
            {"name": s.name, "t0": s.t0, "t1": s.t1, "span_id": s.span_id,
             "parent_id": s.parent_id}
            for s in list(tr.spans)
        ]
    return out


def _level_stats(result) -> list:
    return [
        {"k": s.k, "stored": s.stored, "intersections": s.intersections,
         "candidates": s.candidates}
        for s in result.stats
    ]


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    require_chip: bool = True,
    control: str | None = None,
    log=lambda msg: print(msg, file=sys.stderr, flush=True),
) -> dict:
    """One run. Returns the result object the benchmark prints.

    ``control="approx"`` sends every /mine in the program's sampled mode
    (``mode=approx``), the path that breaks exactness: the control run the
    comparison must fail. ``require_chip=False`` lets the CPU tests drive a
    run with the Pallas kernels interpreted.
    """
    import jax

    phases = {"to_jax_start": time.perf_counter() - t_start}
    devices = jax.devices()
    dev = devices[0]
    phases["jax_devices"] = time.perf_counter() - t_start - phases["to_jax_start"]
    if require_chip and dev.platform != "tpu":
        raise SetupError(f"no TPU: JAX reports platform {dev.platform!r}")
    if len(devices) < cell.chips:
        raise SetupError(f"cell asks for {cell.chips} chips, JAX sees {len(devices)}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")

    from repro.launch.serve_miner import make_server
    from repro.obs.trace import TRACER
    from repro.service import MiningService

    counter = CompileCounter()
    TRACER.configure(max_traces=4096)
    seed = int(seed) & ((1 << 64) - 1)
    config = cell.config
    engine = str(config["engine"])

    def new_service():
        service = MiningService(engine=engine, cache_capacity=4096)
        described = service.placement.describe()
        if require_chip and described.get("interpret"):
            service.close()
            raise SetupError("placement runs the Pallas kernels interpreted")
        return service

    t0 = time.perf_counter()
    # a configuration may fix its table (one released file), so that its
    # level sizes, and with them the executables, are the same in every run
    table_seed = int(config.get("table_seed", seed))
    table = make_rows(config, int(config["rows"]), np.random.default_rng([table_seed, 0]))
    phases["table"] = time.perf_counter() - t0

    mix = cell.mix
    if control == "approx":
        mix = json.loads(json.dumps(mix))
        for req in mix["answer"]:
            if req["route"] == "/mine":
                req["body"].update(mode="approx", epsilon=0.1)
                req["source"] = None
    row_maker = lambda n, rng: make_rows(config, n, rng)  # noqa: E731

    # requests the mix makes in set-up, on both instances (e.g. the cold
    # mine an append mix builds its incremental answers on)
    setup = traffic.setup_requests(mix, config)
    t0 = time.perf_counter()
    warm = new_service()
    warm.append(table)
    phases["store_load"] = time.perf_counter() - t0
    for req in setup:
        _apply_direct(warm, req)
    t0 = time.perf_counter()
    warmed = _warm(warm, traffic.answers(mix, config, seed, row_maker), seconds, counter, log)
    phases["warm_up"] = time.perf_counter() - t0
    warm.close()
    del warm
    gc.collect()

    t0 = time.perf_counter()
    service = new_service()
    service.append(table)
    for req in setup:
        _apply_direct(service, req)
    phases["window_store_load"] = time.perf_counter() - t0
    n_words = int(service.store.device_bits().shape[-1])
    server = make_server(service, "127.0.0.1", 0)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    answers: list[Answer] = []
    try:
        tmp = tempfile.TemporaryDirectory() if trace else None
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host annotations stay on
            jax.profiler.start_trace(tmp.name, profiler_options=options)
        c0 = counter.snapshot()
        setup_s = time.perf_counter() - t_start
        phases["executables_in_setup"] = c0[0]
        log(json.dumps({"setup_s": setup_s, "phases": phases}))
        with jax.profiler.TraceAnnotation("bench.window"):
            w0 = time.perf_counter()
            for requests in traffic.answers(mix, config, seed, row_maker):
                if time.perf_counter() - w0 >= seconds:
                    break
                answers.append(_serve_answer(port, requests, timeout=seconds + 600))
        w1 = time.perf_counter()
        c1 = counter.snapshot()
        device_ops = []
        if trace:
            jax.profiler.stop_trace()
            import trace_reduce

            device_ops = trace_reduce.device_ops(tmp.name, (w0, w1), "bench.window")
            tmp.cleanup()
        peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
        ) or None
        stats = service.stats()["resilience"]
        trace_ids = [t for a in answers for t in a.trace_ids]
        spans = _spans_of(trace_ids) if trace else {}
        if trace:
            for a in answers:
                for req in a.requests:
                    if req["route"] == "/mine" and not a.errors:
                        b = req["body"]
                        r = service.mine(tau=int(b["tau"]), kmax=int(b["kmax"]))
                        a.stats.append(_level_stats(r.result))
    finally:
        server.shutdown()
        server.server_close()
        thread.join()
        service.close()
    del service
    gc.collect()

    compiles = c1[0] - c0[0]
    for a in answers:
        log(json.dumps({
            "answer": [r["body"].get("tau") for r in a.requests],
            "client_s": a.client_s,
            "latency_s": a.latency_s,
            "reply_bytes": sum(len(r) for r in a.replies),
        }))
    log(json.dumps({
        "window_s": w1 - w0,
        "answers": len(answers),
        "warm_answers": len(warmed),
        "executables_created_in_window": compiles,
        "persistent_cache_hits_in_window": c1[1] - c0[1],
        "device_retries": stats["device_retries"],
        "degraded_mines": stats["degraded_mines"],
        "breaker": stats["state"],
    }))

    taus = [int(r["body"]["tau"]) for a in answers for r in a.requests if r["route"] == "/mine"]
    for req in setup:
        if req["route"] == "/append":
            table = np.concatenate([table, np.asarray(req["body"]["rows"])])
    check = _check_answers(cell, table, answers, min(taus, default=1), log)
    failed = sum(1 for a in answers if a.errors)
    for a in answers:
        for e in a.errors:
            log(f"answer failed: {e}")
    device_faults = stats["device_retries"] + stats["degraded_mines"]
    limits = {
        "itemsets_wrong": (check["itemsets_wrong"], 0),
        "answers_failed": (failed, 0),
        "device_retries_or_degraded": (device_faults, 0),
    }
    correct = (
        check["itemsets_wrong"] == 0 and failed == 0 and device_faults == 0
        and check["answers_checked"] == len(taus) and len(answers) > 0
    )

    done = [a for a in answers if not a.errors]
    metrics = {}
    if not trace:
        values = {
            "answer_s": (sum(a.client_s for a in done) / len(done)) if done else None,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    record = RunRecord(
        answers=done, spans=spans, window=(w0, w1), n_words=n_words,
        device_ops=device_ops, device_kind=dev.device_kind,
    )
    out = {
        "correct": bool(correct),
        "attempted": len(answers),
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if trace:
        import trace_reduce

        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        import work

        moved = [work.intersect_bytes(st, n_words) for a in done for st in a.stats]
        log(json.dumps({"intersect_bytes_per_mine": {
            key: sum(m[key] for m in moved) / max(1, len(moved))
            for key in ("lower_bound", "per_pair")}}))
        out["device"]["busy_s"] = trace_reduce.busy_seconds(device_ops, (w0, w1))
        out["device"]["window_s"] = w1 - w0
        out["breakdown"] = trace_reduce.breakdown(device_ops, spans, (w0, w1))
    out["check"] = {
        name: {"value": v, "limit": lim} for name, (v, lim) in limits.items()
    } | {"answers_checked": {"value": check["answers_checked"], "limit": len(taus)}}
    return out
