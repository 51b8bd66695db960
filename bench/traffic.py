"""The one traffic generator: turns a mix file into the answers a run asks for.

A mix, ``bench/traffic/<name>.json``, is data::

    {"loop": "closed", "clients": 1,
     "answer": [{"route": "/mine", "body": {"tau": "$tau", "kmax": "$kmax"},
                 "source": "cold"}],
     "draws": {"tau": {"kind": "sweep", "range": "$tau_range"}}}

One *answer* is the list of requests under ``answer``, sent in order by one
client; the client sends the next answer when the last reply of the previous
one is parsed (a closed loop). A string ``"$name"`` in a request body is the
draw ``name`` of this answer, or else the configuration's key ``name``.
``source`` is the ``source`` the reply must carry. Requests under an
optional ``setup`` key are made once in set-up, before the window, with
the configuration's keys and no draws.

Draw kinds:

* ``sweep``: the integers lo, lo + step, ... up to hi of ``range`` =
  [lo, hi] (``step`` 1 if not given), each at most once per run, in the
  order of a golden-ratio sequence turned by an offset drawn from the seed.
  Every prefix of the order spreads evenly over the range, so runs with
  different seeds ask for work of the same sizes, in another order.
* ``fixed``: ``value`` every time.
* ``rows``: ``size`` new table rows, made by the configuration's table
  generator from the seed and the answer's index.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Callable, Iterator

import numpy as np

_PHI = (math.sqrt(5.0) - 1.0) / 2.0
TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def _resolve(value, draws: dict, config: dict):
    if isinstance(value, str) and value.startswith("$"):
        key = value[1:]
        return draws[key] if key in draws else config[key]
    if isinstance(value, dict):
        return {k: _resolve(v, draws, config) for k, v in value.items()}
    if isinstance(value, list):
        return [_resolve(v, draws, config) for v in value]
    return value


def _sweep(lo: int, hi: int, step: int, rng: np.random.Generator) -> Iterator[int]:
    span = (hi - lo) // step + 1
    offset = float(rng.random())
    seen: set[int] = set()
    i = 0
    while len(seen) < span:
        v = int(((offset + i * _PHI) % 1.0) * span)
        i += 1
        if v not in seen:
            seen.add(v)
            yield lo + v * step


def setup_requests(mix: dict, config: dict) -> list[dict]:
    return [
        {"route": req["route"], "body": _resolve(req.get("body", {}), {}, config),
         "source": req.get("source")}
        for req in mix.get("setup", [])
    ]


def answers(
    mix: dict,
    config: dict,
    seed: int,
    make_rows: Callable[[int, np.random.Generator], np.ndarray],
) -> Iterator[list[dict]]:
    """Answers in the order a run asks for them: each a list of
    ``{"route", "body", "source"}`` requests. ``make_rows(n, rng)`` makes
    table rows for ``rows`` draws."""
    if mix.get("loop") != "closed" or int(mix.get("clients", 1)) != 1:
        raise ValueError("the generator drives one client in a closed loop")
    specs = mix.get("draws", {})
    streams = {}
    for i, (name, spec) in enumerate(sorted(specs.items())):
        spec = _resolve(spec, {}, config)
        if spec["kind"] == "sweep":
            lo, hi = (int(x) for x in spec["range"])
            step = int(spec.get("step", 1))
            streams[name] = _sweep(lo, hi, step, np.random.default_rng([seed, 1, i]))
    index = 0
    while True:
        draws = {}
        for i, (name, spec) in enumerate(sorted(specs.items())):
            spec = _resolve(spec, {}, config)
            if spec["kind"] == "sweep":
                v = next(streams[name], None)
                if v is None:
                    return
                draws[name] = v
            elif spec["kind"] == "fixed":
                draws[name] = spec["value"]
            elif spec["kind"] == "rows":
                rng = np.random.default_rng([seed, 2, i, index])
                draws[name] = make_rows(int(spec["size"]), rng)
            else:
                raise ValueError(f"unknown draw kind {spec['kind']!r}")
        yield [
            {
                "route": req["route"],
                "body": _resolve(req.get("body", {}), draws, config),
                "source": req.get("source"),
            }
            for req in mix["answer"]
        ]
        index += 1
