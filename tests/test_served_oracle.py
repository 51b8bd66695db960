"""The served cold ``/mine`` against the brute-force oracle.

Each case mines through ``MiningService`` on a small table shaped like a
benchmark deployment, turns the answer into the bytes ``serve_miner``
writes, parses them as a client does, and checks the itemsets against
``brute_force_minimal_infrequent`` and every count against the table.
"""

import functools
import json

import numpy as np
import pytest

from repro.core import brute_force_minimal_infrequent, itemize
from repro.data.synth import connect_like, poker_like, pumsb_like
from repro.service import MiningService


def _mirror_table():
    """Column 0 repeated and a constant column added: duplicate row sets
    for ``mine.preprocess`` to fold and mirror expansion to restore."""
    base = pumsb_like(n=150, m=5, seed=2)
    return np.concatenate([base, base[:, :1], np.full((150, 1), 7)], axis=1)


TABLES = {
    "poker": lambda: poker_like(n=200, seed=1),
    "pumsb": lambda: pumsb_like(n=150, m=7, seed=1),
    "connect": lambda: connect_like(n=120, m=8, seed=1),
    "mirror": _mirror_table,
}


@functools.cache
def _table(name):
    return TABLES[name]()


@functools.cache
def _oracle(name, tau, kmax):
    """The minimal τ-infrequent itemsets as sets of ``(column, value)``."""
    table = _table(name)
    items = itemize(table)
    return {
        frozenset((int(items.col[i]), int(items.value[i])) for i in ids)
        for ids in brute_force_minimal_infrequent(table, tau, kmax)
    }


@pytest.fixture(scope="module")
def service():
    services = {}

    def get(name, engine):
        if (name, engine) not in services:
            services[name, engine] = MiningService.from_dataset(
                _table(name), engine=engine
            )
        return services[name, engine]

    yield get
    for svc in services.values():
        svc.close()


@pytest.mark.parametrize("kmax", [1, 2, 3])
@pytest.mark.parametrize("tau", [1, 3, 8])
@pytest.mark.parametrize("engine", ["numpy", "jnp"])
@pytest.mark.parametrize("name", list(TABLES))
def test_served_cold_mine_matches_oracle(service, name, engine, tau, kmax):
    resp = service(name, engine).mine(tau=tau, kmax=kmax)
    assert resp.source == "cold"
    assert bool(resp.result.prep.mirror_of) == (name == "mirror")
    reply = json.loads(resp.reply_pieces().to_bytes())
    assert reply["n_itemsets"] == len(reply["itemsets"])

    got = {frozenset(map(tuple, s["items"])) for s in reply["itemsets"]}
    assert len(got) == len(reply["itemsets"])
    assert got == _oracle(name, tau, kmax)

    table = _table(name)
    for s in reply["itemsets"]:
        cols, values = np.asarray(s["items"]).T
        assert s["count"] == int(np.all(table[:, cols] == values, axis=1).sum())
