"""The benchmark's own rotor schedule keeps Opera's guarantees, and the
program, handed it as its topology, exports the same slices."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.gen import opera_schedule as S
from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
FLUID = R.import_file(ROOT / "bench/drivers/fluid.py", "schedule_fluid")


def config(name, **kw):
    return dict(json.loads((ROOT / f"bench/configs/{name}.json")
                           .read_text()), **kw)


def exact_cover(matchings) -> bool:
    n = len(matchings)
    cover = np.zeros((n, n), dtype=int)
    for p in matchings:
        if not np.array_equal(p[p], np.arange(n)):
            return False
        cover[np.arange(n), p] += 1
    return bool((cover == 1).all())


@pytest.mark.parametrize("n", [8, 12, 108])
def test_factorization_covers_every_pair_once(n):
    assert exact_cover(S.factorization(n, seed=3))


def test_lift_covers_every_pair_once():
    assert exact_cover(S.lift(S.factorization(12, seed=0), 4))
    assert S.lift_factor(432, 12) == 4 and S.lift_factor(108, 6) == 1


@pytest.mark.parametrize("cfg", [
    config("opera-648", k=4, num_racks=8, hosts_per_rack=2,
           num_circuit_switches=2),
    config("opera-648"),
    config("opera-648", num_racks=96, groups=2, topo_seed=5),
], ids=["tiny", "opera-648", "grouped"])
def test_schedule_keeps_guarantees(cfg):
    adj = S.slice_adjacency(S.schedule(cfg), cfg["groups"])
    assert FLUID.validate_topology(adj, cfg) == 0


def test_program_exports_the_schedule():
    from repro.core.topology import OperaTopology

    cfg = config("opera-648", num_racks=24, num_circuit_switches=4)
    sm = S.schedule(cfg)
    topo = OperaTopology(num_racks=24, num_switches=4, switch_matchings=sm,
                         groups=1)
    adj = S.slice_adjacency(sm, 1)
    np.testing.assert_array_equal(topo.matching_tensor(), adj)
    dst = topo.matching_index_tensor()
    back = np.zeros_like(adj)
    t, i, s = np.nonzero(dst < 24)
    back[t, i, dst[t, i, s]] = 1.0
    np.testing.assert_array_equal(back, adj)
