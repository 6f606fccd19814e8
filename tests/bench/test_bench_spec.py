"""BENCHMARK.json resolves, cell by cell, to its files by name, and keeps
to the shape its readers expect."""
import json
import os
import re

import _benchpath  # noqa: F401
import numpy as np
import pytest

from bench import spec, traffic

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = spec.resolve(BENCH, cell)
    assert os.path.isfile(spec.entry_path(c.entry))
    assert c.mix["entry"] in c.config["entry"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.end_to_end + c.per_layer:
        mod = spec.load_file(spec.metric_path(m["name"]))
        assert callable(mod.read)
    assert set(c.config["limits"]) >= {"missing", "not_optimal"}
    assert c.mix["storage"] in traffic.STORAGE
    assert c.mix["storage"] in c.config["control"]


def test_names_units_and_references_are_well_formed():
    configs = {c["name"] for c in BENCH["configs"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for name in list(configs) + cells + metrics:
        assert NAME.match(name), name
    assert len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert os.path.isfile(spec.mix_path(w["traffic"]))
    for c in BENCH["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.resolve(BENCH, "no-such-cell")


def test_every_seed_solves_the_same_pool_in_its_own_order():
    c = spec.resolve(BENCH, "table1-dense")
    mix = dict(c.mix, distinct_calls=3)
    a = traffic.make_calls(mix, c.config, 2 ** 31 + 5)
    b = traffic.make_calls(mix, c.config, 11)
    assert sorted(call[0].name for call in a) == \
        sorted(call[0].name for call in b)
    for call in a:
        # every call holds the Table-1 set once, in standard form
        assert [i.shape for i in call] == [
            (s["m"], s["n"] + s["m"]) for s in c.config["instances"]]
        twin = next(x for x in b if x[0].name == call[0].name)
        for i, j in zip(call, twin):
            assert np.array_equal(i.K, j.K) and np.array_equal(i.c, j.c)


def test_coo_storage_hands_over_the_same_matrix():
    c = spec.resolve(BENCH, "table1-ell")
    dense = spec.resolve(BENCH, "table1-dense")
    (call,) = traffic.make_calls(dict(c.mix, distinct_calls=1), c.config, 3)
    (twin,) = traffic.make_calls(dict(dense.mix, distinct_calls=1),
                                 dense.config, 3)
    for sparse, full in zip(call, twin):
        assert sparse.K is None and sparse.nnz == np.count_nonzero(full.K)
        x = np.linspace(0.0, 1.0, full.shape[1])
        assert np.allclose(sparse.matvec(x), full.K @ x)
