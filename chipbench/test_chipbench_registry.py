"""Cells, configurations, systems, mixes, generators and metric readers
are found by the names BENCHMARK.json gives them, and an unknown name
fails."""

import json
import re

import pytest

from chipbench import registry

BENCH = registry.load_benchmark()
TESTDATA = registry.HERE / "testdata"
# cells on the repo's fixture patterns that the CPU tests run
TEST_BENCH = registry.load_benchmark(TESTDATA / "bench.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(autouse=True)
def _test_mixes(monkeypatch):
    monkeypatch.setattr(registry, "TRAFFIC_DIRS",
                        [*registry.TRAFFIC_DIRS, TESTDATA / "traffic"])


def _cells_resolve(bench):
    for cell in bench["workloads"]:
        cfg = registry.config(bench, cell)
        mix = registry.traffic(cell["traffic"])
        assert cfg["patterns"]
        assert callable(registry.system(cfg["system"]).Cell)
        assert callable(registry.system(cfg["system"]).control(cfg))
        assert callable(registry.generator(mix["generator"]))
        assert registry.end_to_end(bench, cell["name"])
        assert any(m["name"] == "setup_s"
                   for m in registry.end_to_end(bench, cell["name"]))
        assert registry.per_layer(bench, cell["name"])


def _readers_resolve(bench):
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))
        e2e = {e["name"]: e for e in bench["end_to_end"]}
        for cell in m["workloads"]:
            assert registry.applies(e2e[m["moves"]], cell)


def test_every_cell_resolves_its_pieces():
    _cells_resolve(BENCH)


def test_every_test_cell_resolves_its_pieces():
    _cells_resolve(TEST_BENCH)


def test_every_per_layer_metric_has_a_reader():
    _readers_resolve(BENCH)


def test_every_test_metric_has_a_reader():
    _readers_resolve(TEST_BENCH)


def test_benchmark_mixes_are_not_test_data():
    for cell in BENCH["workloads"]:
        assert (registry.HERE / "traffic" / f"{cell['traffic']}.json"
                ).is_file()


def test_names_and_reduced_keys():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for entry in BENCH["configs"]:
        with open(registry.ROOT / entry["file"]) as f:
            cfg = json.load(f)
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
        assert all(k in cfg or k in cfg["matcher"]
                   for k in entry["reduced"])


@pytest.mark.parametrize("lookup", [
    lambda: registry.workload(BENCH, "no-such-cell"),
    lambda: registry.config(BENCH, {"config": "no-such-config"}),
    lambda: registry.traffic("no_such_mix"),
    lambda: registry.metric_reader("no_such_metric"),
    lambda: registry.system("no_such_system"),
    lambda: registry.generator("no_such_generator"),
])
def test_unknown_names_fail(lookup):
    with pytest.raises(LookupError):
        lookup()
