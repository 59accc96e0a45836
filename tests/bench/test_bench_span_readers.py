"""The readers of the program's spans and counters: each has its file and
its entry, averages over the window's queries, and gives nothing where no
query carries its field (as on a program that predates it)."""
import types

import numpy as np
import pytest

from bench import harness, registry
from repro.core.result import MatchStats

READERS = {
    "expand_s.run": ("s", "program_span", "exploration"),
    "emit_s.run": ("s", "program_span", "exploration"),
    "host_syncs.run": ("count", "program_counter", "host"),
    "retry_s.run": ("s", "program_span", "resilience"),
}


def _run(stats):
    queries = [harness.Query(0, np.zeros((0, 2)), True, s) for s in stats]
    return harness.Run(workload="patents-dfs", window_s=1.0, setup_s=1.0,
                       queries=queries, device_kind="TPU v5 lite")


def _stats(expand, emit, syncs, attempts):
    return MatchStats(stage_times={"explore": expand + emit + 0.01,
                                   "expand": expand, "emit": emit},
                      host_syncs=syncs, attempt_s=attempts)


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_has_its_entry(name):
    lay = registry.Layout.load()
    entry = next(m for m in lay.spec["per_layer"] if m["name"] == name)
    unit, source, layer = READERS[name]
    assert (entry["unit"], entry["source"], entry["layer"]) == (unit, source, layer)
    assert entry["better"] == "lower" and entry["moves"] == "query_s"
    assert entry["workloads"] == ["patents-dfs"]
    assert name in [m["name"] for m in lay.metrics("patents-dfs", True)]
    assert lay.spec["per_layer"][-4:] == [
        m for m in lay.spec["per_layer"] if m["name"] in READERS]


@pytest.mark.parametrize("name, want", [
    ("expand_s.run", (7.0 + 8.0) / 2),
    ("emit_s.run", (0.2 + 0.4) / 2),
    ("host_syncs.run", (12 + 30) / 2),
    ("retry_s.run", (0.0 + (1.5 + 2.5)) / 2),
])
def test_each_reader_averages_over_the_queries(name, want):
    run = _run([_stats(7.0, 0.2, 12, [9.0]), _stats(8.0, 0.4, 30, [1.5, 2.5, 8.0])])
    assert registry.Layout.load().reader(name)(run) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_gives_nothing_without_its_field(name):
    read = registry.Layout.load().reader(name)
    older = types.SimpleNamespace(stage_times={"explore": 7.0, "join": 0.1})
    assert read(_run([older, older])) is None
    assert read(_run([])) is None
    if name == "retry_s.run":  # a query refused before any attempt ran
        assert read(_run([MatchStats(attempt_s=[])])) is None
