"""The harness: its command on a machine with no TPU, the layout it finds
cells by, and a run on the CPU of a cell added with files alone."""
import json
import pathlib
import re
import runpy
import sys
import time
import types

import pytest

from bench import harness, registry

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_command_refuses_a_machine_without_a_tpu(monkeypatch, capsys):
    """``bench/run.py`` as the command line runs it, in this process, whose
    JAX has the CPU alone: a non-zero exit and no result."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    script = ROOT / spec["command"][1]
    monkeypatch.setattr(sys, "argv", [str(script), "--workload",
                                      spec["workloads"][0]["name"], "--seed",
                                      "2147483700", "--seconds", "1", "--trace", "0"])
    with pytest.raises(SystemExit) as done:
        runpy.run_path(str(script), run_name="__main__")
    out = capsys.readouterr()
    assert done.value.code != 0
    assert out.out.strip() == ""
    assert "no TPU" in out.err


def test_every_named_part_has_its_file():
    lay = registry.Layout.load()
    spec = lay.spec
    for c in spec["configs"]:
        cfg = lay.config(c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        lay.traffic(w["traffic"])
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        for trace in (False, True):
            names = [m["name"] for m in lay.metrics(w["name"], trace)]
            assert names, (w["name"], trace)
            for n in names:
                assert callable(lay.reader(n))
        assert "setup_s" in [m["name"] for m in lay.metrics(w["name"], False)]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"])
        if "moves" in m:
            assert m["moves"] in {e["name"] for e in spec["end_to_end"]}


def test_a_cell_added_as_files_runs_without_a_harness_edit(tiny_layout):
    """A new configuration, traffic mix and per-layer metric are three files
    and three entries; the harness finds and runs them."""
    home = tiny_layout.home
    cfg = tiny_layout.config("patents")
    cfg.update(name="grid_like", n_nodes=1500, n_edges=5000, n_labels=3)
    (home / "configs" / "grid_like.json").write_text(json.dumps(cfg))
    (home / "traffic" / "paths3.json").write_text(json.dumps({
        "max_matches": 64, "trace_seconds": 1,
        "templates": [{"kind": "path", "nodes": 3}, {"kind": "path", "nodes": 4}]}))
    (home / "metrics" / "rows_per_query.py").write_text(
        "def read(run):\n"
        "    return sum(q.rows.shape[0] for q in run.queries) / len(run.queries)\n")
    spec = tiny_layout.spec
    spec["configs"].append({"name": "grid_like", "source": "test", "reduced": [],
                            "file": "x", "why": "test"})
    spec["workloads"].append({"name": "grid_like-paths3", "config": "grid_like",
                              "traffic": "paths3", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "rows_per_query", "unit": "rows",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["grid_like-paths3"]})
    out = harness.run_cell(tiny_layout, "grid_like-paths3", 11, 1.0, False,
                           time.perf_counter(), require_tpu=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "rows_per_query"}
    assert out["metrics"]["rows_per_query"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1


def test_the_command_runs_a_cell_through_run_py(tiny_layout, monkeypatch, capsys):
    """``run.py``'s main with the layout of a tiny benchmark, its CPU check
    lifted: one JSON line, the cell's end-to-end metrics in it."""
    real = harness.run_cell
    monkeypatch.setattr(registry.Layout, "load", classmethod(lambda cls: tiny_layout))
    monkeypatch.setattr(harness, "run_cell", lambda *a, **k: real(*a, require_tpu=False))
    rc = harness.main(["--workload", "patents-dfs", "--seed", str(2**31 + 3),
                       "--seconds", "1", "--trace", "0"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["correct"]
    assert set(out["metrics"]) == {"setup_s", "query_s"}
    assert sys.modules["bench.harness"] is harness


class _Session:
    """Answers every query at once with no rows, complete."""

    def __init__(self):
        self.asked = []

    def compile(self, q, max_matches):
        self.asked.append(q)
        return self

    def run(self):
        import numpy as np

        return types.SimpleNamespace(rows=np.zeros((0, 2)), complete=True, stats=None)


@pytest.mark.parametrize("seconds", [0.0, 0.05])
def test_the_window_closes_on_a_whole_cycle_of_templates(seconds):
    session = _Session()
    queries, window_s = harness.Client(session, ["a", "b", "c"], 8).window(seconds)
    assert len(queries) >= 3 and len(queries) % 3 == 0
    assert [q.template for q in queries] == [i % 3 for i in range(len(queries))]
    assert session.asked == ["abc"[i % 3] for i in range(len(queries))]
    assert window_s >= seconds


def test_setup_leaves_out_generating_the_graph(tiny_layout, monkeypatch):
    """A graph missing from the cache is generated before set-up; loading
    it from the cache is set-up."""
    from bench import graphs

    real = graphs.make_graph

    def slow(*a, **k):
        time.sleep(3.0)
        return real(*a, **k)

    warmed = []
    real_warm = harness.Client.warm

    def warm(self):
        real_warm(self)
        warmed.append(time.perf_counter())

    monkeypatch.setattr(graphs, "make_graph", slow)
    monkeypatch.setattr(harness.Client, "warm", warm)
    t_start = time.perf_counter()
    out = harness.run_cell(tiny_layout, "patents-dfs", 17, 0.5, False, t_start,
                           require_tpu=False)
    assert out["correct"], out["checks"]
    setup_s = out["metrics"]["setup_s"]["value"]
    assert 0.0 < setup_s < warmed[0] - t_start - 3.0
