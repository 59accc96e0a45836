"""The benchmark's tests import it as ``bench`` from the checkout's root;
`tiny_layout` is a benchmark home of its own with a tiny graph, found the
way the real one is, with the run's caches kept out of the checkout."""
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY = {"n_nodes": 3000, "n_edges": 12000, "n_labels": 6}


@pytest.fixture
def tiny_layout(tmp_path, monkeypatch):
    """A copy of the real benchmark at a tiny scale: the real metric
    readers and traffic mixes, a 3,000-node graph under every configuration
    name, short windows, no persistent compile cache."""
    from bench import graphs, harness, registry

    home = tmp_path / "bench"
    shutil.copytree(ROOT / "bench" / "metrics", home / "metrics")
    (home / "configs").mkdir()
    (home / "traffic").mkdir()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(TINY)
        (home / "configs" / f"{c['name']}.json").write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        mix["templates"] = [dict(t, nodes=min(t["nodes"], 5)) for t in mix["templates"]][:3]
        (home / "traffic" / f"{w['traffic']}.json").write_text(json.dumps(mix))
    (home / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(graphs, "CACHE_DIR", tmp_path / "graph_cache")
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    return registry.Layout.load(home / "BENCHMARK.json", home)
