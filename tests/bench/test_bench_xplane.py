"""The reduction from a profiler trace to busy time, module time and idle
gaps, on a trace recorded on a TPU v5e (``fixtures/``: a 0.43 s window of
six closed-loop queries on a 3,000-node graph, gzipped) and on intervals
made by hand."""
import gzip
import pathlib

import pytest

from bench import xplane

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "tpu_v5e_tiny.xplane.pb.gz"


def test_union_merges_overlaps_and_nesting():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (6, 6.5), (3, 4)]) == [(0, 4), (5, 7)]
    assert xplane.clip([(0, 4), (5, 7)], 1, 6) == [(1, 4), (5, 6)]


def test_gaps_are_named_by_the_innermost_host_events():
    line = [("window", 0, 100), ("run", 10, 90), ("PjitFunction(f)", 20, 30),
            ("sync", 40, 60), ("inner", 45, 50)]
    names = xplane._doing([line], [5.0, 25.0, 47.0, 55.0, 95.0], "window")
    assert names == ["-/python", "run/PjitFunction(f)", "run/inner", "run/sync",
                     "-/python"]


def test_module_name_drops_the_program_suffix():
    assert xplane.module_name("jit_expand_stwig_shard(8123)") == "jit_expand_stwig_shard"
    assert xplane.module_name("jit__lambda") == "jit__lambda"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "tpu.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return xplane.reduce(path), ProfileData.from_file(str(path))


def test_busy_is_the_union_of_device_operations(recorded):
    """Recomputed here from the raw events with a plain sweep."""
    summary, data = recorded
    plane = next(p for p in data.planes if p.name.startswith("/device:TPU"))
    window = next(e for p in data.planes if p.name.startswith("/host:")
                  for line in p.lines for e in line.events if e.name == "window")
    lo, hi = window.start_ns, window.start_ns + window.duration_ns
    ops = next(line for line in plane.lines if line.name == "XLA Ops")
    busy, reach = 0.0, lo
    for s, e in sorted((ev.start_ns, ev.start_ns + ev.duration_ns) for ev in ops.events):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            busy += e - s
            reach = e
    assert summary.window_s == pytest.approx((hi - lo) * 1e-9)
    assert summary.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < summary.busy_s < summary.window_s


def test_programs_are_named_by_the_function_that_launched_them(recorded):
    summary, data = recorded
    assert {"expand_stwig_shard", "emit_stwig_round"} <= set(summary.module_s)
    assert not any(n.startswith("jit_") for n in summary.module_s)
    # one expansion per STwig, at least one emitted round for each
    assert summary.module_n["emit_stwig_round"] >= summary.module_n["expand_stwig_shard"] > 0
    plane = next(p for p in data.planes if p.name.startswith("/device:TPU"))
    modules = next(line for line in plane.lines if line.name == "XLA Modules")
    assert sum(summary.module_s.values()) <= sum(e.duration_ns for e in modules.events) * 1e-9


def test_idle_gaps_fill_the_rest_of_the_window(recorded):
    summary, _ = recorded
    idle = sum(s for _, s in summary.idle_gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert all(name.split("/")[0] in ("run", "-") for name, _ in summary.idle_gaps)
    assert summary.idle_gaps == sorted(summary.idle_gaps, key=lambda kv: -kv[1])
