"""The least bytes of the exploration step, and the readers that turn them
and a trace into a roofline share."""
import types

import numpy as np
import pytest

from bench import harness, kernels, registry, traffic, xplane
from repro.api import GraphSession
from repro.core.backend import n_words
from repro.core.match import Bindings, expand_stwig_shard
from repro.graphstore import generators


def dfs_query(g, rng, n):
    return traffic.sample_templates(g, {"templates": [{"kind": "dfs", "nodes": n}]}, rng)[0]


def test_expand_bytes_by_hand():
    got = kernels.expand_bytes(n_children=2, child_cap=8, child_bound=(True, False),
                               root_bound=False, cap=100, edge_cap=1000, n_words=4)
    read = 400 + 404 + 4000 + 4000 + 16
    written = 2 * 101 * 8 * 4 + 2 * 101 * 4 + 100 + 400 + 5
    assert got == read + written


@pytest.mark.parametrize("seed", [0, 1])
def test_expand_bytes_match_the_steps_arrays(seed):
    """The count is the named inputs plus every output, at the shapes the
    program's own step takes and returns."""
    g = generators.rmat(1500, 6000, 5, seed=seed)
    session = GraphSession.open(g, kernels="jnp")
    q = dfs_query(g, np.random.default_rng(seed), 5)
    plan = session.compile(q).plan
    sg = session.engine.g
    bind = Bindings.fresh(plan.n_qnodes, session.pg.n_total + 1)
    spec = plan.specs[0]
    out = expand_stwig_shard(sg, bind, spec)
    words = n_words(session.pg.n_total + 1)
    assert bind.words.shape[1] == words
    inputs = (sg.labels.nbytes + sg.indptr.nbytes + sg.indices.nbytes
              + sg.indices.size * 4
              + 4 * words * (sum(spec.child_bound) + spec.root_bound))
    outputs = sum(np.asarray(x).nbytes for x in out)
    assert kernels.expand_bytes(
        n_children=spec.n_children, child_cap=spec.child_cap,
        child_bound=spec.child_bound, root_bound=spec.root_bound,
        cap=sg.cap, edge_cap=sg.edge_cap, n_words=words) == inputs + outputs


def test_unknown_chip_has_no_peaks():
    assert kernels.peaks_for("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError):
        kernels.peaks_for("cpu")


def _run(calls, module_s, module_n):
    trace = xplane.TraceSummary(window_s=2.0, busy_s=0.5, module_s=module_s,
                                module_n=module_n, idle_gaps=[])
    return harness.Run(workload="w", window_s=2.0, setup_s=1.0, queries=[],
                       device_kind="TPU v5 lite", trace=trace, calls=calls)


def test_roofline_reader():
    import jax

    g = generators.rmat(1500, 6000, 5, seed=0)
    session = GraphSession.open(g, kernels="jnp")
    plan = session.compile(dfs_query(g, np.random.default_rng(0), 5)).plan
    spec = plan.specs[0]
    sg = session.engine.g
    bind = Bindings.fresh(plan.n_qnodes, session.pg.n_total + 1)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), (sg, bind))
    call = harness.Call(("match", spec, "jnp", "expand", 1), shapes)
    read = registry.Layout.load().reader("expand_roofline.run")
    b = kernels.expand_bytes(
        n_children=spec.n_children, child_cap=spec.child_cap,
        child_bound=spec.child_bound, root_bound=spec.root_bound,
        cap=sg.cap, edge_cap=sg.edge_cap, n_words=bind.words.shape[1])
    t = 2 * b / 819e9
    got = read(_run([call, call], {"expand_stwig_shard": 2 * t}, {"expand_stwig_shard": 2}))
    assert got == pytest.approx(50.0)
    untraced = _run([call], {}, {})
    untraced.trace = None
    assert read(untraced) is None


@pytest.mark.parametrize("calls, n", [(1, 2), (0, 0), (2, 0)],
                         ids=["counts-differ", "nothing-recorded", "no-programs"])
def test_roofline_reader_refuses_what_it_cannot_match(calls, n):
    """A traced run whose exploration calls and programs do not pair one to
    one is an error, not a metric left out."""
    spec = types.SimpleNamespace(n_children=1, child_cap=8, child_bound=(False,),
                                 root_bound=False)
    g = types.SimpleNamespace(labels=np.zeros(10), indices=np.zeros(40))
    bind = types.SimpleNamespace(words=np.zeros((2, 1)))
    call = harness.Call(("match", spec, "jnp", "expand", 1), (g, bind))
    read = registry.Layout.load().reader("expand_roofline.run")
    module = {"expand_stwig_shard": 1.0} if n else {}
    with pytest.raises(RuntimeError):
        read(_run([call] * calls, module, {"expand_stwig_shard": n} if n else {}))


def test_the_engines_cache_keys_are_what_the_reader_reads():
    """Drive the real engine with the harness's recorder: every STwig's
    exploration step is found by its cache key, and its byte count is the
    one its spec and arrays give."""
    g = generators.rmat(1500, 6000, 5, seed=3)
    session = GraphSession.open(g)
    q = dfs_query(g, np.random.default_rng(3), 5)
    calls = []
    session.cache.recorder = lambda key, f, a, kw: calls.append(
        harness.Call(key, harness._shapes(a)))
    res = session.compile(q, max_matches=64).run()
    session.cache.recorder = None
    assert res.complete
    found = [(c, kernels.expand_call_bytes(c.key, c.args)) for c in calls]
    expand = [(c, b) for c, b in found if b is not None]
    specs = session.compile(q, max_matches=64).plan.specs
    assert len(expand) >= len(specs) > 0
    sg = session.engine.g
    words = n_words(session.pg.n_total + 1)
    for c, b in expand:
        spec = c.key[1]
        assert spec in specs
        assert b == kernels.expand_bytes(
            n_children=spec.n_children, child_cap=spec.child_cap,
            child_bound=spec.child_bound, root_bound=spec.root_bound,
            cap=sg.cap, edge_cap=sg.edge_cap, n_words=words)
    assert any(c.key[0] == "match" and c.key[3] == "emit" for c in calls)
