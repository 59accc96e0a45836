"""Query-path spans and counters (`repro.runtime.spans`): the spans a query
leaves in a profiler trace, read back from the ``.xplane.pb``, and the
host-sync count and attempt times in its `MatchStats`."""
import pathlib

import jax
import numpy as np
import pytest

from helpers import dfs_query, nx_oracle
from repro.api import GraphSession
from repro.graphstore import generators
from repro.runtime import ChaosConfig, ChaosInjector, RetryPolicy

# where each span sits: the innermost program span that holds it
PARENT = {
    "repro.attempt": "repro.query",
    "repro.explore": "repro.attempt",
    "repro.stwig": "repro.explore",
    "repro.expand": "repro.stwig",
    "repro.emit": "repro.stwig",
    "repro.round": "repro.emit",
    "repro.fetch": "repro.attempt",
    "repro.join": "repro.attempt",
    "repro.compact": "repro.join",
    "repro.materialize": "repro.attempt",
}


def _graph(seed=3):
    return generators.rmat(400, 1600, 4, seed=seed, symmetrize=True)


def _queries(g, n, size=4, seed=1):
    rng = np.random.default_rng(seed)
    qs = [q for q in (dfs_query(g, rng, size) for _ in range(20)) if q is not None]
    assert len(qs) >= n
    return qs[:n]


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; the host events of its thread as
    ``(name, start, end, stats)``."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        fn()
    path = next(pathlib.Path(tmp_path).glob("plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    lines = [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
         for e in line.events]
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines
    ]
    return max(lines, key=lambda evs: sum(n.startswith("repro.") for n, *_ in evs))


def _parent(ev, events):
    """The innermost program span that holds ``ev``."""
    _, s, e, _ = ev
    holders = [o for o in events if o is not ev and o[0].startswith("repro.")
               and o[1] <= s and e <= o[2] and (o[1], -o[2]) < (s, -e)]
    return min(holders, key=lambda o: o[2] - o[1], default=None)


@pytest.mark.parametrize("backend", ["local", "sharded"])
def test_query_spans_nest_and_carry_one_qid(tmp_path, backend):
    g = _graph()
    qs = _queries(g, 2)
    s = GraphSession.open(g, backend=backend)
    for q in qs:  # compile outside the trace
        s.compile(q, max_matches=64).run()

    def run():
        for q in qs:
            s.compile(q, max_matches=64).run()

    events = _traced(tmp_path, run)
    program = [ev for ev in events if ev[0].startswith("repro.")]
    assert not {"window", "compile", "run"} & {n for n, *_ in events}
    queries = [ev for ev in program if ev[0] == "repro.query"]
    assert len(queries) == 2
    qids = [ev[3]["qid"] for ev in queries]
    assert qids[0] != qids[1]
    seen = set()
    for ev in program:
        name, _, _, meta = ev
        if name == "repro.plan":  # planning precedes the query's id
            assert _parent(ev, events) is None and "qid" not in meta
            continue
        query = next(q for q in queries if q[1] <= ev[1] and ev[2] <= q[2])
        assert meta["qid"] == query[3]["qid"], ev
        if name in PARENT:
            assert _parent(ev, events)[0] == PARENT[name], ev
            seen.add(name)
    assert {"repro.attempt", "repro.explore", "repro.stwig", "repro.expand",
            "repro.emit", "repro.round", "repro.join",
            "repro.materialize"} <= seen
    stwigs = [ev[3] for ev in program if ev[0] == "repro.stwig"]
    assert all({"i", "n_roots"} <= set(m) for m in stwigs)
    assert [ev[3]["attempt"] for ev in program if ev[0] == "repro.attempt"] == [0, 0]


def test_a_stream_carries_one_qid(tmp_path):
    g = _graph()
    (q,) = _queries(g, 1)
    s = GraphSession.open(g)
    list(s.stream(q, page_size=8, child_cap=32))

    def run():
        pages = list(s.stream(q, page_size=8, child_cap=32))
        assert pages

    events = _traced(tmp_path, run)
    program = [ev for ev in events if ev[0].startswith("repro.")]
    # the setup's exploration and each block's join are the stream's
    # outermost spans, all under the one id drawn when it was opened
    stages = [ev for ev in program if ev[0] in ("repro.explore", "repro.join")]
    assert {ev[0] for ev in stages} == {"repro.explore", "repro.join"}
    assert all(_parent(ev, events) is None for ev in stages)
    qids = {ev[3].get("qid") for ev in program if ev[0] != "repro.plan"}
    assert len(qids) == 1 and None not in qids


def _expected_syncs(stats, backend: str) -> int:
    """One read of the root count and, per round, of the row count (and on
    the sharded engine the overflow); locally three more per STwig (rows,
    roots, overflow of its table). Then two per table compacted, the
    join's overflow and the two reads of materialisation."""
    if backend == "local":
        explore = sum(r + 4 for r in stats.rounds)
    else:
        explore = sum(1 + 2 * r for r in stats.rounds)
    return explore + 2 * len(stats.rounds) + 1 + 2


@pytest.mark.parametrize("backend", ["local", "sharded"])
def test_host_syncs_follow_the_rounds(backend):
    g = _graph()
    qs = _queries(g, 4) + _queries(g, 2, size=3, seed=4)
    s = GraphSession.open(g, backend=backend)
    n_stwigs = set()
    for q in qs:
        res = s.compile(q, max_matches=64).run()
        assert res.stats.retries == 0
        assert len(res.stats.attempt_s) == 1
        assert res.stats.host_syncs == _expected_syncs(res.stats, backend)
        n_stwigs.add(len(res.stats.rounds))
    assert len(n_stwigs) > 1  # queries of one and of several STwigs


def test_explore_is_split_into_expand_and_emit():
    g = _graph()
    s = GraphSession.open(g)
    for q in _queries(g, 3):
        st = s.compile(q, max_matches=64).run().stats.stage_times
        assert 0 < st["expand"] and 0 < st["emit"]
        assert st["expand"] + st["emit"] <= st["explore"]


def test_escalation_keeps_every_attempt(tmp_path):
    g = generators.rmat(120, 480, 4, seed=3, symmetrize=True)
    q = dfs_query(g, np.random.default_rng(5), 3)
    chaos = ChaosInjector(ChaosConfig(seed=0, force_overflow=True))
    out = {}

    def run():
        out["res"] = s.run(
            q,
            retry_policy=RetryPolicy(max_retries=2, backoff_s=0.01,
                                     ceiling_bytes=float("inf")),
            child_cap=32,
            join_rows_cap=1 << 18,
        )

    with GraphSession.open(g, backend="local", chaos=chaos) as s:
        events = _traced(tmp_path, run)
    res = out["res"]
    st = res.stats
    # the first plan precedes the query; each replan of an escalation runs
    # inside the attempt that escalates, under the query's id
    (query,) = [ev for ev in events if ev[0] == "repro.query"]
    replans = [ev for ev in events if ev[0] == "repro.plan"
               and query[1] <= ev[1] and ev[2] <= query[2]]
    assert len(replans) == st.retries
    for ev in replans:
        assert _parent(ev, events)[0] == "repro.attempt"
        assert ev[3]["qid"] == query[3]["qid"]
    assert st.retries == 2
    assert len(st.attempt_s) == st.retries + 1
    # two back-offs of at least 0.01 s and 0.02 s precede the last attempt
    assert sum(st.attempt_s[:-1]) >= 0.03
    assert st.host_syncs > _expected_syncs(st, "local")
    assert set(map(tuple, res.rows.tolist())) == nx_oracle(g, q)


def test_a_query_refused_at_admission_ran_no_attempt():
    g = generators.rmat(120, 480, 4, seed=3, symmetrize=True)
    q = dfs_query(g, np.random.default_rng(5), 3)
    with GraphSession.open(g) as s:
        res = s.run(q, deadline_s=0.0)
    assert res.stats.degrade_reason == "deadline"
    assert res.stats.attempt_s == [] and res.stats.host_syncs == 0
