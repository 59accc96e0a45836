"""A run on the CPU with the timed path broken underneath: the harness's
look for a chip is lifted, the rest of the run is real, and ``correct``
has to come out false for each fault the cell can have."""
import time

import pytest

from bench import harness
from repro.core.engine import SubgraphMatcher
from repro.core.result import MatchResult

CELL = "patents-dfs"


def run(layout):
    return harness.run_cell(layout, CELL, 2**31 + 99, 2.0, False,
                            time.perf_counter(), require_tpu=False)


def alter_a_node(monkeypatch):
    real = SubgraphMatcher._materialize

    def altered(self, *a, **k):
        rows = real(self, *a, **k)
        if rows.shape[0]:
            rows = rows.copy()
            rows[0, -1] = (rows[0, -1] + 1) % self.pg.n_nodes
        return rows

    monkeypatch.setattr(SubgraphMatcher, "_materialize", altered)


def halve(complete):
    """Half of each answer's rows left out; the program then calls the
    answer complete, or says it is partial."""
    def fault(monkeypatch):
        real = MatchResult.__init__

        def halved(self, *a, **k):
            real(self, *a, **k)
            self.rows = self.rows[: (self.rows.shape[0] + 1) // 2]
            self.n_matches = int(self.rows.shape[0])
            self.complete = self.complete and complete

        monkeypatch.setattr(MatchResult, "__init__", halved)
    return fault


def test_sound_runs_are_correct(tiny_layout):
    out = run(tiny_layout)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault, check", [
    (alter_a_node, "bad_rows"),
    (halve(complete=True), "count_gap"),
    (halve(complete=False), "count_gap"),
], ids=["altered-node", "half-the-rows-called-complete", "half-the-rows-called-partial"])
def test_a_broken_answer_is_not_correct(tiny_layout, monkeypatch, fault, check):
    fault(monkeypatch)
    out = run(tiny_layout)
    assert not out["correct"]
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]
