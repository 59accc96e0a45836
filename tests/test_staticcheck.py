"""Seeded-violation suite for `repro.analysis.staticcheck`.

Every rule must (a) fire on a planted violation and (b) stay silent on the
real repository — a lint that can't catch its own fixture, or that cries
wolf on the clean tree, gates nothing.
"""
import pathlib
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.staticcheck import (
    archlint,
    cachekeys,
    collective_safety,
    contracts,
    costmodel,
    run_all,
)
from repro.analysis.staticcheck.findings import RULES, report_json
from repro.core import backend as backend_lib
from repro.core.backend import OpContract

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _rules_of(findings):
    return {f.rule for f in findings}


def _write(root: pathlib.Path, rel: str, body: str) -> None:
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))


@pytest.fixture()
def fixture_repo(tmp_path):
    """A minimal repo skeleton the AST passes can walk."""
    _write(tmp_path, "src/repro/__init__.py", "")
    _write(tmp_path, "src/repro/core/__init__.py", "")
    _write(tmp_path, "tests/test_ok.py", "import repro.core\n")
    return tmp_path


# ------------------------------------------------------------ archlint rules
def test_bitset_twiddling_planted(fixture_repo):
    _write(fixture_repo, "src/repro/core/twiddle.py", """\
        def word_of(i):
            return i >> 5, i & 31, i % 32
    """)
    _write(fixture_repo, "tests/test_ok.py",
           "import repro.core.twiddle\n")
    fs = [f for f in archlint.run(str(fixture_repo))
          if f.rule == "bitset-twiddling"]
    assert len(fs) == 3 and all("twiddle.py" in f.path for f in fs)


def test_bitset_twiddling_allowed_in_kernels_bitset(fixture_repo):
    _write(fixture_repo, "src/repro/kernels/__init__.py", "")
    _write(fixture_repo, "src/repro/kernels/bitset/__init__.py", "")
    _write(fixture_repo, "src/repro/kernels/bitset/impl.py", """\
        def word_of(i):
            return i >> 5
    """)
    _write(fixture_repo, "tests/test_ok.py",
           "import repro.kernels.bitset.impl\n")
    assert not [f for f in archlint.run(str(fixture_repo))
                if f.rule == "bitset-twiddling"]


def test_module_jit_state_planted(fixture_repo):
    _write(fixture_repo, "src/repro/core/jitstate.py", """\
        import functools
        import jax

        @functools.lru_cache(maxsize=None)
        def build(n):
            return n

        square = jax.jit(lambda x: x * x)
    """)
    _write(fixture_repo, "tests/test_ok.py", "import repro.core.jitstate\n")
    fs = [f for f in archlint.run(str(fixture_repo))
          if f.rule == "module-jit-state"]
    assert len(fs) == 2  # the decorator AND the import-time jit


def test_direct_engine_construction_planted(fixture_repo):
    _write(fixture_repo, "src/repro/core/sneaky.py", """\
        from repro.core.engine import SubgraphMatcher

        def make(pg):
            return SubgraphMatcher(pg)
    """)
    _write(fixture_repo, "tests/test_ok.py", "import repro.core.sneaky\n")
    fs = [f for f in archlint.run(str(fixture_repo))
          if f.rule == "direct-engine-construction"]
    assert len(fs) == 1 and fs[0].line == 4


def test_stream_host_sync_planted(fixture_repo):
    _write(fixture_repo, "src/repro/core/consumer.py", """\
        import jax

        def drain(compiled):
            out = []
            for page in compiled.stream(page_size=8):
                out.append(jax.device_get(page.rows))
            return out
    """)
    _write(fixture_repo, "tests/test_ok.py", "import repro.core.consumer\n")
    fs = [f for f in archlint.run(str(fixture_repo))
          if f.rule == "stream-host-sync"]
    assert len(fs) == 1


def test_missing_slow_marker_planted(fixture_repo):
    _write(fixture_repo, "tests/test_spawns.py", """\
        import subprocess

        def test_heavy():
            subprocess.run(["true"])
    """)
    fs = [f for f in archlint.run(str(fixture_repo))
          if f.rule == "missing-slow-marker"]
    assert len(fs) == 1
    # module-level pytestmark silences it
    _write(fixture_repo, "tests/test_spawns.py", """\
        import subprocess
        import pytest

        pytestmark = pytest.mark.slow

        def test_heavy():
            subprocess.run(["true"])
    """)
    assert not [f for f in archlint.run(str(fixture_repo))
                if f.rule == "missing-slow-marker"]


def test_orphan_module_planted(fixture_repo):
    _write(fixture_repo, "src/repro/core/dead.py", "VALUE = 1\n")
    fs = [f for f in archlint.run(str(fixture_repo))
          if f.rule == "orphan-module"]
    assert [f.path for f in fs] == ["src/repro/core/dead.py"]
    # the extras/ quarantine is exempt
    _write(fixture_repo, "src/repro/extras/__init__.py", "")
    _write(fixture_repo, "src/repro/extras/dead2.py", "VALUE = 2\n")
    fs = [f for f in archlint.run(str(fixture_repo))
          if f.rule == "orphan-module"]
    assert [f.path for f in fs] == ["src/repro/core/dead.py"]


def test_unused_import_planted(fixture_repo):
    _write(fixture_repo, "src/repro/core/lazy.py", """\
        import os
        import sys

        def cwd():
            return os.getcwd()
    """)
    _write(fixture_repo, "tests/test_ok.py", "import repro.core.lazy\n")
    fs = [f for f in archlint.run(str(fixture_repo))
          if f.rule == "unused-import"]
    assert len(fs) == 1 and "`sys`" in fs[0].message


def test_suppression_comment_silences_rule(fixture_repo):
    _write(fixture_repo, "src/repro/core/twiddle.py", """\
        def word_of(i):
            return i >> 5  # staticcheck: ignore[bitset-twiddling]
    """)
    _write(fixture_repo, "tests/test_ok.py", "import repro.core.twiddle\n")
    assert not [f for f in archlint.run(str(fixture_repo))
                if f.rule == "bitset-twiddling"]


# ------------------------------------------------------------- cache keys
def test_cache_key_coverage_planted(fixture_repo):
    _write(fixture_repo, "src/repro/core/leaky.py", """\
        import jax

        class Engine:
            def fn(self, spec, cap):
                return self.cache.get(
                    ("match", spec),
                    lambda: jax.jit(lambda x: x[:cap]),
                )
    """)
    _write(fixture_repo, "tests/test_ok.py", "import repro.core.leaky\n")
    fs = cachekeys.check_cache_keys(fixture_repo)
    assert len(fs) == 1 and "'cap'" in fs[0].message


def test_cache_key_coverage_assigned_key_and_named_builder(fixture_repo):
    _write(fixture_repo, "src/repro/core/tight.py", """\
        import jax

        class Engine:
            def fn(self, spec, cap):
                def build():
                    return jax.jit(lambda x: x[:cap])

                key = ("match", spec, cap)
                return self.cache.get(key, build)
    """)
    _write(fixture_repo, "tests/test_ok.py", "import repro.core.tight\n")
    assert not cachekeys.check_cache_keys(fixture_repo)


# --------------------------------------------------------- jaxpr contracts
class _FakeKernels:
    """Minimal stand-in for a `Kernels` backend, one op per test."""

    name = "_staticcheck_test"

    def __init__(self, fn):
        self._fn = fn

    def op(self, x):
        return self._fn(x)


def _fake_contract(out_dtypes):
    return OpContract(
        "op",
        lambda: ((jax.ShapeDtypeStruct((8,), jnp.int32),), {}),
        out_dtypes,
    )


def _check_fake(fn, out_dtypes):
    """Register a throwaway backend, run the contract pass on it alone."""
    name = _FakeKernels.name
    backend_lib.register_backend(
        name, lambda: _FakeKernels(fn), contracts=(_fake_contract(out_dtypes),)
    )
    try:
        return contracts.check_kernel_contracts([name])
    finally:
        backend_lib._REGISTRY.pop(name, None)
        backend_lib._INSTANCES.pop(name, None)
        backend_lib._CONTRACTS.pop(name, None)


def test_jaxpr_out_dtype_planted():
    fs = _check_fake(lambda x: x.astype(jnp.float32), out_dtypes=("int32",))
    assert _rules_of(fs) == {"jaxpr-out-dtype"}
    assert "float32" in fs[0].message


def test_jaxpr_out_dtype_trace_failure_is_a_finding():
    def broken(x):
        raise TypeError("no abstract trace for you")

    fs = _check_fake(broken, out_dtypes=("int32",))
    assert _rules_of(fs) == {"jaxpr-out-dtype"}
    assert "failed to trace" in fs[0].message


def test_jaxpr_dtype_width_planted():
    with jax.enable_x64(True):
        fs = _check_fake(
            lambda x: x.astype(jnp.float64), out_dtypes=("float64",)
        )
    assert _rules_of(fs) == {"jaxpr-dtype-width"}


def test_jaxpr_banned_primitive_planted():
    def leaky(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((8,), jnp.int32), x
        )

    fs = _check_fake(leaky, out_dtypes=("int32",))
    assert "jaxpr-banned-primitive" in _rules_of(fs)


def test_real_contracts_trace_clean_on_all_backends():
    # under ambient x64 restrict to jnp, matching the CLI's --x64 policy
    # (pallas interpret-mode runs its grid loop in int64 by itself)
    backends = ["jnp"] if jax.config.jax_enable_x64 else None
    assert contracts.check_kernel_contracts(backends) == []


# ------------------------------------------------- collective safety (d)
def _shard_trace(body, mesh_axes, in_specs, out_specs, *args):
    """Trace `body` under a shard_map on a mesh built from this process's
    single device (axis sizes 1 — the analysis is static, sizes only name
    the axes)."""
    import numpy as np

    from jax.sharding import Mesh
    from jax import shard_map

    devs = np.array(jax.devices()[:1]).reshape((1,) * len(mesh_axes))
    mesh = Mesh(devs, tuple(mesh_axes))
    f = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    return jax.make_jaxpr(f)(*args)


def _one_finding(findings, rule_id):
    assert [f.rule for f in findings] == [rule_id], \
        "\n".join(str(f) for f in findings)
    # the acceptance path: the finding must survive into --json output
    assert rule_id in report_json(findings)
    return findings[0]


def test_collective_divergent_control_planted():
    from jax.sharding import PartitionSpec as P

    def body(v):
        i = jax.lax.axis_index("data")
        return jax.lax.cond(
            i > 0, lambda u: jax.lax.psum(u, "data"), lambda u: u, v
        )

    j = _shard_trace(body, ("data",), P("data"), P("data"),
                     jnp.arange(8, dtype=jnp.int32))
    f = _one_finding(
        collective_safety.check_collective_safety(j, "fx"),
        "coll-divergent-control",
    )
    assert "psum" in f.message


def test_collective_ppermute_bijection_planted():
    from jax.sharding import PartitionSpec as P

    def body(v):  # empty perm: nobody sends, everybody zero-fills
        return jax.lax.ppermute(v, "data", perm=[])

    j = _shard_trace(body, ("data",), P("data"), P("data"),
                     jnp.arange(8, dtype=jnp.int32))
    _one_finding(
        collective_safety.check_collective_safety(j, "fx"),
        "coll-ppermute-bijection",
    )


def test_collective_axis_name_planted():
    from jax.sharding import PartitionSpec as P

    def body(v):  # "model" is a mesh axis, but not the engine's axis
        return jax.lax.psum(v, "model")

    j = _shard_trace(body, ("data", "model"), P("data"), P("data"),
                     jnp.arange(8, dtype=jnp.int32).reshape(8, 1))
    _one_finding(
        collective_safety.check_collective_safety(
            j, "fx", allowed_axes=("data",)
        ),
        "coll-axis-name",
    )


def test_collective_head_gather_planted():
    from jax.sharding import PartitionSpec as P

    def body(h, t):  # Theorem 5: the head table must never be gathered
        g = jax.lax.all_gather(h, "data", tiled=True)
        return g + t

    x = jnp.arange(8, dtype=jnp.int32)
    j = _shard_trace(body, ("data",), (P("data"), P("data")), P("data"),
                     x, x)
    _one_finding(
        collective_safety.check_collective_safety(j, "fx", head_invars=(0,)),
        "coll-head-gather",
    )
    # the same program is clean when the gathered operand is not the head
    assert collective_safety.check_collective_safety(
        j, "fx", head_invars=(1,)
    ) == []


def test_collective_clean_on_benign_body():
    from jax.sharding import PartitionSpec as P

    def body(v):  # full-axis reduce + bijective self-permute: all legal
        s = jax.lax.psum(v, "data")
        p = jax.lax.ppermute(v, "data", perm=[(0, 0)])
        return s + p

    j = _shard_trace(body, ("data",), P("data"), P("data"),
                     jnp.arange(8, dtype=jnp.int32))
    reports = []
    assert collective_safety.check_collective_safety(
        j, "fx", allowed_axes=("data",), reports=reports
    ) == []
    assert reports[0].collectives == ["psum", "ppermute"]


def test_head_taints_for_key_positions():
    schemas = ((0, 1), (1, 2), (2, 3))
    assert collective_safety.head_taints_for_key(
        ("dist_join", schemas, (0, 1, 2), 1, 64, 4, (8, 8, 8), None, "jnp")
    ) == (1, 4)
    assert collective_safety.head_taints_for_key(
        ("dist_gather", 3, 2, (8, 8, 8), None)
    ) == (2, 5)
    assert collective_safety.head_taints_for_key(
        ("dist_join_block", schemas, (0, 1), 64, 4, 8, (8, 8), 16, "jnp")
    ) == (0, 1)
    assert collective_safety.head_taints_for_key(("dist_match", "x")) == ()


# ------------------------------------------------------- cost model (e)
def _est(target, peak=1.0, flops=1.0, coll=0.0):
    return costmodel.CostEstimate(
        target=target, peak_bytes=peak, flops=flops,
        collective_bytes=coll, collective_by_kind={},
    )


_TEST_BUDGETS = {
    "linear_slack": 2.0,
    "entries": {
        "engine:test:jnp:match": {
            "peak_bytes": 1000, "flops": 5000, "collective_bytes": 100,
        },
    },
}


def test_cost_budget_overflow_planted():
    f = _one_finding(
        costmodel.check_budgets(
            [_est("engine:test:jnp:match", peak=1500, flops=10, coll=0)],
            _TEST_BUDGETS,
        ),
        "cost-budget-exceeded",
    )
    assert "peak_bytes" in f.message


def test_cost_budget_missing_fails_closed():
    _one_finding(
        costmodel.check_budgets(
            [_est("engine:test:jnp:new_entry_point", peak=1)], _TEST_BUDGETS,
        ),
        "cost-budget-missing",
    )


def test_cost_budget_within_ceiling_is_clean():
    assert costmodel.check_budgets(
        [_est("engine:test:jnp:match", peak=999, flops=4999, coll=99)],
        _TEST_BUDGETS,
    ) == []


def test_cost_superlinear_memory_planted():
    small = [_est("engine:test:jnp:join", peak=1000)]
    # quadratic structure: 4x graph -> 16x bytes, bound is 2.0 * 4 = 8x
    _one_finding(
        costmodel.check_linear_memory(
            small, [_est("engine:test:jnp:join", peak=16000)],
            size_ratio=4.0, slack=2.0,
        ),
        "cost-superlinear-memory",
    )
    assert costmodel.check_linear_memory(
        small, [_est("engine:test:jnp:join", peak=4000)],
        size_ratio=4.0, slack=2.0,
    ) == []


def test_cost_estimate_counts_quadratic_intermediate():
    """The liveness peak must see a materialized O(n^2) outer product."""
    n = 64

    def outer(a, b):
        z = a[:, None] * b[None, :]          # (n, n) float32
        return z.sum()

    est = costmodel.estimate(
        jax.make_jaxpr(outer)(
            jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32)
        ),
        target="fx",
    )
    assert est.peak_bytes >= n * n * 4


def test_checked_in_budgets_cover_probe_targets():
    """Every engine×kernels×entry-point the probe records has a budget row
    (the fail-closed contract, checked without running the probe)."""
    budgets = costmodel.load_budgets()
    targets = set(budgets["entries"])
    for eng, heads in (
        ("local", ("match", "join")),
        ("sharded", ("dist_match", "dist_join", "dist_gather",
                     "dist_join_block")),
    ):
        for k in ("jnp", "pallas-interpret"):
            for h in heads:
                assert f"engine:{eng}:{k}:{h}" in targets


def test_cost_flops_agree_with_hlo_within_10pct():
    """Acceptance: jaxpr FLOP estimates vs XLA cost_analysis on the
    benchmarked kernels (matmul-shaped cin layer + the join probe's sort)."""
    from repro.kernels.cin.ref import cin_layer_reference

    xk = jnp.ones((4, 8, 16), jnp.float32)
    x0 = jnp.ones((4, 4, 16), jnp.float32)
    w = jnp.ones((32, 8), jnp.float32)
    r = costmodel.hlo_cross_check(cin_layer_reference, xk, x0, w)
    assert r["hlo_flops"] > 0
    assert abs(r["est_flops"] - r["hlo_flops"]) <= 0.1 * r["hlo_flops"], r


# ----------------------------------------------------------- clean repo
def test_static_passes_clean_on_repo():
    """The repo's own tree carries zero findings (the CI gate); the engine
    probe is covered separately (`test_retrace.py`) because it executes."""
    backends = ["jnp"] if jax.config.jax_enable_x64 else None
    fs = run_all(REPO_ROOT, engines=False, kernel_backends=backends)
    assert fs == [], "\n".join(str(f) for f in fs)


def test_every_rule_has_a_registered_description():
    assert len(RULES) >= 15  # incl. the collective-safety + cost rules
    for r in RULES.values():
        assert r.layer and r.description
