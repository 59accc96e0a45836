"""Compile the chip's main path for a described TPU v5e, with no chip.

Every op that ``kernels="auto"`` selects on a TPU (the `TPU_OPS` table),
every repaired Pallas kernel, and the local engine's steps (the two halves
of an STwig match, and a join) are compiled by the TPU compiler at the
widths ``chip_smoke.py`` runs: the US-Patents-shaped graph's 3,774,768
node slots and about 33M edge slots, and the plan caps its queries get
(2^18-row STwig tables, 2^16-row join output, dup window 64). Nothing
runs; a kernel the chip's compiler refuses, or a program that stops
compiling in seconds, fails here at no chip time. This is the only file
that describes the chip.
"""
from __future__ import annotations

import functools
import os
import time

import pytest

import jax
import jax.numpy as jnp

from repro.core.backend import (
    OP_CONTRACTS,
    TPU_KERNELS,
    TPU_OPS,
    get_kernels,
    n_words,
)

NODES = 3_774_768             # node slots of the one-shard Patents graph
EDGE_SLOTS = 2 * 16_522_438   # symmetrised CSR slots (upper bound)
ROWS = 1 << 18                # STwig table rows: make_plan's emission budget
JOIN_OUT = 1 << 16            # make_plan's join_rows_cap
DUP = 64                      # make_plan's join_dup_cap
CHILD_CAP = 8
WORDS = n_words(NODES + 1)
COMPILE_LIMIT_S = 120.0       # minutes per step would sink a chip run


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """Entries compiled for a described chip cannot be read back without
    one; keep them out of any persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*shapes).compile()
    elapsed = time.perf_counter() - t0
    assert elapsed < COMPILE_LIMIT_S, f"compile took {elapsed:.1f} s"
    return compiled


def _op_args(op: str, s):
    """``(array arguments, static arguments, keywords)`` of ``op`` at the
    smoke's widths; ``s(shape, dtype)`` builds one abstract array on the
    described chip."""
    i32, u32, b = jnp.int32, jnp.uint32, jnp.bool_
    if op == "bitset_pack":
        return (s((WORDS * 32,), b),), (), {}
    if op == "bitset_unpack":
        return (s((WORDS,), u32),), (), {}
    if op == "bitset_lookup":       # the root-candidacy lookup over node slots
        return (s((WORDS,), u32), s((NODES,), i32)), (), {}
    if op == "bitset_build":        # one column of an STwig table
        return (s((ROWS,), i32), s((ROWS,), b)), (WORDS,), {}
    if op == "candidate_filter":
        return (
            s((WORDS,), u32), s((EDGE_SLOTS,), i32), s((EDGE_SLOTS,), i32),
            s((EDGE_SLOTS,), b),
        ), (1,), {}
    if op == "stwig_expand":        # a three-child STwig, one child bound
        return (
            s((3, WORDS), u32), s((EDGE_SLOTS,), i32), s((EDGE_SLOTS,), i32),
            s((NODES + 2,), i32), s((EDGE_SLOTS,), b),
        ), (), dict(
            child_labels=(1, 2, 3), child_bound=(True, False, False),
            child_cap=CHILD_CAP, cap=NODES, n_total=NODES,
        )
    if op == "hash_join_probe":     # two STwig tables on a 2-column key
        return (
            s((ROWS,), u32), s((ROWS, 2), i32), s((ROWS,), b),
            s((ROWS,), u32), s((ROWS, 2), i32), s((ROWS,), b),
        ), (), dict(dup_cap=DUP)
    if op == "cin_layer":           # not on the matcher's path
        f32 = jnp.float32
        return (
            s((64, 16, 128), f32), s((64, 16, 128), f32), s((256, 16), f32),
        ), (), {}
    raise AssertionError(f"no smoke-width arguments for op {op!r}")


def _abstract(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_tpu_table_names_every_op():
    assert set(TPU_OPS) == {c.op for c in OP_CONTRACTS}
    assert set(TPU_OPS.values()) <= {"jnp", "pallas"}
    assert TPU_KERNELS.table == TPU_OPS


@pytest.mark.parametrize("op", sorted(TPU_OPS))
def test_auto_op_compiles_for_v5e(one_chip, op):
    """What ``auto`` runs on a TPU compiles there; a Pallas entry really
    lowers to a Mosaic kernel and a jnp entry to plain XLA."""
    arrays, static, kw = _op_args(op, _abstract(one_chip))
    impl = getattr(TPU_KERNELS, op)
    hlo = _compile(lambda *a: impl(*a, *static, **kw), *arrays).as_text()
    assert ("tpu_custom_call" in hlo) == (TPU_OPS[op] == "pallas"), op


@pytest.mark.parametrize("op", ["bitset_pack", "bitset_unpack"])
def test_repaired_pallas_kernel_compiles_for_v5e(one_chip, op):
    arrays, _, _ = _op_args(op, _abstract(one_chip))
    hlo = _compile(getattr(get_kernels("pallas"), op), *arrays).as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("child_cap", [CHILD_CAP, 64])
def test_local_match_step_compiles_for_v5e(one_chip, child_cap):
    """The local engine's two STwig halves over the whole graph, built as
    `SubgraphMatcher._match_fns` builds them: at the plan's first child
    cap, and at 64, the largest a three-child STwig reaches under
    escalation (64^3 is the emission budget, so one root per round). The
    expansion outlives the expand step and is the emit step's argument:
    each step's arguments, outputs and temporaries must fit a 16 GB chip."""
    from repro.core.engine import stwig_step_fns
    from repro.core.match import Bindings, ShardGraph, STwigExpansion
    from repro.core.plan import STwigSpec

    s = _abstract(one_chip)
    i32, b = jnp.int32, jnp.bool_
    g = ShardGraph(
        labels=s((NODES,), i32), indptr=s((NODES + 1,), i32),
        indices=s((EDGE_SLOTS,), i32), edge_src=s((EDGE_SLOTS,), i32),
        n_local=s((), i32), n_local_edges=s((), i32), shard_id=s((), i32),
        all_labels=s((NODES + 1,), i32),
    )
    k = 3
    root_cap = max(1, ROWS // child_cap**k)
    spec = STwigSpec(
        root_label=3, child_labels=(5, 6, 7), root_qnode=0,
        child_qnodes=(1, 2, 3), root_bound=True,
        child_bound=(True, False, False), root_cap=root_cap,
        child_cap=child_cap, rows_cap=root_cap * child_cap**k,
        same_label_child_pairs=(), root_label_child_positions=(),
        child_need=(1, 1, 1),
    )
    expand, emit = stwig_step_fns(spec, TPU_KERNELS, WORDS)
    exp = STwigExpansion(
        cand=s((k, NODES + 1, child_cap), i32), cnt=s((k, NODES + 1), i32),
        root_mask=s((NODES,), b), rank=s((NODES,), i32), n_roots=s((), i32),
        overflow=s((), b),
    )
    steps = {
        "expand": _compile(
            lambda g, words: expand(g, Bindings(words)),
            g, s((k + 1, WORDS), jnp.uint32),
        ),
        "emit": _compile(
            lambda g, exp, r: emit(g, exp, round_idx=r), g, exp, s((), i32),
        ),
    }
    for name, compiled in steps.items():
        mem = compiled.memory_analysis()
        total = (
            mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes
        )
        assert total < 16e9, f"{name}: {total / 1e9:.2f} GB"


def test_local_join_step_compiles_for_v5e(one_chip):
    """One join of two full STwig tables at the plan's caps."""
    from repro.core.join import JoinTable, Schema, sort_merge_join

    s = _abstract(one_chip)

    def table(width):
        return JoinTable(
            s((ROWS, width), jnp.int32), s((ROWS,), jnp.bool_),
            s((), jnp.int32), s((), jnp.bool_),
        )

    sa = Schema((0, 1, 2, 3), (1, 2, 3, 4))
    sb = Schema((3, 4, 5), (4, 5, 6))
    compiled = _compile(
        lambda a, b: sort_merge_join(
            a, b, sa, sb, out_cap=JOIN_OUT, dup_cap=DUP,
            kernels=TPU_KERNELS,
        )[0],
        table(4), table(3),
    )
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4e9
