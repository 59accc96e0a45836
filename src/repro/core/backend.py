"""The kernel backend layer: one swappable op set under every engine.

Every dense inner step of the matcher — bitset pack/unpack/lookup/build,
the fused STwig expansion (candidate filter + per-root compaction), the
standalone candidate filter, and the sort-merge hash-join probe — is an op
on a `Kernels` object. Both engines (`repro.core.engine`,
`repro.core.dist`) call through whatever `Kernels` they were opened with,
and the choice participates in every `ExecutableCache` key, so one session
can compare backends without cache poisoning (DESIGN.md §3).

Registered backends:

  * ``"jnp"``              — pure-jnp reference ops (the portable path and
                             the oracle for everything else);
  * ``"pallas"``           — Pallas TPU kernels (`repro.kernels.bitset`,
                             `repro.kernels.stwig_expand`,
                             `repro.kernels.hash_join`);
  * ``"pallas-interpret"`` — the same kernels in interpret mode: runs on
                             CPU, used by the parity tests in CI;
  * ``"tpu"``              — one implementation per op, named in the
                             static table `TPU_OPS`: the Pallas kernel
                             where it compiles for the chip, the jnp op
                             where it does not;
  * ``"auto"``             — resolves to ``"tpu"`` on TPU, ``"jnp"``
                             elsewhere.

Making any step faster now means writing one kernel and registering it —
not re-plumbing two engines: subclass `Kernels` (override only the ops you
accelerate) and `register_backend` it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.bitset import ref as _bitset_ref
from repro.kernels.hash_join import ref as _join_ref
from repro.kernels.stwig_expand import ref as _expand_ref

WORD_BITS = _bitset_ref.WORD_BITS
n_words = _bitset_ref.n_words


# ------------------------------------------------------------- op contracts
@dataclasses.dataclass(frozen=True)
class OpContract:
    """Machine-checkable shape/dtype contract for one `Kernels` op.

    Declared next to the ops so `register_backend` picks every backend up
    automatically: `repro.analysis.staticcheck` abstractly traces
    ``getattr(kernels, op)(*make_args()...)`` on every registered backend and
    walks the jaxpr — output dtypes must equal ``out_dtypes``, no value in
    the trace may be 64-bit wide (ids stay int32, bitsets stay uint32 — the
    linear-space discipline ROADMAP item 2 rests on), and none of the
    `BANNED_PRIMITIVES` (host callbacks / device transfers) may appear.

    ``make_args`` returns ``(args, kwargs)`` of small *example* inputs at the
    declared dtypes; they are traced, never executed, so cost is nil. New
    kernels: declare a contract here (or pass ``contracts=`` to
    `register_backend`) and the checker enforces it on every backend.
    """

    op: str
    make_args: Callable[[], tuple[tuple, dict]]
    out_dtypes: tuple[str, ...]


def _ex(shape, dtype):
    return jnp.zeros(shape, dtype)


def _contract_bitset_pack():
    return (_ex((64,), jnp.bool_),), {}


def _contract_bitset_unpack():
    return (_ex((2,), jnp.uint32),), {}


def _contract_bitset_lookup():
    return (_ex((2,), jnp.uint32), _ex((8,), jnp.int32)), {}


def _contract_bitset_build():
    return (_ex((8,), jnp.int32), _ex((8,), jnp.bool_), 2), {}


def _contract_candidate_filter():
    return (
        _ex((2,), jnp.uint32),
        _ex((16,), jnp.int32),
        _ex((16,), jnp.int32),
        _ex((16,), jnp.bool_),
        1,
    ), {}


def _contract_stwig_expand():
    return (
        _ex((2, 2), jnp.uint32),   # words_k
        _ex((16,), jnp.int32),     # dst_ids
        _ex((16,), jnp.int32),     # dst_labels
        _ex((8,), jnp.int32),      # lo (rows,)
        _ex((8,), jnp.int32),      # hi (rows,)
    ), dict(
        child_labels=(1, 2),
        child_bound=(True, False),
        child_cap=4,
        n_total=63,
    )


def _contract_hash_join_probe():
    return (
        _ex((16,), jnp.uint32),    # ka_sorted
        _ex((16, 2), jnp.int32),   # a_keys
        _ex((16,), jnp.bool_),     # a_valid
        _ex((8,), jnp.uint32),     # kb
        _ex((8, 2), jnp.int32),    # b_keys
        _ex((8,), jnp.bool_),      # b_valid
    ), dict(dup_cap=4)


def _contract_cin_layer():
    return (
        _ex((2, 3, 4), jnp.float32),   # xk
        _ex((2, 2, 4), jnp.float32),   # x0
        _ex((6, 3), jnp.float32),      # w
    ), {}


OP_CONTRACTS: tuple[OpContract, ...] = (
    OpContract("bitset_pack", _contract_bitset_pack, ("uint32",)),
    OpContract("bitset_unpack", _contract_bitset_unpack, ("bool",)),
    OpContract("bitset_lookup", _contract_bitset_lookup, ("bool",)),
    OpContract("bitset_build", _contract_bitset_build, ("uint32",)),
    OpContract("candidate_filter", _contract_candidate_filter, ("bool",)),
    OpContract("stwig_expand", _contract_stwig_expand, ("int32", "int32")),
    OpContract("hash_join_probe", _contract_hash_join_probe, ("bool", "int32")),
    OpContract("cin_layer", _contract_cin_layer, ("float32",)),
)


class Kernels:
    """The op interface engines program against. The base class IS the jnp
    reference implementation; accelerated backends override per op.

    All ops are shape-polymorphic pure functions safe under ``jit``,
    ``vmap`` and ``shard_map``; static configuration (labels, capacities)
    is keyword-only so engines can close over it at trace time.
    """

    name = "jnp"

    # ---------------------------------------------------- packed bitsets
    def bitset_pack(self, mask: jnp.ndarray) -> jnp.ndarray:
        """(n,) bool (n % 32 == 0) → (n/32,) uint32 packed words."""
        return _bitset_ref.pack_reference(mask)

    def bitset_unpack(self, words: jnp.ndarray) -> jnp.ndarray:
        """(W,) uint32 → (W*32,) bool."""
        return _bitset_ref.unpack_reference(words)

    def bitset_lookup(self, words: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
        """Membership test; negative / out-of-range ids are ``False``."""
        return _bitset_ref.lookup_reference(words, ids)

    def bitset_build(
        self, ids: jnp.ndarray, valid: jnp.ndarray, nwords: int
    ) -> jnp.ndarray:
        """Packed bitset from (possibly duplicated) masked ids."""
        return _bitset_ref.build_reference(ids, valid, nwords)

    # ------------------------------------------------------- exploration
    def candidate_filter(
        self, words, dst_ids, dst_labels, root_ok, child_label: int
    ) -> jnp.ndarray:
        """Fused MatchSTwig step-2 filter for ONE child label."""
        return _bitset_ref.candidate_filter_reference(
            words, dst_ids, dst_labels, root_ok, child_label
        )

    def stwig_expand(
        self,
        words_k,
        dst_ids,
        dst_labels,
        lo,
        hi,
        *,
        child_labels: tuple[int, ...],
        child_bound: tuple[bool, ...],
        child_cap: int,
        n_total: int,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Fused steps 2-3: per-child filter + compaction into candidate
        lists ``(k, rows+1, C)`` with exact counts ``(k, rows)``, one row
        per root candidate. ``lo``, ``hi`` are the ``(rows,)`` bounds of
        each candidate's CSR edge segment, ``[E, E)`` for a pad row; row
        ``rows`` of ``cand`` is all ghost ids."""
        return _expand_ref.stwig_expand_reference(
            words_k,
            dst_ids,
            dst_labels,
            lo,
            hi,
            child_labels=child_labels,
            child_bound=child_bound,
            child_cap=child_cap,
            n_total=n_total,
        )

    # -------------------------------------------------------------- join
    def hash_join_probe(
        self, ka_sorted, a_keys, a_valid, kb, b_keys, b_valid, *, dup_cap: int
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Sorted windowed probe with exact-key verification: ``hit`` and
        sorted-side row indices, both ``(capB, dup_cap)``."""
        return _join_ref.probe_reference(
            ka_sorted, a_keys, a_valid, kb, b_keys, b_valid, dup_cap=dup_cap
        )

    # ---------------------------------------------------------- signatures
    def cin_layer(self, xk, x0, w) -> jnp.ndarray:
        """One CIN layer (compressed interaction): ``(B, H, d) × (B, m, d)
        × (H·m, H') → (B, H', d)`` — the contraction behind ROADMAP item
        3's learned neighborhood-signature filter."""
        from repro.kernels.cin.ref import cin_layer_reference

        return cin_layer_reference(xk, x0, w)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<Kernels {self.name!r}>"


class PallasKernels(Kernels):
    """The Pallas TPU kernel set. ``interpret=True`` runs the same kernels
    through the Pallas interpreter (works on CPU — that is what CI's parity
    tests use); ``interpret=False`` compiles them with Mosaic on TPU."""

    def __init__(self, *, interpret: bool = False):
        self.interpret = interpret
        self.name = "pallas-interpret" if interpret else "pallas"

    def bitset_pack(self, mask):
        from repro.kernels.bitset import bitset_pack

        return bitset_pack(mask, interpret=self.interpret)

    def bitset_unpack(self, words):
        from repro.kernels.bitset import bitset_unpack

        return bitset_unpack(words, interpret=self.interpret)

    def bitset_lookup(self, words, ids):
        from repro.kernels.bitset import bitset_lookup

        return bitset_lookup(words, ids, interpret=self.interpret)

    def bitset_build(self, ids, valid, nwords):
        # scatter stays in XLA (no scatter-OR on TPU vector units); the
        # 32-lane pack runs in-kernel
        from repro.kernels.bitset import bitset_pack

        n_bits = nwords * WORD_BITS
        idx = jnp.where(valid, ids, np.int32(n_bits))
        bits = jnp.zeros((n_bits,), jnp.bool_).at[idx].set(True, mode="drop")
        return bitset_pack(bits, interpret=self.interpret)

    def candidate_filter(self, words, dst_ids, dst_labels, root_ok, child_label):
        from repro.kernels.bitset import candidate_filter

        return candidate_filter(
            words,
            dst_ids,
            dst_labels,
            root_ok,
            child_label,
            interpret=self.interpret,
        )

    def stwig_expand(self, *args, **kw):
        # full submodule path: the package attribute of the same name is
        # shadowed by the submodule if anyone imported it directly first
        from repro.kernels.stwig_expand.stwig_expand import stwig_expand

        return stwig_expand(*args, interpret=self.interpret, **kw)

    def hash_join_probe(self, *args, **kw):
        from repro.kernels.hash_join.hash_join import hash_join_probe

        return hash_join_probe(*args, interpret=self.interpret, **kw)

    def cin_layer(self, xk, x0, w):
        from repro.kernels.cin.cin import cin_layer

        return cin_layer(xk, x0, w, interpret=self.interpret)


# ------------------------------------------------------- per-op TPU choice
# What ``kernels="auto"`` runs for each op on a TPU, and the only place that
# choice is made. An op names "jnp" where its Pallas kernel is refused by the
# TPU compiler and needs a redesign to pass (message as the v5e compiler
# gives it); tests/test_tpu_compile.py compiles every entry for a v5e.
TPU_OPS: dict[str, str] = {
    "bitset_pack": "pallas",
    "bitset_unpack": "pallas",
    # in-kernel 1-D gather of one word per id: "Only 2D gather is supported"
    "bitset_lookup": "jnp",
    "candidate_filter": "jnp",
    # the Pallas op is the jnp bool scatter plus the pack kernel; that
    # scatter alone takes 10 s to compile at 3.8M bits
    "bitset_build": "jnp",
    # in-kernel 1-D gathers ("Only 2D gather is supported"), and `emit`
    # holds the whole (k, E) prefix arrays in VMEM
    "stwig_expand": "jnp",
    # in-kernel 1-D gathers ("Only 2D gather is supported"), and the whole
    # build side is VMEM-resident
    "hash_join_probe": "jnp",
    "cin_layer": "pallas",
}


class PerOpKernels(Kernels):
    """Each op taken from the registered backend its table names."""

    def __init__(self, name: str, table: dict[str, str]):
        missing = {c.op for c in OP_CONTRACTS} - set(table)
        if missing:
            raise ValueError(f"{name!r} names no implementation for {sorted(missing)}")
        self.name = name
        self.table = dict(table)
        for op, impl in self.table.items():
            setattr(self, op, getattr(get_kernels(impl), op))


# ------------------------------------------------------------------ registry
_REGISTRY: dict[str, Callable[[], Kernels]] = {}
_INSTANCES: dict[str, Kernels] = {}
_CONTRACTS: dict[str, tuple[OpContract, ...]] = {}

KERNEL_BACKENDS = ("auto", "jnp", "pallas", "pallas-interpret")


def register_backend(
    name: str,
    factory: Callable[[], Kernels],
    *,
    contracts: tuple[OpContract, ...] = OP_CONTRACTS,
) -> None:
    """Register a kernel backend under ``name`` (factory called lazily,
    once). Third-party backends can register here and be selected by name
    through `GraphSession.open(kernels=...)`.

    Every registered backend is bound to a tuple of `OpContract`s (default:
    the canonical `OP_CONTRACTS`) that `repro.analysis.staticcheck` enforces
    by abstract tracing — a backend that adds ops should pass an extended
    tuple so the new ops are checked too."""
    _REGISTRY[name] = factory
    _INSTANCES.pop(name, None)
    _CONTRACTS[name] = contracts


def op_contracts(name: str) -> tuple[OpContract, ...]:
    """The contract set `register_backend` bound to backend ``name``."""
    return _CONTRACTS.get(name, OP_CONTRACTS)


def available_backends() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def get_kernels(name: str) -> Kernels:
    """The (singleton) `Kernels` registered under ``name``."""
    try:
        inst = _INSTANCES[name]
    except KeyError:
        try:
            factory = _REGISTRY[name]
        except KeyError:
            raise ValueError(
                f"unknown kernel backend {name!r}; registered: "
                f"{available_backends()}"
            ) from None
        inst = _INSTANCES[name] = factory()
    return inst


def resolve_kernels(spec: "str | Kernels | None" = None) -> Kernels:
    """Normalize a user-facing kernels spec: a `Kernels` instance passes
    through, ``None`` means ``"auto"``, and ``"auto"`` picks the per-op
    `TPU_OPS` table on TPU and jnp elsewhere (interpret mode is never
    auto-selected — it is a testing backend)."""
    if isinstance(spec, Kernels):
        return spec
    name = spec or "auto"
    if name == "auto":
        if jax.default_backend() == "tpu":
            return TPU_KERNELS
        name = "jnp"
    return get_kernels(name)


register_backend("jnp", Kernels)
register_backend("pallas", PallasKernels)
register_backend("pallas-interpret", lambda: PallasKernels(interpret=True))

# what "auto" runs on a TPU; not a registered name, so not user-selectable
TPU_KERNELS = PerOpKernels("tpu", TPU_OPS)
