"""Peaks of the chips and the bytes a kernel has to move.

`PEAKS` is copied from `repro.analysis.roofline.PEAKS`: published per-chip
numbers keyed by ``device_kind``. A kind that is not in it is an error.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float    # bf16 FLOP/s
    hbm_bw: float   # HBM bytes/s


# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s. JAX names a v5e "TPU v5 lite".
_V5E = Peaks(flops=197e12, hbm_bw=819e9)
PEAKS: dict[str, Peaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def expand_bytes(*, n_children: int, child_cap: int, child_bound, root_bound: bool,
                 cap: int, edge_cap: int, n_words: int) -> int:
    """The least bytes one exploration step (steps 1-3 of one STwig on one
    shard, `repro.core.match.expand_stwig_shard`) reads and writes, whatever
    implements it. Read: node labels and the CSR (``indptr``, ``indices``),
    one destination label per edge slot, and the binding bitsets of the
    bound root and children. Written: the outputs at their declared shapes,
    ``cand (k, cap+1, C)``, ``cnt (k, cap+1)`` int32, ``root_mask (cap,)``
    bool, ``rank (cap,)`` int32 and two scalars. Intermediates of any one
    implementation are not counted."""
    k, c = n_children, child_cap
    read = 4 * cap + 4 * (cap + 1) + 4 * edge_cap + 4 * edge_cap
    read += 4 * n_words * (sum(bool(b) for b in child_bound) + bool(root_bound))
    written = 4 * k * (cap + 1) * c + 4 * k * (cap + 1) + cap + 4 * cap + 4 + 1
    return read + written


def expand_call_bytes(key: tuple, args: tuple) -> int | None:
    """`expand_bytes` of one call of the program's cached executables, or
    None where the call is no exploration step. It reads the engine's cache
    key ``("match", spec, kernels, "expand", n_words)``
    (`repro.core.engine.SubgraphMatcher._match_fns`) and the call's arguments
    ``(ShardGraph, Bindings)``; ``tests/bench`` pins that layout against
    the real engine."""
    if not (isinstance(key, tuple) and len(key) == 5
            and key[0] == "match" and key[3] == "expand"):
        return None
    spec, (g, bind) = key[1], args
    return expand_bytes(
        n_children=spec.n_children, child_cap=spec.child_cap,
        child_bound=spec.child_bound, root_bound=spec.root_bound,
        cap=g.labels.shape[0], edge_cap=g.indices.shape[0],
        n_words=bind.words.shape[1])
