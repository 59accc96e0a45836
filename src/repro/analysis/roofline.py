"""Three-term roofline model from compiled dry-run artifacts.

    compute    = HLO_FLOPs   / (chips × peak_FLOP/s)
    memory     = HLO_bytes   / (chips × HBM_bw)
    collective = coll_bytes  / (chips × link_bw)

``compiled.cost_analysis()`` supplies FLOPs and bytes; collective bytes are
parsed from the optimized HLO text by summing the *result-shape* bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, with ring-cost multipliers (all-reduce counts 2×(n−1)/n,
all-gather/reduce-scatter (n−1)/n, permute 1×). XLA reports the per-device
partitioned module, so totals are already per-chip; the roofline divides by
chips only when given whole-program numbers (``per_device=False``).

Hardware constants come from `PEAKS`, keyed by ``jax.Device.device_kind``;
a kind that is not in the table is an error, never a default. A static
report made off the chip passes its peaks explicitly.

`engine_rooflines` points the same three-term model at the matcher itself:
per-executable FLOPs / peak bytes / collective bytes come from the
staticcheck cost model (`staticcheck/costmodel.py`) over the engine probe's
recorded entry points, giving per-entry-point ``bottleneck`` and
``roofline_fraction`` without any dry-run artifacts
(``benchmarks/bench_roofline.py`` reports them).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""

    flops: float    # bf16 FLOP/s
    hbm_bw: float   # HBM bytes/s
    ici_bw: float   # chip-to-chip interconnect bytes/s


# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s interconnect.
# JAX reports a v5e as "TPU v5 lite".
_V5E = Peaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8)
PEAKS: dict[str, Peaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; unknown kinds are an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (pass explicit peaks for a static report)"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w[\w\d.\-]*)\s*=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveStats:
    bytes_by_kind: dict[str, float] = {}
    count_by_kind: dict[str, int] = {}
    ring = (n_devices - 1) / max(n_devices, 1)
    mult = {
        "all-reduce": 2.0 * ring,
        "all-gather": ring,
        "reduce-scatter": ring,
        "all-to-all": ring,
        "collective-permute": 1.0,
    }
    for m in _COLL_RE.finditer(hlo_text):
        _, dtype, dims, kind = m.groups()
        b = _shape_bytes(dtype, dims) * mult[kind]
        bytes_by_kind[kind] = bytes_by_kind.get(kind, 0.0) + b
        count_by_kind[kind] = count_by_kind.get(kind, 0) + 1
    return CollectiveStats(bytes_by_kind, count_by_kind)


@dataclasses.dataclass
class Roofline:
    flops: float             # per-device HLO flops
    hbm_bytes: float         # per-device bytes accessed
    collective_bytes: float  # per-device collective bytes moved
    n_chips: int
    peaks: Peaks
    model_flops: float = 0.0  # 6·N·D (or 6·N_active·D) whole-step model flops

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / total HLO FLOPs (remat/redundancy waste detector)."""
        total = self.flops * self.n_chips
        return (self.model_flops / total) if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step's time the dominant term says is 'useful
        peak': model_flops/chips/PEAK divided by the bounding term."""
        bound = max(self.t_compute, self.t_memory, self.t_collective)
        ideal = (self.model_flops / self.n_chips) / self.peaks.flops
        return (ideal / bound) if bound > 0 else 0.0

    def to_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops,
            "hbm_bytes_per_chip": self.hbm_bytes,
            "collective_bytes_per_chip": self.collective_bytes,
            "n_chips": self.n_chips,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


# ------------------------------------------------- matcher engine rooflines
def engine_rooflines(
    backends=None,
    kernels=None,
    *,
    scale: int = 1,
    n_chips: int | None = None,
    peaks: Peaks | None = None,
) -> "dict[str, Roofline]":
    """Per-entry-point rooflines for the matcher engines, attributed from
    the staticcheck cost model — nothing executes beyond the tiny probe.

    `repro.analysis.staticcheck.engines.probe_traces` drives the real entry
    points (compile / run / stream / re-stream) on every (engine × kernels)
    combination and re-traces each cached executable;
    `staticcheck.costmodel.estimate` then walks the jaxprs for FLOPs, peak
    resident bytes and collective bytes. Per target (entry point), the
    per-metric max across its executables — the same aggregation the
    budgets pass uses — feeds one `Roofline`:

      * ``flops``            — the cost model's counted ops;
      * ``hbm_bytes``        — peak resident bytes, standing in for HBM
        traffic (a floor: every resident byte is written and read at least
        once; XLA fusion can only shrink it);
      * ``collective_bytes`` — ring-convention collective bytes;
      * ``model_flops``      — set equal to ``flops``: the matcher has no
        closed-form useful-flops model (no 6·N·D), and every counted op is
        algorithmically required at the jaxpr level, so
        ``roofline_fraction`` reads as "fraction of the bounding term the
        pure-compute time accounts for" (1.0 ⇔ compute-bound).

    ``peaks`` defaults to those of the first device's kind (`peaks_for`).

    Returns ``{target: Roofline}`` with targets like
    ``engine:local:jnp:match``.
    """
    import jax

    from repro.analysis.staticcheck import costmodel
    from repro.analysis.staticcheck import engines as _engines

    backends = tuple(backends or _engines.ENGINE_BACKENDS)
    kernels = tuple(kernels or _engines.KERNEL_BACKENDS)
    chips = n_chips if n_chips is not None else jax.device_count()
    peaks = peaks or peaks_for(jax.devices()[0].device_kind)

    worst: dict[str, dict] = {}
    for b in backends:
        for k in kernels:
            _, traces = _engines.probe_traces(b, k, scale=scale)
            for t in traces:
                est = costmodel.estimate(t.jaxpr, t.target)
                m = worst.setdefault(t.target, {
                    "flops": 0.0, "peak_bytes": 0.0, "collective_bytes": 0.0,
                })
                m["flops"] = max(m["flops"], est.flops)
                m["peak_bytes"] = max(m["peak_bytes"], est.peak_bytes)
                m["collective_bytes"] = max(
                    m["collective_bytes"], est.collective_bytes
                )
    return {
        target: Roofline(
            flops=m["flops"],
            hbm_bytes=m["peak_bytes"],
            collective_bytes=m["collective_bytes"],
            n_chips=chips,
            peaks=peaks,
            model_flops=m["flops"],
        )
        for target, m in sorted(worst.items())
    }


def model_flops_lm(cfg, batch: int, seq: int, kind: str) -> float:
    """6·N·D for training, 2·N·D for inference (N = active params)."""
    n = cfg.n_active_params()
    tokens = batch * seq if kind in ("train", "prefill") else batch
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens


def model_flops_gnn(cfg, n_nodes: int, n_edges: int) -> float:
    """Per-layer: edges × d_hidden message work + nodes × MLP work, ×3 (train)."""
    d = cfg.d_hidden
    per_layer = 2.0 * n_edges * d * d + 2.0 * n_nodes * d * d * 2
    return 3.0 * cfg.n_layers * per_layer


def model_flops_recsys(cfg, batch: int, kind: str) -> float:
    m, d = cfg.n_sparse, cfg.embed_dim
    cin = 0.0
    h_prev = m
    for h in cfg.cin_layers:
        cin += 2.0 * h_prev * m * d * h
        h_prev = h
    dims = [m * d] + list(cfg.mlp_layers) + [1]
    dnn = sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))
    mult = 3.0 if kind == "train" else 1.0
    return mult * batch * (cin + dnn)
