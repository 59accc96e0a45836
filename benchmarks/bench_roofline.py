"""§Roofline: per-entry-point rooflines for the MATCHER engines.

`repro.analysis.roofline.engine_rooflines` drives the staticcheck engine
probe (compile / run / stream / re-stream on a tiny graph) for every
(engine × kernel backend) combination, scores every recorded executable
with the staticcheck cost model, and reports one three-term roofline per
entry point — ``bottleneck`` says what a TPU would saturate first for that
executable's op mix, ``frac`` how much of the bounding term pure compute
accounts for. This replaced the stale LM/GNN dry-run artifact reader that
printed ``roofline_no_artifacts`` on every real run.

Rows: ``roofline_<backend>_<kernels>_<entry>,bound_us,bottleneck=..;...``
(``bound_us`` = the bounding term at TPU-v5e constants — a model, not a
measurement; CPU wall-clock lives in the ``kernels`` suite).

``--json-out PATH`` additionally writes the full per-target roofline dicts
as JSON (CI uploads it as an artifact next to the bench snapshot).
"""
from __future__ import annotations

import argparse
import json
import pathlib


def main(json_out: "str | None" = None) -> None:
    from repro.analysis.roofline import PEAKS, engine_rooflines

    # a static report wherever it runs: scored at the v5e's published peaks
    rooflines = engine_rooflines(peaks=PEAKS["TPU v5 lite"])
    doc = {}
    for target, r in rooflines.items():
        # engine:local:jnp:match -> roofline_local_jnp_match
        name = "roofline_" + "_".join(target.split(":")[1:])
        bound = max(r.t_compute, r.t_memory, r.t_collective)
        print(
            f"{name},{bound*1e6:.3f},"
            f"bottleneck={r.bottleneck};frac={r.roofline_fraction:.3f};"
            f"comp_us={r.t_compute*1e6:.3f};mem_us={r.t_memory*1e6:.3f};"
            f"coll_us={r.t_collective*1e6:.3f};"
            f"mflops={r.flops/1e6:.2f};peak_mb={r.hbm_bytes/1e6:.2f}"
        )
        doc[target] = r.to_dict()
    if json_out:
        path = pathlib.Path(json_out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(doc, indent=2) + "\n")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-out", type=str, default=None,
                    help="also write per-target roofline dicts as JSON")
    main(json_out=ap.parse_args().json_out)
