"""The control of ``correct``: the plain reference put in the program's
place with one guarantee broken (`bench.reference.first_k` with
``drop_edge=True``), on a cell's own graph and templates, one answer
per template.

    python3 bench/control.py --workload patents-dfs --seeds 1 2 3

Prints, per seed, the numbers the comparison reads for the control's
answers and for the sound reference's. The control has to fail; the
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

if __name__ == "__main__":
    _root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

from bench import graphs, reference, registry, traffic  # noqa: E402


def readings(layout: registry.Layout, workload: str, seed: int) -> dict:
    cell = layout.workload(workload)
    mix = layout.traffic(cell["traffic"])
    g = graphs.load_graph(layout.config(cell["config"]), seed)
    templates = traffic.sample_templates(g, mix, graphs.rng_for(seed, 1))
    index = reference.EdgeIndex(g)
    k = mix["max_matches"]
    out = {}
    for name, drop in (("control", True), ("reference", False)):
        rows = {t: reference.first_k(g, index, q, k, drop_edge=drop)
                for t, q in enumerate(templates)}
        answers = [reference.Answer(t, r) for t, r in rows.items()]
        out[name] = {c: v["value"] for c, v in
                     reference.compare(g, templates, answers, k).as_dict().items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    layout = registry.Layout.load()
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = readings(layout, args.workload, seed)
        r.update(workload=args.workload, seed=seed,
                 seconds=time.perf_counter() - t0)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
