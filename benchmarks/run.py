"""Benchmark entry point — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast]``
prints ``name,us_per_call,derived`` CSV rows.

``--snapshot [PATH]`` additionally writes the rows plus environment
metadata as JSON (default ``benchmarks/snapshots/BENCH_<date>.json``) —
the perf trajectory ROADMAP item 4 tracks; CI uploads a fresh snapshot as
an artifact on every run.

 paper artifact                        module
 Table 1 (index linear build/size)    bench_index
 Table 2 (graph loading)              bench_loading
 Fig 8(a,b,c) (query/edge size)       bench_query_size
 Fig 9 (speed-up vs machines)         bench_speedup
 §6.1 (pipelined first-K streaming)   bench_stream
 Fig 10(a,b) (graph size)             bench_graph_size
 Fig 10(c) (graph density)            bench_density
 Fig 10(d) (label density)            bench_label_density
 §Roofline (this brief)               bench_roofline
 Kernel backends (DESIGN.md §3)       bench_kernels
 Serving (DESIGN.md §7)               bench_serve
"""
from __future__ import annotations

import argparse
import contextlib
import datetime
import io
import json
import pathlib
import sys
import time
import traceback


def _parse_rows(suite: str, text: str) -> list[dict]:
    rows = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        parts = line.split(",", 2)
        if len(parts) < 2:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append({
            "suite": suite,
            "name": parts[0],
            "us_per_call": us,
            "derived": parts[2] if len(parts) > 2 else "",
        })
    return rows


def _default_snapshot_path() -> str:
    stamp = datetime.date.today().isoformat()
    return str(
        pathlib.Path(__file__).resolve().parent
        / "snapshots" / f"BENCH_{stamp}.json"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller graphs")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated suite names to run")
    ap.add_argument("--snapshot", nargs="?", const=_default_snapshot_path(),
                    default=None, metavar="PATH",
                    help="also write rows + environment metadata as JSON "
                         "(default benchmarks/snapshots/BENCH_<date>.json)")
    args = ap.parse_args()

    from repro.runtime import compile_cache

    compile_cache.enable()
    from benchmarks import (
        bench_density,
        bench_graph_size,
        bench_index,
        bench_kernels,
        bench_label_density,
        bench_loading,
        bench_loadset,
        bench_query_size,
        bench_roofline,
        bench_serve,
        bench_speedup,
        bench_stream,
    )

    suites = {
        "index": bench_index.main,
        "loading": bench_loading.main,
        "query_size": (lambda: bench_query_size.main(scale=0.005, n_queries=3))
        if args.fast
        else bench_query_size.main,
        "speedup": bench_speedup.main,
        "stream": bench_stream.main,
        "graph_size": bench_graph_size.main,
        "density": bench_density.main,
        "label_density": bench_label_density.main,
        "loadset": bench_loadset.main,
        "roofline": bench_roofline.main,
        "kernels": bench_kernels.main,
        "serve": (lambda: bench_serve.main(smoke=True)) if args.fast
        else bench_serve.main,
    }
    def _gc():
        # each query spec jit-compiles a fresh executable; without clearing,
        # hundreds of cached executables exhaust the JIT code allocator.
        # Executable caches are session-owned now, so dropping the suites'
        # sessions plus jax's trace caches is enough.
        import gc

        import jax

        jax.clear_caches()
        gc.collect()

    only = set(args.only.split(",")) if args.only else None
    snapshot_rows: list[dict] = []
    failed: list[str] = []
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only is not None and name not in only:
            continue
        t0 = time.time()
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf):
                fn()
        except Exception:  # noqa: BLE001 — report, run the other suites
            buf.write(f"{name}_FAILED,0.0,\n")
            traceback.print_exc()
            failed.append(name)
        text = buf.getvalue()
        sys.stdout.write(text)
        snapshot_rows.extend(_parse_rows(name, text))
        _gc()
        print(f"# suite {name} took {time.time()-t0:.1f}s", file=sys.stderr)
        sys.stdout.flush()

    if args.snapshot:
        import jax

        doc = {
            "created": datetime.datetime.now(
                datetime.timezone.utc
            ).isoformat(timespec="seconds"),
            "fast": args.fast,
            "only": args.only,
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "rows": snapshot_rows,
        }
        path = pathlib.Path(args.snapshot)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(doc, indent=2) + "\n")
        print(f"# snapshot -> {path}", file=sys.stderr)
    if failed:
        print(f"# failed suites: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
