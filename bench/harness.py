"""Run one cell of the benchmark and print its result line.

A run is: set-up (load the graph, open a `GraphSession`, sample the cell's
query templates, run each template once so that every executable the
window uses is compiled or loaded from the compile cache), the measured
window, and then, with the program's state freed, the comparison with the
plain reference (`bench.reference`). A graph the cache does not hold yet is
generated into it before set-up starts. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` runs a shorter window under the profiler
and reports its per-layer metrics. Every metric is a reader in
``bench/metrics`` that takes a `Run` and returns a number or None.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import graphs, reference, registry, traffic, xplane


@dataclasses.dataclass
class Query:
    """One query of the window, as the program answered it."""

    template: int
    rows: np.ndarray
    complete: bool          # the program's own word (`MatchResult.complete`)
    stats: object           # the program's `MatchStats`


@dataclasses.dataclass
class Call:
    """One call of a cached executable during a traced window."""

    key: tuple
    args: tuple             # `jax.ShapeDtypeStruct` leaves


@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers take it."""

    workload: str
    window_s: float         # the window as run, to its last answer
    setup_s: float
    queries: list[Query]
    device_kind: str
    trace: xplane.TraceSummary | None = None
    calls: list[Call] = dataclasses.field(default_factory=list)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations (including loads from the persistent cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


def enable_compile_cache() -> str:
    """JAX's persistent compile cache, where `repro.runtime.compile_cache`
    puts it, for every executable however fast it compiled."""
    import jax

    from repro.runtime import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _shapes(tree):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), tree)


class Client:
    """One client in a closed loop: ``compile(q, max_matches=K).run()``,
    the next query after the last answer, cycling through the templates
    in order."""

    def __init__(self, session, templates, k: int):
        self.session, self.templates, self.k = session, templates, k

    def one(self, t: int) -> Query:
        res = self.session.compile(self.templates[t], max_matches=self.k).run()
        return Query(t, res.rows, bool(res.complete), res.stats)

    def warm(self) -> None:
        for t in range(len(self.templates)):
            with _annotate("compile"):
                self.one(t)

    def window(self, seconds: float) -> tuple[list[Query], float]:
        """Queries are started until ``seconds`` have passed and the last
        cycle through the templates is whole, so that every window holds
        each template equally often; the query in flight finishes and
        counts."""
        out: list[Query] = []
        k = len(self.templates)
        t0 = time.perf_counter()
        while not out or len(out) % k or time.perf_counter() - t0 < seconds:
            with _annotate("run"):
                out.append(self.one(len(out) % k))
        return out, time.perf_counter() - t0


def run_cell(layout: registry.Layout, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_tpu: bool = True) -> dict | None:
    """One run of one cell; the result line's object, or None when the
    chips the cell asks for are not there."""
    import jax

    cell = layout.workload(workload)
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        log(f"JAX found no TPU (platform {platform!r}); nothing measured")
        return None
    if len(devices) < cell["chips"]:
        log(f"cell {workload} asks for {cell['chips']} chips, JAX has {len(devices)}")
        return None
    cfg = layout.config(cell["config"])
    mix = layout.traffic(cell["traffic"])

    from repro.api import GraphSession

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    # the benchmark making its data on a cache miss is not the system's
    # set-up; loading the graph from the cache is
    generate_s = graphs.ensure_cached(cfg, seed)
    t0 = time.perf_counter()
    g = graphs.load_graph(cfg, seed)
    t_graph = time.perf_counter() - t0
    session = GraphSession.open(g, n_shards=cfg["shards"])
    templates = traffic.sample_templates(g, mix, graphs.rng_for(seed, 1))
    length = min(seconds, mix["trace_seconds"]) if trace else seconds
    client = Client(session, templates, mix["max_matches"])
    t0 = time.perf_counter()
    client.warm()
    t_warm = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start - generate_s
    log(f"set-up {setup_s:.3f} s: graph load {t_graph:.3f} s ({g.n_nodes} "
        f"nodes, {g.n_edges} CSR slots; generated before set-up in "
        f"{generate_s:.3f} s), warm-up {t_warm:.3f} s of {len(templates)} "
        f"templates, {counter.n} compiles, {len(session.cache)} executables; "
        f"compile cache {cache_dir}")

    calls: list[Call] = []
    summary = None
    before = counter.n
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        session.cache.recorder = lambda key, f, a, kw: calls.append(
            Call(key, _shapes(a)))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        try:
            with jax.profiler.trace(log_dir, profiler_options=opts):
                with _annotate("window"):
                    queries, window_s = client.window(length)
            session.cache.recorder = None
            summary = xplane.reduce(xplane.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        queries, window_s = client.window(length)
    in_window = counter.n - before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[: cell["chips"]])
    counter.close()
    kind = devices[0].device_kind
    del client, session
    gc.collect()

    checks = reference.compare(
        g, templates,
        [reference.Answer(q.template, q.rows) for q in queries],
        mix["max_matches"])
    run = Run(workload=workload, window_s=window_s, setup_s=setup_s,
              queries=queries, device_kind=kind, trace=summary, calls=calls)
    metrics = {}
    for m in layout.metrics(workload, trace):
        value = layout.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {
        "correct": checks.correct,
        "attempted": len(queries),
        "failed": sum(not q.complete for q in queries),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": sorted(summary.module_s.items(), key=lambda kv: -kv[1])[:10],
            "idle_gaps": summary.idle_gaps[:10],
        }
    out["checks"] = checks.as_dict()
    log(f"window {window_s:.3f} s, {len(queries)} answers, "
        f"{in_window} compiles inside the window")
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return out


def main(argv=None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start
    out = run_cell(registry.Layout.load(), args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start)
    if out is None:
        return 2
    print(json.dumps(out), flush=True)
    return 0
