"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The device's work is the ``XLA Ops`` line of each ``/device:`` plane; each
event there is one operation running. Within the benchmark's own
``window`` span (a `jax.profiler.TraceAnnotation` on the host):

  * busy: the union of those intervals, averaged over the devices that ran
    any, so overlapping operations count once;
  * module time: the ``XLA Modules`` line's events, summed per function.
    The device names a program after its jitted function, which for a
    `functools.partial` or a lambda says nothing (``jit__unknown``). The
    host names it: each launch (``PJRT_LoadedExecutable_Execute linkage``)
    lies inside the ``PjitFunction(<function>)`` span of the call that made
    it, and one device runs its programs in the order they were launched.
    So the k-th launch names the k-th program, where the counts agree; where
    they do not, the device's own name is kept, its suffix ``(...)`` dropped;
  * idle gaps: the stretches of the window in which no operation ran on a
    device, each named by what the host was doing at its middle: the
    benchmark's own span there (``run``, ``compile``) and the
    innermost host event of a thread that dispatches device work.
"""
from __future__ import annotations

import dataclasses
import pathlib
from collections import defaultdict

OWN_SPANS = ("window", "compile", "run")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    module_s: dict[str, float]
    module_n: dict[str, int]
    idle_gaps: list[tuple[str, float]]   # (what the host did, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, sorted union of half-open intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def module_name(name: str) -> str:
    return name.split("(", 1)[0]


def reduce(path: str | pathlib.Path, window: str = "window") -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host_lines, device_ops, modules = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops += [(s, e) for _, s, e in evs]
                elif line.name == "XLA Modules":
                    modules += evs
            if ops:
                device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if evs:
                    host_lines.append(evs)
    spans = [ev for evs in host_lines for ev in evs if ev[0] == window]
    if not spans or not device_ops:
        raise ValueError(f"{path}: no {window!r} span or no device operations")
    lo, hi = spans[0][1], spans[0][2]

    busy = [union(clip(ops, lo, hi)) for ops in device_ops]
    busy_ns = sum(e - s for b in busy for s, e in b) / len(busy)

    module_s: dict[str, float] = defaultdict(float)
    module_n: dict[str, int] = defaultdict(int)
    names = _launched(host_lines)
    modules.sort(key=lambda m: m[1])
    if len(names) != len(modules):
        names = [module_name(m[0]) for m in modules]
    for name, (_, s, e) in zip(names, modules):
        if s >= lo and e <= hi:
            module_s[name] += (e - s) * 1e-9
            module_n[name] += 1

    # threads that dispatch device work or carry the benchmark's spans
    dispatch = [evs for evs in host_lines if any(
        n in OWN_SPANS or n.startswith("PjitFunction") for n, _, _ in evs)]
    holes, cursor = [], lo
    for s, e in union([iv for b in busy for iv in b]) + [(hi, hi)]:
        if s > cursor:
            holes.append((cursor, s))
        cursor = max(cursor, e)
    names = _doing(dispatch, [(a + b) / 2 for a, b in holes], window)
    gaps: dict[str, float] = defaultdict(float)
    for (a, b), name in zip(holes, names):
        gaps[name] += (b - a) * 1e-9
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns * 1e-9,
        module_s=dict(module_s),
        module_n=dict(module_n),
        idle_gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
    )


def _launched(lines) -> list[str]:
    """The jitted function of every program launch, in launch order."""
    launches = []
    for evs in lines:
        stack: list[tuple[float, str]] = []
        for s, neg_e, n in sorted((s, -e, n) for n, s, e in evs):
            while stack and stack[-1][0] <= s:
                stack.pop()
            if n.startswith("PjitFunction("):
                stack.append((-neg_e, n[len("PjitFunction("):-1]))
            elif n == "PJRT_LoadedExecutable_Execute linkage":
                launches.append((s, stack[-1][1] if stack else "?"))
    return [n for _, n in sorted(launches)]


def _doing(lines, times: list[float], window: str) -> list[str]:
    """For each of the sorted ``times``: ``span/event``, the innermost own
    span and the innermost other host event that hold it (events of one
    thread nest, so a stack per thread finds both in one sweep)."""
    best_own = [None] * len(times)
    best_other = [None] * len(times)
    for evs in lines:
        evs = sorted((s, -e, n) for n, s, e in evs if n != window)
        stacks: dict[bool, list] = {True: [], False: []}
        i = 0
        for k, t in enumerate(times):
            while i < len(evs) and evs[i][0] <= t:
                s, neg_e, n = evs[i]
                stacks[n in OWN_SPANS].append((-neg_e, s, n))
                i += 1
            for own, best in ((True, best_own), (False, best_other)):
                st = stacks[own]
                while st and st[-1][0] <= t:
                    st.pop()
                if st:
                    e, s, n = st[-1]
                    if best[k] is None or e - s < best[k][0]:
                        best[k] = (e - s, n)
    return [f"{o[1] if o else '-'}/{x[1] if x else 'python'}"
            for o, x in zip(best_own, best_other)]
