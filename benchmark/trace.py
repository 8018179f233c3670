"""From a profiler trace to the numbers the per-layer metrics read.

:func:`capture` wraps the measured window in ``jax.profiler``;
:func:`load` reads the ``.xplane.pb`` it wrote into a :class:`Trace` of
plain tuples. The reductions below work on those tuples only, so
``benchmark/tests`` checks them on a small recorded trace.

On a TPU each chip is a plane ``/device:TPU:<n>``. Its ``XLA Ops`` line
holds one event per operation that ran, with the program (``hlo_module``)
as a stat; its ``XLA Modules`` line one event per program run, named
``<module>(<id>)``. Host threads are lines of ``/host:CPU``; the spans of
interest there are the program's ``crdt.*`` annotations and the
benchmark's ``bench.*`` ones.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil

import jax

#: host spans a gap may be labelled with, most specific first
SPAN_PREFIXES = ("crdt.", "bench.")
WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the enclosed region into ``log_dir`` (emptied first), with
    user annotations on and the Python tracer off."""
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


class Trace:
    """``ops[device]``: ``(start_ns, end_ns, name, module)`` per device
    operation; ``modules[device]``: ``(start_ns, end_ns, module)`` per
    program run; ``spans``: ``(start_ns, end_ns, name)`` host spans;
    ``window``: ``(start_ns, end_ns)`` of the traced window."""

    def __init__(self, ops: dict, modules: dict, spans: list, window: tuple):
        self.ops, self.modules, self.spans, self.window = ops, modules, spans, window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(
            union_ns([(s, e) for s, e, _n, _m in ops], *self.window)
            for ops in self.ops.values()
        ) / len(self.ops) / 1e9

    def module_s(self, patterns: list) -> float:
        """Device seconds of the programs whose module name starts with
        one of ``patterns``, summed over devices."""
        total = 0
        for mods in self.modules.values():
            total += sum(
                e - s for s, e, m in mods if module_matches(m, patterns)
                and s >= self.window[0] and e <= self.window[1]
            )
        return total / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The programs that took most device time, and the longest idle
        gaps, each labelled by the host span open at its midpoint."""
        per: dict = {}
        for mods in self.modules.values():
            for s, e, m in mods:
                per[m] = per.get(m, 0) + (e - s)
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps: list = []
        for dev_ops in self.ops.values():
            gaps += idle_gaps([(s, e) for s, e, _n, _m in dev_ops], *self.window)
        gaps.sort(key=lambda g: g[0] - g[1])
        return {
            "device_ops": [[m, ns / 1e9] for m, ns in ops],
            "idle_gaps": [
                [span_at(self.spans, (s + e) // 2), (e - s) / 1e9] for s, e in gaps[:top]
            ],
        }


def module_matches(module: str, patterns: list) -> bool:
    base = module.split("(", 1)[0]
    return any(base == p or base.startswith(p + ".") for p in patterns)


def merged(intervals: list, lo: int, hi: int) -> list:
    """``intervals`` clipped to ``[lo, hi]`` and merged where they overlap."""
    out: list = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals: list, lo: int, hi: int) -> int:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def idle_gaps(intervals: list, lo: int, hi: int) -> list:
    """``(start, end)`` of every stretch of ``[lo, hi]`` with no interval."""
    gaps, cur = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def span_at(spans: list, t: int) -> str:
    """The innermost host span open at ``t``, ``crdt.*`` before
    ``bench.*``; ``"none"`` where no span but the window is open."""
    for prefix in SPAN_PREFIXES:
        best = None
        for s, e, name in spans:
            if s <= t < e and name.startswith(prefix) and name != WINDOW_SPAN:
                if best is None or e - s < best[1] - best[0]:
                    best = (s, e, name)
        if best is not None:
            return best[2]
    return "none"


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops: dict = {}
    modules: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        (int(e.start_ns), int(e.end_ns), e.name, dict(e.stats).get("hlo_module", ""))
                        for e in line.events
                    ]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        (int(e.start_ns), int(e.end_ns), e.name) for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [
                    (int(e.start_ns), int(e.end_ns), e.name)
                    for e in line.events if e.name.startswith(SPAN_PREFIXES)
                ]
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no window span")
    return Trace(ops, modules, spans, win[0])


def to_json(tr: Trace) -> dict:
    """A :class:`Trace` as JSON (the form kept for the tests)."""
    return {"ops": tr.ops, "modules": tr.modules, "spans": tr.spans, "window": tr.window}


def from_json(d: dict) -> Trace:
    return Trace(
        {k: [tuple(x) for x in v] for k, v in d["ops"].items()},
        {k: [tuple(x) for x in v] for k, v in d["modules"].items()},
        [tuple(x) for x in d["spans"]],
        tuple(d["window"]),
    )
