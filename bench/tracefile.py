"""The one reader of profiler traces: a ``.xplane.pb`` reduced with JAX's
own ``ProfileData`` to what the metrics need.

Device planes are named ``/device:TPU:<i>``. On each, the line
``XLA Modules`` holds one event per executable run (named after the jitted
function, e.g. ``jit_encode(<id>)``) and ``XLA Ops`` one event per
operation. Host planes hold the benchmark's own spans (``bench.*``
annotations), among them ``bench.window``, which marks the traced slice.
Everything is clipped to that slice.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def union_ns(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def module_name(name: str) -> str:
    """``jit_encode(1234)`` -> ``jit_encode``."""
    return _SUFFIX.sub("", name).strip()


@dataclasses.dataclass
class Trace:
    window_ns: tuple                 # (start, end) of the traced slice
    ops: list                        # per device: [(name, start, end)]
    modules: list                    # per device: [(name, start, end)]
    spans: list                      # host: [(name, start, end)]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(union_ns((s, e) for _, s, e in dev)
                   for dev in self.ops) / len(self.ops) / 1e9

    def _modules(self, prefixes):
        return [(s, e) for dev in self.modules for n, s, e in dev
                if module_name(n).startswith(tuple(prefixes))]

    def module_calls(self, prefixes) -> int:
        return len(self._modules(prefixes))

    def module_time(self, prefixes) -> float:
        """Summed device seconds of the named executables' runs."""
        return sum(e - s for s, e in self._modules(prefixes)) / 1e9

    def module_union(self, prefixes) -> float:
        """Device seconds in which any of the named executables ran
        (averaged over devices, as ``busy_s``)."""
        n = max(len(self.modules), 1)
        return union_ns(self._modules(prefixes)) / n / 1e9

    def top_ops(self, k: int = 10) -> list:
        tot: dict = {}
        for dev in self.ops:
            for n, s, e in dev:
                tot[n] = tot.get(n, 0) + (e - s)
        n_dev = max(len(self.ops), 1)
        return [[n, t / n_dev / 1e9] for n, t in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest gaps with no operation on device 0, each named by
        the benchmark's host span that covers most of it."""
        if not self.ops:
            return []
        w0, w1 = self.window_ns
        busy = merged((s, e) for _, s, e in self.ops[0])
        gaps, t = [], w0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w1 > t:
            gaps.append((t, w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for g0, g1 in gaps[:k]:
            cover: dict = {}
            for n, s, e in self.spans:
                ov = min(e, g1) - max(s, g0)
                if ov > 0:
                    cover[n] = cover.get(n, 0) + ov
            name = max(cover, key=cover.get) if cover else "unattributed"
            out.append([name, (g1 - g0) / 1e9])
        return out


def _clip(events, w0, w1):
    return [(n, max(s, w0), min(e, w1)) for n, s, e in events
            if e > w0 and s < w1]


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` to the benchmark's ``Trace``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops.append([(ev.name, ev.start_ns, ev.end_ns)
                        for ev in lines[OPS_LINE].events]
                       if OPS_LINE in lines else [])
            modules.append([(ev.name, ev.start_ns, ev.end_ns)
                            for ev in lines[MODULES_LINE].events]
                           if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend((ev.name, ev.start_ns, ev.end_ns)
                             for ev in ln.events
                             if ev.name.startswith(SPAN_PREFIX))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    w0, w1 = windows[0]
    return Trace(window_ns=(w0, w1),
                 ops=[_clip(d, w0, w1) for d in ops],
                 modules=[_clip(d, w0, w1) for d in modules],
                 spans=[sp for sp in _clip(spans, w0, w1)
                        if sp[0] != WINDOW_SPAN])
