"""Reduce a profiler trace of the measured window to device numbers.

What a TPU trace holds (read by hand from one, TPU v5 lite, JAX 0.9):

* one plane per chip, ``/device:TPU:<n>``, with the line ``XLA Modules``
  (one event per jitted program run, named ``jit_<fn>(<hash>)``) and
  the line ``XLA Ops`` (one event per HLO instruction run, named by
  the instruction's HLO text). Ops nest: a ``while`` spans the ops of
  its body.
* ``/host:CPU``, whose lines are host threads; the program's telemetry
  spans (``session.xmap``, ``serve.batch``, …) appear there when bridged
  (``telemetry.enable_xla_trace``), and so does the harness's
  ``bench.window`` span. Only spans named that way are kept.

A Pallas kernel is an op whose HLO text names
``custom_call_target="tpu_custom_call"``; its instruction is named
after the kernel function and carries no source file
(``kernel_metadata={}``). Several kernels share the function name
``_kernel``, which shows as ``_call.<n>``, so such an op is told apart
by the program it runs in (``KERNEL_MODULES``); a kernel with a name of
its own is keyed by that name.

``Trace`` keeps, per chip, each op as (key, start_ns, end_ns) clipped to
the window, with key ``kernel:<name>`` for kernels and
``<module>/<opcode>`` otherwise, and the host spans. It is saved and
loaded as JSON, which is how the test's recorded trace is kept.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
SHARED_NAME = "_call"  # the instruction name of every ``_kernel`` function

#: Kernel of a shared-name op, by a fragment of its program's name (the
#: programs the cells run them in, as seen in their traces).
KERNEL_MODULES = (
    ("panel_master", "knn_multi_e"),
    ("group_step", "knn_batch"),
)

TELEMETRY_SPAN = re.compile(r"^[a-z_]+\.[A-Za-z_.]+$")


def module_name(event_name: str) -> str:
    """``jit__group_step(7122…)`` → ``jit__group_step``."""
    return event_name.split("(", 1)[0]


def op_key(hlo: str, module: str) -> str:
    """Stable key of one ``XLA Ops`` event (see the module docstring)."""
    head, _, rest = hlo.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    if KERNEL_TARGET in rest:
        if base != SHARED_NAME:
            return f"kernel:{base}"
        for fragment, kernel in KERNEL_MODULES:
            if fragment in module:
                return f"kernel:{kernel}"
        return f"kernel:{module}{SHARED_NAME}"
    return f"{module}/{base}"


def _enclosing(modules, start):
    """Name of the module event that holds ``start`` (sorted list)."""
    lo, hi = 0, len(modules)
    while lo < hi:  # last module starting at or before ``start``
        mid = (lo + hi) // 2
        if modules[mid][1] <= start:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][2] >= start:
        return modules[lo - 1][0]
    return "?"


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


def self_times(ops) -> dict:
    """Per key, the time its ops ran with no nested op inside them."""
    out = defaultdict(int)
    stack: list = []  # [key, start, end, child_ns]

    def pop():
        key, s, e, child = stack.pop()
        out[key] += (e - s) - child
        if stack:
            stack[-1][3] += e - s

    for key, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            pop()
        stack.append([key, s, e, 0])
    while stack:
        pop()
    return dict(out)


class Trace:
    """Device ops and host spans of the measured window."""

    def __init__(self, window, devices, host):
        self.window = tuple(window)  # (start_ns, end_ns)
        self.devices = devices  # plane name -> [(key, start, end)]
        self.host = host  # [(name, start, end)]

    # ------------------------------------------------------- loading

    @classmethod
    def from_dir(cls, log_dir: str, *, window_span: str) -> "Trace":
        files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_xplane(max(files, key=os.path.getmtime),
                               window_span=window_span)

    @classmethod
    def from_xplane(cls, path: str, *, window_span: str) -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        host, planes = [], []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                planes.append(plane)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if TELEMETRY_SPAN.match(ev.name):
                            host.append((ev.name, ev.start_ns, ev.end_ns))
        spans = [h for h in host if h[0] == window_span]
        if not spans:
            raise ValueError(f"trace has no {window_span!r} host span")
        w0, w1 = spans[0][1], spans[0][2]
        devices = {}
        for plane in planes:
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted(((module_name(ev.name), ev.start_ns, ev.end_ns)
                           for ev in lines["XLA Modules"].events),
                          key=lambda m: m[1]) \
                if "XLA Modules" in lines else []
            ops = []
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    ops.append((op_key(ev.name, _enclosing(mods, ev.start_ns)),
                                s, e))
            devices[plane.name] = ops
        host = [(n, max(s, w0), min(e, w1)) for n, s, e in host
                if min(e, w1) > max(s, w0)]
        return cls((w0, w1), devices, host)

    def to_json(self) -> dict:
        w0 = self.window[0]

        def rel(rows):
            return [[r[0], r[1] - w0, r[2] - w0] for r in rows]

        return {"window": [0, self.window[1] - w0],
                "devices": {d: rel(ops) for d, ops in self.devices.items()},
                "host": rel(self.host)}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        return cls(data["window"],
                   {d: [tuple(o) for o in ops]
                    for d, ops in data["devices"].items()},
                   [tuple(h) for h in data["host"]])

    # ------------------------------------------------------- reductions

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_ns([(s, e) for _, s, e in ops])
                   for ops in self.devices.values()) / len(self.devices) / 1e9

    def kernel_s(self, kernels) -> float:
        """Summed device seconds of the named kernels, over all chips."""
        want = {f"kernel:{k}" for k in kernels}
        return sum(e - s for ops in self.devices.values()
                   for key, s, e in ops if key in want) / 1e9

    def idle_gaps(self):
        """(label, start, end) of each gap between device ops, per chip;
        the label is the innermost host span (the program's telemetry
        spans and ``bench.*``) open at the gap's middle, else "none"."""
        gaps = []
        for ops in self.devices.values():
            t = self.window[0]
            for s, e in sorted((s, e) for _, s, e in ops):
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
            if self.window[1] > t:
                gaps.append((t, self.window[1]))
        spans = sorted(self.host, key=lambda h: h[1])
        out, active, j = [], [], 0
        for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
            mid = (s + e) // 2
            while j < len(spans) and spans[j][1] <= mid:
                active.append(spans[j])
                j += 1
            active = [h for h in active if h[2] >= mid]
            label = (min(active, key=lambda h: h[2] - h[1])[0] if active
                     else "none")
            out.append((label, s, e))
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = [o for ops in self.devices.values() for o in ops]
        selfs = sorted(self_times(ops).items(), key=lambda kv: -kv[1])[:top]
        idle = defaultdict(int)
        for label, s, e in self.idle_gaps():
            idle[label] += e - s
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in selfs],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
