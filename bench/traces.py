"""Reduction of a profiler trace to the benchmark's device numbers.

The window is the benchmark's own ``bench.window`` host span. Inside it:

* busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  averaged over the devices used; the idle share is 1 - busy / window;
* a kernel's time is the sum of the durations of its device events, found
  by a pattern on the event name or its ``hlo_op`` / ``hlo_module``;
* each idle gap is named by the innermost ``bench.*`` host span that covers
  its middle (what the host was doing while the device waited).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str            # the op's or program's own name: "rbf_pallas.3"
    start: int           # ns, on the trace's clock
    end: int
    device: int


_OP_NAME = re.compile(r"^%?([^\s=(]+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """An op event's name is its HLO text ("%rbf_pallas.3 = f32[...]
    custom-call(...), custom_call_target=..."), a program's is
    "jit_name(fingerprint)": keep the op's or program's own name, and a
    custom call's target beside it."""
    m = _OP_NAME.match(text)
    name = m.group(1) if m else text
    target = _TARGET.search(text)
    if target and target.group(1) != "tpu_custom_call":
        name += f" ({target.group(1)})"
    return name


@dataclasses.dataclass
class Trace:
    window: tuple[int, int]                 # ns
    ops: list[Event]                        # device ops inside the window
    modules: list[Event]                    # device programs inside it
    host: list[tuple[str, int, int]]        # bench.* spans
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: int) -> list[tuple[int, int]]:
        ivs = sorted((e.start, e.end) for e in self.ops if e.device == device)
        merged: list[list[int]] = []
        for a, b in ivs:
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return [(a, b) for a, b in merged]

    @property
    def busy_s(self) -> float:
        devices = sorted({e.device for e in self.ops}) or [0]
        total = sum(b - a for d in devices
                    for a, b in self.busy_intervals(d))
        return total * 1e-9 / max(self.n_devices, 1)

    def time_s(self, pattern: str, *, line: str = "ops") -> float:
        """Summed device seconds of the events whose own name matches
        ``pattern`` (a regular expression, searched)."""
        rx = re.compile(pattern)
        events = self.ops if line == "ops" else self.modules
        return sum(e.end - e.start for e in events
                   if rx.search(e.name)) * 1e-9

    def top_ops(self, k: int = 10) -> list[list]:
        by: dict[str, int] = {}
        for e in self.ops:
            by[e.name] = by.get(e.name, 0) + e.end - e.start
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The k longest idle gaps of device 0 in the window, each named by
        the host span covering its middle."""
        lo, hi = self.window
        edges = [lo]
        for a, b in self.busy_intervals(0):
            edges += [max(a, lo), min(b, hi)]
        edges.append(hi)
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) // 2
            inner = [s for s in self.host if s[1] <= mid <= s[2]
                     and s[0] != WINDOW_SPAN]
            name = (min(inner, key=lambda s: s[2] - s[1])[0] if inner
                    else "outside any bench span")
            out.append([name, (b - a) * 1e-9])
        return out


def options():
    """Profiler options of a traced window: device ops and the host's
    annotations (the benchmark's spans), without the Python call tracer,
    which would write every function call of the client loop."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, n_devices: int = 1) -> Trace:
    """Read an ``.xplane.pb`` into a ``Trace`` cut to its window span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, ops, modules = [], [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                device = int(plane.name.rsplit(":", 1)[1])
                sink = ops if line.name == OPS_LINE else modules
                for e in line.events:
                    start = int(e.start_ns)
                    sink.append(Event(short_name(e.name), start,
                                      start + int(e.duration_ns), device))
            elif not m:
                for e in line.events:
                    if e.name.startswith("bench."):
                        start = int(e.start_ns)
                        host.append((e.name, start,
                                     start + int(e.duration_ns)))
    windows = [(a, b) for n, a, b in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[0]

    def inside(events):
        return [dataclasses.replace(e, start=max(e.start, lo),
                                    end=min(e.end, hi))
                for e in events if e.end > lo and e.start < hi]

    return Trace((lo, hi), inside(ops), inside(modules), host, n_devices)


# -- what the per-layer metric readers look for ---------------------------------

# the rbf covariance Pallas kernel (kernels/rbf/rbf.py): its custom call
# carries the name of the jitted wrapper, "rbf_pallas.<n>"
RBF_KERNEL = r"^rbf_pallas\b"
# the rank-b update of the whitened global factor (linalg.chol_update_rank),
# a program of its own: "jit_chol_update_rank(<fingerprint>)"
RANK_UPDATE = r"^jit_chol_update_rank\b"


def idle_percent(ctx):
    """100 * (1 - busy / window) of the traced window, or None untraced."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def roofline_percent(ctx, pattern: str, ops: float, nbytes: float):
    """A kernel's share of its roofline, in %: the least time the chip
    needs for ``ops`` and ``nbytes`` (the longer of ops at the bf16 peak
    and bytes at the HBM bandwidth) over the kernel's summed device time.
    None where the trace holds no event of the kernel."""
    if ctx.trace is None or ctx.peaks is None or ops <= 0:
        return None
    t = ctx.trace.time_s(pattern)
    if t <= 0:
        return None
    bound = max(ops / ctx.peaks["flops_bf16_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
