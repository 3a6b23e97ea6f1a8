"""Reduction of a JAX profiler trace (`.xplane.pb`) to device metrics.

Device planes are `/device:TPU:<n>`. On each, the op line ("XLA Ops")
gives the intervals in which an operation ran, and the module line
("XLA Modules") the executables (named after the jitted function) that
per-kernel times are matched against. The traced window is the host's
`bench:window` annotation, so device and host share the profiler's
clock; busy time is the union of op intervals clipped to it.
"""

from __future__ import annotations

import glob
from dataclasses import dataclass, field

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
WINDOW = "bench:window"


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)       # (start, end, name)
    modules: list = field(default_factory=list)   # (start, end, name)


@dataclass
class Trace:
    window: tuple                                  # (start_ns, end_ns)
    devices: list
    host: list                             # (start, end, name, line)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, dev: Device) -> list:
        src = dev.ops or dev.modules
        return merge([(max(s, self.window[0]), min(e, self.window[1]))
                      for s, e, _n in src
                      if e > self.window[0] and s < self.window[1]])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for d in self.devices
                  for s, e in self.busy_intervals(d))
        return tot / len(self.devices) / 1e9

    def module_s(self, patterns) -> float:
        """Device seconds of executables whose name holds a pattern,
        summed over devices, inside the window."""
        tot = 0
        for d in self.devices:
            for s, e, n in d.modules:
                if any(p in n for p in patterns):
                    tot += min(e, self.window[1]) - max(s, self.window[0])
        return max(tot, 0) / 1e9

    def module_runs(self, patterns) -> int:
        """Executions of executables whose name holds a pattern on the
        first device, starting inside the window."""
        if not self.devices:
            return 0
        return sum(1 for s, _e, n in self.devices[0].modules
                   if self.window[0] <= s < self.window[1]
                   and any(p in n for p in patterns))

    def top_ops(self, k: int = 10) -> list:
        """The device ops that took most time (innermost ops only: a
        while loop's time is its body's), seconds per device."""
        tot: dict = {}
        for d in self.devices:
            for s, e, n in leaves(d.ops or d.modules):
                if e > self.window[0] and s < self.window[1]:
                    n = short(n)
                    tot[n] = tot.get(n, 0) + (e - s)
        top = sorted(tot.items(), key=lambda x: -x[1])[:k]
        return [[n, v / 1e9 / max(1, len(self.devices))] for n, v in top]

    def idle_gaps(self, k: int = 10, labels=()) -> list:
        """The longest idle gaps of the first device, each named by what
        the host was doing at its middle: the innermost of `labels`
        ((start_ns, end_ns, name) on the profiler clock: the program's
        own spans, placed there by the driver), else the innermost host
        event of the trace, Python threads first."""
        if not self.devices:
            return []
        busy = self.busy_intervals(self.devices[0])
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            under = [h for h in labels if h[0] <= mid <= h[1]]
            if not under:
                under = [h for h in self.host if h[0] <= mid <= h[1]]
                under = [h for h in under if h[3] == "python"] or under
            name = min(under, key=lambda h: h[1] - h[0])[2] if under \
                else "unattributed"
            out.append([name, (e - s) / 1e9])
        return out

    def host_start(self, name: str) -> float | None:
        """Start of the first host event called `name` in the window."""
        return min((h[0] for h in self.host if h[2] == name
                    and self.window[0] <= h[0] <= self.window[1]),
                   default=None)


def short(name: str) -> str:
    """An HLO op's name without its shape and operands."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(ivs) -> list:
    """Intervals that contain no other interval of the list."""
    ivs = sorted(ivs, key=lambda iv: (iv[0], -iv[1]))
    return [iv for i, iv in enumerate(ivs)
            if i + 1 == len(ivs) or ivs[i + 1][0] >= iv[1]]


def merge(ivs) -> list:
    out: list = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def from_planes(planes) -> Trace:
    """Build a Trace from ProfileData-like planes (name, lines; lines:
    name, events; events: name, start_ns, duration_ns)."""
    devices, host = [], []
    window = None
    for pl in planes:
        if pl.name.startswith("/device:") and "CPU" not in pl.name:
            d = Device(pl.name)
            for ln in pl.lines:
                dst = d.ops if ln.name in OP_LINES else \
                    d.modules if ln.name in MODULE_LINES else None
                if dst is not None:
                    dst.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in ln.events)
            if d.ops or d.modules:
                devices.append(d)
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                for ev in ln.events:
                    if ev.duration_ns <= 0:
                        continue
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns,
                          ev.name, ln.name)
                    if ev.name == WINDOW:
                        window = iv[:2]
                    else:
                        host.append(iv)
    if window is None:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    devices.sort(key=lambda d: d.name)
    return Trace(window=window, devices=devices, host=host)


def load(log_dir) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_planes(ProfileData.from_file(files[-1]).planes)
