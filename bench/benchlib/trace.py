"""Reduction of a profiler trace to device intervals.

The ``.xplane.pb`` the JAX profiler writes is read (through
``jax.profiler.ProfileData``) into plain :class:`Event` records: plane, line,
name, start and duration in nanoseconds.  Only what the reduction reads is
kept: the op and module lines of the device planes, and the host planes.

Device planes are those named ``/device:<KIND>:<n>``.  On a TPU the op line
(``XLA Ops``) carries one event per executed HLO op, Pallas kernels among
them, and the module line (``XLA Modules``) one event per execution of a
compiled program.  Busy time is the union of op intervals, so overlapping
ops are counted once.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(path) -> list[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out: list[Event] = []
    for pl in data.planes:
        device = DEVICE_PLANE.match(pl.name) is not None
        if not device and not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            if device and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            out.extend(Event(pl.name, ln.name, e.name, float(e.start_ns),
                             float(e.duration_ns)) for e in ln.events)
    return out


def load_dir(log_dir) -> list[Event]:
    """The events of the trace the profiler wrote under ``log_dir``."""
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return load_xplane(found[-1])


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """The device view of one traced run, over a window [lo, hi] in the
    trace's clock (nanoseconds)."""

    def __init__(self, events: list[Event], lo: float, hi: float):
        self.events = events
        self.lo, self.hi = float(lo), float(hi)
        self.devices = sorted({e.plane for e in events
                               if DEVICE_PLANE.match(e.plane)})
        self._lines: dict[tuple, list[Event]] = {}
        self._busy: dict[str, list[tuple[float, float]]] = {}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def line(self, name: str, device: str | None = None) -> list[Event]:
        key = (name, device)
        if key not in self._lines:
            self._lines[key] = [
                e for e in self.events if e.line == name and
                (e.plane == device if device else e.plane in self.devices)]
        return self._lines[key]

    def ops(self, device: str | None = None) -> list[Event]:
        return self.line(OPS_LINE, device)

    def modules(self, device: str | None = None) -> list[Event]:
        return self.line(MODULES_LINE, device)

    def busy(self, device: str) -> list[tuple[float, float]]:
        """The device's merged op intervals in the window, merged once (a
        traced run's millions of ops take seconds to sort)."""
        if device not in self._busy:
            self._busy[device] = clip(
                union((e.start_ns, e.end_ns) for e in self.ops(device)),
                self.lo, self.hi)
        return self._busy[device]

    def busy_s(self) -> float:
        """Seconds in the window in which an op ran, averaged over the
        devices in the trace."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(d))
                   for d in self.devices) * 1e-9 / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def matching(self, events: list[Event], pattern: str) -> list[Event]:
        rx = re.compile(pattern)
        return [e for e in events if rx.search(e.name)]

    def host_events(self) -> list[Event]:
        return [e for e in self.events if e.plane.startswith("/host:")]
