"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` and keeps, for every
device plane (``/device:TPU:<i>``), the events of its ``XLA Ops`` line
(one event per device operation) and of its ``XLA Modules`` line (one
per executable run).  Times are in seconds on the trace's own clock.

* busy -- the union of the device-operation intervals (operations nest:
  a loop's event covers its body's), averaged over the devices used;
* idle gaps -- the stretches of the traced window with no device
  operation running, each named after what the host was doing in it;
* op and module totals -- summed durations per event name.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """An operation's event name is its whole HLO instruction; keep the
    instruction's name, and a custom call's target."""
    name = text.split(" = ", 1)[0]
    target = _TARGET.search(text)
    return f"{name} {target.group(1)}" if target else name


@dataclass
class DeviceTrace:
    """Device events of one traced window: per device, ``ops`` and
    ``modules`` as ``(name, start_s, dur_s)`` lists, and host annotations
    as ``(name, start_s, dur_s)``."""
    ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    host: list = field(default_factory=list)

    @classmethod
    def from_dir(cls, logdir: str) -> "DeviceTrace":
        paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {logdir}")
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(max(paths)))

    @classmethod
    def from_profile(cls, pd) -> "DeviceTrace":
        out = cls()
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        dest = (out.ops if line.name == OPS_LINE
                                else out.modules)
                        dest.setdefault(plane.name, []).extend(
                            (sys.intern(e.name), e.start_ns * 1e-9,
                             e.duration_ns * 1e-9) for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    out.host.extend(
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events if e.name.startswith("bench."))
        return out

    @classmethod
    def from_json(cls, d: dict) -> "DeviceTrace":
        conv = lambda evs: [tuple(e) for e in evs]        # noqa: E731
        return cls(ops={k: conv(v) for k, v in d["ops"].items()},
                   modules={k: conv(v) for k, v in d["modules"].items()},
                   host=conv(d["host"]))

    # ---------------------------------------------------------- reductions
    def devices(self) -> list:
        return sorted(self.ops)

    def busy_intervals(self, device: str) -> list:
        """Union of one device's operation intervals, sorted."""
        merged: list = []
        for _, t0, dur in sorted(self.ops.get(device, ()),
                                 key=lambda e: e[1]):
            t1 = t0 + dur
            if merged and t0 <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t1)
            else:
                merged.append([t0, t1])
        return merged

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(sum(t1 - t0 for t0, t1 in self.busy_intervals(d))
                   for d in devs) / len(devs)

    def idle_gaps(self, device: str, t0: float, t1: float) -> list:
        """``(start, end)`` stretches of ``[t0, t1]`` with nothing
        running on ``device``."""
        gaps, cur = [], t0
        for b0, b1 in self.busy_intervals(device):
            if b0 > cur:
                gaps.append((cur, min(b0, t1)))
            cur = max(cur, b1)
            if cur >= t1:
                break
        if cur < t1:
            gaps.append((cur, t1))
        return [(a, b) for a, b in gaps if b > a]

    def total(self, kind: str, match) -> tuple[float, int]:
        """``(seconds, count)`` of the ``kind`` ("ops" or "modules")
        events whose name satisfies ``match``, averaged over devices."""
        table = self.ops if kind == "ops" else self.modules
        devs = sorted(table)
        if not devs:
            return 0.0, 0
        secs = cnt = 0
        for d in devs:
            for name, _, dur in table[d]:
                if match(name):
                    secs += dur
                    cnt += 1
        return secs / len(devs), cnt // len(devs)

    def top(self, kind: str, k: int = 10) -> list:
        """The ``k`` events (by short name) with the most device time.
        Operations nest (a loop's body runs inside the loop's own
        event), so these times overlap."""
        table = self.ops if kind == "ops" else self.modules
        acc: dict = {}
        devs = sorted(table)
        for d in devs:
            for name, _, dur in table[d]:
                key = short_name(name)
                acc[key] = acc.get(key, 0.0) + dur / len(devs)
        return sorted(([n, s] for n, s in acc.items()),
                      key=lambda e: -e[1])[:k]
