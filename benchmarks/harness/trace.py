"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU trace has
one plane per chip (``/device:TPU:<n>``) whose line ``XLA Ops`` holds one
event per executed HLO instruction, and a host plane (``/host:CPU``)
whose lines are threads; ``jax.profiler.TraceAnnotation`` events appear
there under their own names, on the same clock.

* busy: the union of the intervals in which an operation ran on a device
* idle share: 1 - busy / window, the window being the span of all events
  of the trace
* time under an annotation: the device's busy time from the start of the
  host span to the start of the next annotated span (a jitted call
  returns before the device has run it, so the span itself covers only
  the dispatch; the steps annotated here are serial: each is fetched
  before the next is dispatched)
* an idle gap is named by what the host was doing: ``in_<span>`` if it
  starts inside an annotation, ``after_<span>`` if one ended before it,
  else ``before_any_annotation``
* exposed collective time: time inside collective operations during
  which no other operation runs on that device
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)")
# instructions that only enclose others (a scan's loop, a branch): their
# events span their children's, which are listed too
CONTAINER = re.compile(r"^(while|conditional|call)$")


def op_family(name: str) -> str:
    """``%fusion.123`` -> ``fusion``: the instruction's name without the
    number the compiler gave it."""
    name = name.lstrip("%").split(" = ")[0].split("(")[0]
    return re.sub(r"[._]\d+$", "", re.sub(r"\.\d+(\.clone)?$", "", name))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``merged`` (disjoint, sorted) inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged
               if e > lo and s < hi)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class DeviceTrace:
    name: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)

    def busy(self) -> List[Interval]:
        return union([(s, e) for _, s, e in self.ops])


@dataclass
class Reduced:
    window: Interval
    devices: List[DeviceTrace]
    annotations: Dict[str, List[Interval]]      # host spans by name

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s_by_device(self) -> Dict[str, float]:
        return {d.name: total(d.busy()) for d in self.devices}

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices used."""
        b = self.busy_s_by_device()
        return sum(b.values()) / len(b) if b else 0.0

    def idle_share_by_device(self) -> Dict[str, float]:
        return {k: 1.0 - v / self.window_s
                for k, v in self.busy_s_by_device().items()}

    def under_annotation(self, name: str) -> List[float]:
        """For each host span of that name, the busy seconds of the
        busiest device from the span's start to the start of the next
        annotated span of any name (the window's end for the last)."""
        merged = [d.busy() for d in self.devices]
        starts = sorted(s for ivs in self.annotations.values()
                        for s, _ in ivs)
        out = []
        for s, _ in self.annotations.get(name, []):
            later = [t for t in starts if t > s]
            end = later[0] if later else self.window[1]
            out.append(max((clip(m, s, end) for m in merged), default=0.0))
        return out

    def op_seconds(self, pattern: str) -> float:
        """Seconds in operations whose family matches, averaged over the
        devices."""
        rx = re.compile(pattern)
        if not self.devices:
            return 0.0
        return sum(e - s for d in self.devices for n, s, e in d.ops
                   if rx.search(op_family(n))) / len(self.devices)

    def top_ops(self, n: int = 10) -> List[List]:
        acc: Dict[str, float] = {}
        for d in self.devices:
            for name, s, e in d.ops:
                fam = op_family(name)
                if CONTAINER.match(fam):
                    continue
                acc[fam] = acc.get(fam, 0.0) + (e - s) / len(self.devices)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle seconds of the first device by what the host was doing."""
        if not self.devices:
            return []
        spans = sorted((s, e, name) for name, ivs in self.annotations.items()
                       for s, e in ivs)
        gaps = subtract([self.window], self.devices[0].busy())
        acc: Dict[str, float] = {}
        for s, e in gaps:
            inside = [nm for a, b, nm in spans if a <= s < b]
            if inside:
                label = "in_" + inside[-1]
            else:
                before = [(b, nm) for a, b, nm in spans if b <= s]
                label = ("after_" + max(before)[1] if before
                         else "before_any_annotation")
            acc[label] = acc.get(label, 0.0) + (e - s)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def collective_exposed_by_device(self) -> Dict[str, float]:
        out = {}
        for d in self.devices:
            coll = union([(s, e) for n, s, e in d.ops
                          if COLLECTIVE.match(op_family(n))])
            rest = union([(s, e) for n, s, e in d.ops
                          if not COLLECTIVE.match(op_family(n))
                          and not CONTAINER.match(op_family(n))])
            out[d.name] = total(subtract(coll, rest))
        return out


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def reduce_profile(profile, annotation_prefix: str = "bench.") -> Reduced:
    """``profile``: a ``jax.profiler.ProfileData``."""
    devices: List[DeviceTrace] = []
    annotations: Dict[str, List[Interval]] = {}
    lo, hi = float("inf"), float("-inf")
    for plane in profile.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        dev = DeviceTrace(plane.name) if is_device else None
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if is_device and line.name == OPS_LINE:
                    dev.ops.append((ev.name, s, e))
                    lo, hi = min(lo, s), max(hi, e)
                elif not is_device and ev.name.startswith(annotation_prefix):
                    annotations.setdefault(ev.name, []).append((s, e))
                    lo, hi = min(lo, s), max(hi, e)
        if dev is not None and dev.ops:
            devices.append(dev)
    for ivs in annotations.values():
        ivs.sort()
    if not devices:
        lo, hi = 0.0, 0.0
    return Reduced((lo, hi), devices, annotations)


def reduce_dir(trace_dir: str) -> Optional[Reduced]:
    """The newest trace under ``trace_dir`` reduced, or None when there
    is none or it holds no device operation."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    reduced = reduce_profile(ProfileData.from_file(path))
    return reduced if reduced.devices else None
