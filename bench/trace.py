"""From a profiler trace to device busy time, the longest device
operations and the idle gaps by host stage.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are those named ``/device:TPU:<n>``; an operation is an event on
the plane's ``XLA Ops`` line.  Busy time is the union of operation
intervals inside the window, averaged over the devices.  Host spans are
``jax.profiler.TraceAnnotation`` events on the host planes: the window
is the span named ``bench.window`` and each idle gap is named after the
``stage.*`` span that covers its middle (``host.other`` when none does).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
STAGE_PREFIX = "stage."
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {len(paths)}")
    return paths[0]


def op_name(hlo_text: str) -> str:
    """The instruction name of a trace event whose name is the HLO text
    (``%fusion.260 = s32[3]{0} fusion(...)`` -> ``fusion.260``)."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def _events(data) -> Tuple[Dict[str, list], list]:
    """({device plane: [(start, end, name)]}, [(start, end, name)] host)"""
    devices: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or \
                            e.name.startswith(STAGE_PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    return devices, host


def _merge(intervals: List[Tuple[float, float]]):
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(devices: Dict[str, list], host: list) -> Dict:
    """The reduction, on plain event lists (tested on synthetic ones)."""
    if not devices or not any(devices.values()):
        raise ValueError("no device operations in the trace")
    spans = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if spans:
        t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        t0 = min(s for evs in devices.values() for s, _, _ in evs)
        t1 = max(e for evs in devices.values() for _, e, _ in evs)
    stages = sorted((s, e, n) for s, e, n in host
                    if n.startswith(STAGE_PREFIX))
    busy_total, op_time = 0.0, collections.Counter()
    gaps = collections.Counter()
    for evs in devices.values():
        clipped = [(max(s, t0), min(e, t1), n) for s, e, n in evs
                   if e > t0 and s < t1]
        for s, e, n in clipped:
            op_time[n] += (e - s) / len(devices)
        merged = _merge([(s, e) for s, e, _ in clipped])
        busy_total += sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) / 2
            name = "host.other"
            for s, e, n in stages:
                if s <= mid <= e:
                    name = n          # innermost: the latest-starting one
            gaps[name] += (ge - gs) / len(devices)
    window = (t1 - t0) * 1e-9
    return {"busy_s": busy_total / len(devices) * 1e-9,
            "window_s": window,
            "device_ops": [[n, t * 1e-9] for n, t in op_time.most_common(TOP)],
            "idle_gaps": [[n, t * 1e-9] for n, t in gaps.most_common(TOP)],
            "n_devices": len(devices)}


def reduce_trace(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_events(*_events(data))
