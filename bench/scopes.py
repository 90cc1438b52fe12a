"""From a profiler trace to device time by kernel and by layer scope, and
device idle time by the program's own spans.

The trace is the ``.xplane.pb`` that bench/trace.py reads.  A device
operation is an event of a ``/device:TPU:<n>`` plane's ``XLA Ops`` line:

- its kernel is its instruction name without the numeric suffix
  (``lora_matmul_fwd.3`` -> ``lora_matmul_fwd``): the program names
  every Pallas call ``<kernel>_<pass>``;
- its layer scope is the innermost of the model step's
  ``jax.named_scope`` names (``SCOPES``) in the op's path in the program
  (``.../while/body/transpose(jvp(lm_head))/dot_general`` -> ``lm_head``),
  and ``other`` where it has none.  A TPU trace's op events carry no
  path, so it is read from the compiled modules' HLO text as XLA dumps
  it (``--xla_dump_to``), the ``op_name`` of the instruction's metadata,
  found by module (the ``XLA Modules`` event around the op) and
  instruction name.

Device time is summed over leaf operations only: an operation whose
interval holds another operation of the same device (a ``while``, a
``call``, a ``conditional``) counts nothing, so nested loops are not
counted twice.  Idle time is the window less the union of all operations.
It is given for each innermost ``fl.*`` span (the program's own spans,
repro/obs.py) and, inclusive of the spans nested in it, for each span
name.  Every number is in seconds, averaged over the devices.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

from bench import trace as btrace

SCOPES = ("embed", "attention", "recurrence", "mlp", "lm_head")
SPAN_PREFIX = "fl."
OTHER = "other"
MODULES_LINE = "XLA Modules"
_HLO_OP = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def kernel_name(instruction: str) -> str:
    """``lora_matmul_fwd.3`` -> ``lora_matmul_fwd``."""
    return re.sub(r"\.\d+$", "", instruction)


def scope_of(path: str) -> str:
    """The innermost layer scope named in an op's path."""
    for part in reversed(path.split("/")):
        for word in reversed(re.findall(r"\w+", part)):
            if word in SCOPES:
                return word
    return OTHER


def leaf_ops(events: List[Tuple]) -> List[Tuple]:
    """The events (start, end, ...) whose interval holds no other event.
    Of two events with the same interval, the later listed is the leaf."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, ev in enumerate(evs):
        j = i + 1
        while j < len(evs) and evs[j][0] < ev[1] and evs[j][1] > ev[1]:
            j += 1                      # starts inside, ends after: skip
        if not (j < len(evs) and evs[j][0] < ev[1]):
            out.append(ev)
    return out


def innermost_segments(spans: List[Tuple]) -> List[Tuple]:
    """Nested spans (start, end, name) -> disjoint segments
    (start, end, name of the innermost span that covers it)."""
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    by_start = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    out, open_, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(by_start) and by_start[k][0] <= a:
            open_.append(by_start[k])
            k += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        if open_:
            out.append((a, b, open_[-1][2]))     # the latest-starting one
    return out


def overlap_by(xs: List[Tuple], labelled: List[Tuple]) -> Dict:
    """{label: length of the intersection of ``xs`` with the intervals of
    that label}, for two sorted lists of disjoint intervals, ``xs`` of
    (start, end) and ``labelled`` of (start, end, label)."""
    out = collections.Counter()
    i = j = 0
    while i < len(xs) and j < len(labelled):
        lo = max(xs[i][0], labelled[j][0])
        hi = min(xs[i][1], labelled[j][1])
        if hi > lo:
            out[labelled[j][2]] += hi - lo
        if xs[i][1] < labelled[j][1]:
            i += 1
        else:
            j += 1
    return out


def _window(devices: Dict[str, list], host: list) -> Tuple[float, float]:
    spans = [(s, e) for s, e, n in host if n == btrace.WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    return (min(ev[0] for evs in devices.values() for ev in evs),
            max(ev[1] for evs in devices.values() for ev in evs))


def reduce_events(devices: Dict[str, list], host: list) -> Dict:
    """``devices``: {plane: [(start_ns, end_ns, instruction, op_path)]};
    ``host``: [(start_ns, end_ns, span name)], the window span among
    them.  Returns seconds per kernel, per scope and per span."""
    if not devices or not any(devices.values()):
        raise ValueError("no device operations in the trace")
    t0, t1 = _window(devices, host)
    spans = [(max(s, t0), min(e, t1), n) for s, e, n in host
             if n.startswith(SPAN_PREFIX) and e > t0 and s < t1]
    segments = innermost_segments(spans)
    names = sorted({n for _, _, n in spans})
    within = {n: [(s, e, n) for s, e in
                  btrace._merge([(s, e) for s, e, m in spans if m == n])]
              for n in names}
    kernels, scopes = collections.Counter(), collections.Counter()
    idle_in, idle_within = collections.Counter(), collections.Counter()
    nd = len(devices)
    for evs in devices.values():
        clipped = [(max(s, t0), min(e, t1), n, p) for s, e, n, p in evs
                   if e > t0 and s < t1]
        for s, e, name, path in leaf_ops(clipped):
            kernels[kernel_name(name)] += (e - s) / nd
            scopes[scope_of(path)] += (e - s) / nd
        busy = btrace._merge([(s, e) for s, e, _, _ in clipped])
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for n, t in overlap_by(idle, segments).items():
            idle_in[n] += t / nd
        for ivs in within.values():
            for n, t in overlap_by(idle, ivs).items():
                idle_within[n] += t / nd

    def seconds(counter):
        return {k: v * 1e-9 for k, v in counter.most_common()}
    return {"kernels": seconds(kernels), "scopes": seconds(scopes),
            "leaf_s": sum(kernels.values()) * 1e-9,
            "idle_innermost": seconds(idle_in),
            "idle_within": seconds(idle_within),
            "window_s": (t1 - t0) * 1e-9, "n_devices": nd}


def module_name(event_name: str) -> str:
    """``jit_local_update(1234)`` -> ``jit_local_update``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def hlo_op_paths(dump_dir: str) -> Dict[str, Dict[str, str]]:
    """{module: {instruction: op_name}} from the ``*after_optimizations.txt``
    files XLA dumped.  Of modules of one name, the first read wins."""
    out: Dict[str, Dict[str, str]] = {}
    for path in sorted(glob.glob(os.path.join(
            dump_dir, "*after_optimizations.txt"))):
        with open(path) as f:
            lines = iter(f)
            head = next(lines, "")
            if not head.startswith("HloModule "):
                continue
            ops = out.setdefault(head.split()[1].rstrip(","), {})
            for line in lines:
                m = _HLO_OP.match(line)
                if m:
                    ops.setdefault(m.group(1), m.group(2))
    return out


def op_paths(ops: List[Tuple], modules: List[Tuple],
             paths: Dict[str, Dict[str, str]]) -> List[Tuple]:
    """Device ops (start, end, instruction) -> (start, end, instruction,
    path), each looked up in the module whose event holds its start."""
    modules = sorted(modules)
    out, k = [], 0
    for s, e, name in sorted(ops):
        while k < len(modules) and modules[k][1] <= s:
            k += 1
        module = modules[k][2] if k < len(modules) and \
            modules[k][0] <= s else ""
        out.append((s, e, name, paths.get(module, {}).get(name, "")))
    return out


def _events(data, paths) -> Tuple[Dict[str, list], list]:
    devices: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if btrace.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = [(e.start_ns, e.start_ns + e.duration_ns,
                    btrace.op_name(e.name))
                   for e in lines[btrace.OPS_LINE].events] \
                if btrace.OPS_LINE in lines else []
            modules = [(e.start_ns, e.start_ns + e.duration_ns,
                        module_name(e.name))
                       for e in lines[MODULES_LINE].events] \
                if MODULES_LINE in lines else []
            devices[plane.name] = op_paths(ops, modules, paths)
        elif plane.name.startswith("/host:"):
            host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for line in plane.lines for e in line.events
                     if e.name == btrace.WINDOW_SPAN
                     or e.name.startswith(SPAN_PREFIX)]
    return devices, host


def reduce_trace(trace_dir: str, dump_dir: str = "") -> Dict:
    """The reduction of the trace in ``trace_dir``, with op paths from
    the HLO XLA dumped to ``dump_dir`` (every scope ``other`` without)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(btrace.find_xplane(trace_dir))
    paths = hlo_op_paths(dump_dir) if dump_dir else {}
    return reduce_events(*_events(data, paths))
