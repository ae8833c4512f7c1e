"""Reduction of a profiler trace to device busy time, kernel time and gaps.

`events_from_xplane` reads the ``.xplane.pb`` the JAX profiler writes into a
plain form, and `reduce` works on that form alone, so a small trace
recorded on the chip (kept under ``bench/tests/data``) checks the same code
on a CPU:

    {"devices": {plane name: [[op name, start_ns, dur_ns, {stat: value}], ...]},
     "host": [[event name, start_ns, dur_ns], ...],      # the driving thread
     "window_ns": [start, end]}

Which plane is a device, which line holds its operations, and how kernels
are named are read from ``kernel_names.json`` beside the harness.
"""

from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

NAMES = Path(__file__).resolve().parents[1] / "kernel_names.json"


def load_names(path: Path = NAMES) -> dict:
    return json.loads(path.read_text())


def _stat_value(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def events_from_xplane(path: str, names: dict, span: str) -> dict:
    """The device-op lines of every device plane and the events of the host
    thread that drove the run, in the plain form `reduce` reads. The driving
    thread is the host line that holds the event named ``span``, and that
    event is the window, on the trace's own clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, window = {}, [], []
    dev_re = re.compile(names["device_plane"])
    for plane in data.planes:
        if dev_re.search(plane.name):
            for line in plane.lines:
                if line.name == names["ops_line"]:
                    devices[plane.name] = [
                        [e.name, e.start_ns, e.duration_ns,
                         {k: _stat_value(v) for k, v in e.stats}]
                        for e in line.events]
        elif plane.name == names["host_plane"]:
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
                marks = [[s, s + d] for name, s, d in events if name == span]
                if marks and not window:
                    host, window = events, marks
    if not window:
        raise RuntimeError(f"no {span!r} event on any line of the host plane "
                           f"of {path}")
    return {"devices": devices, "host": [e for e in host if e[0] != span],
            "window_ns": window[0]}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def kernel_of(event, kernels: dict) -> str | None:
    """The name-table entry an op event belongs to, or None. An entry
    matches when each of its ``match`` fields (the op name, or one of the
    event's stats) contains its pattern."""
    name, _, _, stats = event
    for kernel, rule in kernels.items():
        ok = True
        for fld, pattern in rule["match"].items():
            value = name if fld == "name" else stats.get(fld)
            if value is None or not re.search(pattern, str(value)):
                ok = False
                break
        if ok:
            return kernel
    return None


@dataclass
class Reduction:
    window_s: float
    busy_s: float                          # mean over the devices traced
    kernel_s: dict = field(default_factory=dict)       # kernel -> seconds
    kernel_calls: dict = field(default_factory=dict)   # kernel -> events
    device_ops: list = field(default_factory=list)     # [[name, s]] top 10
    idle_gaps: list = field(default_factory=list)      # [[host name, s]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _op_name(name: str) -> str:
    """An op event's instruction name: TPU traces name an op by its whole
    HLO line (``%fusion.12 = f32[...] fusion(...)``)."""
    return name.split(" = ")[0].lstrip("%")


def reduce(form: dict, names: dict, top: int = 10) -> Reduction:
    """Busy time (union of op intervals per device, averaged over devices),
    kernel time by the name table (summed over devices), the ops that took
    most time, and the longest idle gaps named by what the host was doing."""
    lo, hi = form["window_ns"]
    window_s = (hi - lo) * 1e-9
    kernels = names["kernels"]
    busy, ksec, kcalls = [], defaultdict(float), defaultdict(int)
    ops = defaultdict(float)
    gaps_by_host = defaultdict(float)
    host = sorted(form["host"], key=lambda e: e[1])
    for _, events in sorted(form["devices"].items()):
        inside = [e for e in events if lo <= e[1] < hi]
        merged = _clip(_union([[e[1], e[1] + e[2]] for e in inside]), lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for e in inside:
            ops[_op_name(e[0])] += e[2] * 1e-9
            k = kernel_of(e, kernels)
            if k is not None:
                ksec[k] += e[2] * 1e-9
                kcalls[k] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps_by_host[_host_at(host, (s + e) / 2)] += (e - s) * 1e-9
    n = max(len(busy), 1)
    return Reduction(
        window_s=window_s, busy_s=sum(busy) / n, kernel_s=dict(ksec),
        kernel_calls=dict(kcalls),
        device_ops=[[k, v] for k, v in sorted(ops.items(),
                                              key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v / n] for k, v in sorted(gaps_by_host.items(),
                                                 key=lambda kv: -kv[1])[:top]])


def _host_at(host, t: float) -> str:
    """The innermost host event running at ``t`` (the latest to start among
    those that cover it), or ``host python`` where the thread ran code that
    leaves no event."""
    best = None
    for name, start, dur in host:
        if start > t:
            break
        if start + dur >= t:
            best = name
    return best if best is not None else "host python"
