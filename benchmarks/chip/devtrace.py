"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` reduces the profiler's ``.xplane.pb`` to plain events: the
op events of each TPU core (the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane, named by their HLO instruction; a loop's event spans its body's ops)
and the harness's own host spans (``window``, ``data``, ``dispatch``,
``wait``).  The reductions below work on that form, which is also what
``testdata/`` keeps of a trace recorded on the chip.  Times are nanoseconds
on the trace's one clock.
"""
from __future__ import annotations

import gzip
import json
from typing import Dict, List, Tuple

HOST_SPANS = ("window", "data", "dispatch", "wait")
OP_LINE = "XLA Ops"

Event = Tuple[str, float, float]  # name, start_ns, end_ns


def load_xplane(path: str) -> dict:
    import jax

    from hlo import instruction_name
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[plane.name] = [
                        (instruction_name(e.name), e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name in HOST_SPANS]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def load(path: str) -> dict:
    """Events kept as gzipped JSON (the form of ``testdata/``)."""
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    ev["devices"] = {k: [tuple(e) for e in v]
                     for k, v in ev["devices"].items()}
    ev["host"] = [tuple(e) for e in ev["host"]]
    return ev


def window(events: dict) -> Tuple[float, float]:
    spans = [e for e in events["host"] if e[0] == "window"]
    if len(spans) != 1:
        raise ValueError(f"expected one 'window' span, found {len(spans)}")
    return spans[0][1], spans[0][2]


def _clip(evs: List[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def union(evs: List[Event]) -> List[Tuple[float, float]]:
    """Disjoint busy intervals covered by the events."""
    out: List[List[float]] = []
    for _, s, e in sorted(evs, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: dict) -> Dict[str, float]:
    """Per device: the length of the union of its op intervals, in the
    window."""
    lo, hi = window(events)
    return {d: sum(e - s for s, e in union(_clip(evs, lo, hi)))
            for d, evs in events["devices"].items()}


def class_ns(events: dict, classes: Dict[str, str], cls: str
             ) -> Dict[str, float]:
    """Per device: the time in the window of ops of one class (``hlo.py``),
    counted as the union of their intervals, so nested events count once."""
    lo, hi = window(events)
    return {d: sum(e - s for s, e in union(
        [x for x in _clip(evs, lo, hi) if classes.get(x[0]) == cls]))
        for d, evs in events["devices"].items()}


def top_ops(events: dict, classes: Dict[str, str], n: int = 10) -> List[List]:
    """The ops that took most device time in the window, mean over devices,
    in seconds, each named ``<instruction> (<class>)``.  Loops and calls are
    left out: their time is their body's ops'."""
    lo, hi = window(events)
    tot: Dict[str, float] = {}
    for evs in events["devices"].values():
        for name, s, e in _clip(evs, lo, hi):
            if classes.get(name) != "control":
                tot[name] = tot.get(name, 0.0) + (e - s)
    k = max(len(events["devices"]), 1)
    ranked = sorted(tot.items(), key=lambda x: -x[1])[:n]
    return [[f"{name} ({classes.get(name, 'unknown')})", t / k / 1e9]
            for name, t in ranked]


def idle_gaps(events: dict, n: int = 10) -> List[List]:
    """The longest idle gaps in the window on the first device, each named
    by the host span (``data``, ``dispatch``, ``wait``) that overlaps it
    most, or ``host`` where none does; seconds."""
    lo, hi = window(events)
    if not events["devices"]:
        return []
    dev = sorted(events["devices"])[0]
    busy = union(_clip(events["devices"][dev], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [e for e in events["host"] if e[0] != "window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, label = 0.0, "host"
        for name, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, name
        out.append([label, (e - s) / 1e9])
    return out
