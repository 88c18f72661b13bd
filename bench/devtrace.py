"""Reduction of a profiler trace to what the per-layer metrics read.

``load(path, span_names)`` reads one ``.xplane.pb`` with
``jax.profiler.ProfileData`` (JAX alone, no TensorBoard) and keeps:

* per device plane that ran anything, the union of the intervals in
  which an XLA operation ran (the ``XLA Ops`` line): the device is busy
  inside it and idle outside;
* device self time per operation (by its HLO name, ``%fusion.3``): an
  event's time less that of the events nested in it, so that a
  ``while`` loop is not counted again for its body; and device time
  per XLA module (``XLA Modules`` line, e.g. ``jit_stage_fn(12)``);
* the host spans named in ``span_names`` (``TraceAnnotation``s), which
  the profiler records on the same clock as the device events.

All times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals) -> np.ndarray:
    """Merge ``[n, 2]`` (start, end) intervals into sorted disjoint
    ones."""
    iv = np.asarray(intervals, dtype=np.int64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # A new run starts where an interval begins after every earlier end.
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = np.maximum.reduceat(iv[:, 1], idx)
    return np.stack([starts, stops], axis=1)


def covered(merged: np.ndarray, a: int, b: int) -> int:
    """Nanoseconds of the disjoint ``merged`` intervals inside
    ``[a, b]``."""
    if b <= a or not len(merged):
        return 0
    lo = np.clip(merged[:, 0], a, b)
    hi = np.clip(merged[:, 1], a, b)
    return int(np.sum(hi - lo))


def gaps(merged: np.ndarray, a: int, b: int) -> np.ndarray:
    """The idle intervals inside ``[a, b]``: its complement of
    ``merged``, as ``[n, 2]``."""
    inside = merged[(merged[:, 1] > a) & (merged[:, 0] < b)] \
        if len(merged) else merged
    edges = [a]
    for s, e in inside:
        edges += [max(int(s), a), min(int(e), b)]
    edges.append(b)
    g = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


def self_times(events):
    """``(name, self ns)`` of ``(start, end, name)`` events that nest
    (a parent's interval holds its children's): each event's duration
    less the durations of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e, _ in events]
    stack = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] < e:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][2], own[i]) for i in range(len(events))]


@dataclasses.dataclass
class Profile:
    busy: List[np.ndarray]                 # per device that ran anything
    op_ns: Dict[str, int]                  # device time per op name
    module_ns: Dict[str, int]              # device time per XLA module
    spans: Dict[str, np.ndarray]           # host spans by name, [n, 2]

    def busy_ns(self, a: int, b: int) -> float:
        """Busy nanoseconds in ``[a, b]``, averaged over the devices."""
        if not self.busy:
            return 0.0
        return float(np.mean([covered(m, a, b) for m in self.busy]))

    def module_time_ns(self, prefix: str) -> int:
        """Device time of the modules whose name starts with
        ``prefix`` (``jit_stage_fn`` matches ``jit_stage_fn(12)``)."""
        return sum(v for k, v in self.module_ns.items()
                   if k.startswith(prefix))

    def window(self, span: str):
        """``(first start, last end)`` of the host spans ``span``."""
        s = self.spans.get(span)
        if s is None or not len(s):
            return None
        return int(s[:, 0].min()), int(s[:, 1].max())


def find_xplane(directory: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under
    ``directory``."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def load(path: str, span_names: Sequence[str]) -> Profile:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    busy, op_ns, module_ns = [], {}, {}
    spans: Dict[str, list] = {n: [] for n in span_names}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            iv = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = [(ev.start_ns, ev.end_ns, ev.name)
                           for ev in line.events]
                    iv.extend((s, e) for s, e, _ in evs)
                    for name, ns in self_times(evs):
                        # "%name = type op(args)": keep the name.
                        name = name.split(" = ", 1)[0]
                        op_ns[name] = op_ns.get(name, 0) + ns
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        module_ns[ev.name] = (module_ns.get(ev.name, 0)
                                              + ev.end_ns - ev.start_ns)
            if iv:
                busy.append(union(iv))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
    return Profile(busy=busy, op_ns=op_ns, module_ns=module_ns,
                   spans={k: np.asarray(v, dtype=np.int64).reshape(-1, 2)
                          for k, v in spans.items()})


def idle_by_span(profile: Profile, a: int, b: int,
                 labels: Sequence[str]) -> Dict[str, float]:
    """Idle nanoseconds of device 0 inside ``[a, b]``, split by what
    the host was doing: the first of ``labels`` whose span covers the
    idle time, else ``"outside spans"``.  ``labels`` go innermost
    first."""
    out: Dict[str, float] = {}
    if not profile.busy:
        return out
    rest = gaps(profile.busy[0], a, b)
    for label in labels:
        spans = union(profile.spans.get(label, np.zeros((0, 2))))
        left = []
        for s, e in rest:
            c = covered(spans, int(s), int(e))
            if c:
                out[label] = out.get(label, 0.0) + c
            left.extend(gaps(spans, int(s), int(e)).tolist())
        rest = np.asarray(left, dtype=np.int64).reshape(-1, 2)
    if len(rest):
        out["outside spans"] = float(np.sum(rest[:, 1] - rest[:, 0]))
    return out
