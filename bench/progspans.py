"""The program's own spans in the window's profiler trace.

The served path records host spans and marks with
``repro.telemetry.spans`` (docs/TELEMETRY.md "Spans"): ``engine.query``
around each query, ``executor.bounds`` / ``embed`` / ``stage`` /
``interference`` / ``head`` inside it, ``rebalance.detect`` and
``rebalance.commit`` where a rebalancing phase starts and commits, and
``jax.compile`` at each backend compile.  They lie on the profiler's
clock, with the device's events.

``of(run)`` reads, from the ``.xplane.pb`` a ``--trace 1`` run leaves
under ``bench/.trace`` (the per-layer readers run before ``run.py``
deletes it), those spans with their metadata and the run intervals of
device 0's XLA modules.  It loads each file once, so the readers share
one load, and returns ``None`` where the trace holds no ``engine.query``
span: a program that records no spans reports none of their metrics.

``idle_split`` splits the device's idle time inside given host intervals
by what the program was doing.  All times are nanoseconds.
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from devtrace import DEVICE_PLANE, MODULES_LINE, find_xplane, union

TRACE_DIR = Path(__file__).resolve().parent / ".trace"
QUERY = "engine.query"
BOUNDS = "executor.bounds"
LAUNCH = ("executor.embed", "executor.stage", "executor.head")
INTERFERENCE = "executor.interference"
#: The executor's spans that wait for the device (their ``syncs``).
SYNCING = (BOUNDS,) + LAUNCH
NAMES = (QUERY, BOUNDS, INTERFERENCE, "rebalance.detect",
         "rebalance.commit", "jax.compile") + LAUNCH


@dataclasses.dataclass
class ProgramSpans:
    spans: Dict[str, np.ndarray]           # name -> [n, 2], by start
    meta: Dict[str, List[dict]]            # name -> metadata, same order
    modules: np.ndarray                    # device 0's module runs, merged

    @property
    def queries(self) -> int:
        return len(self.spans.get(QUERY, ()))

    def intervals(self, *names: str) -> np.ndarray:
        """All spans of ``names``, as ``[n, 2]``."""
        parts = [self.spans[n] for n in names if n in self.spans]
        return (np.concatenate(parts) if parts
                else np.zeros((0, 2), np.int64))

    def count(self, name: str, a: Optional[int] = None,
              b: Optional[int] = None) -> int:
        """Spans ``name`` that start inside ``[a, b]`` (all without)."""
        s = self.intervals(name)[:, 0]
        if a is not None:
            s = s[(s >= a) & (s <= b)]
        return len(s)

    def total(self, name: str, key: str) -> int:
        """Sum of metadata ``key`` over the spans ``name``."""
        return sum(int(m.get(key, 0)) for m in self.meta.get(name, ()))

    def duration_ns(self, name: str) -> int:
        iv = self.intervals(name)
        return int(np.sum(iv[:, 1] - iv[:, 0]))


def load(path: str) -> ProgramSpans:
    from jax.profiler import ProfileData

    found: Dict[str, list] = {n: [] for n in NAMES}
    modules = None
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            runs = [(ev.start_ns, ev.end_ns) for line in plane.lines
                    if line.name == MODULES_LINE for ev in line.events]
            if runs and modules is None:
                modules = union(runs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in found:
                        found[ev.name].append(
                            (ev.start_ns, ev.end_ns, dict(ev.stats)))
    spans, meta = {}, {}
    for name, evs in found.items():
        evs.sort(key=lambda e: e[0])
        spans[name] = np.asarray([e[:2] for e in evs],
                                 np.int64).reshape(-1, 2)
        meta[name] = [e[2] for e in evs]
    return ProgramSpans(spans=spans, meta=meta,
                        modules=(modules if modules is not None
                                 else np.zeros((0, 2), np.int64)))


_LOADED: Dict[tuple, ProgramSpans] = {}


def of(run) -> Optional[ProgramSpans]:
    """The program's spans in ``run``'s traced window, or ``None``."""
    if run.profile is None:
        return None
    try:
        path = find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path)
    ps = _LOADED[key]
    return ps if ps.queries else None


def _segments(a: np.ndarray, b: np.ndarray):
    """The segments between the edges of the merged interval sets ``a``
    and ``b``: ``(start, end, in a, in b)``, in one vectorised sweep."""
    pts = np.concatenate([a[:, 0], a[:, 1], b[:, 0], b[:, 1]])
    na, nb = len(a), len(b)
    da = np.concatenate([np.ones(na), -np.ones(na), np.zeros(2 * nb)])
    db = np.concatenate([np.zeros(2 * na), np.ones(nb), -np.ones(nb)])
    order = np.argsort(pts, kind="stable")
    pts = pts[order]
    # Depth after every edge; between two equal edges the segment is
    # empty, so the depth after the last edge of a point holds.
    ina = np.cumsum(da[order])[:-1] > 0
    inb = np.cumsum(db[order])[:-1] > 0
    return pts[:-1], pts[1:], ina, inb


def _take(rest: np.ndarray, cover: np.ndarray):
    """``(ns of the merged intervals rest inside the merged cover, the
    intervals of rest outside it)``."""
    lo, hi, inr, inc = _segments(rest, cover)
    took = int(np.sum((hi - lo)[inr & inc]))
    left = (hi > lo) & inr & ~inc
    return took, union(np.stack([lo[left], hi[left]], axis=1))


def idle_split(ps: ProgramSpans, busy: np.ndarray,
               within: Sequence) -> Dict[str, int]:
    """Device-idle ns inside the host intervals ``within`` (``busy``:
    the merged intervals in which an operation ran), split five ways:
    ``module gaps`` (inside an XLA module run, between its operations),
    then, outside every module run, inside ``executor.bounds``, inside
    ``launch`` (``executor.embed``/``stage``/``head``: the host's
    dispatch and sync round trips), inside ``executor.interference``,
    and ``outside program spans``."""
    _, rest = _take(union(within), busy)
    out = {}
    out["module gaps"], rest = _take(rest, ps.modules)
    for label, names in (("executor.bounds", (BOUNDS,)),
                         ("launch", LAUNCH),
                         ("executor.interference", (INTERFERENCE,))):
        out[label], rest = _take(rest, union(ps.intervals(*names)))
    out["outside program spans"] = int(np.sum(rest[:, 1] - rest[:, 0]))
    return out
