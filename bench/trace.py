"""From the profiler's trace to the numbers the per-layer metrics read.

A ``--trace 1`` run records its measured window with ``jax.profiler``.
:func:`load` reads the ``.xplane.pb`` into flat :class:`Event` records
(also what the recorded test fixture holds), and :class:`Summary`
reduces them:

- the window is the host span ``bench.window``;
- the device's work is the ``XLA Ops`` line of each ``/device:`` plane
  (on a CPU, which has no device plane, the host events that carry an
  ``hlo_op``); busy time is the union of those intervals inside the
  window, averaged over the devices;
- each operation belongs to the program (``XLA Modules`` event) that
  covers its start; a Pallas kernel is a ``tpu_custom_call`` (or, on a
  CPU trace, an op whose metadata names ``pallas_call``), matched to its
  jitted entry by :func:`pallas_kernel`; a kernel's time is the sum of
  its events' durations;
- ``breakdown()``: the device operations that took most time, and the
  longest idle gaps of the first device, each labelled by the host spans
  of the window's thread that cover it.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
from typing import Callable, List, NamedTuple, Optional

#: the host span the harness puts around the measured window
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: int        # ns
    end: int          # ns
    meta: str         # the event's string stats, space-joined


def load(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                meta = " ".join(f"{k}={v}" for k, v in e.stats
                                if isinstance(v, str))
                start = int(e.start_ns)
                out.append(Event(plane.name, line.name, e.name, start,
                                 start + int(e.duration_ns), meta))
    return out


def dump(events: List[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([list(e) for e in events], f)


def read(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(*e) for e in json.load(f)]


def instruction(e: Event) -> str:
    """An XLA op's short name: the instruction before its HLO text."""
    return e.name.split(" = ")[0].lstrip("%")


def pallas_kernel(entry: str) -> Callable[[Event, str], bool]:
    """Matches the Pallas kernel launched under the jitted entry
    ``entry`` (e.g. ``_ca_run_impl``): a ``tpu_custom_call`` named after
    the entry (a nested jit) or inside the entry's own program."""
    def match(e: Event, module: str) -> bool:
        text = e.name + " " + e.meta
        if "tpu_custom_call" not in text and "pallas_call" not in text:
            return False
        return instruction(e).startswith(entry) \
            or module.startswith(f"jit_{entry}(") or entry in e.meta
    return match


def label(e: Event, module: str) -> str:
    """A device op as the breakdown names it: program / instruction, and
    the custom call's target."""
    m = re.search(r'custom_call_target="([^"]+)"', e.name)
    return f"{module.split('(')[0]}/{instruction(e)}" + \
        (f" {m.group(1)}" if m else "")


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


class Summary:
    def __init__(self, events: List[Event]):
        spans = [e for e in events if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        win = spans[0]
        self.t0, self.t1 = win.start, win.end
        self.thread = (win.plane, win.line)
        self.host = [e for e in events if (e.plane, e.line) == self.thread
                     and e.start < self.t1 and e.end > self.t0
                     and e is not win]
        ops = collections.defaultdict(list)
        modules = collections.defaultdict(list)
        for e in events:
            if e.plane.startswith("/device:") and e.line == OPS_LINE:
                ops[e.plane].append(e)
            if e.plane.startswith("/device:") and e.line == MODULES_LINE:
                modules[e.plane].append(e)
        if not ops:              # a CPU: its ops run on host threads
            for e in events:
                if "hlo_op=" in e.meta and not e.plane.startswith("/device:"):
                    ops["cpu"].append(e)
        self.ops = {p: [e for e in evs if e.start < self.t1
                        and e.end > self.t0] for p, evs in ops.items()}
        self.busy = {p: _union((max(e.start, self.t0), min(e.end, self.t1))
                               for e in evs) for p, evs in self.ops.items()}
        self.module = {}
        for p, evs in self.ops.items():
            mods = sorted(modules[p], key=lambda m: m.start)
            starts = [m.start for m in mods]
            for e in evs:
                i = bisect.bisect_right(starts, e.start) - 1
                self.module[e] = mods[i].name if i >= 0 and \
                    mods[i].end > e.start else ""

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _mean(self, per_device) -> float:
        vals = [per_device(p) for p in sorted(self.busy)]
        return sum(vals) / len(vals) if vals else 0.0

    @property
    def busy_s(self) -> float:
        return self._mean(lambda p: sum(b - a for a, b in self.busy[p])
                          * 1e-9)

    def busy_between(self, start: int, end: int) -> float:
        """Device-busy seconds inside [start, end], mean over devices."""
        return self._mean(lambda p: sum(
            max(0, min(b, end) - max(a, start)) for a, b in self.busy[p])
            * 1e-9)

    def kernel_seconds(self, match) -> float:
        """Summed duration of the device events ``match(event, module)``
        accepts, inside the window, mean over devices; 0 where none
        matches."""
        return self._mean(lambda p: sum(
            min(e.end, self.t1) - max(e.start, self.t0)
            for e in self.ops[p] if match(e, self.module[e])) * 1e-9)

    def spans(self, name: str):
        """(start, end) ns of the window thread's host spans ``name``."""
        return [(e.start, e.end) for e in self.host if e.name == name]

    def _label(self, a: int, b: int) -> str:
        mid = (a + b) // 2
        cover = sorted((e for e in self.host if e.start <= mid < e.end),
                       key=lambda e: (e.start, -e.end))
        return " > ".join(e.name for e in cover[-2:]) or "(no host span)"

    def idle_gaps(self, device: Optional[str] = None):
        """[(start, end)] ns of the idle stretches of ``device`` (default:
        the first) inside the window."""
        if not self.busy:
            return []
        busy = self.busy[device or sorted(self.busy)[0]]
        edges = [self.t0] + [x for ab in busy for x in ab] + [self.t1]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def breakdown(self, k: int = 10) -> dict:
        per_op = collections.Counter()
        first = sorted(self.busy)[0] if self.busy else None
        for e in self.ops.get(first, []):
            per_op[label(e, self.module[e])] += \
                min(e.end, self.t1) - max(e.start, self.t0)
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:k]
        return {"device_ops": [[n, t * 1e-9]
                               for n, t in per_op.most_common(k)],
                "idle_gaps": [[self._label(a, b), (b - a) * 1e-9]
                              for a, b in gaps]}

