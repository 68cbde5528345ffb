"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

The trace is read with ``jax.profiler.ProfileData``. A device plane is
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per
program run and its ``XLA Ops`` line one event per operation. Host
planes hold the benchmark's own spans, named ``bench.*``
(``jax.profiler.TraceAnnotation``). The traced window is the span
``bench.window``; everything is clipped to it.

On a TPU an operation's event is named by its HLO instruction
(``%fusion.12 = bf16[...] fusion(...)``); a loop or a call is an event
too, spanning the operations inside it, and is left out so that no time
counts twice. A module event is named by its program and a fingerprint
(``jit_fn(6636870049680814235)``), the same for every run of one
compiled program.

From these: the device's busy intervals (the union of its operations);
which program each module is (see ``match_runs``); the operations of
each run; and the device's idle time attributed to the host span that
was open while the device waited.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
CALL_SPAN = re.compile(r"^bench\.(\w+)_call$")
CONTAINER = re.compile(r"[)}\]]\s+(while|conditional|call)\(")


@dataclasses.dataclass
class Event:
    name: str
    start: float   # seconds, on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Run:
    """One program run on the device, and the kind of its program."""

    kind: str            # "prefill" | "decode"
    module: Event        # the device's module event
    ops: List[Event]     # device operations inside the module event


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]
    busy: List[Tuple[float, float]]       # merged, inside the window
    runs: List[Run]
    host: List[Event]                     # bench.* spans inside the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[Event]:
    return [Event(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
            for ev in line.events]


def merge(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host: List[Event] = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(tuple(_events(lines[n]) if n in lines else []
                                 for n in (MODULES_LINE, OPS_LINE)))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e.name.startswith("bench.")]
    if not devices:
        raise ValueError(f"{path}: no device plane (/device:TPU:n)")
    wins = [e for e in host if e.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"{path}: expected one {WINDOW_SPAN} span, "
                         f"found {len(wins)}")
    lo, hi = wins[0].start, wins[0].end
    host = sorted((e for e in host if e.end > lo and e.start < hi
                   and e.name != WINDOW_SPAN), key=lambda e: e.start)
    # one chip: the first device plane holds the runs
    modules, ops = devices[0]
    modules = sorted((m for m in modules if m.end > lo and m.start < hi),
                     key=lambda e: e.start)
    ops = sorted((o for o in ops if o.end > lo and o.start < hi
                  and not CONTAINER.search(o.name[:4096])),
                 key=lambda e: e.start)
    busy = merge(_clip([(o.start, o.end) for o in ops] or
                       [(m.start, m.end) for m in modules], lo, hi))
    calls = [e for e in host if CALL_SPAN.match(e.name)]
    steps = [e for e in host if e.name == "bench.step"]
    return Reduced((lo, hi), busy, match_runs(calls, modules, ops, steps),
                   host)


def match_runs(calls: List[Event], modules: List[Event], ops: List[Event],
               steps: List[Event]) -> List[Run]:
    """Give each module event its program's kind, and its operations.

    The host's and the device's clocks in a trace differ by about a
    millisecond, so a run is not matched to its call by start times.
    An engine step waits for each call's logits, so the runs of one
    step lie inside its ``bench.step`` span, in the order of the step's
    calls; the k-th run of a step is its k-th call's. A program's module
    name then takes the kind its runs were matched to most often."""
    votes: Dict[str, Dict[str, int]] = {}
    for st in steps:
        mine = [c for c in calls if st.start <= c.start < st.end]
        ran = [m for m in modules
               if st.start <= 0.5 * (m.start + m.end) < st.end]
        if len(mine) != len(ran):
            continue
        for c, m in zip(mine, ran):
            tally = votes.setdefault(m.name, {})
            kind = CALL_SPAN.match(c.name).group(1)
            tally[kind] = tally.get(kind, 0) + 1
    kind_of = {name: max(t, key=t.get) for name, t in votes.items()}
    runs = [Run(kind_of[m.name], m, []) for m in modules if m.name in kind_of]
    k = 0
    for run in runs:
        while k < len(ops) and ops[k].start < run.module.start:
            k += 1
        while k < len(ops) and ops[k].start < run.module.end:
            run.ops.append(ops[k])
            k += 1
    return runs


def idle_by_span(red: Reduced) -> Dict[str, float]:
    """Seconds of device idle time in the window, by the innermost
    ``bench.*`` host span open at each idle moment ("none" where no
    span was open)."""
    gaps = []
    t = red.window[0]
    for a, b in red.busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < red.window[1]:
        gaps.append((t, red.window[1]))
    # sweep the span and gap boundaries; each piece of a gap goes to the
    # shortest span open over it
    marks = []
    for i, e in enumerate(red.host):
        marks += [(e.start, 1, i), (e.end, -1, i)]
    for a, b in gaps:
        marks += [(a, 2, -1), (b, -2, -1)]
    marks.sort()
    out: Dict[str, float] = {}
    open_spans: set = set()
    in_gap, t = 0, None
    for x, kind, i in marks:
        if in_gap and t is not None and x > t:
            name = (min((red.host[j] for j in open_spans),
                        key=lambda e: e.dur).name if open_spans else "none")
            out[name] = out.get(name, 0.0) + (x - t)
        t = x
        if kind == 1:
            open_spans.add(i)
        elif kind == -1:
            open_spans.discard(i)
        else:
            in_gap += 1 if kind == 2 else -1
    return out


def busy_within(red: Reduced, lo: float, hi: float) -> float:
    return sum(b - a for a, b in _clip(red.busy, lo, hi))


def kernel_seconds(run: Run, is_kernel) -> Optional[float]:
    """Device seconds of the run's operations that ``is_kernel`` picks;
    None when it picks none."""
    picked = [o.dur for o in run.ops if is_kernel(o)]
    return sum(picked) if picked else None
