"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran on that chip.  Host planes hold the
benchmark's own spans (``TraceAnnotation``s named ``bench.*``), all on
the same clock.  The traced window is the ``bench.trace_window`` span.

* busy: the union of the device-op intervals inside the window, per chip,
  averaged over the chips that ran anything;
* device ops: total device time per operation name;
* idle gaps: the stretches of the window in which no operation ran on a
  chip, each named by the innermost benchmark span open at its middle.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.trace_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Event:
    name: str         # a device op's name is its HLO instruction text
    start: float      # ns on the trace's clock
    end: float
    module: str = ""  # device ops: the jitted program that ran it

    @property
    def label(self) -> str:
        """``<program>:<instruction>`` for a device op, e.g.
        ``jit_train_step:%fusion.466``."""
        inst = self.name.split(" = ", 1)[0]
        return f"{self.module}:{inst}" if self.module else inst

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


def union_ns(intervals: Sequence[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if e <= at:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class TraceSummary:
    window: Tuple[float, float]
    device_ops: Dict[str, List[Event]]     # per device plane, in the window
    host_spans: List[Event]                # bench.* spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_by_chip(self) -> Dict[str, float]:
        lo, hi = self.window
        return {plane: union_ns([(e.start, e.end) for e in evs], lo, hi) / 1e9
                for plane, evs in self.device_ops.items() if evs}

    @property
    def busy_s(self) -> float:
        per = self.busy_by_chip()
        return sum(per.values()) / len(per) if per else 0.0

    def ops(self) -> List[Event]:
        return [e for evs in self.device_ops.values() for e in evs]

    def op_seconds(self) -> Dict[str, float]:
        tot: Dict[str, float] = defaultdict(float)
        lo, hi = self.window
        for e in self.ops():
            tot[e.label] += max(0.0, min(e.end, hi) - max(e.start, lo)) / 1e9
        return dict(tot)

    def span_at(self, t: float) -> str:
        """The innermost benchmark span open at ``t`` (the shortest)."""
        best: Optional[Event] = None
        for s in self.host_spans:
            if s.name != WINDOW_SPAN and s.start <= t <= s.end:
                if best is None or s.end - s.start < best.end - best.start:
                    best = s
        return best.name if best else "no benchmark span"

    def idle_gaps(self) -> List[Tuple[str, float]]:
        lo, hi = self.window
        out = []
        for plane, evs in self.device_ops.items():
            for s, e in gaps([(x.start, x.end) for x in evs], lo, hi):
                out.append((self.span_at((s + e) / 2), (e - s) / 1e9))
        out.sort(key=lambda x: -x[1])
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda x: -x[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:top]]}


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns)
        out.append(Event(e.name, start, start + float(e.duration_ns)))
    return out


def _name_modules(ops: List[Event], modules: List[Event]) -> None:
    """Give each op the name of the program (``XLA Modules`` event) that
    contains it, without the program's fingerprint."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    import bisect

    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and mods[i].end >= e.start:
            e.module = mods[i].name.split("(", 1)[0]


def from_profile(pd) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(_events(line))
                elif line.name == MODULES_LINE:
                    mods.extend(_events(line))
            _name_modules(evs, mods)
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith("bench."))
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    win = (windows[0].start, windows[0].end)
    inside = {p: [e for e in evs if e.end > win[0] and e.start < win[1]]
              for p, evs in device.items()}
    return TraceSummary(window=win, device_ops=inside, host_spans=host)


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(log_dir: Path) -> TraceSummary:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(str(find_xplane(log_dir))))
