"""Pieces every driver and metric reader shares: checks, spans, the
compile counter, the tracer, the device's peak memory."""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional


def _load_trace_module():
    """``trace.py`` beside this file (the standard library has a module of
    that name, so it is loaded by path)."""
    import importlib.util
    import sys

    if "chip_trace" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "chip_trace", Path(__file__).resolve().parent / "trace.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chip_trace"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["chip_trace"]


trace_mod = _load_trace_module()

_ns = time.perf_counter_ns


@dataclass
class Check:
    """One number compared with its limit; ``ok`` when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


class WindowClosed(Exception):
    """Raised by a benchmark feed once the window's deadline has passed."""


class Spans:
    """The benchmark's own host spans: kept in memory for the metrics and,
    while a trace runs, written into it as ``TraceAnnotation``s so that the
    trace can say what the host was doing in each idle gap."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.items: List[tuple] = []   # (name, t0_ns, t1_ns)

    def span(self, name: str):
        return _Span(self, name)

    def add(self, name: str, t0: int, t1: int) -> None:
        with self._lock:
            self.items.append((name, t0, t1))

    def longest_ns(self, name: str, t0: int = 0, t1: int = 2 ** 63) -> int:
        """The longest ``name`` span that ends inside ``[t0, t1]``."""
        return max((b - a for n, a, b in self.items
                    if n == name and t0 <= b <= t1), default=0)

    def total_ns(self, name: str, t0: int = 0, t1: int = 2 ** 63) -> int:
        """Time of ``name`` spans inside ``[t0, t1]``."""
        return sum(max(0, min(b, t1) - max(a, t0))
                   for n, a, b in self.items if n == name)


class _Span:
    def __init__(self, spans: Spans, name: str) -> None:
        self.spans, self.name = spans, name

    def __enter__(self):
        import jax

        self._ann = jax.profiler.TraceAnnotation(f"bench.{self.name}")
        self._ann.__enter__()
        self.t0 = _ns()
        return self

    def __exit__(self, *exc):
        t1 = _ns()
        self._ann.__exit__(*exc)
        self.spans.add(self.name, self.t0, t1)
        return False


class CompileCounter:
    """Counts programs that JAX lowers while it is entered (a persistent
    cache hit is still lowered first, so it counts too), and the seconds
    spent in the backend compiler."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    _installed = False
    _active: List["CompileCounter"] = []

    def __init__(self) -> None:
        self.count = 0
        self.backend_s = 0.0

    @classmethod
    def _listener(cls, event: str, duration: float, **kw) -> None:
        for c in cls._active:
            if event == cls.EVENT:
                c.count += 1
            elif event == cls.BACKEND:
                c.backend_s += duration

    def __enter__(self):
        import jax

        if not CompileCounter._installed:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._listener)
            CompileCounter._installed = True
        CompileCounter._active.append(self)
        return self

    def __exit__(self, *exc):
        CompileCounter._active.remove(self)
        return False


class Tracer:
    """Traces the last ``seconds`` of a window with the JAX profiler."""

    def __init__(self, log_dir: Path) -> None:
        self.log_dir = Path(log_dir)
        self.t0 = self.t1 = None
        self._ann = None
        self._summary = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        self._ann.__enter__()
        self.t0 = _ns()

    def stop(self) -> None:
        import jax

        if self._ann is None:
            return
        self.t1 = _ns()
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()

    def summary(self) -> "trace_mod.TraceSummary":
        if self._summary is None:
            self._summary = trace_mod.load(self.log_dir)
        return self._summary


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    spec: Dict[str, Any]
    window: Dict[str, Any]
    devices: list
    trace: Optional["trace_mod.TraceSummary"] = None

    @property
    def config(self) -> dict:
        return self.spec["config"]

    @property
    def traffic(self) -> dict:
        return self.spec["traffic"]

    @property
    def peaks(self):
        from peaks import peaks_for

        return peaks_for(self.devices[0].device_kind)


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    keeps no such statistic, as the CPU does)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def scratch_dir(root: Path, cell: str) -> Path:
    """Per-run scratch space on the checkout's file system (removed when
    the run ends)."""
    base = Path(root) / ".bench_scratch"
    base.mkdir(exist_ok=True)
    path = base / f"{cell}.{os.getpid()}"
    path.mkdir()
    return path


def brief(d: dict) -> str:
    """One line of a window's scalars, for the log."""
    return " ".join(f"{k}={v!r}" for k, v in d.items()
                    if isinstance(v, (int, float, str, bool)))
