"""Profiler traces: record a window, reduce it to intervals, read numbers.

A traced run records one profiler trace around the measured window, with
host spans of its own (``bench:<label>``) wrapped around the program's
functions that the cell's metrics name (see ``SPANS`` in a metric file).
``Trace`` keeps what the metrics read, in nanoseconds on the profiler's
clock: the window span, each device's op and program-execution
intervals, and the host events of the thread that ran the window.  It
round-trips through JSON, so the reduction can be checked on a small
recorded trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import glob
import importlib
import os
import re
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench:window"
SPAN_PREFIX = "bench:"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[str, int, int]   # (name, start_ns, end_ns)


# --------------------------------------------------------------------------- #
# Spans around the program's functions
# --------------------------------------------------------------------------- #


def _resolve(target: str):
    module_name, _, path = target.partition(":")
    owner = importlib.import_module(module_name)
    parts = path.split(".")
    for part in parts[:-1]:
        owner = getattr(owner, part)
    return owner, parts[-1]


@contextlib.contextmanager
def spans_installed(targets: Dict[str, str]):
    """Wrap each ``"module:attr.path"`` in ``targets`` with a host span
    ``bench:<label>`` for the duration of the block.  A target the program
    no longer has fails the run: the metric that reads its span would
    otherwise read nothing."""
    import jax

    from harness import SetupError

    undo = []
    try:
        for target, label in targets.items():
            try:
                owner, attr = _resolve(target)
                original = owner.__dict__[attr] if isinstance(owner, type) \
                    else getattr(owner, attr)
            except (ImportError, AttributeError, KeyError) as exc:
                raise SetupError(f"span {label}: cannot wrap {target} "
                                 f"({exc!r})") from exc

            def wrapper(*args, __f=original, __name=SPAN_PREFIX + label,
                        **kwargs):
                with jax.profiler.TraceAnnotation(__name):
                    return __f(*args, **kwargs)

            functools.update_wrapper(wrapper, original)
            setattr(owner, attr, wrapper)
            undo.append((owner, attr, original))
        yield
    finally:
        for owner, attr, original in reversed(undo):
            setattr(owner, attr, original)


# --------------------------------------------------------------------------- #
# Recording
# --------------------------------------------------------------------------- #


class Recording:
    """Profiler trace of the enclosed block, written to a temporary
    directory and read back into a ``Trace`` on exit."""

    def __enter__(self):
        import jax

        self._dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # a Python tracer swamps host loops
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir.name, profiler_options=opts)
        self.trace = None
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                files = glob.glob(os.path.join(self._dir.name, "**",
                                               "*.xplane.pb"), recursive=True)
                if not files:
                    raise RuntimeError("the profiler wrote no trace")
                self.trace_bytes = os.path.getsize(files[0])
                self.trace = Trace.from_xplane(files[0])
        finally:
            self._dir.cleanup()
        return False


# --------------------------------------------------------------------------- #
# The reduced trace
# --------------------------------------------------------------------------- #


def union_length(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(name, start, end)`` intervals in [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
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


def gaps(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if s > t and t < hi:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    devices: Dict[str, Dict[str, List[Interval]]]   # id -> ops / modules
    host: List[Interval]                             # the window's thread

    # ------------------------------------------------------------ loading

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        devices: Dict[str, Dict[str, List[Interval]]] = {}
        host_lines: List[List[Interval]] = []
        for plane in data.planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                lines = {line.name: [(e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns))
                                     for e in line.events]
                         for line in plane.lines}
                devices[m.group(1)] = {
                    "ops": lines.get("XLA Ops", []),
                    "modules": lines.get("XLA Modules", []),
                }
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host_lines.append([(e.name, int(e.start_ns),
                                        int(e.start_ns + e.duration_ns))
                                       for e in line.events])
        main = [line for line in host_lines
                if any(name == WINDOW for name, _, _ in line)]
        if not main:
            raise RuntimeError(f"the trace has no {WINDOW} span")
        host = main[0]
        _, lo, hi = next(x for x in host if x[0] == WINDOW)
        return cls((lo, hi), devices, host)

    def to_json(self) -> dict:
        return {"window": list(self.window), "devices": self.devices,
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def ivs(xs):
            return [(str(n), int(s), int(e)) for n, s, e in xs]

        return cls(tuple(d["window"]),
                   {k: {kind: ivs(v) for kind, v in dev.items()}
                    for k, dev in d["devices"].items()},
                   ivs(d["host"]))

    # ------------------------------------------------------------ numbers

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> Optional[float]:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.devices:
            return None
        lo, hi = self.window
        busy = [union_length(d["ops"] or d["modules"], lo, hi)
                for d in self.devices.values()]
        return sum(busy) / len(busy) / 1e9

    def op_seconds(self, match) -> Optional[float]:
        """Seconds of device ops whose name ``match``es, summed over the
        window and averaged over the devices; None where none ran."""
        lo, hi = self.window
        per_device, found = [], False
        for d in self.devices.values():
            t = 0
            for name, s, e in d["ops"]:
                if match(name):
                    found = True
                    t += max(0, min(e, hi) - max(s, lo))
            per_device.append(t)
        if not found:
            return None
        return sum(per_device) / len(per_device) / 1e9

    def modules_started(self) -> Optional[float]:
        """Program executions started in the window, averaged over the
        devices."""
        if not self.devices:
            return None
        lo, hi = self.window
        counts = [sum(lo <= s < hi for _, s, _ in d["modules"])
                  for d in self.devices.values()]
        return sum(counts) / len(counts)

    def span_seconds(self, labels) -> Optional[float]:
        """Seconds covered by the host spans ``bench:<label>``."""
        names = {SPAN_PREFIX + label for label in labels}
        spans = [x for x in self.host if x[0] in names]
        if not spans:
            return None
        return union_length(spans, *self.window) / 1e9

    def _labels(self, times: List[int]) -> List[str]:
        """The innermost host event of the window's thread around each of
        the ascending ``times``.  Events of one thread nest, so a stack
        sweep finds them."""
        events = sorted((x for x in self.host if x[0] != WINDOW),
                        key=lambda x: (x[1], -x[2]))
        stack: List[Interval] = []
        out, k = [], 0
        for t in times:
            while k < len(events) and events[k][1] <= t:
                while stack and stack[-1][2] <= events[k][1]:
                    stack.pop()
                stack.append(events[k])
                k += 1
            while stack and stack[-1][2] <= t:
                stack.pop()
            out.append(stack[-1][0] if stack else "no host event")
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the first device's idle
        time by what the host was doing: inside each span of ours, and
        elsewhere by the innermost host event at the middle of each
        stretch."""
        lo, hi = self.window
        per_op: Dict[str, float] = defaultdict(float)
        for d in self.devices.values():
            for name, s, e in d["ops"]:
                per_op[op_label(name)] += max(0, min(e, hi) - max(s, lo)) / 1e9
        n = max(len(self.devices), 1)
        ops = sorted(((k, v / n) for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:top]
        idle: Dict[str, float] = defaultdict(float)
        if not self.devices:
            idle["no device trace"] = self.window_s
        else:
            first = self.devices[sorted(self.devices)[0]]
            idle_ivs = [("idle", s, e) for s, e in
                        gaps(first["ops"] or first["modules"], lo, hi)]
            ours = [x for x in self.host
                    if x[0].startswith(SPAN_PREFIX) and x[0] != WINDOW]
            for label in sorted({x[0] for x in ours}):
                spans = [x for x in ours if x[0] == label]
                idle[label] += overlap(idle_ivs, spans, lo, hi) / 1e9
            rest = [(s, e) for _, s0, e0 in idle_ivs
                    for s, e in gaps(ours, s0, e0)]
            labels = self._labels([(s + e) // 2 for s, e in rest])
            for (s, e), label in zip(rest, labels):
                idle[label] += (e - s) / 1e9
        gap_list = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gap_list]}


_HLO_OP = re.compile(r"^(%[\w.-]+) = (.*?) ([\w-]+)\(")


def op_label(name: str) -> str:
    """A device op's HLO text shortened to its name, output shape and kind,
    e.g. ``%local_stats.1 f32[8,128,65536] custom-call``."""
    m = _HLO_OP.match(name)
    if not m:
        return name[:120]
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {shape} {m.group(3)}"


def overlap(a, b, lo: int, hi: int) -> int:
    """Length of (union of ``a``) intersected with (union of ``b``)."""
    return (union_length(a, lo, hi) + union_length(b, lo, hi)
            - union_length(list(a) + list(b), lo, hi))
