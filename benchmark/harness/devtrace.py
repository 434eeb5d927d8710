"""Reduction of a ``jax.profiler`` trace to device busy time, idle share,
per-operation device time, and idle gaps named by what the host was doing.

Only the process that holds the chip can trace it, so the benchmark
traces its own window: ``Tracing`` starts the profiler, wraps the window
in a ``bench.window`` annotation, and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``.  On a TPU the device's operations are the
events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; the
host's are the events of the ``/host:CPU`` plane, where the benchmark's
``jax.profiler.TraceAnnotation`` regions (``bench.*``) land.
"""

from __future__ import annotations

import bisect
import glob
import os
import shutil

WINDOW = "bench.window"


MODULES = "XLA Modules"
NAMED_GAPS = 10


def tpu_op_lines(plane_name: str, line_name: str) -> bool:
    """The device's operations on a TPU."""
    return plane_name.startswith("/device:TPU:") and line_name == "XLA Ops"


def op_name(event_name: str, module: str | None) -> str:
    """``<program>/<instruction>``: the HLO instruction's name without
    its operands and shapes, under the jitted program it ran in."""
    op = event_name.split(" = ", 1)[0].lstrip("%")
    return op if module is None else f"{module.split('(', 1)[0]}/{op}"


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by ``intervals`` (start, end), overlaps once."""
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def gaps(intervals: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if b <= cur:
            continue
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def self_times(events: list[tuple[float, float, str]]) -> list[float]:
    """Each event's own time: its length less that of the events nested
    in it (a ``while`` op holds its body's ops on the same line), so the
    own times add up to the time covered, not more."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [b - a for a, b, _ in events]
    stack: list[int] = []
    for i in order:
        a, b, _ = events[i]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(b, events[parent][1]) - a
        stack.append(i)
    return own


class Reduced:
    """What one traced window says, times in seconds."""

    def __init__(self, window_s: float, busy_s: float,
                 op_seconds: dict[str, float], named_gaps: list):
        self.window_s = window_s
        self.busy_s = busy_s
        self.op_seconds = op_seconds
        self.named_gaps = named_gaps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(self.op_seconds.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list[list]:
        return [[name, s] for name, s in sorted(self.named_gaps,
                                                key=lambda g: -g[1])[:n]]


def reduce_trace(path: str, device_line=tpu_op_lines) -> Reduced:
    """Reduce the ``.xplane.pb`` at ``path``.  ``device_line(plane,
    line)`` picks the lines whose events are device operations; busy
    time is averaged over the devices found."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    per_device: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list[tuple[float, float, str, str]] = []
    window = None
    for plane in pd.planes:
        for line in plane.lines:
            if device_line(plane.name, line.name):
                per_device.setdefault(plane.name, []).append(
                    [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in line.events if e.duration_ns > 0])
            elif line.name == MODULES:
                modules[plane.name] = sorted(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    host.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name, line.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW} annotation")
    if not per_device:
        raise ValueError(f"{path}: no device operations found")
    lo, hi = window
    ops: dict[str, float] = {}
    busy = 0.0
    merged: list[tuple[float, float]] = []
    for plane, lines in per_device.items():
        mods = modules.get(plane, [])
        starts = [m[0] for m in mods]
        iv = []
        for evs in lines:
            clipped = [(max(a, lo), min(b, hi), n) for a, b, n in evs
                       if b > lo and a < hi]
            for (a, _, n), own in zip(clipped, self_times(clipped)):
                i = bisect.bisect_right(starts, a) - 1
                mod = mods[i][2] if i >= 0 and a < mods[i][1] else None
                k = op_name(n, mod)
                ops[k] = ops.get(k, 0.0) + own / 1e9
            iv.extend((a, b) for a, b, _ in clipped)
        busy += union_length(iv) / 1e9
        merged.extend(iv)
    busy /= len(per_device)
    # only the longest gaps are reported: naming one scans every host event
    longest = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])
    named = [(_host_name(host, (a + b) / 2), (b - a) / 1e9)
             for a, b in longest[:NAMED_GAPS]]
    return Reduced((hi - lo) / 1e9, busy,
                   {k: v / len(per_device) for k, v in ops.items()}, named)


def _host_name(host: list, t: float) -> str:
    """The innermost ``bench.*`` annotation covering ``t``, and within
    its thread the innermost other host event covering ``t``."""
    best = None
    for a, b, name, line in host:
        if a <= t < b and name.startswith("bench.") and name != WINDOW:
            if best is None or b - a < best[1] - best[0]:
                best = (a, b, name, line)
    if best is None:
        return "outside bench annotations"
    inner = None
    for a, b, name, line in host:
        if line == best[3] and a <= t < b and not name.startswith("bench.") \
                and best[0] <= a and b <= best[1]:
            if inner is None or b - a < inner[1] - inner[0]:
                inner = (a, b, name)
    return best[2] if inner is None else f"{best[2]} > {inner[2]}"


class Tracing:
    """The profiler around one window, writing under ``log_dir``, which
    it removes when the trace has been read."""

    def __init__(self, log_dir: str, device_line=tpu_op_lines):
        self.log_dir = log_dir
        self.device_line = device_line
        self.path: str | None = None

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # a Python tracer swamps the host
        shutil.rmtree(self.log_dir, ignore_errors=True)
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.log_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self.path = found[0] if found else None
        return False

    def reduce(self) -> Reduced:
        if self.path is None:
            raise RuntimeError("the profiler wrote no trace")
        try:
            return reduce_trace(self.path, self.device_line)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)
