"""Profiler traces: take one over a part of the window, and reduce the
``.xplane.pb`` it writes to device busy time, kernel time by name, the top
device operations and the idle gaps with what the host was doing in them.

The traced window is marked by a host span, ``bench.window``, opened right
after the profiler starts and closed right before it stops; busy time is the
union of the device's operation intervals inside it.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import shutil
import time

WINDOW = "bench.window"
# idle gaps attributed one by one to a host span, longest first
ATTRIBUTED = 200


class Tracer:
    """Starts the profiler ``start_s`` into the window and stops it
    ``length_s`` later, from the serving loop's own clock calls (the loop
    reads its clock on every turn; it does not always sleep)."""

    def __init__(self, path: str, start_s: float, length_s: float):
        self.path, self.start_s, self.length_s = path, start_s, length_s
        self.t_start = self.t_stop = None
        self._span = None

    def clock(self, w0: float):
        def now() -> float:
            t = time.perf_counter()
            if self.t_start is None and t - w0 >= self.start_s:
                self._start()
            elif self.t_stop is None and self.t_start is not None and \
                    t - self.t_start >= self.length_s:
                self.stop()
            return t

        return now

    def _start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.path, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW)
        self._span.__enter__()
        self.t_start = time.perf_counter()

    def stop(self):
        """Stop (at the latest when the window ends)."""
        import jax

        if self.t_start is None:
            self._start()
        if self.t_stop is None:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.t_stop = time.perf_counter()

    def reduce(self, w0: float, keep: str | None = None) -> dict:
        """Reduce the trace (then delete it, unless ``keep`` names a file to
        copy it to).  Adds the traced window's edges in seconds from ``w0``."""
        files = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.path}")
        out = reduce_file(files[0])
        out["from_s"] = self.t_start - w0
        out["to_s"] = self.t_stop - w0
        if keep:
            shutil.copy(files[0], keep)
        shutil.rmtree(self.path, ignore_errors=True)
        return out


def _device_ops(pd):
    """The ``XLA Ops`` line of each accelerator plane (``/device:TPU:0``, ...;
    planes without one, such as ``/device:CUSTOM:*``, are not chips)."""
    return [ln for p in pd.planes if p.name.startswith("/device:")
            for ln in p.lines if ln.name == "XLA Ops"]


def op_name(event_name: str) -> str:
    """An operation's name without its HLO text and instance number:
    ``%circ_elem_conv.48 = f32[32,256] custom-call(...)`` -> ``circ_elem_conv``."""
    name = event_name.split(" ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_file(path: str) -> dict:
    """``busy_s`` and ``window_s`` (averaged over the devices traced),
    ``ops_s`` (device seconds by operation name, see ``op_name``),
    ``top_ops`` and ``idle_gaps`` (``[name, seconds]``, longest first; a gap
    is named by the host span that overlaps it most)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    marks = [(e.start_ns, e.start_ns + e.duration_ns)
             for ln in host.lines for e in ln.events if e.name == WINDOW]
    if not marks:
        raise RuntimeError(f"no {WINDOW!r} span in {path}")
    w_lo, w_hi = marks[0]
    host_events = _Spans([(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for ln in host.lines for e in ln.events
                          if e.name != WINDOW and e.duration_ns > 0])
    busy, ops = [], collections.Counter()
    gaps = collections.Counter()
    devices = _device_ops(pd)
    for line in devices:
        iv = []
        for e in line.events:
            s = max(e.start_ns, w_lo)
            t = min(e.start_ns + e.duration_ns, w_hi)
            if t > s:
                iv.append((s, t))
                ops[op_name(e.name)] += (t - s) * 1e-9
        merged = _union(iv)
        busy.append(sum(t - s for s, t in merged) * 1e-9)
        edges = [w_lo] + [x for se in merged for x in se] + [w_hi]
        idle = sorted(((t - s, s, t) for s, t in zip(edges[::2], edges[1::2])
                       if t > s), reverse=True)
        for i, (dt, s, t) in enumerate(idle):
            name = host_events.doing(s, t) if i < ATTRIBUTED else "shorter gaps"
            gaps[name] += dt * 1e-9
    n = max(len(devices), 1)
    return {
        "devices": len(devices),
        "busy_s": sum(busy) / n,
        "window_s": (w_hi - w_lo) * 1e-9,
        "ops_s": {k: v / n for k, v in ops.items()},
        "top_ops": [[k, v / n] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v / n] for k, v in gaps.most_common()],
    }


class _Spans:
    """Host spans, to ask what the host was doing in an interval."""

    def __init__(self, spans):
        import numpy as np

        self.lo = np.array([a for a, _, _ in spans], np.float64)
        self.hi = np.array([b for _, b, _ in spans], np.float64)
        self.names = [n for _, _, n in spans]

    def doing(self, s: float, t: float) -> str:
        """The span that overlaps [s, t) the most (the shortest on a tie,
        being the more specific)."""
        import numpy as np

        if not self.names:
            return "nothing traced"
        o = np.minimum(self.hi, t) - np.maximum(self.lo, s)
        if o.max() <= 0:
            return "nothing traced"
        best = np.flatnonzero(o == o.max())
        return self.names[best[np.argmin((self.hi - self.lo)[best])]]


def kernel_s(summary: dict, prefix: str) -> float:
    """Device seconds of the operations whose name starts with ``prefix``."""
    return sum(v for k, v in summary["ops_s"].items() if k.startswith(prefix))
