"""From a profiler trace of the traced window to device numbers.

The harness wraps the traced part of the window in a ``bench.window``
annotation and every timed item in ``bench.<label>``
(``jax.profiler.TraceAnnotation``), so that the host plane of the trace
says what the host was doing at each moment.  :func:`summarize` reads:

* busy: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), clipped to the window, averaged over the
  devices the cell uses;
* the device operations that took the most time, by self time (a loop's
  time less the operations nested in it), mean seconds a chip;
* the longest idle gaps of the first device, each named by the innermost
  ``bench.*`` annotation open at its midpoint.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
TOP = 10
MIN_GAP_NS = 1000       # shorter gaps are the seams between back-to-back ops

Interval = Tuple[float, float]


def merge(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Union of ``intervals`` clipped to ``[lo, hi]``, sorted."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` within ``[lo, hi]``."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Self time per operation name of one device's nested operations:
    each one's duration less that of the operations directly inside it."""
    out: Dict[str, float] = {}
    stack: List[list] = []       # [name, start, end, time of children]

    def close(ev):
        out[ev[0]] = out.get(ev[0], 0.0) + (ev[2] - ev[1]) - ev[3]

    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def label_at(t: float, annotations: Sequence[Tuple[str, float, float]]
             ) -> str:
    """Name of the innermost ``bench.*`` annotation open at ``t``, without
    its prefix."""
    best = None
    for name, a, b in annotations:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0][len("bench."):] if best else "between items"


def summarize(devices: Sequence[Sequence[Tuple[str, float, float]]],
              annotations: Sequence[Tuple[str, float, float]]
              ) -> Optional[Dict]:
    """Device numbers of one traced window.  ``devices`` holds, per chip,
    its operations as ``(name, start_ns, end_ns)``; ``annotations`` the
    host's ``bench.*`` spans the same way, the window among them.  None
    where there is no window or no device operation to read."""
    win = [(a, b) for n, a, b in annotations if n == WINDOW]
    if not win or not devices or not any(devices):
        return None
    lo, hi = win[0]
    busy_per = []
    op_time: Dict[str, float] = {}
    for ops in devices:
        ops = [(name, max(a, lo), min(b, hi)) for name, a, b in ops
               if min(b, hi) > max(a, lo)]
        busy_per.append(merge([(a, b) for _, a, b in ops], lo, hi))
        for name, t in self_times(ops).items():
            op_time[name] = op_time.get(name, 0.0) + t
    n = len(devices)
    busy_s = sum(b - a for m in busy_per for a, b in m) / n / 1e9
    items = [x for x in annotations if x[0] != WINDOW]
    idle = sorted((g for g in gaps(busy_per[0], lo, hi)
                   if g[1] - g[0] >= MIN_GAP_NS), key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name, t / n / 1e9] for name, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label_at((a + b) / 2, items), (b - a) / 1e9]
                      for a, b in idle[:TOP]],
    }


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(hlo: str) -> str:
    """``%fusion.40 = f32[...] fusion(...)`` -> ``fusion.40``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str, n_devices: int):
    """``(devices, annotations)`` of an ``.xplane.pb`` file: the operations
    of the first ``n_devices`` TPU planes and the host's ``bench.*``
    annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, annotations = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append((int(plane.name[len("/device:TPU:"):]),
                                [(op_name(n), a, b) for n, a, b
                                 in _events(lines["XLA Ops"])]))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                annotations += [x for x in _events(ln)
                                if x[0].startswith("bench.")]
    devices.sort(key=lambda d: d[0])
    return [ops for _, ops in devices[:n_devices]], annotations


class Capture:
    """``with Capture(n):`` traces what runs inside into a scratch directory
    under ``$TMPDIR``; ``read()``, called after the measured window,
    returns :func:`summarize` of it (or None) and removes the directory."""

    def __init__(self, n_devices: int):
        self.n_devices = n_devices
        self.dir: Optional[str] = None

    def __enter__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        # host annotations only: the Python tracer would record every
        # call of the window and slow both it and the trace's collection
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._window.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def read(self) -> Optional[Dict]:
        if self.dir is None:
            return None
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            return summarize(*read_xplane(files[0], self.n_devices)) \
                if files else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
