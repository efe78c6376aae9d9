"""From a JAX profiler trace to device busy time, idle share and a
breakdown.

A traced run wraps its measured window in a host annotation named
:data:`WINDOW`.  The reduction reads the ``.xplane.pb`` the profiler
wrote:

* the window is that annotation's interval on the host timeline, which
  the device planes share;
* device busy time is the union of the intervals of the ``XLA Ops`` line
  of every ``/device:TPU:<n>`` plane, clipped to the window, averaged over
  the chips; the idle share is one minus busy over the window;
* ``device_ops`` sums op durations by ``<program> <op>`` name;
* ``idle_gaps`` names each of the longest stretches of the window in
  which no op ran (on the first chip) by the shortest host event that
  spans its midpoint, what the host was doing then, and sums the
  stretches by that name.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os

import numpy as np

WINDOW = "bench.window"
NO_HOST_EVENT = "(no host event)"
SHORTER_GAPS = "(shorter gaps, not named)"
MAX_NAMED_GAPS = 2000  # the longest gaps are named; the rest are summed


def options():
    """Profiler options of a traced run: host events of the program and
    the runtime, no Python function tracing."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    return opts


def union(intervals: np.ndarray) -> np.ndarray:
    """Sorted, disjoint cover of ``(n, 2)`` [start, end) intervals."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins after everything before
    # it has ended
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    stops = np.append(ends[np.nonzero(new)[0][1:] - 1], ends[-1])
    return np.stack([starts, stops], axis=1)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The stretches of [lo, hi) that ``busy`` (a union) leaves free."""
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the chips
    n_devices: int
    device_ops: list  # [[name, seconds], ...] most time first
    idle_gaps: list  # [[name, seconds], ...] most time first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _short_op(name: str) -> str:
    return name.split(" = ", 1)[0]


def _short_program(name: str) -> str:
    return name.split("(", 1)[0]


def reduce_events(window: tuple, devices: list, host: list,
                  top: int = 10) -> Summary:
    """The reduction proper, on plain event lists (ns).

    ``devices`` holds one ``(ops, programs)`` pair per chip, each a list of
    ``(start, end, name)``; ``host`` is a list of ``(start, end, name)``.
    """
    lo, hi = window
    busy_ns, per_op = [], {}
    unions = []
    for ops, programs in devices:
        iv = np.array([(s, e) for s, e, _ in ops], np.float64).reshape(-1, 2)
        u = union(clip(iv, lo, hi))
        unions.append(u)
        busy_ns.append(float((u[:, 1] - u[:, 0]).sum()))
        prog = sorted((s, e, _short_program(n)) for s, e, n in programs)
        starts = [p[0] for p in prog]
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            j = bisect.bisect_right(starts, s) - 1
            owner = prog[j][2] if j >= 0 and prog[j][1] >= s else "?"
            key = f"{owner} {_short_op(name)}"
            per_op[key] = per_op.get(key, 0.0) + d
    idle = {}
    if unions:
        free = gaps(unions[0], lo, hi)
        free = free[np.argsort(free[:, 0] - free[:, 1], kind="stable")]
        if len(free) > MAX_NAMED_GAPS:
            rest = free[MAX_NAMED_GAPS:]
            idle[SHORTER_GAPS] = float((rest[:, 1] - rest[:, 0]).sum())
            free = free[:MAX_NAMED_GAPS]
        h = np.array([(s, e) for s, e, _ in host], np.float64).reshape(-1, 2)
        names = [n for _, _, n in host]
        dur = h[:, 1] - h[:, 0]
        for a, b in free:
            mid = 0.5 * (a + b)
            inside = np.nonzero((h[:, 0] <= mid) & (h[:, 1] >= mid))[0]
            name = (names[inside[np.argmin(dur[inside])]] if inside.size
                    else NO_HOST_EVENT)
            idle[name] = idle.get(name, 0.0) + (b - a)

    def ranked(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=float(np.mean(busy_ns)) * 1e-9 if busy_ns else 0.0,
        n_devices=len(devices),
        device_ops=ranked(per_op),
        idle_gaps=ranked(idle),
    )


def read(trace_dir: str) -> Summary:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    window, devices, host = None, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops = [(e.start_ns, e.end_ns, e.name)
                   for e in lines["XLA Ops"].events]
            progs = [(e.start_ns, e.end_ns, e.name)
                     for e in lines["XLA Modules"].events] \
                if "XLA Modules" in lines else []
            devices.append((ops, progs))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    else:
                        host.append((e.start_ns, e.end_ns, e.name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    host = [ev for ev in host if ev[1] > window[0] and ev[0] < window[1]]
    return reduce_events(window, devices, host)
