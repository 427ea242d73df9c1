"""Reduction of a JAX profiler trace to the benchmark's device numbers.

- busy: the union of the intervals in which an operation ran on a device,
  clipped to the traced window and averaged over the devices;
- the device operations that took the most time (summed over devices);
- the longest idle gaps, each labelled with what the host was doing in it:
  the innermost benchmark span (``bench.*``) and the shortest other host
  event that covers the gap's middle.

The run takes the trace in memory (``start`` / ``stop``): the profiler's
own export would write the window's millions of device events to disk
twice, as XSpace and as trace JSON, and the JSON alone outlasts a run's
time limit.  ``events`` turns a ``jax.profiler.ProfileData`` into plain
tuples; everything after it works on those, so it is tested on hand-made
events as well as on a recorded trace (``load``).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import List, Sequence, Tuple

Interval = Tuple[str, float, float]  # (name, start_ns, end_ns)

# the line of a device plane that holds one event per executed operation,
# and the line that holds one event per executed program
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(python_tracer_level: int = 0, host_tracer_level: int = 2):
    """Start an in-memory profiler session; ``stop`` ends it.  The Python
    tracer is off by default: its calls would swamp the trace."""
    import jax
    from jax._src.lib import _profiler

    jax.devices()  # the backends first, or the TPU tracer sees no device
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = python_tracer_level
    opts.host_tracer_level = host_tracer_level
    return _profiler.ProfilerSession(opts)


def stop(session) -> dict:
    """End the session and return its events (see ``events``)."""
    return events(session.stop_and_get_profile_data())


def load(path: str) -> dict:
    """The events of a recorded ``.xplane.pb``."""
    from jax.profiler import ProfileData

    return events(ProfileData.from_file(path))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def events(pd) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [(line, name, start_ns, end_ns), ...]}`` from a ``ProfileData``."""
    devices, host = {}, []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:"):
            def spans(line):
                return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events] if line is not None else []
            ops = spans(lines.get(OPS_LINE))
            if not ops:
                continue
            devices[plane.name] = {
                "ops": ops, "modules": spans(lines.get(MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((ln.name, e.name, e.start_ns,
                             e.start_ns + e.duration_ns) for e in ln.events)
    return {"devices": devices, "host": host}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Idle intervals of [lo, hi) not covered by the merged ``busy``."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def window_of(host, span: str) -> Tuple[float, float]:
    """Start and end of the host span named ``span`` (the traced window)."""
    hits = [(a, b) for _, name, a, b in host if name == span]
    if not hits:
        raise ValueError(f"no host span {span!r} in the trace")
    return min(a for a, _ in hits), max(b for _, b in hits)


def _label(host_sorted, starts, t: float) -> str:
    """Innermost ``bench.*`` span and shortest other host event at time t."""
    bench, other = None, None
    hi = bisect.bisect_right(starts, t)
    for line, name, a, b in host_sorted[:hi]:
        if b <= t:
            continue
        if name.startswith("bench."):
            if bench is None or b - a < bench[1]:
                bench = (name, b - a)
        elif other is None or b - a < other[1]:
            other = (name, b - a)
    parts = [p[0] for p in (bench, other) if p is not None]
    return " / ".join(parts) if parts else "no host event"


def reduce(events: dict, window: Tuple[float, float], top: int = 10) -> dict:
    """Device busy and idle seconds over ``window`` (ns), top ops, idle gaps."""
    lo, hi = window
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy_ns, op_ns, gap_list = 0.0, defaultdict(float), []
    host_sorted = sorted(events["host"], key=lambda e: e[2])
    starts = [e[2] for e in host_sorted]
    for plane, dev in sorted(devices.items()):
        merged = union(clip([(a, b) for _, a, b in dev["ops"]], lo, hi))
        busy_ns += sum(b - a for a, b in merged)
        mods = sorted(dev["modules"], key=lambda e: e[1])
        mod_starts = [m[1] for m in mods]
        for name, a, b in dev["ops"]:
            a2, b2 = max(a, lo), min(b, hi)
            if b2 <= a2:
                continue
            i = bisect.bisect_right(mod_starts, a) - 1
            mod = mods[i][0] if i >= 0 and mods[i][2] >= b else None
            op = name.split(" = ")[0]  # an op event's name is its HLO text
            op_ns[f"{mod}/{op}" if mod else op] += b2 - a2
        gap_list += [(b - a, (a + b) / 2) for a, b in gaps(merged, lo, hi)]
    n = len(devices)
    gap_list.sort(key=lambda g: -g[0])
    gap_list = [(d, _label(host_sorted, starts, mid)) for d, mid in gap_list[:top]]
    return {
        "devices": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, d * 1e-9] for d, label in gap_list],
    }


def reduce_events(events: dict, span: str = "bench.window") -> dict:
    return reduce(events, window_of(events["host"], span))
