"""Reduce a profiler trace to device busy time, top ops and idle gaps.

The harness traces a slice of the window with ``jax.profiler`` and marks
the slice with a host span named ``WINDOW``; it also wraps its own host
work in spans (``tick``, ``submit``, ``generator-wait``).  From the trace:

* busy: the union of the intervals in which an operation ran on a device
  (the device plane's ``XLA Ops`` line), clipped to the slice, averaged
  over the devices that ran anything;
* the device operations that took the most time, summed by name (a
  loop's own event spans the operations of its body, which are counted
  under their own names too);
* the longest idle gaps between device operations, each named by the host
  span that overlapped it most (``idle`` where the host was in none).
"""
from __future__ import annotations

import glob
import gzip
import os

WINDOW = "bench-window"
HOST_SPANS = ("tick", "submit", "generator-wait")
OPS_LINE = "XLA Ops"


def load(path: str):
    """A ProfileData from an ``.xplane.pb`` file (gzipped or not), or from
    the newest one under a trace directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb*"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    with open(path, "rb") as f:
        data = f.read()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    return ProfileData.from_serialized_xspace(data)


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield line.name, ev


def op_name(text: str) -> str:
    """An XLA op event's name is its whole HLO instruction; keep the part
    before ``=`` (``while.4``, ``copy_bitcast_fusion.6``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def reduce(pd, top: int = 10) -> dict:
    """-> {"busy_s", "window_s", "device_ops", "idle_gaps", "n_devices"}
    for the traced slice; times in seconds."""
    planes = list(pd.planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    spans = {name: [] for name in HOST_SPANS}
    win = None
    for p in host:
        for _, ev in _events(p):
            if ev.name == WINDOW:
                win = (ev.start_ns, ev.end_ns)
            elif ev.name in spans:
                spans[ev.name].append((ev.start_ns, ev.end_ns))
    if win is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo, hi = win
    devices = [p for p in planes if p.name.startswith("/device:")
               and "CPU" not in p.name]
    busy_total, n_dev, op_time, gaps = 0.0, 0, {}, []
    for p in devices:
        has_ops = any(line.name == OPS_LINE for line in p.lines)
        ivs = []
        for _, ev in _events(p, OPS_LINE if has_ops else None):
            a, b = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if b <= a:
                continue
            ivs.append((a, b))
            name = op_name(ev.name)
            op_time[name] = op_time.get(name, 0.0) + (b - a) * 1e-9
        if not ivs:
            continue
        n_dev += 1
        u = _union(ivs)
        busy_total += sum(b - a for a, b in u) * 1e-9
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2], strict=True):
            if b > a:
                gaps.append((a, b))
    if n_dev == 0:
        raise ValueError("no operation ran on a device in the traced slice")
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, best_ov = "idle", 0
        for name, ivs in spans.items():
            ov = sum(max(0, min(b, y) - max(a, x)) for x, y in ivs)
            if ov > best_ov:
                best, best_ov = name, ov
        named.append([best, (b - a) * 1e-9])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_total / n_dev, "window_s": (hi - lo) * 1e-9,
            "n_devices": n_dev, "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": named}
