"""Reduce a profiler trace of the measured window to device metrics.

- ``window_s``: from the first host span's start to the last one's end;
- ``busy_s``: the union of the device's operation intervals inside the
  window, averaged over the devices that ran any;
- ``op_s``: device self time per operation: its interval less the part
  covered by operations nested in it (a ``while`` holds its body's);
- ``idle_s``: the window's idle device time split by the host span open
  at the time (``none`` where no span was open).

Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
names; device operations are the events of each device plane's
``XLA Ops`` line, named by their HLO instruction and its result shape
(``fusion.104 s32[262145]``).
"""
from __future__ import annotations

import numpy as np

SPANS = ("build_query", "run", "readback")
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"


def load(path):
    """The profile written under ``path`` (a ``.xplane.pb`` file)."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _merge(starts: np.ndarray, ends: np.ndarray):
    """Union of intervals, as sorted disjoint ``(starts, ends)``."""
    if not starts.size:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.shape[0], bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def _short(name: str) -> str:
    """``fusion.104 s32[262145]`` from ``%fusion.104 = s32[262145]{0:T(1024)}
    fusion(...)``: the instruction and the shape it produces."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    head = head.lstrip("%")
    return f"{head} {shape}" if shape[:1].isalpha() and "[" in shape \
        else head


def _self_times(starts, ends) -> list:
    """Each interval's length less the part its nested intervals cover
    (intervals nest, as an operation's body does inside it)."""
    order = sorted(range(len(starts)), key=lambda i: (starts[i], -ends[i]))
    out = [0.0] * len(starts)
    stack = []  # [index, end, covered by direct children]
    for i in order:
        while stack and stack[-1][1] <= starts[i]:
            j, end, covered = stack.pop()
            out[j] = end - starts[j] - covered
        if stack:
            stack[-1][2] += min(ends[i], stack[-1][1]) - starts[i]
        stack.append([i, ends[i], 0.0])
    for j, end, covered in stack:
        out[j] = end - starts[j] - covered
    return out


def _covered_before(t: np.ndarray, starts, ends) -> np.ndarray:
    """Length of the disjoint intervals that lies before each ``t``."""
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])
    k = np.searchsorted(starts, t, side="right")
    last = np.maximum(k - 1, 0)
    part = np.where(k > 0, np.clip(t - starts[last], 0.0,
                                   ends[last] - starts[last]), 0.0)
    return cum[last] * (k > 0) + part


def reduce(profile) -> dict:
    spans = {name: [] for name in SPANS}
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [(_short(ev.name), ev.start_ns, ev.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
    opened = [iv for ivs in spans.values() for iv in ivs]
    if not devices or not opened:
        return None
    lo = min(s for s, _ in opened)
    hi = max(e for _, e in opened)
    window = (hi - lo) * 1e-9
    busy, op_s = [], {}
    idle = {name: 0.0 for name in (*SPANS, "none")}
    for ops in devices:
        starts = np.clip(np.array([s for _, s, _ in ops], np.float64), lo, hi)
        ends = np.clip(np.array([e for _, _, e in ops], np.float64), lo, hi)
        for (name, _, _), t in zip(ops, _self_times(starts.tolist(),
                                                     ends.tolist())):
            op_s[name] = op_s.get(name, 0.0) + t * 1e-9 / len(devices)
        bs, be = _merge(starts, ends)
        busy.append(float((be - bs).sum()) * 1e-9)
        # idle intervals: the window minus the busy ones
        gs = np.concatenate([[lo], be])
        ge = np.concatenate([bs, [hi]])
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        open_total = 0.0
        for name, ivs in spans.items():
            if not ivs:
                continue
            a = np.array([s for s, _ in ivs], np.float64)
            b = np.array([e for _, e in ivs], np.float64)
            t = float((_covered_before(b, gs, ge)
                       - _covered_before(a, gs, ge)).sum()) * 1e-9
            idle[name] += t / len(devices)
            open_total += t
        idle["none"] += (float((ge - gs).sum()) * 1e-9
                         - open_total) / len(devices)
    return {"window_s": window, "busy_s": sum(busy) / len(busy),
            "op_s": op_s, "idle_s": idle}


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the operations that took most device
    time, and the idle time by host span, each as ``[name, seconds]``."""
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(((k, v) for k, v in reduced["idle_s"].items() if v > 0),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
