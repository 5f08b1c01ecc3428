"""Read the program's own scopes and spans in a trace of the window.

``reduce`` gives the fields of ``bench/trace.py``'s reduction, with the
same ``window_s``, ``busy_s`` and ``op_s``, and adds what the program's
names (``repro.core.spans``) let it read:

- ``scope_s``: device self time per ``jax.named_scope`` of the program,
  named by the innermost scope in the operation's ``tf_op``
  (``bench/xspace.py``), and ``unscoped`` for operations with no known
  scope or no ``tf_op``; the values sum to those of ``op_s``;
- ``idle_s``: the window's idle device time by the innermost host span
  open at the time.  The program's spans (``repro.*``) nest under the
  driver's: idle time while ``repro.compile`` is open inside ``run``
  counts under ``repro.compile``, and ``run`` keeps what no program span
  covered.  The total, and ``window_s`` (the driver's spans alone), are
  those of ``bench/trace.py``;
- ``span_s``: host seconds of each program span inside the window.

A program from before these names gives no program spans, and every
operation ``unscoped``.
"""
from __future__ import annotations

import re

import numpy as np

import repro.core  # noqa: F401  (the program itself must import)
from bench import trace, xspace

try:
    from repro.core import spans
except ImportError:  # a program from before its scopes and spans
    spans = None

SCOPES = spans.SCOPES if spans is not None else ()
PROGRAM_SPANS = spans.SPANS if spans is not None else ()
UNSCOPED = "unscoped"

#: a name-stack entry wrapped by a transformation: ``vmap(edge_gather)``
_WRAPPED = re.compile(r"[\w.-]*\((.*)\)")


def scope_of(tf_op) -> str:
    """The innermost of the program's scopes in a ``tf_op`` path."""
    for part in reversed((tf_op or "").split("/")):
        while (m := _WRAPPED.fullmatch(part)) is not None:
            part = m.group(1)
        if part in SCOPES:
            return part
    return UNSCOPED


def _parents(starts, ends, outer_first) -> list:
    """Index of the interval each interval nests in directly (-1 for
    none).  Of two intervals with the same bounds, the one that
    ``outer_first`` ranks lower holds the other."""
    order = sorted(range(len(starts)),
                   key=lambda i: (starts[i], -ends[i], outer_first[i]))
    parent = [-1] * len(starts)
    stack = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
        stack.append(i)
    return parent


def split_idle(gap_starts, gap_ends, spans_) -> dict:
    """Idle time (the length of the disjoint ``gap`` intervals) by the
    innermost of ``spans_`` open over it, ``(start, end, name, rank)``
    each, where a lower ``rank`` is the outer one on equal bounds; what
    no span covers goes to ``none``."""
    out = {"none": float((gap_ends - gap_starts).sum())}
    if not spans_:
        return out
    a = np.array([s[0] for s in spans_], np.float64)
    b = np.array([s[1] for s in spans_], np.float64)
    parent = np.array(_parents(a.tolist(), b.tolist(),
                               [s[3] for s in spans_]))

    def idle(lo, hi):
        return (trace._covered_before(hi, gap_starts, gap_ends)
                - trace._covered_before(lo, gap_starts, gap_ends))

    own = idle(a, b)
    # what a span holds leaves the span it nests in (``none`` at the
    # top), counted only as far as it lies inside that span
    nested = parent >= 0
    held = idle(a, np.where(nested, np.minimum(b, b[parent]), b))
    np.subtract.at(own, parent[nested], held[nested])
    out["none"] -= float(held[~nested].sum())
    for (_, _, name, _), t in zip(spans_, own):
        out[name] = out.get(name, 0.0) + float(t)
    return out


def reduce(profile, path) -> dict:
    """The reduction of the profile ``profile`` read from ``path``."""
    names = {**{n: 0 for n in trace.SPANS}, **{n: 1 for n in PROGRAM_SPANS}}
    tf = xspace.tf_ops(path)
    opened, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            ops = [(ev.name, ev.start_ns, ev.end_ns)
                   for line in plane.lines if line.name == trace.OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append((ops, tf.get(plane.name, {})))
        else:
            opened += [(ev.start_ns, ev.end_ns, ev.name, names[ev.name])
                       for line in plane.lines for ev in line.events
                       if ev.name in names]
    driver = [(s, e) for s, e, _, rank in opened if rank == 0]
    if not devices or not driver:
        return None
    lo = min(s for s, _ in driver)
    hi = max(e for _, e in driver)
    inside = [(max(s, lo), min(e, hi), name, rank)
              for s, e, name, rank in opened if min(e, hi) > max(s, lo)]
    span_s = {}
    for s, e, name, rank in inside:
        if rank:
            span_s[name] = span_s.get(name, 0.0) + (e - s) * 1e-9
    busy, op_s, scope_s = [], {}, {}
    idle = {name: 0.0 for name in (*trace.SPANS, "none")}
    n = len(devices)
    for ops, scopes in devices:
        starts = np.clip(np.array([s for _, s, _ in ops], np.float64), lo, hi)
        ends = np.clip(np.array([e for _, _, e in ops], np.float64), lo, hi)
        for (name, _, _), t in zip(ops, trace._self_times(starts.tolist(),
                                                           ends.tolist())):
            short = trace._short(name)
            op_s[short] = op_s.get(short, 0.0) + t * 1e-9 / n
            scope = scope_of(scopes.get(name))
            scope_s[scope] = scope_s.get(scope, 0.0) + t * 1e-9 / n
        bs, be = trace._merge(starts, ends)
        busy.append(float((be - bs).sum()) * 1e-9)
        gs = np.concatenate([[lo], be])
        ge = np.concatenate([bs, [hi]])
        keep = ge > gs
        for name, t in split_idle(gs[keep], ge[keep], inside).items():
            idle[name] = idle.get(name, 0.0) + t * 1e-9 / n
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / len(busy),
            "op_s": op_s, "idle_s": idle, "scope_s": scope_s,
            "span_s": span_s}


def per_query_ms(window, field: str, *names):
    """Milliseconds a query of the window spent in ``names`` of the trace's
    ``field`` (``scope_s``, ``span_s``); ``None`` where the window has no
    trace, no query, or none of the names."""
    found = (window.trace or {}).get(field, {})
    if not window.queries or not any(n in found for n in names):
        return None
    return 1e3 * sum(found.get(n, 0.0) for n in names) / len(window.queries)
