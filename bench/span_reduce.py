"""The program's own spans and named scopes in a profiler trace.

The planning engine marks its phases with host spans named ``plan.*``
(``repro.spans``); the train step marks attention, the MLP and the loss
with the named scopes ``attn``, ``mlp`` and ``loss``, which reach each
HLO instruction's ``op_name``.  ``trace_reduce.load`` keeps only the
benchmark's own spans; this module reads the program's beside them:

* ``load(dir)``: the events ``trace_reduce.load`` keeps, and every host
  span whose name starts with ``plan.``;
* ``innermost(spans, times)``: for each time, the name of the shortest
  span that covers it (sort and heap: O((spans + times) log spans));
* ``span_seconds(events)``: per span name, its count, its total time
  and its self time (less what child spans on its thread line cover),
  clipped to the window;
* ``idle_by_span(events)``: the first device's idle time in the window,
  each piece given to the innermost span that covers it;
* ``control_ops(hlo_text)``: the instructions whose opcode is ``while``,
  ``conditional`` or ``call``: their bodies' ops are events of their
  own, so their events are not leaf time (JAX names a ``lax.cond``
  ``cond.N``, so the opcode decides and not the name);
* ``op_scopes(hlo_text, scopes)``: leaf HLO instruction name -> the
  first of ``scopes`` found as a segment of its ``op_name``, bare
  (``attn``) or wrapped by a transform (``jvp(loss)``);
* ``scope_seconds(events, scopes, control)``: device time of the leaf
  ops (all but ``control``) per scope, and the unscoped rest;
* ``scope_ms_per_step(facts, trace, scope)``: what the train cells'
  ``<scope>_device_ms.train`` readers report.

``python bench/span_reduce.py TRACE [--hlo STEP.txt]`` prints these for
a trace directory (``jax.profiler.trace``'s) or events saved by
``trace_reduce.save``.
"""

from __future__ import annotations

import argparse
import glob
import heapq
import json
import os
import re

import trace_reduce as T

PREFIX = "plan."
#: the train step's named scopes (``moe`` opens none yet)
SCOPES = ("attn", "moe", "mlp", "loss")
CONTROL = ("while", "conditional", "call")
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                      r'op_name="([^"]*)"')
#: an instruction's name and opcode: the first lower-case word followed
#: by "(" after the result's shape (a layout's ``T(8,128)`` follows ":")
_OPCODE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s'
                     r'([a-z][\w\-]*)\(')


def load(trace_root: str) -> list:
    """Events ``[plane, line, name, start_ns, dur_ns]`` of the newest
    trace under ``trace_root``: as ``trace_reduce.load``, with the
    program's ``plan.*`` spans kept too."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_root, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_root}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (T.OPS_LINE, T.MODULES_LINE):
                continue
            for ev in line.events:
                if not device and ev.name not in T.SPANS \
                        and not ev.name.startswith(PREFIX):
                    continue
                out.append([plane.name, line.name, T.op_name(ev.name),
                            int(ev.start_ns), int(ev.duration_ns)])
    return out


def _window(events: list) -> tuple:
    for p, l, n, s, d in events:
        if n == T.WINDOW and not p.startswith("/device:"):
            return s, s + d
    raise ValueError("trace has no 'window' span")


def _host_spans(events: list) -> list:
    """(name, start, end, line) of every host span but the window."""
    return [(n, s, s + d, (p, l)) for p, l, n, s, d in events
            if not p.startswith("/device:") and n != T.WINDOW]


def innermost(spans: list, times: list) -> list:
    """For each of ``times``, the name of the shortest of ``spans``
    (``(name, start, end, ...)``, the window excluded) with start <= t <
    end, the first listed among equals; "none" where none covers it.
    Returns the names in the order of ``times``."""
    spans = [sp for sp in spans if sp[0] != T.WINDOW]
    starts = sorted(range(len(spans)), key=lambda i: spans[i][1])
    out = ["none"] * len(times)
    heap, k = [], 0
    for q in sorted(range(len(times)), key=lambda i: times[i]):
        t = times[q]
        while k < len(starts) and spans[starts[k]][1] <= t:
            i = starts[k]
            heapq.heappush(heap, (spans[i][2] - spans[i][1], i))
            k += 1
        while heap and spans[heap[0][1]][2] <= t:
            heapq.heappop(heap)
        if heap:
            out[q] = spans[heap[0][1]][0]
    return out


def _self_pieces(spans: list, lo: int, hi: int) -> list:
    """The window [lo, hi) cut at every span boundary into pieces
    ``(start, end, innermost name)``."""
    cuts = sorted({lo, hi} | {t for sp in spans for t in sp[1:3]
                              if lo < t < hi})
    mids = [(a + b) // 2 for a, b in zip(cuts, cuts[1:])]
    names = innermost(spans, mids)
    return [(a, b, n) for a, b, n in zip(cuts, cuts[1:], names)]


def span_seconds(events: list) -> dict:
    """name -> {"count", "total_s", "self_s"} of the host spans that
    meet the window, clipped to it; self time is the time in which the
    span is the innermost on its own thread line."""
    w0, w1 = _window(events)
    spans = [sp for sp in _host_spans(events) if sp[1] < w1 and sp[2] > w0]
    out: dict = {}
    for n, s, e, _ in spans:
        rec = out.setdefault(n, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        rec["count"] += 1
        rec["total_s"] += (min(e, w1) - max(s, w0)) / 1e9
    for line in {sp[3] for sp in spans}:
        mine = [sp for sp in spans if sp[3] == line]
        for a, b, n in _self_pieces(mine, w0, w1):
            if n != "none":
                out[n]["self_s"] += (b - a) / 1e9
    return out


def idle_by_span(events: list) -> dict:
    """name -> seconds of the first device's idle time in the window
    during which that span was the innermost host span ("none" where no
    span but the window was open)."""
    w0, w1 = _window(events)
    devs = sorted({p for p, l, *_ in events
                   if p.startswith("/device:") and l == T.OPS_LINE})
    if not devs:
        raise ValueError("trace has no device ops")
    busy = T.clip(T.union([[s, s + d] for p, l, n, s, d in events
                           if p == devs[0] and l == T.OPS_LINE]), w0, w1)
    holes = T.subtract([[w0, w1]], busy)
    spans = [sp for sp in _host_spans(events) if sp[1] < w1 and sp[2] > w0]
    out: dict = {}
    j = 0
    for a, b, n in _self_pieces(spans, w0, w1):     # both sorted
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            idle = min(b, holes[k][1]) - max(a, holes[k][0])
            out[n] = out.get(n, 0.0) + idle / 1e9
            k += 1
    return out


def control_ops(hlo_text: str) -> set:
    """Names of the HLO instructions whose opcode is in ``CONTROL``."""
    out = set()
    for line in hlo_text.splitlines():
        m = _OPCODE.match(line)
        if m and m.group(2) in CONTROL:
            out.add(m.group(1))
    return out


def op_scopes(hlo_text: str, scopes: tuple) -> dict:
    """Leaf HLO instruction name -> the first of ``scopes`` that is a
    segment of its ``op_name`` (``.../attn/dot_general``,
    ``transpose(jvp(loss))/...``); instructions in none, and the
    ``control_ops``, are left out."""
    pats = [(sc, re.compile(r"(?:^|/)(?:[\w\-]+\()*" + re.escape(sc)
                            + r"\)*(?:/|$)")) for sc in scopes]
    control = control_ops(hlo_text)
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if not m or m.group(1) in control:
            continue
        for scope, pat in pats:
            if pat.search(m.group(2)):
                out[m.group(1)] = scope
                break
    return out


def scope_ms_per_step(facts: dict, trace, scope: str):
    """Device milliseconds per window ``step`` of the leaf ops that
    ``facts["op_scopes"]`` puts in ``scope``, from the trace's op times
    (averaged over devices); None where there is no trace, no step, no
    scope map or no op of the scope."""
    scopes = facts.get("op_scopes")
    steps = trace.spans.get("step", 0) if trace is not None else 0
    if not scopes or not steps:
        return None
    t = sum(s for name, s in trace.ops if scopes.get(name) == scope)
    return 1000.0 * t / steps if t else None


def scope_seconds(events: list, scopes: dict, control: set) -> dict:
    """Device seconds inside the window, averaged over devices, of the
    leaf ops (every op but ``control``, as ``control_ops`` gives it) in
    each scope (``scopes``: op name -> scope, as ``op_scopes`` gives
    it), under "unscoped" the rest, and under "leaf" all of them."""
    w0, w1 = _window(events)
    devs = {p for p, l, *_ in events
            if p.startswith("/device:") and l == T.OPS_LINE}
    out = {s: 0.0 for s in sorted(set(scopes.values()))}
    out.update(unscoped=0.0, leaf=0.0)
    for p, l, n, s, d in events:
        if l != T.OPS_LINE or not p.startswith("/device:") \
                or n in control:
            continue
        t = (min(s + d, w1) - max(s, w0)) / 1e9 / len(devs)
        if t > 0:
            out[scopes.get(n, "unscoped")] += t
            out["leaf"] += t
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a trace directory, or events saved "
                    "by trace_reduce.save")
    ap.add_argument("--hlo", help="the compiled step's as_text(), for "
                    "device time by named scope")
    args = ap.parse_args(argv)
    events = load(args.trace) if os.path.isdir(args.trace) \
        else T.read_saved(args.trace)
    out = {"spans": span_seconds(events),
           "idle_by_span": idle_by_span(events)}
    if args.hlo:
        with open(args.hlo) as f:
            text = f.read()
        out["scope_s"] = scope_seconds(events, op_scopes(text, SCOPES),
                                       control_ops(text))
    print(json.dumps(out, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
