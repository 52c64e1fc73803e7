"""From a profiler trace to the numbers the per-layer metrics read.

``load(dir)`` flattens the ``.xplane.pb`` that ``jax.profiler.trace``
wrote into plain events; ``reduce(events)`` works on those alone, so the
test runs it on a small trace recorded on the chip
(``tests/data/trace_v5e.json.gz``).

* busy: the union of the intervals in which an XLA op ran on a device,
  inside the window (the host span ``window``), averaged over devices;
* idle gaps: the holes in that union on the first device, each named by
  the innermost benchmark span (``TraceAnnotation``) that covered it;
* collective exposure: the part of each device's collective ops during
  which no other op ran there;
* device ops: total device time per op name;
* module time: device time of the XLA programs whose name holds a given
  string (``jit_<fn>``).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

#: the benchmark's own host spans (TraceAnnotation names)
WINDOW = "window"
SPANS = ("window", "batch", "step", "metrics_to_host", "sweep",
         "query:min_chips", "query:frontier")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|send|recv", re.I)


def load(trace_root: str) -> list:
    """Events ``[plane, line, name, start_ns, dur_ns]`` of the newest
    trace under ``trace_root``: the device ops and modules, and the
    benchmark's host spans."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_root, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_root}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and ev.name not in SPANS:
                    continue
                out.append([plane.name, line.name, op_name(ev.name),
                            int(ev.start_ns), int(ev.duration_ns)])
    return out


def op_name(text: str) -> str:
    """An op event's name: the HLO instruction name, without the
    instruction text the TPU profiler writes after it."""
    return text.split(" = ", 1)[0].lstrip("%")


def save(events: list, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_saved(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


@dataclass
class Summary:
    window_s: float
    busy_s: float                         # mean over devices
    n_devices: int
    gaps: list                            # [span, seconds], longest first
    ops: list                             # [name, seconds], most first
    collective_exposed_s: float           # mean over devices
    spans: dict = field(default_factory=dict)    # span -> count
    modules: dict = field(default_factory=dict)  # module -> device seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, needle: str) -> float:
        return sum(s for m, s in self.modules.items() if needle in m)

    def breakdown(self) -> dict:
        return {"device_ops": self.ops[:10], "idle_gaps": self.gaps[:10]}


def _innermost(spans: list, t: int) -> str:
    best = None
    for name, s, e in spans:
        if s <= t < e and name != WINDOW \
                and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


def reduce(events: list) -> Summary:
    host = [(n, s, s + d) for p, l, n, s, d in events
            if not p.startswith("/device:")]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError("trace has no 'window' span")
    w0, w1 = windows[0]
    spans = [h for h in host if h[1] < w1 and h[2] > w0]
    ops_by_dev: dict = {}
    modules: dict = {}
    for p, l, n, s, d in events:
        if not p.startswith("/device:"):
            continue
        if l == OPS_LINE:
            ops_by_dev.setdefault(p, []).append((n, s, s + d))
        elif l == MODULES_LINE:
            inside = clip([[s, s + d]], w0, w1)
            if inside:
                modules[n] = modules.get(n, 0) + length(inside)
    devices = sorted(ops_by_dev)
    if not devices:
        raise ValueError("trace has no device ops")

    busy, exposed, op_time = [], [], {}
    for dev in devices:
        ops = ops_by_dev[dev]
        all_u = clip(union([[s, e] for _, s, e in ops]), w0, w1)
        busy.append(length(all_u))
        coll = clip(union([[s, e] for n, s, e in ops
                           if COLLECTIVE.search(n)]), w0, w1)
        comp = clip(union([[s, e] for n, s, e in ops
                           if not COLLECTIVE.search(n)]), w0, w1)
        exposed.append(length(subtract(coll, comp)))
        for n, s, e in ops:
            inside = length(clip([[s, e]], w0, w1))
            if inside:
                op_time[n] = op_time.get(n, 0) + inside

    first = clip(union([[s, e] for _, s, e in ops_by_dev[devices[0]]]),
                 w0, w1)
    holes = subtract([[w0, w1]], first)
    gaps = sorted(([_innermost(spans, (s + e) // 2), (e - s) / 1e9]
                   for s, e in holes), key=lambda g: -g[1])
    nd = len(devices)
    ops = sorted(([n, t / nd / 1e9] for n, t in op_time.items()),
                 key=lambda o: -o[1])
    counts: dict = {}
    for n, s, e in spans:
        counts[n] = counts.get(n, 0) + 1
    return Summary(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy) / nd / 1e9, n_devices=nd,
                   gaps=gaps, ops=ops,
                   collective_exposed_s=sum(exposed) / nd / 1e9,
                   spans=counts,
                   modules={m: t / nd / 1e9 for m, t in modules.items()})
