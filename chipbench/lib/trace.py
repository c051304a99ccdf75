"""From a profiler trace to seconds: the one reduction every cell uses.

A device plane of this installation (jax 0.9, libtpu 0.0.34) carries three
nested lanes, ``Steps`` > ``XLA Modules`` > ``XLA Ops``. Only ``XLA Ops``
holds kernels; summing all three counts every kernel three times (what
`telemetry.kernels.census` does: 1.9 s of "device time" for 0.149 s of steps).
So: busy time is the union of the ``XLA Ops`` intervals alone, per-program
time is read from ``XLA Modules`` alone, and nothing is added across lanes.

`load_lanes` is the only function that touches jax; the rest is arithmetic on
``(name, start_ns, duration_ns)`` triples and is tested on recorded lanes
(`chipbench/testdata/*.lanes.json.gz`).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

OPS, MODULES, STEPS = "XLA Ops", "XLA Modules", "Steps"
SPAN_PREFIX = "cb."          # every host span the benchmark records
WINDOW_SPAN = "cb.window"    # the span that brackets the traced window


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_label(text):
    """An event of the ``XLA Ops`` lane is named by its whole HLO
    instruction. Keep the instruction's own name and, for a custom call, its
    target: ``%jvp__.26 = (...) custom-call(...), custom_call_target=
    "tpu_custom_call"`` -> ``jvp__.26 tpu_custom_call`` (a pallas kernel).
    Patterns then match the op itself, never an operand's name."""
    name = text.strip().lstrip("%").split(" ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return f"{name} {target.group(1)}" if target else name


def load_lanes(path):
    """``{"devices": {plane: {lane: [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns], ...]}`` — the device planes' three
    lanes, and of the host planes only the benchmark's own spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lanes = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = lanes["devices"].setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS, MODULES, STEPS):
                    dev[line.name] = [
                        [op_label(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)] for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                lanes["host"] += [
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    return lanes


def write_lanes(lanes, path):
    with gzip.open(path, "wt") as f:
        json.dump(lanes, f, separators=(",", ":"))


def read_lanes(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- interval arithmetic (nanoseconds, half-open) ----------------------------

def union(intervals):
    """Merged, sorted, non-overlapping ``[start, end]`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of merged `a` that merged `b` does not cover."""
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


def op_family(name):
    """``fusion.123`` -> ``fusion``: one name per kind of instruction."""
    name = op_label(name).split(" ", 1)[0]
    return re.sub(r"[.\d]+$", "", name) or name


def percentile(values, q):
    """Linear-interpolation percentile of a non-empty list, q in [0, 100]."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class Trace:
    """The traced window of one run, reduced on demand."""

    def __init__(self, lanes):
        self.devices = lanes["devices"]
        self.host = lanes["host"]
        if not self.devices:
            raise ValueError("the trace holds no /device:TPU plane")
        win = [ev for ev in self.host if ev[0] == WINDOW_SPAN]
        if win:
            self.lo, self.hi = win[0][1], win[0][1] + win[0][2]
        else:   # a recorded fragment: what its ops and programs span (a
            #         program's event ends a little after its last op)
            evs = [ev for d in self.devices.values()
                   for lane in (OPS, MODULES) for ev in d.get(lane, [])]
            self.lo = min(ev[1] for ev in evs)
            self.hi = max(ev[1] + ev[2] for ev in evs)

    @property
    def window_s(self):
        return (self.hi - self.lo) / 1e9

    def _ops(self, dev, pattern=None):
        rx = re.compile(pattern) if pattern else None
        return [[s, s + d] for name, s, d in self.devices[dev].get(OPS, [])
                if rx is None or rx.search(name)]

    def busy_intervals(self, dev):
        return clip(union(self._ops(dev)), self.lo, self.hi)

    def busy_s(self):
        """Seconds with an operation on the device, mean over devices."""
        per = [total(self.busy_intervals(d)) for d in self.devices]
        return sum(per) / len(per) / 1e9

    def idle_share(self):
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, pattern):
        """Summed durations of the ops whose name matches, mean over
        devices, inside the window."""
        per = []
        for dev in self.devices:
            per.append(total(clip(
                [iv for iv in self._ops(dev, pattern)], self.lo, self.hi)))
        return sum(per) / len(per) / 1e9

    def module_ms(self, pattern):
        """Durations (ms) of the compiled programs whose name matches, all
        devices pooled, programs wholly inside the window."""
        rx = re.compile(pattern)
        return [d / 1e6 for dev in self.devices.values()
                for name, s, d in dev.get(MODULES, [])
                if rx.search(name) and s >= self.lo and s + d <= self.hi]

    def module_seconds(self, pattern):
        """Device time of the matching programs inside the window (a program
        that straddles an edge counts for its part), mean over devices."""
        rx = re.compile(pattern)
        per = [total(clip([[s, s + d] for name, s, d in dev.get(MODULES, [])
                           if rx.search(name)], self.lo, self.hi))
               for dev in self.devices.values()]
        return sum(per) / len(per) / 1e9

    def module_names(self):
        return sorted({name for dev in self.devices.values()
                       for name, _, _ in dev.get(MODULES, [])})

    def top_ops(self, n=10):
        """``[[family, seconds], ...]`` of the first device, largest first."""
        dev = sorted(self.devices)[0]
        sums = {}
        for name, s, d in self.devices[dev].get(OPS, []):
            part = total(clip([[s, s + d]], self.lo, self.hi))
            if part:
                fam = op_family(name)
                sums[fam] = sums.get(fam, 0) + part
        top = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n=10):
        """The first device's idle time by what the host was doing: each gap
        goes to the innermost benchmark span open when the gap began
        (``host:<span>``), else to the ops on either side of it."""
        dev = sorted(self.devices)[0]
        ended, began = {}, {}
        for name, s, d in self.devices[dev].get(OPS, []):
            ended[s + d] = began[s] = name
        gaps = subtract([[self.lo, self.hi]], self.busy_intervals(dev))
        spans = sorted((s, s + d, name) for name, s, d in self.host
                       if name != WINDOW_SPAN)
        sums, nxt, open_spans = {}, 0, []
        for s, e in gaps:             # gaps and spans both in time order
            while nxt < len(spans) and spans[nxt][0] <= s:
                open_spans.append(spans[nxt])
                nxt += 1
            open_spans = [sp for sp in open_spans if sp[1] > s]
            if open_spans:
                label = "host:" + min(open_spans,
                                      key=lambda sp: sp[1] - sp[0])[2]
            else:
                label = "between:{}_{}".format(
                    op_family(ended[s]) if s in ended else "start",
                    op_family(began[e]) if e in began else "end")
            sums[label] = sums.get(label, 0) + (e - s)
        top = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[k[:64], v / 1e9] for k, v in top]
