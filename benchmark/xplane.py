"""From the profiler's .xplane.pb to numbers: busy and idle time of the
device, time per kernel, time per compiled-program execution, exposed
collectives, and which host span each idle gap of the device falls under.

Read with jax.profiler.ProfileData and nothing else. What a TPU trace looks
like (checked on a recorded one, tests/data/tiny_v5e.xplane.pb):

  plane '/device:TPU:<n>'   one per chip
    line 'XLA Modules'      one event per execution of a compiled program;
                            stat run_id matches the host's DoEnqueueProgram
    line 'XLA Ops'          one event per HLO instruction run; the event's
                            name is the instruction's text
    line 'Async XLA Ops'    spans of asynchronous copies and collectives
  plane '/host:CPU'         one line per host thread; TraceAnnotations (the
                            program's spans among them) are on the thread
                            that opened them

All times are kept in seconds on the profile's own clock. The device's clock
lags the host's by a millisecond or so; `host_offset_s` estimates the lag
from matching run_ids, and only the attribution of gaps uses it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)")
_INSTR = re.compile(
    r"^%(?P<name>[^ ]+) = (?P<shape>.*?) (?P<op>[a-z][a-z0-9\-]*)\(")
_FIRST_ARRAY = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def parse_instruction(text: str):
    """'%fusion.12 = f32[8,1024]{..} fusion(...)' -> (key, opcode, is_mosaic).
    The key is how PERF_LEDGER's breakdowns name device operations: the
    instruction's name without its number, its opcode where that differs, and
    its first result shape: `transpose_jvp____custom-call_bf16_128_1024_64_`.
    """
    m = _INSTR.match(text)
    if not m:
        return re.sub(r"[^A-Za-z0-9_\-]", "_", text[:48]), "", False
    stem = re.sub(r"\.[0-9]+$", "", m.group("name"))
    op = m.group("op")
    key = stem if stem == op else f"{stem}_{op}"
    arr = _FIRST_ARRAY.search(m.group("shape"))
    if arr:
        key += f"_{arr.group(1)}_" + arr.group(2).replace(",", "_") + "_"
    mosaic = op == "custom-call" and 'custom_call_target="tpu_custom_call"' in text
    return re.sub(r"[^A-Za-z0-9_\-]", "_", key), op, mosaic


def union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def subtract(a, b):
    """Parts of merged `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class DeviceTrace:
    """One chip's part of a trace."""

    def __init__(self, name):
        self.name = name
        self.ops = []        # (start, end, key, opcode, is_mosaic)
        self.async_ops = []  # (start, end, key, opcode)
        self.modules = []    # (start, end, name, run_id)

    def busy(self):
        return union((s, e) for s, e, *_ in self.ops)


class Trace:
    def __init__(self, path: str):
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        self.devices = []
        self.host_lines = {}     # thread line name -> [(start, end, name)]
        self.enqueues = {}       # run_id -> host time the program was enqueued
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                self.devices.append(self._device(plane))
            elif plane.name == "/host:CPU":
                self._host(plane)
        self.devices.sort(key=lambda d: d.name)

    @staticmethod
    def _device(plane):
        dev = DeviceTrace(plane.name)
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    key, op, mosaic = parse_instruction(ev.name)
                    s = ev.start_ns * 1e-9
                    dev.ops.append((s, s + ev.duration_ns * 1e-9, key, op,
                                    mosaic))
            elif line.name == "Async XLA Ops":
                for ev in line.events:
                    key, op, _ = parse_instruction(ev.name)
                    s = ev.start_ns * 1e-9
                    dev.async_ops.append((s, s + ev.duration_ns * 1e-9, key,
                                          op))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    run_id = dict(ev.stats).get("run_id")
                    dev.modules.append((s, s + ev.duration_ns * 1e-9,
                                        re.sub(r"\(.*", "", ev.name), run_id))
        dev.ops.sort()
        dev.modules.sort()
        return dev

    def _host(self, plane):
        for line in plane.lines:
            evs = []
            for ev in line.events:
                s = ev.start_ns * 1e-9
                evs.append((s, s + ev.duration_ns * 1e-9, ev.name))
                if ev.name == "DoEnqueueProgram":
                    rid = dict(ev.stats).get("run_id")
                    if rid is not None:
                        self.enqueues[rid] = s
            evs.sort()
            self.host_lines[line.name] = evs

    # ---- device time -----------------------------------------------------
    def window(self):
        """(start, end) of the traced window: first to last device event."""
        starts = [d.ops[0][0] for d in self.devices if d.ops]
        ends = [max(e for _, e, *_ in d.ops) for d in self.devices if d.ops]
        if not starts:
            return (0.0, 0.0)
        return (min(starts), max(ends))

    def busy_seconds(self, lo=None, hi=None) -> float:
        """Seconds with an operation running, averaged over the chips."""
        if not self.devices:
            return 0.0
        if lo is None:
            lo, hi = self.window()
        return sum(measure(clip(d.busy(), lo, hi))
                   for d in self.devices) / len(self.devices)

    def op_seconds(self):
        """{key: seconds} over all chips, averaged over the chips."""
        tot = defaultdict(float)
        for d in self.devices:
            for s, e, key, *_ in d.ops:
                tot[key] += e - s
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in tot.items()}

    def module_mosaic_seconds(self, name=None):
        """For each execution of program `name` on chip 0, the device seconds
        of the Pallas (Mosaic) kernels that ran inside it."""
        if not self.devices:
            return []
        dev = self.devices[0]
        name = name or self.main_module()
        kernels = [(s, e) for s, e, _, _, mosaic in dev.ops if mosaic]
        starts = [s for s, _ in kernels]
        out = []
        for s, e, n, _ in dev.modules:
            if n == name:
                i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
                out.append(sum(b - a for a, b in kernels[i:j]))
        return out

    def main_module(self):
        """Name of the compiled program with the most device time on chip 0:
        the train step or the decode tick."""
        tot = defaultdict(float)
        for s, e, name, _ in self.devices[0].modules if self.devices else []:
            tot[name] += e - s
        return max(tot, key=tot.get) if tot else None

    def module_busy_seconds(self, name=None):
        """For each whole execution of program `name` on chip 0, the seconds
        in which one of its operations ran."""
        if not self.devices:
            return []
        dev = self.devices[0]
        name = name or self.main_module()
        busy = dev.busy()
        starts = [s for s, _ in busy]
        out = []
        for s, e, n, _ in dev.modules:
            if n != name:
                continue
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            seg = []
            while i < len(busy) and busy[i][0] < e:
                seg.append(busy[i])
                i += 1
            out.append(measure(clip(seg, s, e)))
        return out

    def exposed_collective_seconds(self) -> float:
        """Seconds with a collective in flight and no other operation
        running, averaged over the chips."""
        if not self.devices:
            return 0.0
        total = 0.0
        for d in self.devices:
            coll = union([(s, e) for s, e, _, op, _ in d.ops
                          if _COLLECTIVE.match(op)]
                         + [(s, e) for s, e, key, op in d.async_ops
                            if _COLLECTIVE.match(key) or _COLLECTIVE.match(op)])
            compute = union((s, e) for s, e, _, op, _ in d.ops
                            if not _COLLECTIVE.match(op))
            total += measure(subtract(coll, compute))
        return total / len(self.devices)

    # ---- host attribution -------------------------------------------------
    def host_offset_s(self) -> float:
        """Add this to a device time to get the host's clock. A program
        cannot start before it was enqueued, so the lag is at least the
        largest (enqueue - device start) over the matched executions."""
        lags = [self.enqueues[rid] - s
                for d in self.devices[:1] for s, _, _, rid in d.modules
                if rid in self.enqueues]
        return max(lags) if lags else 0.0

    def annotated_line(self, marker="benchmark/"):
        """The host thread that opened the benchmark's own annotations."""
        for name, evs in self.host_lines.items():
            if any(n.startswith(marker) for _, _, n in evs):
                return evs
        return []

    def idle_gaps_by_host_span(self, lo=None, hi=None):
        """{host span name: idle seconds of chip 0 under it}. Each instant of
        a gap goes to the innermost annotation open on the benchmark's thread
        at that instant (the one that started last)."""
        if not self.devices:
            return {}
        off = self.host_offset_s()
        busy = [(s + off, e + off) for s, e in self.devices[0].busy()]
        if lo is None:
            lo, hi = busy[0][0], busy[-1][1]
        gaps = subtract([(lo, hi)], clip(busy, lo, hi))
        host = self.annotated_line()
        starts = [s for s, _, _ in host]
        tot = defaultdict(float)
        for gs, ge in gaps:
            # candidates: events that start before the gap ends; walk back
            # over the few that can still cover it
            hi_i = bisect.bisect_left(starts, ge)
            cover = [(s, e, n) for s, e, n in host[max(0, hi_i - 256):hi_i]
                     if e > gs]
            cuts = sorted({gs, ge} | {t for s, e, _ in cover for t in (s, e)
                                      if gs < t < ge})
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                inner = [(s, n) for s, e, n in cover if s <= mid < e]
                name = max(inner)[1] if inner else "_no_host_annotation_open_"
                tot[re.sub(r"[^A-Za-z0-9_:\-/.]", "_", name)] += b - a
        return dict(tot)


def top(table: dict, n=10):
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
