"""Device seconds by `jax.named_scope`, for the work a program leaves to XLA.

`kernel_ops.py` finds a Mosaic kernel in a trace by its instruction's name,
which XLA takes from the scope the Pallas call was made in. What XLA fuses
itself is named `fusion.N`, and a TPU's trace carries the instruction's text
WITHOUT its metadata (PR 61 dumped the events and their statistics: no
`op_name` anywhere), so only the compiled program says which scope an
instruction was traced under: its optimized HLO text keeps `metadata=
{op_name="jit(step)/.../hyper_connection/dot_general" ...}` on every
instruction, a fusion carrying its root's. `install(hlo_texts)` (called by an
adapter whose readers need it, at import) makes the profiler's `result()`
read the same `.xplane.pb` once more before it is deleted, look each of chip
0's operations up by (instruction name, first result's shape) in the programs'
texts (`hlo_texts()`: the adapter's engine's two ticks), and hang `scope_ops`
on the trace: {scope: [(start, end), ...]} for `SCOPES`. A trace read without
it has no such attribute, and the readers that want it leave their metric
out.
"""

from __future__ import annotations

import bisect
import re

SCOPES = ("hyper_connection", "dsa_index", "sparse_latent_attention")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_ARRAY = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_key(text):
    """(name, first result array) of an HLO instruction's text, e.g.
    ("fusion.12", "bf16[64,4096]"); None where it is no instruction."""
    m = _INSTRUCTION.match(text)
    if not m:
        return None
    array = _ARRAY.search(m.group(2))
    return m.group(1), array.group(0) if array else ""


def scoped_instructions(hlo_texts, scopes=SCOPES):
    """{instruction key: scope} over the optimized HLO texts' instructions
    whose `op_name` passes through one of `scopes`."""
    found = {}
    for text in hlo_texts:
        for line in text.splitlines():
            name = _OP_NAME.search(line)
            if not name:
                continue
            parts = name.group(1).split("/")
            scope = next((s for s in scopes if s in parts), None)
            key = instruction_key(line.split(", metadata=")[0])
            if scope and key:
                found[key] = scope
    return found


def scope_ops(path, instructions, scopes=SCOPES):
    """{scope: sorted [(start_s, end_s)]} over the first TPU plane's XLA Ops
    that `instructions` (`scoped_instructions`) places under a scope."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = sorted((p for p in data.planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    found = {s: [] for s in scopes}
    for line in planes[0].lines if planes else []:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            scope = instructions.get(instruction_key(ev.name))
            if scope:
                start = ev.start_ns * 1e-9
                found[scope].append((start, start + ev.duration_ns * 1e-9))
    return {s: sorted(v) for s, v in found.items()}


def install(hlo_texts):
    """Wrap `harness.Profiler.result` (once) so that the trace it returns
    carries `scope_ops`; `hlo_texts()` -> the optimized HLO texts of the
    programs that ran under the profiler."""
    from . import harness, xplane
    if getattr(harness.Profiler, "_keeps_scopes", False):
        return
    result = harness.Profiler.result

    def keeping(self):
        try:
            found = scope_ops(xplane.find_xplane(self.dir),
                              scoped_instructions(hlo_texts()))
        except Exception:       # no trace, no engine, or a text unlike these
            found = None
        trace = result(self)
        if trace is not None and found is not None:
            trace.scope_ops = found
        return trace
    harness.Profiler.result = keeping
    harness.Profiler._keeps_scopes = True


def executions(trace):
    """The tick programs' executions on chip 0, in order: [(start, end)]."""
    from .metrics.hybrid_tick_roofline import tick_modules
    names = set(tick_modules(trace))
    return sorted((s, e) for s, e, name, _ in trace.devices[0].modules
                  if name in names)


def seconds_inside(inside, events):
    """For each interval of `inside` (sorted, disjoint), the seconds of the
    `events` [(start, end)] that started in it."""
    starts = [s for s, _ in inside]
    spent = [0.0] * len(inside)
    for s, e in events:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < inside[i][1]:
            spent[i] += e - s
    return spent


def counted_pairs(run, inside, spent, attrs):
    """(span, seconds) of the traced ticks whose spans carry `attrs`: the
    k-th span from the end is the k-th execution from the end; a tick without
    the counts is left out WITH its seconds."""
    from .metrics.hybrid_tick_roofline import traced_ticks
    return [(s, t) for s, t in zip(reversed(traced_ticks(run, len(inside))),
                                   reversed(spent))
            if all(a in s.attrs for a in attrs)]
