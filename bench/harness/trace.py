"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is first cut down to a small record (``Trace``): per device, the
events of its ``XLA Ops`` line (name, start, duration in ns), and the
benchmark's own host spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``).  Host and device events share the
profiler's clock.  Everything below works on that record, so the
reduction is tested on a small recorded trace without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)
HOST_PREFIX = "bench."
STEP_SPAN = "bench.step"


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]
    host_spans: List[Event]

    def to_json(self) -> dict:
        return {"device_ops": self.device_ops, "host_spans": self.host_spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v]
                    for k, v in d["device_ops"].items()},
                   [tuple(e) for e in d["host_spans"]])


def load_xplane(trace_dir: str) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[plane.name] = [
                        (op_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Trace(device_ops, sorted(host, key=lambda e: e[1]))


def op_name(event_name: str) -> str:
    """The HLO name of a device op event.  A TPU trace names each op by
    its whole HLO text (``%fusion.12 = bf16[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def window_of(trace: Trace) -> Tuple[int, int]:
    """[start of the first step span, end of the last one]."""
    steps = [e for e in trace.host_spans if e[0] == STEP_SPAN]
    if not steps:
        raise ValueError("trace holds no bench.step span")
    return steps[0][1], max(s + d for _, s, d in steps)


def steps_in(trace: Trace) -> int:
    return sum(1 for e in trace.host_spans if e[0] == STEP_SPAN)


def _merge(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
           ) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


CONTROL_FLOW = re.compile(r"(while|conditional|call)(\.\d+)?$")


def work_ops(ops: List[Event]) -> List[Event]:
    """All ops but the control-flow ones (``while``, ``conditional``,
    ``call``).  A TPU trace gives a while loop an event of its own that
    spans the whole loop, gaps between its body ops included, and nests
    the body ops inside it; a fusion's event may hold a marker of no
    length, and is work all the same."""
    return [e for e in ops if not CONTROL_FLOW.match(e[0])]


def busy_intervals(ops: List[Event], lo: int, hi: int
                   ) -> List[Tuple[int, int]]:
    """Where some op ran: the union of the work ops' intervals."""
    return _merge(((s, s + d) for _, s, d in work_ops(ops)), lo, hi)


def busy_ns(ops: List[Event], lo: int, hi: int) -> int:
    return sum(e - s for s, e in busy_intervals(ops, lo, hi))


def mean_busy_ns(trace: Trace, devices: Optional[List[str]] = None) -> float:
    """Busy time inside the window, averaged over devices."""
    lo, hi = window_of(trace)
    devices = devices or sorted(trace.device_ops)
    return sum(busy_ns(trace.device_ops[d], lo, hi)
               for d in devices) / len(devices)


def op_ns(ops: List[Event], match: Callable[[str], bool], lo: int, hi: int
          ) -> int:
    """Summed duration (clipped to the window) of ops whose name matches."""
    return sum(max(0, min(s + d, hi) - max(s, lo))
               for n, s, d in ops if match(n))


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The work ops that took most device time inside the window (seconds,
    averaged over devices), by HLO name."""
    lo, hi = window_of(trace)
    tot: Dict[str, int] = {}
    for ops in trace.device_ops.values():
        for name, s, d in work_ops(ops):
            ns = min(s + d, hi) - max(s, lo)
            if ns > 0:
                tot[name] = tot.get(name, 0) + ns
    k = len(trace.device_ops)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Idle time of the first device inside the window, summed by what
    held each gap at its midpoint: ``in <op>`` inside a control-flow op
    (between a while loop's body ops), else the innermost benchmark host
    span.  The ``n`` largest sums, in seconds."""
    lo, hi = window_of(trace)
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    gaps = []
    t = lo
    for s, e in busy_intervals(ops, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    holders = sorted((e for e in ops if CONTROL_FLOW.match(e[0])),
                     key=lambda e: (e[1], -e[2]))
    tot: Dict[str, int] = {}
    stack: List[Event] = []           # the holders open at the gap
    i = 0
    for s, e in gaps:                 # in order of time
        mid = (s + e) // 2
        while i < len(holders) and holders[i][1] <= mid:
            stack.append(holders[i])
            i += 1
        while stack and stack[-1][1] + stack[-1][2] <= mid:
            stack.pop()
        if stack:
            name = f"in {stack[-1][0]}"
        else:
            inner = [h for h in trace.host_spans
                     if h[1] <= mid < h[1] + h[2]]
            name = min(inner, key=lambda h: h[2])[0] if inner else "no span"
        tot[name] = tot.get(name, 0) + e - s
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


# ---------------------------------------------------------------- HLO names

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s"
                    r"((?:copy|dynamic-slice|dynamic-update-slice|async)"
                    r"-(?:start|done))\((%?[\w.\-]+)?")


def host_copy_names(hlo_text: str) -> set:
    """Names of the async copies that move data between device and host
    memory (memory space ``S(5)`` on a TPU): each ``*-start`` of a copy,
    dynamic slice or dynamic update slice (the chip's compiler prints the
    last two as ``async-start``) whose line names ``S(5)``, and the
    ``*-done`` that completes it."""
    starts, dones = set(), []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, op, operand = m.groups()
        if op.endswith("-start"):
            if "S(5)" in line:
                starts.add(name)
        else:
            dones.append((name, (operand or "").lstrip("%"), "S(5)" in line))
    return starts | {n for n, src, host in dones if host or src in starts}


_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE = re.compile(r"\S*\s+([a-z][\w\-]*)\(")


def opcodes_from_hlo(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its opcode (``fusion``, ``all-reduce-start``,
    ...), for every instruction of the module's text.  An instruction reads
    ``%name = <shape> <opcode>(<operands>), ...``, where a tuple's shape is
    itself in parentheses."""
    out = {}
    for line in hlo_text.splitlines():
        m = _DEF.match(line)
        if not m:
            continue
        i = m.end()
        if line.startswith("(", i):           # a tuple shape
            depth = 0
            for i in range(i, len(line)):
                depth += {"(": 1, ")": -1}.get(line[i], 0)
                if depth == 0:
                    break
            i += 1
        op = _OPCODE.match(line, i)
        if op:
            out[m.group(1)] = op.group(1)
    return out


def dump(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)
