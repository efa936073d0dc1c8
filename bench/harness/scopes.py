"""Device time per named part of the program, from a trace.

The program names the parts of its offloaded step with ``jax.named_scope``
(``SCOPES`` and ``phase_of``, taken from ``repro.core.offload`` so that
the names are written once).  A scope lands in the ``op_name`` metadata of
every HLO op the part lowers to (``jit(step_fn)/transpose(jvp())/while/
body/closed_call/checkpoint/rematted_computation/chain.segment/...``), and
inside a segment JAX's own markers tell the three sweeps apart
(``phase_of``).  A TPU trace's op events carry no ``op_name`` (their stats
hold only the device offset and duration), so it is read from the
``metadata={op_name=...}`` of the same instruction in the compiled step's
HLO text (``op_names_from_hlo``).  A step loaded from the persistent
compile cache keeps the metadata of the compile that filled it: the cache
key leaves metadata out.

Time is given out by ``exclusive_ns``: each instant of a device's busy
time goes to the innermost work op running then, so an event that holds
others (a container such as a called computation's ``region.*``) gets
only its own time, nothing is counted twice, and the parts sum to at most
the busy time of ``harness.trace``.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

from harness import trace as tr
from repro.core.offload import (SCOPE_OPTIMIZER as OPTIMIZER,
                                SCOPE_PRELUDE as PRELUDE,
                                SCOPE_READOUT as READOUT,
                                SCOPE_SEGMENT as SEGMENT, SCOPE_SSD as SSD,
                                SCOPES, phase_of)

# name -> (scopes, phase or None for all phases), in ms per step
PARTS: Dict[str, Tuple[Tuple[str, ...], Optional[str]]] = {
    "chain_fwd_ms": ((SEGMENT,), "forward"),
    "recompute_ms": ((SEGMENT,), "recompute"),
    "chain_bwd_ms": ((SEGMENT,), "backward"),
    "optimizer_ms": ((OPTIMIZER,), None),
    "head_ms": ((PRELUDE, READOUT), None),
    "ssd_ms": ((SSD,), None),
}
# the parts that split the step between them (``ssd`` lies inside segments)
COVER = ("chain_fwd_ms", "recompute_ms", "chain_bwd_ms", "optimizer_ms",
         "head_ms")


# a scope is a whole component of the path, bare or wrapped in a transform
# (``jvp(chain.prelude)``)
_SCOPE_RES = {s: re.compile(r"(?:^|[/(])" + re.escape(s) + r"(?:$|[/)])")
              for s in SCOPES}


def in_scope(op_name: str, scope: str) -> bool:
    return _SCOPE_RES[scope].search(op_name) is not None


def selects(op_name: Optional[str], scopes: Iterable[str],
            phase: Optional[str]) -> bool:
    if not op_name or not any(in_scope(op_name, s) for s in scopes):
        return False
    return phase is None or phase_of(op_name) == phase


_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
_CALLEES = re.compile(r"\b(?:body|condition|calls|to_apply|branch_computations"
                      r"|true_computation|false_computation)="
                      r"(\{[^}]*\}|%?[\w.\-]+)")


def op_names_from_hlo(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata, for every
    instruction of the compiled module's text.  An instruction with none
    of its own (the compiler's copies, prefetches and layout changes) takes
    that of the instruction that calls its computation (a loop's body, a
    called computation), and so on up; one in the entry computation with
    none stays out."""
    own: Dict[str, str] = {}
    computation_of: Dict[str, str] = {}
    caller: Dict[str, str] = {}        # computation -> calling instruction
    comp = None
    for line in hlo_text.splitlines():
        if line[:1] and not line[0].isspace():
            m = _HEADER.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if comp is None or not m:
            continue
        name = m.group(1)
        computation_of[name] = comp
        meta = _OP_NAME.search(line)
        if meta:
            own[name] = meta.group(1)
        for c in _CALLEES.finditer(line):
            for callee in re.findall(r"[\w.\-]+", c.group(1)):
                caller.setdefault(callee, name)
    out = {}
    for name in computation_of:
        n = name
        while n is not None and n not in own:
            n = caller.get(computation_of[n])
        if n is not None:
            out[name] = own[n]
    return out


def exclusive_ns(ops: List[tr.Event], lo: int, hi: int) -> Dict[str, int]:
    """Busy time inside [lo, hi] given out to HLO names: each instant to
    the innermost work op running then (the one that started last).  The
    values sum to ``harness.trace.busy_ns(ops, lo, hi)``."""
    out: Dict[str, int] = {}
    t = lo
    stack: List[Tuple[str, int]] = []          # open ops: (name, end)

    def give(name: str, until: int) -> None:
        nonlocal t
        until = min(until, hi)
        if until > t:
            out[name] = out.get(name, 0) + until - t
            t = until

    for name, s, d in sorted(tr.work_ops(ops), key=lambda e: (e[1], -e[2])):
        if s >= hi:
            break
        while stack and stack[-1][1] <= s:
            give(*stack.pop())
        if stack:
            give(stack[-1][0], s)
        t = max(t, min(s, hi))
        stack.append((name, s + d))
    while stack:
        give(*stack.pop())
    return out


def name_ns(trace: tr.Trace) -> Dict[str, int]:
    """``exclusive_ns`` inside the window, summed over devices."""
    lo, hi = tr.window_of(trace)
    out: Dict[str, int] = {}
    for ops in trace.device_ops.values():
        for name, ns in exclusive_ns(ops, lo, hi).items():
            out[name] = out.get(name, 0) + ns
    return out


def _per_step_ms(trace: tr.Trace) -> float:
    return len(trace.device_ops) * tr.steps_in(trace) * 1e6


def _covered(op_name: Optional[str]) -> bool:
    return any(selects(op_name, *PARTS[p]) for p in COVER)


def part_ms(trace: tr.Trace, op_names: Dict[str, str],
            ns: Optional[Dict[str, int]] = None) -> Dict[str, float]:
    """Each part of ``PARTS``, ``busy_ms`` and ``unscoped_ms`` (busy time
    in no part of ``COVER``), in ms per traced step, clipped to the window
    and averaged over devices.  ``ns`` is ``name_ns(trace)``, if at hand."""
    ns = name_ns(trace) if ns is None else ns
    sums = dict.fromkeys(list(PARTS) + ["busy_ms", "unscoped_ms"], 0)
    for name, t in ns.items():
        op = op_names.get(name)
        sums["busy_ms"] += t
        for part, (scopes, phase) in PARTS.items():
            if selects(op, scopes, phase):
                sums[part] += t
        if not _covered(op):
            sums["unscoped_ms"] += t
    k = _per_step_ms(trace)
    return {part: t / k for part, t in sums.items()}


def unscoped_ops(trace: tr.Trace, op_names: Dict[str, str], n: int = 10,
                 ns: Optional[Dict[str, int]] = None) -> List[List]:
    """The ``n`` work ops with most busy time in no part of ``COVER``: HLO
    name, ms per step, ``op_name``."""
    ns = name_ns(trace) if ns is None else ns
    k = _per_step_ms(trace)
    ranked = sorted(((name, t) for name, t in ns.items()
                     if not _covered(op_names.get(name))),
                    key=lambda kv: -kv[1])[:n]
    return [[name, t / k, op_names.get(name)] for name, t in ranked]


def label(op_name: Optional[str]) -> str:
    """``<innermost scope>/<phase>`` of an op, or ``unscoped``."""
    last = max(((m.start(), s) for s in SCOPES
                for m in _SCOPE_RES[s].finditer(op_name or "")), default=None)
    return "unscoped" if last is None else f"{last[1]}/{phase_of(op_name)}"


def while_gaps(gaps: List[List], op_names: Dict[str, str]) -> List[List]:
    """Those of ``gaps`` (``harness.trace.idle_gaps``) that lie inside a
    control-flow op, each with the scope and phase of that op: ``in
    <op>``, seconds, ``label``."""
    return [[name, s, label(op_names.get(name[3:]))]
            for name, s in gaps if name.startswith("in ")]
