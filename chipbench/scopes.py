"""From a profiler trace to the program's own scopes and spans: device
self time by named scope, and device idle time by the trainer's host span.

The program names its work in two ways (``docs/resilience.md``). Inside
the traced step, ``jax.named_scope`` puts ``encoder``, ``projector``,
``merge``, ``llm``, ``lm_head``, ``optimizer`` and ``health`` (top level)
and ``attention``, ``sdpa`` and ``mlp`` (inside a layer) into each HLO
instruction's ``op_name`` metadata. Around each step on the host,
``ResilientTrainer.run`` writes ``trainer.*`` spans into the trace.

The device ops of a TPU trace carry their instruction's name and nothing
of its metadata, so an op's scopes come from the compiled step's HLO text
(``hlo_op_names``): instruction name -> ``op_name``, where an instruction
without metadata (a copy XLA put in) takes the ``op_name`` of the
instruction that calls its computation. Backward ops carry the scope in a
wrapper, ``transpose(jvp(llm))``, which ``chain`` strips. A fusion counts
under the scope of its root op. Every time is in seconds, only the part of
an op inside the window counts, and device numbers are the mean over the
devices.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence

import trace_reduce as tr

#: the step's top-level scopes, then those inside a layer
TOP = ("encoder", "projector", "merge", "llm", "lm_head", "optimizer",
       "health")
INNER = ("attention", "sdpa", "mlp")
#: the prefix of the trainer's host spans
SPAN_PREFIX = "trainer."
#: the chain of a device op under no scope, and of one not in the module
NONE, UNMATCHED = "none", "unmatched"

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")


def strip(component: str) -> str:
    """``transpose(jvp(llm))`` -> ``llm``: a path component without the
    transformations JAX wraps around it."""
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def chain(op_name: str) -> str:
    """The program's scopes in an ``op_name``, outermost first, joined by
    ``/`` (``llm/attention/sdpa``); ``none`` under none. Of a fused list
    (``a;b``) the first, its root."""
    path = op_name.split(";", 1)[0].split("/")
    return "/".join(c for c in map(strip, path) if c in TOP + INNER) \
        or NONE


def top(chain_: str) -> str:
    """The chain's top-level scope, or an empty string under none."""
    first = chain_.split("/", 1)[0]
    return first if first in TOP else ""


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled module's HLO text."""
    own: Dict[str, str] = {}
    where: Dict[str, str] = {}          # instruction -> its computation
    caller: Dict[str, str] = {}         # computation -> calling instruction
    comp = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c and not line.startswith("HloModule"):
                comp = c.group(1)
            continue
        name = m.group(1)
        where[name] = comp
        meta = _OP_NAME.search(line)
        if meta:
            own[name] = meta.group(1)
        for c in _CALLS.findall(line):
            caller.setdefault(c, name)

    def resolve(name: str, seen=()) -> str:
        if name in own:
            return own[name]
        up = caller.get(where.get(name, ""))
        if up is None or up in seen:
            return ""
        return resolve(up, seen + (name,))
    return {name: resolve(name) for name in where}


def innermost(spans: Sequence[tr.Op], lo: float, hi: float
              ) -> List[tr.Op]:
    """[lo, hi) cut into (start, end, label) pieces, each labelled with
    the innermost span over it (the latest to start, then the first to
    end), ``none`` where no span is."""
    spans = [(max(s, lo), min(e, hi), n) for s, e, n in spans
             if min(e, hi) > max(s, lo)]
    points = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)})
    out: List[tr.Op] = []
    for a, b in zip(points, points[1:]):
        over = [(s, -e, n) for s, e, n in spans if s <= a and e >= b]
        label = max(over)[2] if over else "none"
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def split(intervals: Iterable[tr.Interval], pieces: Sequence[tr.Op]
          ) -> Dict[str, float]:
    """ns of the (sorted, disjoint) ``intervals`` under each label of the
    (sorted, disjoint) ``pieces``."""
    out: Dict[str, float] = {}
    j = 0
    for s, e in intervals:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, label = pieces[k]
            ns = min(b, e) - max(a, s)
            if ns > 0:
                out[label] = out.get(label, 0.0) + ns
            k += 1
    return out


def reduce(devices: Dict[str, List[tr.Op]], host: List[tr.Op],
           window: tr.Interval, op_names: Dict[str, str]) -> Dict:
    """Device self time by scope chain (``scope_s``), the shares of busy
    time found in the module and under no top-level scope, the trainer's
    spans (seconds and count in the window) and the device idle time
    under each innermost trainer span (``idle_by_span_s``)."""
    lo, hi = window
    ndev = max(len(devices), 1)
    spans = [op for op in host if op[2].startswith(SPAN_PREFIX)]
    pieces = innermost(spans, lo, hi)
    scope_ns: Dict[str, float] = {}
    idle_ns: Dict[str, float] = {}
    for ops in devices.values():
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in ops
               if min(e, hi) > max(s, lo)]
        for name, ns in tr.self_times(ops).items():
            key = chain(op_names[name]) if name in op_names else UNMATCHED
            scope_ns[key] = scope_ns.get(key, 0.0) + ns
        idle = tr.subtract([(lo, hi)], tr.union((s, e) for s, e, _ in ops))
        for label, ns in split(idle, pieces).items():
            idle_ns[label] = idle_ns.get(label, 0.0) + ns
    busy = sum(scope_ns.values()) or 1.0

    def span(name):
        ivs = [(s, e) for s, e, n in spans if n == name]
        return {"s": tr.measure(tr.union(tr.clip(ivs, lo, hi))) * 1e-9,
                "count": sum(1 for s, _ in ivs if lo <= s < hi)}
    return {
        "scope_s": {k: ns * 1e-9 / ndev
                    for k, ns in sorted(scope_ns.items())},
        "matched_share": 1.0 - scope_ns.get(UNMATCHED, 0.0) / busy,
        "unscoped_share": sum(ns for k, ns in scope_ns.items()
                              if not top(k)) / busy,
        "spans": {n: span(n) for n in sorted({n for _, _, n in spans})},
        "idle_by_span_s": {k: ns * 1e-9 / ndev
                           for k, ns in sorted(idle_ns.items())},
    }
