"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: per-device busy time and idle share, the top device ops,
collective time and its exposed part, and the idle gaps attributed to
the benchmark's host spans.

Device ops are the events of the ``XLA Ops`` line of each ``/device:``
plane (TPU), named by their HLO instruction. The ``Async XLA Ops`` line
counts towards collective time only: an asynchronous transfer does not
keep the cores busy. Where a trace has no device plane (the CPU
backend, used by the tests), the events that carry an ``hlo_op`` stat on
host threads are the device ops, grouped by their ``device_ordinal``.
Every time is in seconds, and only the part of an event inside the
window counts.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
Op = Tuple[float, float, str]           # (start_ns, end_ns, name)

COLLECTIVE = re.compile(
    r"^(all[-_]gather|all[-_]reduce|collective[-_]permute|reduce[-_]scatter|"
    r"all[-_]to[-_]all|collective[-_]broadcast|send|recv)"
    r"([-_]start|[-_]done)?([.-]|$)")


def latest_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def _stat(ev, key):
    try:
        for k, v in ev.stats:
            if k == key:
                return v
    except (TypeError, ValueError):
        return None
    return None


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str):
    """(device ops by device, async device ops by device, host events)
    from an ``.xplane.pb``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    async_ops: Dict[str, List[Op]] = {}
    host: List[Op] = []
    fallback: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {"XLA Ops": devices,
                        "Async XLA Ops": async_ops}.get(line.name)
                if into is None:
                    continue
                into[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns,
                     op_name(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    op = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    hlo = _stat(e, "hlo_op")
                    if hlo is not None:
                        dev = f"/host-device:{_stat(e, 'device_ordinal')}"
                        fallback.setdefault(dev, []).append(
                            (op[0], op[1], str(hlo)))
                    else:
                        host.append(op)
    return (devices or fallback), async_ops, host


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def measure(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (unioned) ``a`` not covered by the (unioned) ``b``."""
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


def self_times(ops: Sequence[Op]) -> Dict[str, float]:
    """ns of each op name, less the time of ops nested inside it."""
    out: Dict[str, float] = {}
    stack: List[List] = []             # [end, name, duration, child_ns]

    def close(entry):
        out[entry[1]] = out.get(entry[1], 0.0) + entry[2] - entry[3]
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce(devices: Dict[str, List[Op]], host: List[Op],
           window: Interval, spans: Sequence[str] = ("data", "train_step"),
           top: int = 10, async_ops: Optional[Dict[str, List[Op]]] = None
           ) -> Dict:
    """The window's per-device busy/idle/collective numbers, top ops and
    longest idle gaps (each named by the host span it overlaps most)."""
    lo, hi = window
    async_ops = async_ops or {}
    span_ivs = {n: union(clip([(s, e) for s, e, nm in host if nm == n],
                              lo, hi)) for n in spans}
    per_dev, ops_total, gaps = {}, {}, []
    for dev, ops in sorted(devices.items()):
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in ops
               if min(e, hi) > max(s, lo)]
        busy = union((s, e) for s, e, _ in ops)
        coll = union(clip([(s, e) for s, e, n in ops + async_ops.get(dev, [])
                           if COLLECTIVE.match(n)], lo, hi))
        comp = union((s, e) for s, e, n in ops if not COLLECTIVE.match(n))
        per_dev[dev] = {
            "busy_s": measure(busy) * 1e-9,
            "idle_share": 1.0 - measure(busy) / (hi - lo),
            "collective_s": measure(coll) * 1e-9,
            "exposed_collective_s": measure(subtract(coll, comp)) * 1e-9,
            "ops": len(ops),
        }
        for name, ns in self_times(ops).items():
            ops_total[name] = ops_total.get(name, 0.0) + ns
        for s, e in subtract([(lo, hi)], busy):
            over = {n: measure(clip(iv, s, e)) for n, iv in span_ivs.items()}
            best = max(over, key=over.get) if over else None
            label = best if best and over[best] > 0 else "other"
            gaps.append((e - s, f"{dev}:{label}"))
    top_ops = sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": per_dev,
        "device_ops": [[n, ns * 1e-9] for n, ns in top_ops],
        "idle_gaps": [[label, ns * 1e-9] for ns, label in gaps[:top]],
        "span_s": {n: measure(iv) * 1e-9 for n, iv in span_ivs.items()},
        "span_count": {n: sum(1 for s, e, nm in host if nm == n
                              and lo <= s < hi) for n in spans},
    }
