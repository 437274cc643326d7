"""From a profiler trace to the numbers the per-layer metrics read.

``reduce_trace`` reads one ``.xplane.pb`` (``jax.profiler.ProfileData``)
of a traced window and returns, per chip:

- device busy time: the union of the intervals of the ops on the
  device's "XLA Ops" line, clipped to the window;
- device time per layer scope (see ``classify``);
- all-to-all time during which no other op runs;
- idle gaps, each named by the benchmark's host span
  (``jax.profiler.TraceAnnotation``) that overlaps it most;
- device time per op.

The window runs from the start of the first host span to the end of the
last.  An op's scope is read from its HLO metadata: ``op_names`` maps an
HLO instruction name to its ``op_name`` (``hlo_op_names`` parses it from
the compiled program's text); where the trace itself carries a
``tf_op`` stat, that wins.
"""

from __future__ import annotations

import collections
import dataclasses
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
_A2A = re.compile(r"all[-_]to[-_]all")
HOST_SPANS = ("bench_batch", "bench_dispatch", "bench_block")

_META = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                   r'metadata=\{[^}]*?op_name="([^"]*)"')
_EVENT = re.compile(r'^%?([\w.\-]+)(?:\s*=\s*([\w\[\],]+))?')


@dataclasses.dataclass
class Chip:
    name: str
    busy_s: float = 0.0
    scope_s: dict = dataclasses.field(
        default_factory=lambda: collections.defaultdict(float))
    a2a_exposed_s: float = 0.0
    op_s: dict = dataclasses.field(
        default_factory=lambda: collections.defaultdict(float))
    gaps: list = dataclasses.field(default_factory=list)   # (span, s)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: list

    def busy_s(self) -> float:
        """Mean over chips."""
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    def scope_s(self, scope: str) -> float:
        """Summed over chips."""
        return sum(c.scope_s.get(scope, 0.0) for c in self.chips)

    def worst_idle_share(self) -> float:
        return max(1.0 - c.busy_s / self.window_s for c in self.chips)

    def top_ops(self, n: int = 10) -> list:
        """[[op, seconds]]: device time per op, mean over chips."""
        tot = collections.defaultdict(float)
        for c in self.chips:
            for k, v in c.op_s.items():
                tot[k] += v / len(self.chips)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        gaps = [[f"{span}@{c.name}", s] for c in self.chips
                for span, s in c.gaps]
        return sorted(gaps, key=lambda g: -g[1])[:n]


def hlo_op_names(hlo_text: str) -> dict:
    """HLO instruction name -> ``op_name`` metadata."""
    out = {}
    for line in hlo_text.splitlines():
        m = _META.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def classify(op: str, op_name: str, lookup_scope: str,
             update_scope: str) -> str:
    """``a2a``, ``emb_fwd`` (the lookup), ``emb_bwd`` (its transpose and
    the embedding update) or ``other``."""
    if _A2A.search(op) or _A2A.search(op_name):
        return "a2a"
    if update_scope in op_name or (lookup_scope in op_name
                                   and "transpose(" in op_name):
        return "emb_bwd"
    if lookup_scope in op_name:
        return "emb_fwd"
    return "other"


def _union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _minus(a, b):
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def op_of(event_name: str) -> tuple:
    """(HLO instruction name, its result type) of a device op event, whose
    name is the instruction's text (``%fusion.12 = bf16[...] fusion(...)``)
    or the bare instruction name."""
    m = _EVENT.match(event_name)
    return (m.group(1), m.group(2) or "") if m else (event_name, "")


def reduce_trace(profile, op_names: dict, lookup_scope: str,
                 update_scope: str, spans=HOST_SPANS) -> TraceSummary:
    host = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    host.append((ev.start_ns, ev.end_ns, ev.name))
    if not host or not devices:
        raise ValueError(f"trace holds {len(host)} host spans and "
                         f"{len(devices)} device planes")
    lo = min(s for s, _, _ in host)
    hi = max(e for _, e, _ in host)
    chips = []
    for plane in sorted(devices, key=lambda p: p.name):
        chip = Chip(plane.name[len("/device:"):])
        ops, a2a, other = [], [], []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.end_ns <= lo or ev.start_ns >= hi:
                    continue
                op, result = op_of(ev.name)
                name = _stats(ev).get("tf_op") or op_names.get(op, "")
                scope = classify(op, name, lookup_scope, update_scope)
                span = (max(ev.start_ns, lo), min(ev.end_ns, hi))
                secs = (span[1] - span[0]) * 1e-9
                chip.scope_s[scope] += secs
                chip.op_s[f"{op} {result.split('{')[0]} [{scope}]"] += secs
                ops.append(span)
                (a2a if scope == "a2a" else other).append(span)
        busy = _union(ops)
        chip.busy_s = _length(busy) * 1e-9
        chip.a2a_exposed_s = _minus(_union(a2a), _union(other)) * 1e-9
        prev = lo
        for s, e in busy + [[hi, hi]]:
            if s > prev:
                chip.gaps.append((_name_gap(prev, s, host),
                                  (s - prev) * 1e-9))
            prev = max(prev, e)
        chips.append(chip)
    return TraceSummary((hi - lo) * 1e-9, chips)


def _name_gap(start, end, host) -> str:
    best, name = 0, "host:unspanned"
    for s, e, n in host:
        overlap = min(e, end) - max(s, start)
        if overlap > best:
            best, name = overlap, n
    return name


def load(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)
