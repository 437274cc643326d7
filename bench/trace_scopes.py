"""Device time per program scope, from a profiler trace of a window.

The program names its layers with ``jax.named_scope`` (``dlrm.*``,
``emb.*``); XLA keeps the name stack in each HLO instruction's
``op_name`` metadata, where autodiff wraps a scope as ``jvp(<scope>)``
for the forward and ``transpose(jvp(<scope>))`` for the backward.
``scope_times`` reads the same ``.xplane.pb`` as ``bench.trace_reduce``
(its ``load``, ``hlo_op_names``, device planes, ops line and window of
host spans) and sums each device op's time, clipped to the window, into
every scope its ``op_name`` names, split into the forward and the
backward (``transpose(`` in the op's name stack) and summed over the
chips.  An op with no ``op_name`` counts under no scope.
"""

from __future__ import annotations

import collections
import re

from bench.trace_reduce import DEVICE_PREFIX, HOST_SPANS, OPS_LINE, op_of

_WRAPPED = re.compile(r"[\w.\-]+\((.*)\)")


def scopes_of(op_name: str) -> set:
    """The scope names in an ``op_name``, ``jvp(...)`` and
    ``transpose(...)`` unwrapped."""
    out = set()
    for seg in re.split(r"[/;]", op_name):
        while (m := _WRAPPED.fullmatch(seg)):
            seg = m.group(1)
        out.add(seg)
    return out


def scope_times(profile, op_names: dict, spans=HOST_SPANS) -> dict:
    """{scope: {"fwd": seconds, "bwd": seconds}} over the window."""
    host = [(ev.start_ns, ev.end_ns) for plane in profile.planes
            if not plane.name.startswith(DEVICE_PREFIX)
            for line in plane.lines for ev in line.events
            if ev.name in spans]
    if not host:
        raise ValueError("trace holds no host spans of the window")
    lo, hi = min(s for s, _ in host), max(e for _, e in host)
    out = collections.defaultdict(lambda: {"fwd": 0.0, "bwd": 0.0})
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                start, end = max(ev.start_ns, lo), min(ev.end_ns, hi)
                if end <= start:
                    continue
                stats = {k: v for k, v in ev.stats}
                name = stats.get("tf_op") or op_names.get(
                    op_of(ev.name)[0], "")
                part = "bwd" if "transpose(" in name else "fwd"
                for scope in scopes_of(name) if name else ():
                    out[scope][part] += (end - start) * 1e-9
    return dict(out)


def total(times: dict, scope: str, part: str | None = None) -> float:
    """Seconds under ``scope``: its forward, backward or both."""
    t = times.get(scope, {"fwd": 0.0, "bwd": 0.0})
    return t["fwd"] + t["bwd"] if part is None else t[part]
