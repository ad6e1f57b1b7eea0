"""Device time by the program's named scopes, from a traced run.

The program wraps some of its model code in ``jax.named_scope``s
(``repro.core.spans.SCOPES``).  A scope's name lands in the name stack of
every operation traced inside it, which XLA keeps as each HLO op's
``op_name`` metadata and the TPU trace as a stat of each device op on the
``XLA Ops`` line (``tf_op``; ``NAME_STACK_STATS`` lists the stats read, in
order).  A trace keeps such per-op stats on the op's event metadata, which
``jax.profiler.ProfileData`` does not expose, so this module reads the
device planes' metadata from the file's protobuf wire format itself
(``XSpace``: planes 1; ``XPlane``: name 2, event_metadata 4,
stat_metadata 5; ``XEventMetadata``: name 2, stats 5; ``XStat``:
metadata_id 1, str_value 5, ref_value 7 naming a stat metadata) and takes
the events' times from ``ProfileData``.  It reads the newest
``.xplane.pb`` under ``<checkout>/.chipbench/trace`` once per process and
keeps, for the first chip, each op's interval and name stack.

``load(raw)`` returns None on an untraced run and where the trace has no
device plane (a CPU run).  ``seconds_in(scope, lo, hi)`` is 0 where no op
carries the scope (a program without it).
"""
from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Tuple

import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
NAME_STACK_STATS = ("tf_op", "long_name", "name")

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Op = Tuple[float, float, str]          # start_s, end_s, name stack


def name_stack(stats: Dict[str, object]) -> Tuple[Optional[str], str]:
    """(stat, value) of the first name-stack stat an op event carries."""
    for key in NAME_STACK_STATS:
        value = stats.get(key)
        if isinstance(value, str) and value:
            return key, value
    return None, ""


def in_scope(stack: str, scope: str) -> bool:
    """Whether ``scope`` is a whole component of a name stack."""
    return scope in stack.split("/")


class OpScopes:
    """The first chip's device ops of one traced window, with their
    name stacks, sorted by start."""

    def __init__(self, ops: List[Op], stat: Optional[str]):
        self.ops = sorted(ops)
        self.stat = stat

    def seconds_in(self, scope: str, lo: float, hi: float) -> float:
        """Device seconds of the ops that start in [lo, hi) and carry
        ``scope``."""
        return sum(e - s for s, e, stack in self.ops
                   if lo <= s < hi and in_scope(stack, scope))


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for varints,
    a memoryview for length-delimited fields (fixed-width ones skipped)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            i += 8 if wire == 1 else 4
            continue
        yield field, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def metadata_stats(data: bytes) -> Dict[str, Dict[str, Dict[str, str]]]:
    """Per device plane: each event metadata name's string stats."""
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, value in _fields(plane):
            if f == 2:
                name = _text(value)
            elif f == 4:
                events.append(value)
            elif f == 5:
                entry = dict(_fields(value))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))
        if not _DEVICE_PLANE.match(name):
            continue
        table = out.setdefault(name, {})
        for entry in events:
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            ev_name = next((_text(v) for f, v in meta if f == 2), "")
            stats = {}
            for f, stat in meta:
                if f != 5:
                    continue
                st = dict(_fields(stat))
                key = stat_names.get(st.get(1, 0), "")
                if 5 in st:                           # str_value
                    stats[key] = _text(st[5])
                elif 7 in st:                         # ref_value: a name
                    stats[key] = stat_names.get(st[7], "")
            table[ev_name] = stats
    return out


def read_ops(path: str) -> Tuple[List[Op], Optional[str]]:
    """(ops of the first chip, the stat that held their name stacks):
    each event's own stats over its metadata's."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    meta = metadata_stats(data)
    chips: Dict[int, List[Op]] = {}
    used = None
    for plane in ProfileData.from_serialized_xspace(data).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = chips.setdefault(int(m.group(1)), [])
        table = meta.get(plane.name, {})
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                stats = dict(table.get(ev.name, {}))
                stats.update(ev.stats)
                key, stack = name_stack(stats)
                used = used or key
                ops.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9, stack))
    return (chips[min(chips)] if chips else []), used


_cache: Dict[str, Optional[OpScopes]] = {}


def load(raw) -> Optional[OpScopes]:
    """This run's :class:`OpScopes`; None on an untraced run or without
    a device plane.  Read once per process."""
    if raw.get("trace") is None:
        return None
    path = trace_reduce.find_xplane(TRACE_DIR)
    if path is None:
        return None
    key = f"{path}:{os.path.getmtime(path)}"
    if key not in _cache:
        ops, stat = read_ops(path)
        _cache[key] = OpScopes(ops, stat) if ops else None
        print(f"op scopes: {len(ops)} device ops, name stacks from stat "
              f"{stat!r}", file=sys.stderr, flush=True)
    return _cache[key]
