"""From a JAX profiler trace to device busy time, collective time, the top
device operations and the idle gaps labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` the profiler writes into plain interval
lists; ``summarize`` is pure arithmetic on those lists, so it is checked on
a small recorded trace (``tests/data``).

Device time is the union of the intervals of the device's ``XLA Ops``
events; busy time is averaged over the chips the run used.  Host spans are
the benchmark's own ``jax.profiler.TraceAnnotation``s, named ``bench.*``;
``bench.window`` encloses the traced part of the window.

Each op's self time is also kept under the innermost ``rafi.*`` scope of
its ``op_name`` (the program's ``jax.named_scope``s), or ``unscoped``.  The
profiler records an op's ``op_name`` in the op's event metadata (the stat
``tf_op``), which ``load`` reads from the file itself, as ``ProfileData``
does not show event metadata.  A program loaded from the persistent compile
cache carries the ``op_name``s of whichever program was compiled into it
first, so the scopes are those of this tree only where the checkout's cache
was written by this tree.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]  # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVES = (
    "all-to-all", "all-reduce", "all-gather", "collective-permute",
    "reduce-scatter",
)
OP_NAME_STAT = "tf_op"  # the stat of an op's event metadata that holds its op_name
SCOPE = re.compile(r"rafi\.\w+")
UNSCOPED = "unscoped"


@dataclasses.dataclass
class Events:
    device: Dict[str, List[Interval]]  # plane name -> its ops
    host: List[Interval]               # bench.* spans, every host thread
    op_names: Dict[str, str] = dataclasses.field(default_factory=dict)  # op -> its op_name


@dataclasses.dataclass
class Summary:
    busy_s: float                   # mean over chips of the union of op intervals
    window_s: float                 # length of the traced window
    collective_s: float             # mean over chips of the union of collective ops
    top_ops: List[Tuple[str, float]]    # op label, self seconds per chip
    idle_gaps: List[Tuple[str, float]]  # host span at the gap, seconds
    scopes: Dict[str, float]            # rafi.* scope or unscoped -> self seconds per chip


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    device: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(HOST_PREFIX)
                )
    return Events(device=device, host=host, op_names=recorded_op_names(raw))


# A few fields of the profiler's XSpace message (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANE = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_METADATA_NAME, _EVENT_METADATA_STATS = 2, 5
_STAT_METADATA_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of one protobuf message: a varint
    as an int, any other field as the memoryview of its bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in a profiler trace")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def recorded_op_names(raw: bytes) -> Dict[str, str]:
    """The ``op_name`` of each device op that the trace records one for,
    keyed by the op's name (its HLO text, as ``XLA Ops`` events are named)."""
    out: Dict[str, str] = {}
    for num, plane in _fields(memoryview(raw)):
        if num != _SPACE_PLANE:
            continue
        name, events, stat_names = "", [], {}
        for n, v in _fields(plane):
            if n == _PLANE_NAME:
                name = _text(v)
            elif n == _PLANE_EVENT_METADATA:
                events.append(v)
            elif n == _PLANE_STAT_METADATA:
                entry = dict(_fields(v))
                stat = dict(_fields(entry.get(_MAP_VALUE, b"")))
                stat_names[entry.get(_MAP_KEY, 0)] = _text(stat.get(_METADATA_NAME, b""))
        if not name.startswith("/device:") or "CPU" in name:
            continue
        for entry in events:
            meta = dict(_fields(entry)).get(_MAP_VALUE, b"")
            op, op_name = "", None
            for n, v in _fields(meta):
                if n == _METADATA_NAME:
                    op = _text(v)
                elif n == _EVENT_METADATA_STATS:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(_STAT_METADATA_ID, 0)) != OP_NAME_STAT:
                        continue
                    if _STAT_STR in stat:
                        op_name = _text(stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        op_name = stat_names.get(stat[_STAT_REF])
            if op_name:
                out[op] = op_name
    return out


def scope_of(op_name: Optional[str]) -> str:
    """The innermost ``rafi.*`` scope named in ``op_name``, or ``unscoped``."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def scope_ms_per_round(run, scope: str) -> Optional[float]:
    """Device milliseconds per forwarding round in ``scope`` over the traced
    jobs of ``run`` (a ``harness.Run``), or None where the run holds no
    trace, the scope no time or the jobs no round."""
    if run.trace is None:
        return None
    seconds = run.trace.scopes.get(scope)
    rounds = sum(j["rounds"] for j in run.traced)
    return seconds / rounds * 1e3 if seconds and rounds else None


def union(spans: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(spans) -> float:
    return sum(e - s for s, e in spans)


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def op_label(name: str) -> str:
    """``%fusion.146 fusion/kCustom u32[128000,2]`` from the HLO text an
    ``XLA Ops`` event is named by: name, opcode, fusion kind, result shape."""
    lhs, _, rhs = name.partition(" = ")
    opcode = re.search(r"\s([a-z][\w-]*)\(", " " + rhs)
    kind = re.search(r"kind=(\w+)", rhs)
    shape = "" if rhs.startswith("(") else re.sub(r"\{[^}]*\}", "", rhs.split(" ", 1)[0])
    parts = [lhs.strip()] + ([opcode.group(1) + (f"/{kind.group(1)}" if kind else "")] if opcode else [])
    return " ".join(parts + ([shape] if shape else []))


def self_times(ops: List[Interval]) -> Dict[str, float]:
    """Nanoseconds each op ran outside the ops nested in it (a ``while``
    holds its body's ops on the same line)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []  # [name, end, self]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, t = stack.pop()
            out[n] += t
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    for n, _, t in stack:
        out[n] += t
    return out


def _label(host: List[Interval], t: float) -> str:
    """The innermost benchmark span that holds time ``t``."""
    best: Optional[Interval] = None
    for span in host:
        if span[0] != WINDOW_SPAN and span[1] <= t <= span[2]:
            if best is None or span[2] - span[1] < best[2] - best[1]:
                best = span
    return best[0] if best else "host"


def summarize(ev: Events, window_s: float, top: int = 10) -> Optional[Summary]:
    """Busy, collective and idle time of the traced window, and device
    time per scope, or None when the trace holds no accelerator.  ``window_s`` is the window's length on
    the host clock; the gaps are measured inside the ``bench.window`` span
    on the first chip's timeline."""
    if not ev.device:
        return None
    busy, coll = [], []
    per_op: Dict[str, float] = defaultdict(float)
    per_scope: Dict[str, float] = defaultdict(float)
    for ops in ev.device.values():
        busy.append(_length(union([(s, e) for _, s, e in ops])))
        coll.append(_length(union([(s, e) for n, s, e in ops if is_collective(n)])))
        for name, t in self_times(ops).items():
            per_op[op_label(name)] += t
            per_scope[scope_of(ev.op_names.get(name))] += t
    chips = len(ev.device)
    top_ops = sorted(((n, t / chips * 1e-9) for n, t in per_op.items()), key=lambda x: -x[1])
    scopes = dict(sorted(((n, t / chips * 1e-9) for n, t in per_scope.items()), key=lambda x: -x[1]))

    gaps: List[Tuple[str, float]] = []
    windows = [(s, e) for n, s, e in ev.host if n == WINDOW_SPAN]
    if windows:
        w0, w1 = windows[0]
        first = sorted(ev.device)[0]
        merged = union([(max(s, w0), min(e, w1)) for _, s, e in ev.device[first] if e > w0 and s < w1])
        edges = [w0] + [x for span in merged for x in span] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_label(ev.host, (s + e) / 2), (e - s) * 1e-9))
        gaps.sort(key=lambda g: -g[1])
    return Summary(
        busy_s=sum(busy) / chips * 1e-9,
        window_s=window_s,
        collective_s=sum(coll) / chips * 1e-9,
        top_ops=top_ops[:top],
        idle_gaps=gaps[:top],
        scopes=scopes,
    )
