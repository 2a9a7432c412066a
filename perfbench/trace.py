"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The traced stretch of a run is marked on the host by the benchmark's own
``bench.window`` annotation; everything is clipped to it. For each device
plane (``/device:TPU:<i>``, the first ``n_devices``) the reduction keeps the
operations on its ``XLA Ops`` line and the program executions on its
``XLA Modules`` line:

* busy time is the union of the operation intervals (overlaps count once);
* a named scope's time covers the operations whose scope path
  (``tf_op`` or ``long_name``, on the event or its metadata) contains it,
  e.g. ``kernel/bloom.``;
* a program's time is the sum of its executions whose name contains a
  given part, e.g. ``chunk_local``;
* a collective's time is the union of the operations whose name starts
  with its HLO name, e.g. ``all-to-all``; a scope's time is a union too,
  since a loop's event encloses its body's.

Each is the mean over the devices. The breakdown lists the operations that
took most device time of their own (less the operations nested in them),
with their kernel scope, and the longest idle gaps of device 0, each named
by the innermost host annotation open at the gap's middle.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench.window"
SCOPE_STATS = ("tf_op", "long_name", "name")


def _scope(stats: Dict[str, object]) -> str:
    return " ".join(str(stats[k]) for k in SCOPE_STATS if k in stats)


# -- the XSpace protobuf, read only as far as the event metadata ------------
# JAX's ProfileData hands out each event's own stats; on the TPU the named
# scope of an operation (``tf_op``) sits in the plane's event metadata,
# which it does not expose. These few lines read it from the wire format.

def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes):
    """(field number, wire type, value) of a message's fields; a
    length-delimited value is its bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield num, wt, v


def event_metadata(raw: bytes) -> Dict[str, Dict[str, Dict[str, object]]]:
    """{plane name: {event name: {stat name: value}}} from a serialized
    XSpace: the stats attached to each event's metadata."""
    out = {}
    for num, _, plane in _fields(raw):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, _, v in _fields(plane):
            if f == 2:
                name = v.decode("utf-8", "replace")
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict((k, val) for k, _, val in _fields(v))
                sm = dict((k, val) for k, _, val in _fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = sm.get(2, b"").decode()
        events = {}
        for m in metas:
            entry = dict((k, val) for k, _, val in _fields(m))
            ev_name, stats = "", {}
            for k, _, val in _fields(entry.get(2, b"")):
                if k == 2:
                    ev_name = val.decode("utf-8", "replace")
                elif k == 5:
                    st = list(_fields(val))
                    sid = next((x for kk, _, x in st if kk == 1), 0)
                    for kk, _, x in st:
                        if kk == 5:
                            stats[stat_names.get(sid, sid)] = x.decode(
                                "utf-8", "replace")
                        elif kk == 7:
                            stats[stat_names.get(sid, sid)] = \
                                stat_names.get(x, x)
                        elif kk in (3, 4):
                            stats[stat_names.get(sid, sid)] = x
            events[ev_name] = stats
        out[name] = events
    return out


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def kernel_of(scope: str) -> str:
    """The ``<family>.<impl>`` of the kernel scope in a scope path, or ''."""
    i = scope.find("kernel/")
    return scope[i:].split("/")[1].split()[0] if i >= 0 else ""


def self_ns(intervals: np.ndarray) -> np.ndarray:
    """Each interval's length less the intervals nested directly in it
    (a while loop's events enclose its body's)."""
    order = np.lexsort((-intervals[:, 1], intervals[:, 0]))
    out = intervals[:, 1] - intervals[:, 0]
    stack: List[int] = []
    for i in order:
        s, e = intervals[i]
        while stack and intervals[stack[-1], 1] <= s:
            stack.pop()
        if stack and e <= intervals[stack[-1], 1]:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


def union_ns(intervals: np.ndarray) -> float:
    """Total length of the union of (start, end) intervals."""
    if len(intervals) == 0:
        return 0.0
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return float(total + cur_e - cur_s)


def gaps_ns(intervals: np.ndarray, t0: float, t1: float) -> List[Tuple[float, float]]:
    """The idle (start, end) gaps between the union of intervals in [t0, t1]."""
    out, cur = [], t0
    for s, e in intervals[np.argsort(intervals[:, 0], kind="stable")]:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


@dataclass
class Device:
    ops: np.ndarray                     # (n, 2) start, end ns, clipped
    op_names: List[str]
    op_scopes: List[str]
    modules: np.ndarray                 # (m, 2)
    module_names: List[str]


@dataclass
class Trace:
    t0: float
    t1: float
    devices: List[Device]
    host: List[Tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        return float(np.mean([union_ns(d.ops) for d in self.devices])) / 1e9

    def _mean(self, per_device) -> float:
        return float(np.mean([per_device(d) for d in self.devices])) / 1e9

    def scope_s(self, part: str) -> float:
        return self._mean(lambda d: union_ns(d.ops[np.array(
            [part in sc for sc in d.op_scopes], bool).reshape(-1)]))

    def module_s(self, part: str) -> float:
        return self._mean(lambda d: sum(
            e - s for (s, e), n in zip(d.modules, d.module_names)
            if part in n))

    def module_count(self, part: str) -> float:
        return float(np.mean([sum(part in n for n in d.module_names)
                              for d in self.devices]))

    def host_label(self, t: float) -> str:
        best = None
        for s, e, name in self.host:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "(no host span)"

    @property
    def breakdown(self) -> Dict[str, list]:
        per_op: Dict[str, float] = {}
        for d in self.devices:
            if not len(d.ops):
                continue
            for t, n, sc in zip(self_ns(d.ops), d.op_names, d.op_scopes):
                k = kernel_of(sc)
                label = f"{n} (kernel/{k})" if k else n
                per_op[label] = per_op.get(label, 0.0) + t
        n_dev = max(len(self.devices), 1)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
        d0 = self.devices[0] if self.devices else None
        gaps = gaps_ns(d0.ops, self.t0, self.t1) if d0 is not None else []
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[n, v / n_dev / 1e9] for n, v in ops],
                "idle_gaps": [[self.host_label((s + e) / 2), (e - s) / 1e9]
                              for s, e in gaps]}


def _device_index(name: str) -> int:
    tail = name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else -1


def from_profile(pd, n_devices: int, meta=None) -> Trace:
    """``meta`` is :func:`event_metadata` of the same trace, or None."""
    meta = meta or {}
    host, window = [], None
    dev_planes = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            i = _device_index(plane.name)
            if 0 <= i < n_devices:
                dev_planes[i] = plane
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == WINDOW:
                        window = (s, e)
                    elif ev.name.startswith("bench.") or "Session" in ev.name:
                        host.append((s, e, ev.name))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} host span")
    t0, t1 = window
    devices = []
    for i in sorted(dev_planes):
        ops, op_names, op_scopes, mods, mod_names = [], [], [], [], []
        plane_meta = meta.get(dev_planes[i].name, {})
        for line in dev_planes[i].lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = max(ev.start_ns, t0)
                e = min(ev.start_ns + ev.duration_ns, t1)
                if e <= s:
                    continue
                if line.name == "XLA Ops":
                    ops.append((s, e))
                    op_names.append(short_name(ev.name))
                    op_scopes.append(_scope({**plane_meta.get(ev.name, {}),
                                             **dict(ev.stats)}))
                else:
                    mods.append((s, e))
                    mod_names.append(ev.name)
        devices.append(Device(np.array(ops, float).reshape(-1, 2), op_names,
                              op_scopes, np.array(mods, float).reshape(-1, 2),
                              mod_names))
    return Trace(t0, t1, devices, host)


def reduce(path: str, n_devices: int) -> Trace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return from_profile(ProfileData.from_serialized_xspace(raw), n_devices,
                        event_metadata(raw))
