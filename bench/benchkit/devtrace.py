"""Device trace: capture a window with JAX's profiler and reduce it.

Capture (:class:`Window`) writes an ``.xplane.pb`` under a fixed directory
of the checkout, reads it back with ``jax.profiler.ProfileData`` into a
plain, JSON-able form (:func:`extract`) and deletes it.  Everything that
turns the trace into numbers works on that plain form, so it is tested on
a small recorded trace (``bench/testdata``) with no chip and no profiler:

* busy time: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices traced;
* idle gaps: the stretches of the window with no operation on the
  device, each labelled with the innermost program span (the program's
  own tracer spans, moved onto the trace's clock) open at its middle;
* the operations that took most device time, by their name scope;
* the device time of the operations under one name scope, per host span
  (for the roofline of one kernel over the calls that ran it).
"""
from __future__ import annotations

import glob
import os
import shutil
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SYNC = "bench.clock_sync"
TOP = 10
DEVICE_PLANE = "/device:TPU:"
# ops that contain others on the device timeline: busy, but not listed
CONTAINERS = ("while", "conditional", "call")


def _varint(b: bytes, i: int):
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """``(field number, value)`` of a protobuf message, values undecoded."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, v


def op_scopes(xspace: bytes) -> Dict[str, Tuple[str, str]]:
    """``{event name: (short name, name scope)}`` of the device planes'
    ops, from the event metadata of the ``.xplane.pb`` (XSpace: planes =
    1; XPlane: name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata: name = 2, display_name = 4, stats = 5; XStat:
    metadata_id = 1, str_value = 5).  The scope is the ``tf_op`` stat: the
    ``jax.named_scope`` path of the op (``.../vcycle.L0.down/gather:``).
    ``jax.profiler.ProfileData`` does not expose metadata stats."""
    out: Dict[str, Tuple[str, str]] = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                metas.append(v)
            elif f == 5:
                for mf, mv in _fields(v):
                    if mf == 2:
                        d = dict(_fields(mv))
                        stat_names[d.get(1, 0)] = d.get(2, b"").decode()
        if not name.startswith(DEVICE_PLANE):
            continue
        for entry in metas:
            for mf, mv in _fields(entry):
                if mf != 2:
                    continue
                md, scope = {}, ""
                for f, v in _fields(mv):
                    if f == 5:
                        st = dict(_fields(v))
                        if stat_names.get(st.get(1)) == "tf_op":
                            scope = st.get(5, b"").decode()
                    else:
                        md[f] = v
                full = md.get(2, b"").decode()
                short = md.get(4, b"").decode() or full
                out[full] = (short, scope or short)
    return out


def extract(profile_data, scopes: Dict[str, Tuple[str, str]],
            host_prefixes=("bench.",)) -> dict:
    """The plain form of a ``ProfileData``: device op events per device as
    ``[short name, scope, start, duration]``, the host annotations whose
    names start with ``host_prefixes``, and the window the session covered
    (all on the trace's clock, ns, from the session's start)."""
    devices: Dict[str, list] = {}
    host: List[list] = []
    window: Optional[List[float]] = None
    for plane in profile_data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    short, scope = scopes.get(ev.name, (ev.name, ev.name))
                    ops.append([short, scope, float(ev.start_ns),
                                float(ev.duration_ns)])
            devices[plane.name] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(host_prefixes)):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
        elif plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window = [0.0, float(st["profile_stop_time"]
                                     - st["profile_start_time"])]
    if window is None:
        ends = [o[2] + o[3] for ops in devices.values() for o in ops]
        window = [0.0, max(ends, default=0.0)]
    return {"window_ns": window, "devices": devices, "host": host}


def union_ns(intervals: Iterable[Tuple[float, float]],
             window: Sequence[float]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``window``."""
    lo, hi = window
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def busy_ns(trace: dict) -> float:
    """Device busy time in the window, averaged over the devices."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    total = 0.0
    for ops in devs.values():
        merged = union_ns(((o[2], o[2] + o[3]) for o in ops),
                          trace["window_ns"])
        total += sum(e - s for s, e in merged)
    return total / len(devs)


def idle_gaps(trace: dict, spans: Sequence[Tuple[str, float, float]]
              ) -> List[Tuple[str, float]]:
    """Idle gaps of the first device, each ``(label, ns)``; the label is
    the innermost of ``spans`` (``(name, start, end)``, trace clock) open
    at the gap's middle, or ``"no program span"``."""
    if not trace["devices"]:
        return []
    ops = trace["devices"][sorted(trace["devices"])[0]]
    lo, hi = trace["window_ns"]
    merged = union_ns(((o[2], o[2] + o[3]) for o in ops), (lo, hi))
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    pending = sorted(spans, key=lambda sp: sp[1])
    active: List[Tuple[str, float, float]] = []
    nxt, gaps = 0, []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        while nxt < len(pending) and pending[nxt][1] <= mid:
            active.append(pending[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > mid]
        label = max(active, key=lambda sp: sp[1])[0] if active \
            else "no program span"
        gaps.append((label, e - s))
    return gaps


def _label(scope: str) -> str:
    """A scope path without its jit wrapper and trailing colon."""
    parts = [p for p in scope.rstrip(":").split("/")
             if p and not p.startswith("jit(")]
    return "/".join(parts) if parts else scope


def top_ops(trace: dict) -> List[Tuple[str, float]]:
    """The ``TOP`` name scopes with most device time (seconds, summed over
    devices and divided by their number); ops that contain others (a
    ``while`` loop) are left out, their body ops are counted."""
    tot: Dict[str, float] = {}
    ndev = max(1, len(trace["devices"]))
    for ops in trace["devices"].values():
        for name, scope, _, dur in ops:
            if name.startswith(CONTAINERS):
                continue
            key = _label(scope)
            tot[key] = tot.get(key, 0.0) + dur / 1e9 / ndev
    return sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]


def top_gaps(gaps: Sequence[Tuple[str, float]]) -> List[Tuple[str, float]]:
    """Idle time summed by label, the ``TOP`` largest (seconds)."""
    tot: Dict[str, float] = {}
    for label, ns in gaps:
        tot[label] = tot.get(label, 0.0) + ns / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]


def scoped_time_ns(trace: dict, marker: str, start: float, end: float
                   ) -> float:
    """Device time of the first device's ops whose scope contains
    ``marker`` and that start in ``[start, end)``."""
    if not trace["devices"]:
        return 0.0
    ops = trace["devices"][sorted(trace["devices"])[0]]
    return sum(o[3] for o in ops if marker in o[1] and start <= o[2] < end)


def host_spans(trace: dict, prefix: str) -> List[list]:
    """Host annotations named ``prefix...`` that lie inside the window."""
    lo, hi = trace["window_ns"]
    return [h for h in trace["host"] if h[0].startswith(prefix)
            and h[1] >= lo and h[1] + h[2] <= hi]


def program_spans(events: Sequence[dict], offset_ns: float
                  ) -> List[Tuple[str, float, float]]:
    """The program's tracer spans (``perf_counter_ns``) on the trace's
    clock: ``trace time = perf_counter_ns + offset_ns``."""
    return [(e["name"], e["ts_ns"] + offset_ns,
             e["ts_ns"] + e["dur_ns"] + offset_ns)
            for e in events if "dur_ns" in e]


def clock_offset_ns(trace: dict, sync_pc_ns: Optional[float]
                    ) -> Optional[float]:
    """``trace clock - perf_counter_ns``, from the sync annotation."""
    marks = [h for h in trace["host"] if h[0] == SYNC]
    if not marks or sync_pc_ns is None:
        return None
    return marks[0][1] - sync_pc_ns


def summarize(trace: dict, spans: Sequence[dict],
              sync_pc_ns: Optional[float]) -> dict:
    """Busy and window seconds and the breakdown of one traced window."""
    offset = clock_offset_ns(trace, sync_pc_ns)
    prog = program_spans(spans, offset) if offset is not None else []
    lo, hi = trace["window_ns"]
    return {
        "busy_s": busy_ns(trace) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "breakdown": {
            "device_ops": [list(x) for x in top_ops(trace)],
            "idle_gaps": [list(x) for x in top_gaps(idle_gaps(trace,
                                                              prog))],
        },
    }


class Window:
    """One profiled window: ``start()``/``stop()`` from the caller's thread,
    or ``start_after(t, seconds)`` from a helper thread so an open-loop
    driver keeps its schedule; ``reduced(spans)`` reads it back."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.sync_pc_ns: Optional[float] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(SYNC):
            self.sync_pc_ns = float(time.perf_counter_ns())

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def start_after(self, t_abs: float, seconds: float) -> None:
        def body():
            time.sleep(max(0.0, t_abs - time.perf_counter()))
            self.start()
            time.sleep(seconds)
            self.stop()

        self._thread = threading.Thread(target=body, name="bench-profile")
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()

    def load(self) -> Optional[dict]:
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(self.log_dir, "**",
                                              "*.xplane.pb"), recursive=True))
        if not paths:
            return None
        with open(paths[-1], "rb") as f:
            raw = f.read()
        trace = extract(ProfileData.from_serialized_xspace(raw),
                        op_scopes(raw))
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return trace
