"""Device time by program scope, and idle time by program span, from the
profiler trace (``.xplane.pb``) that ``trace.py`` reads.

The program names its device work with ``sflog.scope`` (``jax.named_scope``:
``sf.pack``, ``mat.diag``, ``moe.dispatch``, ``serve.decode``, ...) and its
host work with ``sflog.span`` (``jax.profiler.TraceAnnotation``:
``cg.readback``, ``serve.sample``, ...).  A scope reaches the trace as the
``tf_op`` stat of a device op's event metadata, its JAX name path (for
example ``jit(sf_segment_reduce)/sf.combine/jit(segment_reduce_blocked)/
pallas_call``); JAX's ``ProfileData`` does not expose those stats, so this
module decodes the file itself, with the few XPlane messages it needs
declared below (``google.protobuf`` only).

Inside the ``bench.window`` span, per device plane:

* each ``XLA Ops`` event's self time (as ``trace.py`` computes it) is
  credited to every component of its ``tf_op`` path, so a scope's time is
  inclusive; ops with no program scope on their path are the unscoped
  remainder;
* each idle interval is named by the innermost host span, among the
  program's and the benchmark's, the host was in at its middle.

Times are averaged over the device planes.  A run parses its trace once
(:func:`for_run` memoises) and prints the tables to stderr.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

from bench import harness, trace

__all__ = ["PROGRAM_PREFIXES", "SPAN_PREFIXES", "read_xspace",
           "reduce_scopes", "for_run", "scope_s", "scope_pct",
           "idle_pct", "report"]

PROGRAM_PREFIXES = ("sf.", "dmda.", "mat.", "cg.", "moe.", "model.",
                    "serve.")
SPAN_PREFIXES = PROGRAM_PREFIXES + tuple(
    p for p in trace.SPAN_PREFIXES if p not in PROGRAM_PREFIXES)
TF_OP = "tf_op"
TOP = 12

_XSPACE = None


def _xspace_class():
    """The XSpace message class, from a schema declared here (the subset
    of tsl/profiler/protobuf/xplane.proto this reader uses; field numbers
    as there, unknown fields skipped)."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name, fields, parent=None):
        m = (parent.nested_type if parent else fd.message_type).add(
            name=name)
        for fname, num, typ, rep, tname in fields:
            f = m.field.add(name=fname, number=num, type=typ,
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if tname:
                f.type_name = ".bench_xplane." + tname
        return m

    I64, U64, DBL = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    STR, BYT, MSG = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    stat = message("XStat", [
        ("metadata_id", 1, I64, False, None),
        ("double_value", 2, DBL, False, None),
        ("uint64_value", 3, U64, False, None),
        ("int64_value", 4, I64, False, None),
        ("str_value", 5, STR, False, None),
        ("bytes_value", 6, BYT, False, None),
        ("ref_value", 7, U64, False, None)])
    stat.oneof_decl.add(name="value")
    for f in stat.field[1:]:
        f.oneof_index = 0
    message("XEvent", [
        ("metadata_id", 1, I64, False, None),
        ("offset_ps", 2, I64, False, None),
        ("duration_ps", 3, I64, False, None),
        ("stats", 4, MSG, True, "XStat")])
    message("XLine", [
        ("id", 1, I64, False, None), ("name", 2, STR, False, None),
        ("timestamp_ns", 3, I64, False, None),
        ("events", 4, MSG, True, "XEvent")])
    message("XEventMetadata", [
        ("id", 1, I64, False, None), ("name", 2, STR, False, None),
        ("stats", 5, MSG, True, "XStat")])
    message("XStatMetadata", [
        ("id", 1, I64, False, None), ("name", 2, STR, False, None)])
    plane = message("XPlane", [
        ("id", 1, I64, False, None), ("name", 2, STR, False, None),
        ("lines", 3, MSG, True, "XLine"),
        ("event_metadata", 4, MSG, True, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, MSG, True, "XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(entry, [("key", 1, I64, False, None),
                            ("value", 2, MSG, False, value)], plane)
        e.options.map_entry = True
    message("XSpace", [("planes", 1, MSG, True, "XPlane")])

    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _XSPACE


def read_xspace(path: str):
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _str_stat(stat, stat_names: Dict[int, str]) -> Optional[str]:
    kind = stat.WhichOneof("value")
    if kind == "str_value":
        return stat.str_value
    if kind == "ref_value":
        return stat_names.get(stat.ref_value)
    return None


def _events(plane):
    """(line name, event name, start ns, end ns, metadata id) of a plane,
    in whole nanoseconds as ``ProfileData`` gives them."""
    names = {k: m.name for k, m in plane.event_metadata.items()}
    for line in plane.lines:
        base_ps = line.timestamp_ns * 1000
        for ev in line.events:
            s = float((base_ps + ev.offset_ps) // 1000)
            yield (line.name, names.get(ev.metadata_id, ""), s,
                   s + ev.duration_ps // 1000, ev.metadata_id)


def _paths(plane) -> Dict[int, Tuple[Tuple[str, ...], Tuple[str, ...]]]:
    """metadata id -> (every component of its ``tf_op`` path, the program
    scopes among them), each without repeats and in path order."""
    stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
    tf_ids = {k for k, n in stat_names.items() if n == TF_OP}
    out = {}
    for mid, md in plane.event_metadata.items():
        op = next((_str_stat(st, stat_names) for st in md.stats
                   if st.metadata_id in tf_ids), None) or ""
        comps = tuple(dict.fromkeys(c for c in op.split("/") if c))
        out[mid] = (comps, tuple(c for c in comps
                                 if c.startswith(PROGRAM_PREFIXES)))
    return out


def reduce_scopes(path: str, *, window_span: str = trace.WINDOW_SPAN
                  ) -> Dict:
    """-> {"window_s", "busy_s", "devices", "components", "scope_paths",
    "unscoped_s", "unscoped_ops", "idle", "spans"}; seconds, averaged over
    the device planes.  ``components``: inclusive time of every ``tf_op``
    path component; ``scope_paths``: time by the tuple of program scopes on
    the path (``()`` is the unscoped remainder); ``idle``: idle time by
    innermost span; ``spans``: every span name the host opened."""
    space = read_xspace(path)
    host, devices = [], []
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            host.extend(_events(plane))
        elif plane.name.startswith(trace.DEVICE_PREFIX):
            devices.append(plane)
    win = [(s, e) for _, name, s, e, _ in host if name == window_span]
    if not win:
        raise ValueError(f"trace has no {window_span!r} span")
    if not devices:
        raise ValueError("trace has no device plane")
    lo, hi = win[0]
    named = [(s, e, name) for _, name, s, e, _ in host
             if name.startswith(SPAN_PREFIXES)]
    spans = trace._SpanIndex(named)

    busy, comp_t, path_t, unscoped_ops, idle = [], {}, {}, {}, {}
    for plane in devices:
        paths = _paths(plane)
        evs = [(mid, s, e) for line, _, s, e, mid in _events(plane)
               if line == trace.OP_LINE]
        iv = np.asarray([(max(s, lo), min(e, hi)) for _, s, e in evs
                         if e > lo and s < hi],
                        dtype=np.float64).reshape(-1, 2)
        busy.append(trace.union_length(iv))
        for mid, s, e, own in trace._self_times(evs):
            d = own * max(0.0, min(e, hi) - max(s, lo)) / max(e - s, 1e-30)
            if d <= 0:
                continue
            comps, scopes = paths.get(mid, ((), ()))
            for c in comps:
                comp_t[c] = comp_t.get(c, 0.0) + d
            path_t[scopes] = path_t.get(scopes, 0.0) + d
            if not scopes:
                key = "/".join(comps) or "(no tf_op)"
                unscoped_ops[key] = unscoped_ops.get(key, 0.0) + d
        for g0, g1 in trace.gaps(iv, lo, hi):
            key = spans.at(0.5 * (g0 + g1))
            idle[key] = idle.get(key, 0.0) + (g1 - g0)

    nd = len(devices)
    per = lambda d: {k: v * 1e-9 / nd for k, v in d.items()}
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean(busy)) * 1e-9,
        "devices": nd,
        "components": per(comp_t),
        "scope_paths": per(path_t),
        "unscoped_s": path_t.get((), 0.0) * 1e-9 / nd,
        "unscoped_ops": per(unscoped_ops),
        "idle": per(idle),
        "spans": sorted({n for _, _, n in named}),
    }


def scope_s(summary: Dict, match) -> Optional[float]:
    """Device seconds of the ops under any program scope that ``match``
    accepts (a name, a tuple of names, or a predicate), each op counted
    once; None when no such scope appears in the trace."""
    if isinstance(match, str):
        match = (match,)
    if not callable(match):
        names = set(match)
        match = names.__contains__
    hit, total = False, 0.0
    for scopes, t in summary["scope_paths"].items():
        if any(match(s) for s in scopes):
            hit, total = True, total + t
    return total if hit else None


def scope_pct(summary: Optional[Dict], match) -> Optional[float]:
    """:func:`scope_s` as a share of the window, in %."""
    if summary is None:
        return None
    t = scope_s(summary, match)
    return None if t is None else 100.0 * t / summary["window_s"]


def idle_pct(summary: Optional[Dict], span: str) -> Optional[float]:
    """Device idle time with the host innermost in ``span``, as a share of
    the window in %; None when the host never opened ``span``."""
    if summary is None or span not in summary["spans"]:
        return None
    return 100.0 * summary["idle"].get(span, 0.0) / summary["window_s"]


def _rank(d: Dict[str, float], top: int = TOP) -> Iterable:
    return sorted(d.items(), key=lambda kv: -kv[1])[:top]


def report(summary: Dict, seconds: float, out=None) -> None:
    """The per-scope and per-span tables, and the unscoped remainder (to
    stderr unless ``out`` is given)."""
    w, b = summary["window_s"], summary["busy_s"]
    say = lambda *a: print(*a, file=out or sys.stderr, flush=True)
    say(f"scopes: trace reduced in {seconds:.6f} s; window {w:.6f} s, "
        f"busy {b:.6f} s on {summary['devices']} device(s)")
    scopes = {c: t for c, t in summary["components"].items()
              if c.startswith(PROGRAM_PREFIXES)}
    for name, t in sorted(scopes.items()):
        say(f"scopes: scope {name} {t:.6f} s ({100 * t / w:.4f}% of the "
            f"window, {100 * t / max(b, 1e-30):.4f}% of busy)")
    un = summary["unscoped_s"]
    say(f"scopes: unscoped {un:.6f} s; covered "
        f"{100 * (1 - un / max(b, 1e-30)):.4f}% of busy")
    for name, t in _rank(summary["unscoped_ops"], 5):
        say(f"scopes: unscoped op {t:.6f} s {name[:trace.NAME_CHARS]}")
    for name, t in _rank(summary["idle"]):
        say(f"scopes: idle in {name} {t:.6f} s ({100 * t / w:.4f}% of the "
            f"window)")


_MEMO: Dict[Tuple, Dict] = {}


def for_run(ctx: Dict) -> Optional[Dict]:
    """The scope summary of this run's trace (parsed and reported once);
    None for a run without a trace."""
    if ctx.get("trace") is None:
        return None
    try:
        path = trace.find_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _MEMO:
        t = time.perf_counter()
        _MEMO.clear()
        _MEMO[key] = reduce_scopes(path)
        report(_MEMO[key], time.perf_counter() - t)
    return _MEMO[key]
