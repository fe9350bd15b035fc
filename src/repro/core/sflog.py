"""``-log_view`` for star forests: event tracing, comm volume, ``SFView``.

PETSc answers "what did this run actually communicate?" with two tools the
paper leans on throughout §5-§6: ``PetscLogEvent`` begin/end pairs rendered
by ``-log_view`` (count, time, message volume per event) and ``PetscSFView``
(the structural dump of one SF).  This module is both for the JAX port — a
process-wide registry every SF consumer reports into:

* **Events** (:class:`EventRecord`): named begin/end pairs with wall time,
  exchange counts, and per-event *comm volume* in bytes derived from the
  plan's edge count and the payload's unit row (``core/unit.py``).  Split
  phases additionally accumulate the *overlap window* — the wall time the
  caller kept an exchange in flight between ``*_begin`` and ``*_end``.
* **Counters**: plain named integers.  The pre-existing ad-hoc counter
  surfaces (``PlanCache`` hit/miss, autotuner sweep stats, serving tallies)
  are registry-backed, so one dump carries all of them.
* **SFView** (:func:`sf_view` / :func:`format_sf_view`): nroots/nleaves,
  local-vs-remote edge split, root-degree histogram, backend and cached-plan
  signatures for any ``StarForest`` / ``SFComm`` / ``DynPlan``.

Rendering: :func:`log_view` (the PETSc-style text table) and
:func:`dump_json` (a JSON-ready dict benchmarks stamp into artifacts).

**Spans and scopes: the profiler's view.**  :func:`span` is a host span
(``jax.profiler.TraceAnnotation``) on the profiler's clock: a no-op unless a
trace is being captured, and also an event of this registry when logging is
on, so ``-log_view`` and the trace name each thing once.  :func:`scope` is
``jax.named_scope`` for code that runs under ``jit``: it costs nothing at
run time and names every device op traced inside it (the op's ``tf_op``
path in the trace).  Names are dotted ``<layer>.<part>``; the README section
"Observability" lists those the program opens.

**Trace safety.**  Instrumentation hooks fire at *dispatch* time — Python
call boundaries — never inside a compiled program.  A hook that fires while
``jax.jit`` (or ``shard_map`` / ``lax.while_loop``) is tracing increments
the event's ``traced`` counter and records nothing else: wall time under a
tracer is meaningless, and a traced call executes arbitrarily many times
later via the compiled-program cache.  ``count``/``time``/``bytes`` are
therefore *eager-execution* totals, and ``traced`` is the witness the
no-retrace regression tests assert on (a jitted path whose ``traced`` stays
flat across calls provably did not re-trace).

**Gating.**  ``REPRO_SF_LOG`` selects the mode at import: ``0`` (default)
off, ``1`` on, ``fence`` on + ``jax.block_until_ready`` on every event's
result so times are true wall times rather than dispatch times.  When off,
every hook is a single integer test — the facade adds no measurable cost
(``tests/test_sflog.py`` bounds it at <2% of one exchange).  Counters are
always live: they are bare integer adds and pre-date this layer.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

__all__ = [
    "enabled", "mode", "set_mode", "reset",
    "Counter", "counter", "counters",
    "EventRecord", "event", "events",
    "op_begin", "op_end", "stash_pending", "claim_pending", "pending_end",
    "span", "scope",
    "log_view", "dump_json", "events_snapshot", "events_delta",
    "overlap_efficiency", "exchange_totals",
    "sf_view", "format_sf_view",
]

# --------------------------------------------------------------------------
# mode gate (REPRO_SF_LOG = 0 | 1 | fence)
# --------------------------------------------------------------------------
_OFF, _ON, _FENCE = 0, 1, 2
_MODE_NAMES = {_OFF: "off", _ON: "on", _FENCE: "fence"}


def _parse_mode(value) -> int:
    if value is None or isinstance(value, bool):
        return _ON if value else _OFF
    v = str(value).strip().lower()
    if v in ("fence", "2"):
        return _FENCE
    if v in ("1", "true", "yes", "on"):
        return _ON
    if v in ("", "0", "false", "no", "off"):
        return _OFF
    raise ValueError(f"REPRO_SF_LOG={value!r}: use 0, 1 or fence")


_MODE = _parse_mode(os.environ.get("REPRO_SF_LOG"))


def enabled() -> bool:
    """True when event recording is on (the one test every hook makes)."""
    return _MODE != _OFF


def mode() -> str:
    return _MODE_NAMES[_MODE]


def set_mode(value) -> str:
    """Set the logging mode programmatically (``"off"``/``"on"``/``"fence"``
    or anything ``REPRO_SF_LOG`` accepts); returns the previous mode."""
    global _MODE
    old = _MODE_NAMES[_MODE]
    _MODE = _parse_mode(value)
    return old


def _tracing() -> bool:
    """Are we under a jax trace right now?  Hooks must never record wall
    time or execution counts from inside a trace."""
    import jax
    return not jax.core.trace_ctx.is_top_level()


# --------------------------------------------------------------------------
# counters
# --------------------------------------------------------------------------
class Counter:
    """A named registry integer.  ``add``/``value`` only — cheap enough to
    stay live even when event logging is off (the migrated ``PlanCache`` /
    autotuner / serving tallies sit on these)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def add(self, n: int = 1) -> int:
        self.value += n
        return self.value

    def __repr__(self) -> str:    # pragma: no cover - debugging aid
        return f"Counter({self.name}={self.value})"


_COUNTERS: Dict[str, Counter] = {}
_UNIQ: Dict[str, int] = {}


def counter(name: str, *, unique: bool = False) -> Counter:
    """Get-or-create the counter ``name``.  ``unique=True`` mints a fresh
    ``name#k`` instance instead — per-object counters (one PlanCache, one
    ServeEngine) must not alias across instances."""
    if unique:
        _UNIQ[name] = _UNIQ.get(name, 0) + 1
        name = f"{name}#{_UNIQ[name]}"
    c = _COUNTERS.get(name)
    if c is None:
        c = _COUNTERS[name] = Counter(name)
    return c


def counters() -> Dict[str, int]:
    """Snapshot of every registered counter value."""
    return {n: c.value for n, c in sorted(_COUNTERS.items())}


# --------------------------------------------------------------------------
# events
# --------------------------------------------------------------------------
_MAX_TAG_VALUES = 8


class EventRecord:
    """Aggregate for one named event.

    ``count``/``time``/``bytes``/``overlap`` accumulate over *eager*
    executions only; ``traced`` counts how many times the hook fired while
    a jax trace was active (once per compiled program, never per cached
    execution).  ``tags`` holds bounded value->occurrence maps for context
    keys (backend, op, pattern, request id, step, ...)."""

    __slots__ = ("name", "count", "traced", "time", "bytes", "overlap",
                 "tags")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.traced = 0
        self.time = 0.0
        self.bytes = 0.0
        self.overlap = 0.0
        self.tags: Dict[str, Dict[str, int]] = {}

    def tag(self, key: str, value) -> None:
        vals = self.tags.setdefault(key, {})
        v = str(value)
        if v in vals:
            vals[v] += 1
        elif len(vals) < _MAX_TAG_VALUES:
            vals[v] = 1
        else:                      # bounded: overflow bucket, never unbounded
            vals["..."] = vals.get("...", 0) + 1

    def as_dict(self) -> Dict[str, Any]:
        return {"count": self.count, "traced": self.traced,
                "time_s": self.time, "bytes": self.bytes,
                "overlap_s": self.overlap,
                "tags": {k: dict(v) for k, v in self.tags.items()}}


_EVENTS: Dict[str, EventRecord] = {}


def event(name: str) -> EventRecord:
    ev = _EVENTS.get(name)
    if ev is None:
        ev = _EVENTS[name] = EventRecord(name)
    return ev


def events() -> Dict[str, EventRecord]:
    return dict(_EVENTS)


def reset(*, counters: bool = False) -> None:
    """Clear every event aggregate (and zero counter values when asked —
    counter *objects* survive, live references are everywhere)."""
    _EVENTS.clear()
    if counters:
        for c in _COUNTERS.values():
            c.value = 0


# --------------------------------------------------------------------------
# hooks (call sites: SFComm, FieldBundle, DynPlan, serving, training)
# --------------------------------------------------------------------------
def op_begin() -> float:
    """Start one event window.  Returns the start timestamp, or ``-1.0``
    when a jax trace is active (the end hook then counts ``traced`` only).
    Callers must have checked :func:`enabled` first."""
    if _tracing():
        return -1.0
    return time.perf_counter()


def op_end(name: str, t0: float, out=None, *, nbytes: float = 0.0,
           tags: Optional[Dict[str, Any]] = None) -> None:
    """Close the window opened by :func:`op_begin` for event ``name``.

    ``out`` is fenced with ``jax.block_until_ready`` in fence mode so the
    recorded time is wall time, not dispatch time.  ``nbytes`` is the comm
    volume this execution moved (plan edges x unit row bytes)."""
    if _MODE == _OFF:
        return
    ev = event(name)
    if t0 < 0.0 or _tracing():
        ev.traced += 1
        return
    if _MODE == _FENCE and out is not None:
        import jax
        jax.block_until_ready(out)
    ev.count += 1
    ev.time += time.perf_counter() - t0
    ev.bytes += float(nbytes)
    if tags:
        for k, v in tags.items():
            ev.tag(k, v)


def stash_pending(tok, end_name: str, nbytes: float,
                  tags: Optional[Dict[str, Any]] = None, *,
                  tracing: bool = False) -> None:
    """Attach end-event bookkeeping to an in-flight token (``PendingComm``
    and friends are mutable).  Whoever completes the token first —
    ``SFComm.*_end`` or ``pending.end`` — claims it exactly once, so both
    completion styles record one End event and never two."""
    info = (end_name, -1.0 if tracing else time.perf_counter(),
            float(nbytes), tags)
    try:
        setattr(tok, "_sflog", info)
    except (AttributeError, TypeError):   # frozen/slotted token: no window
        pass


def claim_pending(tok):
    """Pop the stashed end-event info off a token (None if absent or
    already claimed)."""
    info = getattr(tok, "_sflog", None)
    if info is not None:
        try:
            setattr(tok, "_sflog", None)
        except (AttributeError, TypeError):   # pragma: no cover
            pass
    return info


def pending_end(info, t0: float, out=None) -> None:
    """Record the End half of a split-phase pair: ``overlap`` is the window
    the exchange stayed in flight (begin return -> end call), ``time`` is
    the end call itself (wait + unpack)."""
    if _MODE == _OFF:
        return
    end_name, t_begin, nbytes, tags = info
    ev = event(end_name)
    if t_begin < 0.0 or t0 < 0.0 or _tracing():
        ev.traced += 1
        return
    if _MODE == _FENCE and out is not None:
        import jax
        jax.block_until_ready(out)
    now = time.perf_counter()
    ev.count += 1
    ev.overlap += max(t0 - t_begin, 0.0)
    ev.time += now - t0
    ev.bytes += float(nbytes)
    if tags:
        for k, v in tags.items():
            ev.tag(k, v)


def span(name: str, **meta):
    """Host span ``name`` with metadata ``meta``: a context manager.

    Always a ``jax.profiler.TraceAnnotation(name, **meta)`` (a host event on
    the profiler's clock, free unless a trace is being captured); when
    logging is on, also one execution of the event ``name`` with ``meta`` as
    its tags (``traced`` instead, when entered under a jax trace).  With
    logging off it is the bare annotation: nothing else runs."""
    if _MODE == _OFF:
        return jax.profiler.TraceAnnotation(name, **meta)
    return _LoggedSpan(name, meta)


class _LoggedSpan:
    """:func:`span` with logging on: the annotation plus one event."""

    __slots__ = ("_ann", "_name", "_meta", "_t0")

    def __init__(self, name: str, meta: Dict[str, Any]):
        self._ann = jax.profiler.TraceAnnotation(name, **meta)
        self._name = name
        self._meta = meta
        self._t0 = 0.0

    def __enter__(self) -> "_LoggedSpan":
        self._ann.__enter__()
        self._t0 = op_begin()
        return self

    def __exit__(self, *exc) -> None:
        op_end(self._name, self._t0, None, tags=self._meta)
        self._ann.__exit__(*exc)


def scope(name: str):
    """Device scope ``name`` (``jax.named_scope``): every op traced inside
    carries it on its name path, so the profiler's trace credits the op's
    device time to it.  Free at run time; only code traced under ``jit``
    (or inside a jitted helper) is named."""
    return jax.named_scope(name)


# --------------------------------------------------------------------------
# reporting
# --------------------------------------------------------------------------
def dump_json() -> Dict[str, Any]:
    """JSON-ready structured dump: mode, every event aggregate, every
    counter.  Benchmarks stamp this into their artifacts; CI uploads it."""
    return {"mode": mode(),
            "events": {n: ev.as_dict()
                       for n, ev in sorted(_EVENTS.items())},
            "counters": counters()}


def dumps_json(**kw) -> str:
    return json.dumps(dump_json(), indent=2, sort_keys=True, **kw)


def log_view() -> str:
    """The PETSc ``-log_view`` table: one row per event with count, traced
    count, wall time, comm volume, bandwidth and share of logged time,
    followed by split-phase overlap windows and the counter registry."""
    total_t = sum(ev.time for ev in _EVENTS.values()) or 1.0
    width = max([len(n) for n in _EVENTS] + [20])
    bar = "-" * (width + 58)
    lines = [f"SF log_view  (mode={mode()})", bar,
             f"{'Event'.ljust(width)} {'Count':>7} {'Traced':>7} "
             f"{'Time (s)':>12} {'MBytes':>10} {'MB/s':>8} {'%T':>4}",
             bar]
    for name in sorted(_EVENTS):
        ev = _EVENTS[name]
        mb = ev.bytes / 1e6
        rate = mb / ev.time if ev.time > 0 else 0.0
        pct = 100.0 * ev.time / total_t
        lines.append(f"{name.ljust(width)} {ev.count:>7d} {ev.traced:>7d} "
                     f"{ev.time:>12.4e} {mb:>10.4f} {rate:>8.1f} "
                     f"{pct:>4.0f}")
    lines.append(bar)
    ovl = [(n, ev) for n, ev in sorted(_EVENTS.items()) if ev.overlap > 0]
    if ovl:
        lines.append("Split-phase overlap windows (begin->end in-flight "
                     "time):")
        for n, ev in ovl:
            hidden = ev.overlap / (ev.overlap + ev.time) \
                if ev.overlap + ev.time > 0 else 0.0
            lines.append(f"  {n}: window {ev.overlap:.4e} s over "
                         f"{ev.count} pairs (window fraction "
                         f"{hidden:.2f})")
        lines.append(bar)
    live = {n: v for n, v in counters().items() if v}
    if live:
        lines.append("Counters:")
        for n, v in live.items():
            lines.append(f"  {n} = {v}")
        lines.append(bar)
    return "\n".join(lines)


def events_snapshot() -> Dict[str, Dict[str, float]]:
    """Count/traced/bytes snapshot per event — the diffable part (times are
    machine-dependent; counts and bytes are exact)."""
    return {n: {"count": ev.count, "traced": ev.traced, "bytes": ev.bytes}
            for n, ev in _EVENTS.items()}


def events_delta(before: Dict[str, Dict[str, float]],
                 after: Optional[Dict[str, Dict[str, float]]] = None
                 ) -> Dict[str, Dict[str, float]]:
    """Per-event growth between two snapshots (events absent from
    ``before`` count from zero); zero rows are dropped."""
    after = events_snapshot() if after is None else after
    out: Dict[str, Dict[str, float]] = {}
    for n, a in after.items():
        b = before.get(n, {})
        d = {k: a[k] - b.get(k, 0) for k in a}
        if any(d.values()):
            out[n] = d
    return out


def exchange_totals(snap: Optional[Dict[str, Dict[str, float]]] = None
                    ) -> Dict[str, float]:
    """Total SF exchange activity in a snapshot: summed ``count + traced``
    and bytes over every ``SF*`` event.  ``traced`` is included so
    exchanges that live inside compiled programs (one trace per program,
    executions invisible to Python) still witness structural growth — the
    perf-guard regression signal."""
    snap = events_snapshot() if snap is None else snap
    n = sum(d["count"] + d["traced"] for name, d in snap.items()
            if name.startswith("SF"))
    b = sum(d["bytes"] for name, d in snap.items()
            if name.startswith("SF"))
    return {"exchanges": float(n), "bytes": float(b)}


def overlap_efficiency(sync_event: str, split_event: str) -> Optional[float]:
    """Mean-time ratio ``t(sync) / t(split)`` between two recorded events —
    the paper's Fig 5/9 figure of merit (>1: the split-phase formulation is
    winning), derived from registry aggregates instead of hand-rolled
    timers."""
    a, b = _EVENTS.get(sync_event), _EVENTS.get(split_event)
    if not a or not b or not a.count or not b.count or b.time <= 0:
        return None
    return (a.time / a.count) / (b.time / b.count)


# --------------------------------------------------------------------------
# SFView
# --------------------------------------------------------------------------
def sf_view(obj) -> Dict[str, Any]:
    """Structured ``PetscSFView`` analogue for a ``StarForest``, ``SFComm``
    or ``DynPlan``: sizes, local/remote edge split, root-degree histogram,
    pattern kind, and (for a comm) backend + cached-plan signature."""
    from .graph import StarForest
    from .dynplan import DynPlan
    from . import patterns as pat

    backend_name = plan = None
    if isinstance(obj, DynPlan):
        return {"type": "DynPlan", "nroots": obj.nroots,
                "nleaves": obj.nleaves, "unit": repr(obj.unit),
                "label": repr(obj.label), "tune_key": repr(obj.tune_key)}
    sf = obj
    if not isinstance(obj, StarForest):          # SFComm-shaped
        sf = obj.sf
        backend_name = getattr(obj, "backend_name", None)
        backend = getattr(obj, "backend", obj)
        plan = getattr(backend, "plan", None)
        if plan is None:
            plan = getattr(getattr(backend, "dist", None), "plan", None)
    sf.setup()
    edges = sf.edges_global()
    rep = pat.analyze(sf)
    degrees = np.bincount(edges[:, 0].astype(np.int64),
                          minlength=sf.nroots_total) \
        if sf.nroots_total else np.zeros(0, np.int64)
    dv, dc = np.unique(degrees, return_counts=True) \
        if degrees.size else (np.zeros(0), np.zeros(0))
    out = {
        "type": "StarForest",
        "nranks": sf.nranks,
        "nroots": int(sf.nroots_total),
        "nleaves": int(sf.nedges_total),
        "nleafspace": int(sf.nleafspace_total),
        "edges": {"total": int(sf.nedges_total),
                  "local": int(rep.n_local_edges),
                  "remote": int(rep.n_remote_edges)},
        "pattern": rep.kind,
        "root_degree_histogram": {int(d): int(c) for d, c in zip(dv, dc)},
    }
    if backend_name is not None:
        out["backend"] = backend_name
    if plan is not None and hasattr(plan, "comm_signature"):
        out["plan_signature"] = repr(plan.comm_signature())
        out["unit"] = repr(getattr(plan, "unit", None))
    return out


def format_sf_view(obj) -> str:
    """The human-readable SFView block (``PetscSFView`` to stdout)."""
    v = sf_view(obj)
    if v["type"] == "DynPlan":
        return (f"SFView: DynPlan {v['label']}: {v['nroots']} roots, "
                f"{v['nleaves']} leaves, unit {v['unit']}")
    e = v["edges"]
    hist = " ".join(f"{d}x{c}" for d, c in
                    sorted(v["root_degree_histogram"].items()))
    lines = [f"SFView: StarForest ({v['nranks']} ranks): {v['nroots']} "
             f"roots, {v['nleaves']} leaves over {v['nleafspace']} slots",
             f"  pattern: {v['pattern']}  edges: {e['total']} "
             f"({e['local']} local / {e['remote']} remote)",
             f"  root degree histogram (degree x count): {hist or '-'}"]
    if "backend" in v:
        lines.append(f"  backend: {v['backend']}  plan: "
                     f"{v.get('plan_signature', '-')}")
    return "\n".join(lines)
