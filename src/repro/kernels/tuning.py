"""Kernel autotuning and interpret-mode policy for the SF hot path.

PetscSF picks its implementation "based on the characteristics of the
application or the target architecture" (paper abstract, §4–5).  This module
is the kernel-level half of that idea for the JAX port: every SF pack /
unpack entry point has several *candidate lowerings* (a pure-XLA gather, a
row-per-grid-step DMA kernel, row-blocked vectorized kernels at several block
sizes, a fused local-exchange kernel), and the first time a given problem
*signature* is executed the candidates are swept on synthetic data of the
same shape, the winner is memoized, and every later call — including calls
made while tracing under ``jax.jit`` / ``shard_map`` — dispatches straight
to the cached winner.  This is the kernel-search idiom of "Accelerating
Communication for Parallel Programming Models on GPU Systems" (PAPERS.md):
match the transfer strategy to the message shape, once, at setup time.

Cache scope: process-level, keyed by ``(kind, shape signature, plan
signature, interpret flag, jax platform)``.  Repeated halo exchanges (CG
iterations, DMDA sweeps, FieldBundle multi-exchanges) therefore never
re-sweep and never re-trace — ``jax.jit`` sees the same callable and the
same static arguments every time.

Environment knobs (see README "Data-driven backend selection & autotuning"):

``REPRO_SF_INTERPRET``
    ``1`` force Pallas interpret mode, ``0`` force compiled (Mosaic)
    lowering, unset = auto (compiled on TPU, interpret elsewhere).
``REPRO_SF_AUTOTUNE``
    ``0`` never sweep (use the per-platform default lowering), ``1`` always
    sweep, unset = auto (sweep only when the problem is big enough for the
    lowering choice to matter; tiny problems take the default).
``REPRO_SF_IMPL_<KIND>``
    Pin the lowering for one entry-point kind (``PACK``, ``SEGRED``,
    ``LOCALBCAST``), e.g. ``REPRO_SF_IMPL_PACK=xla`` or
    ``REPRO_SF_IMPL_PACK=block:128``.  Pinned lowerings bypass the sweep.
``REPRO_SF_TUNE_ITERS``
    Timing iterations per candidate per round during a sweep (default 3).
``REPRO_SF_TUNE_ROUNDS``
    Interleaved timing rounds per sweep (default 3).  Each candidate's
    score is its best round, so a transient load spike on the host can
    disqualify at most one window instead of crowning a slow lowering.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, Optional, Tuple

import jax

__all__ = [
    "compiled_supported", "resolve_interpret",
    "autotune", "lookup", "winners", "stats", "clear_cache",
]


def compiled_supported() -> bool:
    """True when the Pallas kernels can lower past interpret mode (Mosaic
    today means TPU; everywhere else ``pallas_call`` only interprets)."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The single interpret-vs-compiled decision for every kernel entry point
    (``kernels/ops.py`` wrappers, the pallas backend, DistSF): an explicit
    argument wins, then the ``REPRO_SF_INTERPRET`` env override, then
    platform detection."""
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_SF_INTERPRET", "").strip().lower()
    if env in ("0", "false", "no"):
        return False
    if env in ("1", "true", "yes"):
        return True
    return not compiled_supported()


# --------------------------------------------------------------------------
# winner cache + statistics
# --------------------------------------------------------------------------
Key = Tuple
_WINNERS: Dict[Key, str] = {}


class _StatCounters:
    """Mapping facade over sflog registry counters.

    Keeps the historical ``_STATS["hits"] += 1`` call sites and the
    ``stats()``/``clear_cache()`` contract intact while the values live in
    :mod:`repro.core.sflog` (so ``log_view``/``dump_json`` report autotune
    activity).  The sflog import is deferred to first use: ``repro.core``
    imports this module during package init, so a module-level import would
    be circular.
    """

    _KEYS = ("sweeps", "hits", "defaults", "pinned", "candidate_errors",
             "sweep_ns")

    def __init__(self):
        self._c = None

    def _counters(self):
        if self._c is None:
            from ..core import sflog
            self._c = {k: sflog.counter(f"tuning.{k}") for k in self._KEYS}
        return self._c

    def __getitem__(self, k: str) -> int:
        return self._counters()[k].value

    def __setitem__(self, k: str, v: int) -> None:
        self._counters()[k].value = int(v)

    def __iter__(self):
        return iter(self._KEYS)

    def keys(self):
        return self._KEYS


_STATS = _StatCounters()

# Below this many payload elements the lowering choice is noise — take the
# default instead of paying a sweep (override with REPRO_SF_AUTOTUNE=1).
_MIN_TUNE_WORK = 4096


def stats() -> Dict[str, int]:
    """Counters for tests and diagnostics (sweeps run, cache hits, ...;
    ``sweep_ns`` is the wall time of every sweep, summed)."""
    return dict(_STATS)


_LINKED_CACHES = []


def register_cache(cache: dict) -> None:
    """Link a winner-derived cache (e.g. the jitted dispatch closures in
    ``kernels/ops.py``) so ``clear_cache`` empties it too — a stale closure
    would keep executing a winner the cleared table no longer holds."""
    _LINKED_CACHES.append(cache)


def clear_cache() -> None:
    """Drop every memoized winner and reset counters (test isolation)."""
    _WINNERS.clear()
    for c in _LINKED_CACHES:
        c.clear()
    for k in _STATS:
        _STATS[k] = 0


def lookup(key: Key) -> Optional[str]:
    return _WINNERS.get(key)


def winners() -> Dict[Key, str]:
    """A copy of the full winner cache ``(kind, *signature) -> lowering``
    (benchmark reporting, diagnostics)."""
    return dict(_WINNERS)


def _time_candidate(fn: Callable, args: tuple, iters: int) -> float:
    out = fn(*args)                      # compile + validate
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def autotune(kind: str, key: Key, candidates: Dict[str, Callable],
             make_args: Callable[[], tuple], *, default: str,
             work: Optional[int] = None) -> str:
    """Return the winning candidate name for ``key``, sweeping if needed.

    ``candidates`` maps lowering name -> callable; ``make_args`` builds
    synthetic concrete arrays matching the problem signature (sweeps run
    eagerly even when the caller is mid-trace under ``jax.jit``).  Every
    candidate must run: the candidate sets offer a kernel only where its
    shape rules say it compiles, so a candidate that raises is a bug and
    the sweep re-raises it (counted in ``stats()["candidate_errors"]``)
    rather than letting another lowering win unnoticed.  ``work`` (payload
    elements) gates the sweep in auto mode; ``default`` is used when the
    sweep is skipped.
    """
    full_key = (kind,) + tuple(key)
    winner = _WINNERS.get(full_key)
    if winner is not None:
        _STATS["hits"] += 1
        return winner

    pinned = os.environ.get(f"REPRO_SF_IMPL_{kind.upper()}", "").strip()
    if pinned:
        if pinned not in candidates:
            raise ValueError(
                f"REPRO_SF_IMPL_{kind.upper()}={pinned!r} is not a candidate "
                f"for this problem; have {sorted(candidates)}")
        _STATS["pinned"] += 1
        _WINNERS[full_key] = pinned
        return pinned

    mode = os.environ.get("REPRO_SF_AUTOTUNE", "auto").strip().lower()
    sweep = mode not in ("0", "false", "no") and (
        mode in ("1", "true", "yes")
        or work is None or work >= _MIN_TUNE_WORK)
    if not sweep:
        _STATS["defaults"] += 1
        winner = default if default in candidates else next(iter(candidates))
        _WINNERS[full_key] = winner
        return winner

    t_sweep = time.perf_counter_ns()
    iters = int(os.environ.get("REPRO_SF_TUNE_ITERS", "3"))
    rounds = int(os.environ.get("REPRO_SF_TUNE_ROUNDS", "3"))
    args = make_args()

    def timed(name: str) -> float:
        try:
            return _time_candidate(candidates[name], args, iters)
        except Exception as e:
            _STATS["candidate_errors"] += 1
            raise RuntimeError(
                f"{kind} lowering {name!r} failed on {key}") from e

    # interleaved best-of-rounds: one timing window per candidate per round,
    # candidate's score = min over rounds.  A single load spike can land in
    # at most one window, so it can no longer crown a slow lowering (a
    # mis-pick is sticky for the whole process — worth the extra rounds)
    best: Dict[str, float] = {}
    for _ in range(max(rounds, 1)):
        for name in candidates:
            best[name] = min(best.get(name, float("inf")), timed(name))
    best_name = min(best, key=best.get)
    if best_name != default and default in best:
        # runoff: a mis-crowned winner is sticky for the whole process,
        # so before dethroning the platform default re-time the two
        # head-to-head in alternating windows (load spikes hit both)
        tw = td = float("inf")
        for _ in range(max(rounds, 1)):
            tw = min(tw, timed(best_name))
            td = min(td, timed(default))
        if td <= tw:
            best_name = default
    _STATS["sweeps"] += 1
    _STATS["sweep_ns"] += time.perf_counter_ns() - t_sweep
    _WINNERS[full_key] = best_name
    return best_name
