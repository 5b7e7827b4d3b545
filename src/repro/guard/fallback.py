"""Variant fallback ladder: degrade, quarantine, keep serving.

``registry.call`` trusts the resolved plan; this module wraps it so a
compile or runtime failure in one variant (a Mosaic lowering bug, an
``XlaRuntimeError``, ``RESOURCE_EXHAUSTED`` on a tight device) demotes the
call down the op's candidate ladder instead of killing the request
(DESIGN.md §11). The ladder is the planner's candidate order — the resolved
variant first, the remaining registered variants, and the op's reference
variant (``xla``; ``ref`` for the dataflow-only ``merge``) pinned last, the
same "degrade to the thing that cannot fail" discipline PR 4's cap-doubling
ladder applies to bucket overflow.

Every demotion is visible, never silent:

- a one-line warning on stderr, written whether or not ``repro.obs`` is
  on — a kernel that fails on the chip must not quietly turn into the
  reference;
- ``guard.fallback`` event + counter — which variant failed, which rung
  absorbed the call, and the truncated error.
- ``guard.quarantine`` event + counter — the failing ``(op, variant,
  backend, shape-bucket)`` is quarantined in the planner for the session:
  the plan cache re-points the bucket at the surviving variant, the
  autotuner skips the quarantined plan as known-infeasible, and later calls
  skip the dead rung without paying for another failure.

Only *infrastructure* failures are absorbed (:func:`recoverable`): JAX /
XLA runtime errors, Mosaic lowering failures, RESOURCE_EXHAUSTED, and the
chaos suite's :class:`~repro.guard.inject.InjectedFault`. Input errors
(``EngineInputError`` and friends) propagate — retrying a malformed call on
another variant would just fail differently.
"""
from __future__ import annotations

import sys
from typing import Optional

from repro import obs
from repro.guard.validate import EngineInputError

__all__ = ["guarded_call", "recoverable", "reference_variant"]

#: ops whose most-conservative variant is not named "xla"
_REFERENCE = {"merge": "ref"}

#: exception type names that mark an infrastructure failure worth demoting
#: past (matched by name so jaxlib's binding location doesn't matter)
_RECOVERABLE_TYPES = ("XlaRuntimeError", "JaxRuntimeError", "InternalError",
                      "MosaicError", "LoweringError", "InjectedFault",
                      "NotImplementedError", "CompilationError")

#: message fragments that mark a recoverable failure regardless of type
_RECOVERABLE_MSGS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "Mosaic",
                     "out of memory", "OOM")


def reference_variant(op: str) -> str:
    return _REFERENCE.get(op, "xla")


def recoverable(exc: BaseException) -> bool:
    """Is this an infrastructure failure the ladder may absorb?"""
    if isinstance(exc, (EngineInputError, KeyboardInterrupt, SystemExit)):
        return False
    names = tuple(t.__name__ for t in type(exc).__mro__)
    if any(t in names for t in _RECOVERABLE_TYPES):
        return True
    msg = str(exc)
    return any(m in msg for m in _RECOVERABLE_MSGS)


def _ladder(op: str, plan):
    """Demotion order: resolved variant, the other registered variants in
    the planner's candidate (registry) order, reference variant last."""
    from repro.engine import registry
    ref = reference_variant(op)
    known = registry.variants(op)
    if ref not in known and known:
        ref = known[-1]
    rungs = [plan.variant]
    rungs += [v for v in known if v != plan.variant and v != ref]
    if ref != plan.variant:
        rungs.append(ref)
    return rungs


def _bucket(op: str, args) -> Optional[tuple]:
    """The plan-cache key of this call (None when the op's example args
    cannot be bucketed — the ladder still runs, just without quarantine)."""
    try:
        from repro.engine.api import infer_key
        return infer_key(op, *args)
    except Exception:
        return None


def guarded_call(op: str, plan, *args, **kw):
    """``registry.call`` under the fallback ladder.

    Dispatches ``op`` with ``plan`` (passed down as ``plan=``); on a
    recoverable failure quarantines the rung and retries the next one. The
    last rung's failure — or any non-recoverable error — propagates. The
    ladder's own bookkeeping (the call's bucket and rungs) runs under the
    ``engine.guard`` span, beside the dispatch's ``engine.<op>.<variant>``.
    """
    from repro.engine import registry
    from repro.engine.planner import _key_str, default_planner

    with obs.span("engine.guard"):
        key = _bucket(op, args)
        rungs = _ladder(op, plan)
    for i, variant in enumerate(rungs):
        last_rung = i + 1 == len(rungs)
        if not last_rung and key is not None \
                and default_planner.is_quarantined(key, variant):
            obs.inc("guard.quarantine.skip")
            continue
        p = plan if variant == plan.variant else plan.replace(variant=variant)
        try:
            out = registry.call(op, p.variant, *args, plan=p, **kw)
        except Exception as e:
            if last_rung or not recoverable(e):
                raise
            if key is not None:
                default_planner.quarantine(key, p)
                obs.event("guard.quarantine", op=op, variant=variant,
                          key=_key_str(key))
            print(f"repro.guard: {op} variant {variant!r} failed "
                  f"({type(e).__name__}: {str(e)[:160]}); demoted to "
                  f"{rungs[i + 1]!r}", file=sys.stderr, flush=True)
            obs.inc("guard.fallback")
            obs.inc("guard.quarantine")
            obs.event("guard.fallback", op=op, from_variant=variant,
                      to_variant=rungs[i + 1],
                      key=None if key is None else _key_str(key),
                      error=f"{type(e).__name__}: {e}"[:200])
            continue
        if variant != plan.variant and key is not None:
            # future calls on this bucket resolve straight to the survivor
            default_planner.put(key, p)
        return out
    raise AssertionError("unreachable: empty fallback ladder")  # pragma: no cover
