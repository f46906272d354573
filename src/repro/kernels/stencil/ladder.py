"""Execution-plan registry + degradation ladder state.

The canonical plan order (`MODES`) and the canonical degradation ladder
(`DEGRADATION_LADDER`) live here, along with the process-default mode and
ladder (the CI mode matrix pins the whole suite to one plan through
`set_default_chain_mode`; `tests/conftest.py` sets it from the
REPRO_FUSED_MODE env var).  `run_ladder` is the rung loop every driver
entry uses: any rung failure except ValueError (chain misconfiguration
always surfaces) degrades to the next rung with a recorded
`core.faultinject` event; only the FINAL rung's failure raises.
NotImplementedError (a stage the plan cannot lower on this device) also
always raises: a rung that cannot express the chain is not a fault to
degrade around."""
from __future__ import annotations

# every execution plan, fastest-first: streaming (row-carry rings, one
# full-width tile), tiled2d (streaming + the column-tile grid axis),
# window (overlapping-window recompute), ref (staged chain_ref, no launch)
MODES = ("streaming", "tiled2d", "window", "ref")

# the canonical degradation ladder: every rung to the right is strictly
# simpler/safer — tiled2d drops the carried full-width state for per-tile
# state, window drops carried state entirely, ref is the staged chain_ref
# floor (no Pallas launch, always lowerable).  `fused_chain(ladder=...)` —
# or the process default below — makes any rung failure degrade to the
# next rung with a recorded event instead of raising; the FINAL rung's
# failure always raises.
DEGRADATION_LADDER = ("streaming", "tiled2d", "window", "ref")

# forced default execution plan (the CI mode matrix): when set, auto-mode
# callers run this plan instead of consulting the measured cache / halo
# heuristic.  Explicit mode= arguments always win over the default.
_DEFAULT_MODE: str | None = None

_DEFAULT_LADDER: tuple[str, ...] | None = None


def set_default_chain_mode(mode: str | None) -> str | None:
    """Force the plan auto-mode `fused_chain` calls run ("streaming" |
    "tiled2d" | "window" | "ref"), or None to restore cache-then-heuristic
    routing.  Returns the previous default (so callers can save/restore)."""
    global _DEFAULT_MODE
    if mode is not None and mode not in MODES:
        raise ValueError(f"set_default_chain_mode: unknown mode {mode!r}")
    prev, _DEFAULT_MODE = _DEFAULT_MODE, mode
    return prev


def default_chain_mode() -> str | None:
    return _DEFAULT_MODE


def set_default_ladder(ladder) -> tuple[str, ...] | None:
    """Install a process-default degradation ladder for auto/explicit-mode
    `fused_chain` calls (None disables: rung failures raise, the pre-ladder
    contract).  Returns the previous default (save/restore)."""
    global _DEFAULT_LADDER
    if ladder is not None:
        ladder = tuple(ladder)
        for m in ladder:
            if m not in MODES:
                raise ValueError(f"set_default_ladder: unknown rung {m!r}")
        if not ladder:
            ladder = None
    prev, _DEFAULT_LADDER = _DEFAULT_LADDER, ladder
    return prev


def default_ladder() -> tuple[str, ...] | None:
    return _DEFAULT_LADDER


def resolve_rungs(mode: str, ladder) -> tuple[str, ...]:
    """The rung sequence one call runs: the resolved plan first, then the
    ladder's rungs after it (or the whole ladder when the plan is not a
    rung), deduplicated.  ``ladder=None`` consults the process default;
    no ladder means the single-plan raise-on-failure contract."""
    if ladder is None:
        ladder = _DEFAULT_LADDER
    if not ladder:
        return (mode,)
    ladder = tuple(ladder)
    for m in ladder:
        if m not in MODES:
            raise ValueError(f"fused_chain: unknown ladder rung {m!r}")
    tail = ladder[ladder.index(mode) + 1:] if mode in ladder else ladder
    rungs, seen = [mode], {mode}
    for m in tail:
        if m not in seen:
            rungs.append(m)
            seen.add(m)
    return tuple(rungs)


def run_ladder(rungs, run, *, stage: str, detail: str):
    """Try each rung in order: ValueError and NotImplementedError always
    propagate (chain misconfiguration, or a stage this device cannot
    lower, must surface from every plan), any other failure degrades to
    the next rung with a recorded `core.faultinject` event, and the final
    rung's failure raises."""
    from repro.core import faultinject

    for i, rung in enumerate(rungs):
        try:
            return run(rung)
        except (ValueError, NotImplementedError):
            raise           # misconfiguration / unlowerable: never masked
        except Exception as e:
            if i == len(rungs) - 1:
                raise
            faultinject.record_degradation(
                stage=stage, from_plan=rung, to_plan=rungs[i + 1],
                reason=f"{type(e).__name__}: {e}", detail=detail,
                injected=isinstance(e, faultinject.InjectedFault))
