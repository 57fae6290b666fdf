"""Device time by phase of the FedGS round.

The program names each phase of its round with a ``jax.named_scope``
(``core/fedgs.py``, ``core/engine.py``). A scope reaches the compiled HLO
only as ``op_name`` metadata, for example
``jit(run_chunk)/while/body/fedgs.train/jvp(fedgs.schedule)/mul``, and an
op belongs to the innermost (last) phase in that path. A TPU op event is
named by its instruction's HLO text without the metadata
(``%fusion.733 = f32[...] fusion(...)``), so the phase of an event is
looked up by instruction name in a map read from the compiled module's
text (``compiled.as_text()``). Times are self times, so a loop op does not
count its body twice, and the phases with the ops under no phase
(``unscoped``) add up to the device's busy time. A scope that is no phase
(one a model adds inside ``fedgs.train``, say) is read the same way by
:func:`scope_time_s`.
"""
from __future__ import annotations

import re

from . import trace

PHASES = ("fedgs.sample", "fedgs.schedule", "fedgs.select", "fedgs.train",
          "fedgs.eq4", "fedgs.eq5", "engine.eval")
UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def innermost_phase(op_name: str) -> str | None:
    """The phase that appears last in an ``op_name`` path, or None."""
    best, at = None, -1
    for phase in PHASES:
        i = op_name.rfind(phase)
        if i > at:
            best, at = phase, i
    return best


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` path, for every instruction of a
    compiled module's text that has one (a fusion carries its root's
    metadata)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        op = _OP_NAME.search(line) if m else None
        if op is not None:
            out[m.group(1)] = op.group(1)
    return out


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> phase, for every instruction of a compiled
    module's text whose ``op_name`` holds one."""
    return {name: phase for name, path in op_names(hlo_text).items()
            if (phase := innermost_phase(path)) is not None}


def instruction(event_name: str) -> str:
    """The instruction name of a device-op event: the ``%name =`` prefix
    of a TPU op's name, else the name itself."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def phase_times(tr: trace.Trace, scopes: dict[str, str]) -> dict[str, float]:
    """Device self time inside the window of each phase, by the ops'
    instruction names in ``scopes`` (:func:`op_scopes`), and of the rest
    under ``unscoped``, mean over chips."""
    lo, hi = tr.window
    out = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
    for chip in tr.ops:
        for name, t in trace.self_times(trace._clip(chip, lo, hi)):
            out[scopes.get(instruction(name), UNSCOPED)] += t
    return {k: v / max(len(tr.ops), 1) for k, v in out.items()}


def scope_time_s(tr: trace.Trace, names: dict[str, str], scope: str
                 ) -> float:
    """Device self time inside the window of the ops whose ``op_name``
    path (``names``, from :func:`op_names`) holds ``scope``, mean over
    chips."""
    lo, hi = tr.window
    total = sum(t for chip in tr.ops
                for name, t in trace.self_times(trace._clip(chip, lo, hi))
                if scope in names.get(instruction(name), ""))
    return total / max(len(tr.ops), 1)


def phase_ms_per_round(ctx: dict, phase: str) -> float | None:
    """A per-layer reader's value: device self time per traced round of
    ``phase`` (or of ``unscoped``), in ms. None where the compiled round
    names no op of that phase, or no op of any phase for ``unscoped``."""
    scopes = ctx["scopes"]
    if not scopes or (phase != UNSCOPED and phase not in scopes.values()):
        return None
    return 1e3 * phase_times(ctx["trace"], scopes)[phase] / ctx["rounds"]
