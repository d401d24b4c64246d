"""Jit recompilation telemetry for the serving engine.

XLA's jit cache keys on argument shapes/dtypes; a serving loop that lets
a batch dimension wobble (e.g. sizing the decode batch to the number of
*live* slots instead of padding to ``max_slots``) silently recompiles
every few steps — the classic variable-batch serving bug.  The engine
reports each jitted program's argument signature to a
:class:`RecompilationTracker`; a signature never seen before counts as a
compilation, and any compilation after
:meth:`~RecompilationTracker.mark_warm` (or beyond the first signature
per program) emits a ``recompile`` warning event through the tracer.
Steady-state serving must report **zero** post-warm recompiles
(``tests/test_observatory.py`` asserts it; ``launch/serve.py --profile``
prints the summary).  Kernel times come from a device trace: the step
phases are program spans (``tracing.SPANS``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


class RecompilationTracker:
    """Shape-signature compilation counter for the engine's jitted
    programs.  ``observe`` is on the hot path — one tuple hash and one
    set lookup per call — and only does real work on a novel signature."""

    def __init__(self):
        self.signatures: Dict[str, set] = {}
        self.post_warm: Dict[str, int] = {}
        self.warm = False

    def mark_warm(self) -> None:
        """Declare warmup over: every later novel signature is a
        *post-warm recompile* — shape churn, the thing steady-state
        serving must never do."""
        self.warm = True

    def observe(self, program: str, signature: Tuple,
                tracer=None) -> bool:
        """Record one invocation of ``program`` with argument shape
        ``signature``.  Returns True when the signature is new (i.e. XLA
        compiled).  Beyond each program's first signature — or any novel
        signature after :meth:`mark_warm` — a ``recompile`` warning
        event goes through ``tracer``."""
        sigs = self.signatures.setdefault(program, set())
        if signature in sigs:
            return False
        sigs.add(signature)
        if self.warm:
            self.post_warm[program] = self.post_warm.get(program, 0) + 1
        if tracer is not None and (self.warm or len(sigs) > 1):
            tracer.recompile(program, repr(signature), len(sigs),
                             post_warm=self.warm)
        return True

    @property
    def post_warm_recompiles(self) -> int:
        return sum(self.post_warm.values())

    def compiles(self, program: Optional[str] = None) -> int:
        if program is not None:
            return len(self.signatures.get(program, ()))
        return sum(len(s) for s in self.signatures.values())

    def churning_programs(self, threshold: int = 3) -> List[str]:
        """Programs with suspiciously many signatures — the triage list:
        find which argument's shape wobbles and pad it."""
        return sorted(p for p, s in self.signatures.items()
                      if len(s) >= threshold or self.post_warm.get(p, 0))

    def summary(self) -> Dict[str, object]:
        return {
            "warm": self.warm,
            "compiles_total": self.compiles(),
            "post_warm_recompiles": self.post_warm_recompiles,
            "programs": {p: {"signatures": len(s),
                             "post_warm": self.post_warm.get(p, 0)}
                         for p, s in sorted(self.signatures.items())},
            "churning": self.churning_programs(),
        }
