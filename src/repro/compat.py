"""Deprecation plumbing shared by the pre-engine entry points.

:func:`warn_deprecated` is the warn-once helper of the entry points
(`map_pairs`, the `distributed.make_*` factories) that now delegate to
`repro.engine` — it lives here rather than in the engine package so
`repro.core` modules can import it without a core <-> engine cycle.
"""
from __future__ import annotations

import warnings


_warned: set[str] = set()


def warn_deprecated(name: str, message: str, stacklevel: int = 3) -> None:
    """Emit ``DeprecationWarning`` for ``name`` once per process.

    The shimmed entry points stay fully functional (tests pin the engine
    against them bit-for-bit), so one nudge per process is enough; a
    warning per call would drown the suites that use them as oracles.
    """
    if name in _warned:
        return
    _warned.add(name)
    warnings.warn(message, DeprecationWarning, stacklevel=stacklevel)


def reset_deprecation_warnings() -> None:
    """Re-arm the warn-once latches (test isolation helper)."""
    _warned.clear()
