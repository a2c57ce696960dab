"""Shared backend resolution for every Pallas kernel family.

Each ``kernels/<family>/ops.py`` wrapper takes the same
``backend="auto"|"pallas"|"interpret"|"jnp"`` switch.  Before this module
existed, every ops.py re-implemented the ``"auto"`` rule (and only
``candidate_align`` honored an env override); now all families route
through :func:`resolve_backend`, so the policy lives in exactly one place:

  - ``"auto"`` resolves to the env override when set, else to the Pallas
    kernel on TPU and the bit-exact jnp oracle everywhere else;
  - ``REPRO_BACKEND`` overrides the auto choice for *all* kernel families
    (CI uses ``REPRO_BACKEND=interpret`` to drive the whole pipeline
    through the interpret-mode kernels on CPU);
  - anything other than the four known names raises ``ValueError``.

The ops wrappers are jitted with ``backend`` static, so the env var is
read at *trace* time: set it before the first call in a process (or
call ``<op>.clear_cache()`` after changing it, as the tests do).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "REPRO_BACKEND"

#: every backend an ops.py wrapper accepts after resolution
BACKENDS = ("pallas", "interpret", "jnp")


def resolve_backend(backend: str = "auto", family: str | None = None) -> str:
    """Resolve a kernel-family ``backend`` argument to a concrete backend.

    ``family`` only decorates error messages; the policy is identical for
    every kernel family.  Returns one of :data:`BACKENDS`.
    """
    if backend == "auto":
        backend = os.environ.get(ENV_VAR) or (
            "pallas" if jax.default_backend() == "tpu" else "jnp")
    if backend not in BACKENDS:
        where = f" for kernel family {family!r}" if family else ""
        raise ValueError(f"unknown backend {backend!r}{where}; expected "
                         f"'auto' or one of {BACKENDS}")
    return backend
