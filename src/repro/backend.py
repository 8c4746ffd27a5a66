"""Host vs device: the one rule that picks where CAM profiles and prices.

* :func:`resolve_executor` — the executor a profile or price call runs on:
  an explicit argument wins, then the ``REPRO_ENGINE_EXECUTOR`` environment
  variable (``host`` / ``device``), then the caller's pinned default, then
  auto — ``device`` on a TPU backend, ``host`` everywhere else.
* :func:`default_interpret` — whether a Pallas kernel runs in interpret
  mode: as asked if asked, else everywhere but a TPU.

A leaf module: it imports nothing from ``repro``.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["ENV_EXECUTOR", "on_tpu", "resolve_executor", "default_interpret"]

#: The environment variable that forces the executor of every call that
#: names none itself (it beats a caller's pinned default).
ENV_EXECUTOR = "REPRO_ENGINE_EXECUTOR"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    import jax

    return jax.default_backend() == "tpu"


def resolve_executor(explicit=None, pinned=None):
    """The executor name (or instance) a call runs on.

    Precedence: ``explicit``, then ``$REPRO_ENGINE_EXECUTOR`` (an empty
    value counts as unset), then ``pinned``, then ``"device"`` on a TPU and
    ``"host"`` elsewhere.  An executor instance that wins is returned as
    it is; a name must be ``"host"`` or ``"device"`` (``ValueError``).
    """
    executor = explicit
    if executor is None:
        executor = os.environ.get(ENV_EXECUTOR) or None
    if executor is None:
        executor = pinned
    if executor is None:
        executor = "device" if on_tpu() else "host"
    if isinstance(executor, str) and executor not in ("host", "device"):
        raise ValueError(f"unknown executor {executor!r}; expected "
                         "'host' or 'device'")
    return executor


def default_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if given, else interpret everywhere but a TPU."""
    if interpret is not None:
        return interpret
    return not on_tpu()
