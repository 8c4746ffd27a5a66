"""Shared fixtures.

``engine_executor`` is THE way a test forces the engine/profiling
dispatch: it scopes ``REPRO_ENGINE_EXECUTOR`` through monkeypatch so the
forcing can never leak into another test (a bare ``os.environ`` write
would).  The env var is read per call by ``repro.backend.resolve_executor``,
which both the price side (``PricingEngine._resolve``) and the profile side
(``CostSession``'s mixed-eps pass) call, so one fixture steers both halves
of the chained profile→price path.
"""
import pytest


@pytest.fixture
def engine_executor(monkeypatch):
    """Force (or clear) the executor env override for this test only.

    Returns a setter: ``engine_executor("device")`` pins both the pricing
    and the profiling dispatch; ``engine_executor(None)`` restores the
    auto rule (device iff the default jax backend is TPU).
    """
    def force(name):
        if name is None:
            monkeypatch.delenv("REPRO_ENGINE_EXECUTOR", raising=False)
        else:
            monkeypatch.setenv("REPRO_ENGINE_EXECUTOR", name)
    return force
