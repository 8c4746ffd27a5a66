"""The host-vs-device rule (``repro.backend``): executor precedence and the
Pallas interpret default, as a table."""
import jax
import pytest

from repro import backend

#: Stands in for an executor instance (``DeviceExecutor(interpret=False)``).
INSTANCE = object()


@pytest.fixture
def on(monkeypatch):
    """Set the backend JAX reports: ``on("tpu")`` or ``on("cpu")``."""
    def set_backend(name):
        monkeypatch.setattr(jax, "default_backend", lambda: name)
    return set_backend


# (explicit, env, pinned, backend) -> expected executor
RESOLVE = [
    pytest.param("host", "device", "device", "tpu", "host",
                 id="explicit-beats-env-and-pin"),
    pytest.param("device", "host", "host", "cpu", "device",
                 id="explicit-device-on-cpu"),
    pytest.param(INSTANCE, "host", None, "cpu", INSTANCE,
                 id="explicit-instance-passes-through"),
    pytest.param(None, "device", "host", "cpu", "device",
                 id="env-beats-pin"),
    pytest.param(None, "host", "device", "tpu", "host",
                 id="env-host-on-tpu"),
    pytest.param(None, "host", INSTANCE, "cpu", "host",
                 id="env-beats-pinned-instance"),
    pytest.param(None, "", "device", "cpu", "device",
                 id="empty-env-is-unset"),
    pytest.param(None, None, "device", "cpu", "device",
                 id="pin-beats-auto-on-cpu"),
    pytest.param(None, None, "host", "tpu", "host",
                 id="pin-beats-auto-on-tpu"),
    pytest.param(None, None, INSTANCE, "tpu", INSTANCE,
                 id="pinned-instance-passes-through"),
    pytest.param(None, None, None, "cpu", "host", id="auto-cpu"),
    pytest.param(None, None, None, "tpu", "device", id="auto-tpu"),
]


@pytest.mark.parametrize("explicit,env,pinned,backend_name,want", RESOLVE)
def test_resolve_executor(monkeypatch, on, explicit, env, pinned,
                          backend_name, want):
    on(backend_name)
    if env is None:
        monkeypatch.delenv(backend.ENV_EXECUTOR, raising=False)
    else:
        monkeypatch.setenv(backend.ENV_EXECUTOR, env)
    got = backend.resolve_executor(explicit, pinned)
    assert got is want if want is INSTANCE else got == want


@pytest.mark.parametrize("explicit,env,pinned", [
    pytest.param("gpu", None, None, id="explicit"),
    pytest.param(None, "tpu", None, id="env"),
    pytest.param(None, None, "Device", id="pinned"),
])
def test_resolve_executor_rejects_unknown_name(monkeypatch, on, explicit,
                                               env, pinned):
    on("cpu")
    if env is None:
        monkeypatch.delenv(backend.ENV_EXECUTOR, raising=False)
    else:
        monkeypatch.setenv(backend.ENV_EXECUTOR, env)
    with pytest.raises(ValueError, match="unknown executor"):
        backend.resolve_executor(explicit, pinned)


@pytest.mark.parametrize("interpret,backend_name,want", [
    pytest.param(True, "tpu", True, id="asked-on-tpu"),
    pytest.param(False, "cpu", False, id="asked-off-on-cpu"),
    pytest.param(None, "cpu", True, id="auto-cpu"),
    pytest.param(None, "tpu", False, id="auto-tpu"),
])
def test_default_interpret(on, interpret, backend_name, want):
    on(backend_name)
    assert backend.default_interpret(interpret) is want
    assert backend.on_tpu() is (backend_name == "tpu")
