"""Test harness config.

Tests run on a virtual 8-device CPU mesh (mirrors the reference's in-process
multi-node test clusters, SURVEY.md §4: KVRangeStoreTestCluster et al. — real
components over fake transports). The chip is reached through
``chip_smoke.py``, never from the unit suite.

Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# pay the import once, here: a first `import jax` inside a test stalls the
# event loop for seconds and trips that test's own RPC timeouts
import jax  # noqa: E402,F401

# ---------------------------------------------------------------------------
# Minimal async test support (pytest-asyncio is not in the image and installs
# are not allowed): coroutine tests and async-generator fixtures run on one
# shared event loop.
# ---------------------------------------------------------------------------
import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

_LOOP = None


def _loop():
    global _LOOP
    if _LOOP is None or _LOOP.is_closed():
        _LOOP = asyncio.new_event_loop()
    return _LOOP


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: coroutine test")


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        sig = inspect.signature(func).parameters
        kwargs = {k: pyfuncitem.funcargs[k] for k in sig
                  if k in pyfuncitem.funcargs}
        _loop().run_until_complete(asyncio.wait_for(func(**kwargs), 60))
        return True
    return None


@pytest.hookimpl(tryfirst=True)
def pytest_fixture_setup(fixturedef, request):
    func = fixturedef.func
    if inspect.isasyncgenfunction(func):
        kwargs = {name: request.getfixturevalue(name)
                  for name in fixturedef.argnames}
        gen = func(**kwargs)
        value = _loop().run_until_complete(gen.__anext__())

        def fin():
            try:
                _loop().run_until_complete(gen.__anext__())
            except StopAsyncIteration:
                pass

        request.addfinalizer(fin)
        fixturedef.cached_result = (value, fixturedef.cache_key(request), None)
        return value
    if inspect.iscoroutinefunction(func):
        kwargs = {name: request.getfixturevalue(name)
                  for name in fixturedef.argnames}
        value = _loop().run_until_complete(func(**kwargs))
        fixturedef.cached_result = (value, fixturedef.cache_key(request), None)
        return value
    return None


@pytest.fixture(scope="session")
def certs(tmp_path_factory):
    """Self-signed TLS cert pair shared by TLS listener/RPC tests."""
    import subprocess
    d = tmp_path_factory.mktemp("certs")
    key, crt = str(d / "k.pem"), str(d / "c.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", crt, "-days", "1",
         "-subj", "/CN=localhost"], check=True, capture_output=True)
    return key, crt


@pytest.fixture
def no_implicit_transfers():
    """ISSUE 10 transfer-guard sanitizer: yields a context-manager
    factory; the test warms its path (compiles) first, then serves
    inside ``with no_implicit_transfers():`` — any implicit device
    transfer raises. Proves the guard arms on this jax before handing
    it out, so the harness can never pass vacuously."""
    from bifromq_tpu.analysis import sanitize
    sanitize.assert_guard_arms()
    return sanitize.no_implicit_transfers
