"""Test harness config: run JAX on 8 virtual CPU devices.

The tests run on the CPU: multi-chip sharding paths are exercised on a
virtual CPU mesh, and tests/test_chip_compile.py compiles the served
kernels for a described (not attached) v5e.  On the chip the program is
run through the builder's chip tool, `python chip_smoke.py` first.

The platform is forced twice — the environment variable for child
processes, jax.config for this one, before any backend initializes —
so no test can reach an accelerator whatever the environment says.  CPU
keeps first-shape jit compiles to ~100ms, which matters for cluster
tests with client op timeouts.

The persistent compile cache (ceph_tpu/ops/compile_cache.py) is turned
off for the tests: they must not depend on what an earlier run left
behind, and a described-topology compile writes entries that no CPU
process can read back.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(params=["native", "python"])
def denc_walk(request, monkeypatch):
    """Run a test under each of the codec's two walks (utils/denc.py,
    msg/message.py): the native tier's compiled one, and the Python
    one that serves where `native.get_ext()` has nothing (which also
    sends the CRC and GF entry points to their own fallbacks)."""
    from ceph_tpu import native
    if request.param == "native":
        if native.get_ext() is None:
            pytest.skip("the native tier's extension cannot be built "
                        "here (no g++, or no Python.h)")
    else:
        monkeypatch.setattr(native, "get_ext", lambda: None)
    return request.param


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests (chaos soaks) excluded from tier-1 "
        "via -m 'not slow'")

