"""Shared test configuration: deterministic property testing.

The golden grids demand bit-exact reproducibility, and flaky property
tests would undermine the same CI signal — so when hypothesis is
installed, every property test runs under a fixed-seed, non-randomized
profile (``derandomize=True`` makes example generation a pure function
of the test body; no ``-p no:randomly``-style plugin interference, no
per-run shrink lottery).  Without hypothesis the property-test modules
degrade to their seeded fallback drives, so the suite stays green on a
bare interpreter either way.

``on_host_devices`` runs a test body where several jax devices are needed
(one serving instance per device) without giving this process more.
"""
import os
import subprocess
import sys

import pytest

try:
    from hypothesis import HealthCheck, settings

    settings.register_profile(
        "repro-ci",
        derandomize=True,          # examples derive from the test, not time
        deadline=None,             # simulator drives are slow but bounded
        print_blob=True,
        suppress_health_check=[HealthCheck.too_slow],
    )
    settings.load_profile("repro-ci")
except ImportError:                # seeded fallbacks cover the gap
    pass


_TESTS = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def on_host_devices():
    """``run(fn, *args, n=4)`` calls the module-level test function ``fn``
    with ``args`` (plain literals) in a fresh interpreter whose CPU backend
    has ``n`` devices; this process keeps its one.  Fails on a non-zero
    exit; returns stdout."""
    def run(fn, *args, n: int = 4, timeout: float = 600) -> str:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(_TESTS), "src"), _TESTS])
        code = (f"from {fn.__module__} import {fn.__name__} as f; "
                f"f(*{args!r})")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=timeout)
        assert out.returncode == 0, out.stderr[-3000:]
        return out.stdout
    return run
