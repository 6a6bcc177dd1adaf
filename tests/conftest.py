"""Test-session guards, CI known-failure handling, shared fixtures.

The dry-run's 512-device flag must NEVER leak into the test session: smoke
tests and benches see the real single device (multi-device tests spawn
subprocesses with their own XLA_FLAGS).

With REPRO_CI_XFAIL=1 (set by .github/workflows/ci.yml), the seed's known
failures listed in tests/known_failures.txt are marked xfail(strict=False)
so the CI job is green while new regressions stay visible. Local runs are
unaffected.
"""

import os

import pytest


def pytest_configure(config):
    flags = os.environ.get("XLA_FLAGS", "")
    assert "xla_force_host_platform_device_count" not in flags, (
        "tests must run without the dry-run device-count flag; "
        "launch/dryrun.py is the only entry point that sets it")


def _known_failures():
    path = os.path.join(os.path.dirname(__file__), "known_failures.txt")
    if not os.path.exists(path):
        return set()
    with open(path) as f:
        return {line.strip() for line in f
                if line.strip() and not line.startswith("#")}


def pytest_collection_modifyitems(config, items):
    if os.environ.get("REPRO_CI_XFAIL") != "1":
        return
    known = _known_failures()
    if not known:
        return
    mark = pytest.mark.xfail(strict=False,
                             reason="known seed failure (known_failures.txt)")
    for item in items:
        # nodeid is tests/<file>::<test>[param]; match on the unparametrized id
        base = item.nodeid.split("[", 1)[0]
        if base in known:
            item.add_marker(mark)


@pytest.fixture
def staged_jax_engine():
    """A jax scan engine that runs every rendez-vous level as its staged
    chain of per-stage kernels: its own max-plus kernel re-bound, so
    ``JaxScanEngine.fuses_levels`` is false."""
    from repro.core.exanet import scan_engine as se

    class StagedJaxEngine(se.JaxScanEngine):
        def maxplus_scan(self, D, T, takes):
            return super().maxplus_scan(D, T, takes)

    return StagedJaxEngine()
