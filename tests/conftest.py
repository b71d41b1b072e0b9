"""Test-suite bootstrap."""
from __future__ import annotations


def pytest_configure(config):
    """Point the autotune spec cache at a throwaway path so test runs
    never touch (or depend on) the user's ~/.cache store."""
    import os
    import tempfile

    if "REPRO_AUTOTUNE_CACHE" not in os.environ:
        os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
            tempfile.mkdtemp(prefix="repro-autotune-"), "cache.json"
        )
