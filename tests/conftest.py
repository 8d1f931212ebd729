"""Test-session setup: autotune records go to a per-process scratch file,
never to the tile table kept in the checkout."""

import os
import tempfile


def pytest_configure(config):
    os.environ.setdefault(
        "REPRO_PCILT_TUNE_CACHE",
        os.path.join(tempfile.mkdtemp(prefix="pcilt-tiles-"), "tiles.json"))
