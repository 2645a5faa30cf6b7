"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax is imported.

The suite runs on the CPU by design — correctness, control flow and counts,
never rates (PERF.md).  ``JAX_PLATFORMS=cpu`` keeps JAX off any attached chip
(a chip belongs to one process at a time, and the tests must not take it), and
``--xla_force_host_platform_device_count=8`` gives the CPU backend eight
devices, so every sharding test runs against a real 8-device mesh with XLA
collectives (SURVEY.md §4).  The chip's own checks are ``chip_smoke.py`` (run
through the chip tool) and ``tests/test_tpu_compile.py`` (the chip's compiler
against a described topology, nothing executed).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The suite's wall-clock is dominated by XLA compiles of the SAME tiny shapes
# repeated across modules and runs; the persistent compile cache (the same
# function bench.py, chip_smoke.py and a `serve` boot call) makes warm runs fit
# the tier-1 time budget.  Tests assert on numerics and behavior, never on
# compile time, so cached executables change nothing observable; set
# DABT_COMPILE_CACHE_OFF=1 for a cold-compile run.
from django_assistant_bot_tpu.utils.compile_cache import (  # noqa: E402
    enable_persistent_compile_cache,
)

enable_persistent_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from django_assistant_bot_tpu.parallel import best_mesh_shape, make_mesh

    n = len(jax.devices())
    return make_mesh(best_mesh_shape(n, want_model=2, want_seq=2))


@pytest.fixture()
def tmp_db(tmp_path, monkeypatch):
    """Fresh sqlite database per test."""
    db_path = tmp_path / "dabt.sqlite3"
    monkeypatch.setenv("DABT_DB_PATH", str(db_path))
    from django_assistant_bot_tpu.storage import db

    db.reset_default_database()
    yield db.get_database()
    db.reset_default_database()
