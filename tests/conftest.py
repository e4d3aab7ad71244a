"""Test bootstrap.

Puts JAX on a virtual 8-device CPU platform BEFORE jax is imported
anywhere — the moral equivalent of the reference's SharedSparkContext
`local[*]` trick (SURVEY.md §4): distributed/sharding logic is exercised
in-process without TPU hardware. Subprocesses the tests start inherit the
same environment.
"""

import atexit
import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The compile cache of a test session lives and dies with the session:
# with the variable set the program sets no directory of its own
# (workflow/context.py), so Tier-1 never fills the checkout's .jax_cache.
_cache_dir = tempfile.mkdtemp(prefix="pio_test_jax_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture()
def memory_storage():
    """Isolated all-in-memory Storage registry."""
    from incubator_predictionio_tpu.data.storage import Storage

    env = {
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEMORY",
    }
    storage = Storage.reset_instance(env)
    yield storage
    Storage.reset_instance({
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "MEMORY",
    })


@pytest.fixture()
def sqlite_storage(tmp_path):
    """Isolated SQLite-backed Storage registry in a temp dir."""
    from incubator_predictionio_tpu.data.storage import Storage

    env = {
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
        "PIO_STORAGE_SOURCES_DB_TYPE": "SQLITE",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.sqlite"),
    }
    storage = Storage.reset_instance(env)
    yield storage
    storage.close()
