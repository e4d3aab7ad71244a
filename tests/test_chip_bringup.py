"""What PR 21 (chip bring-up) changed: where the compile cache lives,
chip_smoke.py's no-accelerator contract, device budgets that do not
guess, and a native library keyed by its source."""

import logging
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache placement (workflow/context.py) ---------------------------


@pytest.fixture()
def cache_calls(monkeypatch):
    """Re-arm enable_compilation_cache and RECORD the jax.config.update
    calls it makes instead of applying them (the session's real config
    must not move)."""
    import jax

    from incubator_predictionio_tpu.workflow import context

    calls = []
    monkeypatch.setattr(context, "_cache_enabled", False)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.delenv("PIO_COMPILATION_CACHE", raising=False)
    return calls


def test_cache_dir_variable_set_means_code_sets_nothing(cache_calls):
    import jax

    from incubator_predictionio_tpu.workflow.context import WorkflowContext

    outer = os.environ["JAX_COMPILATION_CACHE_DIR"]  # conftest's session dir
    WorkflowContext()
    assert cache_calls == []
    assert jax.config.jax_compilation_cache_dir == outer


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout(
        cache_calls, monkeypatch, tmp_path):
    """Would have caught bf63b1b: the NameError there meant update() was
    never reached."""
    from incubator_predictionio_tpu.workflow import context

    assert context.DEFAULT_COMPILATION_CACHE_DIR == os.path.join(
        REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    fixed = str(tmp_path / ".jax_cache")  # keep the real one untouched
    monkeypatch.setattr(context, "DEFAULT_COMPILATION_CACHE_DIR", fixed)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    for basedir in ("store_a", "store_b"):  # PIO_FS_BASEDIR plays no part
        monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / basedir))
        monkeypatch.setattr(context, "_cache_enabled", False)
        context.WorkflowContext()
    assert cache_calls == [("jax_compilation_cache_dir", fixed)] * 2
    assert os.path.isdir(fixed)


def test_cache_enable_failure_is_logged_not_swallowed(
        cache_calls, monkeypatch, caplog):
    from incubator_predictionio_tpu.workflow import context

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")

    def refuse(*a, **k):
        raise PermissionError("read-only checkout")

    monkeypatch.setattr(context.os, "makedirs", refuse)
    with caplog.at_level(logging.WARNING, logger="pio.workflow"):
        context.WorkflowContext()  # a train must not die for its cache
    assert cache_calls == []
    rec = [r for r in caplog.records if "NOT enabled" in r.getMessage()]
    assert rec and rec[0].levelno == logging.WARNING
    assert rec[0].exc_info and "read-only checkout" in str(rec[0].exc_info[1])


def test_cache_opt_out_touches_nothing(cache_calls, monkeypatch):
    from incubator_predictionio_tpu.workflow import context

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("PIO_COMPILATION_CACHE", "0")
    context.WorkflowContext()
    assert cache_calls == []


# -- chip_smoke.py without a chip ---------------------------------------------


def test_chip_smoke_fails_fast_without_accelerator_and_stays_off_jax():
    """JAX_PLATFORMS=cpu: non-zero exit within seconds, a reason, no
    result line — and the PARENT never imported jax (a parent that has
    touched JAX holds the chip its children need)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke\n"
         "rc = chip_smoke.main([])\n"
         "print('PARENT_JAX_FREE', 'jax' not in sys.modules)\n"
         "sys.exit(rc)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert time.monotonic() - t0 < 30
    assert r.returncode != 0
    assert "PARENT_JAX_FREE True" in r.stdout
    assert "no tpu device" in r.stderr and "platform='cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program: non-zero, no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode != 0
    assert "is missing" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_plumbing():
    """The same script at ML-100k on the CPU (~1 min): every child, the
    HTTP checks, the float64 references — and it says it is no chip run."""
    r = subprocess.run([sys.executable, "chip_smoke.py", "--cpu-rehearsal"],
                       cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"ok": false' in last and '"rehearsal": true' in last
    assert "NOT a chip run" in r.stdout


# -- device budgets do not guess (parallel/mesh.py) ---------------------------


def test_device_memory_bytes_cpu_default_and_tpu_without_stats(monkeypatch):
    import jax

    from incubator_predictionio_tpu.parallel import mesh

    assert mesh.device_memory_bytes() == 4 * 1024 ** 3  # CPU: no stats

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "local_devices",
                        lambda: [FakeTpu({"bytes_limit": 16 * 1024 ** 3})])
    assert mesh.device_memory_bytes() == 16 * 1024 ** 3
    monkeypatch.setattr(jax, "local_devices", lambda: [FakeTpu(None)])
    with pytest.raises(RuntimeError, match="reports no memory_stats"):
        mesh.device_memory_bytes()


# -- native library keyed by its source (native/__init__.py) ------------------


def test_native_library_rebuilds_when_the_source_changes(
        monkeypatch, tmp_path):
    from incubator_predictionio_tpu import native

    if not native.available():
        pytest.skip("no C++ toolchain")
    current = native._lib_path()
    assert os.path.exists(current)  # built from the tracked source
    assert native._src_digest() in os.path.basename(current)

    edited = tmp_path / "event_codec.cc"
    with open(native._src_path(), "rb") as f:
        edited.write_bytes(f.read() + b"\n// edited, same ABI number\n")
    monkeypatch.setattr(native, "_src_path", lambda: str(edited))
    assert native._lib_path() != current  # the old binary is not found ...

    builds = []

    def fake_build():
        builds.append(native._lib_path())
        raise native.NativeUnavailable("stub build")

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_error", None)
    monkeypatch.setattr(native, "_build", fake_build)
    with pytest.raises(native.NativeUnavailable, match="stub build"):
        native._load()
    assert builds == [native._lib_path()]  # ... so a build is attempted
