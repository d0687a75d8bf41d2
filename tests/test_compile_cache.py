"""The compile cache is placed from outside the program
(``flink_ml_tpu/utils/compile_cache.py``): the deployment's
``JAX_COMPILATION_CACHE_DIR`` untouched, else one fixed directory in the
checkout — never a temporary path, which would never hit."""
import os
import tempfile
import types

import flink_ml_tpu
import flink_ml_tpu.utils.compile_cache as cc

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(flink_ml_tpu.__file__)))


def _recording_jax(monkeypatch):
    """Swap the module's ``jax`` for a recorder: the test must not move the
    real process-wide cache under the rest of the suite."""
    updates = []
    fake = types.SimpleNamespace(
        config=types.SimpleNamespace(update=lambda k, v: updates.append((k, v)))
    )
    monkeypatch.setattr(cc, "jax", fake)
    return updates


def test_env_set_is_left_alone(monkeypatch):
    updates = _recording_jax(monkeypatch)
    monkeypatch.setenv(cc.CACHE_DIR_ENV, "/some/dir")
    assert cc.configure_compile_cache() == "/some/dir"
    assert updates == []  # nothing set in code: JAX reads the variable itself


def test_env_unset_uses_the_fixed_in_checkout_path(monkeypatch):
    updates = _recording_jax(monkeypatch)
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    first, second = cc.configure_compile_cache(), cc.configure_compile_cache()
    assert first == second == os.path.join(REPO_ROOT, ".jax_cache")
    assert ("jax_compilation_cache_dir", first) in updates
    # fixed, and never under the temp root — a path that moves never hits
    assert not first.startswith(tempfile.gettempdir() + os.sep)


def test_no_library_code_sets_the_cache_elsewhere():
    """``configure_compile_cache`` is the ONE place the directory is set."""
    offenders = []
    pkg = os.path.dirname(os.path.abspath(flink_ml_tpu.__file__))
    for root, _dirs, files in os.walk(pkg):
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and path != os.path.abspath(cc.__file__):
                with open(path) as f:
                    if "jax_compilation_cache_dir" in f.read():
                        offenders.append(os.path.relpath(path, pkg))
    assert offenders == []
