"""Unit tests for the tuned launch environment (repro.launch.env)."""

import os

import pytest

from repro.launch import env as launch_env


def test_tuned_env_baseline_flags():
    e = launch_env.tuned_env(tcmalloc=False, base={})
    assert e["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert e["JAX_DEFAULT_DTYPE_BITS"] == "32"
    assert "LD_PRELOAD" not in e
    assert "XLA_FLAGS" not in e  # no device pin requested


def test_host_device_count_pins_xla_flag():
    e = launch_env.tuned_env(8, tcmalloc=False, base={})
    assert e["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"
    with pytest.raises(ValueError, match="host_device_count"):
        launch_env.tuned_env(0, tcmalloc=False, base={})


def test_xla_flags_merge_preserves_and_overrides():
    merged = launch_env.merge_xla_flags(
        "--xla_step_marker_location=1 --xla_force_host_platform_device_count=2",
        "--xla_force_host_platform_device_count=48",
    )
    toks = merged.split()
    assert "--xla_step_marker_location=1" in toks
    assert "--xla_force_host_platform_device_count=48" in toks
    assert "--xla_force_host_platform_device_count=2" not in toks


def test_tuned_env_merges_existing_xla_flags():
    base = {"XLA_FLAGS": "--xla_step_marker_location=1"}
    e = launch_env.tuned_env(4, tcmalloc=False, base=base)
    assert e["XLA_FLAGS"] == (
        "--xla_step_marker_location=1 --xla_force_host_platform_device_count=4"
    )


def test_tcmalloc_preload_when_present(tmp_path, monkeypatch):
    lib = tmp_path / "libtcmalloc.so.4"
    lib.write_bytes(b"")
    monkeypatch.setattr(
        launch_env, "TCMALLOC_CANDIDATES", (str(tmp_path / "missing"), str(lib))
    )
    e = launch_env.tuned_env(base={})
    assert e["LD_PRELOAD"] == str(lib)
    assert (
        e["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"]
        == launch_env.TCMALLOC_REPORT_THRESHOLD
    )


def test_tcmalloc_absent_no_preload(monkeypatch):
    monkeypatch.setattr(launch_env, "TCMALLOC_CANDIDATES", ("/nonexistent/lib.so",))
    e = launch_env.tuned_env(base={})
    assert "LD_PRELOAD" not in e
    assert "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" not in e


def test_apply_respects_user_values(monkeypatch):
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "0")
    monkeypatch.delenv("JAX_DEFAULT_DTYPE_BITS", raising=False)
    applied = launch_env.apply({"TF_CPP_MIN_LOG_LEVEL": "4", "JAX_DEFAULT_DTYPE_BITS": "32"})
    assert "TF_CPP_MIN_LOG_LEVEL" not in applied  # user export wins
    assert os.environ["TF_CPP_MIN_LOG_LEVEL"] == "0"
    assert applied["JAX_DEFAULT_DTYPE_BITS"] == "32"
    assert os.environ["JAX_DEFAULT_DTYPE_BITS"] == "32"


def test_apply_overwrite(monkeypatch):
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "0")
    applied = launch_env.apply({"TF_CPP_MIN_LOG_LEVEL": "4"}, overwrite=True)
    assert applied == {"TF_CPP_MIN_LOG_LEVEL": "4"}
    assert os.environ["TF_CPP_MIN_LOG_LEVEL"] == "4"


def test_render_exports_quoted_and_sorted():
    out = launch_env.render_exports(
        {"B_FLAG": "a b", "A_FLAG": "plain"}
    )
    assert out.splitlines() == ["export A_FLAG=plain", "export B_FLAG='a b'"]


def test_main_prints_exports(capsys, monkeypatch):
    monkeypatch.setattr(launch_env, "TCMALLOC_CANDIDATES", ("/nonexistent/lib.so",))
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    assert launch_env.main(["--devices", "16"]) == 0
    out = capsys.readouterr().out
    # shlex.quote leaves the flag bare (no shell-special characters)
    assert "export XLA_FLAGS=--xla_force_host_platform_device_count=16" in out
    assert "export TF_CPP_MIN_LOG_LEVEL=4" in out


@pytest.mark.parametrize("configured", [True, False])
def test_compile_cache_dir_is_fixed_or_configured(tmp_path, monkeypatch, configured):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves JAX's own
    reading of it alone; otherwise the cache goes to one fixed directory
    inside the checkout, the same on every call."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        if configured:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            jax.config.update("jax_compilation_cache_dir", None)
            assert launch_env.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            first = launch_env.enable_compile_cache()
            assert first == launch_env.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == first
            root = launch_env.DEFAULT_COMPILE_CACHE.parent
            assert first == str(root / ".jax_cache")
            assert (root / "pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
