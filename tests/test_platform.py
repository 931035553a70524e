"""Where the kernels run and where compiled programs are cached: the
two facts every entry point decides once."""
import jax
import pytest

from repro.kernels import default_interpret
from repro.kernels.platform import resolve_interpret
from repro.launch import compile_cache


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False)])
def test_interpret_follows_platform(platform, interpret):
    assert default_interpret(platform) is interpret


def test_interpret_refuses_other_platforms():
    with pytest.raises(RuntimeError, match="gpu"):
        default_interpret("gpu")


def test_interpret_default_is_this_backend():
    assert default_interpret() is (jax.default_backend() == "cpu")


@pytest.mark.parametrize("given", [True, False])
def test_explicit_interpret_stands(given):
    assert resolve_interpret(given) is given
    assert resolve_interpret(None) is default_interpret()


def test_cache_env_var_wins(monkeypatch, tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it: the
    helper reports it and sets nothing."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_into_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path   # fixed, stable
