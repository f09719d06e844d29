"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import cache


@pytest.fixture
def updates(monkeypatch):
    """Record config updates instead of turning the cache on."""
    seen = []
    monkeypatch.setattr(cache.jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def _checkout(root):
    (root / "src" / "repro").mkdir(parents=True)
    (root / "pyproject.toml").write_text("")
    return root


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_dir_from_env_or_checkout(tmp_path, monkeypatch, updates,
                                        env_set):
    root = _checkout(tmp_path / "repo")
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert cache.use_compile_cache(root) == str(tmp_path / "env")
        assert updates == []              # JAX reads the variable itself
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(root / cache.CACHE_DIRNAME)
        assert cache.use_compile_cache(root) == want
        assert updates == [("jax_compilation_cache_dir", want)]


def test_no_cache_outside_a_checkout(tmp_path, monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    (tmp_path / "site-packages").mkdir()
    assert cache.use_compile_cache(tmp_path / "site-packages") is None
    assert updates == []
    assert jax.config.jax_compilation_cache_dir != str(
        tmp_path / "site-packages" / cache.CACHE_DIRNAME)
