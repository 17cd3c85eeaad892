"""Entry-point contracts: where the compile cache goes, and the exit code
of ``repro.launch.serve`` (every request served, or a nonzero exit)."""
import jax

from repro.launch import cache, serve


def _record_config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_defaults_to_checkout(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR unset, the cache sits at the fixed
    <checkout>/.jax_cache (never a temp name, a pid or a time)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_config_updates(monkeypatch)
    path = cache.configure_compile_cache()
    assert path == str(cache.CHECKOUT / ".jax_cache")
    assert (cache.CHECKOUT / "src" / "repro" / "launch" / "cache.py").exists()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert cache.configure_compile_cache() == path      # stable across calls


def test_compile_cache_env_wins_and_sets_nothing(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the helper
    reports it and sets no cache directory in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_config_updates(monkeypatch)
    assert cache.configure_compile_cache() == str(tmp_path)
    assert calls == []


def test_serve_smoke_exits_zero_when_every_request_is_served(monkeypatch):
    monkeypatch.delenv("REPRO_FAULTS", raising=False)
    _record_config_updates(monkeypatch)
    assert serve.main(["--smoke"]) == 0


def test_serve_exits_nonzero_on_unserved_requests_without_faults(
        monkeypatch, capsys):
    """No fault plan armed: an outcome other than a result (here, server
    deadlines that expire before the drain) fails the run even though
    every request is accounted for."""
    monkeypatch.delenv("REPRO_FAULTS", raising=False)
    _record_config_updates(monkeypatch)
    assert serve.main(["--smoke", "--deadline-ms", "0.001"]) == 1
    err = capsys.readouterr().err
    assert "not served without a fault plan" in err
    assert "DeadlineExceeded" in err
