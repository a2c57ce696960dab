"""CPU checks of the chip entry points: the compile-cache placement and
`chip_smoke.py`'s refusal to run anywhere but on a TPU."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]
_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_config():
    """Restore the process-global cache settings the helper changes."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compile_cache_defaults_to_checkout_dir(cache_config, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_env_dir_is_not_overridden(cache_config, monkeypatch,
                                                 tmp_path):
    # JAX reads the variable itself; the helper must leave the directory
    # where the environment put it.
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_chip_smoke_refuses_without_tpu(capsys):
    smoke = _load_smoke()
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    with pytest.raises(SystemExit):
        smoke.check_device(jax.devices())


def test_chip_smoke_device_record_shape():
    smoke = _load_smoke()

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    rec = smoke.check_device([FakeTpu()])
    assert rec == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert json.loads(json.dumps({"ok": True, "device": rec}))["ok"]
    with pytest.raises(SystemExit):
        smoke.check_device([FakeTpu()], chips=4)
