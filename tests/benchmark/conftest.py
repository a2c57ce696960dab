"""Fixtures for the chip benchmark's CPU tests.

``tiny_root`` is a checkout-shaped directory holding a ``BENCHMARK.json``
and small configuration, mix and metric files of its own: the harness
finds them by name exactly as it finds the chip cells, and runs them on
the CPU through the jnp oracle backends.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

TINY_PAIRS = "tiny_pairs"
TINY_LONG = "tiny_long"

#: a metric reader that exists only in the tiny root
EXTRA_METRIC = '''"""Share of the window's pairs mapped by the residual DP."""


def read(run):
    n = run.totals.get("n_pairs", 0)
    return 100.0 * run.totals["dp_mapped"] / n if n else None
'''


def _tiny_config(name: str, lane: str, batch: int) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg["genome"]["length"] = 300_000
    cfg["seedmap"]["table_bits"] = 16
    cfg["lane"], cfg["batch"] = lane, batch
    return cfg


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench_root")
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    (bench / "metrics" / "dp_mapped_share.py").write_text(EXTRA_METRIC)
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    (bench / "peaks.json").write_text(json.dumps(
        {"cpu": {"hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
                 "source": "test stand-in"}}))
    (bench / "configs" / "tiny_pe.json").write_text(json.dumps(
        _tiny_config("chr1_pe150", "pairs", 128)))
    (bench / "configs" / "tiny_hifi.json").write_text(json.dumps(
        _tiny_config("chr1_hifi", "long", 8)))
    mix = json.loads((BENCH / "traffic" / "illumina_higherr.json")
                     .read_text())
    mix["pool_batches"] = 3
    (bench / "traffic" / "tiny_higherr.json").write_text(json.dumps(mix))
    (bench / "traffic" / "tiny_hifi.json").write_text(json.dumps(
        {"lane": "long", "read_len": 2400, "sub_rate": 0.01, "edge_pad": 64,
         "pool_batches": 2}))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bench_json = {
        **real,
        "configs": [
            {"name": "tiny_pe", "source": "test", "reduced": [], "why": "t",
             "file": "benchmarks/chip/configs/tiny_pe.json"},
            {"name": "tiny_hifi", "source": "test", "reduced": [], "why": "t",
             "file": "benchmarks/chip/configs/tiny_hifi.json"}],
        "workloads": [
            {"name": TINY_PAIRS, "config": "tiny_pe",
             "traffic": "tiny_higherr", "chips": 1, "why": "t"},
            {"name": TINY_LONG, "config": "tiny_hifi",
             "traffic": "tiny_hifi", "chips": 1, "why": "t"}],
        "per_layer": [
            {"name": "light_mapped_share", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "t", "moves": "mbp_per_s",
             "workloads": [TINY_PAIRS]},
            {"name": "dp_mapped_share", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "t", "moves": "mbp_per_s",
             "workloads": [TINY_PAIRS]},
            {"name": "device_idle_share", "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "t", "moves": "mbp_per_s"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench_json))
    return root


_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def no_persistent_cache(monkeypatch, tmp_path):
    """Keep the harness's compile-cache set-up from pointing this test
    process's JAX at the checkout's cache directory, and restore the
    process-global settings it changes."""
    import jax

    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
