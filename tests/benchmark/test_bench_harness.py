"""The harness end to end on the CPU, at a size a test run can hold.

The cells come from the tiny root's own files (a configuration, a mix
and a metric that exist nowhere else), found by name.  The device check
is skipped; everything else is a whole run: set-up, window, the sampled
comparison with the plain reference.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench.runner import run_cell

REPO = Path(__file__).resolve().parents[2]
SEED = 2**31 + 5       # the driver's seeds exceed 32 signed bits
TINY_PAIRS, TINY_LONG = "tiny_pairs", "tiny_long"   # cells of `tiny_root`


def _run(root, workload, *, trace=False, control=False):
    return run_cell(root, workload, SEED, 0.5, trace, control=control,
                    require_tpu=False)


@pytest.mark.parametrize("workload", [TINY_PAIRS, TINY_LONG])
def test_sound_run_is_correct(tiny_root, no_persistent_cache, capsys,
                              workload):
    out = _run(tiny_root, workload)
    printed = capsys.readouterr()
    last = json.loads(printed.out.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(out))
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["rows_differing"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"mbp_per_s", "setup_s"}
    assert out["metrics"]["mbp_per_s"]["value"] > 0
    assert out["attempted"] > 0
    assert "check rows_differing 0 limit 0" in printed.err.splitlines()[-1]


def test_second_run_loads_the_store(tiny_root, no_persistent_cache, capsys,
                                    monkeypatch):
    _run(tiny_root, TINY_PAIRS)
    capsys.readouterr()
    import repro.core

    def refuse(*_a, **_k):
        raise AssertionError("build_seedmap called with a store present")

    monkeypatch.setattr(repro.core, "build_seedmap", refuse)
    out = _run(tiny_root, TINY_PAIRS)
    session_line = capsys.readouterr().out.split("[session] ")[1] \
        .splitlines()[0]
    assert "store_load_s" in json.loads(session_line)
    assert out["correct"] is True


def test_new_files_are_found_by_name(tiny_root, no_persistent_cache):
    """A configuration, a mix and a metric reader that only the tiny
    root holds: the traced run reports the new metric."""
    out = _run(tiny_root, TINY_PAIRS, trace=True)
    assert out["correct"] is True
    assert out["metrics"]["dp_mapped_share"]["unit"] == "%"
    assert 0 < out["metrics"]["light_mapped_share"]["value"] <= 100
    # no device plane on the CPU: nothing to read, so no idle share
    assert "device_idle_share" not in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(out["device"])


def test_no_tpu_exits_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "pe150_illumina", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
