"""The control and faults planted in the timed path come out not correct.

Whole harness runs on the CPU at a size a test run can hold, the device
check skipped: the configuration's control (a cheaper path of the
program that breaks a stated guarantee), and the mapper's step broken
underneath the harness so that one answer is altered where it is
produced, or half of each batch is left out.
"""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from chipbench import session
from chipbench.runner import run_cell

SEED = 2**31 + 9
TINY_PAIRS, TINY_LONG = "tiny_pairs", "tiny_long"   # cells of `tiny_root`


def _run(root, workload, *, control=False):
    return run_cell(root, workload, SEED, 0.5, False, control=control,
                    require_tpu=False)


def _plant(monkeypatch, fault):
    """Break the timed path: every step's result goes through ``fault``."""
    real = session.open_session

    def broken(cell, genome, control=False):
        mapper, info = real(cell, genome, control=control)
        attr = "_raw_step" if cell.lane == "pairs" else "_raw_long_step"
        raw = getattr(mapper, attr)
        setattr(mapper, attr, lambda *a: fault(raw(*a)))
        return mapper, info

    monkeypatch.setattr(session, "open_session", broken)


def _altered_answer(res):
    field = res._fields[0]     # pos1 / position of the first row
    return res._replace(**{field: getattr(res, field).at[0].add(1)})


def _half_left_out(res):
    B = res.n_valid.shape[0]
    keep = jnp.arange(B) < B // 2

    def cut(x):
        mask = keep.reshape((B,) + (1,) * (x.ndim - 1))
        return jnp.where(mask, x, jnp.zeros_like(x))

    return res._replace(**{f: cut(getattr(res, f)) for f in res._fields})


@pytest.mark.parametrize("workload", [TINY_PAIRS, TINY_LONG])
def test_control_is_not_correct(tiny_root, no_persistent_cache, workload):
    out = _run(tiny_root, workload, control=True)
    assert out["correct"] is False
    assert out["checks"]["rows_differing"]["value"] > 0


@pytest.mark.parametrize("fault", [_altered_answer, _half_left_out],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("workload", [TINY_PAIRS, TINY_LONG])
def test_planted_fault_is_not_correct(tiny_root, no_persistent_cache,
                                      monkeypatch, workload, fault):
    _plant(monkeypatch, fault)
    out = _run(tiny_root, workload)
    assert out["correct"] is False


