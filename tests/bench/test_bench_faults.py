"""A whole run of each cell with the timed path broken underneath comes
out not correct: once for each fault the cells can have."""
from __future__ import annotations

import pytest

from conftest import CELLS, run_tiny


def _unchanged_state():
    from repro.core import efhc

    step = efhc.step

    def broken(cfg, graph, state, **kw):
        _, aux = step(cfg, graph, state, **kw)
        return state, aux

    return [(efhc, "step", broken)]


def _half_batch():
    from repro.core import efhc

    step = efhc.step

    def broken(cfg, graph, state, *, batch, **kw):
        b = batch[0].shape[1] // 2
        return step(cfg, graph, state,
                    batch=(batch[0][:, :b], batch[1][:, :b]), **kw)

    return [(efhc, "step", broken)]


def _no_exchange():
    """The devices' exchange left out: Event 3 returns every model as it
    was."""
    from repro.core import consensus

    return [(consensus, "mix_sparse", lambda idx, p_diag, p_off, w: w),
            (consensus, "mix_dense", lambda p, w: w)]


def _altered_answer():
    """One device's broadcast decision reported flipped where the step
    produces it."""
    from repro.core import efhc

    step = efhc.step

    def broken(cfg, graph, state, **kw):
        st, aux = step(cfg, graph, state, **kw)
        return st, aux._replace(v=aux.v.at[0].set(~aux.v[0]))

    return [(efhc, "step", broken)]


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "no_exchange": _no_exchange, "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(tiny, workload, fault, monkeypatch):
    from repro.fl import simulator

    for module, attr, broken in FAULTS[fault]():
        monkeypatch.setattr(module, attr, broken)
    simulator._ENGINE_CACHE.clear()
    try:
        res = run_tiny(tiny, workload)
    finally:
        simulator._ENGINE_CACHE.clear()
    assert not res["correct"], res["checks"]
