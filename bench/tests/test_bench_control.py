"""The comparison that decides `correct` fails its control and each
fault the cells can have.

The control is the plain reference put in the program's place and
computed one precision below the configuration's (bfloat16 for
float32). The faults are planted in the program under a run that skips
only the look for a chip: a relax step that returns its state
unchanged, half of a batch left out, and an answer altered where it is
produced. (No cell spans chips, so there is no exchange to leave out.)
"""
import numpy as np
import pytest

from bench import run
from bench.tests.conftest import cells

CELLS = cells()


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 9, 77])
def test_control_is_not_correct(tiny_root, workload, seed):
    r = run.run_cell(workload, seed, 0.2, False, root=tiny_root,
                     require_tpu=False, control=True)
    assert not r["correct"]
    assert any(v["value"] > v["limit"] for v in r["check"].values())


def unchanged_step(monkeypatch):
    import repro.core.engine as engine
    monkeypatch.setattr(engine, "frontier_relax",
                        lambda src_vals, carry, bg, **kw: carry)


def half_batch(monkeypatch):
    """Only the first half of a batch is computed; the rest repeats it."""
    from repro.core.engine import FlipEngine
    real = FlipEngine._execute_local

    def execute(self, srcs, *a, **kw):
        half = max(len(srcs) // 2, 1)
        out, steps, tele, conv, exp = real(self, srcs[:half], *a, **kw)
        take = np.arange(len(srcs)) % half
        return out[take], steps[take], tele, conv[take], exp[take]
    monkeypatch.setattr(FlipEngine, "_execute_local", execute)


def altered_answer(monkeypatch):
    """One reached vertex's answer is off by one in every row."""
    from repro.kernels.frontier.ops import BlockedGraph
    real = BlockedGraph.to_orig

    def to_orig(self, attrs, *a, **kw):
        out = np.array(real(self, attrs, *a, **kw))
        for row in out.reshape(-1, out.shape[-1]):
            row[np.flatnonzero(np.isfinite(row))[0]] += 1.0
        return out
    monkeypatch.setattr(BlockedGraph, "to_orig", to_orig)


FAULTS = {"unchanged_step": unchanged_step, "half_batch": half_batch,
          "altered_answer": altered_answer}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS for f in FAULTS
    if f != "half_batch" or w == "kron-sssp-b8"])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, workload,
                                      fault):
    FAULTS[fault](monkeypatch)
    r = run.run_cell(workload, 3, 0.2, False, root=tiny_root,
                     require_tpu=False)
    assert not r["correct"]
    assert r["failed"] > 0
