"""A run whose timed path is broken underneath comes out as not correct.

Each case skips the harness's look for a chip and drives the rest of a run
(``bench.run.run_cell``) at a small size on the CPU, with one of the faults
of ``bench/faults.py`` planted in the program: a step that returns its
state unchanged; half of the batch left out, the mean taken over the rest;
an answer altered where it is produced.  (These cells run on one chip, so
there is no exchange between chips to leave out; see
test_bench_four_chips.py.)
"""

from __future__ import annotations

import jax
import pytest

from bench import faults, run
from benchsmall import small_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("bench_faults"))


@pytest.mark.parametrize("cell,entry", [("fig2-sync", "sweep"), ("fig2-modes", "sweep"),
                                        ("qwen05b-train-sync", "train")])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(root, cell, entry, fault):
    with faults.FAULTS[entry][fault]():
        line = run.run_cell(cell, 2 ** 31 + 99, 0.0, False, jax.devices(), root=root)
    assert line["correct"] is False, line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("cell,small", [("fig2-sync", "fig2-sync"), ("fig2-modes", "fig2-modes"),
                                        ("fig2-sync-x4", "fig2-sync")])
def test_an_altered_answer_fails_the_committed_loss_limit(root, cell, small):
    """The altered answer (the last loss record 0.1 % off) reads about the
    same gap at any size and on any mesh, so it must exceed the loss limit
    that the cell commits at its own size, not only the small copy's."""
    from bench import registry

    committed = registry.traffic(registry.cell(cell)["traffic"])["check"]["limits"]
    with faults.FAULTS["sweep"]["answer_altered"]():
        line = run.run_cell(small, 2 ** 31 + 98, 0.0, False, jax.devices(), root=root)
    assert line["checks"]["loss_gap"]["value"] > committed["loss_gap"], line["checks"]
