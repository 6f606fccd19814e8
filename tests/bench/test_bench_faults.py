"""With the timed path broken underneath, the harness's run reports
``correct`` false: a PDHG step that returns its state unchanged, half of
a round's answers left out, an answer altered where it is produced, and
an entry whose compiled programs cannot be shown.  (The cells run on one
chip, so there is no exchange between chips to leave out.)"""
import _benchpath  # noqa: F401
import _tinycell
import numpy as np
import pytest

from bench import spec


def _entry(cell):
    return spec.load_file(spec.entry_path(cell.entry)).make(cell.config)


def test_step_that_returns_its_state_unchanged():
    from repro.core import engine

    def frozen(op, upd, b, c, lb, ub, T, Sigma, gamma, state, k1, k2):
        return state

    cell = _tinycell.tiny_cell("table1-dense", max_iters=300)
    with _tinycell.patched(engine, "pdhg_step", frozen):
        ok, numbers, _ = _tinycell.run(cell)
    assert not ok
    assert numbers["not_optimal"] >= 1


class _Wrapped:
    def __init__(self, inner):
        self.inner = inner

    def prepare(self, call):
        return self.inner.prepare(call)

    def programs(self):
        return self.inner.programs()


class _HalfRound(_Wrapped):
    def solve(self, payload):
        answers = self.inner.solve(payload)
        return answers[: len(answers) // 2]


class _Altered(_Wrapped):
    def solve(self, payload):
        answers = self.inner.solve(payload)
        a = dict(answers[0], x=np.asarray(answers[0]["x"]) * 1.01)
        return [a] + answers[1:]


class _Unshown(_Wrapped):
    def __getattribute__(self, name):
        if name == "programs":
            raise AttributeError(name)
        return super().__getattribute__(name)

    def solve(self, payload):
        return self.inner.solve(payload)


def test_half_of_a_round_left_out():
    cell = _tinycell.tiny_cell("table1-dense")
    ok, numbers, run = _tinycell.run(cell, entry=_HalfRound(_entry(cell)))
    assert not ok
    assert numbers["missing"] == 4 * len(run.calls)


@pytest.mark.parametrize("name", ["table1-dense", "table1-ell"])
def test_answer_altered_where_it_is_produced(name):
    cell = _tinycell.tiny_cell(name)
    ok, numbers, _ = _tinycell.run(cell, entry=_Altered(_entry(cell)))
    assert not ok, (name, numbers)


def test_programs_that_cannot_be_shown_fail_the_precision_check():
    cell = _tinycell.tiny_cell("table1-dense")
    ok, numbers, _ = _tinycell.run(cell, entry=_Unshown(_entry(cell)))
    assert not ok
    assert numbers["dots_below_highest"] == float("inf")
