"""The check refuses the control: each cell's program one precision below
what its configuration states (float32 matrix products at ``HIGH``
instead of ``HIGHEST`` for dense K; the ELL gather-multiply on bfloat16
operands for K as nonzeros), at the cells' own sizes on the CPU; the
same cells as they are pass.  On the chip the same control runs through
``bench/control.py``."""
import _benchpath  # noqa: F401
import _tinycell
import pytest

from bench import control

CONTROL = {"table1-dense": "high", "table1-ell": "bfloat16"}


@pytest.mark.parametrize("cell", sorted(CONTROL))
def test_control_is_refused_and_program_passes(cell):
    c = _tinycell.tiny_cell(cell)
    ok, numbers, _ = _tinycell.run(c)
    assert ok, numbers
    assert numbers["dots_below_highest"] == 0
    assert control.control_of(c) == CONTROL[cell]
    with control.lower_precision(control.control_of(c)):
        ok, numbers, _ = _tinycell.run(c)
    assert not ok, numbers


def test_high_passes_every_numeric_check_and_fails_the_structural_one():
    c = _tinycell.tiny_cell("table1-dense")
    with control.lower_precision("high"):
        ok, numbers, _ = _tinycell.run(c)
    assert not ok
    assert numbers["dots_below_highest"] > 0
    assert all(v <= c.config["limits"][k] for k, v in numbers.items()
               if k != "dots_below_highest"), numbers
