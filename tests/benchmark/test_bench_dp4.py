"""The four-chip cell's path at a tiny size on four virtual CPU devices:
the sharded step follows the plain reference, and with the exchange between
chips left out the run comes out not correct."""
import os

from benchpaths import DATA, compared as _compared

ROOT = os.path.join(DATA, "root")


def test_sharded_step_follows_the_reference(run_cell):
    line, err = run_cell(ROOT, "resnet_tiny.dp4_tiny", "--fault", "no_exchange")
    assert line["correct"] is True
    c = _compared(err)
    assert c["grad_norm_worst"][0] < 1e-3 and c["loss_step1"][0] < 1e-4
    # the same fault planted in the reference put in the program's place
    assert c["fault.grad_norm_worst"][0] > 10 * c["grad_norm_worst"][1]


def test_exchange_left_out_is_not_correct(run_cell):
    line, err = run_cell(ROOT, "resnet_no_exchange.dp4_tiny")
    assert line["correct"] is False
    c = _compared(err)
    assert c["grad_norm_worst"][0] > c["grad_norm_worst"][1]
