"""The control, at a size a test holds: the reference one precision step
below the configuration, in the program's place, has to fail the limits.
(On the chip, ``bench/calibrate.py --control 1`` reads it at the cells'
own sizes.)"""
import jax
import pytest

import calibrate
import run
from conftest import SEED, SMALL


@pytest.mark.parametrize("cell_name", ["ctr_campaign.f32",
                                       "granite_serve.decode"])
def test_control_is_not_correct(bench, cell_name):
    cell = run.Cell(bench, cell_name)
    out = calibrate.readings(cell, seed=SEED, seconds=2.0, control=True,
                             devices=jax.devices()[:1],
                             overrides=SMALL[cell.config["system"]])
    small = SMALL[cell.config["system"]].get("config", {})
    limits = small.get("limits", cell.config["limits"])
    assert all(out["program"][k] <= limits[k] for k in out["program"])
    assert any(out["control"][k] > limits[k] for k in limits), out
