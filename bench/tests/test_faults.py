"""A whole run at a small size, the chip look skipped, with the timed path
broken underneath: ``correct`` has to come out false for every fault the
cell can have (``bench/faults.py``)."""
import pytest

from conftest import small_run
from faults import planted


@pytest.mark.parametrize("cell_name", ["ctr_campaign.f32",
                                       "ctr_campaign.mesh4"])
@pytest.mark.parametrize("fault", ["fl.state_unchanged", "fl.half_batch",
                                   "fl.answer_altered"])
def test_fl_faults(bench, cell_name, fault):
    with planted(fault):
        res = small_run(bench, cell_name)
    assert not res["correct"], res["compared"]


def test_fl_exchange_between_chips_left_out(bench):
    with planted("fl.exchange_left_out"):
        res = small_run(bench, "ctr_campaign.mesh4")
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("cell_name", ["granite_serve.decode",
                                       "granite_serve.open"])
@pytest.mark.parametrize("fault", ["serve.token_altered",
                                   "serve.state_unchanged",
                                   "serve.half_batch"])
def test_serving_faults(bench, cell_name, fault):
    with planted(fault):
        res = small_run(bench, cell_name)
    assert not res["correct"], res["compared"]
