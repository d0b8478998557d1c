"""The ``netflix-480189.threshold`` cell, cut to a size the CPU holds,
with the chip check skipped: sound it comes out correct; with the timed
path broken underneath, or with the bfloat16 control in the service's
place, it does not. The faults are those of ``test_chipbench_faults``."""

from __future__ import annotations

import pytest

import chipbench_testlib as lib
from test_chipbench_faults import (_altered_answer, _half_batch,
                                   _refuse_everything)

CELL = "netflix-480189.threshold"


def test_listed_cell_resolves_at_the_published_count():
    cell = lib.resolve(CELL)
    assert cell.config["name"] == "netflix-480189"
    assert cell.config["records"] == cell.config["published"]["records"]
    assert cell.config["reduced"] == []
    assert [s["op"] for s in cell.mix["streams"]] == ["query"]
    assert cell.mix["check"]["sample"] == 100000


def test_sound_run_is_correct():
    out = lib.run_small(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {"lost_answers", "hit_gap_ulps"}


@pytest.mark.parametrize("fault", [_half_batch, _altered_answer,
                                   _refuse_everything])
def test_broken_timed_path_is_not_correct(fault):
    out = lib.run_small(CELL, fault=fault)
    assert not out["correct"], out["checks"]


def test_control_fails_the_comparison():
    out = lib.run_small(CELL, control=True)
    assert out["correct"]
    for name, c in out["control"].items():
        assert c["value"] > c["limit"], (name, c)
