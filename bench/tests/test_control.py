"""The controls of the correctness check, at a size the CPU runs: the
reference computed in bfloat16 (below the float32 storage the
configurations state) and with int8 multiplies (below their bfloat16
multiplies) each has to read above one of its configuration's limits on
every seed, where the served program reads far below all of them.

On the chip the same comparison runs at the cells' own sizes
(``python3 bench/control.py``); ``PERF.md`` gives those readings.
"""
from __future__ import annotations

import time

import pytest

from conftest import PEAKS, smoke_config, write_root

import check
import control
import harness


@pytest.mark.parametrize("mode", ["bf16", "int8"])
@pytest.mark.parametrize("name", ["bk-sdm-small-512", "dit-s2-256"])
def test_control_is_not_correct(name, mode):
    cfg = smoke_config(name)
    rows = control.readings(cfg, seeds=[2**35 + 1, 2**35 + 2, 2**35 + 3],
                            n_requests=2, mode=mode)
    for row in rows:
        c = check.checks(cfg, row["readings"], 0)
        assert not check.passed(c), c


def test_served_program_reads_below_the_limit_at_the_same_size(tmp_path):
    cfg = smoke_config("dit-s2-256")
    spec = write_root(tmp_path, cfg, {"kind": "standing_queue",
                                      "ramp_generations": 1.0})
    res = harness.run_cell(spec, spec["workloads"][0], seed=2**35 + 1,
                           seconds=1.0, trace=False,
                           t_start=time.perf_counter(), root=str(tmp_path),
                           require_compiled=False, peaks=PEAKS)
    assert res["correct"]
    for name in cfg["check"]:
        c = res["checks"][name]
        assert c["value"] < c["limit"] / 10, (name, c)
