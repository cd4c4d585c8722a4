"""The harness's look for a chip skipped, the rest of a run driven, the
timed path broken underneath: ``correct`` has to come out false, once for
each fault that a cell can have."""

import pytest

from test_rehearsal import rehearse

CASES = [
    ("higgs-quarter.fused-armed", "state_unchanged", {}, "leaf_gap"),
    ("higgs-quarter.fused-armed", "half_batch", {}, "leaf_gap"),
    ("higgs-full.dp4-armed", "no_exchange", {}, "leaf_gap"),
    ("higgs-quarter.kill-resume", "restore_altered", {"kill_after_commit": 5},
     "resume_mismatch"),
]


@pytest.mark.parametrize("cell,fault,traffic,caught_by", CASES,
                         ids=[c[1] for c in CASES])
def test_fault_is_caught(capsys, cell, fault, traffic, caught_by):
    import json
    import run

    rc = run.main(["--workload", cell, "--seed", "77", "--seconds",
                   "12" if traffic else "4", "--trace", "0"],
                  rehearsal={"rows": 6000, "traffic": traffic,
                             "plant": {"fault": fault}})
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    value, limit = line["compared"][caught_by]
    assert value > limit


def test_sound_run_of_the_same_seed_is_correct(capsys):
    line = rehearse(capsys, "higgs-quarter.fused-armed", seconds=4, seed=77)
    assert line["correct"] is True
