"""`correct` has to come out false where it should: with the capacity
sums held in bfloat16 (the control, `control.py`), and with the timed
path broken underneath (the store drops an allocation of every plan).
Both drive a whole rehearsal run in this process, past the look for a
chip, with the program patched from here; the sound run beside them
comes out true."""

import json

import pytest

import control
import run

ARGS = ["--seconds", "4", "--trace", "0", "--rehearse"]
STORM, STEADY = "northstar-10k.storm", "northstar-10k.steady"


def result_of(capsys, seed, mark="", cell=STORM):
    assert run.main(ARGS + ["--workload", cell, "--seed", str(seed)],
                    mark=mark) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    prefix = "REHEARSAL " + mark
    assert all(line.startswith(prefix) for line in lines)
    failed = [line for line in lines if line.endswith("FAIL")]
    return json.loads(lines[-1][len(prefix):]), failed


def test_sound_run_is_correct(capsys):
    result, failed = result_of(capsys, 101)
    assert result["correct"] is True and not failed
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("cell,seed", [
    (STORM, 102), (STORM, 2**31 + 11), (STEADY, 2**31 + 13)])
def test_bfloat16_sums_are_not_correct(capsys, cell, seed):
    with control.sums_in_bfloat16():
        result, failed = result_of(capsys, seed, mark="CONTROL bf16 ",
                                   cell=cell)
    assert result["correct"] is False
    assert any("resident_rows_differing" in line for line in failed), failed
    assert result["compared"]["resident_rows_differing"]["ok"] is False
    assert list(result["compared"])[0] == "resident_rows_differing"


@pytest.mark.parametrize("cell", [STORM, STEADY])
def test_a_dropped_allocation_is_not_correct(capsys, monkeypatch, cell):
    from nomad_tpu.state.store import StateStore

    upsert = StateStore.upsert_allocs

    def lossy(self, index, allocs):
        if len(allocs) > 1 and allocs[0].eval_id != "filler":
            allocs = allocs[:-1]
        return upsert(self, index, allocs)

    monkeypatch.setattr(StateStore, "upsert_allocs", lossy)
    result, failed = result_of(capsys, 103, cell=cell)
    assert result["correct"] is False
    assert result["failed"] > 0
    assert any("evals_not_complete_with_all_allocs" in line
               for line in failed), failed
