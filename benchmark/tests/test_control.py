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

ARGS = ["--workload", "northstar-10k.storm", "--seconds", "4",
        "--trace", "0", "--rehearse"]


def result_of(capsys, seed, mark=""):
    assert run.main(ARGS + ["--seed", str(seed)], mark=mark) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    prefix = "REHEARSAL " + mark
    assert all(line.startswith(prefix) for line in lines)
    failed = [line for line in lines if line.endswith("FAIL")]
    return json.loads(lines[-1][len(prefix):]), failed


def test_sound_run_is_correct(capsys):
    result, failed = result_of(capsys, 101)
    assert result["correct"] is True and not failed
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("seed", [102, 2**31 + 11])
def test_bfloat16_sums_are_not_correct(capsys, seed):
    with control.sums_in_bfloat16():
        result, failed = result_of(capsys, seed, mark="CONTROL bf16 ")
    assert result["correct"] is False
    assert any("resident_rows_differing" in line for line in failed), failed


def test_a_dropped_allocation_is_not_correct(capsys, monkeypatch):
    from nomad_tpu.state.store import StateStore

    upsert = StateStore.upsert_allocs

    def lossy(self, index, allocs):
        if len(allocs) > 1 and allocs[0].eval_id != "filler":
            allocs = allocs[:-1]
        return upsert(self, index, allocs)

    monkeypatch.setattr(StateStore, "upsert_allocs", lossy)
    result, failed = result_of(capsys, 103)
    assert result["correct"] is False
    assert result["failed"] > 0
    assert any("evals_not_complete_with_all_allocs" in line
               for line in failed), failed
