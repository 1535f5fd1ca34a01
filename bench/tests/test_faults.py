"""A whole run of each cell at its rehearsal size, past the harness's look
for a chip: sound, it comes out correct; with the timed path broken
underneath (``bench/faults.py``), the check refuses it."""
import pytest

from bench.registry import Benchmark
from bench.run import run_cell

SECONDS = {"paper-sort.smms-zipf": 0.5, "fk-join.statjoin-zipf1.1": 0.5}
# the number each fault must push past its limit, per operation
CAUGHT_BY = {
    "sort": {"control": "keys_wrong", "unchanged": "keys_wrong",
             "half": "keys_wrong", "no_exchange": "keys_wrong",
             "altered": "keys_wrong"},
    # int16 join keys collide past 2^16 distinct keys: the rehearsal's
    # T holds 2^17, so the control joins rows whose keys differ
    "join": {"control": "pairs_wrong", "unchanged": "pairs_missing",
             "half": "pairs_missing", "no_exchange": "pairs_missing",
             "altered": "pairs_wrong"},
}
OP = {"paper-sort.smms-zipf": "sort", "fk-join.statjoin-zipf1.1": "join"}


@pytest.fixture
def front_door(monkeypatch):
    """Restore the front door after a fault replaced it."""
    from repro import cluster
    monkeypatch.setattr(cluster, "sort", cluster.sort)
    monkeypatch.setattr(cluster, "join", cluster.join)


def failed(result):
    return {k for k, c in result["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]}


@pytest.mark.parametrize("cell", sorted(SECONDS))
def test_sound_run_is_correct(cell):
    result = run_cell(Benchmark(), cell, seed=(1 << 33) + 5,
                      seconds=SECONDS[cell], trace=False, rehearse=True)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("fault", ["control", "unchanged", "half",
                                   "no_exchange", "altered"])
@pytest.mark.parametrize("cell", sorted(SECONDS))
def test_fault_is_refused(cell, fault, front_door):
    result = run_cell(Benchmark(), cell, seed=11,
                      seconds=SECONDS[cell], trace=False, rehearse=True,
                      fault=fault)
    assert not result["correct"]
    assert CAUGHT_BY[OP[cell]][fault] in failed(result), result["checks"]
