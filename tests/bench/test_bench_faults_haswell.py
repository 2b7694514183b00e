"""A run of `haswell.grid` with its timed path broken reads not correct."""
import pytest

from benchtools import FAULTS, bench_copy, rehearse


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, tmp_path, monkeypatch,
                                          capsys):
    plant, number = FAULTS[fault]
    plant(monkeypatch)
    rc, line = rehearse(bench_copy(tmp_path), monkeypatch, capsys,
                        "haswell.grid")
    assert rc == 0 and line["correct"] is False
    got = line["compared"][number]
    assert got["value"] > got["limit"], line["compared"]
