"""The per-layer metrics that read the program's host-work spans."""
import math

import pytest

from benchtools import bench_copy, rehearse

HOST_WORK = ("engine_peek_share.sweep", "engine_dispatch_us.sweep",
             "experiment_host_share.sweep", "store_put_share.sweep",
             "engine_statics_collect_share.sweep")


@pytest.fixture()
def root(tmp_path):
    return bench_copy(tmp_path)


def test_traced_run_reads_the_host_work_spans(root, monkeypatch, capsys):
    """The engine loop's peeks, its work around the chunk loop and the
    experiment layer's host work are read from their own spans, and lie
    outside the engine's chunk calls."""
    rc, line = rehearse(root, monkeypatch, capsys, "haswell.grid", trace=1)
    assert rc == 0 and line["correct"] is True
    got = {k: v["value"] for k, v in line["rehearsal"].items()}
    for name in HOST_WORK:
        assert math.isfinite(got[name]) and got[name] > 0, name
    assert got["store_put_share.sweep"] <= got["experiment_host_share.sweep"]
    assert (got["engine_peek_share.sweep"]
            + got["experiment_host_share.sweep"]
            + got["engine_statics_collect_share.sweep"]
            <= got["host_outside_engine_share.sweep"] + 0.01)
