"""Every cell's set-up, window and check at a size the CPU can run: the
benchmark's own run() drives the program's engine through chunked
prefill and paged decode (the Pallas kernels in interpret mode, run-ahead
included), and the served tokens agree with the reference. No chip: the
harness's look for one is skipped, and the device numbers it prints are
not measurements."""
import argparse
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import bench_tiny  # noqa: E402

sys.path.insert(0, bench_tiny.ROOT)
from bench import manifest, run  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", [w["name"] for w in manifest.load(
    bench_tiny.ROOT)["workloads"]] + [bench_tiny.OPEN_CELL])
def test_cell_runs_and_is_correct(root, cell):
    args = argparse.Namespace(workload=cell, seed=1, seconds=3.0, trace=0)
    res = run.run(args, root=root, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert res["window"] == {"preempts": 0}
    assert res["failed"] == 0 and res["attempted"] > 0
    man = manifest.load(root)
    assert set(res["metrics"]) == {m["name"] for m in
                                   manifest.end_to_end(man, cell)}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
