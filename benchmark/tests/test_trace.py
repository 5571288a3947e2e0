"""The trace reducer on a small recorded trace: ``lubm1-tiny`` on a TPU v5e,
0.3 s of a two-client window (my chip run, PR 25), kept gzipped beside this
file."""
import gzip
import os
import shutil

import pytest

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data", "tiny_tpu.xplane.pb.gz")) as src, \
            open(d / "tiny.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.reduce(xplane.find_trace(str(d.parent.parent.parent)))


def test_busy_and_window_of_the_recorded_trace(reduced):
    r = reduced
    assert list(r["per_device_busy_s"]) == ["/device:TPU:0"]
    assert 0.29 < r["window_s"] < 0.31  # the bench.window annotation
    # what the run itself printed: busy 0.037314009 of 0.30032553 s
    assert abs(r["busy_s"] - 0.037314009) < 1e-6
    assert abs(r["window_s"] - 0.30032553) < 1e-6
    lo, hi = r["window_ns"]
    assert all(lo <= a < b <= hi for a, b in r["busy_intervals_ns"])
    assert abs(xplane.busy_within(r, [(lo, hi)]) - r["busy_s"]) < 1e-9


def test_breakdown_of_the_recorded_trace(reduced):
    r = reduced
    ops, idle = r["device_ops"], r["idle_gaps"]
    assert len(ops) == 10 and len(idle) == 10
    assert ops == sorted(ops, key=lambda o: -o[1]) and ops[0][1] > 0
    assert all(len(n) <= xplane.NAME_MAX for n, _s in ops)
    assert idle == sorted(idle, key=lambda g: -g[1])
    # two clients were always in a request: every gap carries their classes
    assert all(lbl.startswith("serve:lubm_q") for lbl, _s in idle)
    assert {c for c, _a, _b in r["serves"]} <= {
        "lubm_q1", "lubm_q2", "lubm_q4", "lubm_q5", "lubm_q6", "lubm_q7"}
    busy = r["busy_s"] * 1e9
    assert sum(s for _l, s in idle) <= (r["window_s"] * 1e9 - busy) / 1e9 + 1e-9
