"""Artifact hygiene for the driver-facing bench headline.

The driver records a bounded tail of bench.py stdout; round 4's final line
carried full per-query detail inline, outgrew that window, and the round's
headline parsed as null (BENCH_r04.json). These tests pin the new contract:
the LAST stdout line is a compact headline hard-capped at
bench.HEADLINE_MAX_BYTES, and the full object lands in a committed side
file the headline points at."""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # side files must land in the sandbox, not over the committed artifacts
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    return mod


def _last_line(capsys) -> str:
    out = capsys.readouterr().out.rstrip("\n")
    return out.splitlines()[-1]


def test_huge_detail_stays_under_cap(bench, tmp_path, capsys):
    detail = {f"lubm_q{i}": {"us": 1.5 * i, "rows": i,
                             "cap_classes": {str(j): 1 << 20 for j in range(9)},
                             "bytes_model": {"segment_bytes": 123456789,
                                             "table_bytes": 987654321,
                                             "total_bytes": 1111111110},
                             "chain": [{"step": j, "peak": j * 7}
                                       for j in range(12)]}
              for i in range(200)}
    bench._emit_final({"metric": "m" * 400, "value": 1.0, "unit": "us",
                       "vs_baseline": None, "backend": "cpu",
                       "dataset": bench.DATASET_NOTES["lubm"],
                       "detail": detail}, "SIDE.json")
    line = _last_line(capsys)
    assert len(line.encode()) <= bench.HEADLINE_MAX_BYTES
    head = json.loads(line)
    for k in ("metric", "value", "unit", "vs_baseline", "backend"):
        assert k in head
    side = json.load(open(tmp_path / "SIDE.json"))
    assert set(side["detail"]) == set(detail)  # nothing truncated in the file


def test_normal_headline_keeps_per_query_us_and_dataset(bench, tmp_path,
                                                        capsys):
    detail = {f"lubm_q{i}": {"us": float(i + 1), "rows": i} for i in range(7)}
    detail["sparql_emu"] = {"qps": 1234.5, "warm_qps": 9876.5}
    bench._emit_final({"metric": "small", "value": 2.0, "unit": "us",
                       "vs_baseline": 1.5, "backend": "tpu",
                       "dataset": bench.DATASET_NOTES["lubm"],
                       "detail": detail}, "SIDE.json")
    head = json.loads(_last_line(capsys))
    assert head["per_query_us"]["lubm_q3"] == 4.0
    assert head["emu_qps"] == 1234.5 and head["emu_warm_qps"] == 9876.5
    assert "synthetic-lubm" in head["dataset"]
    assert head["detail_file"] == "SIDE.json"
    assert len(json.dumps(head).encode()) <= bench.HEADLINE_MAX_BYTES


def test_runaway_metric_is_truncated(bench, capsys):
    bench._emit_final({"metric": "x" * 5000, "value": 1, "unit": "us",
                       "vs_baseline": None, "backend": "cpu"})
    line = _last_line(capsys)
    assert len(line.encode()) <= bench.HEADLINE_MAX_BYTES + 400
    json.loads(line)  # still one parseable JSON object


def test_side_file_failure_does_not_kill_headline(bench, monkeypatch,
                                                  capsys):
    monkeypatch.setattr(bench, "REPO", "/nonexistent/dir/zzz")
    bench._emit_final({"metric": "m", "value": 1, "unit": "us",
                       "vs_baseline": None, "backend": "cpu",
                       "detail": {"q": {"us": 1.0}}}, "SIDE.json")
    head = json.loads(_last_line(capsys))
    assert head["value"] == 1 and "detail_file" not in head


# ---------------------------------------------------------------------------
# Partial-store contracts behind the interrupted-capture path: provisional
# stubs bank per trial, OOM restarts invalidate what they disprove, and
# ladder-rung evidence surfaces without violating freshness/version rules.
# ---------------------------------------------------------------------------


@pytest.fixture()
def pstore(bench, tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "CACHE", str(tmp_path))
    monkeypatch.setattr(bench, "PARTIAL_PATH",
                        str(tmp_path / "bench_partial.json"))
    return bench


def test_oom_drop_removes_provisional_stub(pstore):
    pstore._record_partial(40, "lubm_q1", "tpu",
                           {"us": 80.2, "batch": 1024, "provisional": True})
    assert pstore._best_tpu_partial(40, "lubm_q1") is not None
    pstore._drop_partial(40, "lubm_q1", "tpu", above_batch=512)
    assert pstore._best_tpu_partial(40, "lubm_q1") is None


def test_oom_drop_keeps_smaller_batch_complete_entry(pstore):
    pstore._record_partial(40, "lubm_q2", "tpu", {"us": 99.0, "batch": 256})
    pstore._drop_partial(40, "lubm_q2", "tpu", above_batch=512)
    got = pstore._best_tpu_partial(40, "lubm_q2")
    assert got is not None and got["us"] == 99.0


def test_oom_drop_removes_larger_batch_complete_entry(pstore):
    # a complete entry at a batch the chip just refused claims a
    # configuration this process disproved
    pstore._record_partial(40, "lubm_q3", "tpu", {"us": 50.0, "batch": 1024})
    pstore._drop_partial(40, "lubm_q3", "tpu", above_batch=512)
    assert pstore._best_tpu_partial(40, "lubm_q3") is None


def test_other_scale_evidence_filters_stale_and_groups(pstore, tmp_path):
    import json as _json

    queries = [f"lubm_q{i}" for i in range(1, 8)]
    pstore._record_partial(40, "lubm_q4", "tpu", {"us": 5.0, "batch": 1024})
    pstore._record_partial(160, "lubm_q7", "tpu", {"us": 7.0, "batch": 64})
    pstore._record_partial(40, "lubm_q5", "cpu", {"us": 2.0, "batch": 1024})
    # stale entry: must never surface (freshness contract)
    store = pstore._load_partial()
    key = pstore._partial_key(160, "lubm_q6", "tpu")
    store[key] = {"us": 1.0, "batch": 8, "ts": "2020-01-01T00:00:00"}
    with open(tmp_path / "bench_partial.json", "w") as f:
        _json.dump(store, f)
    got = pstore._other_scale_tpu_evidence(
        2560, queries, pstore._load_partial())
    assert got == {"40": {"lubm_q4": 5.0}, "160": {"lubm_q7": 7.0}}
    # entries at the target scale itself are excluded (they feed the
    # headline geomean instead)
    pstore._record_partial(2560, "lubm_q1", "tpu", {"us": 9.0, "batch": 2})
    got = pstore._other_scale_tpu_evidence(
        2560, queries, pstore._load_partial())
    assert "lubm_q1" not in got.get("2560", {})
