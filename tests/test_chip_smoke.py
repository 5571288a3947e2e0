"""chip_smoke.py rehearsed on the CPU at LUBM-1.

The script refuses to run without a TPU; the platform check is overridden
here, in the test (the script then interprets its Pallas kernel). What this
pins: every phase line parses, the oracle comparison is live (a corrupted
expected table fails the run), and with the check in place a CPU run exits
non-zero without printing a result.
"""

import importlib.util
import json
import os

import jax
import pytest

from wukong_tpu.config import Global
from wukong_tpu.utils.paths import REPO


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke_mod(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    mod = _load()
    monkeypatch.setattr(mod, "PLATFORM", "cpu")
    monkeypatch.setattr(mod, "BATCH", 4)
    monkeypatch.setattr(Global, "enable_tracing", False)  # the run sets it
    cache_dir = jax.config.jax_compilation_cache_dir
    yield mod
    # the run turned the persistent compile cache on for this process
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    cc.reset_cache()


def _phases(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.strip().splitlines()]


def test_phases_run_and_parse(smoke_mod, capsys):
    assert smoke_mod.main(["--scale", "1", "--heavy-batches"]) == 0
    lines = _phases(capsys.readouterr().out)
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": lines[0]["count"]}}
    by_phase: dict = {}
    for ln in lines[:-1]:
        by_phase.setdefault(ln["phase"], []).append(ln)
    assert set(by_phase) == {"device", "load", "oracle", "serve", "console",
                             "batch", "kernels", "memory", "total"}
    assert by_phase["load"][0]["native_lib"] is True
    serve = {ln["query"]: ln for ln in by_phase["serve"]}
    assert sorted(serve) == list(smoke_mod.QUERIES)
    assert all(ln["passed"] and not ln["fallback_events"]
               for ln in serve.values())
    assert serve["lubm_q3"]["route"] == "planner-empty"
    assert {ln["route"] for q, ln in serve.items() if q != "lubm_q3"} \
        <= {"tpu.chain", "template.plan"}
    walked = [ln for ln in serve.values() if ln["route"] == "tpu.chain"]
    assert walked and all(ln["chain_attempts"] >= 1 for ln in walked)
    batch = {ln["query"]: ln for ln in by_phase["batch"]}
    assert batch.pop("lubm_q3")["route"] == "planner-empty"
    assert len(batch) == 6 and all(ln["all_equal"] for ln in batch.values())
    kern = by_phase["kernels"][0]
    assert kern["direct"]["equals_merge_expand"] is True
    assert kern["live"] is False and "cpu" in kern["reason"]
    assert by_phase["total"][0]["failures"] == []


def test_oracle_comparison_is_live(smoke_mod, capsys, monkeypatch):
    """One corrupted expected table -> non-zero, and no result line."""
    real = smoke_mod.oracle_rows

    def corrupt(qn, *a):
        rows = real(qn, *a)
        if qn == "lubm_q4":
            rows = rows.copy()
            rows[0, 0] += 1
        return rows

    monkeypatch.setattr(smoke_mod, "oracle_rows", corrupt)
    monkeypatch.setattr(smoke_mod, "batch_phase", lambda *a: None)
    monkeypatch.setattr(smoke_mod, "kernels_phase", lambda *a: None)
    assert smoke_mod.main(["--scale", "1"]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    q4 = [ln for ln in _phases(out)
          if ln["phase"] == "serve" and ln["query"] == "lubm_q4"]
    assert q4 and q4[0]["passed"] is False


def test_cpu_run_is_refused(capsys):
    """With the platform check in place a CPU run exits non-zero at once."""
    with pytest.raises(SystemExit) as exc:
        _load().main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""
