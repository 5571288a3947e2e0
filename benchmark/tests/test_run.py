"""``run.py`` end to end at LUBM-1 on the CPU: it refuses to run without the
chip; with the look for a chip skipped, a sound run comes out correct and a
run with the timed path broken underneath, or with the control in the
program's place, does not."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "data", "BENCHMARK.test.json")
SEED = 2 ** 31 + 77


def test_no_chip_no_number():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "lubm1-tiny", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--bench-file", BENCH],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no tpu" in p.stderr
    assert "metrics" not in p.stdout and "correct" not in p.stdout


def _run(**kw):
    import jax

    from benchmark import run
    from benchmark.spec import Cell

    d = jax.devices()[0]
    dev = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices())}
    return run.run_cell(Cell(BENCH, "lubm1-tiny"), SEED, 1.5, False, dev, **kw)


def reference_with_partial_answers(world):
    """The control: the plain reference in the program's place, breaking the
    configuration's guarantee that a reply is complete — it keeps at most
    100 rows, as a row budget would, and says SUCCESS."""
    from benchmark.reference import Reference, parse_bgp
    from wukong_tpu.loader.lubm import VirtualLubmStrings

    class Names(dict):  # any vertex the text names, from the data's table
        strings = VirtualLubmStrings(1, SEED)

        def __missing__(self, iri):
            return self.strings.str2id(iri)

    ref = Reference(world.triples, world.index_rows)
    ref.ids = Names(ref.ids)

    class Result:
        status_code, complete = 0, True

    class Reply:
        pass

    def serve_query(text, blind=False):
        rows = ref.evaluate(text)[:100]
        q = Reply()
        q.result = Result()
        q.result.table = rows
        q.result.required_vars = list(range(len(parse_bgp(text)[0])))
        q.result.v2c_map = {i: i for i in q.result.required_vars}
        return q

    world.proxy.serve_query = serve_query


@pytest.mark.parametrize("plant,control,correct,failing", [
    (None, None, True, set()),
    # an id altered where the reply is produced; the reply says SUCCESS
    (None, "alter", False, {"wrong_replies"}),
    (reference_with_partial_answers, None, False, {"wrong_replies"}),
    # the program's own row budget: every reply partial, none to compare
    (None, "partial", False, {"failed_replies", "replies_compared"}),
])
def test_correct_is_decided(plant, control, correct, failing):
    from wukong_tpu.config import Global

    try:
        res = _run(break_program=plant, control=control)
    finally:
        Global.query_budget_rows = 0
    assert res["correct"] is correct, res["checks"]
    assert list(res)[-1] == "checks"
    if control is None:
        assert res["checks"]["replies_compared"]["value"] >= 9
    for name, c in res["checks"].items():
        over = c["value"] < c["limit"] if c["rule"] == ">=" \
            else c["value"] > c["limit"]
        assert over == (name in failing), (name, c)
    if correct:
        assert res["failed"] == 0 and res["attempted"] > 20
        assert set(res["metrics"]) == {"qps", "light_p50_ms", "light_p95_ms",
                                       "setup_s"}  # no device: no HBM peak
        json.dumps(res)
    else:
        assert res["failed"] > 0
