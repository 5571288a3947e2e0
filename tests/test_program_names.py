"""Every device program carries its ``wk_`` name into the profile.

The module XLA compiles a jitted function to is named after the function
(``jit_<__name__>``), and a profile's operation events carry that module
(on the CPU backend as their ``hlo_module`` stat; on the chip through the
device plane's ``XLA Modules`` line). A walk chain, a whole-plan template
program and the join's level probe are profiled here, on the CPU, and every
operation they run is read back under its program's name. An eager ``jnp``
operation in host code is a module of its own that JAX names after the
operation: it holds one operation, and no program of this repo is one.
"""

import glob
from collections import defaultdict

import numpy as np
import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.template_compile import _build_program, reset_demotions
from wukong_tpu.engine.tpu import TPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.planner.optimizer import make_planner
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.store.gstore import build_partition

PREFIX = """
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
"""
Q_CHAIN = PREFIX + """SELECT ?X ?Y ?Z WHERE {
    ?X ub:memberOf ?Y .
    ?Y ub:subOrganizationOf ?Z .
}"""


@pytest.fixture(scope="module")
def proxy():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    p = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    p.planner = make_planner(triples, None)
    p.tpu.stats = p.planner.stats
    return p


@pytest.fixture()
def template_device():
    saved = Global.template_device
    reset_demotions()
    yield
    Global.template_device = saved
    reset_demotions()


def profiled(tmp_path, block) -> dict[str, set]:
    """Module -> the HLO operations it ran, of every operation event the
    profile recorded around ``block()``."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        block()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    ops = defaultdict(set)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats and "hlo_module" in stats:
                    ops[str(stats["hlo_module"])].add(str(stats["hlo_op"]))
    assert ops, "the profile holds no operation event"
    return dict(ops)


def unnamed(ops: dict) -> dict:
    return {m: o for m, o in ops.items() if not m.startswith("jit_wk_")}


def test_a_walk_chain_runs_under_walk_names(proxy, template_device,
                                            tmp_path):
    Global.template_device = "host"
    proxy.serve_query(Q_CHAIN, blind=False)  # compiled, staged
    got = []
    ops = profiled(tmp_path, lambda: got.append(
        proxy.serve_query(Q_CHAIN, blind=False)))
    assert got[0].result.status_code == 0 and len(got[0].result.table)
    named = {m for m in ops if m.startswith("jit_wk_")}
    assert {"jit_wk_walk_init_from_list", "jit_wk_walk_expand"} <= named
    assert all(m.startswith("jit_wk_walk_") for m in named)
    # what is left is JAX's own: one eager operation a module
    assert all(len(o) == 1 for o in unnamed(ops).values()), unnamed(ops)


def test_a_template_program_is_named_for_its_label(proxy, template_device,
                                                   tmp_path):
    Global.template_device = "device"
    proxy.serve_query(Q_CHAIN, blind=False)  # compiled, settled, staged
    proxy.serve_query(Q_CHAIN, blind=False)
    got = []
    ops = profiled(tmp_path, lambda: got.append(
        proxy.serve_query(Q_CHAIN, blind=False)))
    label = got[0]._template_label
    progs = [p for p in proxy.template_engine()._programs.values()
             if p.label == label]
    assert progs and label.startswith("t") and len(label) == 9
    assert set(ops) == {f"jit_wk_template_{label}"}
    assert progs[0].fn.__wrapped__.__name__ == f"wk_template_{label}"


def test_a_template_program_scopes_its_steps():
    """Step ``k`` of the plan runs under the scope ``s<k>_<op>``, which the
    lowered program's locations carry (the chip's trace carries it in each
    operation's ``tf_op``)."""
    import jax
    import jax.numpy as jnp

    spec = (("index", 19, 0), ("expand", 13, 1, 0),
            ("filter_pair", 12, 1, 1, 0))

    def i(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = [i(1024), i()] + [i(64), i(65), i(256)] * 2
    fn, _forms = _build_program(spec, (1024, 2048), (3,), (1 << 7,) * 2,
                                None, label="t0123abcd")
    text = fn.lower(*args).as_text(debug_info=True)
    assert "@jit_wk_template_t0123abcd" in text
    for scope in ("s0_index", "s1_expand", "s2_filter_pair"):
        assert scope in text, scope


def test_the_level_programs_run_under_their_names(tmp_path):
    import jax
    import jax.numpy as jnp

    from wukong_tpu.join import kernels

    ranges = kernels.jit_level_ranges((4,), (0,), False)
    anchors = jnp.arange(8, dtype=jnp.int32).reshape(1, 8) % 5
    keys, offsets = jnp.arange(4, dtype=jnp.int32), \
        jnp.arange(5, dtype=jnp.int32) * 2
    edges = jnp.arange(8, dtype=jnp.int32)
    jax.block_until_ready(ranges(anchors, 0, keys, offsets))
    probe = kernels.jit_level_probe(0, (3,), False, None, 16, 8)
    window, dummy = np.array([0, 0, 8, 0], dtype=np.int32), \
        jnp.zeros(1, dtype=jnp.int32)
    got = []

    def level():  # as the executor calls them: no eager operation between
        starts, degs, choice, _mins = ranges(anchors, 0, keys, offsets)
        got.append(jax.block_until_ready(probe(
            choice, starts, degs, window, dummy, edges)))

    level()  # compiled, and the operands made
    del got[:]
    ops = profiled(tmp_path, level)
    assert set(ops) == {"jit_wk_level_ranges", "jit_wk_level_probe"}
    assert int(got[0][2]) == 14  # seven rows' runs of two; 4 is no key
