"""The main path's device programs, compiled for the chip without the chip.

The TPU's compiler is installed beside the CPU backend the tests run on, and
compiles for a v5e that is described and not attached: what it refuses here
(a Mosaic lowering, a VMEM or HBM overrun) it refuses on the chip. Nothing
runs, so this file says nothing about results or times — interpret-mode
parity lives in test_stream_expand.py / test_merge_path.py, the chip run in
chip_smoke.py. Shapes: the engine's smallest classes, and LUBM-640's
largest segment (takesCourse) as ``lubm_headers(640)`` sizes it.

The topology is described inside a module-scoped fixture, never at import:
only the xdist worker that runs this file may load the TPU library.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from wukong_tpu.engine import tpu_kernels as K
from wukong_tpu.engine import tpu_stream
from wukong_tpu.engine.device_store import BUCKET, _next_pow2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip (it warns and recompiles)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shapes640():
    """(S, E, C, cap_out) of a takesCourse IN step at LUBM-640: the sorted
    keys and edges padded as DeviceStore stages them, a frontier of an
    eighth of the keys (2^18 courses), 16 edges out per frontier row."""
    from wukong_tpu.loader.lubm import P, lubm_headers
    from wukong_tpu.types import IN

    nk, ne, _md = lubm_headers(640)["segs"][(P["takesCourse"], IN)]
    S, E = _next_pow2(nk), _next_pow2(ne)
    assert (S, E) == (1 << 21, 1 << 25)
    return S, E, S >> 3, S << 1


SMALL = (4096, 65536, 1024, 16384)


def _i32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def _merge_args(one_chip, S, E, C):
    i = partial(_i32, one_chip)
    live = jax.ShapeDtypeStruct((C,), jnp.bool_, sharding=one_chip)
    return i(S), i(S), i(S), i(E), i(C), i(), live


def _compile(lowered, label, pallas: bool):
    compiled = lowered.compile()
    print(f"\n{label}: {compiled.memory_analysis()}")
    has = "tpu_custom_call" in compiled.as_text()
    assert has == pallas, f"{label}: tpu_custom_call present={has}"
    return compiled


@pytest.mark.parametrize("size", ["small", "lubm640"])
def test_stream_expand_compiles(one_chip, shapes640, size):
    """The Pallas streaming emitter in the variant stream_available()
    tries first (MXU compaction + the m-hot duplicate-anchor arm)."""
    S, E, C, cap_out = SMALL if size == "small" else shapes640
    assert tpu_stream.FIRST_CHOICE == "mxu+mhot"
    _compile(tpu_stream.wk_walk_merge_stream_expand.lower(
        *_merge_args(one_chip, S, E, C), cap_out=cap_out, mxu=True,
        mhot=True, mdup=tpu_stream.MDUP), f"stream_expand[{size}]", True)


def test_mhot_emitter_compiles(one_chip):
    """The m-hot kernel on its own (inside stream_expand it is one arm of a
    device-side cond)."""
    _S, E, _C, cap_out = SMALL
    G = E // tpu_stream.TILE
    tile = _i32(one_chip, G, tpu_stream.TILE)
    fn = jax.jit(partial(tpu_stream._stream_emit_m, cap_out=cap_out,
                         mxu=True, mdup=tpu_stream.MDUP))
    _compile(fn.lower(tile, tile, tile), "stream_emit_m", True)


def test_merge_expand_compiles(one_chip):
    """The XLA sort-merge expand stream_expand is checked against, and its
    lookup half alone. Small class only: at LUBM-640's widths both are
    part of stream_expand's program above (the lookup, and the XLA emit as
    the arm for multiplicity past MDUP), and the lookup's variadic sort
    alone takes the chip's compiler ~30 s there."""
    S, E, C, cap_out = SMALL
    args = _merge_args(one_chip, S, E, C)
    _compile(K.wk_walk_merge_expand.lower(*args, cap_out=cap_out),
             "merge_expand", False)
    _compile(jax.jit(K._merge_lookup).lower(*args[:3], args[4]),
             "_merge_lookup", False)


@pytest.mark.parametrize("size", ["small", "lubm640"])
def test_hash_probe_expand_compiles(one_chip, shapes640, size):
    """The v1 hash-probe expand (__graft_entry__.entry()'s forward), with
    the fingerprint-packed probe the engine selects by default."""
    S, E, C, cap_out = SMALL if size == "small" else shapes640
    NB = S // (BUCKET // 2)  # build_hash_table: <= 50 % load
    i = partial(_i32, one_chip)
    _compile(K.wk_walk_expand.lower(
        i(1, C), i(), i(NB * BUCKET), i(NB * BUCKET), i(NB * BUCKET), i(E),
        col=0, cap_out=cap_out, max_probe=2, fpw0=i(NB), fpw1=i(NB),
        fp_dup=2), f"expand[{size}]", False)


def test_template_program_compiles(one_chip):
    """One whole-plan template program: the unanchored two-hop chain
    ``?x advisor ?y . ?y worksFor ?z`` (index start, two expands) at
    LUBM-640's segment sizes, tables unpadded as JoinTableCache stages
    them."""
    from wukong_tpu.engine.template_compile import _build_program
    from wukong_tpu.loader.lubm import P, lubm_headers
    from wukong_tpu.types import OUT

    segs = lubm_headers(640)["segs"]
    i = partial(_i32, one_chip)
    args = []
    for name in ("advisor", "worksFor"):
        nk, ne, _md = segs[(P[name], OUT)]
        args += [i(nk), i(nk + 1), i(ne)]
    n_start = segs[(P["advisor"], OUT)][0]
    caps = (_next_pow2(n_start),) * 3  # both hops have out-degree 1
    spec = (("index", P["advisor"], OUT), ("expand", P["advisor"], OUT, 0),
            ("expand", P["worksFor"], OUT, 1))
    # vertex ids are dense from 2^17 up: the student segment ends near
    # the graph's last id, which 2^25 bounds at LUBM-640
    fn, _forms = _build_program(spec, caps, (), (1 << 25,) * 2, (0, 1, 2))
    _compile(fn.lower(i(caps[0]), i(), *args), "template two-hop", False)


# C3's last expansion at WatDiv scale factor 1000 as shapes: a frontier of
# 262,144 rows and six columns, friendOf's 400,120 subjects and 44,750,179
# edges, 14.1 M vertex ids (PERF.md section 5)
C3_ROWS_IN, C3_COLS_IN = 1 << 18, 6
FRIEND_KEYS, FRIEND_EDGES, ID_BOUND = 400_120, 44_750_179, 14_100_000


def _c3_last_expansion(one_chip, cap_out: int):
    """The tail of C3's program, as ``_build_program`` traces it: the key
    lookup, the expansion to ``cap_out`` rows, every column carried through
    it and the reply's table stacked."""
    from wukong_tpu.join.kernels import (
        direct_lookup_wins,
        expand_padded_device,
        lookup_ranges_device,
    )

    assert direct_lookup_wins(C3_ROWS_IN, FRIEND_KEYS, ID_BOUND)

    def tail(cols, valid, keys, offsets, edges):
        start, deg = lookup_ranges_device(keys, offsets, cols[0], ID_BOUND)
        deg = jnp.where(valid, deg, 0)
        rowc, newv, valid, total, ovf = expand_padded_device(
            start, deg, edges, cap_out)
        table = jnp.stack([c[rowc] for c in cols] + [newv], axis=1)
        return table, valid, total, ovf

    i = partial(_i32, one_chip)
    live = jax.ShapeDtypeStruct((C3_ROWS_IN,), jnp.bool_, sharding=one_chip)
    return _compile(jax.jit(tail).lower(
        [i(C3_ROWS_IN)] * C3_COLS_IN, live, i(FRIEND_KEYS),
        i(FRIEND_KEYS + 1), i(FRIEND_EDGES)),
        f"C3 last expansion[{cap_out:,}]", False)


def test_c3_last_expansion_compiles_smaller_at_its_finer_class(one_chip):
    """At 9 x 2^20 rows (the class of twice the planner's 4,227,401) and
    at 2^24 (the power of two above it): both compile, and the finer
    class's temporaries and reply are smaller as the classes are."""
    from wukong_tpu.join.kernels import capacity_class, pad_pow2

    fine, coarse = capacity_class(2 * 4_227_401), pad_pow2(2 * 4_227_401)
    assert (fine, coarse) == (9 << 20, 1 << 24)
    mem = {cap: _c3_last_expansion(one_chip, cap).memory_analysis()
           for cap in (fine, coarse)}
    for what in ("output_size_in_bytes", "temp_size_in_bytes"):
        small, large = getattr(mem[fine], what), getattr(mem[coarse], what)
        assert 0 < small < 0.6 * large, what  # the classes: 0.5625


# a level of the join's device route at LSQB's sizes (PERF.md section 4 has
# the counts): (prefix rows, adjacencies as (keys, edges, id bound, search
# depth), the prefix column each anchors on, the generator, the list as
# (length, the store's vertex bound) or None, the slots of one call)
KNOWS3 = (27_000, 1_090_334, 158_072, 11)
KNOWS10 = (73_000, 3_906_840, 204_100, 12)
CREATOR3 = (10_841_688, 10_841_688, 11_245_376, 2)
CREATOR10 = (29_300_000, 29_300_000, 30_200_000, 2)
LEVELS = {
    # q3's widest level at scale factor 3, one tensor of 2^24 slots
    "q3_widest_sf3": (459_816, (KNOWS3, KNOWS3), (0, 1), 0, None, 1 << 24),
    # one slice of q3's widest level at scale factor 10
    "q3_slice_sf10": (1_714_554, (KNOWS10, KNOWS10), (0, 1), 0, None,
                      1 << 22),
    # q2's last level at scale factor 3: the comment's author, known by the
    # post's, in the persons' list
    "q2_last_level_sf3": (4_557_932, (CREATOR3, KNOWS3), (0, 1), 0,
                          (27_000, 11_245_376), 1 << 23),
    # q2's third level: a post's replies in the comments' list
    "q2_comments_sf3": (1_410_892, ((2_737_800, 4_557_932, 11_245_376, 9),),
                        (0,), 0, (8_103_888, 11_245_376), 1 << 23),
    # one slice of q2's last level at scale factor 10
    "q2_slice_sf10": (12_300_000, (CREATOR10, KNOWS10), (0, 1), 0,
                      (73_000, 30_200_000), 1 << 22),
}


@pytest.mark.parametrize("case", sorted(LEVELS))
def test_level_programs_compile_at_lsqb_sizes(one_chip, case):
    """The two programs of a level made on the chip: ``wk_level_ranges``
    over every prefix row (each adjacency's keys a table over the id
    range, by ``direct_lookup_wins`` at these shapes: no ``while``) and
    ``wk_level_probe`` for one call (the rows spread over the slots, the
    edge-run search of the other adjacency in one loop, the list's
    membership as a table, the compaction by a scatter of a permutation a
    column). A probe compiles to 15.7-19.9 MB of program text at these
    shapes and a ranges program to 2.4-3.6, which stays resident while it
    is cached; the probe's temporaries stay under 1 GiB (874 MB at q3's
    2^24 slots and at a slice of q2 over 2^24 rows: the spread rows, the
    searches' cursors, the compaction's permutation), the ranges' under
    0.5 GiB (403 MB)."""
    from wukong_tpu.join import kernels

    i = partial(_i32, one_chip)
    n, adj, anchor_of, gen, lst, slots = LEVELS[case]
    rows = kernels.pad_pow2(n)
    for nkeys, _ne, bound, _d in adj:
        assert kernels.direct_lookup_wins(rows, nkeys, bound)
    ranges = kernels.jit_level_ranges(tuple(a[2] for a in adj), anchor_of,
                                      lst is not None)
    tables = [x for nkeys, _ne, _b, _d in adj for x in (i(nkeys),
                                                        i(nkeys + 1))]
    looked = _compile(ranges.lower(i(max(anchor_of) + 1, rows), i(), *tables),
                      f"wk_level_ranges[{case}]", False)
    if lst is not None:
        assert kernels.direct_lookup_wins(slots, *lst)
    probe = kernels.jit_level_probe(gen, tuple(a[3] for a in adj),
                                    lst is not None,
                                    None if lst is None else lst[1], slots,
                                    rows)
    choice = jax.ShapeDtypeStruct((rows,), jnp.int8, sharding=one_chip)
    probed = _compile(probe.lower(
        choice, i(len(adj), rows), i(len(adj), rows), i(4),
        i(1 if lst is None else lst[0]), *[i(a[1]) for a in adj]),
        f"wk_level_probe[{case}]", False)
    assert "while" not in looked.as_text()
    assert probed.as_text().count(" while(") == (len(adj) > 1)
    for c, limit in ((looked, 1 << 29), (probed, 1 << 30)):
        mem = c.memory_analysis()
        assert mem.temp_size_in_bytes < limit
        assert mem.generated_code_size_in_bytes < 22 << 20


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """A mesh over the four described chips of a v5e:2x2, as the sharded
    engine lays its partitions (one axis, ``x``)."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices), ("x",))


def test_sharded_chain_compiles_for_four_chips(four_chips):
    """LUBM q1's sharded chain (``wk_dist_chain``) over four chips at the
    per-shard sizes of LUBM-2560 (``lubm_headers(2560)`` over four shards,
    the classes its warm-up learns): an index start, the expansion to the
    students, three row exchanges over ICI and three membership probes by
    fingerprint, each shard's keys homed by their bits above the shard's,
    one program. The compiler keeps its temporaries under 4 GiB
    a chip and puts the all-to-all in."""
    import types

    from jax.sharding import NamedSharding, PartitionSpec as P

    from wukong_tpu.loader.lubm import P as PRED, lubm_headers
    from wukong_tpu.parallel.dist_engine import DistEngine, _Plan, _Step
    from wukong_tpu.types import IN, OUT, TYPE_ID

    D = 4
    segs = lubm_headers(2560)["segs"]

    def table(pid, d):  # a shard's share, staged as sharded_store stages it
        nk, ne, _md = segs[(pid, d)]
        nb = max(_next_pow2((-(-nk // D) + 3) // 4), 2)
        return [(D, nb * BUCKET)] * 3 + [(D, _next_pow2(-(-ne // D)))] \
            + [(D, nb)] * 2  # the fingerprint words

    steps = [_Step("init_index", pid=17, dir=IN, cap=1 << 16),
             _Step("expand", pid=PRED["undergraduateDegreeFrom"], dir=IN,
                   col=0, cap=1 << 21, new_col=True),
             _Step("expand", pid=PRED["memberOf"], dir=OUT, col=1,
                   cap=1 << 21, exch_cap=1 << 20, new_col=True),
             _Step("member", pid=PRED["subOrganizationOf"], dir=OUT, col=2,
                   vals_col=0, cap=1 << 21, exch_cap=1 << 19),
             _Step("member", pid=TYPE_ID, dir=OUT, col=1, const=24,
                   cap=1 << 16, exch_cap=1 << 16),
             _Step("member", pid=TYPE_ID, dir=OUT, col=2, const=18,
                   cap=1 << 16, exch_cap=1 << 16)]
    shapes = [[(D, 1024), (D, 1)]] + [table(s.pid, s.dir) for s in steps[1:]]
    bounds = types.SimpleNamespace(max_probe=1, max_deg_log2=12, fpw0=True,
                                   max_fp_dup=2, key_shift=2)
    eng = types.SimpleNamespace(
        D=D, axis="x", mesh=four_chips,
        sstore=types.SimpleNamespace(segment=lambda pid, d: bounds))
    sharded = NamedSharding(four_chips, P("x", None))
    args = [tuple(jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharded)
                  for s in group) for group in shapes]
    fn = DistEngine._compile(eng, _Plan(steps=steps), args)
    compiled = fn.lower(*DistEngine._flatten_args(args)).compile()
    mem = compiled.memory_analysis()
    print(f"\nsharded q1 chain: {mem}")
    assert mem.temp_size_in_bytes < 4 << 30
    assert "all-to-all" in compiled.as_text()
