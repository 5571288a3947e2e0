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


@pytest.mark.parametrize("case", ["knows", "hasCreator"])
def test_level_probe_compiles_at_lsqb_sizes(one_chip, case):
    """One slice of the join's level probe (``kernels.LEVEL_SLICE`` = 2^22
    candidates) over LSQB's tables at scale factor 10: q3's widest level
    probes ``knows`` (73,000 keys, 3.9 M edges, a table over 204,000 ids for
    the anchors) and ``isPartOf``; q2's probes ``hasCreator`` keyed by 29.3 M
    messages (a table over 30.2 M ids) beside the list of 21.9 M comments.
    Each is a few seconds of compiling and under 0.5 GiB of temporaries,
    where the level as one ``pad_pow2`` tensor was 2^27 slots."""
    from wukong_tpu.join import kernels

    i = partial(_i32, one_chip)
    S = kernels.LEVEL_SLICE
    valid = jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip)
    if case == "knows":
        fn = kernels.jit_level_probe((11, 8), False, (204_100, 205_500))
        args = [i(1), i(73_000), i(73_001), i(3_950_000), i(S),
                i(1_343), i(1_344), i(1_343), i(S)]
    else:
        fn = kernels.jit_level_probe((2,), True, (30_200_000,))
        args = [i(21_900_000), i(29_300_000), i(29_300_001), i(29_300_000),
                i(S)]
    compiled = _compile(fn.lower(valid, i(S), *args), f"wk_level_probe[{case}]",
                        False)
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


@pytest.mark.parametrize("case", ["q2_last_level", "q2_comments"])
def test_level_probe_of_one_run_addresses_tables_at_lsqb_sizes(one_chip, case):
    """A level of one run at LSQB's scale factor 3 (2^23 slots): q2's last
    level looks the post's author up among ``knows``' 27,000 keys and the
    candidate in the persons' list of 27,000; its third level looks the
    candidate up in the comments' list of 8,103,888. Since PR 35 each is a
    table over the id range (``direct_lookup_wins`` at these shapes), so the
    program holds no ``while`` (``searchsorted``'s loop: 15 and 23 rounds of
    a gather a slot), and its temporaries stay under 0.3 GiB."""
    from wukong_tpu.join import kernels

    i = partial(_i32, one_chip)
    S, vbound = 1 << 23, 11_245_376
    valid = jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=one_chip)
    if case == "q2_last_level":
        assert kernels.direct_lookup_wins(S, 27_000, 158_072)
        assert kernels.direct_lookup_wins(S, 27_000, vbound)
        fn = kernels.jit_level_probe((11,), True, (158_072,), vbound)
        args = [i(27_000), i(27_000), i(27_001), i(1_090_334), i(S)]
    else:
        assert kernels.direct_lookup_wins(S, 8_103_888, vbound)
        fn = kernels.jit_level_probe((), True, (), vbound)
        args = [i(8_103_888)]
    compiled = _compile(fn.lower(valid, i(S), *args),
                        f"wk_level_probe[{case}]", False)
    assert "while" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 300 << 20
