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
    _compile(tpu_stream.stream_expand.lower(
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
    _compile(K.merge_expand.lower(*args, cap_out=cap_out), "merge_expand",
             False)
    _compile(jax.jit(K._merge_lookup).lower(*args[:3], args[4]),
             "_merge_lookup", False)


@pytest.mark.parametrize("size", ["small", "lubm640"])
def test_hash_probe_expand_compiles(one_chip, shapes640, size):
    """The v1 hash-probe expand (__graft_entry__.entry()'s forward), with
    the fingerprint-packed probe the engine selects by default."""
    S, E, C, cap_out = SMALL if size == "small" else shapes640
    NB = S // (BUCKET // 2)  # build_hash_table: <= 50 % load
    i = partial(_i32, one_chip)
    _compile(K.expand.lower(
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
