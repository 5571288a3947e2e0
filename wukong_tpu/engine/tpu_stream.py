"""Pallas streaming merge-expand: the bandwidth-bound heavy-query emitter.

Role: the emit half of known_to_unknown expansion (the reference computes it
with per-row pointer chasing + prefix sums on CUDA — gpu_hash.cu:262-477 +
gpu_engine_cuda.hpp:112-197). The XLA merge path (tpu_kernels.merge_expand)
pays, per OUTPUT element, one scatter (~13 ns), one cummax (~2.5 ns) and one
random gather (~9.5 ns) on the [cap_out] grid — ~25 ns/elem (an earlier
installation's figures; not measured on the attached chip). This kernel
streams the segment's EDGE array through VMEM instead and re-derives
everything from prefix sums of sparse per-edge deltas:

  - the XLA side scatters O(R) run boundaries (R = matched frontier rows)
    into two [E] delta arrays: dsel (+1 at run start, -1 at run end) and
    dpar (parent id deltas at run starts);
  - the kernel streams (edges, dsel, dpar) tiles, integrates the deltas
    (prefix sums with inter-tile carries in SMEM), compacts selected edges
    with a one-hot plane (no per-lane gather — the Mosaic constraint that
    killed the round-1 probe kernel), and DMAs full, ALIGNED output blocks
    from a VMEM accumulator (aligned blocks are disjoint, so the chained
    dynamic-offset DMAs can stay async without write races).

Per streamed edge that's ~12 B of HBM reads + ~8 B of writes per emitted
row and a few VPU ops — ~3 ns/edge, vs ~25 ns/output for the XLA path, a
win whenever the expansion is dense in the segment (heavy index-origin
chains are exactly that; the host gates on estimated density).

Duplicate anchors (two frontier rows with one key) make runs overlap, which
plain 0/1 delta-integration cannot represent. The m-hot arm handles
multiplicity up to MDUP: dsel's `.add` boundaries already accumulate a
per-edge multiplicity m(e), the selection plane becomes an interval test
(each edge owns m(e) consecutive output rows — edge-repeat order, a
permutation of the XLA emit's run-repeat order), and parents are emitted as
rank positions (dupstart + copy index, integrated from a third delta
channel) that one XLA gather resolves afterwards. Beyond MDUP a device-side
`lax.cond` falls back to the XLA emit — no mid-chain host sync, all emits
are branch arms of one compiled program.

All intra-kernel prefix sums are triangular-ONES matmuls (MXU) rather than
`cumsum`, because matmul is the one primitive guaranteed to lower in
Mosaic; 32-bit payloads split into 16-bit halves so fp32 accumulation stays
exact (recombined mod 2^32, which prefix-sum deltas make exact again).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from wukong_tpu.engine.tpu_kernels import (
    INT32_MAX,
    _merge_lookup,
    _saturate_total,
)

TILE = 256  # edges per grid step (TILE//128 sublane rows per cumsum)

# test hook: run the kernel in interpreter mode on CPU (lets the executor
# integration be exercised without TPU hardware)
FORCE_INTERPRET = False

# compaction backend: the one-hot plane either feeds two VPU masked
# reductions (~6 passes over (2T, T)) or one MXU matmul on 16-bit halves at
# precision=HIGHEST (multi-pass bf16, required for exactness on real
# silicon — the default single-pass dot rounds inputs to 8 significant
# bits; each output row selects at most one input and halves are < 2^16 so
# fp32 accumulation is lossless). stream_available() probes the MXU variant
# first and flips to VPU if it fails to lower or corrupts; their relative
# cost is not measured on the attached chip.
USE_MXU_COMPACT = True

def _variant_name(mxu: bool, mhot: bool) -> str:
    return f"{'mxu' if mxu else 'vpu'}+{'mhot' if mhot else 'nomhot'}"


# the variant the code prefers; stream_available() reports which one runs
FIRST_CHOICE = _variant_name(USE_MXU_COMPACT, True)

_stream_state = {"ok": None, "mhot": True, "variant": None,
                 "reason": "not probed"}


def stream_report() -> dict:
    """(kernel, variant, live, reason) as the capability probe left it —
    read by chip_smoke.py and the /device report, so a kernel that was
    dropped, or runs in other than its first-choice variant, shows."""
    return {"kernel": "stream_expand",
            "variant": _stream_state["variant"],
            "live": bool(_stream_state["ok"]),
            "reason": _stream_state["reason"]}


def _probe_variant(mxu: bool, mhot: bool) -> str:
    """Compile + run a tiny stream_expand in one variant; returns "" when
    it is exact, else what was wrong. Compile errors propagate."""
    # edge values near INT32_MAX with odd low bits: a backend that
    # lowers the compaction dot but truncates fp32 inputs (bf16
    # passes) would corrupt exactly these, so the probe must use
    # values that exercise both 16-bit halves at full width
    big = INT32_MAX - 2
    skey = jnp.asarray([3, INT32_MAX], jnp.int32)
    sstart = jnp.asarray([0, 0], jnp.int32)
    sdeg = jnp.asarray([2, 0], jnp.int32)
    edges = jnp.full(2 * TILE, INT32_MAX, jnp.int32)
    edges = edges.at[0].set(big).at[1].set(65_537)
    cur = jnp.full(8, INT32_MAX, jnp.int32).at[5].set(3)
    live = jnp.ones(8, bool)
    v, p, n, t = wk_walk_merge_stream_expand(
        skey, sstart, sdeg, edges, cur, jnp.int32(6), live, cap_out=1024,
        mxu=mxu, mhot=mhot, mdup=stream_mdup())
    got = [(int(v[i]), int(p[i])) for i in range(min(int(n), 8))]
    if got != [(big, 5), (65_537, 5)]:
        return f"distinct-anchor probe emitted {got}"
    if mhot:
        # duplicate anchors (multiplicity 2) through the m-hot arm:
        # rows 1 and 5 both anchor key 3 — expect each edge twice
        # with both parents (edge-repeat order)
        cur2 = cur.at[1].set(3)
        v, p, n, t = wk_walk_merge_stream_expand(
            skey, sstart, sdeg, edges, cur2, jnp.int32(6), live,
            cap_out=1024, mxu=mxu, mhot=True, mdup=stream_mdup())
        got = sorted((int(v[i]), int(p[i])) for i in range(min(int(n), 8)))
        want = sorted([(big, 1), (big, 5), (65_537, 1), (65_537, 5)])
        if int(t) != 4 or got != want:
            return f"m-hot probe emitted {got} (total {int(t)})"
    return ""


def stream_available() -> bool:
    """One-time capability probe: compile + run a tiny stream_expand on the
    current backend (exercises the grid, SMEM carries, triangular matmuls,
    accumulator flush DMAs) and, when enabled, the m-hot duplicate-anchor
    arm. Preference order: (mxu, mhot) > (vpu, mhot) > (mxu, no-mhot) >
    (vpu, no-mhot); total failure selects the XLA path for the life of the
    process. Every variant that is passed over is logged once with its
    reason, and stream_report() keeps the outcome."""
    global USE_MXU_COMPACT
    if _stream_state["ok"] is not None:
        return _stream_state["ok"]
    platform = jax.devices()[0].platform
    if platform != "tpu":
        _stream_state.update(ok=False, reason=f"platform is {platform}: "
                             "the kernel runs on TPU only")
        return False
    from wukong_tpu.utils.logger import log_warn

    mxu_opts = (True, False) if USE_MXU_COMPACT else (False,)
    skipped = []
    for mhot in (True, False):
        for mxu in mxu_opts:
            variant = _variant_name(mxu, mhot)
            try:
                why = _probe_variant(mxu, mhot)
            except Exception as e:  # the compiler's or runtime's refusal
                why = f"{type(e).__name__}: {e}"
            if not why:
                USE_MXU_COMPACT = mxu
                _stream_state.update(ok=True, mhot=mhot, variant=variant,
                                     reason="; ".join(skipped))
                return True
            log_warn(f"stream_expand variant {variant} not usable: {why}")
            skipped.append(f"{variant}: {why[:300]}")
    log_warn("stream_expand has no usable variant; dense expansions take "
             "the XLA emit")
    _stream_state.update(ok=False, reason="; ".join(skipped))
    return False


def mhot_enabled() -> bool:
    """Whether the duplicate-anchor m-hot arm is active (probe result +
    the WUKONG_ENABLE_STREAM_MHOT A/B toggle)."""
    import os

    if os.environ.get("WUKONG_ENABLE_STREAM_MHOT", "1") == "0":
        return False
    return _stream_state["mhot"]


def stream_mdup() -> int:
    """The active multiplicity cap: WUKONG_STREAM_MDUP env (hardware tuning
    — e.g. 8 lets B=8 replicate heavy batches stream) or the MDUP default."""
    import os

    try:
        v = int(os.environ.get("WUKONG_STREAM_MDUP", MDUP))
    except ValueError:
        return MDUP
    return max(1, min(v, 16))


def want_stream(est_out: float, num_edges: int, cap_out: int) -> bool:
    """Host-side STATIC dispatch: stream when the expansion is estimated
    dense enough that streaming the whole edge array beats per-output
    scatter+gather (~25 ns/out vs ~3 ns/edge => density >= ~1/8), and the
    segment is big enough to amortize kernel launch."""
    from wukong_tpu.config import Global

    if not getattr(Global, "enable_stream_expand", True):
        return False
    if num_edges < 4 * TILE or cap_out % TILE != 0:
        return False
    if est_out < num_edges / 8.0:
        return False
    return FORCE_INTERPRET or stream_available()


# ---------------------------------------------------------------------------
# in-kernel prefix sums via triangular-ones matmuls
# ---------------------------------------------------------------------------


def _tri_ones(n: int, upper: bool, strict: bool):
    """Triangular ones matrix M[a, b]. upper => a-vs-b with a on rows:
    upper selects (a <= b) / (a < b) — right-multiply for lane prefix sums;
    lower selects (a >= b) / (a > b) — left-multiply for sublane offsets."""
    a = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    if upper:
        m = (a < b) if strict else (a <= b)
    else:
        m = (a > b) if strict else (a >= b)
    return m.astype(jnp.float32)


def _psum_small(x2, incl: bool):
    """Prefix sum over the flattened (R, 128) tile for SMALL values (every
    prefix < 2^24, fp32-exact): one lane matmul + one sublane matmul."""
    R = x2.shape[0]
    xf = x2.astype(jnp.float32)
    # precision=HIGHEST everywhere: the default single-pass bf16 MXU dot
    # rounds INPUTS to 8 significant bits, silently corrupting the 16-bit
    # halves (65533 -> 65536) and any row total > 2^8 — third real-silicon
    # lesson, round 5; the fp32-exactness contract needs full-precision
    # passes and these matrices are tiny
    within = jnp.dot(xf, _tri_ones(128, upper=True, strict=False),
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    rtot = jnp.dot(xf, jnp.ones((128, 1), jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    # exclusive prefix of the row totals: roff[a] = sum_{b < a} rtot[b]
    roff = jnp.dot(_tri_ones(R, upper=False, strict=True), rtot,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    out = within + roff
    if not incl:
        out = out - xf
    return out.astype(jnp.int32)


def _psum_i32(x2, incl: bool):
    """Prefix sum for full-range int32 deltas: 16-bit halves, fp32-exact
    partial sums, recombined mod 2^32 (prefix-sum deltas wrap-correct)."""
    lo = x2 & jnp.int32(0xFFFF)
    hi = (x2 - lo) >> 16  # signed high half
    plo = _psum_small(lo, incl)  # prefixes <= T * 65535 < 2^24
    phi = _psum_small(hi, incl)  # |prefixes| <= T * 32768 < 2^24
    return phi * jnp.int32(1 << 16) + plo


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def _dma_ring(stage_a, stage_b, out_a, out_b, sems, carry, cap_pad: int):
    """Double-buffered aligned-block flush helpers shared by both emit
    kernels. Capacity overflow skips the DMA but still counts blocks, so
    waits are flag-guarded ([6+slot]), never inferred from block math.

    Blocks are staged LANE-MAJOR as (TILE//128, 128): tpu.memref_slice
    requires lane-dim slices aligned to the (·,128) tiling, so a (T, 1)
    column stage can never be DMA'd on real silicon (second real-silicon
    lesson, round 5); outputs are (cap_pad//128, 128) HBM buffers whose
    row-major flattening is the column order the callers expect."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = TILE
    R2 = T // 128

    def wait_slot(slot):
        @pl.when(carry[6 + slot] == 1)
        def _():
            blk_idx = carry[4 + slot]
            pltpu.make_async_copy(
                stage_a.at[slot], out_a.at[pl.ds(blk_idx * R2, R2), :],
                sems.at[slot, 0]).wait()
            pltpu.make_async_copy(
                stage_b.at[slot], out_b.at[pl.ds(blk_idx * R2, R2), :],
                sems.at[slot, 1]).wait()
            carry[6 + slot] = 0

    def start_block(blk, slot, src_a, src_b):
        @pl.when((blk + 1) * T <= cap_pad)
        def _():
            stage_a[slot] = src_a.reshape(R2, 128)
            stage_b[slot] = src_b.reshape(R2, 128)
            pltpu.make_async_copy(
                stage_a.at[slot], out_a.at[pl.ds(blk * R2, R2), :],
                sems.at[slot, 0]).start()
            pltpu.make_async_copy(
                stage_b.at[slot], out_b.at[pl.ds(blk * R2, R2), :],
                sems.at[slot, 1]).start()
            carry[4 + slot] = blk
            carry[6 + slot] = 1

    return wait_slot, start_block


def _emit_kernel(edges_ref, dsel_ref, dpar_ref,
                 val_out, par_out, total_out,
                 stage_val, stage_par, acc_val, acc_par, sems, carry,
                 *, cap_pad: int, mxu: bool):
    """Grid step t: integrate deltas over one edge tile, append the selected
    (value, parent) pairs to the VMEM accumulator, flush full aligned TILE
    blocks to HBM via async DMA (double-buffered staging).

    SMEM carry: [0]=sel prefix, [1]=par prefix, [2]=acc fill, [3]=blocks
    emitted, [4+slot]=block index per staging slot, [6+slot]=slot has an
    in-flight DMA (capacity overflow skips the DMA but still counts blocks,
    so waits must be flag-guarded, never inferred from block arithmetic)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = TILE
    R = T // 128
    t = pl.program_id(0)
    G = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        for k in range(8):
            carry[k] = 0
        acc_val[...] = jnp.zeros((2 * T, 1), jnp.int32)
        acc_par[...] = jnp.zeros((2 * T, 1), jnp.int32)

    es2 = edges_ref[...].reshape(R, 128)
    dsel2 = dsel_ref[...].reshape(R, 128)
    dpar2 = dpar_ref[...].reshape(R, 128)

    # integrate: inside-a-matched-run indicator + running parent id
    csel = _psum_small(dsel2, incl=True) + carry[0]
    cpar = _psum_i32(dpar2, incl=True) + carry[1]
    sel = csel > 0
    selin = sel.astype(jnp.int32)
    lrank = _psum_small(selin, incl=False)  # exclusive rank within tile
    count = jnp.sum(selin)

    # append to the accumulator at fill offset f via a one-hot plane:
    # M2[i, j] = sel[j] and (f + lrank[j] == i); rows i < f stay untouched
    f = carry[2]
    # reshape the int32 form: Mosaic's infer-vector-layout rejects i1 shape
    # casts ((2,128)->(1,256) on vector<i1>) — real-silicon lesson, round 5
    sel_r = selin.reshape(1, T) > 0
    lrank_r = lrank.reshape(1, T) + f
    es_r = es2.reshape(1, T)
    par_r = cpar.reshape(1, T)
    ii = jax.lax.broadcasted_iota(jnp.int32, (2 * T, T), 0)
    m2 = sel_r & (lrank_r == ii)
    if mxu:
        # one fp32 matmul on 16-bit halves instead of four VPU plane passes;
        # es/cpar are >= 0 everywhere (pads are INT32_MAX, cpar holds the
        # last run's parent between runs), so the shifts are sign-safe
        mf = m2.astype(jnp.float32)  # (2T, T)
        halves = jnp.concatenate([
            (es_r >> 16).reshape(T, 1), (es_r & 0xFFFF).reshape(T, 1),
            (par_r >> 16).reshape(T, 1), (par_r & 0xFFFF).reshape(T, 1),
        ], axis=1).astype(jnp.float32)  # (T, 4)
        out4 = jnp.dot(mf, halves, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
        acc_val[...] = acc_val[...] + (out4[:, 0:1] * jnp.int32(1 << 16)
                                       + out4[:, 1:2])
        acc_par[...] = acc_par[...] + (out4[:, 2:3] * jnp.int32(1 << 16)
                                       + out4[:, 3:4])
    else:
        acc_val[...] = acc_val[...] + jnp.sum(
            jnp.where(m2, es_r, 0), axis=1, keepdims=True)
        acc_par[...] = acc_par[...] + jnp.sum(
            jnp.where(m2, par_r, 0), axis=1, keepdims=True)
    fnew = f + count
    _wait_slot, _start_block = _dma_ring(stage_val, stage_par, val_out,
                                         par_out, sems, carry, cap_pad)

    @pl.when(fnew >= T)
    def _flush():
        blk = carry[3]
        slot = blk % 2
        _wait_slot(slot)  # free the staging slot before overwriting it
        _start_block(blk, slot, acc_val[0:T], acc_par[0:T])
        # shift the accumulator down one block
        acc_val[0:T] = acc_val[T:2 * T]
        acc_par[0:T] = acc_par[T:2 * T]
        acc_val[T:2 * T] = jnp.zeros((T, 1), jnp.int32)
        acc_par[T:2 * T] = jnp.zeros((T, 1), jnp.int32)
        carry[3] = blk + 1

    carry[2] = jnp.where(fnew >= T, fnew - T, fnew)
    carry[0] = carry[0] + jnp.sum(dsel2)
    carry[1] = carry[1] + jnp.sum(dpar2)

    @pl.when(t == G - 1)
    def _fin():
        blk = carry[3]
        f_end = carry[2]
        # final partial block (aligned, disjoint from all flushed blocks)
        slot = blk % 2
        _wait_slot(slot)
        _start_block(blk, slot, acc_val[0:T], acc_par[0:T])
        _wait_slot(slot)
        _wait_slot(1 - slot)  # drain any DMA still in flight
        total_out[0, 0] = blk * T + f_end


def _tpu_compiler_params(pltpu):
    """Sequential-grid + side-effect compiler params."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                has_side_effects=True)


def _stream_emit(edges2, dsel2, dpar2, cap_out: int, interpret: bool = False,
                 mxu: bool | None = None):
    """pallas_call wrapper: edges2/dsel2/dpar2 are [G, TILE]; returns
    (val [cap_pad, 1], par [cap_pad, 1], emitted [1]) with cap_pad =
    cap_out + TILE (the final partial block may carry zero garbage past the
    true total — callers mask with the returned count)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G = edges2.shape[0]
    T = TILE
    R = T // 128
    # the (cap_pad//128, 128) HBM output layout needs 128-aligned capacity
    # (all engine callers allocate via next_capacity: multiples of 1024)
    assert cap_out % 128 == 0, f"cap_out must be 128-aligned, got {cap_out}"
    cap_pad = cap_out + T
    # Mosaic requires the last two block dims to be (8k, 128m) or exactly
    # the array dims; a [G, T] layout with (1, T) blocks violates the
    # sublane rule for every G > 1 (first real-silicon lesson, round 5).
    # Carrying the tiles as [G, R, 128] makes the block (1, R, 128) — last
    # two dims == array dims — which lowers.
    edges2 = edges2.reshape(G, R, 128)
    dsel2 = dsel2.reshape(G, R, 128)
    dpar2 = dpar2.reshape(G, R, 128)
    tile = pl.BlockSpec((1, R, 128), lambda t: (t, 0, 0),
                        memory_space=pltpu.VMEM)
    kern = partial(_emit_kernel, cap_pad=cap_pad,
                   mxu=USE_MXU_COMPACT if mxu is None else mxu)
    val, par, total = pl.pallas_call(
        kern,
        grid=(G,),
        in_specs=[tile, tile, tile],
        out_shape=(jax.ShapeDtypeStruct((cap_pad // 128, 128), jnp.int32),
                   jax.ShapeDtypeStruct((cap_pad // 128, 128), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        scratch_shapes=[
            pltpu.VMEM((2, T // 128, 128), jnp.int32),  # stage_val
            pltpu.VMEM((2, T // 128, 128), jnp.int32),  # stage_par
            pltpu.VMEM((2 * T, 1), jnp.int32),  # acc_val
            pltpu.VMEM((2 * T, 1), jnp.int32),  # acc_par
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((8,), jnp.int32),
        ],
        compiler_params=_tpu_compiler_params(pltpu),
        interpret=interpret,
    )(edges2, dsel2, dpar2)
    return val.reshape(cap_pad, 1), par.reshape(cap_pad, 1), total


# ---------------------------------------------------------------------------
# m-hot variant: duplicate-anchor frontiers with multiplicity <= MDUP
# ---------------------------------------------------------------------------

MDUP = 4  # default m-hot multiplicity cap (plane height scales with it;
#           override per call via stream_expand(..., mdup=...) or the
#           WUKONG_STREAM_MDUP env consulted by stream_mdup())

_ROW_OFF = 1 << 18  # keeps the q payload non-negative for the halves trick


def _emit_kernel_m(edges_ref, dsel_ref, drow_ref,
                   val_out, row_out, total_out,
                   stage_val, stage_row, acc_val, acc_row, sems, carry,
                   *, cap_pad: int, mxu: bool, mdup: int):
    """Duplicate-anchor streaming: dsel integrates to a per-edge
    MULTIPLICITY m(e) in [0, mdup] (duplicated runs scatter +k/-k at their
    shared boundaries), each edge occupies m(e) consecutive output rows
    (edge-repeat order — bag semantics downstream), and instead of a
    parent id the kernel emits a ROW POSITION rowpos = dupstart(run) +
    copy_index; the XLA wrapper resolves parents with one sorted-rank
    gather. drow integrates to dupstart(run) per edge (deltas at
    first-occurrence run starts, like dpar).

    SMEM carry: [0]=mult prefix, [1]=rowbase prefix, [2]=acc fill,
    [3]=blocks emitted, [4+slot]=block per staging slot, [6+slot]=busy."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = TILE
    R = T // 128
    A = (mdup + 1) * T  # accumulator rows: fill < T plus <= mdup*T new
    t = pl.program_id(0)
    G = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        for k in range(8):
            carry[k] = 0
        acc_val[...] = jnp.zeros((A, 1), jnp.int32)
        acc_row[...] = jnp.zeros((A, 1), jnp.int32)

    es2 = edges_ref[...].reshape(R, 128)
    dsel2 = dsel_ref[...].reshape(R, 128)
    drow2 = drow_ref[...].reshape(R, 128)

    mult = jnp.maximum(_psum_small(dsel2, incl=True) + carry[0], 0)
    crow = _psum_i32(drow2, incl=True) + carry[1]
    lrank = _psum_small(mult, incl=False)  # exclusive, < mdup*T (fp32-exact)
    count = jnp.sum(mult)
    f = carry[2]

    mult_r = mult.reshape(1, T)
    lrank_r = lrank.reshape(1, T) + f
    es_r = es2.reshape(1, T)
    # rowpos(ii) = rowbase[j] + (ii - lrank[j]) for the edge j covering
    # output row ii; q = rowbase - lrank (+offset so both halves stay
    # non-negative: rowbase < C <= 2^25, lrank < (mdup+1)*T <= 17*T < 2^18)
    q_r = crow.reshape(1, T) - lrank_r + jnp.int32(_ROW_OFF)
    ii = jax.lax.broadcasted_iota(jnp.int32, (A, T), 0)
    m2 = (ii >= lrank_r) & (ii < lrank_r + mult_r)
    ii_col = jax.lax.broadcasted_iota(jnp.int32, (A, 1), 0)
    if mxu:
        mf = m2.astype(jnp.float32)  # (A, T)
        halves = jnp.concatenate([
            (es_r >> 16).reshape(T, 1), (es_r & 0xFFFF).reshape(T, 1),
            (q_r >> 16).reshape(T, 1), (q_r & 0xFFFF).reshape(T, 1),
            jnp.ones((T, 1), jnp.int32),
        ], axis=1).astype(jnp.float32)  # (T, 5)
        out5 = jnp.dot(mf, halves, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
        cov = out5[:, 4:5]  # covered-row indicator (0/1)
        acc_val[...] = acc_val[...] + (out5[:, 0:1] * jnp.int32(1 << 16)
                                       + out5[:, 1:2])
        acc_row[...] = acc_row[...] + (
            out5[:, 2:3] * jnp.int32(1 << 16) + out5[:, 3:4]
            + (ii_col - jnp.int32(_ROW_OFF)) * cov)
    else:
        cov = jnp.sum(m2.astype(jnp.int32), axis=1, keepdims=True)
        acc_val[...] = acc_val[...] + jnp.sum(
            jnp.where(m2, es_r, 0), axis=1, keepdims=True)
        acc_row[...] = acc_row[...] + (
            jnp.sum(jnp.where(m2, q_r, 0), axis=1, keepdims=True)
            + (ii_col - jnp.int32(_ROW_OFF)) * cov)
    fnew = f + count
    _wait_slot, _start_block = _dma_ring(stage_val, stage_row, val_out,
                                         row_out, sems, carry, cap_pad)

    # flush every full block (up to mdup+1 per tile), then slide the tail
    # block down and clear the rest — rows at/after fnew are always zero,
    # so the dynamic tail read only moves live data + zeros
    nblk = fnew // T
    for k in range(mdup + 1):
        @pl.when(k < nblk)
        def _(k=k):
            blk = carry[3] + k
            slot = (carry[3] + k) % 2
            _wait_slot(slot)
            _start_block(blk, slot, acc_val[k * T:(k + 1) * T],
                         acc_row[k * T:(k + 1) * T])

    tail_val = acc_val[pl.ds(nblk * T, T)]
    tail_row = acc_row[pl.ds(nblk * T, T)]
    acc_val[...] = jnp.zeros((A, 1), jnp.int32)
    acc_row[...] = jnp.zeros((A, 1), jnp.int32)
    acc_val[0:T] = tail_val
    acc_row[0:T] = tail_row
    carry[3] = carry[3] + nblk
    carry[2] = fnew - nblk * T
    carry[0] = carry[0] + jnp.sum(dsel2)
    carry[1] = carry[1] + jnp.sum(drow2)

    @pl.when(t == G - 1)
    def _fin():
        blk = carry[3]
        f_end = carry[2]
        slot = blk % 2
        _wait_slot(slot)
        _start_block(blk, slot, acc_val[0:T], acc_row[0:T])
        _wait_slot(slot)
        _wait_slot(1 - slot)
        total_out[0, 0] = blk * T + f_end


def _stream_emit_m(edges2, dsel2, drow2, cap_out: int, interpret: bool = False,
                   mxu: bool | None = None, mdup: int = MDUP):
    """pallas_call wrapper for the m-hot kernel: returns (val [cap_pad, 1],
    rowpos [cap_pad, 1], emitted [1]); cap_pad = cap_out + (mdup+1)*TILE so
    every in-capacity flush block stays aligned and disjoint."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G = edges2.shape[0]
    T = TILE
    R = T // 128
    A = (mdup + 1) * T
    # same 128-aligned capacity precondition as _stream_emit
    assert cap_out % 128 == 0, f"cap_out must be 128-aligned, got {cap_out}"
    cap_pad = cap_out + A
    # same [G, R, 128] layout as _stream_emit — see the Mosaic block-dim
    # note there
    edges2 = edges2.reshape(G, R, 128)
    dsel2 = dsel2.reshape(G, R, 128)
    drow2 = drow2.reshape(G, R, 128)
    tile = pl.BlockSpec((1, R, 128), lambda t: (t, 0, 0),
                        memory_space=pltpu.VMEM)
    kern = partial(_emit_kernel_m, cap_pad=cap_pad,
                   mxu=USE_MXU_COMPACT if mxu is None else mxu, mdup=mdup)
    val, rowpos, total = pl.pallas_call(
        kern,
        grid=(G,),
        in_specs=[tile, tile, tile],
        out_shape=(jax.ShapeDtypeStruct((cap_pad // 128, 128), jnp.int32),
                   jax.ShapeDtypeStruct((cap_pad // 128, 128), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        scratch_shapes=[
            pltpu.VMEM((2, T // 128, 128), jnp.int32),  # stage_val
            pltpu.VMEM((2, T // 128, 128), jnp.int32),  # stage_row
            pltpu.VMEM((A, 1), jnp.int32),     # acc_val
            pltpu.VMEM((A, 1), jnp.int32),     # acc_row
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((8,), jnp.int32),
        ],
        compiler_params=_tpu_compiler_params(pltpu),
        interpret=interpret,
    )(edges2, dsel2, drow2)
    return val.reshape(cap_pad, 1), rowpos.reshape(cap_pad, 1), total


# ---------------------------------------------------------------------------
# the drop-in expand (merge_expand contract)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cap_out", "interpret", "mxu", "mhot",
                                   "mdup"))
def wk_walk_merge_stream_expand(skey, sstart, sdeg, edges, cur, n, live,
                                cap_out: int, interpret: bool = False,
                                mxu: bool | None = None, mhot: bool = True,
                                mdup: int = MDUP):
    """known_to_unknown expansion with the streaming emitter: (val
    [cap_out], parent [cap_out], out_n, total).

    Distinct-anchor frontiers are bit-identical to
    tpu_kernels.wk_walk_merge_expand (edge order = key-sorted anchor order).
    Duplicate-anchor frontiers with per-key multiplicity <= MDUP stream
    through the m-hot kernel (edge-repeat order — a permutation of the
    same bag; downstream is order-insensitive); higher multiplicity falls
    back to the XLA emit. `mhot=False` drops the middle arm entirely (for
    backends where the m-hot kernel fails to lower)."""
    from wukong_tpu.engine import tpu_kernels as K

    C = cur.shape[0]
    S = skey.shape[0]
    E = edges.shape[0]
    T = TILE
    rows = jnp.arange(C, dtype=jnp.int32)
    ok_row = (rows < n) & live
    curm = jnp.where(ok_row, cur, INT32_MAX)
    ks, ts, found, start, deg, is_seg = _merge_lookup(skey, sstart, sdeg,
                                                      curm)
    deg = jnp.where(is_seg, 0, deg)
    cum = jnp.cumsum(deg)
    total = _saturate_total(cum)
    st_ex = cum - deg

    # duplicate anchors: two adjacent FOUND query rows sharing a key
    dup = jnp.any((~is_seg[1:]) & (~is_seg[:-1]) & found[1:]
                  & (ks[1:] == ks[:-1]) & (ks[1:] != INT32_MAX))

    def _xla(_):
        val, parent = K._emit_gather(ts, S, start, deg, st_ex, edges,
                                     total, cap_out)
        return val, parent

    # per-row group bookkeeping in merged-sorted order (the segment row
    # sorts first within each key, duplicates follow adjacently) — shared
    # by the m-hot arm and its multiplicity gate
    is_run = (~is_seg) & found & (deg > 0)
    rank = jnp.cumsum(is_run.astype(jnp.int32)) - 1
    SC = is_run.shape[0]
    prev_run = jnp.concatenate([jnp.zeros(1, bool), is_run[:-1]])
    # prev_ks[0] is arbitrary: prev_run[0] is False, so it never matters
    prev_ks = jnp.concatenate([ks[:1], ks[:-1]])
    first_occ = is_run & ~(prev_run & (prev_ks == ks))

    def _mhot(_):
        Et = max(E, T)
        # dsel over ALL runs: duplicated boundaries accumulate multiplicity
        tgt = jnp.where(is_run, rank, SC)
        rstart = jnp.zeros(SC, jnp.int32).at[tgt].set(start, mode="drop")
        rdeg = jnp.zeros(SC, jnp.int32).at[tgt].set(deg, mode="drop")
        n_runs = jnp.sum(is_run.astype(jnp.int32))
        valid_r = jnp.arange(SC, dtype=jnp.int32) < n_runs
        s_idx = jnp.where(valid_r, rstart, Et)
        e_idx = jnp.where(valid_r, rstart + rdeg, Et)
        dsel = (jnp.zeros(Et + 1, jnp.int32)
                .at[s_idx].add(1, mode="drop")
                .at[e_idx].add(-1, mode="drop"))
        # drow: dupstart deltas at FIRST-occurrence run starts only
        rk1 = jnp.cumsum(first_occ.astype(jnp.int32)) - 1
        tgt1 = jnp.where(first_occ, rk1, SC)
        r1start = jnp.zeros(SC, jnp.int32).at[tgt1].set(start, mode="drop")
        r1dst = jnp.zeros(SC, jnp.int32).at[tgt1].set(
            jnp.where(first_occ, rank, 0), mode="drop")
        n1 = jnp.sum(first_occ.astype(jnp.int32))
        valid1 = jnp.arange(SC, dtype=jnp.int32) < n1
        s1 = jnp.where(valid1, r1start, Et)
        prev1 = jnp.concatenate([r1dst[:1] * 0, r1dst[:-1]])
        d1 = jnp.where(valid1, r1dst - prev1, 0)
        drow = jnp.zeros(Et + 1, jnp.int32).at[s1].add(d1, mode="drop")
        # parents of found rows in sorted-rank order (the rowpos codomain)
        parents_sorted = jnp.zeros(SC, jnp.int32).at[tgt].set(
            ts - S, mode="drop")

        ed = edges if E >= T else jnp.pad(edges, (0, T - E),
                                          constant_values=INT32_MAX)
        G = Et // T
        v2, rp2, _tot = _stream_emit_m(ed.reshape(G, T),
                                       dsel[:Et].reshape(G, T),
                                       drow[:Et].reshape(G, T),
                                       cap_out=cap_out, interpret=interpret,
                                       mxu=mxu, mdup=mdup)
        rowpos = jnp.clip(rp2[:cap_out, 0], 0, SC - 1)
        return v2[:cap_out, 0], parents_sorted[rowpos]

    def _stream(_):
        # compact matched runs (disjoint, ascending starts in key order)
        is_run = (~is_seg) & found & (deg > 0)
        rk = jnp.cumsum(is_run.astype(jnp.int32)) - 1
        tgt = jnp.where(is_run, rk, C)
        rstart = jnp.zeros(C, jnp.int32).at[tgt].set(start, mode="drop")
        rdeg = jnp.zeros(C, jnp.int32).at[tgt].set(deg, mode="drop")
        rpar = jnp.zeros(C, jnp.int32).at[tgt].set(ts - S, mode="drop")
        n_runs = jnp.sum(is_run.astype(jnp.int32))
        valid_r = jnp.arange(C, dtype=jnp.int32) < n_runs

        Et = max(E, T)  # static; segment edges are pow2-padded upstream
        s_idx = jnp.where(valid_r, rstart, Et)
        e_idx = jnp.where(valid_r, rstart + rdeg, Et)
        dsel = (jnp.zeros(Et + 1, jnp.int32)
                .at[s_idx].add(1, mode="drop")
                .at[e_idx].add(-1, mode="drop"))
        prev = jnp.concatenate([rpar[:1] * 0, rpar[:-1]])
        dpv = jnp.where(valid_r, rpar - prev, 0)
        # run starts are distinct, but a start can equal another run's END
        # (dsel handles that with .add); dpar only ever hits starts
        dpar = jnp.zeros(Et + 1, jnp.int32).at[s_idx].add(dpv, mode="drop")

        ed = edges if E >= T else jnp.pad(edges, (0, T - E),
                                          constant_values=INT32_MAX)
        G = Et // T
        v2, p2, _tot = _stream_emit(ed.reshape(G, T),
                                    dsel[:Et].reshape(G, T),
                                    dpar[:Et].reshape(G, T),
                                    cap_out=cap_out, interpret=interpret,
                                    mxu=mxu)
        return v2[:cap_out, 0], p2[:cap_out, 0]

    if mhot:
        # per-key multiplicity bound decides the middle arm on device
        dupstart_g = jax.lax.cummax(jnp.where(first_occ, rank, -1))
        mmax = jnp.max(jnp.where(is_run, rank - dupstart_g + 1, 0))

        def _dup_arm(_):
            return jax.lax.cond(mmax <= mdup, _mhot, _xla, None)

        val, parent = jax.lax.cond(dup, _dup_arm, _stream, None)
    else:
        val, parent = jax.lax.cond(dup, _xla, _stream, None)
    j = jnp.arange(cap_out, dtype=jnp.int32)
    okj = j < total
    return (jnp.where(okj, val, 0), jnp.where(okj, parent, 0),
            jnp.minimum(total, cap_out).astype(jnp.int32), total)
