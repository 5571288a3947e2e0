#!/usr/bin/env python3
"""ns an element for what ``join/kernels.py:direct_lookup_wins`` weighs, on
the device JAX finds: gather, scatter, fill and scan at 2^23, then both forms
of the key lookup over a 13.9 M-key segment by frontier size (the shapes of
LUBM-640's type segment), then both forms of a list's membership at the
shapes of LSQB's level probe (PR 35). One JSON line an operation. The
constants of ``DIRECT_NS`` are this script's readings on a TPU v5 lite
(PERF.md, PR 27); run it again through the chip tool before moving them. A
CPU run times the CPU backend and is no source for them.

    python scripts/bench_direct_lookup.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from wukong_tpu.join import kernels as K

NK, BOUND, R = 13_937_249, 14_068_321, 1 << 23


def timeit(name, fn, *args, elems, reps=3):
    f = jax.jit(fn)
    out = jax.block_until_ready(f(*args))  # compiles
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    print(json.dumps({"op": name, "ms": round(best * 1e3, 3), "elems": elems,
                      "ns_per_elem": round(best * 1e9 / elems, 3)}),
          flush=True)
    return out


def main():
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}))
    rng = np.random.default_rng(27)
    keys_np = np.sort(rng.choice(np.arange(1 << 17, BOUND, dtype=np.int32),
                                 NK, replace=False))
    keys = jnp.asarray(keys_np)
    offsets = jnp.asarray(np.concatenate(
        [[0], np.cumsum(rng.integers(0, 3, NK))]).astype(np.int32))
    table = jnp.arange(BOUND, dtype=jnp.int32)
    idx = jnp.asarray(rng.integers(0, BOUND, R).astype(np.int32))
    x = jnp.asarray(rng.integers(0, 1 << 20, R).astype(np.int32))
    marks = jnp.sort(jnp.asarray(
        rng.choice(R, 1 << 21, replace=False).astype(np.int32)))
    ids = jnp.arange(NK, dtype=jnp.int32)
    timeit("gather 2^23 random", lambda t, i: t[i], table, idx, elems=R)
    timeit("gather 2^23 sorted", lambda t, i: t[i], table, jnp.sort(idx),
           elems=R)
    timeit("scatter 13.9M sorted unique", lambda k: jnp.full(
        BOUND, -1, jnp.int32).at[k].set(
            ids, mode="drop", indices_are_sorted=True, unique_indices=True),
        keys, elems=NK)
    timeit("scatter 13.9M, no promise", lambda k: jnp.full(
        BOUND, -1, jnp.int32).at[k].set(ids, mode="drop"), keys, elems=NK)
    timeit("fill 14.07M", lambda k: jnp.full(BOUND, -1, jnp.int32) + k[0],
           keys, elems=BOUND)
    timeit("cummax 2^23", jax.lax.cummax, x, elems=R)
    timeit("cumsum 2^23", jnp.cumsum, x, elems=R)
    timeit("scatter-max 2^21 sorted into 2^23", lambda p: jnp.zeros(
        R, jnp.int32).at[p].max(jnp.arange(1 << 21, dtype=jnp.int32) + 1,
                                mode="drop", indices_are_sorted=True),
        marks, elems=1 << 21)
    rule = K.direct_lookup_wins
    for lg in (10, 14, 16, 17, 18, 20, 21, 23):
        rows = 1 << lg
        vids = jnp.asarray(np.where(
            rng.random(rows) < 0.7, keys_np[rng.integers(0, NK, rows)],
            rng.integers(0, BOUND, rows)).astype(np.int32))
        for form in (False, True):
            K.direct_lookup_wins = lambda *_a, _f=form: _f
            timeit(f"lookup {'direct' if form else 'search'} rows=2^{lg}",
                   lambda k, o, v: K.lookup_ranges_device(k, o, v, BOUND),
                   keys, offsets, vids,
                   elems=NK if form else rows * NK.bit_length())
        K.direct_lookup_wins = rule
        print(json.dumps({"rows": rows, "rule_says_direct":
                          rule(rows, NK, BOUND)}), flush=True)
    # a list's membership (``member_sorted_device``): the persons' and the
    # comments' lists of LSQB at scale factor 3, under its vertex bound
    vbound = 11_245_376
    for n, lg in ((27_000, 21), (27_000, 23), (8_103_888, 23)):
        rows = 1 << lg
        lst_np = np.sort(rng.choice(vbound, n, replace=False)
                         .astype(np.int32))
        lst = jnp.asarray(lst_np)
        vals = jnp.asarray(np.where(
            rng.random(rows) < 0.6, lst_np[rng.integers(0, n, rows)],
            rng.integers(0, vbound, rows)).astype(np.int32))
        for form in (False, True):
            K.direct_lookup_wins = lambda *_a, _f=form: _f
            timeit(f"member {'direct' if form else 'search'} n={n} "
                   f"rows=2^{lg}",
                   lambda a, v: K.member_sorted_device(a, v, vbound),
                   lst, vals, elems=n if form else rows * n.bit_length())
        K.direct_lookup_wins = rule
        print(json.dumps({"rows": rows, "list": n, "rule_says_direct":
                          rule(rows, n, vbound)}), flush=True)


if __name__ == "__main__":
    main()
