"""NT -> ID-Triples converter (reference: datagen/generate_data.cpp).

Reads a directory of N-Triples files, assigns ids with the reference's scheme
(generate_data.cpp:112-123: __PREDICATE__=0, rdf:type=1, index ids from 2 in first-seen
order, normal ids from 2^17 in first-seen order), detects typed-literal attribute
triples (find_type, generate_data.cpp:53-64), honors ``@prefix`` lines
(generate_data.cpp:144-149, 173-194), and writes ``id_<file>``/``attr_<file>`` plus
``str_index``, ``str_normal`` and ``str_attr_index`` tables.

Streaming replay (``--timestamps N``): emit 4-column ``s p o ts`` rows with
seeded pseudo-random timestamps drawn from N distinct epochs, deliberately
OUT OF ORDER within the file — the shape real arrival logs have — so
``stream.FileSource`` replay exercises its timestamp sort/group path
instead of the synthetic in-order axis (PR 2 follow-up c).
"""

from __future__ import annotations

import json
import os
import random
import sys

RDF_TYPE_STR = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"

_ATTR_SUFFIXES = [
    ("^^xsd:int", 1), ("^^<http://www.w3.org/2001/XMLSchema#int>", 1),
    ("^^xsd:float", 2), ("^^<http://www.w3.org/2001/XMLSchema#float>", 2),
    ("^^xsd:double", 3), ("^^<http://www.w3.org/2001/XMLSchema#double>", 3),
]


def _find_type(obj: str) -> int:
    for suf, t in _ATTR_SUFFIXES:
        if suf in obj:
            return t
    return 0


def _find_value(obj: str) -> str:
    a = obj.find('"')
    b = obj.find('"', a + 1)
    if a < 0 or b < 0:
        raise ValueError(f"malformed typed literal: {obj!r}")
    return obj[a + 1:b]


class IdAssigner:
    def __init__(self):
        from wukong_tpu.types import NORMAL_ID_START

        self.str_to_id: dict[str, int] = {"__PREDICATE__": 0, RDF_TYPE_STR: 1}
        self.index_str: list[str] = ["__PREDICATE__", RDF_TYPE_STR]
        self.normal_str: list[str] = []
        self.attr_index_str: list[str] = []
        self.index_to_type: dict[str, int] = {}
        self.next_index_id = 2
        self.next_normal_id = NORMAL_ID_START

    def normal(self, s: str) -> int:
        i = self.str_to_id.get(s)
        if i is None:
            i = self.str_to_id[s] = self.next_normal_id
            self.next_normal_id += 1
            self.normal_str.append(s)
        return i

    def index(self, s: str, attr_type: int = 0) -> int:
        i = self.str_to_id.get(s)
        if i is None:
            i = self.str_to_id[s] = self.next_index_id
            self.next_index_id += 1
            if attr_type:
                self.attr_index_str.append(s)
                self.index_to_type[s] = attr_type
            else:
                self.index_str.append(s)
        return i


def _expand_prefix(token: str, prefixes: dict[str, str]) -> str:
    """prefix:name -> <full_uri_name> using @prefix map (generate_data.cpp:173-194)."""
    if prefixes and not token.startswith("<") and ":" in token:
        key, rest = token.split(":", 1)
        if key in prefixes:
            base = prefixes[key]
            return base[:-1] + rest + ">"
    return token


def convert_dir(src_dir: str, dst_dir: str, timestamps: int = 0,
                ts_seed: int = 0) -> dict:
    """Convert ``src_dir`` N-Triples into id-format under ``dst_dir``.

    ``timestamps > 0`` switches the id_* files to the 4-column
    ``s p o ts`` form: each row draws a seeded pseudo-random epoch in
    [0, timestamps) — shuffled, not monotone, so replays arrive out of
    order like real logs. 0 keeps the reference 3-column form.
    """
    os.makedirs(dst_dir, exist_ok=True)
    ids = IdAssigner()
    nfiles = 0
    ts_rng = random.Random(ts_seed) if timestamps > 0 else None
    for name in sorted(os.listdir(src_dir)):
        if name.startswith("."):
            continue
        nfiles += 1
        prefixes: dict[str, str] = {}
        with open(os.path.join(src_dir, name)) as fin, \
                open(os.path.join(dst_dir, f"id_{name}"), "w") as fout, \
                open(os.path.join(dst_dir, f"attr_{name}"), "w") as fattr:
            for line in fin:
                parts = line.split()
                if len(parts) < 4:
                    continue
                subject, predicate, obj = parts[0], parts[1], " ".join(parts[2:-1])
                if subject == "@prefix":
                    prefixes[predicate.rstrip(":").split(":")[0]] = obj
                    continue
                # expand prefixes before id assignment on BOTH branches (the
                # reference expands only on the normal branch,
                # generate_data.cpp:171-194, which splits a prefixed subject
                # into two ids when it also has attribute triples — fixed here)
                subject = _expand_prefix(subject, prefixes)
                predicate = _expand_prefix(predicate, prefixes)
                t = _find_type(obj)
                if t:
                    sid = ids.normal(subject)
                    pid = ids.index(predicate, attr_type=t)
                    fattr.write(f"{sid}\t{pid}\t{t}\t{_find_value(obj)}\n")
                    continue
                obj = _expand_prefix(obj, prefixes)
                sid = ids.normal(subject)
                pid = ids.index(predicate)
                oid = ids.index(obj) if predicate == RDF_TYPE_STR else ids.normal(obj)
                if ts_rng is not None:
                    fout.write(f"{sid}\t{pid}\t{oid}\t"
                               f"{ts_rng.randrange(timestamps)}\n")
                else:
                    fout.write(f"{sid}\t{pid}\t{oid}\n")

    with open(os.path.join(dst_dir, "str_normal"), "w") as f:
        for s in ids.normal_str:
            f.write(f"{s}\t{ids.str_to_id[s]}\n")
    with open(os.path.join(dst_dir, "str_index"), "w") as f:
        for s in ids.index_str:
            f.write(f"{s}\t{ids.str_to_id[s]}\n")
    with open(os.path.join(dst_dir, "str_attr_index"), "w") as f:
        for s in ids.attr_index_str:
            f.write(f"{s}\t{ids.str_to_id[s]}\t{ids.index_to_type[s]}\n")

    meta = {
        "total_vertex": len(ids.str_to_id),
        "normal_vertex": len(ids.normal_str),
        "index_vertex": len(ids.index_str),
        "attr_vertex": len(ids.attr_index_str),
        "files": nfiles,
        "timestamps": int(timestamps),
    }
    return meta


# ---------------------------------------------------------------------------
# synthetic cyclic worlds (the WCOJ workload suite — LUBM has no cycles)
# ---------------------------------------------------------------------------
#
# Each generator returns ([M,3] int64 triples, meta) where meta carries the
# predicate/type id map and the cyclic query as a parsed-form pattern list
# (vars negative, triple orientation) plus its projection vars — enough for
# tests to build queries without a string server.
#
# The triangle/diamond worlds embed the AGM lower-bound instance (star +
# co-star hubs: R(A,B) = {a*}xB ∪ Ax{b*}): every PAIRWISE join is Θ(m²)
# while the cyclic result is Θ(m), so ANY walk order materializes a
# quadratic wedge set — exactly the blow-up worst-case-optimal joins avoid.

def _cyclic_meta(P: dict, T: dict, patterns: list, vars_: list) -> dict:
    return {"P": dict(P), "T": dict(T), "patterns": list(patterns),
            "vars": list(vars_)}


def _star_costar(rng, rows: list, pid: int, L, R, noise: int, m: int) -> None:
    """Append the AGM lower-bound hub relation {L[0]}xR ∪ Lx{R[0]} (plus
    ``noise*m`` random background edges) for one predicate — the instance
    where every pairwise join is quadratic while the cyclic result stays
    linear. Shared by the triangle and diamond world builders."""
    import numpy as np

    rows.append(np.column_stack([np.full(len(R), L[0]),
                                 np.full(len(R), pid), R]))
    rows.append(np.column_stack([L, np.full(len(L), pid),
                                 np.full(len(L), R[0])]))
    if noise > 0:
        k = noise * m
        rows.append(np.column_stack([rng.choice(L, k),
                                     np.full(k, pid), rng.choice(R, k)]))


def generate_triangle(m: int = 256, noise: int = 4, seed: int = 0):
    """Tripartite triangle world A--p1->B--p2->C with closing A--p3->C.

    Star/co-star hubs on all three relations (each relation ~2m edges, all
    pairwise joins Θ(m²), triangles Θ(m)) plus ``noise*m`` random edges per
    relation and per-entity type triples.
    """
    import numpy as np

    from wukong_tpu.types import NORMAL_ID_START, TYPE_ID

    rng = np.random.default_rng(seed)
    P = {"p1": 2, "p2": 3, "p3": 4}
    T = {"A": 5, "B": 6, "C": 7}
    A = np.arange(NORMAL_ID_START, NORMAL_ID_START + m, dtype=np.int64)
    B, C = A + m, A + 2 * m
    rows = []
    _star_costar(rng, rows, P["p1"], A, B, noise, m)
    _star_costar(rng, rows, P["p2"], B, C, noise, m)
    _star_costar(rng, rows, P["p3"], A, C, noise, m)
    for t, part in ((T["A"], A), (T["B"], B), (T["C"], C)):
        rows.append(np.column_stack([part, np.full(m, TYPE_ID),
                                     np.full(m, t)]))
    triples = np.concatenate(rows).astype(np.int64)
    va, vb, vc = -1, -2, -3
    meta = _cyclic_meta(P, T, [(va, P["p1"], vb), (vb, P["p2"], vc),
                               (va, P["p3"], vc)], [va, vb, vc])
    return triples, meta


def generate_diamond(m: int = 192, noise: int = 4, seed: int = 0):
    """4-cycle world A--p1->B--p2->C--p3->D with closing A--p4->D (the
    diamond BGP), star/co-star hubs on every relation + noise + types."""
    import numpy as np

    from wukong_tpu.types import NORMAL_ID_START, TYPE_ID

    rng = np.random.default_rng(seed)
    P = {"p1": 2, "p2": 3, "p3": 4, "p4": 5}
    T = {"A": 6, "B": 7, "C": 8, "D": 9}
    A = np.arange(NORMAL_ID_START, NORMAL_ID_START + m, dtype=np.int64)
    B, C, D = A + m, A + 2 * m, A + 3 * m
    rows = []
    _star_costar(rng, rows, P["p1"], A, B, noise, m)
    _star_costar(rng, rows, P["p2"], B, C, noise, m)
    _star_costar(rng, rows, P["p3"], C, D, noise, m)
    _star_costar(rng, rows, P["p4"], A, D, noise, m)
    for t, part in ((T["A"], A), (T["B"], B), (T["C"], C), (T["D"], D)):
        rows.append(np.column_stack([part, np.full(m, TYPE_ID),
                                     np.full(m, t)]))
    triples = np.concatenate(rows).astype(np.int64)
    va, vb, vc, vd = -1, -2, -3, -4
    meta = _cyclic_meta(P, T, [(va, P["p1"], vb), (vb, P["p2"], vc),
                               (vc, P["p3"], vd), (va, P["p4"], vd)],
                        [va, vb, vc, vd])
    return triples, meta


def generate_clique4(n: int = 400, fan: int = 8, ncliques: int = 24,
                     seed: int = 0):
    """Single-predicate world with planted (direction-consistent) 4-cliques
    in a random lower-id->higher-id background graph. The 4-clique BGP is
    the densest small cyclic shape (6 patterns over 4 vars)."""
    import numpy as np

    from wukong_tpu.types import NORMAL_ID_START, TYPE_ID

    rng = np.random.default_rng(seed)
    P = {"p": 2}
    T = {"V": 3}
    V = np.arange(NORMAL_ID_START, NORMAL_ID_START + n, dtype=np.int64)
    src = np.repeat(V[:-1], fan)
    dst_off = rng.integers(1, np.maximum(n - 1 - (src - V[0]), 1) + 1)
    dst = src + dst_off  # strictly higher id: no 2-cycles
    rows = [np.column_stack([src, np.full(len(src), P["p"]), dst])]
    for _ in range(ncliques):
        picks = np.sort(rng.choice(n, 4, replace=False)) + V[0]
        for i in range(4):
            for j in range(i + 1, 4):
                rows.append(np.array([[picks[i], P["p"], picks[j]]]))
    rows.append(np.column_stack([V, np.full(n, TYPE_ID),
                                 np.full(n, T["V"])]))
    triples = np.concatenate(rows).astype(np.int64)
    v1, v2, v3, v4 = -1, -2, -3, -4
    pats = [(a, P["p"], b) for a, b in
            ((v1, v2), (v1, v3), (v1, v4), (v2, v3), (v2, v4), (v3, v4))]
    meta = _cyclic_meta(P, T, pats, [v1, v2, v3, v4])
    return triples, meta


class CyclicStrings:
    """Minimal virtual string backend for the synthetic cyclic worlds
    (``<urn:cyc:p:NAME>`` predicates, ``<urn:cyc:t:NAME>`` types,
    ``<urn:cyc:v:K>`` entities) — enough for the parser/proxy path."""

    def __init__(self, meta: dict):
        self._s2i = {f"<urn:cyc:p:{n}>": i for n, i in meta["P"].items()}
        self._s2i.update({f"<urn:cyc:t:{n}>": i
                          for n, i in meta["T"].items()})
        self._s2i["<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"] = 1
        self._i2s = {i: s for s, i in self._s2i.items()}

    def str2id(self, s: str) -> int:
        from wukong_tpu.types import NORMAL_ID_START

        if s in self._s2i:
            return self._s2i[s]
        if s.startswith("<urn:cyc:v:") and s.endswith(">"):
            return NORMAL_ID_START + int(s[len("<urn:cyc:v:"):-1])
        raise KeyError(s)

    def id2str(self, i: int) -> str:
        from wukong_tpu.types import NORMAL_ID_START

        if i in self._i2s:
            return self._i2s[i]
        return f"<urn:cyc:v:{i - NORMAL_ID_START}>"

    def exist(self, s: str) -> bool:
        try:
            self.str2id(s)
            return True
        except (KeyError, ValueError):
            return False

    def exist_id(self, i: int) -> bool:
        return True


def cyclic_query_text(meta: dict) -> str:
    """SPARQL text of a cyclic world's query (CyclicStrings vocabulary)."""
    p_name = {i: n for n, i in meta["P"].items()}

    def term(v: int) -> str:
        return f"?v{-v}" if v < 0 else f"<urn:cyc:p:{p_name[v]}>"

    sel = " ".join(f"?v{-v}" for v in meta["vars"])
    body = " ".join(f"{term(s)} <urn:cyc:p:{p_name[p]}> {term(o)} ."
                    for (s, p, o) in meta["patterns"])
    return f"SELECT {sel} WHERE {{ {body} }}"


def watdiv_cyclic_patterns() -> dict:
    """WatDiv-based cyclic query set (parsed-form patterns over the
    loader/watdiv.py id space): the social triangle (two friends liking
    the same product) and the follows/friendOf diamond. Run against
    ``generate_watdiv`` worlds. No caller in the tree (ROADMAP D10)."""
    from wukong_tpu.loader.watdiv import P

    u, v, w = -1, -2, -3
    pa, pb, g = -3, -4, -5
    return {
        "w_tri_likes": {  # two friends liking the same product
            "patterns": [(u, P["friendOf"], v), (u, P["likes"], pa),
                         (v, P["likes"], pa)],
            "vars": [u, v, pa]},
        "w_tri_follows": {  # a follow edge closed by a common friend
            "patterns": [(u, P["follows"], v), (u, P["friendOf"], w),
                         (v, P["friendOf"], w)],
            "vars": [u, v, w]},
        "w_pentagon": {  # friends liking same-genre products (5-cycle)
            "patterns": [(u, P["friendOf"], v), (u, P["likes"], pa),
                         (v, P["likes"], pb), (pa, P["hasGenre"], g),
                         (pb, P["hasGenre"], g)],
            "vars": [u, v, pa, pb, g]},
    }


def make_vectors(vids, dim: int, seed: int = 0, clusters: int = 16):
    """Deterministic clustered embeddings for a set of vertex ids.

    Each vertex is assigned (by id hash, so the mapping survives
    re-generation) to one of ``clusters`` unit-norm centers and placed
    at center + small Gaussian jitter — k-NN over the result has
    non-trivial structure (neighbors cluster, cosine and L2 disagree
    near cluster borders) instead of the uniform-random mush where every
    top-k is noise. Returns ``[len(vids), dim]`` float32."""
    import numpy as np

    vids = np.asarray(vids, dtype=np.int64).ravel()
    clusters = max(int(clusters), 1)
    rng = np.random.default_rng(int(seed))
    centers = rng.standard_normal((clusters, int(dim))).astype(np.float32)
    centers /= np.maximum(
        np.linalg.norm(centers, axis=1, keepdims=True), 1e-12)
    assign = (vids % np.int64(clusters)).astype(np.int64)
    # per-vertex jitter seeded by the vertex id, not array position:
    # the embedding of vid V is identical no matter which batch, order,
    # or subset it is generated in
    jitter = np.empty((len(vids), int(dim)), dtype=np.float32)
    for i, v in enumerate(vids):
        jr = np.random.default_rng(int(seed) * 1_000_003 + int(v))
        jitter[i] = jr.standard_normal(int(dim)).astype(np.float32)
    return centers[assign] + 0.15 * jitter


def write_vectors(dst_dir: str, n_normal: int, dim: int,
                  seed: int = 0, clusters: int = 16) -> dict:
    """Emit ``vectors.npz`` (vids + [n, dim] float32 vecs) covering every
    normal vertex the converter assigned — the dataset-side half of the
    vector plane (``upsert_batch_into`` loads it at boot)."""
    import numpy as np

    from wukong_tpu.types import NORMAL_ID_START

    vids = np.arange(NORMAL_ID_START, NORMAL_ID_START + int(n_normal),
                     dtype=np.int64)
    vecs = make_vectors(vids, dim, seed=seed, clusters=clusters)
    np.savez(os.path.join(dst_dir, "vectors.npz"), vids=vids, vecs=vecs)
    return {"vector_dim": int(dim), "vector_count": int(len(vids)),
            "vector_clusters": int(clusters), "vector_seed": int(seed)}


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m wukong_tpu.loader.datagen",
        description="NT -> ID-Triples converter")
    ap.add_argument("src_dir")
    ap.add_argument("dst_dir")
    ap.add_argument("--timestamps", type=int, default=0, metavar="N",
                    help="emit 4-column s p o ts rows with shuffled "
                         "timestamps over N epochs (streaming replay)")
    ap.add_argument("--ts-seed", type=int, default=0,
                    help="seed for the timestamp shuffle")
    ap.add_argument("--vectors", type=int, default=0, metavar="DIM",
                    help="also emit vectors.npz: deterministic clustered "
                         "DIM-dim embeddings for every normal vertex "
                         "(the hybrid graph+vector plane's dataset half)")
    ap.add_argument("--vec-seed", type=int, default=0,
                    help="seed for the embedding clusters/jitter")
    ns = ap.parse_args(argv if argv is not None else sys.argv[1:])
    meta = convert_dir(ns.src_dir, ns.dst_dir, timestamps=ns.timestamps,
                       ts_seed=ns.ts_seed)
    if ns.vectors > 0:
        meta.update(write_vectors(ns.dst_dir, meta["normal_vertex"],
                                  ns.vectors, seed=ns.vec_seed))
    print(json.dumps(meta))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
