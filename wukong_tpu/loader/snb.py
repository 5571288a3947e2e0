"""LDBC SNB's social graph as LSQB reads it, synthesized in id space.

LSQB (Mhedhbi, Lissandrini, Kuiper, Waudby, Szarnyas: *LSQB: a large-scale
subgraph query benchmark*, GRADES-NDA 2021; github.com/ldbc/lsqb) runs nine
global subgraph patterns over the LDBC Social Network Benchmark graph,
Datagen's output without attributes. Like loader/watdiv.py this makes the
dataset directly as ``[M, 3]`` id triples with a formulaic id layout and a
virtual string backend; ``scale_factor`` is Datagen's (1 is 11,000 persons,
10 is 73,000; a fraction makes the small graphs of the tests).

Kept from the source: the eleven classes and the edge types with their
domains and ranges (``EDGES``), every node typed, a message typed both
``Message`` and ``Post`` or ``Comment``, Datagen's person counts and fixed
dictionaries (``FIXED``), ``knows`` stored in both directions, heavy-tailed
in degree, growing with the scale (Datagen's mean degree
``n ** (0.512 - 0.028 * log10(n))``) and drawn along the source's three
dimensions, place of study, interest and random, 45 / 45 / 10 %: a person's
friends are near it in the order of that dimension, so friends share a
country far more often than chance and the same-country triangle of LSQB's
q3 is not empty; a message tree (Forum -> Post <- Comment <- Comment) whose
comments are written mostly by friends and forum members of the post's
author, which is what LSQB's q2 counts.

Every count a person, exponent and probability is this file's own
(``ASSUMED``) and is listed by name under ``assumed`` in
``benchmark/configs/lsqb-1chip.json``; they were set so that the totals at
scale factor 10 come within a few percent of the source's (about 30 M nodes
and 177 M edges, 3.9 M directed ``knows``, 7.4 M posts, 21.9 M comments).
"""

from __future__ import annotations

import bisect
import json
import math
import os
import re

import numpy as np

from wukong_tpu.types import NORMAL_ID_START, PREDICATE_ID, TYPE_ID

# the loader of the benchmark refuses a program whose generator is not this
# data model
SCHEMA = "ldbc-snb-lsqb-1"

SNB = "http://www.ldbc.eu/ldbc_socialnet/1.0/vocabulary/"
DATA = "http://www.ldbc.eu/ldbc_socialnet/1.0/data/"
RDF_TYPE_STR = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"
NS = {"snb": SNB, "rdf": "http://www.w3.org/1999/02/22-rdf-syntax-ns#"}

# Datagen's person counts by scale factor, and its fixed dictionaries
PERSONS = {0.1: 1_700, 0.3: 3_500, 1: 11_000, 3: 27_000, 10: 73_000,
           30: 182_000, 100: 499_000}
FIXED = {"City": 1_343, "Country": 111, "Continent": 6, "University": 6_380,
         "Company": 1_575, "TagClass": 71, "Tag": 16_080}
# id ranges in this order: the classes the joins key by come first and the
# messages last, so a table keyed by person or place has a small id bound
CLASSES = ("Person", "City", "Country", "Continent", "University", "Company",
           "TagClass", "Tag", "Forum", "Post", "Comment")
TYPE_NAMES = CLASSES + ("Message",)
# predicate -> ((domain classes), (range classes))
MESSAGE = ("Post", "Comment")
EDGES = {
    "knows": (("Person",), ("Person",)),
    "isLocatedIn": (("Person",) + MESSAGE, ("City", "Country")),
    "isPartOf": (("City", "Country"), ("Country", "Continent")),
    "hasCreator": (MESSAGE, ("Person",)),
    "replyOf": (("Comment",), MESSAGE),
    "containerOf": (("Forum",), ("Post",)),
    "hasMember": (("Forum",), ("Person",)),
    "hasModerator": (("Forum",), ("Person",)),
    "hasTag": (MESSAGE + ("Forum",), ("Tag",)),
    "hasInterest": (("Person",), ("Tag",)),
    "likes": (("Person",), MESSAGE),
    "hasType": (("Tag",), ("TagClass",)),
    "isSubclassOf": (("TagClass",), ("TagClass",)),
    "studyAt": (("Person",), ("University",)),
    "workAt": (("Person",), ("Company",)),
}
P = {name: 2 + i for i, name in enumerate(EDGES)}
T = {name: 2 + len(EDGES) + i for i, name in enumerate(TYPE_NAMES)}

# what the source's generator would have set and this file sets instead
ASSUMED = {
    "degree_factor": 0.8,  # of Datagen's mean degree: pairs it fails to match
    "degree_sigma": 0.8,  # log-normal spread of a person's target degree
    "degree_cap_share": 0.05,  # a degree is at most this share of the persons
    "knows_shares": (0.45, 0.45, 0.10),  # study place, interest, random
    # mean distance of a partner in the first two orders: this times the
    # mean picks a person times the mean of that and the person's own picks
    # (a window that widens with the degree as Datagen's does; by the
    # square, so that the triangles grow with the edges and not faster)
    "knows_window": (0.104, 0.25),
    "knows_boost": 1.10,  # picks lost as duplicates, made up
    "country_zipf": 1.0,  # persons over countries
    "study_share": 0.8, "study_abroad": 0.1,
    "work_mean": 2.2, "interests_mean": 23.0, "tag_zipf": 0.9,
    "forums_per_person": 8.15,  # a wall each, the rest groups
    "group_members_mean": 36.0, "group_members_friends": 0.7,
    "posts_per_person": 101.4, "posts_on_walls": 0.55,
    "post_weight_sigma": 1.0,  # log-normal weight of a forum among its kind
    "comments_per_post": 2.96, "posts_with_comments": 0.6,
    "thread_weight_sigma": 1.0,
    "reply_to_post": 0.47,  # of a thread's later comments; its first always
    "comment_by_friend": 0.80, "comment_by_member": 0.15,
    "message_at_home": 0.9,  # a message's country is its creator's
    "post_tags_mean": 0.7, "comment_tags_mean": 1.3, "forum_tags_mean": 3.4,
    "likes_per_post": 0.9, "likes_per_comment": 0.9, "like_by_friend": 0.8,
}


def persons_at(scale_factor: float) -> int:
    """Datagen's person count; between and under its scale factors the
    power law its table follows (a fraction is the tests' small graph)."""
    for sf, n in PERSONS.items():
        if abs(sf - scale_factor) < 1e-9:
            return n
    return max(int(round(11_000 * float(scale_factor) ** 0.82)), 60)


def mean_degree(persons: int) -> float:
    """Datagen's mean ``knows`` degree for a graph of ``persons``, less the
    pairs its windows fail to match (``degree_factor``)."""
    n = float(persons)
    return ASSUMED["degree_factor"] * n ** (0.512 - 0.028 * math.log10(n))


def index_strings():
    rows = [("__PREDICATE__", PREDICATE_ID), (RDF_TYPE_STR, TYPE_ID)]
    rows += [(f"<{SNB}{name}>", i) for name, i in P.items()]
    rows += [(f"<{SNB}{name}>", i) for name, i in T.items()]
    return rows


class SnbLayout:
    """Id ranges ``[base, base + n)`` of the classes, from
    ``NORMAL_ID_START`` up, in the order of ``CLASSES``."""

    def __init__(self, scale_factor: float, seed: int = 0):
        self.scale_factor, self.seed = scale_factor, int(seed)
        a = ASSUMED
        persons = persons_at(scale_factor)
        posts = int(round(a["posts_per_person"] * persons))
        self.n = {"Person": persons, **FIXED,
                  "Forum": int(round(a["forums_per_person"] * persons)),
                  "Post": posts,
                  "Comment": int(round(a["comments_per_post"] * posts))}
        self.base, cur = {}, NORMAL_ID_START
        for name in CLASSES:
            self.base[name] = cur
            cur += self.n[name]
        self.id_end = cur
        self._bases = [self.base[c] for c in CLASSES]

    def ids(self, name: str) -> np.ndarray:
        return self.base[name] + np.arange(self.n[name], dtype=np.int64)

    def class_of(self, vid: int):
        """-> (class, index within it), or None."""
        if not NORMAL_ID_START <= vid < self.id_end:
            return None
        name = CLASSES[bisect.bisect_right(self._bases, vid) - 1]
        return name, vid - self.base[name]


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed), 34, *stream]))


def _zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def _draw(rng, cum: np.ndarray, size: int) -> np.ndarray:
    """``size`` indexes drawn by the cumulated weights ``cum``."""
    return np.minimum(np.searchsorted(cum, rng.random(size) * cum[-1],
                                      side="right"), len(cum) - 1)


def _allot(total: int, weights: np.ndarray) -> np.ndarray:
    """``total`` things over ``len(weights)`` owners, one each at least and
    the rest by weight (largest remainders)."""
    k = len(weights)
    share = weights / weights.sum() * (total - k)
    out = 1 + np.floor(share).astype(np.int64)
    rest = total - int(out.sum())
    out[np.argsort(-(share - np.floor(share)), kind="stable")[:rest]] += 1
    return out


def _unique_pairs(a: np.ndarray, b: np.ndarray, nb: int):
    """The distinct pairs ``(a, b)``, ``b`` below ``nb``, sorted by ``a``."""
    key = a.astype(np.int64) * nb + b
    key.sort()
    if len(key):
        key = key[np.concatenate(([True], key[1:] != key[:-1]))]
    return np.divmod(key, nb)


def _csr(src: np.ndarray, n: int):
    """Offsets of the sorted ``src`` over ``[0, n)``."""
    return np.searchsorted(src, np.arange(n + 1))


def _pick(rng, off: np.ndarray, vals: np.ndarray, owner: np.ndarray,
          fallback: np.ndarray) -> np.ndarray:
    """For each ``owner`` one of its ``vals[off[o]:off[o + 1]]`` at random,
    or ``fallback`` where it has none."""
    deg = off[owner + 1] - off[owner]
    at = off[owner] + (rng.random(len(owner)) * deg).astype(np.int64)
    out = fallback.copy()
    has = deg > 0
    out[has] = vals[at[has]]
    return out


def places():
    """The fixed places, from the constants alone: each city's country, each
    country's continent, each university's country, and the countries'
    shares of the persons."""
    w = _zipf_weights(FIXED["Country"], ASSUMED["country_zipf"])
    city_country = np.repeat(np.arange(FIXED["Country"]),
                             _allot(FIXED["City"], np.sqrt(w)))
    univ_country = np.repeat(np.arange(FIXED["Country"]),
                             _allot(FIXED["University"], np.sqrt(w)))
    continent = np.arange(FIXED["Country"]) % FIXED["Continent"]
    return w, city_country, univ_country, continent


def people(lay: SnbLayout):
    """-> dict of per-person arrays: ``country``, ``city``, ``univ`` (-1:
    none), ``interest`` (the main one), and the two orders ``knows`` is
    drawn along."""
    a, n = ASSUMED, lay.n["Person"]
    rng = _rng(lay.seed, 1)
    w, city_country, univ_country, _cont = places()
    country = _draw(rng, np.cumsum(w), n)
    city_off = _csr(city_country, FIXED["Country"])
    ncity = np.diff(city_off)[country]
    city = city_off[country] + (ncity * rng.random(n) ** 2).astype(np.int64)
    # a university of the home country, or for one in ten of any
    ucountry = np.where(rng.random(n) < a["study_abroad"],
                        _draw(rng, np.cumsum(w), n), country)
    univ_off = _csr(univ_country, FIXED["Country"])
    nuniv = np.diff(univ_off)[ucountry]
    univ = univ_off[ucountry] + (nuniv * rng.random(n) ** 2).astype(np.int64)
    univ[rng.random(n) >= a["study_share"]] = -1
    tag_cum = np.cumsum(_zipf_weights(FIXED["Tag"], a["tag_zipf"]))
    interest = _draw(rng, tag_cum, n)
    tie = rng.random(n)
    return {"country": country, "city": city, "univ": univ,
            "interest": interest,
            "by_study": np.lexsort((tie, univ, country)),
            "by_interest": np.lexsort((tie, interest))}


def knows(lay: SnbLayout, ppl: dict):
    """The friendship graph, both directions, sorted by source: (src, dst)
    as person indexes. A person draws half its target degree in each
    dimension as partners a geometric distance away in that dimension's
    order (anyone, in the random one); the other half comes from those
    who draw it."""
    a, n = ASSUMED, lay.n["Person"]
    rng = _rng(lay.seed, 2)
    sigma = a["degree_sigma"]
    target = mean_degree(n) * np.exp(sigma * rng.standard_normal(n)
                                     - sigma * sigma / 2)
    target = np.clip(target, 1.0, max(a["degree_cap_share"] * n, 2.0))
    orders = (ppl["by_study"], ppl["by_interest"], None)
    los, his = [], []
    for dim, (share, order) in enumerate(zip(a["knows_shares"], orders)):
        picks = rng.poisson(target * share / 2 * a["knows_boost"])
        src = np.repeat(np.arange(n), picks)
        if order is None:
            dst = rng.integers(0, n, len(src))
        else:
            pos = np.empty(n, dtype=np.int64)
            pos[order] = np.arange(n)
            window = a["knows_window"][dim] * picks.mean() \
                * (picks.mean() + picks[src]) / 2
            off = np.ceil(rng.exponential(window)).astype(np.int64)
            off *= rng.integers(0, 2, len(src)) * 2 - 1
            dst = order[(pos[src] + off) % n]
        los.append(np.minimum(src, dst))
        his.append(np.maximum(src, dst))
    lo, hi = np.concatenate(los), np.concatenate(his)
    keep = lo != hi
    lo, hi = _unique_pairs(lo[keep], hi[keep], n)
    return _unique_pairs(np.concatenate([lo, hi]), np.concatenate([hi, lo]), n)


def generate_snb(scale_factor: float, seed: int = 0):
    """Returns ([M,3] int64 triples, meta). Deterministic in
    ``(scale_factor, seed)``. ``meta`` holds the layout and the counts a
    predicate and a class."""
    a = ASSUMED
    lay = SnbLayout(scale_factor, seed)
    n = lay.n
    B = lay.base
    parts = []  # (subjects, predicate id, objects), vertex ids

    def edge(name, s_cls, o_cls, s_idx, o_idx):
        parts.append((B[s_cls] + np.asarray(s_idx, dtype=np.int64), P[name],
                      B[o_cls] + np.asarray(o_idx, dtype=np.int64)))

    w_country, city_country, _univ_country, continent = places()
    country_cum = np.cumsum(w_country)
    tag_cum = np.cumsum(_zipf_weights(n["Tag"], a["tag_zipf"]))
    ppl = people(lay)
    np_ = n["Person"]
    everyone = np.arange(np_)

    # ---- places, tags, persons ---------------------------------------
    edge("isPartOf", "City", "Country", np.arange(n["City"]), city_country)
    edge("isPartOf", "Country", "Continent", np.arange(n["Country"]),
         continent)
    rng = _rng(seed, 3)
    edge("hasType", "Tag", "TagClass", np.arange(n["Tag"]),
         _draw(rng, np.cumsum(_zipf_weights(n["TagClass"], 0.5)), n["Tag"]))
    sub = np.arange(1, n["TagClass"])
    edge("isSubclassOf", "TagClass", "TagClass", sub, (sub - 1) // 3)
    edge("isLocatedIn", "Person", "City", everyone, ppl["city"])
    studies = np.flatnonzero(ppl["univ"] >= 0)
    edge("studyAt", "Person", "University", studies, ppl["univ"][studies])
    jobs = np.repeat(everyone, rng.poisson(a["work_mean"], np_))
    edge("workAt", "Person", "Company", *_unique_pairs(
        jobs, rng.integers(0, n["Company"], len(jobs)), n["Company"]))
    fans = np.repeat(everyone, rng.poisson(a["interests_mean"] - 1, np_))
    edge("hasInterest", "Person", "Tag", *_unique_pairs(
        np.concatenate([everyone, fans]),
        np.concatenate([ppl["interest"], _draw(rng, tag_cum, len(fans))]),
        n["Tag"]))

    ksrc, kdst = knows(lay, ppl)
    edge("knows", "Person", "Person", ksrc, kdst)
    koff = _csr(ksrc, np_)
    degree = np.diff(koff)

    # ---- forums: a wall a person, then groups ------------------------
    rng = _rng(seed, 4)
    nf, walls = n["Forum"], min(np_, n["Forum"])
    by_degree = np.cumsum(degree + 1.0)
    moderator = np.concatenate([everyone[:walls],
                                _draw(rng, by_degree, nf - walls)])
    edge("hasModerator", "Forum", "Person", np.arange(nf), moderator)
    # a wall's members are its owner's friends; a group's are drawn among
    # its moderator's friends and among everyone
    seats = np.repeat(np.arange(walls, nf),
                      1 + rng.poisson(a["group_members_mean"], nf - walls))
    anyone = rng.integers(0, np_, len(seats))
    friend = _pick(rng, koff, kdst, moderator[seats], anyone)
    member = np.where(rng.random(len(seats)) < a["group_members_friends"],
                      friend, anyone)
    wall_of = ksrc[ksrc < walls]
    msrc, mdst = _unique_pairs(np.concatenate([wall_of, seats]),
                               np.concatenate([kdst[:len(wall_of)], member]),
                               np_)
    edge("hasMember", "Forum", "Person", msrc, mdst)
    moff = _csr(msrc, nf)
    tagged = np.repeat(np.arange(nf), rng.poisson(a["forum_tags_mean"], nf))
    edge("hasTag", "Forum", "Tag", *_unique_pairs(
        tagged, _draw(rng, tag_cum, len(tagged)), n["Tag"]))

    # ---- posts: in forum order, a wall's by its owner -----------------
    rng = _rng(seed, 5)
    npost = n["Post"]
    on_walls = int(round(a["posts_on_walls"] * npost)) if nf > walls \
        else npost
    weight = np.exp(a["post_weight_sigma"] * rng.standard_normal(nf))
    weight[:walls] *= degree[:walls] + 1.0
    forum = np.sort(np.concatenate([
        _draw(rng, np.cumsum(weight[:walls]), on_walls),
        walls + _draw(rng, np.cumsum(weight[walls:]), npost - on_walls)
        if nf > walls else np.empty(0, dtype=np.int64)]))
    posts = np.arange(npost)
    edge("containerOf", "Forum", "Post", forum, posts)
    post_creator = np.where(
        forum < walls, moderator[forum],
        _pick(rng, moff, mdst, forum, moderator[forum]))
    edge("hasCreator", "Post", "Person", posts, post_creator)

    def located(rng, creator):
        home = ppl["country"][creator]
        away = rng.random(len(creator)) >= a["message_at_home"]
        home[away] = _draw(rng, country_cum, int(away.sum()))
        return home

    edge("isLocatedIn", "Post", "Country", posts,
         located(rng, post_creator))

    # ---- comments: in thread order, mostly by the author's friends ----
    rng = _rng(seed, 6)
    ncom = n["Comment"]
    weight = np.exp(a["thread_weight_sigma"] * rng.standard_normal(npost))
    weight[rng.random(npost) >= a["posts_with_comments"]] = 0.0
    root = np.sort(_draw(rng, np.cumsum(weight), ncom))
    comments = np.arange(ncom)
    first = np.concatenate(([True], root[1:] != root[:-1])) if ncom \
        else np.empty(0, dtype=bool)
    start = np.maximum.accumulate(np.where(first, comments, 0))
    rank = comments - start  # a comment's place in its thread
    to_post = first | (rng.random(ncom) < a["reply_to_post"])
    earlier = start + (rng.random(ncom) * rank).astype(np.int64)
    parts.append((B["Comment"] + comments, P["replyOf"],
                  np.where(to_post, B["Post"] + root,
                           B["Comment"] + earlier)))
    author = post_creator[root]
    anyone = rng.integers(0, np_, ncom)
    u = rng.random(ncom)
    by_friend = _pick(rng, koff, kdst, author, anyone)
    by_member = _pick(rng, moff, mdst, forum[root], anyone)
    com_creator = np.where(
        u < a["comment_by_friend"], by_friend,
        np.where(u < a["comment_by_friend"] + a["comment_by_member"],
                 by_member, anyone))
    del by_friend, by_member, anyone, u, earlier, start, rank
    edge("hasCreator", "Comment", "Person", comments, com_creator)
    edge("isLocatedIn", "Comment", "Country", comments,
         located(rng, com_creator))

    # ---- tags and likes of messages ------------------------------------
    rng = _rng(seed, 7)
    for cls, count, mean, creator in (
            ("Post", npost, a["post_tags_mean"], post_creator),
            ("Comment", ncom, a["comment_tags_mean"], com_creator)):
        tagged = np.repeat(np.arange(count), rng.poisson(mean, count))
        edge("hasTag", cls, "Tag", *_unique_pairs(
            tagged, _draw(rng, tag_cum, len(tagged)), n["Tag"]))
        liked = np.repeat(np.arange(count), rng.poisson(
            a[f"likes_per_{cls.lower()}"], count))
        anyone = rng.integers(0, np_, len(liked))
        fan = np.where(rng.random(len(liked)) < a["like_by_friend"],
                       _pick(rng, koff, kdst, creator[liked], anyone),
                       anyone)
        liked, fan = _unique_pairs(liked, fan, np_)
        edge("likes", "Person", cls, fan, liked)

    # ---- types: every node its class, a message also ``Message`` -------
    for cls in CLASSES:
        parts.append((lay.ids(cls), TYPE_ID,
                      np.full(n[cls], T[cls], dtype=np.int64)))
    for cls in MESSAGE:
        parts.append((lay.ids(cls), TYPE_ID,
                      np.full(n[cls], T["Message"], dtype=np.int64)))

    m = sum(len(s) for s, _p, _o in parts)
    triples = np.empty((m, 3), dtype=np.int64)
    by_pred: dict[str, int] = {}
    names = {i: name for name, i in P.items()}
    names[TYPE_ID] = "rdf:type"
    at = 0
    for k in range(len(parts)):
        s, pid, o = parts[k]
        parts[k] = None  # the part goes as soon as it is copied
        sl = slice(at, at + len(s))
        triples[sl, 0], triples[sl, 1], triples[sl, 2] = s, pid, o
        at += len(s)
        by_pred[names[pid]] = by_pred.get(names[pid], 0) + len(s)
    meta = {"schema": SCHEMA, "scale_factor": scale_factor, "seed": int(seed),
            "layout": lay, "nodes": dict(n), "edges": by_pred,
            "num_nodes": int(sum(n.values())),
            "num_edges": int(m - by_pred["rdf:type"]),
            "num_triples": int(m)}
    return triples, meta


_ENTITY = re.compile(rf"<{re.escape(DATA)}([A-Za-z]+)(\d+)>")


class VirtualSnbStrings:
    """O(1)-memory string<->id mapping for a synthesized SNB dataset:
    ``<.../data/Person17>`` for the 18th person."""

    def __init__(self, scale_factor: float, seed: int = 0):
        self.lay = SnbLayout(scale_factor, seed)
        rows = index_strings()
        self._s2i = {s: i for s, i in rows}
        self._i2s = {i: s for s, i in rows}
        self.pid2type = {}

    def str2id(self, s: str) -> int:
        if s in self._s2i:
            return self._s2i[s]
        m = _ENTITY.fullmatch(s)
        if m and int(m.group(2)) < self.lay.n.get(m.group(1), 0):
            return self.lay.base[m.group(1)] + int(m.group(2))
        raise KeyError(s)

    def id2str(self, i: int) -> str:
        if i in self._i2s:
            return self._i2s[i]
        info = self.lay.class_of(int(i))
        if info is None:
            raise KeyError(i)
        return f"<{DATA}{info[0]}{info[1]}>"

    def exist(self, s: str) -> bool:
        try:
            self.str2id(s)
            return True
        except KeyError:
            return False

    def exist_id(self, i: int) -> bool:
        try:
            self.id2str(i)
            return True
        except KeyError:
            return False


def write_string_tables(outdir: str, scale_factor: float,
                        seed: int = 0) -> dict:
    """The small files of a dataset directory, all a ``StringServer`` needs
    of a synthesized SNB: ``str_index`` and, last, the
    ``str_normal_virtual`` marker, whose meta is returned."""
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, "str_index"), "w") as f:
        for s, i in index_strings():
            f.write(f"{s}\t{i}\n")
    meta = {"generator": "snb", "scale_factor": scale_factor,
            "seed": int(seed)}
    with open(os.path.join(outdir, "str_normal_virtual"), "w") as f:
        json.dump(meta, f)
    return meta


# ---------------------------------------------------------------------------
# LSQB's two cyclic patterns whose matches are few beside the work, as
# row-returning SELECTs; a label is an ``rdf:type`` pattern. The order of the
# patterns is one the benchmark's plain reference can take without forming a
# product: it starts at the rarest class (q2: ``Person``, q3: ``Country``),
# takes a pattern whose both ends are bound before any other, and else the
# next in the order of the text with one end bound, where a class counts as
# a bound end: so a label stands behind the pattern that binds its variable.
# ---------------------------------------------------------------------------

_HEAD = "".join(f"PREFIX {p}: <{iri}>\n" for p, iri in NS.items())
_BODIES = {
    "q2": ("?person1 ?person2 ?comment ?post",
           "?person1 rdf:type snb:Person . ?comment snb:hasCreator ?person1 . "
           "?comment snb:replyOf ?post . ?post snb:hasCreator ?person2 . "
           "?person1 snb:knows ?person2 . ?comment rdf:type snb:Comment . "
           "?post rdf:type snb:Post . ?person2 rdf:type snb:Person"),
    "q3": ("?person1 ?person2 ?person3",
           "?country rdf:type snb:Country . ?city1 snb:isPartOf ?country . "
           "?person1 snb:isLocatedIn ?city1 . ?person1 snb:knows ?person2 . "
           "?person2 snb:isLocatedIn ?city2 . ?city2 snb:isPartOf ?country . "
           "?person2 snb:knows ?person3 . ?person3 snb:knows ?person1 . "
           "?person3 snb:isLocatedIn ?city3 . ?city3 snb:isPartOf ?country . "
           "?person1 rdf:type snb:Person . ?person2 rdf:type snb:Person . "
           "?person3 rdf:type snb:Person . ?city1 rdf:type snb:City . "
           "?city2 rdf:type snb:City . ?city3 rdf:type snb:City"),
}
QUERIES = {
    name: f"{_HEAD}\nSELECT {select} WHERE {{\n"
    + "".join(f"\t{p.strip()} .\n" for p in body.split(" . ")) + "}\n"
    for name, (select, body) in _BODIES.items()}
