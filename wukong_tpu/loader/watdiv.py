"""WatDiv data model, synthesized in id space, and its 20 basic-testing templates.

WatDiv is the Waterloo SPARQL Diversity Test Suite (Aluc, Hartig, Ozsu,
Daudjee, ISWC 2014; dsg.uwaterloo.ca/watdiv). Like loader/lubm.py this makes
the dataset directly as ``[M, 3]`` id triples with a formulaic layout and a
virtual string backend; ``scale`` is WatDiv's scale factor (scale 1 is about
109 k triples, scale 1000 "WatDiv 100M").

What is the source's shape and is kept: the classes and how they scale
(``SCALING`` per unit of scale factor, ``FIXED`` not at all), which class
each predicate links to which, optional properties present with a
probability, multi-valued ones with a drawn count, and Zipfian targets for
the relations whose in-degrees the suite is named for (``likes``,
``follows``, ``friendOf``, ``hasReview``, ``purchaseFor``, ``gr:includes``,
``og:tag``, ``hasGenre``). What is this file's own and so listed under
``assumed`` in ``benchmark/configs/watdiv100m-1chip.json``: every presence
probability, count range and Zipf exponent of ``SPEC``, the pool sizes of
the literals, and properties given to every product category alike.

Literals are vertices, as in upstream's id-format data: one of its own for
each subject where the value is near-unique (``UNIQUE``: captions, titles,
serial numbers, e-mail), drawn from a shared pool where it is of low
cardinality (``POOLS``: dates, prices, ratings, names).

Two departures serve the benchmark's traffic generator, which draws a
placeholder from the subjects of ``(?, rdf:type, <class>)``: every instance
of a class in ``MEMBER_CLASSES`` carries one such class-membership triple;
and in S3 and S5, whose placeholder is itself a class (the object of
``rdf:type``), the 15 product categories are drawn as the instances of a
class of classes, ``wsdbm:ProductCategory`` (15 triples whose subject is an
index id).
"""

from __future__ import annotations

import bisect
import json
import os
import re
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from wukong_tpu.types import NORMAL_ID_START, PREDICATE_ID, TYPE_ID

# the loader of the benchmark refuses a program whose generator is not this
# data model (the sketch before it had no such marker)
SCHEMA = "watdiv-wsdbm-1"

WSDBM = "http://db.uwaterloo.ca/~galuc/wsdbm/"
RDF_TYPE_STR = "<http://www.w3.org/1999/02/22-rdf-syntax-ns#type>"
NS = {
    "wsdbm": WSDBM,
    "sorg": "http://schema.org/",
    "gr": "http://purl.org/goodrelations/",
    "rev": "http://purl.org/stuff/rev#",
    "og": "http://ogp.me/ns#",
    "mo": "http://purl.org/ontology/mo/",
    "foaf": "http://xmlns.com/foaf/",
    "dc": "http://purl.org/dc/terms/",
    "gn": "http://www.geonames.org/ontology#",
    "rdf": "http://www.w3.org/1999/02/22-rdf-syntax-ns#",
}

# instances per unit of scale factor, and the classes that do not scale
SCALING = {"User": 1000, "Product": 250, "Review": 1500, "Offer": 900,
           "Purchase": 1500, "Retailer": 12, "Website": 50}
FIXED = {"City": 240, "Country": 25, "Topic": 250, "SubGenre": 145,
         "Language": 25, "AgeGroup": 9, "Gender": 2}
# objects of rdf:type: index ids, not vertices
ROLES, CATEGORIES, GENRES = 3, 15, 21
# classes whose instances carry a class-membership triple (see the docstring)
MEMBER_CLASSES = ("User", "Website", "City", "Topic", "Retailer", "Country",
                  "AgeGroup", "SubGenre")

# literal pools (name -> size) and the literals with one vertex a subject
POOLS = {"date": 3650, "price": 10000, "rating": 10, "number": 1000,
         "hits": 100000, "givenName": 5000, "familyName": 20000,
         "jobTitle": 2000, "word": 5000}

U, Z = "uniform", "zipf"
# (predicate, subject class, presence probability, (lo, hi) values a subject,
#  target: a class, a pool, or None for a literal of the subject's own,
#  draw of the target, Zipf exponent). ``inverse`` rows draw the SUBJECT for
#  each object instead (a purchase has one buyer, a review one product, an
#  offer one retailer).
SPEC = [
    # --- users ---------------------------------------------------------
    ("wsdbm:userId", "User", 1.0, (1, 1), None, U, 0),
    ("foaf:givenName", "User", 0.7, (1, 1), "givenName", U, 0),
    ("foaf:familyName", "User", 0.7, (1, 1), "familyName", U, 0),
    ("sorg:email", "User", 0.9, (1, 1), None, U, 0),
    ("sorg:telephone", "User", 0.2, (1, 1), None, U, 0),
    ("sorg:birthDate", "User", 0.2, (1, 1), "date", U, 0),
    ("sorg:jobTitle", "User", 0.05, (1, 1), "jobTitle", U, 0),
    ("foaf:age", "User", 0.5, (1, 1), "AgeGroup", U, 0),
    ("wsdbm:gender", "User", 0.6, (1, 1), "Gender", U, 0),
    ("sorg:nationality", "User", 0.2, (1, 1), "Country", Z, 0.5),
    ("dc:Location", "User", 0.4, (1, 1), "City", U, 0),
    ("foaf:homepage", "User", 0.05, (1, 1), "Website", U, 0),
    ("wsdbm:follows", "User", 0.8, (1, 81), "User", Z, 0.5),
    ("wsdbm:friendOf", "User", 0.4, (1, 223), "User", Z, 0.5),
    ("wsdbm:likes", "User", 0.25, (1, 8), "Product", Z, 0.8),
    ("wsdbm:subscribes", "User", 0.2, (1, 13), "Website", Z, 0.6),
    # --- purchases -----------------------------------------------------
    ("wsdbm:makesPurchase", "Purchase", 1.0, (1, 1), "User", Z, 0.5, "inverse"),
    ("wsdbm:purchaseFor", "Purchase", 1.0, (1, 1), "Product", Z, 0.8),
    ("wsdbm:purchaseDate", "Purchase", 0.9, (1, 1), "date", U, 0),
    ("wsdbm:price", "Purchase", 0.5, (1, 1), "price", U, 0),
    # --- reviews -------------------------------------------------------
    ("rev:hasReview", "Review", 1.0, (1, 1), "Product", Z, 0.8, "inverse"),
    ("rev:reviewer", "Review", 1.0, (1, 1), "User", Z, 0.5),
    ("rev:title", "Review", 0.3, (1, 1), None, U, 0),
    ("rev:text", "Review", 0.6, (1, 1), None, U, 0),
    ("rev:rating", "Review", 1.0, (1, 1), "rating", U, 0),
    ("rev:totalVotes", "Review", 0.1, (1, 1), "number", U, 0),
    # --- offers --------------------------------------------------------
    ("gr:offers", "Offer", 1.0, (1, 1), "Retailer", Z, 0.4, "inverse"),
    ("gr:includes", "Offer", 1.0, (1, 1), "Product", Z, 0.8),
    ("gr:price", "Offer", 1.0, (1, 1), "price", U, 0),
    ("gr:serialNumber", "Offer", 1.0, (1, 1), None, U, 0),
    ("gr:validFrom", "Offer", 0.5, (1, 1), "date", U, 0),
    ("gr:validThrough", "Offer", 0.5, (1, 1), "date", U, 0),
    ("sorg:eligibleQuantity", "Offer", 1.0, (1, 1), "number", U, 0),
    ("sorg:eligibleRegion", "Offer", 0.5, (1, 9), "Country", U, 0),
    ("sorg:priceValidUntil", "Offer", 0.2, (1, 1), "date", U, 0),
    # --- products ------------------------------------------------------
    ("og:title", "Product", 1.0, (1, 1), None, U, 0),
    ("sorg:caption", "Product", 0.3, (1, 1), None, U, 0),
    ("sorg:description", "Product", 0.6, (1, 1), None, U, 0),
    ("sorg:keywords", "Product", 0.3, (1, 1), None, U, 0),
    ("sorg:text", "Product", 0.3, (1, 1), None, U, 0),
    ("sorg:contentRating", "Product", 0.3, (1, 1), "rating", U, 0),
    ("sorg:contentSize", "Product", 0.1, (1, 1), "number", U, 0),
    ("sorg:language", "Product", 0.3, (1, 1), "Language", Z, 1.0),
    ("wsdbm:hasGenre", "Product", 1.0, (1, 3), "SubGenre", Z, 0.7),
    ("og:tag", "Product", 0.6, (1, 9), "Topic", Z, 0.7),
    ("foaf:homepage", "Product", 0.25, (1, 1), "Website", U, 0),
    ("sorg:publisher", "Product", 0.05, (1, 1), "User", Z, 0.5),
    ("sorg:trailer", "Product", 0.02, (1, 1), None, U, 0),
    ("mo:artist", "Product", 0.05, (1, 1), "User", Z, 0.5),
    ("mo:conductor", "Product", 0.01, (1, 1), "User", Z, 0.5),
    ("sorg:actor", "Product", 0.05, (1, 5), "User", Z, 0.5),
    ("sorg:author", "Product", 0.05, (1, 1), "User", Z, 0.5),
    ("sorg:editor", "Product", 0.02, (1, 1), "User", Z, 0.5),
    ("sorg:director", "Product", 0.02, (1, 1), "User", Z, 0.5),
    ("sorg:producer", "Product", 0.02, (1, 1), "User", Z, 0.5),
    ("mo:producer", "Product", 0.01, (1, 1), "User", Z, 0.5),
    ("mo:performer", "Product", 0.01, (1, 1), "User", Z, 0.5),
    ("mo:performed_in", "Product", 0.01, (1, 1), "City", U, 0),
    ("sorg:award", "Product", 0.02, (1, 1), "word", U, 0),
    ("sorg:aggregateRating", "Product", 0.1, (1, 1), "rating", U, 0),
    ("sorg:datePublished", "Product", 0.1, (1, 1), "date", U, 0),
    ("sorg:duration", "Product", 0.05, (1, 1), "number", U, 0),
    ("sorg:isbn", "Product", 0.05, (1, 1), None, U, 0),
    ("sorg:bookEdition", "Product", 0.05, (1, 1), "number", U, 0),
    ("sorg:numberOfPages", "Product", 0.05, (1, 1), "number", U, 0),
    ("sorg:printPage", "Product", 0.01, (1, 1), "number", U, 0),
    ("sorg:printSection", "Product", 0.01, (1, 1), "word", U, 0),
    ("sorg:printColumn", "Product", 0.01, (1, 1), "number", U, 0),
    ("sorg:printEdition", "Product", 0.01, (1, 1), "number", U, 0),
    ("sorg:expires", "Product", 0.01, (1, 1), "date", U, 0),
    ("mo:record_number", "Product", 0.02, (1, 1), "number", U, 0),
    ("mo:release", "Product", 0.02, (1, 1), "word", U, 0),
    ("mo:movement", "Product", 0.01, (1, 1), "word", U, 0),
    ("mo:opus", "Product", 0.01, (1, 1), "word", U, 0),
    # --- retailers, websites, cities, sub-genres -------------------------
    ("sorg:legalName", "Retailer", 1.0, (1, 1), None, U, 0),
    ("gr:name", "Retailer", 0.8, (1, 1), None, U, 0),
    ("gr:description", "Retailer", 0.5, (1, 1), None, U, 0),
    ("sorg:contactPoint", "Retailer", 0.3, (1, 1), "User", U, 0),
    ("sorg:faxNumber", "Retailer", 0.2, (1, 1), None, U, 0),
    ("sorg:openingHours", "Retailer", 0.5, (1, 1), "word", U, 0),
    ("sorg:paymentAccepted", "Retailer", 0.8, (1, 1), "word", U, 0),
    ("sorg:employee", "Retailer", 0.5, (1, 10), "User", U, 0),
    ("sorg:url", "Website", 1.0, (1, 1), None, U, 0),
    ("wsdbm:hits", "Website", 1.0, (1, 1), "hits", U, 0),
    ("sorg:language", "Website", 0.5, (1, 1), "Language", Z, 1.0),
    ("gn:parentCountry", "City", 1.0, (1, 1), "Country", U, 0),
    ("og:tag", "SubGenre", 1.0, (1, 3), "Topic", Z, 0.7),
]


def _iri(qname: str) -> str:
    pfx, local = qname.split(":")
    return f"<{NS[pfx]}{local}>"


# predicates in first-appearance order (``sorg:language``, ``og:tag`` and
# ``foaf:homepage`` serve two subject classes each)
PRED_NAMES = list(dict.fromkeys(row[0] for row in SPEC))
P = {q: 2 + i for i, q in enumerate(PRED_NAMES)}
# the local names the cyclic bench patterns use (loader/datagen.py)
P.update({q.split(":")[1]: i for q, i in list(P.items())
          if q.startswith("wsdbm:")})
TYPE_NAMES = ([f"Role{k}" for k in range(ROLES)]
              + [f"ProductCategory{k}" for k in range(CATEGORIES)]
              + [f"Genre{k}" for k in range(GENRES)]
              + list(MEMBER_CLASSES) + ["ProductCategory"])
T = {name: 2 + len(PRED_NAMES) + i for i, name in enumerate(TYPE_NAMES)}
UNIQUE = [(row[0], row[1]) for row in SPEC if row[4] is None]


def index_strings():
    rows = [("__PREDICATE__", PREDICATE_ID), (RDF_TYPE_STR, TYPE_ID)]
    rows += [(_iri(q), P[q]) for q in PRED_NAMES]
    rows += [(f"<{WSDBM}{name}>", T[name]) for name in TYPE_NAMES]
    return rows


def _lit_name(qname: str, cls: str) -> str:
    return f"{qname.split(':')[1]}Of{cls}"


class WatdivLayout:
    """Id ranges: entity classes, then literal pools, then the per-subject
    literals, each ``[base, base + n)`` from ``NORMAL_ID_START`` up."""

    def __init__(self, scale: int, seed: int = 0):
        self.scale, self.seed = int(scale), int(seed)
        self.n = {c: k * self.scale for c, k in SCALING.items()}
        self.n.update(FIXED)
        self.literal = set(POOLS)
        self.n.update(POOLS)
        for q, cls in UNIQUE:
            self.n[_lit_name(q, cls)] = self.n[cls]
            self.literal.add(_lit_name(q, cls))
        self.base, cur = {}, NORMAL_ID_START
        for name, n in self.n.items():
            self.base[name] = cur
            cur += n
        self.id_end = cur
        self._names = list(self.base)
        self._bases = [self.base[c] for c in self._names]

    def ids(self, name: str) -> np.ndarray:
        return self.base[name] + np.arange(self.n[name], dtype=np.int64)

    def class_of(self, vid: int):
        """-> (class or literal name, index within it), or None."""
        if not NORMAL_ID_START <= vid < self.id_end:
            return None
        name = self._names[bisect.bisect_right(self._bases, vid) - 1]
        return name, vid - self.base[name]


def _zipf(rng, n: int, size: int, s: float) -> np.ndarray:
    """``size`` ranks in [0, n) with P(rank k) about (k + 1) ** -s: the
    inverse of the continuous distribution's CDF, so one pow a draw."""
    u = rng.random(size)
    if abs(s - 1.0) < 1e-9:
        x = np.exp(u * np.log(n + 1.0))
    else:
        x = ((n + 1.0) ** (1.0 - s) - 1.0) * u + 1.0
        np.power(x, 1.0 / (1.0 - s), out=x)
    return np.minimum(x.astype(np.int64) - 1, n - 1)


def _scatter_ranks(rank: np.ndarray, n: int, k: int) -> np.ndarray:
    """Rank -> instance by a bijection of row ``k``'s own, so that the most
    purchased product is not also the most offered and the most reviewed
    (each relation is drawn independently, as the source draws them).
    ``wsdbm:likes`` alone keeps rank = index: L2 names ``wsdbm:Product0``."""
    a = 2_147_483_629 + 2 * k  # odd, and stepped until coprime to n
    while np.gcd(a, n) != 1:
        a += 2
    return (rank * a + 7919 * (k + 1)) % n


def _row_edges(row, k: int, lay: WatdivLayout):
    """One row of SPEC -> (subject ids, object ids), duplicates removed."""
    q, cls, prob, (lo, hi), target, draw, s = row[:7]
    rng = np.random.Generator(np.random.PCG64([lay.seed, 7, k]))
    n = lay.n[cls]
    have = np.flatnonzero(rng.random(n) < prob) if prob < 1.0 \
        else np.arange(n, dtype=np.int64)
    if target is None:  # the subject's own literal
        return lay.base[cls] + have, lay.base[_lit_name(q, cls)] + have
    if hi > 1:
        have = np.repeat(have, rng.integers(lo, hi + 1, len(have)))
    nt = lay.n[target]
    if draw == Z:
        tgt = _zipf(rng, nt, len(have), s)
        if q != "wsdbm:likes":
            tgt = _scatter_ranks(tgt, nt, k)
    else:
        tgt = rng.integers(0, nt, len(have))
    if hi > 1:  # draws with replacement repeat a pair: one packed key each
        key = have * nt + tgt
        key.sort()
        key = key[np.concatenate(([True], key[1:] != key[:-1]))]
        have, tgt = np.divmod(key, nt)
    sub, obj = lay.base[cls] + have, lay.base[target] + tgt
    return (obj, sub) if row[-1] == "inverse" else (sub, obj)


def generate_watdiv(scale: int, seed: int = 0):
    """Returns ([M,3] int64 triples, WatdivLayout). Deterministic in
    ``(scale, seed)``: every row of SPEC draws from a stream of its own, so
    the rows are made side by side on threads."""
    lay = WatdivLayout(scale, seed)
    rng = np.random.Generator(np.random.PCG64([lay.seed, 7]))
    parts = []  # (subjects, predicate id, objects)

    def types(cls, names, probs=None):
        n = lay.n[cls]
        pick = rng.choice(len(names), n, p=probs) if len(names) > 1 \
            else np.zeros(n, dtype=np.int64)
        parts.append((lay.ids(cls), TYPE_ID,
                      np.array([T[x] for x in names], dtype=np.int64)[pick]))

    for cls in MEMBER_CLASSES:
        types(cls, [cls])
    types("User", [f"Role{k}" for k in range(ROLES)], [0.5, 0.3, 0.2])
    w = 1.0 / np.sqrt(np.arange(1, CATEGORIES + 1))
    types("Product", [f"ProductCategory{k}" for k in range(CATEGORIES)],
          w / w.sum())
    types("SubGenre", [f"Genre{k}" for k in range(GENRES)])
    # the categories themselves, as the instances S3 and S5 draw from
    cats = np.array([T[f"ProductCategory{k}"] for k in range(CATEGORIES)],
                    dtype=np.int64)
    parts.append((cats, TYPE_ID, np.full(CATEGORIES, T["ProductCategory"])))

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        # the two largest relations first, so the threads end together
        order = sorted(range(len(SPEC)), key=lambda k: -SPEC[k][2]
                       * (SPEC[k][3][0] + SPEC[k][3][1])
                       * lay.n[SPEC[k][1]])
        done = dict(zip(order, pool.map(
            lambda k: _row_edges(SPEC[k], k, lay), order)))
    for k, row in enumerate(SPEC):
        sub, obj = done.pop(k)
        parts.append((sub, P[row[0]], obj))

    m = sum(len(sub) for sub, _p, _o in parts)
    triples = np.empty((m, 3), dtype=np.int64)
    at = 0
    for sub, pid, obj in parts:
        sl = slice(at, at + len(sub))
        triples[sl, 0], triples[sl, 1], triples[sl, 2] = sub, pid, obj
        at += len(sub)
    return triples, lay


_ENTITY = re.compile(rf"<{re.escape(WSDBM)}([A-Za-z]+)(\d+)>")
_LITERAL = re.compile(r'"([A-Za-z_]+)(\d+)"')


class VirtualWatdivStrings:
    """O(1)-memory string<->id mapping for a synthesized WatDiv dataset:
    ``<wsdbm:Product17>`` for an entity, ``"captionOfProduct17"`` or
    ``"date17"`` for a literal vertex."""

    def __init__(self, scale: int, seed: int = 0):
        self.lay = WatdivLayout(scale, seed)
        rows = index_strings()
        self._s2i = {s: i for s, i in rows}
        self._i2s = {i: s for s, i in rows}
        self.pid2type = {}

    def str2id(self, s: str) -> int:
        if s in self._s2i:
            return self._s2i[s]
        m = _ENTITY.fullmatch(s) or _LITERAL.fullmatch(s)
        if m:
            name, k = m.group(1), int(m.group(2))
            if (name in self.lay.literal) == (s[0] == '"') \
                    and k < self.lay.n.get(name, 0):
                return self.lay.base[name] + k
        raise KeyError(s)

    def id2str(self, i: int) -> str:
        if i in self._i2s:
            return self._i2s[i]
        info = self.lay.class_of(int(i))
        if info is None:
            raise KeyError(i)
        name, k = info
        return f'"{name}{k}"' if name in self.lay.literal \
            else f"<{WSDBM}{name}{k}>"

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


# ---------------------------------------------------------------------------
# the basic-testing workload: L1-L5 linear, S1-S7 star, F1-F5 snowflake,
# C1-C3 complex. ``%prefix:Class`` stands where WatDiv has ``%vN%``.
# ---------------------------------------------------------------------------

_BODIES = {
    "L1": "?v0 wsdbm:subscribes %wsdbm:Website . ?v2 sorg:caption ?v3 . "
          "?v0 wsdbm:likes ?v2",
    "L2": "%wsdbm:City gn:parentCountry ?v1 . ?v2 wsdbm:likes wsdbm:Product0 ."
          " ?v2 sorg:nationality ?v1",
    "L3": "?v0 wsdbm:likes ?v1 . ?v0 wsdbm:subscribes %wsdbm:Website",
    "L4": "?v0 og:tag %wsdbm:Topic . ?v0 sorg:caption ?v2",
    "L5": "?v0 sorg:jobTitle ?v1 . %wsdbm:City gn:parentCountry ?v3 . "
          "?v0 sorg:nationality ?v3",
    "S1": "?v0 gr:includes ?v1 . %wsdbm:Retailer gr:offers ?v0 . "
          "?v0 gr:price ?v3 . ?v0 gr:serialNumber ?v4 . "
          "?v0 gr:validFrom ?v5 . ?v0 gr:validThrough ?v6 . "
          "?v0 sorg:eligibleQuantity ?v7 . ?v0 sorg:eligibleRegion ?v8 . "
          "?v0 sorg:priceValidUntil ?v9",
    "S2": "?v0 dc:Location ?v1 . ?v0 sorg:nationality %wsdbm:Country . "
          "?v0 wsdbm:gender ?v3 . ?v0 rdf:type wsdbm:Role2",
    "S3": "?v0 rdf:type %wsdbm:ProductCategory . ?v0 sorg:caption ?v2 . "
          "?v0 wsdbm:hasGenre ?v3 . ?v0 sorg:publisher ?v4",
    "S4": "?v0 foaf:age %wsdbm:AgeGroup . ?v0 foaf:familyName ?v2 . "
          "?v3 mo:artist ?v0 . ?v0 sorg:nationality wsdbm:Country1",
    "S5": "?v0 rdf:type %wsdbm:ProductCategory . ?v0 sorg:description ?v2 . "
          "?v0 sorg:keywords ?v3 . ?v0 sorg:language wsdbm:Language0",
    "S6": "?v0 mo:conductor ?v1 . ?v0 rdf:type ?v2 . "
          "?v0 wsdbm:hasGenre %wsdbm:SubGenre",
    "S7": "?v0 rdf:type ?v1 . ?v0 sorg:text ?v2 . %wsdbm:User wsdbm:likes ?v0",
    # the Topic pattern last (the source has it first): the benchmark's plain
    # reference takes its second pattern in the order of the text, and with
    # the category's list the shorter one it paired every product of the
    # category with everything the topic tags (1.2 x 10^9 rows, 34 GB)
    "F1": "?v0 rdf:type ?v2 . ?v3 sorg:trailer ?v4 . ?v3 sorg:keywords ?v5 . "
          "?v3 wsdbm:hasGenre ?v0 . ?v3 rdf:type wsdbm:ProductCategory2 . "
          "?v0 og:tag %wsdbm:Topic",
    "F2": "?v0 foaf:homepage ?v1 . ?v0 og:title ?v2 . ?v0 rdf:type ?v3 . "
          "?v0 sorg:caption ?v4 . ?v0 sorg:description ?v5 . "
          "?v1 sorg:url ?v6 . ?v1 wsdbm:hits ?v7 . "
          "?v0 wsdbm:hasGenre %wsdbm:SubGenre",
    "F3": "?v0 sorg:contentRating ?v1 . ?v0 sorg:contentSize ?v2 . "
          "?v0 wsdbm:hasGenre %wsdbm:SubGenre . ?v4 wsdbm:makesPurchase ?v5 . "
          "?v5 wsdbm:purchaseDate ?v6 . ?v5 wsdbm:purchaseFor ?v0",
    "F4": "?v0 foaf:homepage ?v1 . ?v2 gr:includes ?v0 . "
          "?v0 og:tag %wsdbm:Topic . ?v0 sorg:description ?v4 . "
          "?v0 sorg:contentSize ?v8 . ?v1 sorg:url ?v5 . ?v1 wsdbm:hits ?v6 . "
          "?v1 sorg:language wsdbm:Language0 . ?v7 wsdbm:likes ?v0",
    "F5": "?v0 gr:includes ?v1 . %wsdbm:Retailer gr:offers ?v0 . "
          "?v0 gr:price ?v3 . ?v0 gr:validThrough ?v4 . ?v1 og:title ?v5 . "
          "?v1 rdf:type ?v6",
    "C1": "?v0 sorg:caption ?v1 . ?v0 sorg:text ?v2 . "
          "?v0 sorg:contentRating ?v3 . ?v0 rev:hasReview ?v4 . "
          "?v4 rev:title ?v5 . ?v4 rev:reviewer ?v6 . ?v7 sorg:actor ?v6 . "
          "?v7 sorg:language ?v8",
    "C2": "?v0 sorg:legalName ?v1 . ?v0 gr:offers ?v2 . "
          "?v2 sorg:eligibleRegion wsdbm:Country5 . ?v2 gr:includes ?v3 . "
          "?v4 sorg:jobTitle ?v5 . ?v4 foaf:homepage ?v6 . "
          "?v4 wsdbm:makesPurchase ?v7 . ?v7 wsdbm:purchaseFor ?v3 . "
          "?v3 rev:hasReview ?v8 . ?v8 rev:totalVotes ?v9",
    "C3": "?v0 wsdbm:likes ?v1 . ?v0 wsdbm:friendOf ?v2 . "
          "?v0 dc:Location ?v3 . ?v0 foaf:age ?v4 . ?v0 wsdbm:gender ?v5 . "
          "?v0 foaf:givenName ?v6",
}


def _template(body: str) -> str:
    used = dict.fromkeys(re.findall(r"%?(\w+):\w", body))
    variables = dict.fromkeys(re.findall(r"\?v\d+", body))
    head = "".join(f"PREFIX {p}: <{NS[p]}>\n" for p in used)
    pats = "".join(f"\t{p.strip()} .\n" for p in body.split(" . "))
    return f"{head}\nSELECT {' '.join(variables)} WHERE {{\n{pats}}}\n"


TEMPLATES = {name: _template(body) for name, body in _BODIES.items()}


def write_dataset(outdir: str, scale: int, seed: int = 0,
                  chunk_rows: int | None = None) -> dict:
    """Write an id-format WatDiv dataset. `chunk_rows` splits the triple
    array over multiple ``id_triples_<k>.npy`` files; the reader
    (loader/base.py) preallocates and fills per chunk, so its transient
    peak is one chunk above the dataset (the generator itself is a
    vectorized in-RAM build either way)."""
    os.makedirs(outdir, exist_ok=True)
    triples, lay = generate_watdiv(scale, seed)
    if chunk_rows:
        for k in range(0, len(triples), chunk_rows):
            np.save(os.path.join(outdir, f"id_triples_{k // chunk_rows:05d}.npy"),
                    triples[k:k + chunk_rows])
    else:
        np.save(os.path.join(outdir, "id_triples.npy"), triples)
    with open(os.path.join(outdir, "str_index"), "w") as f:
        for s, i in index_strings():
            f.write(f"{s}\t{i}\n")
    meta = {"generator": "watdiv", "scale": scale, "seed": seed,
            "num_triples": int(len(triples))}
    with open(os.path.join(outdir, "str_normal_virtual"), "w") as f:
        json.dump(meta, f)
    qdir = os.path.join(outdir, "queries")
    os.makedirs(qdir, exist_ok=True)
    for name, text in TEMPLATES.items():
        with open(os.path.join(qdir, name), "w") as f:
            f.write(text)
    return meta
