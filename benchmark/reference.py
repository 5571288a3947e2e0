"""The plain reference: basic graph patterns over the generated triples.

It imports nothing of ``wukong_tpu`` and takes nothing the program has built:
its inputs are the ``[M, 3]`` id triples the generator made from the seed, the
rows of the dataset's ``str_index`` table (predicate and class IRIs), the ids
the traffic generator drew for the constants it wrote into the texts, and the
query text itself. It has its own reader for the SPARQL subset the benchmark's
query files use (PREFIX, SELECT ?vars WHERE { s p o . ... }), its own join
order (connected patterns first, filters before expansions) and its own
per-predicate sorted arrays, built on first use with NumPy.

The answer of a query is the multiset of rows over the SELECT variables, in
SELECT order; ``sorted_rows`` puts a table into the one order two equal
multisets share.
"""

from __future__ import annotations

import re

import numpy as np

PREFIX = re.compile(r"PREFIX\s+(\w*):\s*<([^>]*)>", re.I)
_SELECT = re.compile(r"SELECT\s+(.*?)\s+WHERE\s*\{(.*)\}", re.I | re.S)
_TERM = re.compile(r"<[^>]*>|\?\w+|%?\w*:\w+")


class ReferenceError_(ValueError):
    """The text is outside the subset the reference reads."""


def parse_bgp(text: str):
    """-> (select vars, [(s, p, o)]) with terms ``?var`` or ``<iri>``."""
    prefixes = {m.group(1): m.group(2) for m in PREFIX.finditer(text)}
    m = _SELECT.search(text)
    if not m:
        raise ReferenceError_("no SELECT ... WHERE { ... } in the text")
    select = re.findall(r"\?\w+", m.group(1))
    if not select:
        raise ReferenceError_("SELECT names no variable")

    def term(tok: str) -> str:
        if tok[0] in "?<":
            return tok
        pfx, _, local = tok.partition(":")
        if pfx not in prefixes:
            raise ReferenceError_(f"unknown prefix in {tok!r}")
        return f"<{prefixes[pfx]}{local}>"

    body = m.group(2)
    if re.search(r"\b(OPTIONAL|UNION|FILTER)\b|[{}]", body, re.I):
        raise ReferenceError_("only plain triple patterns are read")
    toks = _TERM.findall(body)  # the '.' between patterns is no term
    if not toks or len(toks) % 3:
        raise ReferenceError_(f"{len(toks)} terms do not make triple patterns")
    patterns = [tuple(term(t) for t in toks[i:i + 3])
                for i in range(0, len(toks), 3)]
    return select, patterns


def sorted_rows(rows: np.ndarray) -> np.ndarray:
    rows = np.asarray(rows, dtype=np.int64)
    if rows.ndim != 2 or len(rows) < 2:
        return rows
    return rows[np.lexsort(rows.T[::-1])]


class _Pred:
    """One predicate's edges, sorted by subject and, on demand, by object."""

    def __init__(self, s: np.ndarray, o: np.ndarray):
        order = np.argsort(s, kind="stable")
        self.s, self.o_of_s = s[order], o[order]
        self._by_o = None
        self._pairs = None

    @property
    def by_o(self):
        if self._by_o is None:
            order = np.argsort(self.o_of_s, kind="stable")
            self._by_o = (self.o_of_s[order], self.s[order])
        return self._by_o

    @property
    def pairs(self) -> np.ndarray:
        if self._pairs is None:  # ids are below 2^31
            self._pairs = np.sort((self.s << 32) | self.o_of_s)
        return self._pairs


def _expand(keys: np.ndarray, vals: np.ndarray, probe: np.ndarray):
    """For each probe value the run of ``vals`` under the equal ``keys``
    (sorted): (row index of the probe repeated, the values)."""
    lo = np.searchsorted(keys, probe, side="left")
    hi = np.searchsorted(keys, probe, side="right")
    cnt = hi - lo
    total = int(cnt.sum())
    rep = np.repeat(np.arange(len(probe)), cnt)
    if total == 0:
        return rep, vals[:0]
    offs = np.arange(total) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    return rep, vals[np.repeat(lo, cnt) + offs]


class Reference:
    def __init__(self, triples: np.ndarray, index_rows):
        """``index_rows``: (iri, id) of predicates and classes. ``ids`` also
        takes the vertices the traffic names, once it has drawn them."""
        self.triples = triples
        self.ids = {s: int(i) for s, i in index_rows}
        self._preds: dict[int, _Pred] = {}

    # ------------------------------------------------------------------
    def pred(self, pid: int) -> _Pred:
        p = self._preds.get(pid)
        if p is None:
            rows = self.triples[self.triples[:, 1] == pid]
            p = self._preds[pid] = _Pred(np.ascontiguousarray(rows[:, 0]),
                                         np.ascontiguousarray(rows[:, 2]))
        return p

    def edge_count(self, pid: int) -> int:
        return len(self.pred(pid).s)

    def const_count(self, pid: int, obj: int) -> int:
        keys, _ = self.pred(pid).by_o
        return int(np.searchsorted(keys, obj, "right")
                   - np.searchsorted(keys, obj, "left"))

    def instances(self, type_iri: str, type_pred: int = 1) -> np.ndarray:
        """Sorted subjects of (?, rdf:type, type): one pass over the
        triples, no index built (the traffic generator asks in set-up)."""
        t = self.resolve(type_iri)
        tr = self.triples
        return np.sort(tr[(tr[:, 2] == t) & (tr[:, 1] == type_pred), 0])

    def resolve(self, iri: str) -> int:
        try:
            return self.ids[iri]
        except KeyError:
            raise ReferenceError_(f"no id known for {iri}") from None

    # ------------------------------------------------------------------
    def resolved(self, text: str):
        select, patterns = parse_bgp(text)
        out = []
        for s, p, o in patterns:
            if p[0] == "?":
                raise ReferenceError_("variable predicates are not read")
            out.append((s if s[0] == "?" else self.resolve(s),
                        self.resolve(p),
                        o if o[0] == "?" else self.resolve(o)))
        return select, out

    def evaluate(self, text: str) -> np.ndarray:
        """The reply the text is owed: sorted int64 rows over SELECT."""
        select, todo = self.resolved(text)
        cols: dict[str, np.ndarray] = {}
        nrows = None  # None: no pattern evaluated yet

        def is_var(t):
            return isinstance(t, str)

        def known(t):
            return not is_var(t) or t in cols

        while todo:
            # filters (both ends known) first, then a pattern with one end
            # known; the first pattern is the constant one with fewest rows
            if nrows is None:
                def cost(pt):
                    s, p, o = pt
                    if not is_var(o):
                        return self.const_count(p, o)
                    if not is_var(s):
                        return self.edge_count(p)  # upper bound
                    return 1 << 62
                pick = min(todo, key=cost)
            else:
                both = [pt for pt in todo if known(pt[0]) and known(pt[2])]
                one = [pt for pt in todo if known(pt[0]) or known(pt[2])]
                if not both and not one:
                    raise ReferenceError_("pattern group is not connected")
                pick = (both or one)[0]
            todo.remove(pick)
            s, p, o = pick
            pr = self.pred(p)
            if nrows is None:
                if not is_var(s) and not is_var(o):
                    raise ReferenceError_("ground pattern")
                if not is_var(o):
                    keys, vals = pr.by_o
                    _, got = _expand(keys, vals, np.array([o]))
                    cols[s] = got
                elif not is_var(s):
                    _, got = _expand(pr.s, pr.o_of_s, np.array([s]))
                    cols[o] = got
                elif s == o:
                    cols[s] = pr.s[pr.s == pr.o_of_s]
                else:
                    cols[s], cols[o] = pr.s, pr.o_of_s
                nrows = len(next(iter(cols.values())))
                continue
            sv = cols[s] if is_var(s) and s in cols else None
            ov = cols[o] if is_var(o) and o in cols else None
            if known(s) and known(o):
                left = sv if sv is not None else np.full(nrows, s, np.int64)
                right = ov if ov is not None else np.full(nrows, o, np.int64)
                key = (left << 32) | right
                at = np.searchsorted(pr.pairs, key)
                at[at == len(pr.pairs)] = 0
                keep = pr.pairs[at] == key if len(pr.pairs) else \
                    np.zeros(nrows, bool)
                cols = {v: c[keep] for v, c in cols.items()}
            elif known(s):  # expand to the objects
                probe = sv if sv is not None else np.full(nrows, s, np.int64)
                rep, got = _expand(pr.s, pr.o_of_s, probe)
                cols = {v: c[rep] for v, c in cols.items()}
                cols[o] = got
            else:  # expand to the subjects
                keys, vals = pr.by_o
                probe = ov if ov is not None else np.full(nrows, o, np.int64)
                rep, got = _expand(keys, vals, probe)
                cols = {v: c[rep] for v, c in cols.items()}
                cols[s] = got
            nrows = len(next(iter(cols.values())))
        missing = [v for v in select if v not in cols]
        if missing:
            raise ReferenceError_(f"SELECT names unbound {missing}")
        table = np.stack([cols[v] for v in select], axis=1) if nrows else \
            np.empty((0, len(select)), np.int64)
        return sorted_rows(table)


def read_index_rows(path: str):
    """(iri, id) rows of a dataset's ``str_index`` file."""
    rows = []
    with open(path) as f:
        for line in f:
            s, _, i = line.rstrip("\n").rpartition("\t")
            if s:
                rows.append((s, int(i)))
    return rows
