"""Whole-plan compiled template execution (ROADMAP item 8).

Compile the template, not the step: instead of N host↔device round trips
(one per BGP step), an eligible walk-strategy plan is fused — expand +
intersect + filter + projection — into ONE jitted XLA program over
padded CSR tensors (the capacity-class posture of the WCOJ level probe,
in finer classes: a program's classes are its own, ``_program_key`` holds
them, so they step in eighths of an octave, ``capacity_class``, and lie
never above the class of the most rows the data can put there; the probe
and the walk, whose kernels queries share by class, keep ``pad_pow2``).
One sizing rule, whose room is what the totals can differ by between
requests: a first attempt takes twice the planner's estimate, and a
program that binds an operand query by query (a constant's start list, a
vertex as a constant object, a constant's member list) keeps those
classes, so that they do not follow the draw; a program that no draw can
change (every operand staged once, a type constant among them: the
signature keeps it; the store read-only at a version that is in the key)
settles, after its first sound run, on the classes of the totals that run
measured, which cannot overflow and take no room
(``_settled_caps``; ``wukong_template_settles_total``, the trace event
``capacity.settle``); its next request builds that program and the
guess's goes (``_cache_put``: one program a template stays resident).
TrieJax runs the whole LFTJ dataflow as one pipelined
hardware graph; "Column-Oriented Datalog on the GPU" shows eager
device-resident buffers paying off exactly when iteration state never
leaves the device — this module is the walk engine's equivalent.

Byte identity with the host walk is structural, not tested-in: every
fused op reproduces the corresponding ``engine/cpu.py`` kernel's row
order exactly (``expand_padded_device`` is ``np.repeat`` order over live rows,
filters only mask, the final host-side validity compaction preserves
position order), and anything the extractor cannot prove — unions,
OPTIONAL, FILTER, attrs, predicate variables, TYPE_ID+IN adjacency,
corun, deadlines, mt slices — routes to the host walk untouched.

Programs are cached per ``(template signature, store version, capacity
classes, route-knob set)`` beside the plan recipe (``_program_key`` —
the template-coherence analysis gate holds this shape), LRU-bounded by
``template_budget_mb`` with every fill/evict/invalidate charged on the
PR 18 residency ledger (kind ``template``), and every dispatch charged
through ``maybe_device_dispatch`` (site ``template.plan``) so the
compile ledger's variant-storm sentinel sees whole-plan variants too.

Routing follows the JOIN_ROUTES/CONSUMED_INPUTS pattern: a
``template_device`` knob + the :data:`TEMPLATE_ROUTES` literal registry.
Under ``auto`` programs win at both ends and the walk keeps the middle
(:func:`route_why`): few padded rows (every capacity class under
``template_min_rows``) and the calls are the cost, so one beats the
walk's k; many live rows (the estimated peak at or over it) and the
device is the cost; large padded classes with a small reply go to the
walk, which compacts between steps. Demotion is on evidence a reply
carries (its live rows out of a large class, a failure);
a measured signal the chooser reads has to come through
``read_device_input()`` against a declared ``DEVICE_INPUTS`` member (it
reads none: a site-wide figure would judge one template by what the
others ran). A losing or failing compile degrades to the host walk
byte-identically and latches a per-template demotion (re-armed by a
store mutation), visible in ``/device`` and EXPLAIN.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict

import numpy as np

from wukong_tpu.analysis.lockdep import declare_leaf, make_lock
from wukong_tpu.config import Global
from wukong_tpu.join.kernels import (
    DeviceRangeError,
    capacity_class,
    direct_lookup_wins,
    expand_padded_device,
    lookup_ranges_device,
    pad_pow2,
    pair_member,
    to_device_i32,
)
from wukong_tpu.join.wcoj import JoinTableCache
from wukong_tpu.obs.device import (
    maybe_device_dispatch,
    maybe_device_resident,
    note_compile_cache,
    note_feedback,
)
from wukong_tpu.obs.metrics import get_registry
from wukong_tpu.obs.trace import span, traced_execute
from wukong_tpu.runtime import faults
from wukong_tpu.types import (
    IN,
    NORMAL_ID_START,
    OUT,
    PREDICATE_ID,
    TYPE_ID,
    AttrType,
)
from wukong_tpu.utils.logger import log_info
from wukong_tpu.utils.timer import get_usec

#: the dispatch site every whole-plan program charges (DEVICE_INPUTS
#: reads against it drive the route chooser below)
SITE = "template.plan"

#: every route a template may take, with what it means — the literal
#: registry the template-coherence analysis gate anchors on (the
#: JOIN_ROUTES pattern: routes are an enumerable contract, not strings
#: scattered through call sites)
TEMPLATE_ROUTES = {
    "device": "whole-plan fused XLA program: one dispatch per query",
    "host": "the NumPy walk engine, one kernel per BGP step",
    "latched_host": "demoted: a failing or losing compiled attempt "
                    "latched host for this template until the next "
                    "store mutation",
}

#: int32 sentinel used to pad sorted membership lists — binary search
#: stays exact for every live value at or below it
_PAD_SENTINEL = (1 << 31) - 1

# both locks guard pure dict moves; program builds and XLA dispatches
# run outside them (the join.tables discipline)
declare_leaf("template.programs")
declare_leaf("template.routes")

_M_EXEC = get_registry().counter(
    "wukong_template_exec_total",
    "Compiled-template execution attempts by outcome "
    "(compiled / unsupported / overflow)",
    labels=("outcome",))
_M_DEMOTED = get_registry().counter(
    "wukong_template_demotions_total",
    "Per-template compiled-route demotion latches by reason",
    labels=("reason",))
_M_SETTLED = get_registry().counter(
    "wukong_template_settles_total",
    "Capacity classes of a whole-plan program that no draw can change, "
    "lowered to the class of the total its first sound run measured")


class TemplateUnsupported(Exception):
    """The plan shape cannot be compiled — route host, no latch."""


class TemplateOverflow(Exception):
    """Capacity retries exhausted — degrade to the host walk."""


# ---------------------------------------------------------------------------
# demotion latch (per template signature, re-armed by store mutation)
# ---------------------------------------------------------------------------

_DEM_LOCK = make_lock("template.routes")
#: {tsig: (reason, store version at latch time)}
_DEMOTED: dict = {}  # guarded by: _DEM_LOCK


def _label(tsig) -> str:
    """Bounded-cardinality template label for metrics/EXPLAIN."""
    return "t" + hashlib.sha1(repr(tsig).encode()).hexdigest()[:8]


def latch_demotion(tsig, reason: str, version: int | None = None) -> None:
    """Latch ``host`` for this template (a deterministic compile or
    dispatch failure would otherwise re-pay the failed device attempt
    on every same-template query). The latch carries the store version
    it was taken at: a mutation re-arms the device attempt, mirroring
    the plan-cache memo keys."""
    if tsig is None:
        return
    with _DEM_LOCK:
        _DEMOTED[tsig] = (str(reason), version)
    _M_DEMOTED.labels(reason=str(reason)).inc()
    note_feedback("template_route", str(reason))


def is_demoted(tsig, version: int | None = None) -> bool:
    with _DEM_LOCK:
        ent = _DEMOTED.get(tsig)
    if ent is None:
        return False
    if version is not None and ent[1] is not None and ent[1] != version:
        return False  # store mutated since the latch: re-arm
    return True


def demotion_report() -> dict:
    """{template label: reason} for /device and tests."""
    with _DEM_LOCK:
        return {_label(t): r for t, (r, _v) in _DEMOTED.items()}


def reset_demotions() -> None:
    with _DEM_LOCK:
        _DEMOTED.clear()


# ---------------------------------------------------------------------------
# route chooser
# ---------------------------------------------------------------------------

def _route_knobs() -> tuple:
    """The route-relevant knob set — part of every compiled-program
    cache key, so a runtime knob flip can never serve a program chosen
    under different routing rules (the template-coherence gate checks
    ``_program_key`` composes this)."""
    return (str(Global.template_device).strip().lower(),
            int(Global.template_min_rows))


def route_why(est_rows: int | None, caps: tuple | None = None) -> str | None:
    """Which half of the ``auto`` rule sends a plan to its program, or
    None for the walk. ``small_classes``: every capacity class of the
    program lies under ``template_min_rows``, so the device has next to
    nothing to do and the calls are the cost: one program beats the walk's
    chain of them (``caps`` is given only where the walk is the device
    engine; a NumPy walk makes no calls). ``estimate``: the estimated peak
    reaches ``template_min_rows``, the device is the cost. Between the two
    (large padded classes, few live rows) the walk compacts step by step."""
    floor = max(int(Global.template_min_rows), 1)
    if caps and max(caps) < floor:
        return "small_classes"
    if est_rows is not None and est_rows >= floor:
        return "estimate"
    return None


def choose_template_route(tsig, est_rows: int | None = None,
                          version: int | None = None,
                          caps: tuple | None = None) -> str:
    """Plan-time route for one template. The knob forces host/device;
    under ``auto`` :func:`route_why` decides from the planner's estimated
    peak rows and the capacity classes the plan's program runs at; a
    served reply whose live rows fill no large class, or a failure,
    latches the demotion that is read here. A measured signal would come
    through :func:`read_device_input` against a declared ``DEVICE_INPUTS``
    member (the gate-held contract)."""
    knob = str(Global.template_device).strip().lower()
    if knob == "host":
        return "host"
    if is_demoted(tsig, version):
        return "latched_host"
    if knob == "device":
        return "device"
    if knob != "auto":
        return "host"
    return "device" if route_why(est_rows, caps) else "host"


# ---------------------------------------------------------------------------
# plan extraction: prove the walk chain compilable, or refuse
# ---------------------------------------------------------------------------

def extract_template(q) -> tuple | None:
    """(spec, v2c, proj, width) for a compilable plan, else None.

    The extractor simulates ``engine/cpu.py``'s ``_execute_one_pattern``
    dispatch over the plan: every step must land on a kernel the fused
    program reproduces bit-for-bit. Anything else — unions, OPTIONAL,
    FILTER, attr patterns, predicate variables, ``vp``/type-index
    adjacencies, corun, mt slices, deadlines, repeated const-starts —
    returns None and the host walk serves the query untouched.
    """
    pg = q.pattern_group
    res = q.result
    if (pg.unions or pg.optional or pg.filters or not pg.patterns
            or q.pattern_step != 0 or q.corun_enabled or q.planner_empty
            or q.mt_factor > 1 or q.deadline is not None
            or getattr(q, "knn", None) is not None):
        return None

    def stat(ssid: int, v2c: dict) -> str:
        return "const" if ssid >= 0 else ("known" if ssid in v2c
                                          else "unknown")

    def seg_ok(pid: int, d: int) -> bool:
        # the vp pseudo-segment (PREDICATE_ID) and the per-type Python
        # loop (TYPE_ID + IN) have no CSR twin the program can probe
        return pid != PREDICATE_ID and not (pid == TYPE_ID and d == IN)

    v2c: dict[int, int] = {}
    spec: list[tuple] = []
    width = 1
    for step, pat in enumerate(pg.patterns):
        if pat.predicate < 0 or pat.pred_type != int(AttrType.SID_t):
            return None
        s, p, d, o = (pat.subject, pat.predicate, int(pat.direction),
                      pat.object)
        if step == 0:
            if q.start_from_index():
                if o >= 0 or s < 0:
                    return None
                spec.append(("index", s, d))
            else:
                if s < 0 or o >= 0:
                    return None
                spec.append(("const_list", s, p, d))
            v2c[o] = 0
            continue
        key = (stat(s, v2c), stat(o, v2c))
        if key == ("known", "unknown"):
            if not seg_ok(p, d):
                return None
            spec.append(("expand", p, d, v2c[s]))
            v2c[o] = width
            width += 1
        elif key == ("known", "known"):
            if not seg_ok(p, d):
                return None
            spec.append(("filter_pair", p, d, v2c[s], v2c[o]))
        elif key == ("known", "const"):
            if not seg_ok(p, d):
                return None
            spec.append(("filter_pair_const", p, d, v2c[s], o))
        elif key == ("const", "known"):
            spec.append(("filter_member", s, p, d, v2c[o]))
        else:
            # (const, unknown) past step 0 and every unknown-subject
            # shape raise on the host too — let the walk own them
            return None

    # projection fuses on-device only when it IS the final process:
    # distinct/orders/offset/limit and blind replies keep the full
    # table and run the host engine's _final_process verbatim
    proj = None
    req = [v for v in res.required_vars if not res.is_attr_var(v)]
    if (not res.blind and not q.distinct and not q.orders
            and q.offset == 0 and q.limit < 0 and req
            and not any(res.is_attr_var(v) for v in res.required_vars)
            and all(v in v2c for v in req)):
        proj = tuple(v2c[v] for v in req)
    return tuple(spec), v2c, proj, width


# ---------------------------------------------------------------------------
# the fused program
# ---------------------------------------------------------------------------

def _build_program(spec: tuple, caps: tuple, depths: tuple,
                   id_bounds: tuple, proj: tuple | None,
                   blind: bool = False, label: str = "t"):
    """jax.jit the whole plan: one traced function from the padded
    start list to the (projected) padded result table. All structure —
    op kinds, capacity classes, binary-search depths, each CSR op's id
    bound (its segment's last key + 1), projection — is static; every
    value (start list, CSR triplets, member lists, const ids) is a
    traced argument, so same-shape templates share compiles and consts
    never mint variants.

    The program is named ``wk_template_<label>`` (``label``: the template's
    ``_label``, one a family, so no draw mints a name), the module the
    profile shows; step ``k`` of the plan runs under the scope
    ``s<k>_<op>``.

    Returns ``(fn, forms)``: ``forms`` lists, once ``fn`` has traced,
    which form each key lookup of the program took (True the direct
    table, False the search: ``join/kernels.py:direct_lookup_wins``)."""
    import jax
    import jax.numpy as jnp

    n_expand = sum(1 for op in spec if op[0] == "expand")
    forms: list[bool] = []

    def wk_template(*args):
        del forms[:]
        it = iter(args)
        vals = next(it)
        n0 = next(it)
        with jax.named_scope(f"s0_{spec[0][0]}"):
            valid = jnp.arange(caps[0]) < n0
        cols = [vals]
        totals, ovfs = [], []
        ci, di, bi = 1, 0, 0
        for k, op in enumerate(spec[1:], 1):
            with jax.named_scope(f"s{k}_{op[0]}"):
                kind = op[0]
                if kind != "filter_member":
                    keys, offsets, edges = next(it), next(it), next(it)
                    bound = id_bounds[bi]
                    bi += 1
                    forms.append(direct_lookup_wins(
                        cols[op[3]].shape[0], keys.shape[0], bound))
                if kind == "expand":
                    start, deg = lookup_ranges_device(keys, offsets,
                                                      cols[op[3]], bound)
                    deg = jnp.where(valid, deg, 0)
                    rowc, newv, valid, total, ovf = expand_padded_device(
                        start, deg, edges, caps[ci])
                    cols = [c[rowc] for c in cols] + [newv]
                    totals.append(total)
                    ovfs.append(ovf)
                    ci += 1
                elif kind == "filter_pair":
                    ok = pair_member(keys, offsets, edges, cols[op[3]],
                                     cols[op[4]], xp=jnp, depth=depths[di],
                                     id_bound=bound)
                    di += 1
                    valid = valid & ok
                elif kind == "filter_pair_const":
                    objc = next(it)
                    anchors = cols[op[3]]
                    ok = pair_member(keys, offsets, edges, anchors,
                                     jnp.broadcast_to(objc, anchors.shape),
                                     xp=jnp, depth=depths[di], id_bound=bound)
                    di += 1
                    valid = valid & ok
                else:  # filter_member
                    mlist, mlen = next(it), next(it)
                    col = cols[op[4]]
                    idx = jnp.searchsorted(mlist, col)
                    idxc = jnp.clip(idx, 0, mlist.shape[0] - 1)
                    valid = valid & (idx < mlen) & (mlist[idxc] == col)
        live = jnp.sum(valid.astype(jnp.int32))
        totals_a = (jnp.stack(totals) if totals
                    else jnp.zeros(0, dtype=jnp.int32))
        ovfs_a = (jnp.stack(ovfs) if ovfs
                  else jnp.zeros(0, dtype=bool))
        if blind:
            # the blind reply IS the live count (the host walk's
            # _final_process returns before touching the table): the
            # padded table is never built, never fetched
            return totals_a, ovfs_a, live
        out_cols = cols if proj is None else [cols[c] for c in proj]
        table = jnp.stack(out_cols, axis=1)
        return table, valid, totals_a, ovfs_a, live

    assert len(caps) == n_expand + 1
    wk_template.__name__ = wk_template.__qualname__ = f"wk_template_{label}"
    return jax.jit(wk_template), forms


class _Program:
    """One cached compiled template: the jitted fn plus the device
    operands every draw of the template shares (an index start list, the
    CSR triplets, a constant object the signature keeps: a type). What a
    draw's own constants decide (a constant's start list, a constant
    object that is a vertex, a constant's member list) is ``None`` in
    ``args`` and bound query by query (``_bind``): the program is cached
    under the template's signature, which leaves vertex constants out and
    keeps the ids under ``NORMAL_ID_START``.
    ``fixed``: no operand is a draw's, so every request runs the program
    over the same values (``_settled_caps`` reads it).
    Steady-state execution is ``fn(*bound args)`` and one result fetch."""

    __slots__ = ("fn", "forms", "args", "caps", "spec", "v2c", "proj",
                 "width", "nbytes", "label", "blind", "fixed")

    def __init__(self, fn, forms, args, caps, spec, v2c, proj, width,
                 nbytes, label, blind=False):
        self.fn = fn
        self.forms = forms  # per key lookup, once traced: direct form?
        self.args = args  # None where a draw's constants decide
        self.caps = caps
        self.spec = spec
        self.v2c = v2c
        self.proj = proj
        self.width = width
        self.nbytes = nbytes
        self.label = label
        self.blind = blind
        self.fixed = all(a is not None for a in args)


def _program_key(tsig, store_version: int, caps: tuple,
                 blind: bool = False) -> tuple:
    """THE compiled-program cache key: template signature + the store
    version the operands were staged at + the capacity classes the
    program was traced with + the blind/materializing mode + the
    route-knob set (``_route_knobs``) — a dynamic insert, a capacity
    regrowth, or a runtime knob flip each make stale programs
    unreachable. The template-coherence analysis gate holds this exact
    composition."""
    return (tsig, int(store_version), tuple(int(c) for c in caps),
            bool(blind), _route_knobs())


def _budget_bytes() -> int:
    return max(int(Global.template_budget_mb), 1) * (1 << 20)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class TemplateCompiledEngine:
    """Serves eligible walk-strategy queries through cached whole-plan
    XLA programs; everything else (and every failure) degrades to the
    host walk byte-identically. One instance per proxy, sharing the
    WCOJ executor's per-version device-table discipline through its own
    :class:`JoinTableCache`."""

    def __init__(self, gstore, str_server=None):
        from wukong_tpu.engine.cpu import CPUEngine

        self.g = gstore
        self.cpu = CPUEngine(gstore, str_server)
        self.tables = JoinTableCache(gstore)
        self._programs: OrderedDict = OrderedDict()  # guarded by: _lock
        self._good_caps: dict = {}  # guarded by: _lock
        self._first_caps: dict = {}  # guarded by: _lock
        self._lock = make_lock("template.programs")
        get_registry().gauge(
            "wukong_template_programs",
            "Cached whole-plan compiled programs resident "
            "(LRU-bounded by template_budget_mb)",
        ).set_function(lambda: float(len(self._programs)))

    def _version(self) -> int:
        return int(getattr(self.g, "version", 0))

    # -- program cache -------------------------------------------------
    def _cache_get(self, key):
        with self._lock:
            ent = self._programs.get(key)
            if ent is not None:
                self._programs.move_to_end(key)
        note_compile_cache("hit" if ent is not None else "miss",
                           site="template")
        return ent

    def _cache_put(self, key, prog: _Program):
        evicted = []
        with self._lock:
            version = key[1]
            stale = [k for k in self._programs if k[1] != version]
            stale_bytes = sum(self._programs.pop(k).nbytes for k in stale)
            self._programs[key] = prog
            self._programs.move_to_end(key)
            # one program a template: the classes a settled template left
            # go when the program that takes their place is here, not
            # before, so that what is resident moves as little as it can
            # (a program's time follows where its buffers lie)
            for k in [k for k in self._programs
                      if k[0] == key[0] and k[2] != key[2]]:
                evicted.append(self._programs.pop(k))
            budget = _budget_bytes()
            total = sum(p.nbytes for p in self._programs.values())
            while total > budget and len(self._programs) > 1:
                _k, old = self._programs.popitem(last=False)
                total -= old.nbytes
                evicted.append(old)
        if stale:
            maybe_device_resident("invalidate", "template", stale_bytes,
                                  version=int(version))
        maybe_device_resident("fill", "template", prog.nbytes)
        for old in evicted:
            maybe_device_resident("evict", "template", old.nbytes)
            note_compile_cache("evict", site="template")
        return prog

    def program_count(self) -> int:
        with self._lock:
            return len(self._programs)

    def drop_template(self, tsig) -> None:
        """A demoted template's programs go, and their program text on the
        device with them (some MiB each): nothing runs them again before a
        store mutation re-arms the template, and that makes them stale."""
        with self._lock:
            keys = [k for k in self._programs if k[0] == tsig]
        for key in keys:
            self._drop_program(key)

    def clear(self) -> None:
        with self._lock:
            dropped = sum(p.nbytes for p in self._programs.values())
            self._programs.clear()
            self._good_caps.clear()
            self._first_caps.clear()
        if dropped:
            maybe_device_resident("invalidate", "template", dropped)
        self.tables.clear()

    # -- staging -------------------------------------------------------
    def _start_values(self, op) -> np.ndarray:
        if op[0] == "index":
            return np.asarray(self.g.get_index(op[1], op[2]),
                              dtype=np.int64)
        return np.asarray(self.g.get_triples(op[1], op[2], op[3]),
                          dtype=np.int64)

    def _stage(self, tsig, spec, caps, v2c, proj, width,
               blind=False) -> _Program:
        """Build one compiled program: stage every operand on device
        (CSR triplets through the version-keyed JoinTableCache, start
        and member lists padded here) and trace the fused fn. Raises
        DeviceRangeError when any operand exceeds int32 — the caller
        degrades to the host walk."""
        faults.site("template.compile")
        args: list = []
        depths: list[int] = []
        id_bounds: list[int] = []
        if spec[0][0] == "index":  # the same list for every draw
            args += self._start_args(spec[0], caps[0])
        else:
            args += [None, None]
        for op in spec[1:]:
            kind = op[0]
            if kind in ("expand", "filter_pair", "filter_pair_const"):
                keys, offsets, edges, depth, id_bound = (
                    self.tables.device_tables(op[1], op[2]))
                args += [keys, offsets, edges]
                id_bounds.append(id_bound)
                if kind != "expand":
                    depths.append(int(depth))
                if kind == "filter_pair_const":
                    # a type is the template's own (its signature keeps
                    # it), a vertex the draw's
                    args.append(np.int32(op[4])
                                if op[4] < NORMAL_ID_START else None)
            else:  # filter_member
                args += [None, None]
        label = _label(tsig)
        fn, forms = _build_program(spec, caps, tuple(depths),
                                   tuple(id_bounds), proj, blind, label)
        # what a cached program keeps on the device is its start list. (A
        # run's result buffer lives for that run: counted, one program of
        # 2^24 rows was twice the budget alone, and it and whichever
        # program ran beside it evicted each other on every request.)
        return _Program(fn, forms, args, caps, spec, v2c, proj, width,
                        caps[0] * 4, label, blind)

    def _start_args(self, op, cap: int) -> list:
        vals = self._start_values(op)
        padded = np.zeros(cap, dtype=np.int64)
        padded[:len(vals)] = vals
        return [to_device_i32(padded), np.int32(len(vals))]

    def _bind(self, prog: _Program, spec: tuple) -> list:
        """``prog.args`` with this query's constants in their slots: the
        constant's start list, vertices as constant objects (a type is
        staged with the program), a constant's member list
        (padded, as a start list is, to the class of the longest list its
        segment holds, so no constant's list length mints a program)."""
        args = list(prog.args)
        if args[0] is None:
            args[0:2] = self._start_args(spec[0], prog.caps[0])
        at = 2
        for op in spec[1:]:
            if op[0] == "filter_member":
                ml = np.asarray(self.g.get_triples(op[1], op[2], op[3]),
                                dtype=np.int64)
                if len(ml) > 1 and not bool((ml[1:] >= ml[:-1]).all()):
                    ml = np.sort(ml)
                pml = np.full(pad_pow2(
                    max(len(ml), self.g.max_degree(op[2], op[3])),
                    floor=max(int(Global.table_capacity_min), 1)),
                    _PAD_SENTINEL, dtype=np.int64)
                pml[:len(ml)] = ml
                args[at:at + 2] = [to_device_i32(pml), np.int32(len(ml))]
                at += 2
                continue
            at += 3
            if op[0] == "filter_pair_const":
                if args[at] is None:
                    if not (0 <= op[4] < (1 << 31)):
                        raise DeviceRangeError(
                            f"const object {op[4]} exceeds int32")
                    args[at] = np.int32(op[4])
                at += 1
        return args

    def _start_len(self, spec) -> int:
        """Rows the start list may hold: an index list's length, or for a
        constant the longest list any constant of its segment has, so that
        a template's classes do not follow the constant drawn."""
        op = spec[0]
        n0 = len(self._start_values(op))
        if op[0] == "const_list":
            n0 = max(n0, self.g.max_degree(op[2], op[3]))
        return n0

    def plan_caps(self, q) -> tuple:
        """The classes the program of ``q``'s plan runs at, for the route
        rule: those its last sound run left remembered (the classes it ran
        at or, where no draw can change the program, the classes of the
        totals it measured: ``_settled_caps``) or, before any run, those a
        first attempt would take (from what the proxy stamped on ``q`` at
        plan time; memoised per template and store version, nothing is
        staged or built). ``()`` where the small end of the rule cannot
        apply: a plan that cannot be compiled, or a template whose type has
        peers (it is itself an instance of a class: WatDiv's S3 and S5), so
        that requests may draw it: a program is kept under the signature,
        which keeps the type, and would be built once a type."""
        tsig, version = getattr(q, "_tsig", None), self._version()
        if tsig is None:
            return ()
        with self._lock:
            first = self._first_caps.get(tsig)
            good = self._good_caps.get((tsig, version))
        if first is None or first[0] != version:
            ext = extract_template(q)
            drawn = any(
                p == TYPE_ID and isinstance(o, tuple) and o[0] == "k"
                and len(self.g.get_triples(o[1], TYPE_ID, OUT))
                for (_s, p, _d, o, _t) in tsig)
            first = (version, () if ext is None or drawn else
                     self._initial_caps(
                         tsig, ext[0], getattr(q, "_template_est_rows", None),
                         getattr(q, "_template_est_steps", None)))
            with self._lock:
                self._first_caps[tsig] = first
        return first[1] and (good or first[1])

    def _initial_caps(self, tsig, spec, est_rows: int | None,
                      est_steps: list | None = None) -> tuple:
        """The classes the next attempt runs at: those the template's last
        sound run at this store version left remembered (``_settled_caps``:
        for a program that no draw can change the classes of its measured
        totals, without the room), else a first attempt's: where the planner
        walked the chain, each expansion's own estimate with one class of room,
        that is twice the estimate (a start from a constant scaled to the
        heaviest constant, as its start list is), and never under its
        segment's longest edge list, with the steps after lifted by the
        same factor (one row of the frontier may be the heaviest key:
        ``TPUEngine._estimate_rows`` sizes the walk so), so that a
        template's classes do not follow the draw; else four times the step
        before, at least the peak. A size is rounded up by
        ``capacity_class`` (eighths of an octave from 8,192 rows), so the
        rounding adds at most an eighth to the room, where a power of two
        made anything between two and four times the estimate of it (C3 of
        WatDiv, estimated 4,227,401 rows, ran at 2^24). And a class is
        never above the class of the most rows the data can put there
        (``_fill_bound``, exact): such a class cannot overflow, so neither
        the room nor the floor (``table_capacity_min``, room for a small
        estimate's error) is added to it. A light plan's classes are a few
        dozen rows, and an expansion over a segment of out-degree 1 runs at
        the class of its start list."""
        cap_max = int(Global.table_capacity_max)
        version = self._version()
        with self._lock:
            good = self._good_caps.get((tsig, version))
        n0 = self._start_len(spec)
        floor = max(int(Global.table_capacity_min), 1)
        if good is not None:
            if good[0] >= n0:
                return good
            return (capacity_class(n0, floor, cap_max),) + tuple(good[1:])
        caps = [capacity_class(n0, floor, cap_max)]
        walked = est_steps is not None and len(est_steps) == len(spec)
        scale = max(n0 / max(float(est_steps[0]), 1.0), 1.0) if walked else 1.0
        for k, op in enumerate(spec):
            if op[0] == "expand":
                if walked:
                    mean = max(float(est_steps[k]) * scale, 1.0)
                    heavy = self.g.max_degree(op[1], op[2])
                    guess = max(mean * 2, heavy)
                    if heavy > mean:  # the room is for the mean, not the skew
                        scale *= heavy / mean
                else:
                    guess = caps[-1] * 4
                    if est_rows:
                        guess = max(guess, est_rows)
                caps.append(capacity_class(int(guess), floor, cap_max))
        return tuple(min(c, capacity_class(b, 1, cap_max))
                     for c, b in zip(caps, self._fill_bound(spec, n0)))

    def _fill_bound(self, spec, n0: int) -> list:
        """The most rows each class can ever hold: the start list's length
        (exact: ``_start_len``), and after an expansion the rows before it
        times its segment's longest edge list. A class of this many rows
        cannot overflow, so it needs no room."""
        bound = [int(n0)]
        for op in spec:
            if op[0] == "expand":
                bound.append(min(
                    bound[-1] * self.g.max_degree(op[1], op[2]),
                    int(Global.table_capacity_max)))
        return bound

    @staticmethod
    def _grow_caps(caps: tuple, totals: np.ndarray,
                   ovfs: np.ndarray) -> tuple:
        caps = list(caps)
        k = int(np.argmax(ovfs))  # first overflowed expand
        t = int(totals[k])
        cap_max = int(Global.table_capacity_max)
        if 0 < t <= cap_max:
            # twice the class that overflowed at least (a class doubled is
            # a class), up to the cap, which holds ``t``
            caps[k + 1] = min(max(capacity_class(t), caps[k + 1] * 2),
                              cap_max)
        else:
            caps[k + 1] = caps[k + 1] * 4
        for j in range(k + 2, len(caps)):
            # downstream totals were computed over garbage rows: grow
            # them to at least the repaired step's class
            caps[j] = max(caps[j], caps[k + 1])
        if any(c > cap_max for c in caps):
            raise TemplateOverflow(
                f"capacity class past table_capacity_max ({cap_max})")
        return tuple(caps)

    @staticmethod
    def _settled_caps(prog: _Program, totals: np.ndarray) -> tuple:
        """The classes a sound run of ``prog`` leaves remembered for its
        template. Where a draw's constants decide an operand, the classes it
        ran at: the totals are that draw's, and a template's classes do not
        follow the draw. Where every operand is the same for every request
        (``prog.fixed``) the store is read-only at a version and the version
        is in the key, so each expansion's total is a fact of the store: the
        class of the total cannot overflow and takes neither the room nor
        ``table_capacity_min``, as the class of ``_fill_bound`` does not.
        Never above the class it ran at (and so never above the bound's),
        never under one row; the start class is exact already. A program at
        settled classes measures the same totals and settles to itself."""
        if not prog.fixed:
            return prog.caps
        cap_max = int(Global.table_capacity_max)
        return prog.caps[:1] + tuple(
            min(c, capacity_class(int(t), 1, cap_max))
            for c, t in zip(prog.caps[1:], totals))

    def _drop_program(self, key) -> None:
        """A program that is not come back to goes (classes that overflowed,
        a demoted template's), and with it its few MiB of program text on
        the device."""
        with self._lock:
            dropped = self._programs.pop(key, None)
        if dropped is not None:
            maybe_device_resident("evict", "template", dropped.nbytes)

    @staticmethod
    def _note_settled(prog: _Program, settled: tuple, tr) -> None:
        """Counted, logged and, traced, one ``capacity.settle`` event a step
        that came down. The guess's program stays until the template's next
        request has built the settled one (``_cache_put``)."""
        for k, (c0, c1) in enumerate(zip(prog.caps, settled)):
            if c1 != c0:
                _M_SETTLED.inc()
                if tr is not None:
                    tr.event("capacity.settle", site=SITE, step=k,
                             cap_from=c0, cap_to=c1)
        log_info(f"compiled template {prog.label} settled on its measured "
                 f"totals: classes {prog.caps} -> {settled}")

    # -- execution -----------------------------------------------------
    def try_execute(self, q) -> bool:
        """Serve ``q`` through the compiled program. Returns True when
        served (byte-identical to the host walk), False when the plan
        shape is not compilable (caller walks, nothing latched). Raises
        on compile/dispatch failure with ``q`` UNTOUCHED — the caller
        latches the per-template demotion and walks. Traced, the whole
        attempt is one ``template.execute`` span; ``template.stage``
        (program lookup, staging on a miss, the draw's constants bound),
        ``template.dispatch``,
        ``template.sync`` and ``template.commit`` lie inside it; it ends
        with how many of the program's key lookups took the direct form
        and how many the search (``direct_lookups``, ``search_lookups``)."""
        return traced_execute(
            q, "template.execute", lambda: self._try_execute(q),
            lambda: {"label": getattr(q, "_template_label", None),
                     "attempts": getattr(q, "_template_attempts", 0),
                     "rows": q.result.nrows,
                     **getattr(q, "_template_lookups", {})})

    def _try_execute(self, q) -> bool:
        ext = extract_template(q)
        if ext is None:
            _M_EXEC.labels(outcome="unsupported").inc()
            return False
        spec, v2c, proj, width = ext
        tsig = getattr(q, "_tsig", None) or spec
        est = getattr(q, "_template_est_rows", None)
        # a blind reply is the live-row COUNT (the host _final_process
        # returns before touching the table): the blind program never
        # builds or fetches the padded result table at all
        blind = bool(q.result.blind)
        version = self._version()
        caps = self._initial_caps(
            tsig, spec, est, getattr(q, "_template_est_steps", None))
        retries = max(int(Global.template_capacity_retries), 0)
        tr = getattr(q, "trace", None)
        for _attempt in range(retries + 1):
            q._template_attempts = _attempt + 1
            with span(tr, "template.stage"):
                key = _program_key(tsig, version, caps, blind)
                prog = self._cache_get(key)
                if prog is None:
                    prog = self._cache_put(key, self._stage(
                        tsig, spec, caps, v2c, proj, width, blind))
                args = self._bind(prog, spec)
            tbl, val, live, totals, ovfs = self._dispatch(prog, args, q, tr)
            if not (ovfs.size and bool(ovfs.any())):
                settled = self._settled_caps(prog, totals)
                with self._lock:
                    self._good_caps[(tsig, version)] = settled
                if settled != caps:
                    self._note_settled(prog, settled, tr)
                q._template_caps = caps  # what the reply's feedback judges
                with span(tr, "template.commit"):
                    self._commit(q, prog, tbl, val, live)
                q._template_compiled = True
                q._template_label = prog.label
                if tr is not None:
                    # which program this reply ran: the form is chosen at
                    # trace time, so it is a property of the program
                    direct = sum(prog.forms)
                    q._template_lookups = {
                        "direct_lookups": direct,
                        "search_lookups": len(prog.forms) - direct,
                        "steps": len(spec), "width": width}
                _M_EXEC.labels(outcome="compiled").inc()
                return True
            grown = self._grow_caps(caps, totals, ovfs)
            # the classes that overflowed are not come back to (the ones
            # that fit are remembered)
            self._drop_program(key)
            del prog
            if tr is not None:
                for k, (c0, c1) in enumerate(zip(caps, grown)):
                    if c1 != c0:
                        tr.event("capacity.retry", site="template.plan",
                                 step=k, cap_from=c0, cap_to=c1)
            caps = grown
        _M_EXEC.labels(outcome="overflow").inc()
        raise TemplateOverflow(
            f"padded table overflowed after {retries + 1} attempts")

    def _dispatch(self, prog: _Program, args: list, q, tr):
        """One fused dispatch, charged at the sync point. Returns the
        fetched (table, valid, live rows, per-step totals, per-step
        overflow flags); the caller regrows where a flag is set. Nothing is
        kept on the engine: clients dispatch side by side."""
        import jax

        faults.site("template.dispatch")
        t0 = get_usec()
        with span(tr, "template.dispatch"):
            if tr is not None:
                tr.event("device.dispatch", kernel=prog.label)
            outs = prog.fn(*args)
        with span(tr, "template.sync"):
            # one fetch of everything the program returned: the copies are
            # started together and waited for once (the sync point)
            outs = jax.device_get(outs)
            if prog.blind:
                totals, ovfs, live = outs
                tbl = val = None
                nbytes = 12
            else:
                tbl, val, totals, ovfs, live = outs
                nbytes = int(tbl.nbytes) + int(val.nbytes)
            live = int(live)
        wall = get_usec() - t0
        rec = maybe_device_dispatch(
            SITE, template=prog.label, live=live,
            capacity=int(prog.caps[-1]), wall_us=int(wall),
            nbytes=nbytes)
        if rec is not None:
            dev = getattr(q, "device_steps", None)
            if dev is None:
                dev = q.device_steps = []
            dev.append({**rec, "step": len(q.pattern_group.patterns),
                        "eff": (int(live) / max(int(prog.caps[-1]), 1))})
        return tbl, val, live, totals, ovfs

    def _commit(self, q, prog: _Program, tbl: np.ndarray,
                val: np.ndarray, live: int) -> None:
        """Install the compiled result exactly as the walk would have
        left it: validity compaction preserves the host row order; the
        fused projection sets the walk's post-projection v2c map, the
        unfused path replays the host ``_final_process`` verbatim. A
        blind program commits only the live count — the client-visible
        blind reply — with the walk's v2c metadata."""
        res = q.result
        if prog.blind:
            res.v2c_map = dict(prog.v2c)
            res.col_num = prog.width
            res.nrows = int(live)
            q.pattern_step = len(q.pattern_group.patterns)
            return
        out = tbl[val].astype(np.int64)
        if out.ndim == 1:
            out = out.reshape(-1, max(prog.width, 1))
        res.set_table(out)
        if prog.proj is not None:
            normal = [v for v in res.required_vars
                      if not res.is_attr_var(v)]
            res.v2c_map = {v: i for i, v in enumerate(normal)}
            res.col_num = len(normal)
        else:
            res.v2c_map = dict(prog.v2c)
            res.col_num = prog.width
        q.pattern_step = len(q.pattern_group.patterns)
        if prog.proj is None:
            self.cpu._final_process(q)
