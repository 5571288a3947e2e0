"""Spans inside ``Proxy.serve_query`` and the two device routes behind it.

A served query, traced, carries the proxy's layer spans at trace level and
under ``proxy.execute`` the spans of the route that answered: the walk
(``tpu.execute`` > ``tpu.stage``, ``tpu.chain`` > ``tpu.dispatch`` +
``tpu.sync`` per attempt, ``tpu.finalize``) or the whole-plan template
(``template.execute`` > ``template.stage``, ``.dispatch``, ``.sync``,
``.commit``). Every span names the span that caused it; jitted calls are
counted as events; with a trace live each span also enters a ``wk:`` profiler
annotation; every span is closed before the flight recorder has the trace;
with tracing off none of it is entered.
"""

import threading

import pytest

from wukong_tpu.config import Global
from wukong_tpu.engine.cpu import CPUEngine
from wukong_tpu.engine.template_compile import reset_demotions
from wukong_tpu.engine.tpu import TPUEngine
from wukong_tpu.loader.lubm import VirtualLubmStrings, generate_lubm
from wukong_tpu.obs import QueryTrace, chrome_trace_events
from wukong_tpu.obs import trace as obs_trace
from wukong_tpu.obs.profile import EXECUTE_SPANS, decompose
from wukong_tpu.obs.trace import span
from wukong_tpu.planner.optimizer import make_planner
from wukong_tpu.runtime.proxy import Proxy
from wukong_tpu.store.gstore import build_partition

pytestmark = pytest.mark.obs

PREFIX = """
PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
"""
Q_CHAIN = PREFIX + """SELECT ?X ?Y ?Z WHERE {
    ?X ub:memberOf ?Y .
    ?Y ub:subOrganizationOf ?Z .
}"""

ROUTES = ("walk", "template")
PROXY_SPANS = ["proxy.parse", "proxy.plan", "proxy.execute", "proxy.reply"]
ROUTE_SPANS = {
    "walk": {"tpu.execute": "proxy.execute", "tpu.stage": "tpu.execute",
             "tpu.chain": "tpu.execute", "tpu.dispatch": "tpu.chain",
             "tpu.sync": "tpu.chain", "tpu.finalize": "tpu.execute"},
    "template": {"template.execute": "proxy.execute",
                 "template.stage": "template.execute",
                 "template.dispatch": "template.execute",
                 "template.sync": "template.execute",
                 "template.commit": "template.execute"},
}


@pytest.fixture(scope="module")
def world():
    triples, _ = generate_lubm(1, seed=42)
    g = build_partition(triples, 0, 1)
    ss = VirtualLubmStrings(1, seed=42)
    return g, ss, make_planner(triples, None)


@pytest.fixture()
def proxy(world):
    g, ss, planner = world
    p = Proxy(g, ss, CPUEngine(g, ss), TPUEngine(g, ss))
    p.planner = planner
    p.tpu.stats = planner.stats
    return p


def _route(monkeypatch, route: str) -> None:
    reset_demotions()
    monkeypatch.setattr(Global, "template_device",
                        "device" if route == "template" else "host")


def _serve_traced(proxy, monkeypatch, route: str):
    _route(monkeypatch, route)
    monkeypatch.setattr(Global, "enable_tracing", True)
    q = proxy.serve_query(Q_CHAIN, blind=False)
    assert q.result.status_code == 0 and q.result.nrows > 0
    assert bool(getattr(q, "_template_compiled", False)) == (route == "template")
    return q


def _by_name(tr) -> dict:
    out: dict[str, list] = {}
    for sp in tr.spans:
        out.setdefault(sp.name, []).append(sp)
    return out


@pytest.mark.parametrize("route", ROUTES)
def test_served_trace_carries_the_layer_spans(proxy, monkeypatch, route):
    tr = _serve_traced(proxy, monkeypatch, route).trace
    top = [sp.name for sp in tr.spans if sp.depth == 0]
    assert top == PROXY_SPANS
    spans = _by_name(tr)
    for name, parent in ROUTE_SPANS[route].items():
        assert name in spans, f"{route}: no {name} span"
        for sp in spans[name]:
            assert tr.spans[sp.parent].name == parent
    other = "template" if route == "walk" else "walk"
    assert not set(ROUTE_SPANS[other]) & set(spans)


@pytest.mark.parametrize("route", ROUTES)
def test_parent_and_containment(proxy, monkeypatch, route):
    tr = _serve_traced(proxy, monkeypatch, route).trace
    for i, sp in enumerate(tr.spans):
        assert sp.index == i
        if sp.depth == 0:
            assert sp.parent == -1
            continue
        up = tr.spans[sp.parent]
        assert up.depth == sp.depth - 1 and up.tid == sp.tid
        assert up.t0_us <= sp.t0_us and sp.t1_us <= up.t1_us
    assert [sp.to_dict()["parent"] for sp in tr.spans] == \
        [sp.parent for sp in tr.spans]


@pytest.mark.parametrize("route", ROUTES)
def test_recorder_gets_a_trace_nothing_writes_to_again(proxy, monkeypatch,
                                                       route):
    """The reply-side span closes before ``on_complete``: what the recorder
    rings (and may dump) has every span ended inside the trace's own
    interval, and the served reply's trace is that same, unchanged, list."""
    seen = []
    on_complete = proxy.recorder.on_complete

    def spy(trace, status):
        on_complete(trace, status)
        seen.append([(sp.name, sp.t0_us, sp.t1_us) for sp in trace.spans])

    monkeypatch.setattr(proxy.recorder, "on_complete", spy)
    tr = _serve_traced(proxy, monkeypatch, route).trace
    (recorded,) = seen
    assert recorded == [(sp.name, sp.t0_us, sp.t1_us) for sp in tr.spans]
    assert tr.status == "SUCCESS" and tr.t1_us is not None
    for _name, t0, t1 in recorded:
        assert t1 is not None and tr.t0_us <= t0 <= t1 <= tr.t1_us


@pytest.mark.parametrize("route", ROUTES)
def test_decompose_of_a_served_trace(proxy, monkeypatch, route):
    tr = _serve_traced(proxy, monkeypatch, route).trace
    comp = decompose(tr)["components"]
    assert comp["parse"] > 0 and comp["plan"] > 0 and comp["execute"] > 0
    execs = [sp for sp in tr.spans if sp.name in EXECUTE_SPANS]
    assert comp["execute"] == sum(sp.dur_us for sp in execs)


@pytest.mark.parametrize("route", ROUTES)
def test_tracing_off_enters_no_span_helper(proxy, monkeypatch, route):
    _route(monkeypatch, route)
    entered = []
    monkeypatch.setattr(obs_trace._SpanScope, "__enter__",
                        lambda self: entered.append(self.name))
    monkeypatch.setattr(obs_trace, "_annotate",
                        lambda name: entered.append("wk:" + name))
    monkeypatch.setattr(QueryTrace, "start_span",
                        lambda self, name, **kw: entered.append(name))
    q = proxy.serve_query(Q_CHAIN, blind=False)
    assert q.result.status_code == 0 and q.result.nrows > 0
    assert getattr(q, "trace", None) is None
    assert entered == []


def test_span_helper_runs_the_body_bare_without_a_trace():
    with span(None, "x.y", k=1) as sp:
        assert sp is None
    assert span(None, "a") is span(None, "b")  # one shared no-op


@pytest.mark.parametrize("route", ROUTES)
def test_dispatch_events(proxy, monkeypatch, route):
    """One ``device.dispatch`` per call of a jitted function, inside the
    route's dispatch span and nowhere else."""
    tr = _serve_traced(proxy, monkeypatch, route).trace
    spans = _by_name(tr)
    prefix = "tpu" if route == "walk" else "template"
    calls = [n for sp in spans[f"{prefix}.dispatch"] for _t, n, _a in sp.events
             if n == "device.dispatch"]
    assert tr.event_names().count("device.dispatch") == len(calls) >= 1
    if route == "walk":
        chain = spans["tpu.chain"][0]
        assert len(calls) >= chain.attrs["dispatches"]


def test_walk_has_one_dispatch_sync_pair_per_attempt(proxy, monkeypatch):
    """An underestimated capacity class makes the chain run again: the
    retry shows as a second ``tpu.dispatch`` / ``tpu.sync`` pair."""
    monkeypatch.setattr(proxy.tpu, "cap_min", 8)
    monkeypatch.setattr(proxy.tpu, "_estimate_rows", lambda *a, **kw: 1)
    monkeypatch.setattr(proxy.tpu, "_chain_estimates", lambda pats: {})
    tr = _serve_traced(proxy, monkeypatch, "walk").trace
    spans = _by_name(tr)
    chain = spans["tpu.chain"][0]
    assert chain.attrs["attempts"] >= 2
    assert len(spans["tpu.sync"]) == chain.attrs["attempts"]
    assert len(spans["tpu.dispatch"]) == chain.attrs["attempts"]
    assert chain.attrs["dispatches"] == \
        chain.attrs["attempts"] * chain.attrs["steps"]


def test_template_execute_attributes(proxy, monkeypatch):
    q = _serve_traced(proxy, monkeypatch, "template")
    (sp,) = _by_name(q.trace)["template.execute"]
    assert sp.attrs["label"] == q._template_label
    assert sp.attrs["attempts"] == \
        len(_by_name(q.trace)["template.sync"]) >= 1
    assert sp.attrs["rows"] == q.result.nrows


def test_template_execute_says_which_lookups_its_program_took(
        proxy, monkeypatch):
    """The lookup's form is chosen at trace time from static shapes, so it
    is a property of the compiled program: the span of a reply names it.
    Q_CHAIN is two expands, each one key lookup; at LUBM-1 the frontier
    (2^13 rows and more) outweighs either segment's keys."""
    q = _serve_traced(proxy, monkeypatch, "template")
    (sp,) = _by_name(q.trace)["template.execute"]
    assert (sp.attrs["direct_lookups"], sp.attrs["search_lookups"]) == (2, 0)
    # served again from the cached program: the same program, the same count
    q2 = _serve_traced(proxy, monkeypatch, "template")
    (sp2,) = _by_name(q2.trace)["template.execute"]
    assert (sp2.attrs["direct_lookups"], sp2.attrs["search_lookups"]) == (2, 0)


Q_LIGHT = PREFIX + """SELECT ?X WHERE {
    ?X ub:subOrganizationOf <http://www.Department0.University0.edu> .
    ?X rdf:type ub:ResearchGroup .
}"""


def test_a_program_reply_is_one_call_and_one_fetch(proxy, monkeypatch):
    """One run of a program costs the host one call and one fetch: a traced
    reply holds one ``template.sync`` span and one ``device.dispatch``
    event, and the draw's constants are bound inside ``template.stage``
    (so the metric that reads the stage spans holds the bind)."""
    eng = proxy.template_engine()
    bind, seen = eng._bind, []

    def bound(prog, spec):
        tr = obs_trace.current()
        stack = tr._stacks.get(threading.get_ident())
        seen.append(stack[-1].name if stack else None)
        return bind(prog, spec)

    monkeypatch.setattr(eng, "_bind", bound)
    _route(monkeypatch, "walk")
    monkeypatch.setattr(Global, "template_device", "auto")
    monkeypatch.setattr(Global, "enable_tracing", True)
    for k in range(2):  # the first draw builds the program, the second finds it
        q = proxy.serve_query(Q_LIGHT, blind=False)
        assert q._template_compiled and q.result.nrows > 0
        spans = _by_name(q.trace)
        assert len(spans["template.sync"]) == 1
        assert len(spans["template.dispatch"]) == 1
        assert len(spans["template.stage"]) == 1
        assert q.trace.event_names().count("device.dispatch") == 1
        assert "capacity.retry" not in q.trace.event_names()
    assert seen == ["template.stage", "template.stage"]


@pytest.mark.parametrize("knob,text,why", [
    ("auto", Q_LIGHT, "small_classes"), ("auto", Q_CHAIN, "estimate"),
    ("device", Q_LIGHT, "knob"), ("host", Q_LIGHT, None)],
    ids=["small_classes", "estimate", "knob", "walk"])
def test_route_event_says_why(proxy, monkeypatch, knob, text, why):
    """The ``proxy.route`` event of a reply from a template program says
    which half of the rule sent it there: every class of the program under
    ``template_min_rows``, or the estimated peak at or over it."""
    reset_demotions()
    monkeypatch.setattr(Global, "template_device", knob)
    # with the batcher on the small end does not apply (no classes are
    # asked for), and the light's estimate is far under the threshold:
    # neither half of the rule, the knob alone sends it to its program
    monkeypatch.setattr(Global, "enable_batching", why == "knob")
    monkeypatch.setattr(Global, "enable_tracing", True)
    q = proxy.serve_query(text, blind=False)
    (attrs,) = [a for sp in q.trace.spans for _t, n, a in sp.events
                if n == "proxy.route"]
    assert attrs["route"] == ("template" if why else "walk")
    assert attrs.get("why") == why
    assert attrs["template_route"] == q.template_route


@pytest.mark.parametrize("route", ROUTES)
def test_spans_enter_wk_annotations(proxy, monkeypatch, route):
    """Each span of a live trace also enters ``TraceAnnotation("wk:" +
    name)``, properly nested: the profiler's host plane then carries the
    span tree on the device trace's clock."""
    log = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("in", self.name))

        def __exit__(self, *exc):
            log.append(("out", self.name))
            return False

    monkeypatch.setattr(obs_trace, "_annotation", Note)
    tr = _serve_traced(proxy, monkeypatch, route).trace
    entered = [n for io, n in log if io == "in"]
    assert entered == ["wk:" + sp.name for sp in tr.spans]
    stack = []
    for io, name in log:
        if io == "in":
            stack.append(name)
        else:
            assert stack.pop() == name
    assert stack == []


def test_real_annotation_is_the_profilers(monkeypatch):
    import jax.profiler

    tr = QueryTrace()
    with span(tr, "proxy.parse") as sp:
        assert sp is tr.spans[0]
    assert obs_trace._annotation is jax.profiler.TraceAnnotation
    assert sp.t1_us is not None


def test_chrome_export_carries_parent(proxy, monkeypatch):
    tr = _serve_traced(proxy, monkeypatch, "walk").trace
    evs = [e for e in chrome_trace_events([tr]) if e["ph"] == "X"]
    assert len(evs) == len(tr.spans)
    for e, sp in zip(evs, tr.spans):
        assert e["args"]["parent"] == sp.parent


def test_span_ended_on_another_thread_leaves_the_stack():
    tr = QueryTrace()
    sp = tr.start_span("pool.queue")
    t = threading.Thread(target=tr.end_span, args=(sp,))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert sp.t1_us is not None
    here = tr.start_span("cpu.execute")
    tr.end_span(here)
    assert (here.parent, here.depth, here.index) == (-1, 0, 1)


def test_event_without_open_span_is_a_trace_level_span():
    tr = QueryTrace()
    tr.event("batch.settled", dispatch_us=5)
    (sp,) = tr.spans
    assert (sp.parent, sp.depth, sp.index, sp.dur_us) == (-1, 0, 0, 0)


def test_step_trace_and_tracing_stub_are_gone():
    import wukong_tpu.obs as obs

    assert not hasattr(obs, "StepTrace")
    assert not hasattr(obs_trace, "StepTrace")
    with pytest.raises(ModuleNotFoundError):
        import wukong_tpu.runtime.tracing  # noqa: F401
