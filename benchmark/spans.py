"""Shared by the span readers: what a traced reply spent where."""



def front_ms(reply):
    """Parse + plan of one reply in ms: the benchmark's own spans around
    ``Proxy._parse_text`` and ``Proxy._plan_prepared`` (``driver.FrontSpans``;
    ``serve_query`` records no ``proxy.parse`` / ``proxy.plan`` span)."""
    return reply.front_ms


def execute_ms(reply):
    """The reply's time from send to host table less its parse and plan
    spans: the route choice, the engine behind it and the fetch."""
    return (reply.t_done - reply.t_send) * 1e3 - front_ms(reply)


def traced(run, kind):
    return [r for r in run.replies
            if r.ok and r.req.kind == kind and r.spans is not None]
