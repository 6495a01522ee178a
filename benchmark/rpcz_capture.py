"""The program's rpcz capture of the traced stretch, grouped into requests.

While the profiler records, the program keeps every rpcz span whole
(``incubator_brpc_tpu.observability.span.last_capture``): the client
span of each call, the server span, and one ``ici`` leg span per hop,
all on the call's trace id, every stamp on the wall clock in µs.  A
request is the spans of one trace; it counts when its client span
starts and ends inside the capture's armed interval and every stamp a
reader needs is set.  A program without the capture gives no requests,
and each reader then returns None.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional

CLIENT_STAMPS = ("start_us", "response_write_us", "received_us",
                 "dequeued_us", "end_us")
SERVER_STAMPS = ("received_us", "dequeued_us", "callback_start_us",
                 "callback_done_us")
LEG_STAMPS = ("start_us", "placed_us")


@dataclass
class Request:
    client: object  # the root client span
    server: object
    legs: List[object] = field(default_factory=list)  # one per hop


def last_capture():
    """The program's last capture, or None where it has none."""
    try:
        from incubator_brpc_tpu.observability import span
    except ImportError:
        return None
    read = getattr(span, "last_capture", None)
    return read() if read is not None else None


def _has(span, names) -> bool:
    return all(getattr(span, n, 0) for n in names)


def requests(cap) -> List[Request]:
    """The complete requests of a capture: a root client span that
    starts and ends inside the armed interval (an open interval ends at
    the last stamp seen), one server span, and at least two fabric
    legs, each with the stamps the readers use."""
    if cap is None:
        return []
    lo = cap.start_us
    hi = cap.stop_us or max((s.end_us for s in cap.spans), default=0)
    by_trace = {}
    for s in cap.spans:
        by_trace.setdefault(s.trace_id, []).append(s)
    out = []
    for spans in by_trace.values():
        roots = [s for s in spans if s.kind == "client" and not s.parent_span_id]
        servers = [s for s in spans if s.kind == "server"]
        legs = [s for s in spans if s.kind == "collective" and s.service == "ici"]
        if len(roots) != 1 or len(servers) != 1 or len(legs) < 2:
            continue
        (c,), (srv,) = roots, servers
        if c.error_code or not (lo <= c.start_us and c.end_us <= hi):
            continue
        if not (_has(c, CLIENT_STAMPS) and _has(srv, SERVER_STAMPS)
                and all(_has(g, LEG_STAMPS) for g in legs)):
            continue
        out.append(Request(client=c, server=srv, legs=legs))
    return out


# ---- per request, µs ---------------------------------------------------------

def client_us(r: Request) -> float:
    """The client's own work: before its request enters the fabric, and
    after its reply frame is picked up."""
    c = r.client
    return (c.response_write_us - c.start_us) + (c.end_us - c.dequeued_us)


def fabric_us(r: Request) -> float:
    """Placement and transmit dispatch of every hop."""
    return float(sum(g.placed_us - g.start_us for g in r.legs))


def cq_wait_us(r: Request) -> float:
    """Both frames' completion-queue waits."""
    c, s = r.client, r.server
    return (s.dequeued_us - s.received_us) + (c.dequeued_us - c.received_us)


def service_us(r: Request) -> float:
    """The handler, with whatever it calls (the store)."""
    return float(r.server.callback_done_us - r.server.callback_start_us)


def client_span_us(r: Request) -> float:
    return float(r.client.end_us - r.client.start_us)


PARTS = {"client_us": client_us, "fabric_us": fabric_us,
         "cq_wait_us": cq_wait_us, "service_us": service_us}


def mean_of(part: Callable[[Request], float], cap=None) -> Optional[float]:
    """The mean of ``part`` over the capture's complete requests (the
    program's last capture by default), or None where there is none."""
    reqs = requests(last_capture() if cap is None else cap)
    if not reqs:
        return None
    return sum(part(r) for r in reqs) / len(reqs)


def run_mean(run, part: Callable[[Request], float]) -> Optional[float]:
    """``mean_of`` for a traced run: the capture is that of its traced
    stretch.  An untraced run armed none."""
    if getattr(run, "trace", None) is None:
        return None
    return mean_of(part)


def summary(cap) -> dict:
    """Counts and means of a capture, with the part of the client span
    the four parts leave uncovered."""
    reqs = requests(cap)
    out = {"spans": len(cap.spans) if cap is not None else 0,
           "overflow": cap.overflow if cap is not None else 0,
           "complete_requests": len(reqs)}
    if reqs:
        means = {k: sum(f(r) for r in reqs) / len(reqs) for k, f in PARTS.items()}
        span = sum(client_span_us(r) for r in reqs) / len(reqs)
        out.update(means, client_span_us=span,
                   remainder_us=span - sum(means.values()),
                   covered_pct=100.0 * sum(means.values()) / span if span else None)
    return out
