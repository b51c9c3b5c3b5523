"""Share of the traced training window the prefetch producer spent building
owner plans: the union of the ``repro.producer.owner_plan`` spans (inside
``repro.producer.sample``) over the window.  None where the trace holds no
such span (a program that writes none, or a run without owner plans)."""

from bench import spans

OWNER_PLAN = "repro.producer.owner_plan"


def read(r):
    if r.trace is None or not any(n == OWNER_PLAN for _, n, _, _ in r.trace.host):
        return None
    return spans.share(r, OWNER_PLAN)
