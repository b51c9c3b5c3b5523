"""Share of the traced training window the loop spent inside
``repro.train.next_batch`` (the union of those spans over the window): the
in-program reading of the wait that ``input_wait_share.train`` times from
outside the work."""

from bench import spans


def read(r):
    return spans.share(r, spans.NEXT_BATCH)
