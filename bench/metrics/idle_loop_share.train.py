"""Share of the traced training window in which no op ran on the device
while the loop was inside neither ``repro.train.next_batch`` nor
``repro.train.sync``: dispatch, the fence, the loop's bookkeeping and any
stall between steps.  Averaged over the chips."""

from bench import spans


def read(r):
    split = spans.idle_split(r)
    return None if split is None else split["loop"]
