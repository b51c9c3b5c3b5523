"""Share of the traced training window in which no op ran on the device
while the loop was inside ``repro.train.sync`` (blocked on the step's
loss): the device's own gaps within a step.  Averaged over the chips."""

from bench import spans


def read(r):
    split = spans.idle_split(r)
    return None if split is None else split["sync"]
