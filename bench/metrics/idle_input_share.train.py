"""Share of the traced training window in which no op ran on the device
while the loop was inside ``repro.train.next_batch``: the device waiting on
the producer.  Averaged over the chips."""

from bench import spans


def read(r):
    split = spans.idle_split(r)
    return None if split is None else split["input"]
