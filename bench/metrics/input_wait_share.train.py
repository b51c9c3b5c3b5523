"""Share of the window the train loop spent outside its steps, waiting on
the next batch: 1 - (sum of ``LoopResult.step_times``) / window."""


def read(r):
    c = r.counters
    if r.kind != "train" or not c.get("steps"):
        return None
    return 100.0 * (1.0 - c["step_time_sum_s"] / c["window_s"])
