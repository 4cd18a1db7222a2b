"""Share of the window the loop spent inside ``next(batch_iterator)``: the
benchmark's ``next_batch`` span over window time."""


def read(record, trace):
    if record["window_s"] <= 0:
        return None
    return (100.0 * record["span_seconds"].get("next_batch", 0.0)
            / record["window_s"])
