"""Device time of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute events over the traced segment, mean over devices; 0 on
one chip."""


def read(record, trace):
    if trace is None or trace.window_s <= 0 or not trace.devices:
        return None
    return 100.0 * trace.collective_s / trace.window_s
