"""The recurrent state's share of the bytes the window's decode steps must
move (``decode_hbm_roofline_pct`` says which bytes): every live slot's
state read and written once a step, over weights + state + K/V.  The
mechanism's share of the decode step: it grows with every live slot while
the weights do not."""
from harness import program_spans
from layer_metrics import decode_hbm_roofline_pct as _bytes


def read(record, trace):
    found = _bytes.window_bytes(record, trace)
    if found is None:
        return None
    total = found[0]
    return program_spans.report(
        "recurrent_state_bytes_pct",
        100.0 * total["recurrent_state"] / sum(total.values()),
        bytes_by_term=total)
