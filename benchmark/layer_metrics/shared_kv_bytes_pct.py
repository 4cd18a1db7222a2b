"""The shared cache's share of the bytes the window's decode steps must move
(``decode_hbm_roofline_pct`` says which bytes): the K/V of the one
full-attention layer of a decoder-hybrid-decoder, read once by each of the
layers that attend it, over all the terms the family counts (weights, the
shared K/V, the window layers' rings, the recurrent state).  The
cross-decoder's share of a decode step, as ``recurrent_state_bytes_pct`` is
the state's: it grows with every live slot's context while the weights do
not.  A family that counts no ``shared_kv`` term reports nothing."""
from harness import program_spans
from layer_metrics import decode_hbm_roofline_pct as _bytes


def read(record, trace):
    found = _bytes.window_bytes(record, trace)
    if found is None or "shared_kv" not in found[0]:
        return None
    total = found[0]
    return program_spans.report(
        "shared_kv_bytes_pct",
        100.0 * total["shared_kv"] / sum(total.values()),
        bytes_by_term=total)
