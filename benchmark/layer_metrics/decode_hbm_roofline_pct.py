"""The decode program's share of the chip's memory bandwidth: the bytes its
steps MUST move over the time the device spent on it, times the published
HBM bandwidth.

The bytes are the family's (``decode_step_bytes``: the weights once a step,
every live slot's recurrent and convolution state read and written once,
every live slot's K/V up to its position), made from what the program's
spans say each dispatch held: ``steps`` and ``cached_tokens`` on
``serve.decode_dispatch``, ``live_steps`` (live slot-steps) on
``serve.decode_fetch``.

The time is the decode program's own.  The scheduler dispatches a tick's
admitting windows last of its windows, then the decode program, and reads
the admitting windows' tokens only after (``serve.first_token_read``): the
last of those reads returns when the last window before the decode program
has run, and ``serve.decode_fetch`` returns when the decode program has, so
the program ran from the one to the other (with the copy of one state
snapshot, 0.2 ms, ahead of it).  Only dispatches with such a read that
WAITED (0.5 ms or more: the host was there when the window ended) are
measured; the others are counted and left out, with their bytes.  A lower
bound on the traffic over the program's own time, so it cannot pass 100.  A
program without those spans (a parent commit), a family without the byte
count, a CPU: nothing is reported."""
import os

from harness import peaks, program_spans, spec

NAME = "decode_hbm_roofline_pct"
WAITED_US = 500.0


def cell_family():
    """(family, configuration) of the cells that list this metric in
    ``BENCHMARK.json``: a reader is handed the record and the trace, not
    the cell.  None unless they share one configuration whose family
    counts a decode step's bytes."""
    root = os.path.dirname(os.path.abspath(__file__))
    while not os.path.isfile(os.path.join(root, "BENCHMARK.json")):
        root, last = os.path.dirname(root), root
        if root == last:
            return None
    bench = spec.Benchmark(root)
    listed = next((m.get("workloads") or [] for m in bench.doc["per_layer"]
                   if m["name"] == NAME), [])
    cells = [bench.cell(name) for name in listed]
    if len({c.config_name for c in cells}) != 1:
        return None
    family = bench.family(cells[0])
    if not hasattr(family.module, "decode_step_bytes"):
        return None
    return family, cells[0].config


def decode_dispatches(window):
    """Per decode dispatch of the window: (seconds the program ran or None
    where no read marks its start, steps, live slot-steps, cached tokens,
    active slots), or None where the program's spans do not say."""
    spans, out = window.spans, []
    for tick in window.units:
        kids = window.children.get(tick, ())
        named = {spans[c].name: spans[c] for c in kids}
        dispatch = named.get("serve.decode_dispatch")
        fetch = named.get("serve.decode_fetch")
        if dispatch is None or fetch is None:
            continue
        if ("cached_tokens" not in dispatch.args
                or "live_steps" not in fetch.args):
            return None
        reads = [spans[g] for c in kids for g in window.children.get(c, ())
                 if spans[g].name == "serve.first_token_read"
                 and dispatch.end_us <= spans[g].start_us <= fetch.start_us]
        last = max(reads, key=lambda s: s.end_us, default=None)
        ran = ((fetch.end_us - last.end_us) / 1e6
               if last is not None
               and last.end_us - last.start_us >= WAITED_US else None)
        out.append((ran, int(dispatch.args["steps"]),
                    int(fetch.args["live_steps"]),
                    int(dispatch.args["cached_tokens"]),
                    int(dispatch.args["active"])))
    return out or None


def window_bytes(record, trace, measured_only=False):
    """(bytes by term summed over the window's decode steps, seconds the
    measured dispatches' programs ran, dispatches measured, dispatches), or
    None.  ``measured_only`` leaves out the bytes of dispatches whose time
    is not known."""
    window = program_spans.window(record, trace)
    found = cell_family() if window is not None else None
    if found is None:
        return None
    family, config = found
    dispatches = decode_dispatches(window)
    if dispatches is None:
        return None
    total = {"weights": 0.0, "recurrent_state": 0.0, "kv": 0.0}
    seconds, measured = 0.0, 0
    for ran, steps, live_steps, cached, active in dispatches:
        if ran is not None:
            seconds += ran
            measured += 1
        elif measured_only:
            continue
        # a slot is live for live_steps / active of the dispatch's steps on
        # average; its K/V is read once for each of them
        live = live_steps / steps
        step = family.decode_step_bytes(
            config, live, cached * live / max(active, 1))
        for term in total:
            total[term] += steps * step[term]
    return total, seconds, measured, len(dispatches)


def read(record, trace):
    found = window_bytes(record, trace, measured_only=True)
    if found is None or record["platform"] == "cpu" or not found[1]:
        return None
    total, seconds, measured, dispatches = found
    peak = peaks.peak_for(record["device_kind"]).hbm_bytes_per_s
    return program_spans.report(
        NAME, 100.0 * sum(total.values()) / (seconds * peak),
        bytes_by_term=total, decode_program_s=seconds,
        dispatches_measured=measured, dispatches=dispatches,
        peak_hbm_bytes_per_s=peak)
