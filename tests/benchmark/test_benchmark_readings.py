"""The arithmetic of readings, and the generators' grids and sessions."""
import collections

import numpy as np
import pytest

import bench_paths
from harness import grids, readings, spec

BENCH = spec.Benchmark(bench_paths.ROOT)


def _readings(durations, tokens=100):
    out, t = [], 10.0
    for d in durations:
        out.append(readings.Reading(t, t + d, tokens))
        t += d
    return out


def test_a_stall_moves_the_rate_by_its_whole_length_and_the_median_not():
    steady = _readings([1.0] * 20)
    stalled = _readings([1.0] * 10 + [1.5] + [1.0] * 9)
    # the end-to-end rate: all tokens over all time, so 2.4 % lower here
    assert readings.wall_rate(steady) == pytest.approx(100.0)
    assert readings.wall_rate(stalled) == pytest.approx(2000 / 20.5)
    # the per-layer view beside it does not move
    assert readings.median_rate(steady) == readings.median_rate(stalled) \
        == pytest.approx(100.0)
    assert readings.wall_rate([]) is None and readings.median_rate([]) is None


def test_slow_readings_are_those_over_a_factor_of_the_median():
    seconds = [1.0] * 10 + [1.19, 1.21, 4.2]
    assert readings.slow(seconds, readings.SLOW_READING_FACTOR) == [1.21, 4.2]
    ticks = [0.27, 0.48, 0.48, 0.48, 0.77, 0.97, 3.5]    # median 0.48
    assert readings.slow(ticks, readings.SLOW_TICK_FACTOR) == [0.97, 3.5]
    assert readings.slow([], 1.2) == []


def test_tick_aligned_readings_never_cut_a_tick():
    ends = [0.0, 0.9, 1.8, 2.7, 3.6, 4.5, 5.4, 6.3, 7.2, 8.1]
    counts = [99, 10, 10, 10, 10, 10, 10, 10, 10, 10]
    out = readings.tick_aligned(ends, counts, 3.0)
    assert [(r.start, r.end, r.tokens) for r in out] == [
        (0.0, 3.6, 40), (3.6, 7.2, 40)]
    assert readings.tick_aligned([], [], 3.0) == []


def test_nearest_rank_and_summary():
    values = list(range(1, 101))
    assert readings.nearest_rank(values, 95) == 95
    assert readings.nearest_rank([5.0], 95) == 5.0
    assert readings.nearest_rank([], 95) is None
    s = readings.summary([1.0, 2.0, 3.0, 4.0])
    assert (s["count"], s["min"], s["max"]) == (4, 1.0, 4.0)
    assert s["q1"] < s["median"] < s["q3"]


# ------------------------------------------------------------- generators

CHAT = BENCH.cell("gpt2-xl.chat_sessions")


def test_quantile_grid_is_fixed_and_clipped():
    spec_ = CHAT.traffic["params"]["user_message_tokens"]
    grid = grids.quantile_grid(spec_)
    assert len(grid) == 64 and grid == sorted(grid)
    assert min(grid) >= 16 and max(grid) <= 160
    assert 40 <= sorted(grid)[32] <= 56          # median 48
    assert grids.quantile_grid(spec_) == grid
    with pytest.raises(ValueError):
        grids.quantile_grid(dict(spec_, dist="pareto"))


def _play(traffic_params, seed, turns, vocab=50257, max_len=1024):
    """Drive one client with made-up replies -> its turns."""
    gen = BENCH.load_module("generators", "closed_loop_sessions")
    client = gen.make(traffic_params, seed, vocab).clients[0]
    out, reply = [], None
    for k in range(turns):
        turn = client.next_turn(reply)
        out.append((turn, reply))
        assert len(turn.prompt) + turn.max_new_tokens <= max_len
        reply = [vocab - 1 - (k % 7)] * turn.max_new_tokens
    return out


def test_every_seed_plays_the_same_lengths_with_other_tokens():
    params = CHAT.traffic["params"]
    gen = BENCH.load_module("generators", "closed_loop_sessions")

    def first_turns(seed, turns=8):
        out = {}
        for client in gen.make(params, seed, 50257).clients:
            reply, plays = None, []
            for _ in range(turns):
                turn = client.next_turn(reply)
                plays.append((len(turn.prompt), turn.max_new_tokens))
                reply = [1] * turn.max_new_tokens
            out[client._hand] = (plays, turn.prompt)
        return out

    a, b = first_turns(1), first_turns(2 ** 31 + 7)
    assert sorted(a) == sorted(b) == list(range(params["clients"]))
    for hand in a:                   # the same schedule of lengths ...
        assert a[hand][0] == b[hand][0]
        assert not np.array_equal(a[hand][1], b[hand][1])   # ... other ids
    # one pass over all hands deals every value of each grid exactly once
    dealt = sorted(budget for plays, _ in a.values() for _, budget in plays)
    assert dealt == grids.quantile_grid(params["output_tokens"])
    assert first_turns(1)[3][0] == a[3][0]


def test_deal_hands_out_every_value_once_a_pass():
    deal = grids.Deal(list(range(12)), hands=4, tag=0)
    for deal_pass in range(2):
        got = [deal.value(h, deal_pass * 3 + i)
               for h in range(4) for i in range(3)]
        assert sorted(got) == list(range(12))
    again = grids.Deal(list(range(12)), hands=4, tag=0)
    assert [deal.value(1, k) for k in range(6)] == \
        [again.value(1, k) for k in range(6)]
    other = grids.Deal(list(range(12)), hands=4, tag=1)    # not in step
    assert [deal.value(1, k) for k in range(6)] != \
        [other.value(1, k) for k in range(6)]
    with pytest.raises(ValueError):
        grids.Deal(list(range(10)), hands=4, tag=0)


def test_session_history_holds_the_generated_tokens_and_ends_at_the_limit():
    params = CHAT.traffic["params"]
    turns = _play(params, seed=5, turns=40)
    system = turns[0][0].prompt[:params["system_prompt_tokens"]]
    fresh = 0
    for (turn, reply), (prev, _) in zip(turns[1:], turns):
        assert np.array_equal(turn.prompt[:len(system)], system)
        if turn.turn_index == 0:
            fresh += 1
            continue
        # the earlier prompt, then the engine's reply, then the new message
        n = len(prev.prompt)
        assert np.array_equal(turn.prompt[:n], prev.prompt)
        assert list(turn.prompt[n:n + len(reply)]) == reply
        assert len(turn.prompt) > n + len(reply)
        assert (len(turn.prompt) + turn.max_new_tokens
                <= params["session_token_limit"])
    assert fresh >= 2                      # sessions do end and start anew
    again = _play(params, seed=5, turns=40)
    assert all(np.array_equal(a[0].prompt, b[0].prompt)
               for a, b in zip(turns, again))


def test_stall_readers_count_what_the_median_readers_do_not_see():
    record = {"reading_seconds": [0.976] * 40 + [4.214], "steps_per_reading": 1,
              "tick_seconds": [0.3, 0.5, 0.5, 0.7, 2.9]}
    assert BENCH.layer_reader("slow_readings")(record, None) == 1.0
    assert BENCH.layer_reader("slow_ticks")(record, None) == 1.0
    assert BENCH.layer_reader("step_ms_p50")(record, None) == \
        pytest.approx(976.0)
    assert BENCH.layer_reader("tick_ms_p50")(record, None) == \
        pytest.approx(500.0)
    quiet = {"reading_seconds": [1.0, 1.01, 0.99], "tick_seconds": []}
    assert BENCH.layer_reader("slow_readings")(quiet, None) == 0.0
    assert BENCH.layer_reader("slow_ticks")(quiet, None) is None


def test_memory_on_the_last_line_is_the_allocators_counters_alone():
    from harness import device, readers

    class Chip:
        def __init__(self, **stats):
            self._stats = dict(stats, bytes_limit=16_000)

        def memory_stats(self):
            return self._stats

    # a train step: live state 4,300, program temporaries reserved 9,000
    train = device.memory_report([
        Chip(peak_bytes_in_use=4_310, bytes_in_use=4_300, bytes_reserved=9_000,
             peak_bytes_reserved=9_500),
        Chip(peak_bytes_in_use=4_200, bytes_in_use=4_200, bytes_reserved=9_000)])
    assert train["memory_peak_bytes"] == 13_300      # in use + reserved, now
    assert train["allocator_stats"]["peak_bytes_reserved"] == 9_500
    assert readers.hbm_peak_pct({"memory": train}, None) == \
        pytest.approx(100 * 13_300 / 16_000)
    # a peak of live buffers in set-up that was larger than what is held now
    early = device.memory_report([
        Chip(peak_bytes_in_use=10_700, bytes_in_use=6_300, bytes_reserved=0)])
    assert early["memory_peak_bytes"] == 10_700

    class NoStats:
        def memory_stats(self):
            return None

    nothing = device.memory_report([NoStats()])
    assert nothing["memory_peak_bytes"] == 0
    assert readers.hbm_peak_pct({"memory": nothing}, None) is None


def test_train_tokens_same_seed_same_inputs():
    gen = BENCH.load_module("generators", "train_tokens")
    params = {"pool_batches": 2, "global_batch": 3, "seq_len": 8}
    a, b = gen.generate(params, 2 ** 31 + 5, 100), \
        gen.generate(params, 2 ** 31 + 5, 100)
    assert a.shape == (6, 9) and a.dtype == np.int32
    assert np.array_equal(a, b) and a.max() < 100
    assert not np.array_equal(a, gen.generate(params, 6, 100))
