"""obs/ telemetry layer tests: Chrome-trace validity, Prometheus text
round-trip, the /metrics + /healthz endpoint, in-graph device health,
and the end-to-end TrainSession acceptance path (TraceHook +
MetricsExportHook + RetraceGuard retrace instants + a live scrape).
"""
import json
import math
import os
import sys
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu import data, obs, ops, optim, train
from distributed_tensorflow_tpu.obs import device as obs_device
from distributed_tensorflow_tpu.obs import reqtrace
from distributed_tensorflow_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode("utf-8")


# ------------------------------------------------------------- tracing

class TestTrace:
    def test_chrome_trace_json_valid(self, tmp_path):
        t = obs.Tracer(enabled=True, pid=3, host="hostX")
        with t.span("dispatch", step=1):
            pass
        t.add_span("data_load", 10.0, 20.0, step=2)
        t.instant("retrace", fn="step", arg_diff="~ x: f32[2] -> f32[3]")
        path = t.save(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        by_name = {e["name"]: e for e in events}
        # metadata record carries the host label for multi-host merging
        assert by_name["process_name"]["ph"] == "M"
        assert "hostX" in by_name["process_name"]["args"]["name"]
        assert by_name["dispatch"]["ph"] == "X"
        assert by_name["dispatch"]["dur"] >= 0
        assert by_name["data_load"]["dur"] == pytest.approx(10.0)
        assert by_name["retrace"]["ph"] == "i"
        assert all(e["pid"] == 3 for e in events)
        # every non-metadata event is timestamped (merge-sortable)
        assert all("ts" in e for e in events if e["ph"] != "M")

    def test_disabled_tracer_records_nothing(self):
        t = obs.Tracer(enabled=False)
        with t.span("dispatch"):
            pass
        t.instant("retrace")
        assert [e for e in t.events() if e["ph"] != "M"] == []

    def test_active_tracer_module_sink(self):
        t = obs.Tracer(enabled=True)
        obs_trace.instant("orphan")          # no active tracer: no-op
        with obs_trace.activated(t):
            obs_trace.instant("mark", k=1)
            with obs_trace.span("s"):
                pass
        obs_trace.instant("after")           # deactivated again
        names = [e["name"] for e in t.events()
                 if e["ph"] != "M" and e["name"] != obs_trace.GC]
        assert names == ["mark", "s"]
        assert t.instant_counts == {"mark": 1}


# ------------------------------------------------------------- metrics

class TestMetrics:
    def test_exposition_roundtrips_prometheus_text(self):
        reg = obs.Registry()
        reg.counter("requests_total", "Requests.",
                    labels={"path": "a"}).inc(3)
        reg.counter("requests_total", "Requests.",
                    labels={"path": "b"}).inc()
        reg.gauge("temp_celsius", "Temp.").set(-1.5)
        h = reg.histogram("lat_seconds", "Latency.", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        text = reg.expose()
        parsed = obs.parse_exposition(text)
        assert parsed["requests_total"]["type"] == "counter"
        assert parsed["requests_total"]["samples"][
            ("requests_total", (("path", "a"),))] == 3.0
        assert parsed["requests_total"]["samples"][
            ("requests_total", (("path", "b"),))] == 1.0
        assert parsed["temp_celsius"]["samples"][
            ("temp_celsius", ())] == -1.5
        hs = parsed["lat_seconds"]["samples"]
        # cumulative buckets + +Inf + sum/count — the full histogram law
        assert hs[("lat_seconds_bucket", (("le", "0.1"),))] == 1.0
        assert hs[("lat_seconds_bucket", (("le", "1"),))] == 3.0
        assert hs[("lat_seconds_bucket", (("le", "+Inf"),))] == 4.0
        assert hs[("lat_seconds_count", ())] == 4.0
        assert hs[("lat_seconds_sum", ())] == pytest.approx(6.05)

    def test_get_or_create_shares_series_and_checks_types(self):
        reg = obs.Registry()
        a = reg.counter("steps_total", "Steps.")
        b = reg.counter("steps_total")
        assert a is b
        a.inc(2)
        assert b.value == 2
        with pytest.raises(ValueError):
            reg.gauge("steps_total")
        with pytest.raises(ValueError):
            reg.counter("bad name!")
        with pytest.raises(ValueError):
            a.inc(-1)

    def test_histogram_quantile_estimate(self):
        h = obs.Histogram("h", "", (), buckets=(0.01, 0.1, 1.0))
        assert math.isnan(h.quantile(0.5))
        for _ in range(9):
            h.observe(0.05)
        h.observe(5.0)
        assert h.quantile(0.5) == 0.1
        assert h.quantile(0.99) == float("inf")


# ---------------------------------------------------------------- http

class TestHttp:
    def test_metrics_and_healthz_endpoints(self):
        reg = obs.Registry()
        reg.counter("ticks_total", "Ticks.").inc(7)
        server = obs.MetricsServer(reg, port=0,
                                   health_fn=lambda: {"status": "ok",
                                                      "replica": 2})
        server.start()
        try:
            assert server.port != 0   # ephemeral port resolved
            status, text = _get(server.url + "/metrics")
            assert status == 200
            parsed = obs.parse_exposition(text)
            assert parsed["ticks_total"]["samples"][
                ("ticks_total", ())] == 7.0
            status, body = _get(server.url + "/healthz")
            assert status == 200
            assert json.loads(body)["replica"] == 2
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.url + "/nope")
            assert e.value.code == 404
        finally:
            server.stop()

    def test_statusz_debug_snapshot(self):
        """/statusz merges the live obs sinks (goodput split, tracer
        occupancy, reqtrace ring) with whatever statusz_fn contributes —
        the curl-a-wedged-process endpoint (docs/OBSERVABILITY.md)."""
        from distributed_tensorflow_tpu.obs import goodput as goodput_lib
        acct = goodput_lib.GoodputAccountant()
        tracer = obs_trace.Tracer(enabled=True)
        tracer.instant("retrace", fn="step")
        server = obs.MetricsServer(
            obs.Registry(), port=0,
            statusz_fn=lambda: {"engine": {"running": 3,
                                           "waiting": 1}}).start()
        try:
            with obs_trace.activated(tracer), \
                    goodput_lib.activated(acct):
                with goodput_lib.account("step"):
                    pass
                status, body = _get(server.url + "/statusz")
            assert status == 200
            doc = json.loads(body)
            gp = doc["goodput"]
            assert set(gp["buckets_s"]) == set(goodput_lib.BUCKETS)
            assert gp["wall_s"] >= gp["buckets_s"]["step"] >= 0.0
            assert doc["trace"]["events"] >= 1
            assert doc["trace"]["instant_counts"]["retrace"] == 1
            # a tracer is active inside the with-block, so reqtrace
            # minting reports enabled; the ring is untouched
            assert doc["reqtrace"]["enabled"] is True
            assert doc["reqtrace"]["live"] == 0
            # the statusz_fn extras (Engine.stats() in serving) merge in
            assert doc["engine"] == {"running": 3, "waiting": 1}

            # with every sink inactive, the endpoint still answers
            status, body = _get(server.url + "/statusz")
            doc = json.loads(body)
            assert status == 200 and "goodput" not in doc
        finally:
            server.stop()

    def test_statusz_fn_failure_is_500_not_a_crash(self):
        def broken():
            raise RuntimeError("stats wedged")

        server = obs.MetricsServer(obs.Registry(), port=0,
                                   statusz_fn=broken).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.url + "/statusz")
            assert e.value.code == 500
            assert "wedged" in e.value.read().decode()
        finally:
            server.stop()

    def test_healthz_failure_is_503_not_a_crash(self):
        def sick():
            raise RuntimeError("replica wedged")

        server = obs.MetricsServer(obs.Registry(), port=0,
                                   health_fn=sick).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(server.url + "/healthz")
            assert e.value.code == 503
            assert "wedged" in e.value.read().decode()
        finally:
            server.stop()


# --------------------------------------------- exposition round-trip

class TestExpositionRoundTrip:
    """parse_exposition/render_exposition must be exact duals —
    including +Inf histogram buckets and escaped label values, the two
    spots where a lossy pass would silently corrupt a federated proxy.
    No hypothesis in the image, so "property test" = seeded random
    adversarial cases + the parse∘render fixpoint law on each."""

    ALPHABET = ['a', 'Z', '0', ' ', '"', "\\", "\n", "n",
                "\\n", "\\\\", 'x"y', "µ", "{", "}", "=", ","]

    def _random_families(self, rng):
        fams = {}
        for fi in range(rng.randrange(1, 4)):
            name = f"dttpu_prop_{fi}_total"
            samples = {}
            for si in range(rng.randrange(1, 4)):
                labels = tuple(sorted(
                    (f"l{li}", "".join(rng.choice(self.ALPHABET)
                                       for _ in range(rng.randrange(0, 6))))
                    for li in range(rng.randrange(0, 3))))
                value = rng.choice(
                    [0.0, -1.5, 3e18, float("inf"), float("-inf"),
                     rng.random()])
                samples[(name, labels)] = value
            # help is "rest of line": trailing SPACES can't survive a
            # line-stripping parser (escaped \n and \\ do) — rstrip
            # them; label VALUES stay fully adversarial, they're quoted
            help_text = "".join(rng.choice(self.ALPHABET)
                                for _ in range(5)).rstrip(" ")
            fams[name] = {"type": rng.choice(["counter", "gauge"]),
                          "help": help_text,
                          "samples": samples}
        return fams

    def test_random_families_survive_parse_render_parse(self):
        import random
        rng = random.Random(0xD77)
        for _ in range(50):
            fams = self._random_families(rng)
            text = obs.render_exposition(fams)
            parsed = obs.parse_exposition(text)
            for fam, entry in fams.items():
                assert parsed[fam]["samples"] == entry["samples"], text
                assert parsed[fam]["help"] == entry["help"], text
            # the fixpoint law: one more render/parse round changes
            # nothing (what lets the federation re-proxy a proxy)
            again = obs.parse_exposition(obs.render_exposition(parsed))
            assert again == parsed

    def test_inf_buckets_and_escapes_roundtrip_through_registry(self):
        reg = obs.Registry()
        h = reg.histogram("dttpu_prop_lat_seconds", "Latency.",
                          buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        reg.counter("dttpu_prop_req_total", 'Say "hi"\nback\\slash.',
                    labels={"path": 'a\\n"b"\nc'}).inc()
        text = reg.expose()
        parsed = obs.parse_exposition(text)
        hs = parsed["dttpu_prop_lat_seconds"]["samples"]
        assert hs[("dttpu_prop_lat_seconds_bucket",
                   (("le", "+Inf"),))] == 3.0
        assert parsed["dttpu_prop_req_total"]["samples"][
            ("dttpu_prop_req_total",
             (("path", 'a\\n"b"\nc'),))] == 1.0
        # literal-backslash-then-n must NOT decode as newline, and the
        # second round trip must agree with the first exactly
        assert obs.parse_exposition(
            obs.render_exposition(parsed)) == parsed

    def test_adjacent_escape_sequences_decode_single_pass(self):
        # ``\\n`` (escaped backslash, then literal n) was the v3 bug:
        # a sequential .replace() chain ate the backslash it decoded
        reg = obs.Registry()
        reg.gauge("dttpu_prop_g", "G.", labels={"v": "\\n"}).set(1)
        parsed = obs.parse_exposition(reg.expose())
        assert parsed["dttpu_prop_g"]["samples"][
            ("dttpu_prop_g", (("v", "\\n"),))] == 1.0

    def test_extra_labels_stamp_and_override(self):
        reg = obs.Registry()
        reg.counter("dttpu_prop_c", "C.", labels={"replica": "9",
                                                  "path": "a"}).inc(2)
        text = obs.render_exposition(obs.parse_exposition(reg.expose()),
                                     extra_labels={"replica": "0"})
        parsed = obs.parse_exposition(text)
        assert parsed["dttpu_prop_c"]["samples"][
            ("dttpu_prop_c", (("path", "a"), ("replica", "0")))] == 2.0
        with pytest.raises(ValueError):
            obs.render_exposition({}, extra_labels={"bad name!": "x"})


# -------------------------------------------------------- device health

class TestDeviceHealth:
    def test_grad_health_in_graph_counts_nonfinite(self):
        grads = {"a": jnp.asarray([3.0, 4.0]),
                 "b": jnp.asarray([[float("nan"), float("inf")],
                                   [0.0, 0.0]])}

        @jax.jit
        def health(g):
            return obs_device.grad_health(g)

        out = health(grads)
        assert float(out[obs_device.NONFINITE_KEY]) == 2.0
        assert not math.isfinite(float(out[obs_device.GRAD_NORM_KEY]))
        clean = health({"a": jnp.asarray([3.0, 4.0])})
        assert float(clean[obs_device.GRAD_NORM_KEY]) == pytest.approx(5.0)
        assert float(clean[obs_device.NONFINITE_KEY]) == 0.0

    def test_train_step_device_health_rides_metrics_dict(self):
        model = ops.serial(ops.Dense(8, "relu"), ops.Dense(32, "sigmoid"))
        opt = optim.adam()
        state = train.init_train_state(model, opt, jax.random.PRNGKey(0),
                                       (64,))
        step = train.make_train_step(model, "mse", opt, device_health=True)
        (xt, yt), _ = data.xor_data(100, val_size=10, seed=0)
        state, m = step(state, (xt[:50], yt[:50]))
        assert float(m[obs_device.GRAD_NORM_KEY]) > 0
        assert float(m[obs_device.NONFINITE_KEY]) == 0.0

    def test_live_arrays_bytes_counts_new_buffer(self):
        before = obs_device.live_arrays_bytes()
        keep = jnp.ones((256, 256), jnp.float32)
        keep.block_until_ready()
        after = obs_device.live_arrays_bytes()
        assert after - before >= 256 * 256 * 4
        del keep


# ------------------------------------------------- end-to-end acceptance

def test_session_telemetry_end_to_end(tmp_path):
    """ISSUE 3 acceptance: a short TrainSession run with TraceHook +
    MetricsExportHook yields (a) valid Chrome trace JSON containing
    dispatch and retrace events and (b) a live /metrics scrape showing
    the step counter and the step-time histogram."""
    from distributed_tensorflow_tpu.analysis.sanitizer import RetraceGuard

    tele = obs.Telemetry(trace_dir=str(tmp_path), metrics_port=0)
    (xt, yt), _ = data.xor_data(200, val_size=10, seed=0)
    with RetraceGuard(budget=1, mode="warn",
                      stream=open("/dev/null", "w")) as guard:
        model = ops.serial(ops.Dense(16, "relu"), ops.Dense(32, "sigmoid"))
        opt = optim.adam()
        state = train.init_train_state(model, opt, jax.random.PRNGKey(0),
                                       (64,))
        # built INSIDE the guard: traces are counted and mirrored onto
        # the active tracer as jit_compile/retrace instants
        step = train.make_train_step(model, "mse", opt, device_health=True)
        with train.TrainSession(
                state, step, telemetry=tele,
                hooks=[train.TraceHook(tele),
                       train.MetricsExportHook(tele, every_steps=1,
                                               examples_per_step=50),
                       train.StopAtStepHook(4)]) as sess:
            n = 0
            while not sess.should_stop():
                # last batch changes shape: a real retrace, on purpose
                b = (xt[:30], yt[:30]) if n == 3 else (xt[:50], yt[:50])
                sess.run_step(b)
                n += 1
        status, text = _get(tele.metrics_url())
    tele.close()
    assert guard.violations, "the shape change must have retraced"

    # (a) the trace file is valid Chrome trace JSON with the span/instant
    # vocabulary docs/OBSERVABILITY.md documents
    doc = json.load(open(tele.trace_path))
    events = doc["traceEvents"]
    names = {}
    for e in events:
        names[e["name"]] = names.get(e["name"], 0) + 1
    assert names["train.dispatch"] == 4  # one per run_step, from session
    assert names["train.step"] == 4      # the whole run_step, likewise
    assert "step" not in names and "data_load" not in names  # no seconds
    assert names["jit_compile"] >= 1     # first trace instant
    assert names["retrace"] == 1         # the shape-change recompile
    retrace = next(e for e in events if e["name"] == "retrace")
    assert "arg_diff" in retrace["args"]         # actionable, not forensic
    assert "[30,64]" in retrace["args"]["arg_diff"]
    steps_args = sorted(e["args"]["step"] for e in events
                        if e["name"] == "train.step")
    assert steps_args == [1, 2, 3, 4]

    # (b) the live scrape carried the step counter + step-time histogram
    assert status == 200
    parsed = obs.parse_exposition(text)
    assert parsed["dttpu_steps_total"]["type"] == "counter"
    assert parsed["dttpu_steps_total"]["samples"][
        ("dttpu_steps_total", ())] == 4.0
    hist = parsed["dttpu_step_time_seconds"]
    assert hist["type"] == "histogram"
    assert hist["samples"][("dttpu_step_time_seconds_count", ())] == 4.0
    assert hist["samples"][("dttpu_step_time_seconds_sum", ())] > 0
    # throughput, retrace count, device health, memory gauge all exported
    assert parsed["dttpu_examples_per_second"]["samples"][
        ("dttpu_examples_per_second", ())] > 0
    assert parsed["dttpu_retraces_total"]["samples"][
        ("dttpu_retraces_total", ())] == 1.0
    assert parsed["dttpu_live_arrays_bytes"]["samples"][
        ("dttpu_live_arrays_bytes", ())] > 0
    assert ("dttpu_grad_norm", ()) in parsed["dttpu_grad_norm"]["samples"]


def test_telemetry_checkpoint_span_and_duration(tmp_path):
    """session.save() under telemetry: a 'checkpoint' span lands on the
    timeline and the save-duration histogram observes it."""
    model = ops.serial(ops.Dense(8, "relu"), ops.Dense(32, "sigmoid"))
    opt = optim.adam()
    state = train.init_train_state(model, opt, jax.random.PRNGKey(0), (64,))
    step = train.make_train_step(model, "mse", opt)
    (xt, yt), _ = data.xor_data(100, val_size=10, seed=0)
    tele = obs.Telemetry(trace_dir=str(tmp_path))
    with train.TrainSession(state, step, checkpoint_dir=str(tmp_path / "ck"),
                            telemetry=tele,
                            hooks=[train.StopAtStepHook(2)]) as sess:
        while not sess.should_stop():
            sess.run_step((xt[:50], yt[:50]))
    tele.close()
    doc = json.load(open(tele.trace_path))
    assert any(e["name"] == "checkpoint" for e in doc["traceEvents"])
    h = tele.registry.get("dttpu_checkpoint_save_seconds")
    assert h is not None and h.count >= 1


def test_telemetry_off_is_inert(tmp_path):
    """No trace_dir, no metrics_port: spans are no-ops, nothing is
    written, and the session hot path takes the telemetry-off branch."""
    tele = obs.Telemetry()
    assert tele.trace_path is None and tele.metrics_url() is None
    with tele.tracer.span("dispatch"):
        pass
    assert tele.save_trace() is None
    assert [e for e in tele.tracer.events() if e["ph"] != "M"] == []
    tele.close()


# ------------------------------------------------------ request tracing

class TestReqtrace:
    """obs.reqtrace unit tier: minting gates, lane lifecycle, migration
    stitching, forensics.  The integration tier (real scheduler through
    a double migration) lives in tests/test_migration.py."""

    @pytest.fixture(autouse=True)
    def _clean(self):
        reqtrace.reset()
        yield
        reqtrace.reset()

    def test_mint_gates_on_tracer_and_configure(self):
        assert not reqtrace.enabled()
        assert reqtrace.mint() is None          # no active tracer
        t = obs_trace.activate(obs.Tracer(enabled=True))
        try:
            assert reqtrace.enabled()
            tid = reqtrace.mint()
            assert tid is not None and tid.startswith("req-")
            assert reqtrace.mint() != tid       # sequence advances
            reqtrace.configure(enabled=False)
            assert reqtrace.mint() is None      # the bench's off arm
            reqtrace.configure(enabled=True)
            assert reqtrace.mint("sim").startswith("sim-")
        finally:
            obs_trace.deactivate(t)

    def test_lifecycle_lane_rings_and_trees(self):
        tid = "req-t-000001"
        reqtrace.submitted(tid, ts_us=0.0, rid=1, plen=7)
        reqtrace.stage(tid, "prefill", ts_us=10.0)
        reqtrace.mark(tid, "first_token", ts_us=15.0, ttft_s=1.5e-5)
        reqtrace.stage(tid, "decode", ts_us=15.0)
        assert reqtrace.live_ids() == [tid]
        reqtrace.retired(tid, "ok", ts_us=40.0, tokens=3)
        assert reqtrace.live_ids() == []
        (rec,) = reqtrace.completed()
        assert rec["status"] == "ok" and rec["hops"] == 0
        # every async event shares the one (cat, id) pair — the track key
        assert {(e["cat"], e["id"]) for e in rec["events"]} == {
            ("request", tid)}
        t = reqtrace.tree(tid)
        (root,) = t["spans"]
        assert root["name"] == "request"
        assert root["start_us"] == 0.0 and root["end_us"] == 40.0
        assert [c["name"] for c in root["children"]] == [
            "queued", "prefill", "decode"]
        assert [m["name"] for m in root["children"][1]["marks"]] == [
            "first_token"]
        assert root["args"]["status"] == "ok"

    def test_migrated_lane_is_one_contiguous_tree(self):
        tid = "req-t-000002"
        reqtrace.submitted(tid, ts_us=0.0)
        reqtrace.stage(tid, "prefill", ts_us=5.0)
        reqtrace.exported(tid, ts_us=9.0, generated=2)
        reqtrace.retired(tid, "migrated", ts_us=9.0)   # no-op: lane open
        assert reqtrace.live_ids() == [tid]
        reqtrace.imported(tid, ts_us=11.0, resumed=2)
        reqtrace.stage(tid, "decode", ts_us=14.0)
        reqtrace.retired(tid, "ok", ts_us=20.0)
        rec = reqtrace.lookup(tid)
        assert rec["hops"] == 1 and rec["status"] == "ok"
        # exactly one flow arrow: s (export, binding-point e) then f
        flow = [(e["ph"], e.get("bp")) for e in rec["events"]
                if e["cat"] == "migration"]
        assert flow == [("s", "e"), ("f", None)]
        t = reqtrace.tree(tid)
        (root,) = t["spans"]                  # ONE root: one lane
        assert [c["name"] for c in root["children"]] == [
            "queued", "prefill", "queued", "decode"]
        assert all(c["end_us"] is not None for c in root["children"])
        assert [m["name"] for m in root["marks"]] == [
            "exported", "imported"]

    def test_events_forward_to_active_tracer(self):
        t = obs_trace.activate(obs.Tracer(enabled=True))
        try:
            tid = reqtrace.mint()
            reqtrace.submitted(tid)
            reqtrace.retired(tid, "ok")
        finally:
            obs_trace.deactivate(t)
        evs = [e for e in t.events() if e.get("cat") == "request"]
        assert [e["ph"] for e in evs] == ["b", "b", "e", "e"]
        assert {e["id"] for e in evs} == {tid}

    def test_forensic_dump_snapshots_live_victim(self):
        tid = "req-t-000003"
        reqtrace.submitted(tid, ts_us=0.0)
        reqtrace.stage(tid, "prefill", ts_us=3.0)
        entry = reqtrace.forensic_dump(tid, "watchdog_quarantine",
                                       replica=4)
        assert entry["reason"] == "watchdog_quarantine"
        assert entry["context"] == {"replica": 4}
        (root,) = entry["spans"]
        assert root["end_us"] is None          # still live when dumped
        assert root["children"][-1]["name"] == "prefill"
        assert reqtrace.forensics_log()[-1]["trace_id"] == tid
        assert reqtrace.forensic_dump("req-unknown", "x") is None

    def test_ring_is_bounded(self):
        reqtrace.configure(ring=4)
        for i in range(9):
            tid = f"req-t-{i:06x}"
            reqtrace.submitted(tid, ts_us=0.0)
            reqtrace.retired(tid, "ok", ts_us=1.0)
        ids = [r["trace_id"] for r in reqtrace.completed()]
        assert len(ids) == 4 and ids[-1] == "req-t-000008"


# --------------------------------------------------------- merge_traces

class TestMergeTraces:
    def _merge_mod(self):
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import merge_traces
        finally:
            sys.path.pop(0)
        return merge_traces

    def _host_doc(self, pid, tid):
        meta = {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": f"host{pid}"}}
        return {"displayTimeUnit": "ms", "traceEvents": [
            meta, dict(meta),                 # per-file duplicate
            {"name": "request", "ph": "b", "cat": "request", "id": tid,
             "ts": 1.0 + pid, "pid": pid, "tid": 0}]}

    def test_merge_concatenates_and_dedupes_metadata(self):
        mod = self._merge_mod()
        tid = "req-abc-000001"
        merged = mod.merge([self._host_doc(0, tid),
                            self._host_doc(1, tid)])
        assert merged["displayTimeUnit"] == "ms"
        evs = merged["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        # one per (pid, name, args): in-file + cross-file dupes dropped
        assert [m["pid"] for m in metas] == [0, 1]
        lanes = [e for e in evs if e.get("cat") == "request"]
        # both hosts' async events survive with the SAME (cat, id) —
        # the stitching invariant the merge exists to preserve
        assert len(lanes) == 2
        assert {(e["cat"], e["id"]) for e in lanes} == {
            ("request", tid)}
        assert {e["pid"] for e in lanes} == {0, 1}

    def test_cli_merges_files(self, tmp_path):
        mod = self._merge_mod()
        a, b = tmp_path / "trace-host0.json", tmp_path / "trace-host1.json"
        a.write_text(json.dumps(self._host_doc(0, "req-1")))
        b.write_text(json.dumps(self._host_doc(1, "req-1")))
        out = tmp_path / "trace-fleet.json"
        assert mod.main([str(a), str(b), "-o", str(out)]) == 0
        doc = json.loads(out.read_text())
        assert len(doc["traceEvents"]) == 4   # 2 metas + 2 lane events


# ------------------------------------------------------------ federation

class TestFederatedMetrics:
    def test_registries_merge_under_distinct_replica_labels(self):
        fed = obs.FederatedMetrics()
        for i in range(2):
            reg = obs.Registry()
            reg.counter("dttpu_serve_tokens_total", "Tokens.").inc(
                10 * (i + 1))
            fed.add_registry(reg, replica=str(i))
        parsed = obs.parse_exposition(fed.expose())
        s = parsed["dttpu_serve_tokens_total"]["samples"]
        assert s[("dttpu_serve_tokens_total",
                  (("replica", "0"),))] == 10.0
        assert s[("dttpu_serve_tokens_total",
                  (("replica", "1"),))] == 20.0
        assert parsed["dttpu_federation_sources"]["samples"][
            ("dttpu_federation_sources", ())] == 3.0  # 2 regs + own

    def test_scraped_peer_and_dead_peer(self):
        peer = obs.Registry()
        peer.gauge("dttpu_serve_queue_depth", "Depth.").set(5)
        server = obs.MetricsServer(peer, port=0).start()
        fed = obs.FederatedMetrics()
        fed.add_scrape(server.url + "/metrics", host="peer0")
        try:
            parsed = obs.parse_exposition(fed.expose())
            assert parsed["dttpu_serve_queue_depth"]["samples"][
                ("dttpu_serve_queue_depth", (("host", "peer0"),))] == 5.0
        finally:
            server.stop()
        # dead peer: skipped + counted, never raises
        parsed = obs.parse_exposition(fed.expose())
        assert "dttpu_serve_queue_depth" not in parsed
        assert parsed["dttpu_federation_scrape_errors_total"]["samples"][
            ("dttpu_federation_scrape_errors_total", ())] >= 1.0

    def test_slo_gauges_from_streamed_evidence(self):
        fed = obs.FederatedMetrics()
        for i in range(100):
            fed.ingest("pro", ttft_s=0.01 * (i + 1),
                       tpot_s=0.001, ttft_ok=i < 90, itl_ok=True)
        parsed = obs.parse_exposition(fed.expose())
        pro = (("tenant", "pro"),)
        sam = lambda n: parsed[n]["samples"][(n, pro)]
        # nearest-rank percentiles over the sorted reservoir
        assert sam("dttpu_slo_ttft_p50_seconds") == pytest.approx(0.50)
        assert sam("dttpu_slo_ttft_p99_seconds") == pytest.approx(0.99)
        assert sam("dttpu_slo_tpot_p50_seconds") == pytest.approx(0.001)
        assert sam("dttpu_slo_tpot_p99_seconds") == pytest.approx(0.001)
        # verdicts pool TTFT and inter-token: (90 + 100) / 200
        assert sam("dttpu_slo_attainment") == pytest.approx(0.95)

    def test_federation_behind_metrics_server(self):
        reg = obs.Registry()
        reg.counter("dttpu_steps_total", "Steps.").inc(3)
        fed = obs.FederatedMetrics().add_registry(reg, replica="0")
        server = obs.MetricsServer(fed, port=0).start()
        try:
            status, text = _get(server.url + "/metrics")
            assert status == 200
            parsed = obs.parse_exposition(text)
            assert parsed["dttpu_steps_total"]["samples"][
                ("dttpu_steps_total", (("replica", "0"),))] == 3.0
        finally:
            server.stop()
