"""Goodput accountant tests (obs/goodput.py, docs/OBSERVABILITY.md
§Goodput): exclusive bucket accounting, module-level activation, metric
+ counter-lane export, and THE chaos acceptance — a supervised run under
corrupt_checkpoint + kill_prefetch + a forced retrace whose goodput
report's buckets sum to wall-clock within 1% with every fault-path
bucket nonzero and ``dttpu_goodput_seconds_total`` visible on
``/metrics``."""
import os
import threading
import time
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu import data, ops, optim, train
from distributed_tensorflow_tpu.analysis.sanitizer import RetraceGuard
from distributed_tensorflow_tpu.obs import goodput as goodput_lib
from distributed_tensorflow_tpu.obs import metrics as metrics_lib
from distributed_tensorflow_tpu.obs import trace as trace_lib
from distributed_tensorflow_tpu.obs.http import MetricsServer
from distributed_tensorflow_tpu.resilience import (NonfiniteGuardHook,
                                                   Supervisor)


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode("utf-8")


# ---------------------------------------------------------------------------
# accountant mechanics


class TestAccountant:
    def test_exclusive_nesting_no_double_count(self):
        """A nested frame pauses its parent: wall seconds land in
        exactly one bucket (the compile-inside-step shape)."""
        t = [0.0]
        clock = lambda: t[0]                       # noqa: E731
        acct = goodput_lib.GoodputAccountant(clock=clock).start()
        with acct.account("step"):
            t[0] += 1.0
            with acct.account("compile"):
                t[0] += 3.0
            t[0] += 0.5
        acct.stop()
        snap = acct.snapshot()
        assert snap["step"] == pytest.approx(1.5)
        assert snap["compile"] == pytest.approx(3.0)
        assert snap["other"] == pytest.approx(0.0)
        assert sum(snap.values()) == pytest.approx(acct.wall_seconds())

    def test_other_is_the_unattributed_remainder(self):
        t = [0.0]
        acct = goodput_lib.GoodputAccountant(clock=lambda: t[0]).start()
        with acct.account("step"):
            t[0] += 2.0
        t[0] += 3.0                                # untracked host time
        acct.stop()
        rep = acct.report()
        assert rep["buckets_s"]["other"] == pytest.approx(3.0)
        assert rep["wall_s"] == pytest.approx(5.0)
        assert rep["goodput_pct"] == pytest.approx(40.0)
        assert sum(rep["buckets_s"].values()) == pytest.approx(5.0)

    def test_unknown_bucket_rejected(self):
        acct = goodput_lib.GoodputAccountant()
        with pytest.raises(ValueError, match="unknown goodput bucket"):
            acct.account("lunch")
        with pytest.raises(ValueError, match="unknown goodput bucket"):
            acct.accrue("lunch", 1.0)

    def test_thread_frames_are_independent(self):
        """Per-thread stacks: a frame on a worker thread never pauses or
        resumes a frame on the main thread."""
        acct = goodput_lib.GoodputAccountant().start()
        stop = threading.Event()

        def worker():
            while not stop.is_set():
                with acct.account("data_stall"):
                    time.sleep(0.002)

        th = threading.Thread(target=worker, daemon=True)
        th.start()
        with acct.account("step"):
            time.sleep(0.05)
        stop.set()
        th.join(timeout=10)
        acct.stop()
        snap = acct.snapshot()
        assert snap["step"] >= 0.04                # not eaten by worker
        assert snap["data_stall"] > 0.0

    def test_registry_export_and_counter_lane(self):
        """Accruals land on dttpu_goodput_seconds_total{bucket=} AND as
        Chrome "C" counter events on the active tracer."""
        reg = metrics_lib.Registry()
        tracer = trace_lib.Tracer(enabled=True)
        acct = goodput_lib.GoodputAccountant(registry=reg)
        with trace_lib.activated(tracer):
            with goodput_lib.activated(acct):
                with goodput_lib.account("checkpoint_save"):
                    time.sleep(0.01)
        c = reg.get("dttpu_goodput_seconds_total",
                    labels={"bucket": "checkpoint_save"})
        assert c is not None and c.value > 0.0
        lanes = [e for e in tracer.events() if e.get("ph") == "C"]
        assert lanes and lanes[-1]["name"] == "goodput_seconds"
        assert lanes[-1]["args"]["checkpoint_save"] > 0.0

    def test_module_account_is_noop_when_inactive(self):
        goodput_lib.deactivate()
        trace_lib.deactivate()
        frame = goodput_lib.account("step")
        # no accountant, no tracer: the site's span, which is the cached
        # null span (zero alloc)
        assert frame is trace_lib.span("train.dispatch")
        assert frame is trace_lib._NULL_SPAN
        with frame:
            pass

    def test_activated_restores_previous(self):
        a, b = goodput_lib.GoodputAccountant(), \
            goodput_lib.GoodputAccountant()
        goodput_lib.activate(a)
        try:
            with goodput_lib.activated(b):
                assert goodput_lib.active() is b
            assert goodput_lib.active() is a
            assert b._stopped_at is not None       # scoped stop happened
        finally:
            goodput_lib.deactivate()


# ---------------------------------------------------------------------------
# the chaos acceptance (ISSUE 15)


def _make_bits():
    model = ops.serial(ops.Dense(16, "relu"), ops.Dense(32, "sigmoid"))
    opt = optim.adam()
    state = train.init_train_state(model, opt, jax.random.PRNGKey(0),
                                   (64,))
    step = train.make_train_step(model, "mse", opt, device_health=True,
                                 skip_nonfinite=True)
    (xt, yt), _ = data.xor_data(500, val_size=10, seed=0)
    return state, step, data.Dataset([xt, yt], 50, seed=0)


@pytest.mark.chaos
def test_chaos_goodput_report_attributes_the_whole_run(tmp_path,
                                                       activate_faults):
    """Supervisor run with corrupt_checkpoint + kill_prefetch + a forced
    retrace: the goodput report's buckets sum to wall within 1%,
    checkpoint_restore / restart_backoff / data_stall / compile are all
    nonzero, and dttpu_goodput_seconds_total is served on /metrics."""
    reg = metrics_lib.Registry()
    d = str(tmp_path)
    TARGET = 12
    activate_faults({"kind": "corrupt_checkpoint", "at": 1},
                    {"kind": "kill_prefetch", "at": 8},
                    registry=reg)

    def build_session():
        state, step, ds = _make_bits()
        sess = train.TrainSession(
            state, step, checkpoint_dir=d,
            hooks=[train.CheckpointHook(every_steps=3, every_secs=None),
                   NonfiniteGuardHook(max_consecutive=3),
                   train.StopAtStepHook(last_step=TARGET)])
        sess._chaos_ds = ds
        return sess

    retrace_me = None

    def train_fn(sess):
        nonlocal retrace_me
        if retrace_me is None:
            # jitted INSIDE the warn-mode guard window: the second,
            # differently-shaped call below is the forced retrace
            retrace_me = jax.jit(lambda x: x * 2.0)
            retrace_me(jnp.zeros((2,)))
        retrace_me(jnp.zeros((3 + int(sess.step),)))
        it = data.prefetch_to_device(iter(sess._chaos_ds.epochs(100)),
                                     size=2)
        for batch in it:
            if sess.should_stop():
                break
            sess.run_step(batch)
        return sess.state

    acct = goodput_lib.GoodputAccountant(registry=reg)
    sup = Supervisor(max_restarts=3, backoff_base=0.01, registry=reg)
    with RetraceGuard(budget=1, mode="warn", enforce_donation=False,
                      stream=open(os.devnull, "w")) as guard:
        with goodput_lib.activated(acct):
            final_state = sup.run(build_session, train_fn)

    assert int(final_state.step) == TARGET
    assert reg.get("dttpu_restarts_total").value >= 1
    assert guard.violations                        # the retrace happened

    rep = acct.report()
    buckets = rep["buckets_s"]
    # every second attributed: the split sums to wall within 1%
    assert sum(buckets.values()) == pytest.approx(rep["wall_s"],
                                                  rel=0.01)
    for bucket in ("step", "compile", "checkpoint_restore",
                   "restart_backoff", "data_stall", "checkpoint_save",
                   "fault_recovery"):
        assert buckets[bucket] > 0.0, f"{bucket} bucket empty: {rep}"
    assert 0.0 < rep["goodput_pct"] <= 100.0
    assert rep["coverage_pct"] <= 100.0

    # the same split is live on /metrics
    server = MetricsServer(reg, port=0).start()
    try:
        status, text = _get(server.url + "/metrics")
        assert status == 200
        assert 'dttpu_goodput_seconds_total{bucket="step"}' in text
        assert 'dttpu_goodput_seconds_total{bucket="checkpoint_restore"}' \
            in text
    finally:
        server.stop()
