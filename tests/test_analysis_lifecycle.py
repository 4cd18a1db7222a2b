"""dtlint lifecycle tier (DT6xx) + the runtime leak ledger.

Static half: one planted / fixed-twin / suppression triple per rule
DT601-DT605, the ownership-transfer exemptions (stored on self,
returned, handed off, passed to a releasing callee), the typestate
shapes the engine had to learn from the real scheduler (guarded
``acquire()`` results, timeout acquires, acquire-raise edges, except
handlers), the ``--rules`` selector, the tier cache key, and the
zero-findings self-check over the real package.

Runtime half: ``ResourceLedger`` balance semantics (idempotent second
release is not a release, a release finding no pin is an over-release,
handoff counts through its internal release), the
``@pytest.mark.resource_ledger`` fixture, the satellite regression for
the ``_begin_prefill`` unwind, and the chaos acceptances — a fault
storm through a paged+LoRA engine and a kill_replica migration, both
required to finish with lease/pin traffic exactly balanced.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from distributed_tensorflow_tpu import analysis, fleet, serve
from distributed_tensorflow_tpu.analysis import cli as cli_mod
from distributed_tensorflow_tpu.analysis.callgraph import Project
from distributed_tensorflow_tpu.analysis.leak_ledger import (
    LedgerImbalance, ResourceLedger)
from distributed_tensorflow_tpu.analysis.lifecycle import PROTOCOLS
from distributed_tensorflow_tpu.analysis.lifecycle_rules import (
    LIFECYCLE_RULES, run_lifecycle_rules)
from distributed_tensorflow_tpu.analysis.report import Severity
from distributed_tensorflow_tpu.analysis.walker import Source
from distributed_tensorflow_tpu.models.gpt import gpt_tiny
from distributed_tensorflow_tpu.obs import metrics as metrics_lib
from distributed_tensorflow_tpu.serve import pages as pages_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(code, mod="m"):
    src = Source(mod.replace(".", "/") + ".py", textwrap.dedent(code))
    return run_lifecycle_rules(Project.from_sources({mod: src}))


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# DT601: leak on an exception/early-return path


def test_dt601_exception_path_leaks_lease():
    fs = lint("""
        def serve(pool, req):
            lease = pool.begin(req.rid, need=4)
            decode(req)          # may raise -> lease leaked
            pool.release(lease)
    """)
    assert rules_of(fs) == ["DT601"]
    (f,) = fs
    # anchored at the acquire, where the fix (try/finally) goes
    assert f.line == 3 and f.severity is Severity.ERROR
    assert "page lease" in f.message and "leaked" in f.message


def test_dt601_fixed_twin_try_finally():
    assert lint("""
        def serve(pool, req):
            lease = pool.begin(req.rid, need=4)
            try:
                decode(req)
            finally:
                pool.release(lease)
    """) == []


def test_dt601_fixed_twin_handler_releases_and_reraises():
    assert lint("""
        def serve(pool, req):
            lease = pool.begin(req.rid, need=4)
            try:
                decode(req)
            except Exception:
                pool.release(lease)
                raise
            pool.release(lease)
    """) == []


def test_dt601_early_return_leaks():
    assert rules_of(lint("""
        def serve(pool, req):
            lease = pool.begin(req.rid, need=4)
            if req.bad:
                return None
            pool.release(lease)
    """)) == ["DT601"]


def test_dt601_transfer_stored_on_self_is_silent():
    assert lint("""
        def admit(self, pool, req):
            lease = pool.begin(req.rid, need=4)
            self.lease = lease
    """) == []


def test_dt601_transfer_returned_is_silent():
    assert lint("""
        def admit(pool, req):
            lease = pool.begin(req.rid, need=4)
            return lease
    """) == []


def test_dt601_handoff_transfers_but_earlier_call_edge_still_leaks():
    # handoff alone is a clean transfer; a raising call BETWEEN begin
    # and handoff still strands the lease on that edge
    assert lint("""
        def publish(pool, req, toks):
            lease = pool.begin(req.rid, need=4)
            pool.handoff(lease, toks)
    """) == []
    assert rules_of(lint("""
        def publish(pool, req, toks):
            lease = pool.begin(req.rid, need=4)
            decode(req)
            pool.handoff(lease, toks)
    """)) == ["DT601"]


def test_dt601_releasing_callee_summary_is_silent():
    assert lint("""
        def cleanup(pool, lease):
            pool.release(lease)

        def serve(pool, req):
            lease = pool.begin(req.rid, need=4)
            cleanup(pool, lease)
    """) == []


def test_dt601_second_acquire_raising_leaks_the_first():
    # the acquire call itself is an exception edge: if the second
    # begin() raises (pool exhausted), the first lease is stranded
    assert rules_of(lint("""
        def admit_pair(pool, a, b):
            la = pool.begin(a.rid, need=4)
            lb = pool.begin(b.rid, need=4)
            pool.release(la)
            pool.release(lb)
    """)) == ["DT601"]


def test_dt601_suppression():
    assert lint("""
        def serve(pool, req):
            lease = pool.begin(req.rid, need=4)  # dtlint: disable=DT601 -- transferred via side table
            decode(req)
            pool.release(lease)
    """) == []


# ---------------------------------------------------------------------------
# DT602: use-after-release / double release of a non-idempotent protocol


def test_dt602_double_release_non_idempotent_pin():
    fs = lint("""
        def drop(adapters, aid):
            adapters.acquire(aid)
            adapters.release(aid)
            adapters.release(aid)
    """)
    assert rules_of(fs) == ["DT602"]
    assert fs[0].line == 5          # anchored at the offending release


def test_dt602_idempotent_double_release_is_silent():
    # PagePool.release is declared idempotent in the protocol registry
    assert lint("""
        def serve(pool, req):
            lease = pool.begin(req.rid, need=4)
            pool.release(lease)
            pool.release(lease)
    """) == []


def test_dt602_fires_inside_except_handler():
    # handler entry includes the post-release state of the try body
    assert rules_of(lint("""
        def drop(adapters, aid):
            adapters.acquire(aid)
            adapters.release(aid)
            try:
                flush()
            except Exception:
                adapters.release(aid)
                raise
    """)) == ["DT602"]


def test_dt602_suppression():
    assert lint("""
        def drop(adapters, aid):
            adapters.acquire(aid)
            adapters.release(aid)
            adapters.release(aid)  # dtlint: disable=DT602 -- table tolerates it
    """) == []


# ---------------------------------------------------------------------------
# DT603: bare lock acquire without release on every path


def test_dt603_bare_lock_early_return():
    fs = lint("""
        def pump(self):
            self._lock.acquire()
            if self.closed:
                return
            self._lock.release()
    """)
    assert rules_of(fs) == ["DT603"]
    assert fs[0].severity is Severity.WARNING


def test_dt603_fixed_twin_try_finally():
    assert lint("""
        def pump(self):
            self._lock.acquire()
            try:
                if self.closed:
                    return
            finally:
                self._lock.release()
    """) == []


def test_dt603_with_lock_is_silent():
    assert lint("""
        def pump(self):
            with self._lock:
                step(self)
    """) == []


def test_dt603_split_acquire_release_api_is_silent():
    # no matching release anywhere in the function (an __enter__ half
    # of a split API): the consistency gate keeps the tier quiet
    assert lint("""
        def __enter__(self):
            self._lock.acquire()
            return self
    """) == []


def test_dt603_guarded_acquire_result_shape():
    # the scheduler's export shape: the acquire RESULT is a guard, not
    # an alias of the lock; if-gated release on the guard is clean
    assert lint("""
        def export(self, rid):
            clean = self._lock.acquire()
            try:
                return self._do_export(rid, clean)
            finally:
                if clean:
                    self._lock.release()
    """) == []


def test_dt603_timeout_guard_acquire_shape():
    # export_all: acquire(timeout=...) may fail; only the guard-true
    # branch holds, so releasing under the guard covers every path
    assert lint("""
        def export_all(self, timeout_s):
            clean = self._lock.acquire(timeout=timeout_s)
            try:
                return [self._do_export(r, clean) for r in self._live()]
            finally:
                if clean:
                    self._lock.release()
    """) == []


def test_dt603_suppression():
    assert lint("""
        def pump(self):
            self._lock.acquire()  # dtlint: disable=DT603 -- released by the watchdog
            if self.closed:
                return
            self._lock.release()
    """) == []


# ---------------------------------------------------------------------------
# DT604: resource held across a yield / into an un-shimmed callback


def test_dt604_lease_held_across_yield():
    fs = lint("""
        def stream(pool, req):
            lease = pool.begin(req.rid, need=4)
            try:
                for tok in decode(req):
                    yield tok
            finally:
                pool.release(lease)
    """)
    assert rules_of(fs) == ["DT604"]
    assert fs[0].severity is Severity.WARNING


def test_dt604_contextmanager_exempt():
    assert lint("""
        import contextlib

        @contextlib.contextmanager
        def leased(pool, req):
            lease = pool.begin(req.rid, need=4)
            try:
                yield lease
            finally:
                pool.release(lease)
    """) == []


def test_dt604_shimmed_callback_is_silent():
    # callback inside a try with handlers: a raise is caught and the
    # lease released — that is the shim the rule asks for
    assert lint("""
        def serve(self, pool, req):
            lease = pool.begin(req.rid, need=4)
            try:
                self.on_token(req)
                pool.release(lease)
            except Exception:
                pool.release(lease)
                raise
    """) == []


def test_dt604_unshimmed_callback_in_finally():
    # the callback runs un-shimmed while the lease is held (DT604) and
    # its raise strands the lease before the release line (DT601)
    assert rules_of(lint("""
        def serve(self, pool, req):
            lease = pool.begin(req.rid, need=4)
            try:
                step(req)
            finally:
                self.on_token(req)
                pool.release(lease)
    """)) == ["DT601", "DT604"]


def test_dt604_suppression():
    assert lint("""
        def stream(pool, req):
            lease = pool.begin(req.rid, need=4)
            try:
                for tok in decode(req):
                    yield tok  # dtlint: disable=DT604 -- consumer owns the generator
            finally:
                pool.release(lease)
    """) == []


# ---------------------------------------------------------------------------
# DT605: protocol-order violations


def test_dt605_register_after_release():
    fs = lint("""
        def publish(pool, req, toks):
            lease = pool.begin(req.rid, need=4)
            pool.release(lease)
            pool.register(lease, toks)
    """)
    assert rules_of(fs) == ["DT605"]
    # anchored at the offending op, not the acquire
    assert fs[0].line == 5 and fs[0].severity is Severity.ERROR


def test_dt605_terminal_recancel():
    assert rules_of(lint("""
        def abort(engine, rid):
            handle = engine.submit(rid)
            handle.cancel()
            handle.cancel()
    """)) == ["DT605"]


def test_dt605_suppression():
    assert lint("""
        def publish(pool, req, toks):
            lease = pool.begin(req.rid, need=4)
            pool.release(lease)
            pool.register(lease, toks)  # dtlint: disable=DT605 -- registry replays idempotently
    """) == []


# ---------------------------------------------------------------------------
# shared shapes


def test_with_lease_auto_releases():
    assert lint("""
        def serve(pool, req):
            with pool.begin(req.rid, need=4) as lease:
                decode(req)
    """) == []


def test_loop_release_then_reacquire_no_false_storm():
    assert lint("""
        def serve(pool, reqs):
            for req in reqs:
                lease = pool.begin(req.rid, need=4)
                try:
                    decode(req)
                finally:
                    pool.release(lease)
    """) == []


def test_begin_prefill_unwind_shape_is_clean():
    # the fixed scheduler admission shape: pin stored on the request
    # (ownership transferred to the retire path), broad unwind releases
    # the lease and the pin on ANY failure, then re-raises
    assert lint("""
        def begin_prefill(self, req):
            req.adapter_row = self.adapters.acquire(req.adapter_id)
            try:
                lease = self.pages.begin(req.ctx, req.total)
                req.lease = lease
                return [req, lease]
            except BaseException:
                if req.lease is not None:
                    self.pages.release(req.lease)
                self.adapters.release(req.adapter_id)
                raise
    """) == []


def test_lifecycle_rule_catalog_ids_and_severities():
    assert [r for r, _, _ in LIFECYCLE_RULES] == [
        "DT601", "DT602", "DT603", "DT604", "DT605"]
    ids = [rid for rid, _, _ in analysis.full_rule_catalog()]
    assert ids[-5:] == ["DT601", "DT602", "DT603", "DT604", "DT605"]


def test_protocol_registry_names():
    assert {p.name for p in PROTOCOLS} == {
        "page lease", "adapter pin", "lock", "request handle"}


# ---------------------------------------------------------------------------
# --rules selection


def test_expand_rules_exact_wildcard_case_and_unknown():
    expand = cli_mod._expand_rules
    assert expand(None) is None and expand("") is None
    assert expand("DT601") == {"DT601"}
    assert expand("dt601, dt303") == {"DT601", "DT303"}
    assert expand("DT6xx") == {"DT601", "DT602", "DT603", "DT604",
                               "DT605"}
    assert expand("dt6XX,DT101") == {"DT601", "DT602", "DT603",
                                     "DT604", "DT605", "DT101"}
    for tier in ("DT1xx", "DT2xx", "DT3xx", "DT4xx", "DT5xx"):
        assert expand(tier), tier
    with pytest.raises(ValueError, match="unknown rule"):
        expand("DT999")
    with pytest.raises(ValueError, match="unknown tier"):
        expand("DT9xx")


MIXED_TIER_SRC = """
import threading

def fire(work):
    t = threading.Thread(target=work, name="w", daemon=True)
    t.start()

def serve(pool, req):
    lease = pool.begin(req.rid, need=4)
    decode(req)
    pool.release(lease)
"""


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "distributed_tensorflow_tpu.analysis",
         *argv], capture_output=True, text=True, cwd=REPO)


def test_cli_rules_filter_narrows_across_tiers(tmp_path):
    f = tmp_path / "mixed.py"
    f.write_text(MIXED_TIER_SRC)
    base = (str(f), "--no-cache", "--format", "json")

    proc = _run_cli(*base)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    both = {x["rule"] for x in json.loads(proc.stdout)["findings"]}
    assert both == {"DT305", "DT601"}

    proc = _run_cli(*base, "--rules", "DT601")
    assert {x["rule"] for x in json.loads(proc.stdout)["findings"]} \
        == {"DT601"}

    proc = _run_cli(*base, "--rules", "dt3xx")       # case-insensitive
    assert {x["rule"] for x in json.loads(proc.stdout)["findings"]} \
        == {"DT305"}


def test_cli_rules_unknown_id_exits_2(tmp_path):
    f = tmp_path / "x.py"
    f.write_text("x = 1\n")
    proc = _run_cli(str(f), "--no-cache", "--rules", "DT777")
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr + proc.stdout


def test_cli_no_lifecycle_flag_drops_tier(tmp_path):
    f = tmp_path / "leak.py"
    f.write_text(textwrap.dedent("""
        def serve(pool, req):
            lease = pool.begin(req.rid, need=4)
            decode(req)
            pool.release(lease)
    """))
    proc = _run_cli(str(f), "--no-cache", "--format", "json")
    assert proc.returncode == 1
    assert [x["rule"] for x in json.loads(proc.stdout)["findings"]] \
        == ["DT601"]
    proc = _run_cli(str(f), "--no-cache", "--format", "json",
                    "--no-lifecycle")
    assert proc.returncode == 0
    assert json.loads(proc.stdout)["count"] == 0


def test_cli_timings_include_lifecycle_tier(tmp_path):
    f = tmp_path / "x.py"
    f.write_text("x = 1\n")
    proc = _run_cli(str(f), "--no-cache", "--timings")
    assert proc.returncode == 0
    assert "lifecycle (DT6xx)" in proc.stderr


# ---------------------------------------------------------------------------
# tier cache


class TestLifecycleTierCache:
    """Cold run computes, warm run hits, an edited file re-runs the
    tier (full-tree key: the typestate walk is interprocedural)."""

    def _setup(self, tmp_path, monkeypatch):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "clean.py").write_text("x = 1\n")
        monkeypatch.setenv("DTLINT_CACHE_DIR", str(tmp_path / "cache"))
        calls = {"life": 0}
        real = cli_mod.run_lifecycle_rules

        def counted(*a, **kw):
            calls["life"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(cli_mod, "run_lifecycle_rules", counted)
        return d, calls

    def test_cold_warm_and_file_edit_invalidation(self, tmp_path,
                                                  monkeypatch):
        d, calls = self._setup(tmp_path, monkeypatch)
        cat = analysis.full_rule_catalog()

        assert analysis.analyze_paths(
            [str(d)], cache=analysis.ResultCache(catalog=cat)) == []
        assert calls["life"] == 1

        assert analysis.analyze_paths(
            [str(d)], cache=analysis.ResultCache(catalog=cat)) == []
        assert calls["life"] == 1          # warm: tier cache hit

        (d / "clean.py").write_text("x = 2\n")
        analysis.analyze_paths(
            [str(d)], cache=analysis.ResultCache(catalog=cat))
        assert calls["life"] == 2          # tree changed: recompute

    def test_no_lifecycle_pass_skips_tier(self, tmp_path, monkeypatch):
        d, calls = self._setup(tmp_path, monkeypatch)
        cat = analysis.full_rule_catalog()
        analysis.analyze_paths(
            [str(d)], lifecycle_pass=False,
            cache=analysis.ResultCache(catalog=cat))
        assert calls["life"] == 0


# ---------------------------------------------------------------------------
# self-check: the real package is clean, with no unjustified escapes


def test_dt6xx_clean_on_real_package():
    """The tier's findings on the repo itself were triaged to zero: the
    scheduler/pages/adapters release discipline is the proof surface.
    A regression here is a real leak (or an engine false positive) —
    either way it blocks."""
    files = analysis.collect_files(
        [os.path.join(REPO, "distributed_tensorflow_tpu")])
    project = analysis.Project.from_sources({
        analysis.module_name_for(os.path.relpath(p, REPO)):
            analysis.Source(p, open(p, encoding="utf-8").read())
        for p in files})
    findings = run_lifecycle_rules(project)
    assert findings == [], [(f.rule, f.path, f.line, f.message)
                            for f in findings]


def test_no_dt6xx_suppressions_in_package():
    out = subprocess.run(
        ["grep", "-rn", r"dtlint: disable=DT60[1-5]",
         os.path.join(REPO, "distributed_tensorflow_tpu")],
        capture_output=True, text=True)
    assert out.stdout == "", \
        f"unexpected DT6xx suppressions:\n{out.stdout}"


def test_lifecycle_model_sees_serve_protocol_traffic():
    """The typestate walk must actually visit the serve tier's
    acquire/release sites — if the prescan gate ever skips them, the
    clean self-check above means nothing."""
    from distributed_tensorflow_tpu.analysis.lifecycle import (
        LifecycleModel)
    serve_dir = os.path.join(REPO, "distributed_tensorflow_tpu",
                             "serve")
    files = analysis.collect_files([serve_dir])
    project = analysis.Project.from_sources({
        analysis.module_name_for(os.path.relpath(p, REPO)):
            analysis.Source(p, open(p, encoding="utf-8").read())
        for p in files})
    model = LifecycleModel(project, PROTOCOLS)
    walked = {q.rsplit(".", 1)[-1] for (_, q) in model.walked}
    for expect in ("_begin_prefill", "_retire_accounting", "export",
                   "export_all"):
        assert expect in walked, sorted(walked)


# ---------------------------------------------------------------------------
# ResourceLedger unit semantics


def _pool(**kw):
    kw.setdefault("num_pages", 8)
    kw.setdefault("page_size", 4)
    kw.setdefault("pages_per_slot", 4)
    return pages_lib.PagePool(**kw)


def _ctx(n=6, seed=0):
    return np.arange(seed, seed + n, dtype=np.int32)


def test_ledger_balanced_pages_extent():
    with ResourceLedger(track=("pages",)) as led:
        pool = _pool()
        lease = pool.begin(_ctx(), 8)
        pool.release(lease)
        pool.release(lease)            # idempotent: NOT a second credit
    assert led.counts() == {"pages.begin": 1, "pages.release": 1}


def test_ledger_detects_leaked_lease_and_gauge_drift():
    with pytest.raises(LedgerImbalance) as err:
        with ResourceLedger(track=("pages",)):
            pool = _pool()
            pool.begin(_ctx(), 8)      # never released
    msg = str(err.value)
    assert "page leases: 1 acquired vs 0 released" in msg
    assert "_lease_count 0 -> 1" in msg
    assert "traffic:" in msg


def test_ledger_handoff_counts_as_release():
    ctx = _ctx(8)
    with ResourceLedger(track=("pages",)) as led:
        pool = _pool()
        lease = pool.begin(ctx, 8)
        pool.handoff(lease, ctx)       # register + release internally
    c = led.counts()
    assert c["pages.handoff"] == 1
    assert c["pages.begin"] == c["pages.release"] == 1


@pytest.fixture(scope="module")
def adapter_table():
    from distributed_tensorflow_tpu.serve.adapters import AdapterTable
    model = gpt_tiny(dropout_rate=0.0)
    table = AdapterTable(model, capacity=2, rank=2)
    table.register("tuned", model.init_lora(jax.random.PRNGKey(0),
                                            rank=2))
    return table


def test_ledger_books_adapter_over_release(adapter_table):
    with pytest.raises(LedgerImbalance) as err:
        with ResourceLedger(track=("adapters",)) as led:
            adapter_table.acquire("tuned")
            adapter_table.release("tuned")
            adapter_table.release("tuned")   # finds no pin
    assert "release(s) found no pin" in str(err.value)
    assert led.counts()["adapters.over_release"] == 1


def test_ledger_adapter_none_id_is_not_traffic(adapter_table):
    with ResourceLedger(track=("adapters",)) as led:
        assert adapter_table.acquire(None) == 0
        adapter_table.release(None)
    assert led.counts() == {}


def test_ledger_extents_cannot_nest():
    with ResourceLedger(track=("pages",)):
        with pytest.raises(RuntimeError, match="nest"):
            with ResourceLedger(track=("pages",)):
                pass


def test_ledger_stays_silent_when_body_raises():
    # the imbalance report must never mask the test's own failure
    with pytest.raises(RuntimeError, match="real failure"):
        with ResourceLedger(track=("pages",)):
            pool = _pool()
            pool.begin(_ctx(), 8)      # leaked, but the raise wins
            raise RuntimeError("real failure")


def test_ledger_restores_class_methods_on_exit():
    orig = (pages_lib.PagePool.begin, pages_lib.PagePool.release,
            pages_lib.PagePool.handoff)
    with ResourceLedger(track=("pages",)):
        assert pages_lib.PagePool.begin is not orig[0]
    assert (pages_lib.PagePool.begin, pages_lib.PagePool.release,
            pages_lib.PagePool.handoff) == orig


def test_ledger_rejects_unknown_surface():
    with pytest.raises(ValueError, match="unknown ledger surface"):
        ResourceLedger(track=("pages", "filehandles"))


def test_ledger_untracked_surface_is_ignored():
    with ResourceLedger(track=("goodput",)):
        pool = _pool()
        pool.begin(_ctx(), 8)          # pages surface not instrumented


@pytest.mark.resource_ledger(track=("pages",))
def test_resource_ledger_marker_wraps_test_body(request):
    ledger = request.node.resource_ledger
    assert isinstance(ledger, ResourceLedger)
    assert ledger.track == ("pages",)
    pool = _pool()
    lease = pool.begin(_ctx(), 8)
    pool.release(lease)
    assert ledger.counts()["pages.begin"] == 1
    # teardown re-checks balance; this extent is balanced


# ---------------------------------------------------------------------------
# satellite regression: _begin_prefill unwinds on ANY admission failure


def _model_params(seed=0, **kw):
    model = gpt_tiny(dropout_rate=0.0, **kw)
    return model, model.init(jax.random.PRNGKey(seed))


def _prompt(plen, seed=1, vocab=512):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (plen,), 0, vocab), np.int32)


def _adapter(model, seed, rank=4, scale=0.3):
    ad = model.init_lora(jax.random.PRNGKey(seed), rank=rank)
    for t in model._LORA_TARGETS:
        ad[t]["b"] = scale * jax.random.normal(
            jax.random.PRNGKey(seed + 1), ad[t]["b"].shape)
    return ad


def test_begin_prefill_unwinds_pin_when_page_begin_fails_hard():
    """A non-transient begin() failure (ValueError, not exhaustion)
    used to strand the adapter pin: the old unwind only covered
    PagePoolExhausted.  The broad unwind must release it and leave no
    lease born."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       adapter_capacity=1, adapter_rank=4,
                       registry=metrics_lib.Registry())
    eng.load_adapter("tuned", _adapter(model, seed=3))

    def boom(prompt, total_cols):
        raise ValueError("synthetic admission failure after the pin")

    eng.scheduler.pages.begin = boom
    eng.submit(_prompt(5), 4, adapter_id="tuned")
    with pytest.raises(ValueError, match="synthetic"):
        eng.step()
    assert eng.adapters._refs == {}                 # pin unwound
    assert eng.scheduler.pages._lease_count == 0    # nothing leaked


# ---------------------------------------------------------------------------
# chaos acceptance: fault storms under the ledger must balance exactly


@pytest.mark.chaos
@pytest.mark.resource_ledger
def test_chaos_storm_lease_and_pin_traffic_balances(request,
                                                    activate_faults):
    """THE DT6xx runtime acceptance: a paged+LoRA engine under a fault
    storm (two targeted decode failures + a stalled tick) retires every
    request — ok or failed — with lease/pin traffic exactly balanced.
    The marker fixture re-asserts balance (and pool/table gauge return)
    at teardown; an imbalance fails the test with the per-resource
    table."""
    model, params = _model_params()
    eng = serve.Engine(model, params, num_slots=2, max_len=32,
                       prefill_chunk=4, tick_steps=2, page_size=8,
                       adapter_capacity=2, adapter_rank=4,
                       registry=metrics_lib.Registry())
    eng.load_adapter("a", _adapter(model, seed=3))
    eng.load_adapter("b", _adapter(model, seed=7))
    activate_faults({"kind": "fail_decode", "at": 1},
                    {"kind": "fail_decode", "at": 3},
                    {"kind": "stall_tick", "at": 2, "seconds": 0.02})
    hs = [eng.submit(_prompt(4 + i % 3, seed=i), 5,
                     adapter_id=("a", "b", None)[i % 3])
          for i in range(6)]
    eng.drain()
    assert sorted(h.status for h in hs) == ["failed"] * 2 + ["ok"] * 4

    c = request.node.resource_ledger.counts()
    assert c["pages.begin"] >= 6               # every admission leased
    assert c["pages.begin"] == c["pages.release"]
    assert c["adapters.acquire"] == c["adapters.release"]
    assert "adapters.over_release" not in c


@pytest.mark.chaos
@pytest.mark.resource_ledger
def test_kill_replica_migration_balances_lease_traffic(request,
                                                       activate_faults):
    """Killing a replica mid-traffic exports its in-flight work
    (handoff: publish-then-release) and re-admits it on the survivor
    (fresh leases) — the whole migration must net to zero held pages
    and every handle still completes."""
    model, params = _model_params()
    reg = metrics_lib.Registry()
    router = fleet.Router(
        [serve.Engine(model, params, num_slots=2, max_len=64,
                      prefill_chunk=4, tick_steps=2, page_size=8,
                      registry=reg) for _ in range(2)],
        registry=reg)
    activate_faults({"kind": "kill_replica", "at": 2, "replica": 1})
    hs = [router.submit(_prompt(3 + i % 3, seed=i), 6,
                        deadline_s=120.0) for i in range(6)]
    router.step()
    assert router.drain(timeout_s=120)
    for h in hs:
        assert h.status == "ok", (h.status, h.error)

    c = request.node.resource_ledger.counts()
    assert c["pages.begin"] == c["pages.release"]
    assert c["pages.begin"] > 6     # migration re-admissions leased anew
