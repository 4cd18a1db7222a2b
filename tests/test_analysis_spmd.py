"""dtlint SPMD tier (DT5xx): propagation byte-exactness, one planted /
fixed-twin / suppression triple per rule, the tier cache key, the
``--report comms`` table, and the sentinel's static comm-drift gate.

Fixture style mirrors tests/test_analysis_graph.py: entries registered
on a throwaway ``Registry`` with abstract args and declared
``in_specs``/``mesh``, traced on CPU — nothing compiles, nothing runs.
The mesh math is pinned exactly: on a known mesh every collective's
wire bytes follow the ring formulas in ``analysis.spmd``, so the
assertions are equalities, not ranges.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from distributed_tensorflow_tpu import analysis
from distributed_tensorflow_tpu.analysis import graph as graph_lib
from distributed_tensorflow_tpu.analysis import spmd as spmd_lib
from distributed_tensorflow_tpu.analysis import spmd_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

f32 = jnp.float32


def sds(*shape, dtype=f32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest forces 8 host devices"
    return Mesh(np.array(devs[:8]).reshape(8), ("data",))


def sm(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset({"data"}),
                         check_vma=False)


def run_registry(reg):
    traced = graph_lib.trace_registry(reg)
    reports = spmd_lib.analyze_traced(traced)
    return reports, spmd_rules.run_spmd_rules(reports, reg)


def rules_of(findings):
    return [f.rule for f in findings]


W = sds(16, 16)          # 1024 B replicated param
X = sds(32, 16)          # batch, sharded over data


# ------------------------------------------------- wire-byte formulas


def test_collective_wire_bytes_ring_formulas_exact():
    wb = spmd_lib.collective_wire_bytes
    assert wb("psum", 1024, 8) == 2 * 1024 * 7 / 8
    assert wb("all_gather", 128, 8) == 128 * 7
    assert wb("reduce_scatter", 1024, 8) == 1024 * 7 / 8
    assert wb("ppermute", 512, 8) == 512
    assert wb("all_to_all", 1024, 8) == 1024 * 7 / 8
    assert wb("resharding", 256, 8) == 256 * 7
    # degenerate group: nothing moves
    assert wb("psum", 1024, 1) == 0.0


def test_psum_over_data_axis_exact_bytes_and_time(mesh, monkeypatch):
    """The canonical data-parallel all-reduce, priced on a known mesh
    with a pinned link bandwidth: one psum of the replicated (16,16)
    f32 param = 1024 B payload -> 2*1024*(8-1)/8 = 1792 wire bytes."""
    monkeypatch.setenv("DTTPU_AXIS_BW_DATA", "1e9")
    reg = graph_lib.Registry()

    @reg.trace_entry("psum", specs=(W, X),
                     in_specs=(P(), P("data")), mesh=mesh)
    def entry(w, x):
        def body(w, x):
            return jax.lax.pmean((x @ w).sum() * w, "data")
        return sm(body, mesh, (P(), P("data")), P())(w, x)

    reports, findings = run_registry(reg)
    assert findings == []
    (ev,) = reports[0].ledger.events
    assert ev.op == "psum" and ev.axes == ("data",)
    assert ev.payload_bytes == 1024.0
    assert ev.wire_bytes == 1792.0
    assert ev.count == 1
    assert ev.time_s == pytest.approx(1792.0 / 1e9)
    assert reports[0].ledger.per_axis_bytes() == {"data": 1792.0}


def test_reduce_scatter_all_gather_pair_nets_zero_residency(mesh):
    """The ZeRO step shape: rs a full (16,16) grad (shed 7/8 of 1024 B)
    then ag the (2,16) updated shard (gain 7x128 B) — the per-chip
    residency delta is exactly zero, so DT503 stays silent."""
    reg = graph_lib.Registry()

    @reg.trace_entry("zero1", specs=(W, X), in_specs=(P(), P("data")),
                     mesh=mesh, sharded_update_axis="data")
    def entry(w, x):
        def body(w, x):
            g = jax.lax.psum_scatter(w * 2.0, "data",
                                     scatter_dimension=0, tiled=True)
            return jax.lax.all_gather(g * 0.01, "data", axis=0,
                                      tiled=True)
        return sm(body, mesh, (P(), P("data")), P())(w, x)

    reports, findings = run_registry(reg)
    assert findings == []
    events = {e.op: e for e in reports[0].ledger.events}
    rs, ag = events["reduce_scatter"], events["all_gather"]
    assert rs.payload_bytes == 1024.0 and rs.wire_bytes == 896.0
    assert ag.payload_bytes == 128.0 and ag.wire_bytes == 896.0
    # residency algebra DT503 checks: gathered == scattered
    assert ag.payload_bytes * 7 == rs.payload_bytes * (1 - 1 / 8)


# ------------------------------------------------------------- DT501


def _dt501_entry(reg, name, in_specs, mesh, line_suffix=""):
    @reg.trace_entry(name, specs=(W, X), in_specs=in_specs, mesh=mesh)
    def entry(w, x):
        def body(w, x):
            return (x @ w).sum() * w
        # body's in_specs replicate the batch: P() on both operands
        return sm(body, mesh, (P(), P()), P(None))(w, x)
    return entry


def test_dt501_planted_spec_conflict_reshards(mesh):
    reg = graph_lib.Registry()
    _dt501_entry(reg, "planted", (P(), P("data")), mesh)
    reports, findings = run_registry(reg)
    assert rules_of(findings) == ["DT501"]
    assert "all-gather over data" in findings[0].message
    resh = [e for e in reports[0].ledger.events if e.op == "resharding"]
    # local shard of (32,16) f32 = 2048/8 = 256 B, gathered: 256*(8-1)
    assert resh[0].payload_bytes == 256.0
    assert resh[0].wire_bytes == 1792.0


def test_dt501_fixed_twin_matching_specs_silent(mesh):
    reg = graph_lib.Registry()

    @reg.trace_entry("fixed", specs=(W, X),
                     in_specs=(P(), P("data")), mesh=mesh)
    def entry(w, x):
        def body(w, x):
            return jax.lax.psum((x @ w).sum(), "data") * w
        return sm(body, mesh, (P(), P("data")), P())(w, x)

    reports, findings = run_registry(reg)
    assert findings == []
    assert not [e for e in reports[0].ledger.events
                if e.op == "resharding"]


def test_dt501_unknown_specs_never_fire(mesh):
    """No declared in_specs -> unknown sharding -> the tier claims
    nothing (the documented degrade-to-silence contract)."""
    reg = graph_lib.Registry()
    _dt501_entry(reg, "unknown", None, mesh)
    reports, findings = run_registry(reg)
    assert findings == []


def test_dt501_suppression_on_registration_line(mesh):
    reg = graph_lib.Registry()
    specs = (P(), P("data"))

    @reg.trace_entry("sup", specs=(W, X), in_specs=specs, mesh=mesh)  # dtlint: disable=DT501
    def entry(w, x):
        def body(w, x):
            return (x @ w).sum() * w
        return sm(body, mesh, (P(), P()), P(None))(w, x)

    _, findings = run_registry(reg)
    assert findings == []


# ------------------------------------------------------------- DT502


def _scan_psum_entry(reg, name, mesh, tainted):
    @reg.trace_entry(name, specs=(W, X), in_specs=(P(), P("data")),
                     mesh=mesh)
    def entry(w, x):
        def body(w, x):
            def it(c, _):
                operand = c * 0.5 + w if tainted else w
                return c + jax.lax.psum(operand, "data"), ()
            out, _ = jax.lax.scan(it, jnp.zeros_like(w), None,
                                  length=16)
            return out
        return sm(body, mesh, (P(), P("data")), P())(w, x)
    return entry


def test_dt502_planted_loop_invariant_psum_in_scan(mesh):
    reg = graph_lib.Registry()
    _scan_psum_entry(reg, "planted", mesh, tainted=False)
    reports, findings = run_registry(reg)
    assert rules_of(findings) == ["DT502"]
    assert "scan[16]" in findings[0].message
    (ev,) = reports[0].ledger.events
    assert ev.op == "psum" and ev.count == 16     # trips folded in


def test_dt502_fixed_twin_carry_dependent_operand_silent(mesh):
    reg = graph_lib.Registry()
    _scan_psum_entry(reg, "fixed", mesh, tainted=True)
    _, findings = run_registry(reg)
    assert findings == []


def test_dt502_suppression_on_registration_line(mesh):
    reg = graph_lib.Registry()

    @reg.trace_entry("sup", specs=(W, X), in_specs=(P(), P("data")), mesh=mesh)  # dtlint: disable=DT502
    def entry(w, x):
        def body(w, x):
            def it(c, _):
                return c + jax.lax.psum(w, "data"), ()
            out, _ = jax.lax.scan(it, jnp.zeros_like(w), None,
                                  length=16)
            return out
        return sm(body, mesh, (P(), P("data")), P())(w, x)

    _, findings = run_registry(reg)
    assert findings == []


# ------------------------------------------------------------- DT503


def test_dt503_planted_no_reduce_scatter(mesh):
    reg = graph_lib.Registry()

    @reg.trace_entry("planted", specs=(W, X),
                     in_specs=(P(), P("data")), mesh=mesh,
                     sharded_update_axis="data")
    def entry(w, x):
        def body(w, x):
            return jax.lax.psum(w * 2.0, "data")
        return sm(body, mesh, (P(), P("data")), P())(w, x)

    _, findings = run_registry(reg)
    assert rules_of(findings) == ["DT503"]
    assert "effectively replicated" in findings[0].message


def test_dt503_planted_unpaired_reduce_scatter(mesh):
    reg = graph_lib.Registry()

    @reg.trace_entry("planted", specs=(W, X),
                     in_specs=(P(), P("data")), mesh=mesh,
                     sharded_update_axis="data")
    def entry(w, x):
        def body(w, x):
            return jax.lax.psum_scatter(w * 2.0, "data",
                                        scatter_dimension=0, tiled=True)
        return sm(body, mesh, (P(), P("data")), P("data"))(w, x)

    _, findings = run_registry(reg)
    assert rules_of(findings) == ["DT503"]
    assert "1 reduce_scatter but 0 all_gather" in findings[0].message


def test_dt503_without_declaration_never_fires(mesh):
    """DT503 is an opt-in contract: the same unpaired program without
    ``sharded_update_axis`` is not judged."""
    reg = graph_lib.Registry()

    @reg.trace_entry("undeclared", specs=(W, X),
                     in_specs=(P(), P("data")), mesh=mesh)
    def entry(w, x):
        def body(w, x):
            return jax.lax.psum_scatter(w * 2.0, "data",
                                        scatter_dimension=0, tiled=True)
        return sm(body, mesh, (P(), P("data")), P("data"))(w, x)

    _, findings = run_registry(reg)
    assert findings == []


def test_dt503_suppression_on_registration_line(mesh):
    reg = graph_lib.Registry()

    @reg.trace_entry("sup", specs=(W, X), in_specs=(P(), P("data")), mesh=mesh, sharded_update_axis="data")  # dtlint: disable=DT503
    def entry(w, x):
        def body(w, x):
            return jax.lax.psum(w * 2.0, "data")
        return sm(body, mesh, (P(), P("data")), P())(w, x)

    _, findings = run_registry(reg)
    assert findings == []


# ------------------------------------------------------------- DT504


def _dt504_entry(reg, name, mesh, establish):
    @reg.trace_entry(name, specs=(W, X), in_specs=(P(), P("data")),
                     mesh=mesh)
    def entry(w, x):
        def body(w, x):
            v = (x * 2.0).sum()
            if establish:
                v = jax.lax.psum(v, "data")
            return v * w
        return sm(body, mesh, (P(), P("data")), P())(w, x)
    return entry


def test_dt504_planted_unestablished_replication_claim(mesh):
    reg = graph_lib.Registry()
    _dt504_entry(reg, "planted", mesh, establish=False)
    _, findings = run_registry(reg)
    assert rules_of(findings) == ["DT504"]
    assert "check_vma=False" in findings[0].message


def test_dt504_fixed_twin_psum_establishes_silent(mesh):
    reg = graph_lib.Registry()
    _dt504_entry(reg, "fixed", mesh, establish=True)
    _, findings = run_registry(reg)
    assert findings == []


def test_dt504_sharded_out_spec_claims_nothing(mesh):
    """out_spec P('data') claims no replication — device-varying
    results are the declared contract, nothing to check."""
    reg = graph_lib.Registry()

    @reg.trace_entry("sharded_out", specs=(W, X),
                     in_specs=(P(), P("data")), mesh=mesh)
    def entry(w, x):
        def body(w, x):
            return x * 2.0
        return sm(body, mesh, (P(), P("data")), P("data"))(w, x)

    _, findings = run_registry(reg)
    assert findings == []


def test_dt504_suppression_on_registration_line(mesh):
    reg = graph_lib.Registry()

    @reg.trace_entry("sup", specs=(W, X), in_specs=(P(), P("data")), mesh=mesh)  # dtlint: disable=DT504
    def entry(w, x):
        def body(w, x):
            return (x * 2.0).sum() * w
        return sm(body, mesh, (P(), P("data")), P())(w, x)

    _, findings = run_registry(reg)
    assert findings == []


# ------------------------------------------------------------- DT505


def _dt505_entry(reg, name, mesh, same_branches):
    # out_specs shard the result: a device-varying predicate means the
    # cond output can't be replicated, so claiming P() would be its own
    # (correct) DT504 — this fixture isolates the ordering hazard.
    @reg.trace_entry(name, specs=(W, X), in_specs=(P(), P("data")),
                     mesh=mesh)
    def entry(w, x):
        def body(w, x):
            i = jax.lax.axis_index("data")
            t = lambda w: jax.lax.psum(w, "data")
            f = t if same_branches else (lambda w: w * 2.0)
            return jax.lax.cond(i > 0, t, f, w)
        return sm(body, mesh, (P(), P("data")), P("data"))(w, x)
    return entry


def test_dt505_planted_branches_disagree_under_varying_pred(mesh):
    reg = graph_lib.Registry()
    _dt505_entry(reg, "planted", mesh, same_branches=False)
    _, findings = run_registry(reg)
    assert rules_of(findings) == ["DT505"]
    assert "deadlock" in findings[0].message


def test_dt505_fixed_twin_matching_branches_silent(mesh):
    reg = graph_lib.Registry()
    _dt505_entry(reg, "fixed", mesh, same_branches=True)
    _, findings = run_registry(reg)
    assert findings == []


def test_dt505_replicated_predicate_silent(mesh):
    """Same asymmetric branches, but the predicate is computed from a
    replicated value — every device takes the same path."""
    reg = graph_lib.Registry()

    @reg.trace_entry("uniform", specs=(W, X),
                     in_specs=(P(), P("data")), mesh=mesh)
    def entry(w, x):
        def body(w, x):
            return jax.lax.cond(w.sum() > 0,
                                lambda w: jax.lax.psum(w, "data"),
                                lambda w: w * 2.0, w)
        return sm(body, mesh, (P(), P("data")), P(None))(w, x)

    _, findings = run_registry(reg)
    assert findings == []


def test_dt505_suppression_on_registration_line(mesh):
    reg = graph_lib.Registry()

    @reg.trace_entry("sup", specs=(W, X), in_specs=(P(), P("data")), mesh=mesh)  # dtlint: disable=DT505
    def entry(w, x):
        def body(w, x):
            i = jax.lax.axis_index("data")
            return jax.lax.cond(i > 0,
                                lambda w: jax.lax.psum(w, "data"),
                                lambda w: w * 2.0, w)
        return sm(body, mesh, (P(), P("data")), P("data"))(w, x)

    _, findings = run_registry(reg)
    assert findings == []


# ------------------------------------------- auto-region propagation


def test_auto_region_contraction_prices_the_gradient_allreduce(mesh):
    """Outside any shard_map: a dot_general contracting the sharded
    batch dim means XLA must all-reduce — the data-parallel gradient
    psum, detected purely from specs."""
    reg = graph_lib.Registry()

    @reg.trace_entry("auto", specs=(X,), in_specs=(P("data", None),),
                     mesh=mesh)
    def entry(x):
        return x.T @ x          # contracts dim 0 (sharded over data)

    reports, findings = run_registry(reg)
    assert findings == []
    (ev,) = reports[0].ledger.events
    assert ev.op == "psum" and ev.axes == ("data",)
    assert ev.payload_bytes == 1024.0       # (16,16) f32 out, replicated
    assert ev.wire_bytes == 1792.0


def test_auto_region_unknown_primitive_degrades_silently(mesh):
    """An unhandled shape-changing primitive (concatenate) makes
    downstream values unknown — no events, no findings, nothing
    guessed.  (Same-shape unhandled primitives like sort DO inherit a
    consistent operand spec; degradation is for shapes the default
    rule can't align.)"""
    reg = graph_lib.Registry()

    @reg.trace_entry("degrade", specs=(X,), in_specs=(P("data"),),
                     mesh=mesh)
    def entry(x):
        y = jnp.concatenate([x, x], axis=0)
        return y.T @ y          # would psum if the spec were known

    reports, findings = run_registry(reg)
    assert findings == []
    assert reports[0].ledger.events == []


# ------------------------------------------------- real registry


@pytest.fixture(scope="module")
def real_reports():
    from distributed_tensorflow_tpu.analysis import entries
    reg = entries.load_registry()
    traced = graph_lib.trace_registry(reg)
    return spmd_lib.analyze_traced(traced), reg


def test_parallel_entries_have_nonzero_comm(real_reports):
    reports, _ = real_reports
    by_name = {r.name.split(".")[1]: r for r in reports
               if r.name.startswith("parallel.")}
    assert set(by_name) == {"data_parallel", "pipeline", "ring",
                            "ring_flash"}
    for name, r in by_name.items():
        assert r.ledger.total_bytes > 0, name
        assert r.ledger.total_time_s > 0, name
    # the data-parallel step's ledger is exactly its two pmeans
    dp = by_name["data_parallel"]
    assert dp.ledger.count("psum") == 2
    assert dp.ledger.per_axis_bytes().keys() == {"data"}
    # the pipeline moves activations every tick by design: ppermutes
    # carry the scan trip count, and DT502 has nothing to hoist
    pp = by_name["pipeline"]
    assert pp.ledger.count("ppermute") > 1


def test_real_registry_is_clean_zero_suppressions(real_reports):
    """The triage goal: the tier raises nothing on the real parallel/ +
    train/ code, and not because anything was suppressed."""
    reports, reg = real_reports
    findings = spmd_rules.run_spmd_rules(reports, reg)
    assert findings == []
    out = subprocess.run(
        ["grep", "-rn", r"dtlint: disable=DT50[1-5]",
         os.path.join(REPO, "distributed_tensorflow_tpu")],
        capture_output=True, text=True)
    assert out.stdout == "", f"unexpected DT5xx suppressions:\n{out.stdout}"


def test_entry_comm_bench_seam(mesh):
    """The hook bench.py calls: returns a ledger for an arbitrary fn +
    specs, no registry involved."""
    def step(w, x):
        def body(w, x):
            return jax.lax.pmean((x @ w).sum() * w, "data")
        return sm(body, mesh, (P(), P("data")), P())(w, x)

    led = spmd_lib.entry_comm(step, W, X, in_specs=(P(), P("data")),
                              mesh=mesh)
    assert led.total_bytes == 1792.0
    assert led.count("psum") == 1


# ----------------------------------------------------- CLI + cache


def test_cli_report_comms_smoke():
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tensorflow_tpu.analysis",
         "--report", "comms"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert "parallel.data_parallel.make_psum_train_step" in proc.stdout
    assert "per-axis mb" in proc.stdout
    # nonzero bytes rendered for the parallel entries
    for line in proc.stdout.splitlines():
        if line.startswith("parallel."):
            assert "data:" in line or "pipe:" in line or "seq:" in line


def test_cli_no_spmd_flag(tmp_path):
    f = tmp_path / "x.py"
    f.write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tensorflow_tpu.analysis",
         str(f), "--no-spmd", "--no-cache", "--format", "json"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout)["count"] == 0


def test_rule_catalog_includes_spmd_tier():
    ids = [rid for rid, _, _ in analysis.full_rule_catalog()]
    # the lifecycle tier (DT6xx) now tails the catalog; the SPMD
    # block sits just before it
    assert ids[-10:-5] == ["DT501", "DT502", "DT503", "DT504", "DT505"]


class TestSpmdTierCache:
    """The DT5xx cache key: package tree hash + the mesh/bandwidth env
    signature.  The traced-registry load is stubbed so the fixture runs
    in milliseconds; what's under test is the keying, not the trace."""

    def _setup(self, tmp_path, monkeypatch):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "clean.py").write_text("x = 1\n")
        monkeypatch.setenv("DTLINT_CACHE_DIR", str(tmp_path / "cache"))
        from distributed_tensorflow_tpu.analysis import cli as cli_mod
        from distributed_tensorflow_tpu.analysis import (graph_rules,
                                                         spmd_rules)
        calls = {"trace": 0, "graph": 0, "spmd": 0}

        def fake_load():
            calls["trace"] += 1
            return graph_lib.Registry(), []

        def count(key, real):
            def wrapper(*a, **kw):
                calls[key] += 1
                return real(*a, **kw)
            return wrapper

        monkeypatch.setattr(cli_mod, "_load_traced", fake_load)
        monkeypatch.setattr(cli_mod, "_covers_package",
                            lambda files: True)
        monkeypatch.setattr(graph_rules, "run_graph_rules",
                            count("graph", graph_rules.run_graph_rules))
        monkeypatch.setattr(spmd_rules, "run_spmd_rules",
                            count("spmd", spmd_rules.run_spmd_rules))
        return d, calls

    def test_cold_warm_and_env_key_invalidation(self, tmp_path,
                                                monkeypatch):
        d, calls = self._setup(tmp_path, monkeypatch)
        cat = analysis.full_rule_catalog()

        cold = analysis.analyze_paths(
            [str(d)], cache=analysis.ResultCache(catalog=cat))
        assert cold == []
        assert calls == {"trace": 1, "graph": 1, "spmd": 1}

        warm = analysis.analyze_paths(
            [str(d)], cache=analysis.ResultCache(catalog=cat))
        assert warm == []
        assert calls == {"trace": 1, "graph": 1, "spmd": 1}

        # a bandwidth knob is part of the spmd key (modeled times move)
        # but NOT of the graph key: only the spmd tier re-runs
        monkeypatch.setenv("DTTPU_AXIS_BW", "1e9")
        analysis.analyze_paths(
            [str(d)], cache=analysis.ResultCache(catalog=cat))
        assert calls == {"trace": 2, "graph": 1, "spmd": 2}

    def test_no_spmd_pass_skips_tier(self, tmp_path, monkeypatch):
        d, calls = self._setup(tmp_path, monkeypatch)
        cat = analysis.full_rule_catalog()
        analysis.analyze_paths(
            [str(d)], spmd_pass=False,
            cache=analysis.ResultCache(catalog=cat))
        assert calls["spmd"] == 0 and calls["graph"] == 1


# ------------------------------------------------- sentinel comm gate


def test_sentinel_comm_drift_reds_on_static_growth():
    from distributed_tensorflow_tpu.obs import sentinel as sent
    assert sent.classify_field("analytical_comm_bytes") == "lower"

    base = {"config": "gpt", "measured": {},
            "analytical": {"analytical_comm_bytes": 1000.0,
                           "analytical_comm_time_s": 1e-5}}
    grown = {"config": "gpt", "measured": {},
             "analytical": {"analytical_comm_bytes": 1300.0,
                            "analytical_comm_time_s": 1.3e-5}}
    same = {"config": "gpt", "measured": {},
            "analytical": {"analytical_comm_bytes": 1010.0,
                           "analytical_comm_time_s": 1.01e-5}}

    s = sent.Sentinel()
    bad = s.check(grown, baseline=base)
    comm = [v for v in bad if v.kind == "comm"]
    assert len(comm) == 2
    assert all(not v.ok for v in comm)       # 1.3x > the tight 1.2
    assert "program changed" in comm[0].detail

    ok = s.check(same, baseline=base)
    assert all(v.ok for v in ok if v.kind == "comm")

    # per-field override loosens the gate like any other tolerance
    s2 = sent.Sentinel(tolerances={
        "analytical_comm_bytes": sent.Tolerance(max_ratio=1.5),
        "analytical_comm_time_s": sent.Tolerance(max_ratio=1.5)})
    assert all(v.ok for v in s2.check(grown, baseline=base))
