"""Benchmark harness — prints ONE JSON line to stdout.

Default metric (per BASELINE.md): MNIST-MLP training examples/sec/chip,
measured on the framework's compiled data-parallel train step on whatever
devices are available (the real TPU chip under the driver; the virtual CPU
mesh in tests), plus a convergence gate (final eval accuracy must clear the
per-provenance threshold or the result is reported as failed).

Other configs: ``python bench.py --config=cifar_cnn|resnet50|bert|gpt|llama|gpt_decode``
measure those rows (same JSON shape; resnet50/bert are throughput+finite-loss
benches, no convergence gate).  ``DTTPU_BENCH_SMOKE=1`` shrinks model/batch
sizes so every config path smoke-runs on the CPU mesh.

Device: the bench measures on the accelerator and says so.  ``main()``
runs inline in this one process; with no ``--device`` a platform other than
``tpu`` is a non-zero exit with a one-line reason and NO metric line — a
CPU number is never printed under a device metric's name.
``--device=cpu`` (or ``DTTPU_BENCH_DEVICE=cpu``) is the explicit wiring
check the CPU tests use: same code path, smoke sizes via
``DTTPU_BENCH_SMOKE=1``, and every line carries its backend in
``fingerprint``.

Every JSON line also carries an ``mfu`` field when the chip's peak FLOP/s is
known (model FLOPs utilisation = achieved FLOP/s ÷ peak): the per-step FLOP
count comes from XLA's own cost analysis of the exact compiled executable
(``lower().compile().cost_analysis()``), falling back to an analytic model.
Image benches carry ``data: real|synthetic`` provenance (real files under
``DTTPU_DATA_DIR`` vs the procedural stand-ins in data/datasets.py) and gate
convergence on the provenance-appropriate threshold.

Telemetry (obs/): unless ``DTTPU_BENCH_TELEMETRY=0``, train-config JSON
lines carry ``step_time_p50_ms``/``step_time_p95_ms`` (per-update host
latency, every sample closed with a completion barrier) and
``trace_file`` — a Chrome-trace/Perfetto host timeline of the measured
dispatches plus every jit compile/retrace the sanitizer observed
(``DTTPU_BENCH_TRACE_FILE`` overrides the path,
``DTTPU_BENCH_LATENCY_STEPS`` sizes the async latency pass).

``vs_baseline``: the reference publishes no numbers (BASELINE.md:
"published: {}"), so the baseline is a measured stand-in for its
CPU/GPU-era stack: the SAME model/batch/optimizer stepped with torch on CPU
(the reference's TF-1.4 path is unrunnable here).  When torch is
unavailable the documented fallback constant is used.  Everything except
the JSON line goes to stderr.
"""
import json
import os
import sys
import time

SMOKE = bool(os.environ.get("DTTPU_BENCH_SMOKE"))

# Telemetry (obs/): DTTPU_BENCH_TELEMETRY=0 disables.  When on, the run
# records a host timeline (dispatch spans + RetraceGuard compile/retrace
# instants) whose file path lands in the JSON line as `trace_file`, and
# per-update host latencies (each closed with a completion barrier, never
# the async-dispatch lie dtlint DT107 flags) feed `step_time_p50_ms` /
# `step_time_p95_ms`.  Measured overhead on the CPU smoke bench is under
# 1% (docs/OBSERVABILITY.md).
TELEMETRY = os.environ.get("DTTPU_BENCH_TELEMETRY", "1") != "0"
_STEP_TIMES = []   # per-update seconds, barrier-closed (see _time_steps)
LATENCY_STEPS = int(os.environ.get("DTTPU_BENCH_LATENCY_STEPS", "10"))

_PROMOTED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "docs", "PROMOTED.json")


def _load_promoted_defaults():
    """docs/PROMOTED.json (written by scripts/promote_levers.py from
    measured MFU-ablation winners) supplies DEFAULTS for the lever env
    knobs — setdefault, so an explicitly exported env var still wins, and
    rows that record their lever state (loss_seq_chunk / remat_policy /
    mlm_predictions_per_seq in the result JSON) disclose what ran.

    Called from main() only — importing bench as a library must not
    mutate os.environ — and skipped under SMOKE: wiring checks measure
    nothing, so promoted real-hardware defaults would only make their
    behavior depend on repo state."""
    if SMOKE or not os.path.exists(_PROMOTED):
        return
    try:
        with open(_PROMOTED) as f:
            for k, v in (json.load(f).get("env") or {}).items():
                os.environ.setdefault(k, str(v))
    except (OSError, ValueError) as e:
        print(f"bench: ignoring unreadable {_PROMOTED}: {e}",
              file=sys.stderr)

# Estimated examples/sec for the reference-era stack on a single CPU host —
# used only if the live torch baseline cannot run.  Per config: these are
# measured torch-CPU rates from this machine (mnist/cifar) or the
# torchvision-resnet50-on-CPU ballpark (no torchvision in this image).
FALLBACK_BASELINE = {"mnist_mlp": 1.9e5, "cifar_cnn": 9.0e2,
                     "resnet50": 3.0}

BATCH = int(os.environ.get("DTTPU_BENCH_BATCH", 512 if SMOKE else 8192))
# Scanned updates per dispatch.  Each dispatch pays one host->device
# launch; more steps/call amortize it.
STEPS_PER_CALL = int(os.environ.get("DTTPU_BENCH_STEPS",
                                    4 if SMOKE else 64))
WARMUP_CALLS = 1 if SMOKE else 2
CALLS = 2 if SMOKE else 8
# Timed windows per measurement; the headline takes the BEST window.
# Applied symmetrically to the framework paths AND the torch baseline:
# the two sides run minutes apart, and on a shared host a background
# spike landing in one side's single window flips a ~1.0x ratio (the
# r04 rehearsal measured 0.97 and 1.01 for identical configs).
WINDOWS = 1 if SMOKE else max(1, int(os.environ.get("DTTPU_BENCH_WINDOWS",
                                                    "3")))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


_SYNC = None


def _sync_every_step() -> bool:
    """XLA:CPU collective rendezvous can't take deep async dispatch queues
    (a 40 s thread rendezvous deadlocks under many queued steps), so on the
    CPU mesh every step is blocked individually; on TPU the queue stays
    async and only the window-closing value fetch blocks."""
    global _SYNC
    if _SYNC is None:
        import jax
        _SYNC = SMOKE or jax.default_backend() == "cpu"
    return _SYNC


def _fetch(metrics) -> float:
    """Device->host fetch of the loss — the ONE completion rule of this
    file: a value fetch is a barrier on every backend (the bytes cannot
    arrive before the program that makes them has run).  The steps in a
    window form a donated-state chain, so fetching the last loss proves
    every step ran."""
    import numpy as np
    return float(np.asarray(metrics["loss"]).ravel()[-1])


# ---------------------------------------------------------------------------
# MFU accounting

# bf16 peak FLOP/s per chip by device_kind substring (public TPU specs).
_PEAK_BF16 = [("v6e", 918e12), ("v6 lite", 918e12), ("v5p", 459e12),
              ("v5e", 197e12), ("v5 lite", 197e12), ("v4", 275e12),
              ("v3", 123e12), ("v2", 46e12)]


def _peak_for_device(table, what):
    """This device's entry of a per-``device_kind`` peak table.  None off
    the accelerator (a CPU has no row and gets no utilization figure); a
    TPU whose kind is NOT in the table is an error — returning None there
    made the MFU field quietly disappear from a chip run."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind.lower()
    for key, val in table:
        if key in kind:
            return val
    raise RuntimeError(
        f"no {what} for TPU device_kind {dev.device_kind!r}: add it to "
        f"the table in bench.py with its source")


def _peak_flops_per_chip():
    """Per-chip peak bf16 FLOP/s, or None on the CPU mesh.
    ``DTTPU_PEAK_FLOPS`` overrides (tests pin a fake roofline with it)."""
    env = os.environ.get("DTTPU_PEAK_FLOPS")
    if env:
        return float(env)
    return _peak_for_device(_PEAK_BF16, "peak bf16 FLOP/s")


def _flops_of(fn, *args):
    """Total FLOPs of one call of a jitted ``fn`` on ``args``, from XLA's
    cost analysis of the exact compiled executable.  Returns None when the
    backend doesn't report flops.  Lowering is shape-only (nothing runs,
    donated buffers are untouched)."""
    try:
        target = fn if hasattr(fn, "lower") else None
        if target is None:
            return None
        cost = target.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        f = float(cost.get("flops", 0.0) or 0.0)
        return f if f > 0 else None
    except Exception as e:  # pragma: no cover - backend-specific
        log(f"cost_analysis unavailable ({e})")
        return None


def _per_example_flops(f_total, global_examples, mesh):
    """XLA's ``cost_analysis`` reports the per-device SPMD program's FLOPs;
    divide by the per-device (local) example count — global/ data shards —
    not the global batch, or mfu understates by the shard count on
    multi-device meshes (advisor round 2)."""
    if not f_total:
        return None
    from distributed_tensorflow_tpu import parallel
    return f_total * parallel.data_shards(mesh) / global_examples


def _attach_mfu(result: dict, rate_per_chip: float, flops_per_example,
                analytic=None, scanned=False) -> dict:
    """Add flops/example + mfu fields to a bench result.  ``rate_per_chip``
    is examples/s/chip (or tokens/s/chip with flops per token).

    XLA's ``cost_analysis`` counts a ``lax.scan`` body ONCE regardless of
    trip count (measured: an 8-iteration scan of a matmul body reports the
    same flops as a 1-iteration scan), so for scanned programs — the LM
    layer stacks, the K-step multi-dispatch — the compiled-step figure
    undercounts by ~the trip count and the mfu field understated by the
    same factor in rounds 2-4 (gpt read 0.17 while the analytic 6N+12Lhs
    accounting of the identical run gives 0.45).  Callers whose timed
    program contains a scan pass ``scanned=True``; for those rows, when
    the XLA figure is less than 60% of the analytic estimate, trust the
    analytic model and keep the raw XLA number in
    ``flops_xla_scan_undercount`` for the record.  Unscanned rows always
    keep the XLA source (resnet: XLA ~= 3x the forward-only analytic
    constant, and silently replacing an honest compiled-step figure with
    a rough hard-coded constant would corrupt the provenance trail)."""
    f = flops_per_example or analytic
    if not f:
        return result
    source = "xla" if flops_per_example else "analytic"
    if (scanned and flops_per_example and analytic
            and flops_per_example < 0.6 * analytic):
        result["flops_xla_scan_undercount"] = round(float(flops_per_example), 1)
        f, source = analytic, "analytic"
    result["flops_per_example"] = round(float(f), 1)
    result["flops_source"] = source
    peak = _peak_flops_per_chip()
    if peak:
        result["mfu"] = round(rate_per_chip * f / peak, 4)
    return result


# HBM bandwidth per chip by device_kind substring (public TPU specs),
# bytes/s — the roofline's second axis next to _PEAK_BF16.
_PEAK_HBM_BW = [("v6e", 1640e9), ("v6 lite", 1640e9), ("v5p", 2765e9),
                ("v5e", 819e9), ("v5 lite", 819e9), ("v4", 1228e9),
                ("v3", 900e9), ("v2", 700e9)]


def _peak_hbm_bw():
    """Per-chip HBM bandwidth in bytes/s, or None on the CPU mesh.
    ``DTTPU_PEAK_BW`` overrides (tests pin a fake roofline with it)."""
    env = os.environ.get("DTTPU_PEAK_BW")
    if env:
        return float(env)
    return _peak_for_device(_PEAK_HBM_BW, "peak HBM bytes/s")


def _attach_analytical(result: dict, step_fn, abstract_args,
                       tokens_per_step=None, in_specs=None,
                       mesh=None) -> dict:
    """Add the dtlint graph-tier cost model's static numbers next to the
    measured ones, making every perf claim cross-checkable against a
    roofline that was computed from the SAME traced program the lint
    gate checks (docs/ANALYSIS.md §graph tier):

    * ``analytical_flops`` / ``analytical_bytes``: FLOPs and bytes-moved
      of ONE compiled step per the cost model — scan bodies count times
      their trip count, so unlike XLA's ``cost_analysis`` this figure
      does not undercount the layer stack or the K-step dispatch;
    * ``analytical_flops_per_token`` when ``tokens_per_step`` is given;
    * ``analytical_mfu``: the roofline CEILING as an MFU fraction —
      ``min(1, peak_bw * intensity / peak_flops)`` — i.e. the best MFU
      this program shape can reach on this part.  A measured ``mfu``
      above it means the accounting (not the hardware) is wrong; far
      below it means the implementation leaves roofline on the table.
      Needs a known peak (``DTTPU_PEAK_FLOPS``/``DTTPU_PEAK_BW`` pin a
      fake roofline on the CPU smoke; bw unknown -> compute-bound
      ceiling 1.0);
    * ``analytical_comm_bytes`` / ``analytical_comm_time_s`` (when the
      caller passes ``in_specs``+``mesh``): the SPMD tier's static
      communication ledger for the same traced step — per-device wire
      bytes and modeled time of every collective the propagation finds
      (docs/ANALYSIS.md §spmd tier).  The sentinel holds these to a
      tight tolerance: static comm volume only moves when the program
      changes, so unexpected growth reds ``scripts/perf_gate.py``.

    Tracing is abstract (``jax.eval_shape``-style args) and never
    compiles; any failure logs and leaves the measured row intact.
    """
    try:
        from distributed_tensorflow_tpu.analysis import graph as graph_lib
        cost = graph_lib.entry_cost(step_fn, *abstract_args)
    except Exception as e:  # pragma: no cover - shape-spec drift
        log(f"analytical cost model unavailable ({e})")
        return result
    result["analytical_flops"] = round(float(cost.flops), 1)
    result["analytical_bytes"] = round(float(cost.bytes), 1)
    if tokens_per_step:
        result["analytical_flops_per_token"] = round(
            float(cost.flops) / tokens_per_step, 1)
    peak = _peak_flops_per_chip()
    if peak:
        bw = _peak_hbm_bw()
        ceiling = (min(1.0, bw * cost.intensity / peak) if bw else 1.0)
        result["analytical_mfu"] = round(ceiling, 4)
    if in_specs is not None and mesh is not None:
        try:
            from distributed_tensorflow_tpu.analysis import spmd as spmd_lib
            ledger = spmd_lib.entry_comm(step_fn, *abstract_args,
                                         in_specs=in_specs, mesh=mesh)
            result["analytical_comm_bytes"] = round(
                float(ledger.total_bytes), 1)
            result["analytical_comm_time_s"] = float(
                f"{ledger.total_time_s:.3e}")
        except Exception as e:  # pragma: no cover - propagation drift
            log(f"analytical comm ledger unavailable ({e})")
    return result


def _transformer_flops_per_token(params, num_layers: int, hidden: int,
                                 seq: int) -> float:
    """Analytic training FLOPs/token for a dense transformer: 6N for the
    matmul path (fwd 2N + bwd 4N) + 12*L*h*s for attention logits/context
    (fwd 4*L*h*s halves for QK^T and PV, x3 for training)."""
    import jax
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    return 6.0 * n + 12.0 * num_layers * hidden * seq


# ---------------------------------------------------------------------------
# Measurement core


def bench_framework():
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_tpu import data, models, optim, parallel, train

    n_chips = len(jax.devices())
    mesh = parallel.data_parallel_mesh()
    log(f"framework: {n_chips} x {jax.devices()[0].platform}, "
        f"mesh={dict(mesh.shape)}")

    data_dir = os.environ.get("DTTPU_DATA_DIR")
    prov = data.provenance("mnist", data_dir)
    (xt, yt), (xv, yv) = data.mnist(data_dir, flatten=True)
    model = models.mnist_mlp()
    optimizer = optim.adam()
    step = train.make_train_step(model, "sparse_categorical_crossentropy",
                                 optimizer, mesh=mesh)
    eval_step = train.make_eval_step(model, "sparse_categorical_crossentropy",
                                     metric_fns={"accuracy": "accuracy"})
    state = train.init_train_state(model, optimizer, jax.random.PRNGKey(0),
                                   (784,))
    state = jax.device_put(state, NamedSharding(mesh, P()))
    bsh = NamedSharding(mesh, P("data"))

    batch = parallel.round_batch_to_mesh(BATCH, mesh)
    # backend="auto": the native C++ threaded gather loader when built.
    ds = data.Dataset([xt, yt], batch, seed=0, backend="auto")

    # Convergence gate: a couple of epochs must clear the eval threshold.
    for b in ds.epochs(1 if SMOKE else 2):
        state, m_ = step(state, jax.device_put(b, bsh))
        if _sync_every_step():
            jax.block_until_ready(m_["loss"])
    acc = float(eval_step(state, (xv[:8192], yv[:8192]))["accuracy"])
    log(f"eval accuracy after 2 epochs ({prov} data): {acc:.4f}")

    # Throughput: the framework's multi-step path — STEPS_PER_CALL updates
    # scanned inside ONE compiled dispatch (train.make_multi_train_step), a
    # device-resident stacked batch, block at the end.
    multi = train.make_multi_train_step(
        model, "sparse_categorical_crossentropy", optimizer,
        steps_per_call=STEPS_PER_CALL, mesh=mesh)
    k = STEPS_PER_CALL
    xs = np.resize(xt, (k * batch, xt.shape[1])).reshape(k, batch, -1)
    ys = np.resize(yt, (k * batch,)).reshape(k, batch)
    msh = NamedSharding(mesh, P(None, "data"))
    bench_batch = (jax.device_put(xs, msh), jax.device_put(ys, msh))
    f_total = _flops_of(multi, state, bench_batch)
    flops_per_example = _per_example_flops(f_total, k * batch, mesh)
    rate, _, sec, state = _time_steps(multi, state, bench_batch,
                                      warmup=WARMUP_CALLS, steps=CALLS,
                                      updates_per_call=k)
    eps = rate * k * batch
    log(f"framework (multi-step): {eps:,.0f} examples/s total, "
        f"{eps / n_chips:,.0f} /chip ({sec / k * 1e3:.2f} ms/step, "
        f"best of {WINDOWS} windows, {k} steps/dispatch)")

    # Single-step dispatch path (what TrainSession drives per batch) — kept
    # visible so a regression there can't hide behind the scanned number.
    single_batch = (bench_batch[0][0], bench_batch[1][0])
    rate, _, sec, state = _time_steps(step, state, single_batch,
                                      warmup=5, steps=40)
    eps_single = rate * batch
    log(f"framework (single-step): {eps_single:,.0f} examples/s total "
        f"({sec * 1e3:.2f} ms/step, best of {WINDOWS} windows)")
    return (eps / n_chips, acc, eps_single / n_chips, prov,
            flops_per_example)


def bench_torch_baseline():
    """Same MLP/batch/optimizer stepped with torch on CPU (reference-era
    proxy: host-resident training, no XLA)."""

    def build():
        import torch
        import torch.nn as nn
        model = nn.Sequential(nn.Linear(784, 128), nn.ReLU(),
                              nn.Dropout(0.2), nn.Linear(128, 10))
        x = torch.rand(BATCH, 784)
        y = torch.randint(0, 10, (BATCH,))
        ce = nn.CrossEntropyLoss()
        return model, lambda out: ce(out, y), \
            torch.optim.Adam(model.parameters()), (x,), BATCH

    # steps matches the framework's single-step window (40; _time_steps
    # clamps to 4 under SMOKE): comparable window DURATION means equal
    # exposure to background-noise spikes, so the two sides' best-of-N
    # statistics are comparable
    return _torch_step_rate(build, warmup=3, steps=4 if SMOKE else 40)


def _time_steps(step, state, batch, warmup=3, steps=12, updates_per_call=1):
    """Generic throughput timing for a compiled train step.  Returns
    (steps/sec, last loss, sec/step, final state) from the BEST of
    ``WINDOWS`` timed windows (same treatment as the torch baseline —
    see WINDOWS); per-chip normalization is the caller's job.  The input
    ``state`` is DONATED into the step chain — callers continuing to
    step must use the returned state.  On the CPU mesh every step is
    synced (see ``_sync_every_step``).

    Telemetry side channel (``TELEMETRY``): each timed dispatch is
    wrapped in an obs "dispatch" span, and per-UPDATE host latencies are
    collected into ``_STEP_TIMES`` for the JSON line's p50/p95 — only
    where a completion barrier closes the step: inline on the synced CPU
    mesh, and via a short dedicated pass (``LATENCY_STEPS``, each step
    closed with a value fetch) on async backends, where a per-step host
    clock inside the pipelined window would time dispatch (the DT107
    lie).  ``updates_per_call``: scanned multi-step dispatches report
    per-update latency, not per-dispatch."""
    import jax
    from distributed_tensorflow_tpu.obs import trace as obs_trace
    if SMOKE:
        warmup, steps = min(warmup, 2), min(steps, 4)
    for _ in range(warmup):
        state, m = step(state, batch)
        if _sync_every_step():
            jax.block_until_ready(m["loss"])
    _fetch(m)
    sync = _sync_every_step()
    # every window's (dt, loss) is captured together so the returned rate,
    # sec/step and loss all come from the SAME (best) window
    best_dt, best_loss = None, None
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(steps):
            t_step = time.perf_counter()
            with obs_trace.span("dispatch", updates=updates_per_call):
                state, m = step(state, batch)
            if sync:
                jax.block_until_ready(m["loss"])
                if TELEMETRY:
                    _STEP_TIMES.append(
                        (time.perf_counter() - t_step) / updates_per_call)
        loss = _fetch(m)
        dt = time.perf_counter() - t0
        if best_dt is None or dt < best_dt:
            best_dt, best_loss = dt, loss
    if TELEMETRY and not sync:
        for _ in range(min(steps, LATENCY_STEPS)):
            t_step = time.perf_counter()
            with obs_trace.span("dispatch", updates=updates_per_call):
                state, m = step(state, batch)
            _fetch(m)   # value fetch: the only honest barrier (docstring)
            _STEP_TIMES.append(
                (time.perf_counter() - t_step) / updates_per_call)
    return steps / best_dt, best_loss, best_dt / steps, state


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Ran out of memory", "out of memory",
                "hbm capacity", "Allocation failure")


def _is_oom(e: Exception) -> bool:
    """OOM classification for the batch ladder.  Primary signal: a jaxlib
    ``XlaRuntimeError`` whose status line is RESOURCE_EXHAUSTED (the
    canonical ``{code}: {message}`` rendering); the marker substrings cover
    runtimes that phrase allocation failure differently."""
    try:
        from jax.errors import JaxRuntimeError
        if (isinstance(e, JaxRuntimeError)
                and str(e).lstrip().startswith("RESOURCE_EXHAUSTED")):
            return True
    except ImportError:
        pass
    return any(k in str(e) for k in _OOM_MARKERS)


def _run_batch_ladder(name, ladder, mesh, build, step, warmup, steps):
    """Time ``step`` at the largest per-chip batch that fits.

    ``build(global_batch) -> (state, bench_batch)`` allocates fresh device
    buffers per rung (the step donates state, so a failed rung's state is
    unusable).  Only OOM errors descend the ladder — anything else is a
    real bug and raises immediately with its original traceback.  Failed
    rungs' buffers are dropped before the next allocation so the retry
    doesn't OOM on the dead rung's memory.

    Returns (steps/sec, loss, sec/step, global_batch, step_flops|None).
    """
    from distributed_tensorflow_tpu import parallel
    err = None
    for per_chip in ladder:
        batch = parallel.round_batch_to_mesh(
            per_chip * parallel.data_shards(mesh), mesh)
        state, bench_batch = build(batch)
        try:
            flops = _flops_of(step, state, bench_batch)
            rate, loss, ms, _ = _time_steps(step, state, bench_batch,
                                            warmup=warmup, steps=steps)
            return rate, loss, ms, batch, flops
        except Exception as e:
            if not _is_oom(e):
                raise
            err = e
            log(f"{name}: batch {per_chip}/chip OOM; retrying smaller")
            state = bench_batch = None   # free before the next rung
    raise err


def _torch_step_rate(build, warmup=2, steps=3):
    """examples/sec for the same workload stepped with torch on CPU;
    ``build() -> (module, loss_fn, optimizer, example_inputs, batch)``.
    Returns None (logged) on ANY failure — a missing torch/torchvision
    feature must not lose the framework measurement."""
    try:
        import torch
        torch.manual_seed(0)
        model, loss_fn, opt, inputs, batch = build()
        for _ in range(warmup):
            opt.zero_grad(); loss_fn(model(*inputs)).backward(); opt.step()
        eps = 0.0
        for _ in range(WINDOWS):    # best-of, same as the framework side
            t0 = time.perf_counter()
            for _ in range(steps):
                opt.zero_grad()
                loss_fn(model(*inputs)).backward()
                opt.step()
            eps = max(eps, steps * batch / (time.perf_counter() - t0))
    except Exception as e:  # pragma: no cover
        log(f"torch baseline unavailable ({e})")
        return None
    log(f"torch CPU baseline: {eps:,.1f} examples/s (best of {WINDOWS})")
    return eps


def bench_cifar_cnn():
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_tensorflow_tpu import data, models, optim, parallel, train

    n_chips = len(jax.devices())
    mesh = parallel.data_parallel_mesh()
    batch = parallel.round_batch_to_mesh(64 if SMOKE else 1024, mesh)
    data_dir = os.environ.get("DTTPU_DATA_DIR")
    prov = data.provenance("cifar10", data_dir)
    (xt, yt), (xv, yv) = data.cifar10(data_dir)
    model = models.cifar_cnn()
    optimizer = optim.adam()
    step = train.make_train_step(model, "sparse_categorical_crossentropy",
                                 optimizer, mesh=mesh)
    eval_step = train.make_eval_step(model, "sparse_categorical_crossentropy",
                                     metric_fns={"accuracy": "accuracy"})
    state = train.init_train_state(model, optimizer, jax.random.PRNGKey(0),
                                   (32, 32, 3))
    state = jax.device_put(state, NamedSharding(mesh, P()))
    bsh = NamedSharding(mesh, P("data"))
    ds = data.Dataset([xt, yt], batch, seed=0, backend="auto")
    epochs = 1 if SMOKE else 2
    for i, b in enumerate(ds.epochs(epochs)):
        state, m = step(state, jax.device_put(b, bsh))
        # smoke: enough steps to actually clear the 0.15 smoke gate
        # (one step left accuracy at chance and the gate un-passable)
        if SMOKE and i >= 30:
            break
        if _sync_every_step():
            jax.block_until_ready(m["loss"])
    acc = float(eval_step(state, (xv[:2048], yv[:2048]))["accuracy"])
    log(f"cifar_cnn eval accuracy ({prov} data): {acc:.4f}")
    bench_batch = jax.device_put(next(iter(ds)), bsh)
    f_total = _flops_of(step, state, bench_batch)
    rate, loss, ms, _ = _time_steps(step, state, bench_batch)
    eps = rate * batch / n_chips
    log(f"cifar_cnn: {eps:,.0f} examples/s/chip ({ms*1e3:.2f} ms/step)")

    def torch_build():
        import torch
        import torch.nn as nn
        m = nn.Sequential(
            nn.Conv2d(3, 32, 3), nn.ReLU(), nn.Conv2d(32, 32, 3), nn.ReLU(),
            nn.MaxPool2d(2), nn.Conv2d(32, 64, 3), nn.ReLU(),
            nn.Conv2d(64, 64, 3), nn.ReLU(), nn.MaxPool2d(2), nn.Flatten(),
            nn.LazyLinear(256), nn.ReLU(), nn.Dropout(0.5), nn.Linear(256, 10))
        tb = 64
        x = torch.rand(tb, 3, 32, 32)
        y = torch.randint(0, 10, (tb,))
        ce = nn.CrossEntropyLoss()
        m(x)  # materialize lazy
        return m, lambda out: ce(out, y), torch.optim.Adam(m.parameters()), (x,), tb

    # steps=8 keeps the torch windows in the same duration ballpark as the
    # framework's 12-step windows (best-of-N comparability, see WINDOWS)
    baseline = (_torch_step_rate(torch_build, steps=2 if SMOKE else 8)
                or FALLBACK_BASELINE["cifar_cnn"])
    gate = 0.15 if SMOKE else (0.40 if prov == "real" else 0.35)
    result = dict(metric="cifar_cnn_train_examples_per_sec_per_chip"
                         + ("" if acc > gate else "_NOT_CONVERGED"),
                  value=round(eps, 1), unit="examples/sec/chip",
                  vs_baseline=round(eps / baseline, 3),
                  eval_accuracy=round(acc, 4), data=prov)
    return _attach_mfu(result, eps, _per_example_flops(f_total, batch, mesh),
                       analytic=1.53e8)


def bench_resnet50():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_tensorflow_tpu import models, optim, parallel, train

    n_chips = len(jax.devices())
    mesh = parallel.data_parallel_mesh()
    size = 64 if SMOKE else 224
    model = models.resnet50(num_classes=1000)
    optimizer = optim.momentum(0.1, beta=0.9)
    # mixed_bfloat16: without the policy the f32 conv kernels promote the
    # bf16 batch back to f32 and every conv runs off the bf16 MXU path —
    # the master params stay f32 (grads/update in f32)
    step = train.make_train_step(model, "sparse_categorical_crossentropy",
                                 optimizer, mesh=mesh,
                                 policy="mixed_bfloat16")
    rng = np.random.default_rng(0)
    bsh = NamedSharding(mesh, P("data"))

    def build(batch):
        state = train.init_train_state(model, optimizer,
                                       jax.random.PRNGKey(0),
                                       (size, size, 3))
        state = jax.device_put(state, NamedSharding(mesh, P()))
        x = rng.random((batch, size, size, 3), np.float32)
        y = rng.integers(0, 1000, batch).astype(np.int32)
        return state, (jax.device_put(jnp.asarray(x, jnp.bfloat16), bsh),
                       jax.device_put(y, bsh))

    # 256/chip measured +22% over 64/chip on v5e (probe 2026-07-30); the
    # bf16 policy halves activation memory so 512 leads the ladder, which
    # descends on OOM for smaller-HBM parts.
    rate, loss, ms, batch, f_total = _run_batch_ladder(
        "resnet50", [8] if SMOKE else [512, 256, 128, 64], mesh, build, step,
        warmup=2, steps=4 if SMOKE else 10)
    eps = rate * batch / n_chips
    log(f"resnet50: {eps:,.1f} examples/s/chip ({ms*1e3:.1f} ms/step, "
        f"loss={loss:.3f})")

    def torch_build():
        import torch
        import torch.nn as nn
        try:
            from torchvision.models import resnet50 as tv_resnet50
            m = tv_resnet50()
        except Exception:
            raise RuntimeError("torchvision unavailable")
        tb = 4
        x = torch.rand(tb, 3, size, size)
        y = torch.randint(0, 1000, (tb,))
        ce = nn.CrossEntropyLoss()
        return m, lambda out: ce(out, y), \
            torch.optim.SGD(m.parameters(), 0.1, momentum=0.9), (x,), tb

    baseline = _torch_step_rate(torch_build) or FALLBACK_BASELINE["resnet50"]
    finite = np.isfinite(loss)
    result = dict(metric="resnet50_train_examples_per_sec_per_chip"
                         + ("" if finite else "_NONFINITE_LOSS"),
                  value=round(eps, 2), unit="examples/sec/chip",
                  vs_baseline=round(eps / baseline, 3),
                  image_size=size, batch=batch)
    return _attach_mfu(result, eps, _per_example_flops(f_total, batch, mesh),
                       analytic=12.3e9 * (size / 224) ** 2)


def bench_bert():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_tensorflow_tpu import optim, train, parallel
    from distributed_tensorflow_tpu.models.bert import Bert, BertConfig

    n_chips = len(jax.devices())
    mesh = parallel.data_parallel_mesh()
    seq = int(os.environ.get("DTTPU_BENCH_BERT_SEQ", "128"))
    # DTTPU_BENCH_MLM_GATHER=1: head on masked positions only (cap 20% of
    # seq) — A/B hook until the hardware ablation decides the default
    gather = (seq // 5
              if os.environ.get("DTTPU_BENCH_MLM_GATHER") == "1" else 0)
    # DTTPU_BENCH_BERT_REMAT: "" (off) / "full" / "dots".  Evidence
    # (builder-measured 2026-08-01, docs/PERF.md ablation tables — note
    # every ablation arm including "base" ran remat=True, unlike this
    # row's no-remat default): dots-vs-full is +12.4% (147,351 vs 131,123 tok/s/chip),
    # and the full lever set (dots + gather + b128, arm
    # remat_dots_gather 168,819) beats the same window's measured bench
    # row (gather, no remat, b96: 134,995) by +25% — that composite win
    # is what promote_levers' mapping buys.
    remat_policy = os.environ.get("DTTPU_BENCH_BERT_REMAT", "").strip().lower()
    if remat_policy in ("0", "off", "false", "no", "none"):
        remat_policy = ""  # natural disable spellings, not a policy name
    elif remat_policy and remat_policy not in ("full", "dots",
                                               "dots_no_batch"):
        raise SystemExit("DTTPU_BENCH_BERT_REMAT must be ''/off/full/dots/"
                         f"dots_no_batch; got {remat_policy!r}")
    remat = dict(remat=True, remat_policy=remat_policy) if remat_policy \
        else {}
    # DTTPU_BENCH_BERT_FUSED_LN=1: the fused Pallas LayerNorm.  The pure
    # arm measured +6.4% (08-01 ablation) but its composition with the
    # promoted remat_dots+gather defaults is unmeasured — promote_levers
    # deliberately has NO mapping for it until the composite arm
    # (remat_dots_gather_ln, queued) is, so this knob is for measured
    # flips only.
    fused_ln = os.environ.get("DTTPU_BENCH_BERT_FUSED_LN") == "1"
    # dropout_rate=0.0: aligns this row with the gpt/llama rows (and with
    # every mfu_ablation arm) — BertConfig's 0.1 default was the ONLY LM
    # row still paying per-layer dropout mask generation, which measured
    # 47% on 2026-08-01 (bench row 119,627 vs the same-lever ablation arm
    # 176,237 tok/s/chip, logs/followups_r5b.log).
    config = (BertConfig(vocab_size=512, hidden_size=128, num_layers=2,
                         num_heads=2, intermediate_size=512,
                         max_position=seq, dtype=jnp.bfloat16,
                         dropout_rate=0.0,
                         mlm_predictions_per_seq=gather,
                         fused_layernorm=fused_ln, **remat) if SMOKE
              else BertConfig(max_position=seq, dtype=jnp.bfloat16,
                              dropout_rate=0.0,
                              mlm_predictions_per_seq=gather,
                              fused_layernorm=fused_ln, **remat))
    model = Bert(config, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    optimizer = optim.adamw(1e-4)
    step = train.make_custom_train_step(model.mlm_loss_fn(), optimizer,
                                        grad_clip_norm=1.0)
    rng = np.random.default_rng(0)
    bsh = NamedSharding(mesh, P("data"))

    def build(batch):
        state = train.TrainState.create(params, optimizer.init(params))
        state = jax.device_put(state, NamedSharding(mesh, P()))
        bench_batch = jax.device_put({
            "input_ids": rng.integers(0, config.vocab_size,
                                      (batch, seq)).astype(np.int32),
            "labels": rng.integers(0, config.vocab_size,
                                   (batch, seq)).astype(np.int32),
            "mlm_mask": (rng.random((batch, seq)) < 0.15).astype(np.float32),
            "attention_mask": np.ones((batch, seq), np.int32),
        }, bsh)
        return state, bench_batch

    # 96/chip measured best on v5e without levers (probe 2026-07-30:
    # 109k tok/s/chip vs 85k at 32/chip; 128/chip OOMs without remat at
    # seq 128).  With REMAT on, the 08-01 ablation measured batch 128
    # fitting AND faster (remat_dots_gather b128 168,819 — the best
    # arm), so the ladder tries 128 first; an OOM rung falls through.
    # Gather alone does NOT unlock 128 — no arm measured b128 without
    # remat, and the 07-30 probe says it OOMs — so that case keeps the
    # 96-first ladder.
    ladder = [128, 96, 48, 24] if remat_policy else [96, 48, 24]
    rate, loss, ms, batch, f_total = _run_batch_ladder(
        "bert", [4] if SMOKE else ladder, mesh, build, step,
        warmup=2, steps=4 if SMOKE else 10)
    tokens = rate * batch * seq / n_chips
    log(f"bert: {tokens:,.0f} tokens/s/chip ({ms*1e3:.1f} ms/step, "
        f"loss={loss:.3f})")
    finite = np.isfinite(loss)
    result = dict(metric="bert_mlm_train_tokens_per_sec_per_chip"
                         + ("" if finite else "_NONFINITE_LOSS"),
                  value=round(tokens, 1), unit="tokens/sec/chip",
                  vs_baseline=1.0,  # no runnable reference-era BERT
                  # baseline exists; 1.0 = "unity ratio by definition"
                  seq_len=seq, batch=batch)
    # the gathered head skips work on non-gathered tokens; the XLA-counted
    # f_total already reflects this, the analytic fallback must too
    from distributed_tensorflow_tpu.models.bert import \
        mlm_gather_flops_correction
    analytic = (_transformer_flops_per_token(params, config.num_layers,
                                             config.hidden_size, seq)
                - mlm_gather_flops_correction(config, seq))
    if gather:
        result["mlm_predictions_per_seq"] = gather
    if remat_policy:
        result["remat_policy"] = remat_policy
    if fused_ln:
        result["fused_layernorm"] = True
    return _attach_mfu(
        result, tokens, _per_example_flops(f_total, batch * seq, mesh),
        analytic=analytic, scanned=True)


def bench_mnist_mlp():
    value_multi, acc, value_single, prov, flops = bench_framework()
    baseline = bench_torch_baseline()
    if baseline is None:
        baseline = FALLBACK_BASELINE["mnist_mlp"]
    gate = 0.95 if prov == "real" else 0.9
    converged = acc > gate
    # Headline = best dispatch mode.  Both are legitimate framework paths
    # (TrainSession drives single-step; fit(steps_per_execution=K) the
    # scanned one); on a single CPU device the scan's state-donation chain
    # is slower than plain dispatch, and reporting the multi-step number
    # unconditionally handed r03's fallback 0.92 while the same run's
    # single-step was 1.03.  This is a CONFIG selection (which dispatch
    # discipline to run), not extra noise samples — each mode's own rate
    # is already its best-of-WINDOWS, same as the torch side's.
    value = max(value_multi, value_single)
    result = {
        "metric": "mnist_mlp_train_examples_per_sec_per_chip"
                  + ("" if converged else "_NOT_CONVERGED"),
        "value": round(value, 1),
        "unit": "examples/sec/chip",
        "vs_baseline": round(value / baseline, 3),
        "steps_per_call": STEPS_PER_CALL,
        "dispatch_mode": "multi" if value_multi >= value_single else "single",
        "multi_step_value": round(value_multi, 1),
        "single_step_value": round(value_single, 1),
        # r01-r03 records reported the multi-step ratio unconditionally;
        # keep emitting it so cross-round trend lines stay comparable
        "multi_step_vs_baseline": round(value_multi / baseline, 3),
        "eval_accuracy": round(acc, 4),
        "data": prov,
    }
    # flops comes from the K-step multi-dispatch scan (bench_framework)
    return _attach_mfu(result, value, flops, analytic=6.1e5, scanned=True)


def _gpt_bench_config(seq, experts=0):
    """The GPT bench model: GPT-2-small (or the SMOKE shrink), bf16.
    ONE constructor shared by the train and decode rows so their numbers
    stay measurements of the same model."""
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.gpt import GPTConfig

    # remat=True: the layer-scan otherwise saves every activation for
    # backward and OOMs a 16G chip at batch 48/seq 256; rematerialising
    # measured FASTER at equal batch too (scripts/tune_gpt_batch.py,
    # 2026-07-31: 120k tok/s at remat batch 48 vs 101-108k no-remat 24)
    moe = dict(moe_experts=experts, moe_top_k=2) if experts else {}
    # DTTPU_BENCH_LOSS_CHUNK > 0: chunked LM loss (the [tokens, vocab]
    # logits never materialise); DTTPU_BENCH_REMAT_POLICY: what the
    # per-layer checkpoint saves — A/B hooks until the hardware ablation
    # (scripts/mfu_ablation.py) decides the defaults
    chunk = int(os.environ.get("DTTPU_BENCH_LOSS_CHUNK", "0"))
    rpol = os.environ.get("DTTPU_BENCH_REMAT_POLICY", "full")
    return (GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                      num_heads=2, intermediate_size=512,
                      max_position=seq, dtype=jnp.bfloat16,
                      dropout_rate=0.0, remat=True, remat_policy=rpol,
                      loss_seq_chunk=chunk, **moe) if SMOKE
            else GPTConfig(vocab_size=50257, hidden_size=768,
                           num_layers=12, num_heads=12,
                           intermediate_size=3072, max_position=seq,
                           dtype=jnp.bfloat16, dropout_rate=0.0,
                           remat=True, remat_policy=rpol,
                           loss_seq_chunk=chunk, **moe))


def bench_gpt(seq=None, experts=None):
    """Causal-LM training throughput (tokens/s/chip) on a GPT-2-small-
    shaped decoder, bf16, adamw — the LM-family row next to BERT's MLM.
    Explicit ``seq``/``experts`` arguments WIN over the env vars (the
    moe/long rows pass them to define their row; an exported
    DTTPU_BENCH_SEQ must not silently retarget a named row) — the env
    vars only fill in when the caller passes None."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_tensorflow_tpu import optim, train, parallel
    from distributed_tensorflow_tpu.models.gpt import GPT

    n_chips = len(jax.devices())
    mesh = parallel.data_parallel_mesh()
    seq = (int(seq) if seq is not None
           else int(os.environ.get("DTTPU_BENCH_SEQ", 256)))
    experts = (int(experts) if experts is not None
               else int(os.environ.get("DTTPU_BENCH_GPT_MOE", 0)))
    config = _gpt_bench_config(seq, experts)
    model = GPT(config, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0))
    optimizer = optim.adamw(1e-4)
    step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                        grad_clip_norm=1.0)
    rng = np.random.default_rng(0)
    bsh = NamedSharding(mesh, P("data"))

    def build(batch):
        state = train.TrainState.create(params, optimizer.init(params))
        state = jax.device_put(state, NamedSharding(mesh, P()))
        tokens = rng.integers(0, config.vocab_size,
                              (batch, seq + 1)).astype(np.int32)
        # lm_loss_fn shifts internally: inputs ids[:, :-1], targets [:, 1:]
        bench_batch = jax.device_put({"input_ids": tokens}, bsh)
        return state, bench_batch

    ladder = ([4] if SMOKE else
              [max(1, 48 * 256 // seq), max(1, 24 * 256 // seq),
               max(1, 12 * 256 // seq)])
    if config.loss_seq_chunk and not SMOKE:
        # chunked LM loss removes the [tokens, vocab] logits wall (~2.5GB f32
        # at seq 2048 batch 6) — the explicit A/B lever earns a 2x rung
        # the plain ladder can't attempt
        ladder = [max(1, 96 * 256 // seq)] + ladder
    rate, loss, ms, batch, f_total = _run_batch_ladder(
        "gpt", ladder, mesh, build, step,
        warmup=2, steps=4 if SMOKE else 10)
    tokens_s = rate * batch * seq / n_chips
    log(f"gpt: {tokens_s:,.0f} tokens/s/chip ({ms*1e3:.1f} ms/step, "
        f"loss={loss:.3f})")
    finite = np.isfinite(loss)
    result = dict(metric="gpt_lm_train_tokens_per_sec_per_chip"
                         + ("" if finite else "_NONFINITE_LOSS"),
                  value=round(tokens_s, 1), unit="tokens/sec/chip",
                  vs_baseline=1.0,  # no reference-era GPT baseline exists
                  seq_len=seq, batch=batch)
    if config.loss_seq_chunk:
        result["loss_seq_chunk"] = config.loss_seq_chunk
    if config.remat_policy != "full":
        result["remat_policy"] = config.remat_policy
    analytic = _transformer_flops_per_token(params, config.num_layers,
                                            config.hidden_size, seq)
    if experts:
        # 6N counts every expert's FFN weights, but each token routes
        # through only top_k of them — discount the inactive experts'
        # matmul flops or the MoE row's mfu overstates by ~experts/top_k
        # on the FFN share
        from jax.tree_util import tree_flatten_with_path
        n_exp = sum(int(v.size) for p, v in tree_flatten_with_path(params)[0]
                    if any("expert" in str(k).lower() for k in p))
        analytic -= 6.0 * n_exp * max(0.0, 1.0 - config.moe_top_k / experts)
    result = _attach_mfu(
        result, tokens_s, _per_example_flops(f_total, batch * seq, mesh),
        analytic=analytic, scanned=True)
    # graph-tier static cross-check: trace the SAME step abstractly and
    # attach the cost model's flops/bytes + the roofline MFU ceiling
    state_a = jax.eval_shape(
        lambda p: train.TrainState.create(p, optimizer.init(p)), params)
    batch_a = {"input_ids": jax.ShapeDtypeStruct((batch, seq + 1),
                                                 jnp.int32)}
    return _attach_analytical(
        result, step, (state_a, batch_a), tokens_per_step=batch * seq,
        in_specs=(P(), {"input_ids": P("data")}), mesh=mesh)



def bench_llama():
    """Llama-recipe causal-LM training throughput (tokens/s/chip): the
    same harness as bench_gpt on the rmsnorm/swiglu/rope/GQA decoder
    (models/llama.py) — the modern-LM row of the matrix."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_tensorflow_tpu import optim, train, parallel
    from distributed_tensorflow_tpu.models.gpt import GPT
    from distributed_tensorflow_tpu.models.llama import llama_config

    n_chips = len(jax.devices())
    mesh = parallel.data_parallel_mesh()
    seq = int(os.environ.get("DTTPU_BENCH_SEQ", "256"))
    # ~160M-param body (GPT-2-small-ish dims + GQA 12q/4kv) so the row is
    # comparable to the gpt row while fitting the v5e ladder comfortably
    # remat=True for the same reason as _gpt_bench_config: bigger ladder
    # rungs fit and the rematerialised step measured faster at equal batch
    chunk = int(os.environ.get("DTTPU_BENCH_LOSS_CHUNK", "0"))
    rpol = os.environ.get("DTTPU_BENCH_REMAT_POLICY", "full")
    # DTTPU_BENCH_LLAMA_FUSED_LN=1: the fused rmsnorm kernel — measured
    # flips only (no promote mapping until the llama fused_ln arm lands)
    fused_ln = os.environ.get("DTTPU_BENCH_LLAMA_FUSED_LN") == "1"
    config = (llama_config(vocab_size=512, hidden_size=128, num_layers=2,
                           num_heads=4, num_kv_heads=2,
                           intermediate_size=384, max_position=seq,
                           dtype=jnp.bfloat16, remat=True,
                           remat_policy=rpol, fused_layernorm=fused_ln,
                           loss_seq_chunk=chunk) if SMOKE
              else llama_config(vocab_size=32000, hidden_size=768,
                                num_layers=12, num_heads=12,
                                num_kv_heads=4, intermediate_size=2048,
                                max_position=seq, dtype=jnp.bfloat16,
                                remat=True, remat_policy=rpol,
                                fused_layernorm=fused_ln,
                                loss_seq_chunk=chunk))
    model = GPT(config)
    params = model.init(jax.random.PRNGKey(0))
    optimizer = optim.adamw(1e-4)
    step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                        grad_clip_norm=1.0)
    rng = np.random.default_rng(0)
    bsh = NamedSharding(mesh, P("data"))

    def build(batch):
        state = train.TrainState.create(params, optimizer.init(params))
        state = jax.device_put(state, NamedSharding(mesh, P()))
        tokens = rng.integers(0, config.vocab_size,
                              (batch, seq + 1)).astype(np.int32)
        bench_batch = jax.device_put({"input_ids": tokens}, bsh)
        return state, bench_batch

    ladder = ([4] if SMOKE else
              [max(1, 48 * 256 // seq), max(1, 24 * 256 // seq),
               max(1, 12 * 256 // seq)])
    rate, loss, ms, batch, f_total = _run_batch_ladder(
        "llama", ladder, mesh, build, step,
        warmup=2, steps=4 if SMOKE else 10)
    tokens_s = rate * batch * seq / n_chips
    log(f"llama: {tokens_s:,.0f} tokens/s/chip ({ms*1e3:.1f} ms/step, "
        f"loss={loss:.3f})")
    finite = np.isfinite(loss)
    result = dict(metric="llama_lm_train_tokens_per_sec_per_chip"
                         + ("" if finite else "_NONFINITE_LOSS"),
                  value=round(tokens_s, 1), unit="tokens/sec/chip",
                  vs_baseline=1.0,  # no reference-era Llama baseline exists
                  seq_len=seq, batch=batch)
    if config.loss_seq_chunk:
        result["loss_seq_chunk"] = config.loss_seq_chunk
    if config.remat_policy != "full":
        result["remat_policy"] = config.remat_policy
    if fused_ln:
        result["fused_layernorm"] = True
    return _attach_mfu(
        result, tokens_s, _per_example_flops(f_total, batch * seq, mesh),
        analytic=_transformer_flops_per_token(params, config.num_layers,
                                              config.hidden_size, seq),
        scanned=True)



def _decode_eval_weights(model, config, train_steps=150):
    """Trained-or-random weights for the decode rows' HONESTY metrics.

    Random-init logits are near-uniform, so greedy argmax sits on
    rounding-order ties: ANY two numerically-equivalent decode paths
    (bf16 vs f32, fp vs int8, spec vs plain) diverge at the first tie
    and the per-token agreement compounds toward chance — measured
    2026-08-01 on the v5e: int8-vs-fp greedy match 0.58 at random init,
    pure tie noise, says nothing about quantization fidelity.  Training
    ~150 steps on a learnable order-1 Markov corpus (next = (tok * 31
    + 7) % active with p=0.9, uniform otherwise — a 512-entry lookup a
    decoder learns in seconds) gives the logits real margins so the
    agreement metrics measure the decode paths, not the init.
    Disabled (random init, steps=0) via DTTPU_BENCH_DECODE_TRAIN=0.

    Returns (params, train_steps_run, corpus_sampler) where
    corpus_sampler(rng, batch, length) draws in-distribution prompts."""
    import jax
    import numpy as np

    active = min(512, config.vocab_size)

    def sample(rng, batch, length):
        toks = np.empty((batch, length), np.int64)
        toks[:, 0] = rng.integers(0, active, batch)
        for t in range(1, length):
            follow = rng.random(batch) < 0.9
            toks[:, t] = np.where(follow, (toks[:, t - 1] * 31 + 7) % active,
                                  rng.integers(0, active, batch))
        return toks.astype(np.int32)

    params = model.init(jax.random.PRNGKey(0))
    if os.environ.get("DTTPU_BENCH_DECODE_TRAIN", "1") == "0":
        return params, 0, sample
    # 30 smoke steps: enough for the toy model to learn the chain so the
    # match metrics have margins (2 steps measured match 0.77 at seq 64
    # — still in the tie-noise regime the training exists to leave)
    steps = 30 if SMOKE else train_steps
    params = _train_lm(model, params, steps, sample,
                       min(128, config.max_position), seed=7)
    return params, steps, sample


def _train_lm(model, init_params, steps, sample, seq_train, seed):
    """ONE bench-training harness for the decode rows' pre-train AND the
    spec row's draft distillation (same recipe by construction).

    CAUTION: the train step DONATES its input state — ``init_params``
    buffers are consumed; callers whose tree shares buffers with a tree
    they still need must deep-copy first.  Returns the DEVICE-resident
    trained params: a device_get would make every later generate()
    re-upload ~250MB of weights per call (builder-measured 2026-08-01:
    fp decode 991 tok/s from a host tree vs 23.6k device-resident)."""
    import jax
    import numpy as np
    from distributed_tensorflow_tpu import optim, train

    optimizer = optim.adamw(3e-4)
    step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                        grad_clip_norm=1.0)
    state = train.TrainState.create(init_params,
                                    optimizer.init(init_params))
    rng = np.random.default_rng(seed)
    if steps <= 0:
        return init_params
    for _ in range(steps):
        batch = {"input_ids": jax.device_put(sample(rng, 32, seq_train + 1))}
        state, metrics = step(state, batch)
    _fetch(metrics)
    return state.params


def bench_gpt_decode():
    """Serving-side decode throughput (tokens/s/chip): greedy KV-cache
    generation on the GPT-2-small decoder, bf16.  The timed window is one
    full ``generate`` dispatch — its ``lax.scan`` teacher-forces the
    ``prompt_len - 1`` prompt positions in the same loop as the new-token
    steps, so the short 8-token prompt biases ms/token by under 3% — and
    closes with a value fetch of the emitted tokens (docs/PERF.md
    methodology).  Generation is placed on ONE device (no mesh), so the
    per-chip figure is the measured throughput undivided."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_tensorflow_tpu.models.gpt import GPT

    seq = int(os.environ.get("DTTPU_BENCH_SEQ", "256"))
    config = _gpt_bench_config(seq)
    model = GPT(config)
    params = model.init(jax.random.PRNGKey(0))
    batch = 4 if SMOKE else 64
    prompt_len = 8
    new_tokens = 16 if SMOKE else seq - prompt_len
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, config.vocab_size,
                          (batch, prompt_len)).astype(np.int32)

    gen = jax.jit(lambda p, ids: model.generate(
        p, ids, max_new_tokens=new_tokens, temperature=0.0, max_len=seq))
    np.asarray(gen(params, prompt))              # compile + warmup
    dt = None
    for _ in range(WINDOWS):                     # best-of, like every row
        t0 = time.perf_counter()
        out = gen(params, prompt)
        np.asarray(out)                          # value fetch closes window
        w = time.perf_counter() - t0
        dt = w if dt is None else min(dt, w)
    tokens_s = batch * new_tokens / dt          # single-device: per chip
    log(f"gpt_decode: {tokens_s:,.0f} tokens/s/chip "
        f"({dt * 1e3 / new_tokens:.2f} ms/token at batch {batch})")
    return dict(metric="gpt_decode_tokens_per_sec_per_chip",
                value=round(tokens_s, 1), unit="tokens/sec/chip",
                vs_baseline=1.0,  # no reference-era decode baseline exists
                batch=batch, new_tokens=new_tokens, seq_len=seq)


def bench_gpt_decode_int8():
    """Weight-only int8 decode (ops.quant): the int8 tree is the jitted
    ``generate``'s argument and ``dequantize_tree`` runs INSIDE the jit,
    so weights stay int8 in HBM (4x smaller reads — decode is
    bandwidth-bound) and the scale multiply fuses into the matmul
    prologue.  Also measures the FULL-int8 serving point (int8 weights
    + ``kv_cache_dtype="int8"`` — halved cache traffic on top of the
    weight reads).  Reports all three rates from the same run and the
    greedy-token agreement of each quantized path vs fp — the honesty
    signal that rounding didn't change the decoded text."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_tensorflow_tpu.models.gpt import GPT
    from distributed_tensorflow_tpu.ops import quant

    seq = int(os.environ.get("DTTPU_BENCH_SEQ", "256"))
    config = _gpt_bench_config(seq)
    model = GPT(config)
    model_kv8 = GPT(dataclasses.replace(config, kv_cache_dtype="int8"))
    # trained weights + in-distribution prompts: the agreement metrics
    # measure quantization fidelity, not random-init argmax-tie noise
    # (see _decode_eval_weights) — rates are weight-value-independent
    params, trained_steps, sample = _decode_eval_weights(model, config)
    qparams = quant.quantize_tree(params)
    batch = 4 if SMOKE else 64
    prompt_len = 8
    new_tokens = 16 if SMOKE else seq - prompt_len
    rng = np.random.default_rng(0)
    prompt = sample(rng, batch, prompt_len)

    gen_fp = jax.jit(lambda p, ids: model.generate(
        p, ids, max_new_tokens=new_tokens, temperature=0.0, max_len=seq))
    gen_q = jax.jit(lambda qp, ids: model.generate(
        quant.dequantize_tree(qp), ids, max_new_tokens=new_tokens,
        temperature=0.0, max_len=seq))
    gen_q_kv8 = jax.jit(lambda qp, ids: model_kv8.generate(
        quant.dequantize_tree(qp), ids, max_new_tokens=new_tokens,
        temperature=0.0, max_len=seq))

    def timed(fn, args):
        np.asarray(fn(*args))                    # compile + warmup
        t0 = time.perf_counter()
        out = fn(*args)
        toks = np.asarray(out)                   # value fetch closes window
        return batch * new_tokens / (time.perf_counter() - t0), toks

    fp_rate, fp_toks = timed(gen_fp, (params, prompt))
    q_rate, q_toks = timed(gen_q, (qparams, prompt))
    kv8_rate, kv8_toks = timed(gen_q_kv8, (qparams, prompt))
    match = float(np.mean(fp_toks[:, prompt_len:] == q_toks[:, prompt_len:]))
    kv8_match = float(np.mean(fp_toks[:, prompt_len:]
                              == kv8_toks[:, prompt_len:]))
    # tie-noise floor: the same fp weights decoded in float32 — the
    # bf16-vs-f32 disagreement is pure rounding-order tie noise, so an
    # int8 match at/above this floor means quantization changed nothing
    # the dtype itself doesn't (one un-timed decode; compile-only cost)
    model_f32 = GPT(dataclasses.replace(config, dtype=jnp.float32))
    params_f32 = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    f32_toks = np.asarray(jax.jit(lambda p, ids: model_f32.generate(
        p, ids, max_new_tokens=new_tokens, temperature=0.0,
        max_len=seq))(params_f32, prompt))
    floor = float(np.mean(fp_toks[:, prompt_len:]
                          == f32_toks[:, prompt_len:]))
    log(f"gpt_decode_int8: {q_rate:,.0f} tokens/s/chip vs fp "
        f"{fp_rate:,.0f} ({q_rate / fp_rate:.2f}x), greedy match "
        f"{match:.3f} (bf16-vs-f32 floor {floor:.3f}); +kv8 "
        f"{kv8_rate:,.0f} ({kv8_rate / fp_rate:.2f}x, match {kv8_match:.3f})")
    return dict(metric="gpt_decode_int8_tokens_per_sec_per_chip",
                value=round(q_rate, 1), unit="tokens/sec/chip",
                vs_baseline=round(q_rate / fp_rate, 3),  # fp path, same run
                fp_value=round(fp_rate, 1), greedy_token_match=round(match, 4),
                tie_noise_floor_match=round(floor, 4),
                full_int8_value=round(kv8_rate, 1),
                full_int8_greedy_match=round(kv8_match, 4),
                trained_steps=trained_steps,
                batch=batch, new_tokens=new_tokens, seq_len=seq)


def bench_gpt_decode_spec():
    """Speculative greedy decode (models/speculative.py): the GPT-2-small
    target verifies proposals from a 2-layer draft built by TRUNCATING
    the target's own stacked decoder params (shared embeddings/head —
    the cheapest self-distilled draft) and briefly fine-tuned on the
    target's training corpus (see _decode_eval_weights).  Reports spec
    and plain rates from the same run, the acceptance fraction, and the
    greedy-match honesty signal: the two paths agree by construction
    except where two vocab entries argmax-tie closer than the ~1e-4
    window-vs-step reduction difference (the same tie-noise class the
    int8 row's floor calibrates) — on TRAINED weights the margins are
    real, so a match well below 1.0 means a decode-stack bug.  Batch 1:
    speculative decoding is the latency play."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_tensorflow_tpu.models.gpt import GPT
    from distributed_tensorflow_tpu.models.speculative import \
        generate_speculative

    seq = int(os.environ.get("DTTPU_BENCH_SEQ", "256"))
    config = _gpt_bench_config(seq)
    model = GPT(config)
    # speculative speedup = f(draft/target agreement), and two RANDOM-init
    # models cannot agree (measured 2026-08-01: acceptance 0.022, spec
    # 0.80x — the machinery pays its overhead and wins nothing).  Train
    # the target on the learnable Markov corpus, then distill the
    # truncated draft on the same corpus, so the row measures the
    # hardware speedup at a REALISTIC acceptance (the deployment regime:
    # drafts are distilled from their targets precisely so they agree).
    params, trained_steps, sample = _decode_eval_weights(model, config)
    draft_layers = min(2, config.num_layers)
    draft_model = GPT(dataclasses.replace(config,
                                          num_layers=draft_layers))
    # the stacked decoder tree slices by layer; everything else is shared
    draft_params = dict(params)
    draft_params["decoder"] = jax.tree.map(lambda a: a[:draft_layers],
                                           params["decoder"])
    if trained_steps:
        # deep-copy: _train_lm's step DONATES its input state, and the
        # truncated draft tree shares the target's embedding/head
        # buffers — donating those would delete the target's params
        draft_init = jax.tree.map(lambda a: jnp.array(a, copy=True),
                                  draft_params)
        draft_params = _train_lm(draft_model, draft_init,
                                 2 if SMOKE else 100, sample,
                                 min(128, seq), seed=11)
    prompt_len = 8
    # DTTPU_BENCH_SPEC_GAMMA: proposals per verify step — the speedup
    # curve's x-axis (more proposals amortise the target pass further
    # but waste more draft work per rejection); 4 is the bench default
    gamma = int(os.environ.get("DTTPU_BENCH_SPEC_GAMMA", "4"))
    # the learned position table has seq rows; speculative windows embed
    # positions up to total + gamma - 2, so leave gamma - 1 headroom
    new_tokens = 16 if SMOKE else seq - prompt_len - gamma + 1
    rng = np.random.default_rng(0)
    prompt = sample(rng, 1, prompt_len)

    gen_plain = jax.jit(lambda p, ids: model.generate(
        p, ids, max_new_tokens=new_tokens, temperature=0.0,
        max_len=seq))
    gen_spec = jax.jit(lambda tp, dp, ids: generate_speculative(
        model, tp, draft_model, dp, ids, max_new_tokens=new_tokens,
        gamma=gamma))

    def timed(fn, args):
        out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0])      # compile + warmup
        dt = None
        for _ in range(WINDOWS):
            t0 = time.perf_counter()
            out = fn(*args)
            np.asarray(jax.tree.leaves(out)[0])  # value fetch
            w = time.perf_counter() - t0
            dt = w if dt is None else min(dt, w)
        return new_tokens / dt, out

    plain_rate, plain_out = timed(gen_plain, (params, prompt))
    spec_rate, (spec_out, acc) = timed(gen_spec,
                                       (params, draft_params, prompt))
    match = float(np.mean(np.asarray(plain_out)[:, prompt_len:]
                          == np.asarray(spec_out)[:, prompt_len:]))
    log(f"gpt_decode_spec: {spec_rate:,.0f} tok/s vs plain "
        f"{plain_rate:,.0f} ({spec_rate / plain_rate:.2f}x), acceptance "
        f"{float(acc):.3f}, greedy match {match:.3f}")
    return dict(metric="gpt_decode_spec_tokens_per_sec",
                value=round(spec_rate, 1), unit="tokens/sec",
                vs_baseline=round(spec_rate / plain_rate, 3),  # plain, same run
                plain_value=round(plain_rate, 1),
                acceptance=round(float(acc), 4),
                greedy_token_match=round(match, 4),
                gamma=gamma, draft_layers=draft_layers, batch=1,
                trained_steps=trained_steps,
                new_tokens=new_tokens, seq_len=seq)


def bench_gpt_serve():
    """Continuous-batching serving engine (serve/) vs lock-step batching,
    measured in the SAME process on the same model and the same seeded
    mixed-length arrival trace.  The engine path replays the trace
    through ``serve.Engine`` — slot-scheduled KV cache, chunked prefill,
    retrace-free admission — and reports aggregate tokens/s plus TTFT
    p50/p95 under load; the lock-step comparator groups the same
    requests into ``generate()`` batches in arrival order (LEFT-padded
    ragged prompts, each batch decoding until its longest member's
    budget), which is the fixed-batch serving discipline the engine
    replaces.  ``vs_lockstep`` > 1.0 is the acceptance bar: short
    requests no longer pay for long batchmates.  Single device (no
    mesh), like the other decode rows; wall clocks close with host
    value fetches on both sides.

    ``vs_lockstep`` is the engine's ratio on the mixed trace
    (``vs_lockstep_paged`` is the same number under its older name, kept
    so that no reader of the row loses a key).

    Two more measured phases (serve/pages.py):

    * ``shared_prefix``: a seeded arrival trace where requests share
      one of a few SYSTEM PROMPTS (plus the mixed trace's per-group
      long-tail stragglers).  Replayed on the paged engine with the
      radix prefix cache ON and OFF (``prefix_cache=False`` — same
      engine, same paging, reuse ablated): ``vs_no_reuse`` is the
      cache's own win, ``prefix_hit_rate``/``prefill_windows_skipped``
      the mechanism, and the TTFT p50 delta the latency effect.
    * ``slots_at_fixed_mem``: with the page pool capped at the HBM of
      ``slots`` full ``[max_len]`` stripes, a burst
      of short requests shows how many slots the paged engine actually
      runs CONCURRENTLY — strictly more than the ``slots`` that a
      stripe per slot would hold, because pages are allocated per
      actual footprint.
    """
    import jax
    import numpy as np
    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models.gpt import GPT
    from distributed_tensorflow_tpu.obs import reqtrace

    seq = int(os.environ.get("DTTPU_BENCH_SEQ", "256"))
    config = _gpt_bench_config(seq)
    model = GPT(config)
    params = model.init(jax.random.PRNGKey(0))
    slots = int(os.environ.get('DTTPU_BENCH_SERVE_SLOTS',
                               6 if SMOKE else 16))
    chunk = 16 if SMOKE else 32
    tick_steps = int(os.environ.get("DTTPU_BENCH_SERVE_TICK",
                                    "6" if SMOKE else "8"))
    n_req = 30 if SMOKE else 96   # a multiple of slots: full groups/batches
    rng = np.random.default_rng(0)

    # Mixed-length trace: mostly short answers with a heavy tail of long
    # ones — the regime where a lock-step batch stalls on its longest
    # member.  Arrival order is uncorrelated with length, so the longs
    # land spread out (one seeded position per group of ``slots``
    # consecutive arrivals — the expected interleaving, which is also
    # the lock-step WORST case only in the sense that nearly every
    # fixed batch inherits one straggler).  Budgets clamp so both
    # servers fit max_len = seq.
    plens = rng.integers(3, 2 * chunk + 1, n_req)
    p_max = int(plens.max())
    long_req = np.zeros(n_req, bool)
    for lo in range(0, n_req, slots):
        long_req[lo + int(rng.integers(0, min(slots, n_req - lo)))] = True
    # long budgets come from THREE discrete tiers (not a continuum) so
    # the lock-step comparator compiles at most three per-batch budget
    # values — its per-budget traces are legitimate, but they must stay
    # inside the bench retrace budget so the JSON's retrace_warnings
    # remains a clean signal for the ENGINE's no-recompile contract
    long_tiers = np.array([seq // 3, (5 * seq) // 12, seq // 2])
    budgets = np.where(long_req,
                       rng.choice(long_tiers, n_req),
                       rng.integers(2, 9, n_req))
    cap = seq - max(p_max, 2 * chunk) - 1
    budgets = np.clip(budgets, 1, cap).astype(int)
    prompts = [rng.integers(0, config.vocab_size, p).astype(np.int32)
               for p in plens]
    # short arrival stagger (in ticks): the queue builds while the
    # first admissions are still prefilling, as live traffic would
    arrivals = np.sort(rng.integers(0, slots + 1, n_req))
    # seeded tenant ids ride the trace (drawn AFTER the arrays above so
    # the prompts/budgets/arrivals stay byte-identical to earlier
    # rounds); this engine enforces no tenancy policy — the ids feed
    # the per-tenant serve metrics and keep the trace shared with
    # --config=fleet, which does enforce fair-share
    tenants = rng.choice(["free", "pro", "batch"], n_req)

    def make_engine(**kw):
        """Engine + warmup covering the mid+last prefill windows, the
        admit splice/arm, and the tick (a cold engine would otherwise
        compile inside the measured window)."""
        eng = serve.Engine(model, params, num_slots=kw.pop("num_slots",
                                                           slots),
                           max_len=seq, prefill_chunk=chunk,
                           tick_steps=tick_steps, **kw)
        eng.submit(rng.integers(0, config.vocab_size,
                                chunk + 2).astype(np.int32), 4)
        eng.submit(prompts[0], 2)
        eng.drain()
        return eng

    def replay_engine(eng, trace_prompts, trace_budgets, trace_arrivals,
                      trace_tenants=None):
        handles = []
        i = tick = 0
        n = len(trace_prompts)
        t0 = time.perf_counter()
        while i < n or eng.busy:
            while i < n and trace_arrivals[i] <= tick:
                handles.append(eng.submit(
                    trace_prompts[i], int(trace_budgets[i]),
                    tenant=("default" if trace_tenants is None
                            else str(trace_tenants[i]))))
                i += 1
            eng.step()
            tick += 1
        # the final tick fetched its tokens: the wall is barrier-closed
        wall = time.perf_counter() - t0
        return wall, handles

    def ttft_pcts(handles):
        ttfts = sorted(h.ttft_s for h in handles)
        return (ttfts[int(0.50 * (len(ttfts) - 1))],
                ttfts[int(0.95 * (len(ttfts) - 1))])

    # best of 2 windows on BOTH sides (the WINDOWS rationale: a
    # background spike landing in one side's single window flips the
    # ratio); TTFTs are reported from the best engine window
    eng = make_engine()
    wall_engine, handles = min(
        (replay_engine(eng, prompts, budgets, arrivals, tenants)
         for _ in range(2)), key=lambda r: r[0])
    total_tokens = sum(len(h.tokens) for h in handles)
    engine_tps = total_tokens / wall_engine
    ttft_p50, ttft_p95 = ttft_pcts(handles)
    page_size = eng.scheduler.page_size

    # Kernel read path: the SAME paged layout read through the fused
    # Pallas page-walk kernel instead of the XLA gather.  Off-TPU the
    # kernel runs in interpret mode, so the CPU smoke exercises the
    # real kernel body but the ratio only certifies a win on TPU
    # (scripts/validate_paged_tpu.py owns the Mosaic-compiled numbers).
    eng_k = make_engine(use_paged_kernel=True)
    wall_kernel, handles_k = min(
        (replay_engine(eng_k, prompts, budgets, arrivals, tenants)
         for _ in range(2)), key=lambda r: r[0])
    kernel_tps = sum(len(h.tokens) for h in handles_k) / wall_kernel

    # Lock-step comparator: same requests, batches of `slots` in arrival
    # order, LEFT-padded to the global max prompt, each batch running its
    # longest member's budget.  Useful tokens = each request's own
    # budget (the surplus a short request decodes past its budget is
    # lock-step waste, not throughput).  One jitted generate with the
    # budget static: <= one trace per batch, under the retrace budget.
    gen_j = jax.jit(
        lambda p, ids, valid, mn: model.generate(
            p, ids, max_new_tokens=mn, temperature=0.0, max_len=seq,
            prompt_valid=valid),
        static_argnums=(3,))
    batch_args = []
    for lo in range(0, n_req, slots):
        idx = range(lo, min(lo + slots, n_req))
        ids = np.zeros((slots, p_max), np.int32)
        valid = np.zeros((slots, p_max), np.int32)
        for r, j in enumerate(idx):
            ids[r, p_max - plens[j]:] = prompts[j]
            valid[r, p_max - plens[j]:] = 1
        batch_args.append((ids, valid,
                           int(budgets[list(idx)].max())))
    for ids, valid, mn in batch_args:        # compile warmup per budget
        np.asarray(gen_j(params, ids, valid, mn))
    wall_lock = None
    for _ in range(2):                       # best of 2, same as engine
        t0 = time.perf_counter()
        for ids, valid, mn in batch_args:
            np.asarray(gen_j(params, ids, valid, mn))  # fetch closes
        w = time.perf_counter() - t0
        wall_lock = w if wall_lock is None else min(wall_lock, w)
    lock_tps = float(budgets.sum()) / wall_lock

    ratio_paged = engine_tps / lock_tps
    ratio_kernel = kernel_tps / lock_tps
    kernel_vs_gather = kernel_tps / engine_tps
    log(f"gpt_serve: paged {engine_tps:,.0f} tok/s, "
        f"kernel {kernel_tps:,.0f}, lockstep {lock_tps:,.0f} "
        f"(paged {ratio_paged:.2f}x / "
        f"kernel {ratio_kernel:.2f}x, kernel vs gather "
        f"{kernel_vs_gather:.2f}x), "
        f"ttft p50 {ttft_p50*1e3:.1f} ms / p95 {ttft_p95*1e3:.1f} ms "
        f"over {n_req} requests")

    # ---- shared-prefix trace: the radix cache's own measured win ----
    # Same long-tail discipline as the mixed trace, but every prompt is
    # one of a few SYSTEM PROMPTS (3 pages each) plus a short unique
    # tail — the multi-user serving shape prefix reuse exists for.
    rng2 = np.random.default_rng(7)
    n_sp = 24 if SMOKE else 48
    sys_len = 3 * page_size
    sys_prompts = [rng2.integers(0, config.vocab_size,
                                 sys_len).astype(np.int32)
                   for _ in range(3)]
    which = rng2.integers(0, 3, n_sp)
    sp_prompts = [np.concatenate([
        sys_prompts[w],
        rng2.integers(0, config.vocab_size,
                      int(rng2.integers(4, 13))).astype(np.int32)])
        for w in which]
    sp_long = np.zeros(n_sp, bool)
    for lo in range(0, n_sp, slots):
        sp_long[lo + int(rng2.integers(0, min(slots, n_sp - lo)))] = True
    sp_budgets = np.where(sp_long, rng2.choice(long_tiers, n_sp),
                          rng2.integers(2, 9, n_sp))
    sp_max = max(p.size for p in sp_prompts)
    sp_budgets = np.clip(sp_budgets, 1, seq - sp_max - 1).astype(int)
    sp_arrivals = np.sort(rng2.integers(0, slots + 1, n_sp))

    sp_results = {}
    for label, reuse in (("reuse", True), ("no_reuse", False)):
        eng_sp = make_engine(prefix_cache=reuse)
        wall, hs = min(
            (replay_engine(eng_sp, sp_prompts, sp_budgets, sp_arrivals)
             for _ in range(2)), key=lambda r: r[0])
        p50, p95 = ttft_pcts(hs)
        sp_results[label] = dict(
            tps=sum(len(h.tokens) for h in hs) / wall,
            p50=p50, p95=p95, stats=eng_sp.stats())

    # the kernel read path over the SAME shared-prefix trace (radix
    # reuse on): prefix hits land pages the kernel then walks
    eng_spk = make_engine(prefix_cache=True, use_paged_kernel=True)
    wall_spk, hs_spk = min(
        (replay_engine(eng_spk, sp_prompts, sp_budgets, sp_arrivals)
         for _ in range(2)), key=lambda r: r[0])
    sp_kernel_tps = sum(len(h.tokens) for h in hs_spk) / wall_spk

    sp_args = []
    for lo in range(0, n_sp, slots):
        idx = range(lo, min(lo + slots, n_sp))
        ids = np.zeros((slots, sp_max), np.int32)
        valid = np.zeros((slots, sp_max), np.int32)
        for r, j in enumerate(idx):
            ids[r, sp_max - sp_prompts[j].size:] = sp_prompts[j]
            valid[r, sp_max - sp_prompts[j].size:] = 1
        sp_args.append((ids, valid, int(sp_budgets[list(idx)].max())))
    for ids, valid, mn in sp_args:
        np.asarray(gen_j(params, ids, valid, mn))
    sp_lock = None
    for _ in range(2):
        t0 = time.perf_counter()
        for ids, valid, mn in sp_args:
            np.asarray(gen_j(params, ids, valid, mn))
        w = time.perf_counter() - t0
        sp_lock = w if sp_lock is None else min(sp_lock, w)
    sp_lock_tps = float(sp_budgets.sum()) / sp_lock

    st = sp_results["reuse"]["stats"]
    shared_prefix = dict(
        requests=n_sp,
        tokens_per_sec=round(sp_results["reuse"]["tps"], 1),
        no_reuse_tokens_per_sec=round(sp_results["no_reuse"]["tps"], 1),
        vs_no_reuse=round(sp_results["reuse"]["tps"]
                          / sp_results["no_reuse"]["tps"], 3),
        lockstep_tokens_per_sec=round(sp_lock_tps, 1),
        vs_lockstep=round(sp_results["reuse"]["tps"] / sp_lock_tps, 3),
        kernel_tokens_per_sec=round(sp_kernel_tps, 1),
        kernel_vs_gather=round(
            sp_kernel_tps / sp_results["reuse"]["tps"], 3),
        prefix_hit_rate=round(st.prefix_hit_rate, 3),
        prefill_windows_skipped=st.prefill_windows_skipped_total,
        prefix_tokens_reused=st.prefix_tokens_reused_total,
        ttft_p50_ms=round(sp_results["reuse"]["p50"] * 1e3, 3),
        ttft_p95_ms=round(sp_results["reuse"]["p95"] * 1e3, 3),
        no_reuse_ttft_p50_ms=round(sp_results["no_reuse"]["p50"] * 1e3,
                                   3))
    log(f"gpt_serve shared-prefix: reuse "
        f"{shared_prefix['tokens_per_sec']:,.0f} tok/s vs no-reuse "
        f"{shared_prefix['no_reuse_tokens_per_sec']:,.0f} "
        f"({shared_prefix['vs_no_reuse']:.2f}x), hit rate "
        f"{shared_prefix['prefix_hit_rate']:.2f}, "
        f"{shared_prefix['prefill_windows_skipped']} windows skipped, "
        f"ttft p50 {shared_prefix['ttft_p50_ms']:.1f} ms vs "
        f"{shared_prefix['no_reuse_ttft_p50_ms']:.1f} ms uncached")

    # ---- slots_at_fixed_mem: concurrency at a stripe-per-slot budget ----
    # Page pool capped at the HBM of ``slots`` full [max_len] stripes;
    # 2x the slots; a same-tick burst of short requests.  Peak
    # concurrent ACTIVE slots is the measured claim: pages allocated
    # per actual footprint, not per worst-case stripe.
    eng_m = make_engine(num_slots=2 * slots,
                        num_pages=slots * (seq // page_size) + 1)
    burst_n = 2 * slots
    b_prompts = [rng2.integers(0, config.vocab_size,
                               int(rng2.integers(4, 2 * chunk))
                               ).astype(np.int32)
                 for _ in range(burst_n)]
    b_handles = [eng_m.submit(p, 8) for p in b_prompts]
    peak_active = 0
    while eng_m.busy:
        eng_m.step()
        peak_active = max(peak_active, eng_m.stats().active)
    assert all(h.done for h in b_handles)
    log(f"gpt_serve slots_at_fixed_mem: {peak_active} concurrent slots "
        f"on a {slots}-stripe budget (a stripe per slot: {slots})")

    # ---- tracing overhead: the span-emission budget, measured ----
    # The mixed trace replayed with request tracing ON (ids minted at
    # Engine.submit, lifecycle spans emitted by the scheduler) vs OFF
    # (``reqtrace.configure(enabled=False)``: mint returns None and
    # every carrier skips the calls — one attribute check per
    # request).  Two fresh engines, arms INTERLEAVED best-of-2, so a
    # background spike or cache-warmth drift can't land on one side.
    # With no active tracer (TELEMETRY=0) both arms mint nothing and
    # the ratio degenerates to noise around 1.0 — still reported, but
    # the ON arm's traced lane count says which regime ran.
    eng_on = make_engine()
    eng_off = make_engine()
    wall_on = wall_off = None
    toks_on = 0
    try:
        for _ in range(2):
            reqtrace.configure(enabled=True)
            w, hs_t = replay_engine(eng_on, prompts, budgets,
                                    arrivals, tenants)
            if wall_on is None or w < wall_on:
                wall_on, toks_on = w, sum(len(h.tokens) for h in hs_t)
            reqtrace.configure(enabled=False)
            w, hs_t = replay_engine(eng_off, prompts, budgets,
                                    arrivals, tenants)
            wall_off = w if wall_off is None else min(wall_off, w)
    finally:
        reqtrace.configure(enabled=True)
    on_tps = toks_on / wall_on
    off_tps = toks_on / wall_off     # same trace: same token total
    tracing = dict(
        on_tokens_per_sec=round(on_tps, 1),
        off_tokens_per_sec=round(off_tps, 1),
        ratio=round(on_tps / off_tps, 4),
        overhead_pct=round(max(0.0, 1.0 - on_tps / off_tps) * 100, 2),
        traced_requests=len(reqtrace.completed()))
    log(f"gpt_serve tracing: on {on_tps:,.0f} tok/s vs off "
        f"{off_tps:,.0f} (ratio {tracing['ratio']:.3f}, "
        f"{tracing['traced_requests']} lanes in the ring)")

    # ---- critical path: head-of-line interference, measured ----
    # An ADVERSARIAL long-prompt trace under an active obs.critpath
    # ledger: a wave of short requests with real decode budgets fills
    # the slots first, then multi-window long prompts land mid-decode —
    # every decode tick sharing the pump with those prefill windows is
    # stretched, and the ledger attributes exactly that stretch to the
    # victims' prefill_interference phase.  The interference_share_*
    # fields are top-level (the perf ledger only lifts top-level
    # numerics into ``measured``) so the sentinel gates their drift
    # (up is bad — docs/OBSERVABILITY.md Critical path).
    from distributed_tensorflow_tpu.obs import critpath as critpath_lib

    rng3 = np.random.default_rng(11)
    # leave free slots for the longs: they must ADMIT (and prefill)
    # while the shorts are still decoding, not queue behind them
    n_long = max(2, slots // 3)
    n_short = max(1, slots - n_long)
    cp_prompts = [rng3.integers(0, config.vocab_size,
                                int(rng3.integers(4, 9))
                                ).astype(np.int32)
                  for _ in range(n_short)]
    cp_prompts += [rng3.integers(0, config.vocab_size,
                                 3 * chunk + 4).astype(np.int32)
                   for _ in range(n_long)]
    cp_budgets = np.array([6 * tick_steps] * n_short + [4] * n_long)
    cp_budgets = np.clip(cp_budgets, 1, seq - (3 * chunk + 4) - 1)
    # shorts at tick 0, longs two ticks later: the longs' windows hit
    # slots that are already decoding
    cp_arrivals = np.array([0] * n_short + [2] * n_long)
    cp_tenants = ["interactive"] * n_short + ["batch"] * n_long
    cp_ledger = critpath_lib.CritpathLedger()
    eng_cp = make_engine()
    with critpath_lib.activated(cp_ledger):
        wall_cp, hs_cp = replay_engine(eng_cp, cp_prompts, cp_budgets,
                                       cp_arrivals, cp_tenants)
    assert all(h.done for h in hs_cp)
    cp_rep = cp_ledger.report()

    # the same vocabulary fleet-wide on virtual time: a seeded
    # workload through the real Router over SimEngines — the sim must
    # reproduce a NONZERO interference distribution for the
    # decomposition to be believed at fleet scale (the >=1e6-request
    # run lives in the slow test tier / --config=fleet_sim)
    from distributed_tensorflow_tpu.fleet import sim as sim_lib
    from distributed_tensorflow_tpu.fleet import workload as workload_lib
    sim_n = 2000 if SMOKE else 20000
    sim_cm = sim_lib.CostModel.analytic(
        n_params=1e8, prefill_chunk=64, num_slots=8, tick_steps=16)
    sim_tr = workload_lib.synthesize(sim_n, seed=11,
                                     horizon_s=sim_n / 80.0)
    sim_rep = sim_lib.FleetSim(
        sim_tr, sim_cm, replicas=2,
        engine={"num_slots": 8, "prefill_chunk": 64,
                "tick_steps": 16}).run()
    critpath = dict(
        requests=cp_rep["requests"],
        interference_ratio=cp_rep["interference_ratio"],
        phase_seconds=cp_rep["phase_seconds"],
        worst_e2e_s=round(cp_rep["worst"][0]["e2e_s"], 6)
        if cp_rep["worst"] else 0.0,
        sim_requests=sim_rep["simulated_requests"],
        sim_interference_share_p50=sim_rep["interference_share_p50"],
        sim_interference_share_p95=sim_rep["interference_share_p95"])
    log(f"gpt_serve critpath: interference share p50 "
        f"{cp_rep['interference_share_p50']:.3f} / p95 "
        f"{cp_rep['interference_share_p95']:.3f} over "
        f"{cp_rep['requests']} adversarial requests (ratio "
        f"{cp_rep['interference_ratio']:.3f}); sim p95 "
        f"{sim_rep['interference_share_p95']:.3f} over "
        f"{sim_rep['simulated_requests']} virtual requests")
    report_path = os.environ.get("DTTPU_CRITPATH_REPORT")
    if report_path:
        # the CI artifact: the full ledger document plus the sim leg
        with open(report_path, "w") as f:
            json.dump({"serve": cp_rep, "sim": sim_rep}, f, indent=2)

    return dict(metric="gpt_serve_tokens_per_sec_per_chip",
                value=round(engine_tps, 1), unit="tokens/sec/chip",
                tracing=tracing,
                vs_baseline=round(ratio_paged, 3),  # lock-step, same run
                tokens_per_sec=round(engine_tps, 1),
                lockstep_tokens_per_sec=round(lock_tps, 1),
                vs_lockstep=round(ratio_paged, 3),
                vs_lockstep_paged=round(ratio_paged, 3),
                kernel_tokens_per_sec=round(kernel_tps, 1),
                vs_lockstep_paged_kernel=round(ratio_kernel, 3),
                paged_kernel_vs_gather=round(kernel_vs_gather, 3),
                ttft_p50_ms=round(ttft_p50 * 1e3, 3),
                ttft_p95_ms=round(ttft_p95 * 1e3, 3),
                interference_share_p50=cp_rep["interference_share_p50"],
                interference_share_p95=cp_rep["interference_share_p95"],
                sim_interference_share_p50=sim_rep[
                    "interference_share_p50"],
                sim_interference_share_p95=sim_rep[
                    "interference_share_p95"],
                requests=n_req, num_slots=slots, prefill_chunk=chunk,
                tick_steps=tick_steps, total_new_tokens=total_tokens,
                seq_len=seq, page_size=page_size,
                shared_prefix=shared_prefix,
                slots_at_fixed_mem=peak_active,
                slots_at_fixed_mem_contiguous=slots,
                critpath=critpath)


def bench_fleet():
    """Multi-replica fleet serving (fleet/): an ADVERSARIAL three-tenant
    burst routed over N Engine replicas by the least-loaded Router, with
    a deficit-weighted fair-share tenancy policy and a LoRA adapter
    hot-swapped per request on one tenant's traffic.  The tenants carry
    EQUAL total token demand in skewed request shapes — ``free`` many
    short requests, ``pro`` medium (under a LoRA adapter), ``batch``
    few long — submitted as whole per-tenant blocks in that order, the
    worst case for FIFO admission (the last tenant would wait for both
    blocks ahead of it).  The JSON reports fleet tokens/s, per-tenant
    TTFT p50/p95, and ``fairness_ratio``: over the contended window
    (up to the admission that exhausts the first tenant's backlog), the
    min/max ratio of weight-normalized cumulative ADMITTED token
    budgets per tenant — the deficit scheduler's own decision variable,
    so 1.0 is perfect token-weighted fair-share and plain FIFO on this
    trace measures 0.0 (the last block admits nothing inside the
    window).  CPU mesh, single process, zero retrace_warnings
    (admission, retirement, failover, and adapter swaps never
    recompile).  A page-wire leg then drains a replica of long-prompt
    requests with their KV pages shipped (fleet/pagewire.py) vs
    re-prefilled, reporting the destination's skipped prefill windows
    and a chunk_pages × overlap sweep (``wire`` in the JSON)."""
    import jax
    import numpy as np
    from distributed_tensorflow_tpu import fleet, serve
    from distributed_tensorflow_tpu.models.gpt import GPT
    from distributed_tensorflow_tpu.obs import metrics as metrics_lib

    seq = int(os.environ.get("DTTPU_BENCH_SEQ", "256"))
    config = _gpt_bench_config(seq)
    model = GPT(config)
    params = model.init(jax.random.PRNGKey(0))
    n_replicas = int(os.environ.get("DTTPU_BENCH_FLEET_REPLICAS", "2"))
    slots = int(os.environ.get("DTTPU_BENCH_SERVE_SLOTS",
                               4 if SMOKE else 8))
    chunk = 16 if SMOKE else 32
    tick_steps = int(os.environ.get("DTTPU_BENCH_SERVE_TICK",
                                    "4" if SMOKE else "8"))
    # equal per-tenant token demand, skewed request shapes
    demand = 60 if SMOKE else 240
    profiles = {"free": (2, 5), "pro": (5, 9), "batch": (10, 16)}
    tenants = tuple(profiles)
    rng = np.random.default_rng(0)

    reqs = []                  # (tenant, prompt, budget, adapter_id)
    for tenant, (lo, hi) in profiles.items():
        left = demand
        while left > 0:
            budget = min(int(rng.integers(lo, hi)), left)
            plen = int(rng.integers(3, 2 * chunk + 1))
            prompt = rng.integers(0, config.vocab_size,
                                  plen).astype(np.int32)
            # tenant "pro" serves a fine-tuned LoRA variant: the
            # adapter swap rides the measured path
            adapter = "pro-tuned" if tenant == "pro" else None
            reqs.append((tenant, prompt, budget, adapter))
            left -= budget
    # per-tenant blocks in profile order — the FIFO worst case the
    # fair-share queue has to undo (arrival order is part of the trace)
    n_req = len(reqs)

    policy = fleet.TenantPolicy(quantum=8)
    reg = metrics_lib.Registry()
    engines = [serve.Engine(model, params, num_slots=slots, max_len=seq,
                            prefill_chunk=chunk, tick_steps=tick_steps,
                            registry=reg, tenancy=policy,
                            adapter_capacity=2, adapter_rank=4)
               for _ in range(n_replicas)]
    router = fleet.Router(engines, registry=reg)
    adapter = model.init_lora(jax.random.PRNGKey(7), rank=4)
    router.load_adapter("pro-tuned", adapter)

    # Warmup covers every executable on EVERY replica (round-robin by
    # load): two requests per replica — one multi-window prefill, one
    # short — plus one adapter-carrying request per replica.
    for _ in range(n_replicas):
        router.submit(rng.integers(0, config.vocab_size,
                                   chunk + 2).astype(np.int32), 4)
        router.submit(reqs[0][1], 2)
        router.submit(reqs[0][1], 2, adapter_id="pro-tuned")
    router.drain()

    def replay():
        # the whole adversarially-ordered trace arrives as one burst,
        # then the fleet drains it
        handles = [(tenant, budget,
                    router.submit(prompt, budget, tenant=tenant,
                                  adapter_id=ad))
                   for tenant, prompt, budget, ad in reqs]
        t0 = time.perf_counter()
        while router.busy:
            router.step()
        wall = time.perf_counter() - t0
        return wall, handles

    (wall, handles) = min((replay() for _ in range(2)),
                          key=lambda r: r[0])
    assert all(h.status == "ok" for _, _, h in handles)
    total_tokens = sum(len(h.tokens) for _, _, h in handles)
    tps = total_tokens / wall

    # fairness over the contended window: walk admissions in TTFT order
    # (burst submit => admission order), accumulating each tenant's
    # admitted token budget, and stop at the admission that exhausts the
    # first tenant's backlog — beyond it the comparison is meaningless.
    remaining = {t: sum(1 for tt, _, _ in handles if tt == t)
                 for t in tenants}
    admitted = {t: 0 for t in tenants}
    for tenant, budget, _ in sorted(handles,
                                    key=lambda r: r[2].ttft_s):
        admitted[tenant] += budget
        remaining[tenant] -= 1
        if remaining[tenant] == 0:
            break
    norm = [admitted[t] / policy.quota(t).weight for t in tenants]
    fairness = (min(norm) / max(norm)) if max(norm) > 0 else 0.0

    def pct(vals, q):
        vals = sorted(vals)
        return vals[int(q * (len(vals) - 1))]

    ttft_all = [h.ttft_s for _, _, h in handles]
    tenant_p50, tenant_p95 = {}, {}
    for tenant in tenants:
        ts = [h.ttft_s for t, _, h in handles if t == tenant]
        tenant_p50[tenant] = round(pct(ts, 0.50) * 1e3, 3)
        tenant_p95[tenant] = round(pct(ts, 0.95) * 1e3, 3)

    # -- migration leg (docs/RESILIENCE.md §migration): rolling-restart
    # cost with and without live migration, plus decode work preserved
    # across a kill.  Same engines/executables as the fairness run, so
    # nothing below compiles anything new.
    from distributed_tensorflow_tpu.resilience import faults

    mig_budget = 16 if SMOKE else 24

    def mig_batch(n=6):
        hs = []
        for _ in range(n):
            plen = int(rng.integers(3, 2 * chunk + 1))
            pr = rng.integers(0, config.vocab_size, plen).astype(np.int32)
            hs.append(router.submit(pr, mig_budget))
        for _ in range(3):
            router.step()           # decode in flight on both replicas
        return hs

    # drain-with-migration: export + import on the survivor, then the
    # drained replica is immediately free for its restart
    hs_m = mig_batch()
    t0 = time.perf_counter()
    router.drain_replica(0, migrate=True, timeout_s=600)
    drain_migrate_ms = (time.perf_counter() - t0) * 1e3
    router.drain()
    assert all(h.status == "ok" for h in hs_m)
    router.resume_replica(0)

    # wait-drain (the legacy path): the restart waits out every decode
    hs_w = mig_batch()
    t0 = time.perf_counter()
    router.drain_replica(0, migrate=False, timeout_s=600)
    drain_wait_ms = (time.perf_counter() - t0) * 1e3
    router.drain()
    assert all(h.status == "ok" for h in hs_w)
    router.resume_replica(0)

    # kill: replica 0 dies mid-decode; its requests migrate with their
    # progress.  tokens_preserved_ratio = fraction of the migrated
    # requests' final decode work that was salvaged from the snapshot
    # instead of regenerated on the survivor.
    kill_plan = faults.FaultPlan(
        [{"kind": "kill_replica", "at": 4, "replica": 0}], registry=reg)
    with faults.activated(kill_plan):
        hs_k = mig_batch()
        router.drain()
    assert all(h.status == "ok" for h in hs_k)
    migrated = [h for h in hs_k if h.migrations]
    preserved = sum(h.tokens_preserved for h in migrated)
    mig_total = sum(len(h.tokens) for h in migrated)
    preserved_ratio = preserved / mig_total if mig_total else 0.0

    # -- page-wire leg (docs/RESILIENCE.md §page wire): migrate a
    # replica's long-prompt requests with their KV pages SHIPPED over
    # the wire vs re-prefilled from scratch.  Long UNIQUE prompts (no
    # radix reuse between requests or arms) make the comparison clean:
    # the no-wire arm's destination skips zero prefill windows, the
    # wire arm's destination skips every window the shipped pages
    # cover.  Placement is forced onto the victim by draining the
    # survivors around the submit, so every arm migrates the same
    # number of requests.
    router.add_replica(engines[0])        # the kill leg's victim rejoins
    live_rids = sorted(router.stats())
    victim_rid, surv_rids = live_rids[0], live_rids[1:]
    surv_engines = [router.replica(r) for r in surv_rids]

    def wire_arm(wire_obj, n=4):
        router.page_wire = wire_obj
        for rid in surv_rids:
            router.drain_replica(rid, migrate=False, timeout_s=600)
        hs = []
        for _ in range(n):
            plen = 4 * chunk + 3          # multi-window, multi-page
            pr = rng.integers(0, config.vocab_size,
                              plen).astype(np.int32)
            hs.append(router.submit(pr, mig_budget))
        for rid in surv_rids:
            router.resume_replica(rid)
        for _ in range(256):              # prefill fully on the victim
            router.step()
            if all(len(h.tokens) >= 1 for h in hs):
                break
        skip0 = sum(e.stats().prefill_windows_skipped_total
                    for e in surv_engines)
        c0 = reg.get("dttpu_wire_chunks_total")
        b0 = reg.get("dttpu_wire_bytes_total")
        r0 = reg.get("dttpu_wire_chunk_retries_total")
        c0, b0, r0 = [m.value if m is not None else 0
                      for m in (c0, b0, r0)]
        t0 = time.perf_counter()
        router.drain_replica(victim_rid, migrate=True, timeout_s=600)
        drain_ms = (time.perf_counter() - t0) * 1e3
        router.drain()
        # total = drain + completing the migrated requests: the
        # re-prefill arm pays its recompute here, not in the drain
        total_ms = (time.perf_counter() - t0) * 1e3
        assert all(h.status == "ok" for h in hs)
        skipped = sum(e.stats().prefill_windows_skipped_total
                      for e in surv_engines) - skip0
        router.resume_replica(victim_rid)
        get = lambda name: (reg.get(name).value
                            if reg.get(name) is not None else 0)
        return dict(drain_migrate_ms=round(drain_ms, 3),
                    total_ms=round(total_ms, 3),
                    dest_windows_skipped=int(skipped),
                    chunks=int(get("dttpu_wire_chunks_total") - c0),
                    bytes=int(get("dttpu_wire_bytes_total") - b0),
                    retries=int(
                        get("dttpu_wire_chunk_retries_total") - r0))

    wire = fleet.PageWire(registry=reg, chunk_pages=2, overlap=2)
    wire_arm(wire, n=1)       # trace _wire_gather/_wire_splice once
    nowire = wire_arm(None)
    wired = wire_arm(wire)
    # chunk/overlap sweep: how framing granularity and frames-in-flight
    # trade wall clock for retry blast radius on this link
    sweep = []
    combos = ([(1, 1), (2, 2)] if SMOKE
              else [(1, 1), (2, 1), (2, 2), (4, 2), (4, 4)])
    for cp, ov in combos:
        w = fleet.PageWire(registry=reg, chunk_pages=cp, overlap=ov)
        arm = wire_arm(w)
        sweep.append(dict(chunk_pages=cp, overlap=ov, **arm))
    router.page_wire = None
    wire_pages = int(reg.get("dttpu_wire_pages_shipped_total").value)
    wire_transfers = int(reg.get("dttpu_wire_transfers_total").value)

    log(f"fleet wire: migrate+complete {wired['total_ms']:.0f} ms "
        f"shipping pages ({wired['dest_windows_skipped']} dest windows "
        f"skipped) vs {nowire['total_ms']:.0f} ms re-prefill "
        f"({nowire['dest_windows_skipped']} skipped); "
        f"{wire_transfers} transfers, {wire_pages} pages shipped")

    log(f"fleet: {n_replicas} replicas {tps:,.0f} tok/s, admission "
        f"fairness {fairness:.3f} (FIFO on this trace: 0.0), per-tenant "
        "ttft p95 "
        + ", ".join(f"{t} {tenant_p95[t]:.1f} ms" for t in tenants))
    log(f"fleet migration: drain {drain_migrate_ms:.0f} ms migrate vs "
        f"{drain_wait_ms:.0f} ms wait; kill preserved "
        f"{preserved}/{mig_total} tokens "
        f"({preserved_ratio:.2f}) across {len(migrated)} migrations")
    return dict(metric="fleet_tokens_per_sec",
                value=round(tps, 1), unit="tokens/sec",
                tokens_per_sec=round(tps, 1),
                fairness_ratio=round(fairness, 4),
                ttft_p50_ms=round(pct(ttft_all, 0.50) * 1e3, 3),
                ttft_p95_ms=round(pct(ttft_all, 0.95) * 1e3, 3),
                tenant_ttft_p50_ms=tenant_p50,
                tenant_ttft_p95_ms=tenant_p95,
                drain_migrate_ms=round(drain_migrate_ms, 3),
                drain_wait_ms=round(drain_wait_ms, 3),
                tokens_preserved_ratio=round(preserved_ratio, 4),
                wire=dict(shipped=wired, re_prefill=nowire,
                          sweep=sweep, transfers=wire_transfers,
                          pages_shipped=wire_pages),
                migrations=int(
                    reg.get("dttpu_migrations_total").value),
                replicas=n_replicas, requests=n_req,
                num_slots=slots, prefill_chunk=chunk,
                tick_steps=tick_steps, total_new_tokens=total_tokens,
                seq_len=seq)


def bench_fleet_sim():
    """Million-request fleet simulation (fleet/sim.py): the REAL
    Router/Watchdog/tenancy/faults stack driven at virtual-time speed
    by ``SimEngine`` replicas priced with the graph-tier cost model.
    Four legs, one JSON line:

    1. **autoscaler** — the seeded diurnal+burst trace (two scheduled
       ``correlated_kill`` events included) under the SLO-driven
       ``Autoscaler`` (scale-out on missed attainment/backlog,
       migrate-based scale-in, heal after kills).
    2. **static** — the SAME trace on a fixed peak-sized fleet.
       ``autoscaler_vs_static`` = attainment per replica-second,
       autoscaler over static — >= 1.0 means the policy buys the same
       SLO for less provisioned capacity.
    3. **curve** — SLO attainment vs static replica count on a clean
       subset trace (``slo_vs_replicas``), the capacity-planning curve.
    4. **affinity ablation** — the saturated Zipf-prefix trace through
       a 4-replica static fleet twice: prefix-affinity placement on
       (``affinity_weight=1``) vs blind least-loaded
       (``affinity_weight=0``), SAME seeded trace (fingerprint
       equality asserted).  ``affinity_vs_blind`` is the tokens/s
       ratio on virtual time and ``fleet_prefix_hit_rate`` the
       affinity arm's fleet-wide radix hit rate — both PerfLedger
       fields the perf gate watches.  10⁶ requests full-scale, 2k
       under DTTPU_BENCH_SMOKE (DTTPU_BENCH_FLEET_SIM_ABLATION
       overrides); the Zipf population scales with the request count
       (512 per 2k requests) so the cold-landing rate — the thing
       placement policy controls — is scale-invariant instead of
       washing out once every replica has seen every prefix (sim
       fingerprints never evict).
    5. **real affinity** — the same on/off comparison on a REAL
       2-replica CPU ``serve.Engine`` fleet (tiny GPT, shared system
       prompts): placement quality is judged by the replicas' actual
       radix caches, pinning that the sim conclusion transfers
       (``DTTPU_BENCH_FLEET_AFFINITY_REAL=0`` skips).
    6. **validation** — a small burst replayed against BOTH a real
       2-replica ``serve.Engine`` fleet and the simulator with a
       ``CostModel.calibrate``\\ d from two measured points on that
       engine; asserts sim-predicted tokens/s and TTFT p50 land within
       25% of the real replay (``DTTPU_BENCH_FLEET_SIM_VALIDATE=0``
       skips, e.g. where no jax backend is wanted).

    ``sim_wall_s`` counts legs 1-3 only (the virtual-time claim:
    >= 1e6 simulated requests under 60 s of CPU wall-clock);
    ``simulated_requests`` is their request total.  The ablation legs
    keep their own clock (``ablation.wall_s``) so the headline claim
    stays comparable across PRs."""
    import gc
    import numpy as np
    from distributed_tensorflow_tpu import fleet
    from distributed_tensorflow_tpu.fleet import sim as sim_lib
    from distributed_tensorflow_tpu.fleet import workload
    from distributed_tensorflow_tpu.obs import federate, reqtrace

    n_main = int(os.environ.get("DTTPU_BENCH_FLEET_SIM_REQUESTS",
                                "8000" if SMOKE else "400000"))
    n_curve = int(os.environ.get("DTTPU_BENCH_FLEET_SIM_CURVE",
                                 "2000" if SMOKE else "65000"))
    horizon_s = 1800.0
    curve_replicas = (2, 3, 4, 6)
    slo = fleet.SLO(ttft_s=2.0, itl_s=0.02)
    # a ~200M-param weight-streaming decode point: mean demand sits
    # right at the 2-replica floor, so the diurnal peak and the burst
    # spikes genuinely need the autoscaler, while a peak-sized static
    # fleet idles through the trough
    engine_kw = dict(num_slots=8, prefill_chunk=64, tick_steps=16)
    cm = sim_lib.CostModel.analytic(
        n_params=2.0e8, prefill_chunk=64, num_slots=8, tick_steps=16,
        hw=sim_lib.HardwarePoint())
    trace = workload.synthesize(
        n_main, seed=0, horizon_s=horizon_s, bursts=3,
        burst_magnitude=5.0, failures=2, failure_k=2)

    sim_wall = [0.0]
    simulated = [0]

    # One federation over every leg's registries: the per-tenant SLO
    # gauges (dttpu_slo_*) stream in from the sims' TTFT/TPOT samples,
    # and the request lanes the SimEngines sample (1-in-trace_sample,
    # VIRTUAL timestamps) land in the bench tracer next to the host
    # spans — DTTPU_BENCH_TRACE_FILE carries both out for the CI merge.
    fed = federate.FederatedMetrics()

    def run_leg(tr, cost=None, engine=None, account=True, **kw):
        fs = sim_lib.FleetSim(tr, cost if cost is not None else cm,
                              slo=slo,
                              engine=dict(engine if engine is not None
                                          else engine_kw),
                              **kw)
        fs.metrics.federation = fed
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        try:
            rep = fs.run()
        finally:
            gc.enable()
        rep["wall_s"] = time.perf_counter() - t0
        if account:
            sim_wall[0] += rep["wall_s"]
            simulated[0] += rep["simulated_requests"]
        return rep

    auto_rep = run_leg(
        trace, replicas=2,
        autoscaler=dict(min_replicas=2, max_replicas=8,
                        eval_interval_s=15.0, cooldown_s=60.0),
        watchdog=dict(tick_deadline_s=5.0), seed=1)
    log(f"fleet_sim autoscaler: {auto_rep['completed']:,} ok, "
        f"attainment {auto_rep['slo_attainment']:.4f}, "
        f"{auto_rep['scale_outs']} out / {auto_rep['scale_ins']} in, "
        f"{auto_rep['migrations']} migrations, "
        f"{auto_rep['replica_seconds']:,.0f} replica-s")

    static_rep = run_leg(trace, replicas=6, seed=1)
    log(f"fleet_sim static x6: attainment "
        f"{static_rep['slo_attainment']:.4f}, "
        f"{static_rep['replica_seconds']:,.0f} replica-s")
    vs_static = ((auto_rep["slo_attainment"]
                  / max(auto_rep["replica_seconds"], 1e-9))
                 / (static_rep["slo_attainment"]
                    / max(static_rep["replica_seconds"], 1e-9)))

    curve_trace = workload.synthesize(
        n_curve, seed=1, horizon_s=horizon_s / 4, bursts=2,
        burst_magnitude=4.0, failures=0)
    curve = {}
    for r in curve_replicas:
        rep = run_leg(curve_trace, replicas=r, seed=2)
        curve[str(r)] = dict(
            slo_attainment=rep["slo_attainment"],
            attainment_ttft=rep["attainment_ttft"],
            attainment_itl=rep["attainment_itl"],
            ttft_p99_ms=rep["ttft_p99_ms"],
            itl_p99_ms=rep["itl_p99_ms"])
    log("fleet_sim curve: " + ", ".join(
        f"{r}r {c['slo_attainment']:.3f}" for r, c in curve.items()))

    # -- affinity ablation: prefix-affinity placement on vs off --------
    # Saturated arrivals (1000 req/s against a 4-replica fleet) so
    # virtual time is compute-bound, prefix-dominated requests (short
    # own-suffix, small decode budget, 512 Zipf populations) so the
    # prefill a hot landing skips is a material share of the work —
    # the regime ROADMAP item 6 is about, where blind placement
    # forfeits the radix win on every cold landing.
    n_abl = int(os.environ.get("DTTPU_BENCH_FLEET_SIM_ABLATION",
                               "2000" if SMOKE else "1000000"))
    # Zipf population scales with the trace (512 per 2k requests =
    # smoke-identical at smoke scale): sim fingerprints never evict,
    # so a FIXED population saturates every replica after a few
    # thousand requests and both arms converge to hit rate ~1 — the
    # cold-landing rate the placement policy controls must stay
    # scale-invariant for the 10⁶ leg to measure anything.
    abl_pops = max(512, (n_abl * 512) // 2000)
    abl_engine = dict(num_slots=8, prefill_chunk=16, tick_steps=8)
    abl_cm = sim_lib.CostModel.analytic(
        n_params=2.0e8, prefill_chunk=16, num_slots=8, tick_steps=8,
        hw=sim_lib.HardwarePoint())

    def abl_trace():
        return workload.synthesize(
            n_abl, seed=3, horizon_s=n_abl / 1000.0,
            prefix_populations=abl_pops, prefix_fraction=0.9,
            plen_mean=12.0, new_tokens_mean=4.0, bursts=0, failures=0)

    abl_fp = abl_trace().fingerprint()

    def abl_arm(weight):
        # re-synthesize per arm and assert fingerprint equality: both
        # arms provably replay the IDENTICAL workload, so the ratio
        # below measures placement policy and nothing else
        tr = abl_trace()
        assert tr.fingerprint() == abl_fp, "ablation arms diverged"
        return run_leg(tr, cost=abl_cm, engine=abl_engine, replicas=4,
                       seed=4, affinity_weight=weight,
                       account=False)

    abl_on = abl_arm(1.0)
    abl_off = abl_arm(0.0)
    assert abl_on["tokens_generated"] == abl_off["tokens_generated"], (
        "ablation arms generated different token counts")
    tps_on = abl_on["tokens_generated"] / abl_on["virtual_time_s"]
    tps_off = abl_off["tokens_generated"] / abl_off["virtual_time_s"]
    affinity_vs_blind = tps_on / tps_off
    ablation = dict(
        requests=n_abl, replicas=4, populations=abl_pops,
        wall_s=round(abl_on["wall_s"] + abl_off["wall_s"], 3),
        trace_fingerprint=abl_fp,
        affinity=dict(
            fleet_prefix_hit_rate=abl_on["fleet_prefix_hit_rate"],
            tokens_per_vsec=round(tps_on, 2),
            virtual_time_s=abl_on["virtual_time_s"],
            ttft_p50_ms=abl_on["ttft_p50_ms"],
            ttft_p95_ms=abl_on["ttft_p95_ms"]),
        blind=dict(
            fleet_prefix_hit_rate=abl_off["fleet_prefix_hit_rate"],
            tokens_per_vsec=round(tps_off, 2),
            virtual_time_s=abl_off["virtual_time_s"],
            ttft_p50_ms=abl_off["ttft_p50_ms"],
            ttft_p95_ms=abl_off["ttft_p95_ms"]))
    log(f"fleet_sim affinity ablation ({n_abl:,} req): hit rate "
        f"{abl_on['fleet_prefix_hit_rate']:.4f} (affinity) vs "
        f"{abl_off['fleet_prefix_hit_rate']:.4f} (blind), tokens/s "
        f"ratio {affinity_vs_blind:.4f}")

    real_affinity = None
    if os.environ.get("DTTPU_BENCH_FLEET_AFFINITY_REAL", "1") != "0":
        real_affinity = _fleet_affinity_real()
        log(f"fleet affinity (real 2-replica): hit rate "
            f"{real_affinity['affinity']['fleet_prefix_hit_rate']:.4f}"
            f" (affinity) vs "
            f"{real_affinity['blind']['fleet_prefix_hit_rate']:.4f} "
            f"(blind), {real_affinity['affinity']['affinity_hits']} "
            f"affinity placements")

    validation = None
    if os.environ.get("DTTPU_BENCH_FLEET_SIM_VALIDATE", "1") != "0":
        validation = _fleet_sim_validate(cm_seed=0)
        log(f"fleet_sim validation: sim/real tokens/s "
            f"{validation['tokens_per_sec_ratio']:.3f}, ttft p50 "
            f"{validation['ttft_p50_ratio']:.3f} (|err| <= 0.25)")

    total_tokens = (auto_rep["tokens_generated"]
                    + static_rep["tokens_generated"])
    result = dict(
        metric="fleet_sim_requests_per_sec",
        value=round(simulated[0] / max(sim_wall[0], 1e-9), 1),
        unit="requests/sec",
        simulated_requests=simulated[0],
        sim_wall_s=round(sim_wall[0], 3),
        virtual_time_s=round(auto_rep["virtual_time_s"], 3),
        autoscaler=auto_rep, static=static_rep,
        autoscaler_vs_static=round(vs_static, 4),
        slo_vs_replicas=curve,
        # top-level (measured) perf-gate fields: deterministic virtual-
        # time numbers, gated by scripts/perf_gate.py via the committed
        # ledger/baseline.jsonl fleet_sim row
        affinity_vs_blind=round(affinity_vs_blind, 4),
        fleet_prefix_hit_rate=abl_on["fleet_prefix_hit_rate"],
        ablation=ablation,
        slo=dict(ttft_s=slo.ttft_s, itl_s=slo.itl_s),
        cost_model=dict(prefill_window_s=cm.prefill_window_s,
                        decode_tick_s=cm.decode_tick_s,
                        overhead_s=cm.overhead_s,
                        provenance=cm.provenance),
        total_tokens=total_tokens,
        requests_main=n_main, requests_curve=n_curve)
    fed_text = fed.expose()
    result["federation"] = dict(
        slo_series=sum(1 for ln in fed_text.splitlines()
                       if ln.startswith("dttpu_slo_")),
        sources=fed.source_count())
    result["tracing"] = dict(
        # ring-bounded (256): "did sampling run", not a request count
        sampled_lanes=len(reqtrace.completed()),
        trace_sample=int(engine_kw.get("trace_sample", 64)))
    log(f"fleet_sim federation: {result['federation']['slo_series']} "
        f"SLO series over {result['federation']['sources']} source(s), "
        f"{result['tracing']['sampled_lanes']} sampled lanes in the "
        f"trace ring")
    if real_affinity is not None:
        result["real_affinity"] = real_affinity
    if validation is not None:
        result["validation"] = validation
    return result


def _fleet_affinity_real():
    """The affinity ablation's REAL leg: a tiny 2-replica CPU
    ``serve.Engine`` fleet behind the Router with prefix-affinity
    placement on vs off.  Requests share a handful of system prompts
    (distinct unique suffixes); a seeding wave registers each prompt's
    pages on whichever replica first serves it, then the measured wave
    is placed by each policy and the replicas' ACTUAL radix caches
    judge the outcome — ``fleet_prefix_hit_rate`` summed over both
    engines' pool counters, exactly the sim leg's metric.  Wall time
    is deliberately not compared (2 real engines timeshare one CPU);
    this leg pins that the placement-quality conclusion transfers from
    cost-model to hardware."""
    import jax
    import numpy as np
    from distributed_tensorflow_tpu import fleet, serve
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
    from distributed_tensorflow_tpu.obs import metrics as metrics_lib
    import jax.numpy as jnp

    config = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                       num_heads=2, intermediate_size=256,
                       max_position=128, dtype=jnp.float32,
                       dropout_rate=0.0)
    model = GPT(config)
    params = model.init(jax.random.PRNGKey(0))
    slots, chunk, ticks = 4, 16, 4
    pops, followers, budget = 4, 24, 4
    rng = np.random.default_rng(7)
    system = [rng.integers(0, config.vocab_size, 2 * chunk)
              .astype(np.int32) for _ in range(pops)]

    def prompt(pop):
        suffix = rng.integers(0, config.vocab_size, 5).astype(np.int32)
        return np.concatenate([system[pop], suffix])

    # one prompt set, replayed by BOTH arms — the comparison measures
    # placement policy, not workload luck.  The follower population
    # order is SHUFFLED: a round-robin order would parity-align with
    # blind placement's strict alternation and hand the blind arm the
    # holder by coincidence.
    seed_prompts = [prompt(pop) for pop in range(pops)]
    follower_prompts = [prompt(int(pop))
                        for pop in rng.integers(0, pops, followers)]

    def arm(weight):
        reg = metrics_lib.Registry()
        engines = [serve.Engine(model, params, num_slots=slots,
                                max_len=128, prefill_chunk=chunk,
                                tick_steps=ticks, registry=reg)
                   for _ in range(2)]
        router = fleet.Router(engines, registry=reg,
                              affinity_weight=weight)
        # seeding wave: one request per system prompt — its admission
        # registers the prompt's pages on the serving replica
        for p in seed_prompts:
            router.submit(p, budget)
        router.drain()
        seeded = {rid: (s.prefix_lookups_total, s.prefix_hits_total)
                  for rid, s in router.stats().items()}
        hs = [router.submit(p, budget) for p in follower_prompts]
        router.drain()
        assert all(h.status == "ok" for h in hs)
        stats = router.stats()
        lookups = sum(s.prefix_lookups_total - seeded[rid][0]
                      for rid, s in stats.items())
        hits = sum(s.prefix_hits_total - seeded[rid][1]
                   for rid, s in stats.items())
        return dict(
            fleet_prefix_hit_rate=round(hits / lookups
                                        if lookups else 0.0, 4),
            prefix_tokens_reused=int(sum(
                s.prefix_tokens_reused_total for s in stats.values())),
            affinity_hits=int(reg.get(
                "dttpu_router_affinity_hits_total").value),
            placements=list(router.placements))

    on, off = arm(1.0), arm(0.0)
    return dict(requests=followers, populations=pops,
                affinity=dict((k, v) for k, v in on.items()
                              if k != "placements"),
                blind=dict((k, v) for k, v in off.items()
                           if k != "placements"))


def _fleet_sim_validate(cm_seed=0):
    """The fleet_sim stub-validation leg: one small burst through a
    real single-replica CPU ``serve.Engine`` fleet (still behind the
    Router) and through the simulator with a cost model CALIBRATED
    from two measured points (a decode tick at full batch, a
    prefill-window tick) on that same engine.  One replica because the
    comparison is wall-vs-virtual time: N real engines timeshare one
    CPU (wall = sum of their work) while N sim replicas run in
    parallel virtual time — single-replica makes the two clocks
    commensurable.  Returns the sim/real ratios and asserts both
    within 25%."""
    import jax
    import numpy as np
    from distributed_tensorflow_tpu import fleet, serve
    from distributed_tensorflow_tpu.analysis import graph as graph_lib
    from distributed_tensorflow_tpu.fleet import sim as sim_lib
    from distributed_tensorflow_tpu.fleet import workload
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
    from distributed_tensorflow_tpu.obs import metrics as metrics_lib
    import jax.numpy as jnp

    # deliberately tiny: the contract under test is sim-vs-real on the
    # SAME engine, not model scale
    config = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                       num_heads=2, intermediate_size=256,
                       max_position=128, dtype=jnp.float32,
                       dropout_rate=0.0)
    model = GPT(config)
    params = model.init(jax.random.PRNGKey(0))
    slots, chunk, ticks = 4, 16, 4
    n_req, budget = 48, 10
    rng = np.random.default_rng(cm_seed)
    prompts = [rng.integers(0, config.vocab_size,
                            int(rng.integers(3, 2 * chunk + 1)))
               .astype(np.int32) for _ in range(n_req)]

    def make_engine(reg):
        return serve.Engine(model, params, num_slots=slots,
                            max_len=128, prefill_chunk=chunk,
                            tick_steps=ticks, registry=reg)

    reg = metrics_lib.Registry()
    engines = [make_engine(reg)]
    router = fleet.Router(engines, registry=reg)
    # warmup: compile every executable on both replicas
    for _ in range(2):
        router.submit(prompts[0], 2)
        router.submit(rng.integers(0, config.vocab_size,
                                   chunk + 3).astype(np.int32), 2)
    router.drain()

    # -- calibration: two measured points on engine 0 ------------------
    eng = engines[0]
    for _ in range(slots):                   # full decode batch
        eng.submit(prompts[0][:4], 64)
    while eng.stats().active < slots:        # admit + prefill everyone
        eng.step()
    # per-step MIN, not mean: each step is the same deterministic
    # compute, so scheduler preemption on a shared core only ever adds
    # time — the minimum is the clean sample
    tick_samples = []
    while eng.stats().active == slots and len(tick_samples) < 12:
        t0 = time.perf_counter()
        eng.step()
        tick_samples.append(time.perf_counter() - t0)
    measured_tick_s = min(tick_samples)
    eng.drain()
    # prefill point: one long prompt alone; the first step (admit +
    # first window) is untimed, the remaining pure-window steps are
    eng.submit(rng.integers(0, config.vocab_size,
                            6 * chunk).astype(np.int32), 1)
    eng.step()
    window_samples = []
    while eng.stats().prefilling and len(window_samples) < 12:
        t0 = time.perf_counter()
        eng.step()
        window_samples.append(time.perf_counter() - t0)
    measured_window_s = (min(window_samples) if window_samples
                         else measured_tick_s)
    eng.drain()
    targets = {t.name: t for t in eng.scheduler.graph_targets()}
    window_cost = graph_lib.target_cost(targets["prefill_window"])
    tick_cost = graph_lib.target_cost(targets["decode_tick"])
    cm = sim_lib.CostModel.calibrate(window_cost, tick_cost,
                                     measured_window_s, measured_tick_s)

    # -- real replay: the whole burst, wall-clock (min of 3 on both
    # wall and ttft p50 to shed scheduler noise on a shared CI core) --
    def real_replay():
        hs = [router.submit(p, budget) for p in prompts]
        t0 = time.perf_counter()
        while router.busy:
            router.step()
        wall = time.perf_counter() - t0
        assert all(h.status == "ok" for h in hs)
        ttfts = sorted(h.ttft_s for h in hs)
        return wall, ttfts[len(ttfts) // 2], hs
    replays = [real_replay() for _ in range(3)]
    real_wall = min(r[0] for r in replays)
    real_ttft_p50 = min(r[1] for r in replays)
    real_tokens = sum(len(h.tokens) for h in replays[0][2])
    real_tps = real_tokens / real_wall

    # -- sim replay: same burst shape, same engine geometry ------------
    tr = workload.Trace(
        arrival_s=np.zeros(n_req, dtype=np.float64),
        plen=np.array([len(p) for p in prompts], dtype=np.int32),
        new_tokens=np.full(n_req, budget, dtype=np.int32),
        tenant=np.zeros(n_req, dtype=np.int16),
        prefix_id=np.zeros(n_req, dtype=np.int32),
        prefix_len=np.zeros(n_req, dtype=np.int32),
        adapter=np.full(n_req, -1, dtype=np.int16),
        tenants=(("default", 1.0),), events=(), horizon_s=0.0,
        seed=cm_seed)
    fs = sim_lib.FleetSim(
        tr, cm, replicas=1,
        engine=dict(num_slots=slots, prefill_chunk=chunk,
                    tick_steps=ticks),
        quantum_s=measured_tick_s, inflight_cap_per_replica=n_req,
        seed=0)
    sim_rep = fs.run()
    sim_tps = sim_rep["tokens_generated"] / sim_rep["virtual_time_s"]
    sim_ttft_p50 = sim_rep["ttft_p50_ms"] / 1e3

    tps_ratio = sim_tps / real_tps
    ttft_ratio = sim_ttft_p50 / real_ttft_p50
    assert abs(tps_ratio - 1.0) <= 0.25, (
        f"sim tokens/s off by {tps_ratio:.3f}x "
        f"(sim {sim_tps:.1f} vs real {real_tps:.1f})")
    assert abs(ttft_ratio - 1.0) <= 0.25, (
        f"sim ttft p50 off by {ttft_ratio:.3f}x "
        f"(sim {sim_ttft_p50*1e3:.1f} ms vs real "
        f"{real_ttft_p50*1e3:.1f} ms)")
    return dict(
        requests=n_req,
        measured_tick_s=round(measured_tick_s, 6),
        measured_window_s=round(measured_window_s, 6),
        calibrated=dict(prefill_window_s=round(cm.prefill_window_s, 6),
                        decode_tick_s=round(cm.decode_tick_s, 6),
                        overhead_s=round(cm.overhead_s, 6)),
        real_tokens_per_sec=round(real_tps, 2),
        sim_tokens_per_sec=round(sim_tps, 2),
        tokens_per_sec_ratio=round(tps_ratio, 4),
        real_ttft_p50_ms=round(real_ttft_p50 * 1e3, 3),
        sim_ttft_p50_ms=round(sim_ttft_p50 * 1e3, 3),
        ttft_p50_ratio=round(ttft_ratio, 4))


def bench_gpt_moe():
    """The gpt row with a mixture-of-experts FFN (ops.moe top-2/8 capacity
    routing + aux load-balance loss) — the measured row for the MoE
    subsystem.  Single-chip the experts are co-located (no all_to_all);
    the routing/capacity compute is what this row prices."""
    experts = int(os.environ.get("DTTPU_BENCH_GPT_MOE", "8"))
    result = bench_gpt(experts=experts)
    result["metric"] = "gpt_moe" + result.pop("metric")[len("gpt"):]
    result["moe_experts"] = experts
    return result


def bench_gpt_long():
    """The gpt row at seq 2048 — the long-context operating point where
    ``use_flash="auto"`` actually dispatches the fused Pallas kernel on
    TPU (crossover at DTTPU_FLASH_MIN_SEQ=2048, docs/PERF.md); seq 256
    keeps the default gpt row on the XLA path, so this row is the one
    that exercises flash attention end-to-end in a train step."""
    result = bench_gpt(seq=2048)
    result["metric"] = "gpt_long" + result.pop("metric")[len("gpt"):]
    return result


def bench_recovery():
    """Recovery smoke (docs/RESILIENCE.md): a small training run with an
    injected prefetch-producer kill mid-flight; the resilience
    ``Supervisor`` restarts it from the last good checkpoint.  The JSON
    line reports ``restore_ms`` (wall clock of the verified
    ``restore_latest_good`` walk on the retry) and
    ``recovery_steps_lost`` (steps between the restored checkpoint and
    the failure point — the save-interval tax), so the restart path has
    a measured number instead of a vibe.  Always tiny (XOR MLP): this
    row measures the recovery machinery, not the model."""
    import shutil
    import tempfile
    import jax
    from distributed_tensorflow_tpu import data, ops, optim, train
    from distributed_tensorflow_tpu.obs import metrics as metrics_lib
    from distributed_tensorflow_tpu.resilience import (NonfiniteGuardHook,
                                                       Supervisor, faults)

    target_step, save_every, kill_at_batch = 24, 5, 13
    reg = metrics_lib.Registry()
    ckpt_dir = tempfile.mkdtemp(prefix="dttpu-recovery-")
    restore_ms: list = []
    resumed_steps: list = []
    fail_steps: list = []

    def make_bits():
        model = ops.serial(ops.Dense(16, "relu"), ops.Dense(32, "sigmoid"))
        opt = optim.adam()
        state = train.init_train_state(model, opt, jax.random.PRNGKey(0),
                                       (64,))
        step = train.make_train_step(model, "mse", opt, device_health=True,
                                     skip_nonfinite=True)
        (xt, yt), _ = data.xor_data(500, val_size=10, seed=0)
        return state, step, data.Dataset([xt, yt], 50, seed=0)

    def build_session():
        state, step, ds = make_bits()
        t0 = time.perf_counter()
        restored, _ = train.checkpoint.restore_latest_good(state, ckpt_dir)
        dt_ms = (time.perf_counter() - t0) * 1e3
        if restored is not None:
            state = restored
            restore_ms.append(dt_ms)
            resumed_steps.append(int(state.step))
        sess = train.TrainSession(
            state, step, checkpoint_dir=ckpt_dir, restore=False,
            hooks=[train.CheckpointHook(every_steps=save_every,
                                        every_secs=None),
                   NonfiniteGuardHook(max_consecutive=3),
                   train.StopAtStepHook(last_step=target_step)])
        sess._recovery_ds = ds
        return sess

    def train_fn(sess):
        it = data.prefetch_to_device(iter(sess._recovery_ds.epochs(1000)),
                                     size=2)
        try:
            for batch in it:
                if sess.should_stop():
                    break
                sess.run_step(batch)
        except BaseException:
            fail_steps.append(sess.step)
            raise
        return sess.step

    plan = faults.FaultPlan(
        [{"kind": "kill_prefetch", "at": kill_at_batch}], registry=reg)
    sup = Supervisor(max_restarts=2, backoff_base=0.01, registry=reg)
    # goodput accounting (obs/goodput.py): the supervised run's wall
    # clock attributed into step / checkpoint / backoff / stall buckets
    # — the recovery row carries the split so "how much did that fault
    # cost" is a number, not a rerun
    from distributed_tensorflow_tpu.obs import goodput as goodput_lib
    acct = goodput_lib.GoodputAccountant(registry=reg)
    try:
        with faults.activated(plan), goodput_lib.activated(acct):
            final_step = sup.run(build_session, train_fn)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    goodput_report = acct.report()

    lost = (fail_steps[0] - resumed_steps[0]
            if fail_steps and resumed_steps else -1)

    # -- serve-tier watchdog smoke (docs/RESILIENCE.md §watchdog): a
    # 2-replica fleet takes an injected stall_tick on replica 0; the
    # Watchdog's tick-deadline policy must detect it at the first check
    # after the stalled tick, quarantine the replica, and migrate its
    # requests to the survivor.  detect_ms measures stall start ->
    # quarantine (separate registry: the training-recovery fault count
    # above stays the row's faults_injected).
    import numpy as np
    from distributed_tensorflow_tpu import fleet as fleet_lib
    from distributed_tensorflow_tpu import serve as serve_lib
    from distributed_tensorflow_tpu.models.gpt import gpt_tiny

    wreg = metrics_lib.Registry()
    gmodel = gpt_tiny(dropout_rate=0.0)
    gparams = gmodel.init(jax.random.PRNGKey(0))
    engines = [serve_lib.Engine(gmodel, gparams, num_slots=2, max_len=64,
                                prefill_chunk=4, tick_steps=2,
                                registry=wreg) for _ in range(2)]
    wrouter = fleet_lib.Router(engines, registry=wreg)
    # warm-compile every executable before arming a tick deadline (a
    # first-compile tick is legitimately slower than any sane deadline)
    warm = [e.submit(np.arange(1, 7, dtype=np.int32), 3)
            for e in engines]
    for _ in range(8):
        for e in engines:
            e.step()
    tick_deadline_s, stall_s = 0.25, 1.0
    wd = fleet_lib.Watchdog(wrouter, tick_deadline_s=tick_deadline_s,
                            registry=wreg)
    wplan = faults.FaultPlan(
        [{"kind": "stall_tick", "at": 3, "replica": 0,
          "seconds": stall_s}], registry=wreg)
    wrng = np.random.default_rng(3)
    detect_ms = None
    t_stall = None
    with faults.activated(wplan):
        whs = [wrouter.submit(
                   wrng.integers(0, 50, 5).astype(np.int32), 8)
               for _ in range(4)]
        while wrouter.busy:
            t0 = time.perf_counter()
            wrouter.step()
            if t_stall is None and wplan.log:
                t_stall = t0        # the stall landed inside this step
            if wd.check() and detect_ms is None:
                detect_ms = (time.perf_counter() - t_stall) * 1e3
    watchdog_ok = (detect_ms is not None
                   and 0 in wrouter.quarantined
                   and all(h.status == "ok" for h in whs)
                   and all(h.done for h in warm))

    ok = (final_step >= target_step and restore_ms
          and reg.get("dttpu_restarts_total").value >= 1
          and watchdog_ok)
    return {
        "metric": "recovery_restore_ms" + ("" if ok else "_FAILED"),
        "value": round(restore_ms[0], 3) if restore_ms else 0.0,
        "unit": "ms",
        "restore_ms": round(restore_ms[0], 3) if restore_ms else None,
        "recovery_steps_lost": lost,
        "restarts": reg.get("dttpu_restarts_total").value,
        "faults_injected": reg.get("dttpu_faults_injected_total").value,
        "final_step": final_step,
        # watchdog smoke: detection latency from stall start (the stall
        # itself is stall_s, so "within deadline" means detect_ms stays
        # a small overhead above it), quarantine + migration counts
        "watchdog_detect_ms": (round(detect_ms, 3)
                               if detect_ms is not None else None),
        "watchdog_stall_s": stall_s,
        "watchdog_tick_deadline_s": tick_deadline_s,
        "watchdog_quarantined": len(wrouter.quarantined),
        "watchdog_migrations": int(
            wreg.get("dttpu_migrations_total").value),
        # where the supervised run's wall clock went (buckets sum to
        # wall_s by construction; goodput_pct = step/wall)
        "goodput": goodput_report,
        "goodput_pct": goodput_report["goodput_pct"],
    }


CONFIGS = {
    "mnist_mlp": bench_mnist_mlp,
    "cifar_cnn": bench_cifar_cnn,
    "resnet50": bench_resnet50,
    "bert": bench_bert,
    "gpt": bench_gpt,
    "gpt_long": bench_gpt_long,
    "gpt_moe": bench_gpt_moe,
    "llama": bench_llama,
    "gpt_decode": bench_gpt_decode,
    "gpt_decode_int8": bench_gpt_decode_int8,
    "gpt_decode_spec": bench_gpt_decode_spec,
    "gpt_serve": bench_gpt_serve,
    "fleet": bench_fleet,
    "fleet_sim": bench_fleet_sim,
    "recovery": bench_recovery,
}


def _git_sha() -> str:
    """Code identity for the perf ledger: ``DTTPU_GIT_SHA`` when the
    driver exports it (detached workdirs), else ``git rev-parse`` of the
    bench's own checkout, else "unknown" — never an exception."""
    sha = os.environ.get("DTTPU_GIT_SHA")
    if sha:
        return sha
    try:
        import subprocess
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _backend_fingerprint() -> dict:
    """Backend/mesh identity for the perf ledger: rows from an 8-way
    virtual CPU mesh, a single CPU device, and a v4-8 must never be
    compared as if they were the same machine."""
    import jax
    devices = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_count": len(devices),
        "device_kind": getattr(devices[0], "device_kind", "unknown"),
        "process_count": jax.process_count(),
    }


def _stamp_identity(result: dict, config: str) -> dict:
    """Stamp the JSON line with run identity (obs/ledger.py schema):
    anonymous rows can only be compared by filename convention."""
    import uuid
    from distributed_tensorflow_tpu.obs import ledger as ledger_lib
    result["schema_version"] = ledger_lib.SCHEMA_VERSION
    result["run_id"] = uuid.uuid4().hex[:16]
    result["git_sha"] = _git_sha()
    result["config"] = config
    result["timestamp"] = round(time.time(), 3)
    result["fingerprint"] = _backend_fingerprint()
    return result


def main():
    _load_promoted_defaults()
    config = "mnist_mlp"
    device = os.environ.get("DTTPU_BENCH_DEVICE")
    for arg in sys.argv[1:]:
        if arg.startswith("--device="):
            device = arg.split("=", 1)[1]
            continue
        config = arg.split("=", 1)[1] if arg.startswith("--config=") else arg
    if config not in CONFIGS:
        log(f"unknown config {config!r}; choices: {sorted(CONFIGS)}")
        sys.exit(2)

    import jax
    if device:
        jax.config.update("jax_platforms", device)
    from distributed_tensorflow_tpu.utils import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    if not device and devices[0].platform != "tpu":
        # no fallback: a CPU rate is not a device metric
        log(f"bench: no TPU (found platform {devices[0].platform!r}) — "
            "nothing measured; pass --device=cpu for the wiring check")
        sys.exit(3)
    log(f"backend up: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform})")
    # Warn-only retrace sanitizer (analysis/sanitizer.py): every jit built
    # during the bench gets a trace budget, so a rate that was silently
    # dominated by recompiles arrives annotated instead of trusted.  The
    # budget default leaves room for the batch ladder's legitimate
    # shape-driven retraces (one lower() + one call per rung); warnings
    # go to stderr with an arg-diff, and the JSON line carries the count.
    # Telemetry tracer: active for the whole measurement so _time_steps'
    # dispatch spans AND the sanitizer's jit_compile/retrace instants land
    # on one host timeline, written next to the JSON line as `trace_file`.
    tracer = None
    if TELEMETRY:
        from distributed_tensorflow_tpu.obs import trace as obs_trace
        tracer = obs_trace.activate(obs_trace.Tracer(enabled=True))
    if os.environ.get("DTTPU_BENCH_SANITIZE", "1") != "0":
        from distributed_tensorflow_tpu.analysis.sanitizer import RetraceGuard
        budget = int(os.environ.get("DTTPU_BENCH_RETRACE_BUDGET", "6"))
        with RetraceGuard(budget=budget, mode="warn",
                          enforce_donation=False) as guard:
            result = CONFIGS[config]()
        if guard.violations:
            result["retrace_warnings"] = len(guard.violations)
    else:
        result = CONFIGS[config]()
    if _STEP_TIMES:
        # barrier-closed per-update host latencies (see _time_steps);
        # decode configs time whole generate() calls instead and carry
        # no step-time fields
        ts = sorted(_STEP_TIMES)
        result["step_time_p50_ms"] = round(ts[int(0.50 * (len(ts) - 1))]
                                           * 1e3, 3)
        result["step_time_p95_ms"] = round(ts[int(0.95 * (len(ts) - 1))]
                                           * 1e3, 3)
    if tracer is not None:
        import tempfile
        path = os.environ.get("DTTPU_BENCH_TRACE_FILE") or os.path.join(
            tempfile.gettempdir(), f"dttpu-bench-{config}-trace.json")
        try:
            result["trace_file"] = tracer.save(path)
        except OSError as e:
            log(f"could not write trace file {path}: {e}")
    _stamp_identity(result, config)
    ledger_path = os.environ.get("DTTPU_BENCH_LEDGER")
    if ledger_path:
        # opt-in (CI sets it): a default repo path would dirty every
        # test run's working tree with measurement rows
        try:
            from distributed_tensorflow_tpu.obs import ledger as ledger_lib
            ledger_lib.PerfLedger(ledger_path).append(
                ledger_lib.row_from_bench(result))
            log(f"ledger: appended {config} row to {ledger_path}")
        except Exception as e:
            log(f"ledger append failed ({e}); JSON line still printed")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
