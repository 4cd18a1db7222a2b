#!/usr/bin/env python3
"""Chip smoke: the quickest proof that train and serve still start on the TPU.

``python3 chip_smoke.py`` (one chip, one process, no child processes) drives
the library's own entry points at full GPT-2-small width — 12 layers, hidden
768, 12 heads, FFN 3072, vocab 50,257, bf16, seeded random weights:

  device      platform / device_kind / count / memory_stats
  train       TrainState -> shard_train_state -> make_custom_train_step ->
              TrainSession, batch 12 x seq 1024, checkpoint save + restore
  train_long  the same step at batch 6 x seq 2048, where use_flash="auto"
              dispatches the Pallas flash kernel; compared with use_flash=False
  serve       serve.Engine(num_slots=16, max_len=1024): mixed-length requests,
              a shared prefix, the fused paged-attention kernel vs the gather
              read path against a float64 host truth
  serve_pool  the same engine at GPT-2-XL widths (25 heads x 64: a width that
              is no multiple of a lane tile), 8 slots x 1024: the compiled
              text of its three hot programs moves no copy or slice the size
              of the page pool or of one layer of it

``python3 chip_smoke.py --chips=4`` runs only the path that exists only
across chips: the same train step on a data x fsdp mesh of four, compared
with per-shard losses computed on one device of the same process.

Output contract.  Every phase prints one JSON line on stdout as it ends;
human text goes to stderr.  The LAST line of stdout is always exactly

    {"ok": <bool>, "device": {"platform": ..., "kind": ..., "count": ...}}

with the device as JAX reports it.  ``ok`` is true only on a TPU with every
phase passed, and the exit code is 0 only then.  Off-TPU the same phases run
at a tiny width picked here from the platform found (there is no option for
it), so the control flow can be rehearsed on the CPU; that run truthfully
ends ``"ok": false, "platform": "cpu"`` and exits 1.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import re
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

KERNEL_MARK = "tpu_custom_call"   # how a Mosaic kernel shows in HLO text

# bf16 step-to-step agreement of the LM loss between two attention
# implementations (flash vs dense) or two layouts (mesh vs one device) of the
# same batch and weights.  The loss is ~ln(vocab) ~ 10.8 here, so 0.02 is
# 0.2 %: well above bf16 rounding of a mean over >= 12k tokens, far below
# what a wrong mask, a dropped shard or a mis-scaled softmax would move it.
FIRST_LOSS_TOL = 0.02
# After two optimizer updates the two variants have also accumulated
# different rounding in their weights; a wrong backward moves this by O(1).
LAST_LOSS_TOL = 0.1

FULL = dict(
    model=dict(vocab_size=50257, hidden_size=768, num_layers=12,
               num_heads=12, intermediate_size=3072),
    train=dict(batch=12, seq=1024, steps=6),
    train_long=dict(batch=6, seq=2048, steps=3),
    serve=dict(num_slots=16, max_len=1024, new_tokens=32, warm_len=40,
               prefix=128, prompt_lens=(8, 23, 64, 148, 300, 33, 200, 161)),
    serve_pool=dict(model=dict(vocab_size=50257, hidden_size=1600,
                               num_layers=48, num_heads=25,
                               intermediate_size=6400),
                    num_slots=8, max_len=1024),
    dp4=dict(batch=24, seq=2048, steps=3),
)
# Off-TPU only: small enough that XLA:CPU and the Pallas interpreter walk
# every phase in about a minute.  Never reported as a pass.
TINY = dict(
    model=dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=2,
               intermediate_size=128),
    train=dict(batch=4, seq=32, steps=6),
    train_long=dict(batch=2, seq=64, steps=3),
    serve=dict(num_slots=4, max_len=64, new_tokens=8, warm_len=40,
               prefix=16, prompt_lens=(4, 7, 12, 22, 40, 9, 30, 27)),
    serve_pool=dict(model=dict(vocab_size=512, hidden_size=256, num_layers=2,
                               num_heads=4, intermediate_size=128),
                    num_slots=4, max_len=64),
    dp4=dict(batch=8, seq=64, steps=3),
)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------- the contract


def final_line(ok: bool, device: dict) -> str:
    """THE line the driver reads: exactly ``ok`` and ``device``, and in
    ``device`` exactly ``platform``, ``kind``, ``count``.  Nothing else ever
    goes in it — phase results live on the earlier lines."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])},
    })


def describe_device() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def claim_stdout():
    """Keep the real stdout for the JSON lines and point fd 1 at stderr, so
    nothing else in the process — a library ``print``, a C++ log line, a
    late thread — can write before, between or after them."""
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return out


# ------------------------------------------------------------------ plumbing


class CompileMeter:
    """Counts XLA backend compiles (and persistent-cache hits/misses) through
    ``jax.monitoring`` — the per-phase ``compile_seconds`` and the
    "no compile after warm-up" checks read it."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def _on_duration(self, name, secs, **_):
        if name == self._COMPILE:
            self.compiles += 1
            self.seconds += secs

    def _on_event(self, name, **_):
        if name == self._HIT:
            self.hits += 1
        elif name == self._MISS:
            self.misses += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def snapshot(self):
        return (self.compiles, self.seconds, self.hits, self.misses)


@dataclasses.dataclass
class Ctx:
    sizes: dict
    out_dir: str
    meter: CompileMeter
    seed: int = 0


def _abstract(tree):
    """Shapes + shardings of a pytree of arrays: lowering input that survives
    the donation of the arrays themselves."""
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


# ----------------------------------------------------------------- training


def _gpt_config(ctx, seq, use_flash):
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models.gpt import GPTConfig
    return GPTConfig(**ctx.sizes["model"], max_position=seq,
                     dtype=jnp.bfloat16, dropout_rate=0.0, remat=True,
                     use_flash=use_flash)


def _train_setup(ctx, mesh, *, batch, seq, use_flash):
    """What examples/train_gpt.py does, at the width ``ctx`` names:
    (initial params, sharded TrainState, jitted step, device batch)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from distributed_tensorflow_tpu import optim, train
    from distributed_tensorflow_tpu.models.gpt import GPT

    config = _gpt_config(ctx, seq, use_flash)
    model = GPT(config, mesh=mesh)
    optimizer = optim.adamw(1e-3)
    fsdp = mesh.shape.get("fsdp", 1) > 1
    params = jax.jit(model.init)(jax.random.PRNGKey(ctx.seed))
    state = train.TrainState.create(params, optimizer.init(params))
    state = train.shard_train_state(state, mesh,
                                    model.partition_rules(fsdp=fsdp))
    step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                        grad_clip_norm=1.0)
    # lm_loss_fn shifts internally: inputs ids[:, :-1], targets ids[:, 1:]
    tokens = np.random.default_rng(ctx.seed).integers(
        0, config.vocab_size, (batch, seq + 1)).astype(np.int32)
    spec = P(("data", "fsdp")) if fsdp else P("data")
    device_batch = jax.device_put({"input_ids": tokens},
                                  NamedSharding(mesh, spec))
    return params, state, step, device_batch


def _run_steps(ctx, state, step, batch, steps, checkpoint_dir=None):
    """``steps`` updates through a TrainSession on one fixed batch ->
    (final state, losses, compiles seen after the first step)."""
    from distributed_tensorflow_tpu import train
    losses = []
    after_first = 0
    with train.TrainSession(state, step, checkpoint_dir=checkpoint_dir,
                            hooks=[train.StopAtStepHook(steps)],
                            sharded_checkpoint=True) as sess:
        while not sess.should_stop():
            metrics = sess.run_step(batch)
            losses.append(float(metrics["loss"]))   # fetch = step finished
            if len(losses) == 1:
                after_first = ctx.meter.compiles
        late = ctx.meter.compiles - after_first
    return sess.state, losses, late


def _one_device_mesh():
    import jax
    from distributed_tensorflow_tpu import parallel
    return parallel.make_mesh({"data": 1, "fsdp": 1},
                              devices=jax.devices()[:1])


def phase_device(ctx):
    import jax
    import jaxlib
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "bytes_limit": stats.get("bytes_limit"),
        "memory_stats_keys": sorted(stats),
        "checks": {
            "platform_is_tpu": dev.platform == "tpu",
            "memory_stats_present": ("bytes_limit" in stats
                                     and "peak_bytes_in_use" in stats),
        },
    }


def phase_train(ctx):
    import jax
    import numpy as np
    from distributed_tensorflow_tpu import train
    from distributed_tensorflow_tpu.utils import native

    size = ctx.sizes["train"]
    ckpt_dir = os.path.join(ctx.out_dir, "train_ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    _, state, step, batch = _train_setup(
        ctx, _one_device_mesh(), batch=size["batch"], seq=size["seq"],
        use_flash="auto")
    try:
        final, losses, late = _run_steps(ctx, state, step, batch,
                                         size["steps"], ckpt_dir)
        saved = sorted(n for n in os.listdir(ckpt_dir)
                       if n.startswith("ckpt-"))
        # a new session over the same directory restores on entry
        restored = train.TrainSession(final, step, checkpoint_dir=ckpt_dir,
                                      sharded_checkpoint=True)
        same = jax.tree.map(
            lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
            restored.state, final)
        return {
            "batch": size["batch"], "seq": size["seq"],
            "loss_first": losses[0], "loss_last": losses[-1],
            "losses": losses, "compiles_after_first_step": late,
            "checkpoints": saved, "restored_step": restored.step,
            # False = the checkpoint checksums ran in pure Python, which
            # at full width costs minutes (no toolchain on this host?)
            "native_checksums": native.native_available(build=False),
            "checks": {
                "steps_taken": len(losses) == size["steps"],
                "loss_finite": bool(np.all(np.isfinite(losses))),
                "loss_decreased": losses[-1] < losses[0],
                "no_compile_after_first_step": late == 0,
                "one_checkpoint_written": len(saved) == 1,
                "restored_same_step": (restored.last_saved_step
                                       == size["steps"] == restored.step),
                "restored_same_values": all(jax.tree.leaves(same)),
            },
        }
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)   # ~1.5 GB at full width


def phase_train_long(ctx):
    """The same steps on the same batch and weights with use_flash="auto"
    (the only shape at which the kernel dispatches by itself) and with
    use_flash=False, the independent dense implementation."""
    import numpy as np
    size = ctx.sizes["train_long"]
    mesh = _one_device_mesh()
    runs = {}
    for use_flash in ("auto", False):
        _, state, step, batch = _train_setup(
            ctx, mesh, batch=size["batch"], seq=size["seq"],
            use_flash=use_flash)
        lower_args = (_abstract(state), _abstract(batch))
        _, losses, late = _run_steps(ctx, state, step, batch, size["steps"])
        runs[use_flash] = losses, late
        if use_flash == "auto":
            has_kernel = KERNEL_MARK in step.lower(
                *lower_args).compile().as_text()
        del state
        gc.collect()
    (losses, late), (dense, _) = runs["auto"], runs[False]
    return {
        "batch": size["batch"], "seq": size["seq"],
        "losses": losses, "losses_use_flash_false": dense,
        "first_loss_tol": FIRST_LOSS_TOL, "last_loss_tol": LAST_LOSS_TOL,
        "compiles_after_first_step": late,
        "checks": {
            "loss_finite": bool(np.all(np.isfinite(losses))),
            "first_loss_matches": abs(losses[0] - dense[0]) <= FIRST_LOSS_TOL,
            "last_loss_matches": abs(losses[-1] - dense[-1]) <= LAST_LOSS_TOL,
            "no_compile_after_first_step": late == 0,
            "flash_kernel_in_compiled_step": has_kernel,
        },
    }


# ------------------------------------------------------------------ four chips


def phase_dp4(ctx):
    """Data x fsdp training on four devices, and what it is compared with:
    the mean of the per-shard losses of the same batch and weights computed
    on ONE device of this process, a shard's worth of sequences at a time
    (the whole 24 x 2048 batch does not fit one chip: its f32 logits alone
    are ~9.9 GB)."""
    import jax
    import numpy as np
    from distributed_tensorflow_tpu import parallel
    from distributed_tensorflow_tpu.models.gpt import GPT

    size = ctx.sizes["dp4"]
    mesh = parallel.make_mesh({"data": 2, "fsdp": 2})
    shards = parallel.data_shards(mesh)
    params, state, step, batch = _train_setup(
        ctx, mesh, batch=size["batch"], seq=size["seq"], use_flash="auto")

    # reference first: step 1 donates the state that holds these weights
    dev0 = jax.devices()[0]
    ref_model = GPT(_gpt_config(ctx, size["seq"], use_flash=False))
    ref_loss = jax.jit(lambda p, b: ref_model.lm_loss_fn()(
        p, (), b, None, False)[0])
    tokens = np.asarray(batch["input_ids"])
    per = size["batch"] // shards
    shard_losses = [
        float(ref_loss(params, {"input_ids": jax.device_put(
            tokens[i * per:(i + 1) * per], dev0)}))
        for i in range(shards)]
    del params
    reference = float(np.mean(shard_losses))

    wq = state.params["decoder"]["attention"]["query"]["kernel"]
    placement = {
        "param_devices": len({s.device for s in wq.addressable_shards}),
        "param_shard_shape": list(wq.addressable_shards[0].data.shape),
        "param_shape": list(wq.shape),
        "batch_devices": len({s.device for s in
                              batch["input_ids"].addressable_shards}),
        "batch_shard_shape": list(
            batch["input_ids"].addressable_shards[0].data.shape),
    }
    lower_args = (_abstract(state), _abstract(batch))
    _, losses, late = _run_steps(ctx, state, step, batch, size["steps"])
    text = step.lower(*lower_args).compile().as_text()
    n = len(jax.devices())
    return {
        "batch": size["batch"], "seq": size["seq"], "mesh": dict(mesh.shape),
        "losses": losses, "shard_losses_one_device": shard_losses,
        "reference_loss": reference, "first_loss_tol": FIRST_LOSS_TOL,
        "compiles_after_first_step": late, **placement,
        "checks": {
            "loss_finite": bool(np.all(np.isfinite(losses))),
            "loss_decreased": losses[-1] < losses[0],
            "first_loss_matches_one_device": (abs(losses[0] - reference)
                                              <= FIRST_LOSS_TOL),
            "every_device_holds_a_param_shard": (
                placement["param_devices"] == n
                and math.prod(placement["param_shard_shape"])
                < math.prod(placement["param_shape"])),
            "every_device_holds_a_batch_shard": (
                placement["batch_devices"] == n
                and placement["batch_shard_shape"][0] == per),
            "all_reduce_in_compiled_step": "all-reduce" in text,
            "all_gather_in_compiled_step": "all-gather" in text,
            "flash_kernel_in_compiled_step": KERNEL_MARK in text,
        },
    }


# -------------------------------------------------------------------- serving


def _paged_read_errors(model, num_slots, max_len, page_size, window, seed):
    """The fused paged-attention kernel and the XLA gather read path, each
    against a float64 host softmax over the same pool, at the engine's own
    pool shape -> {"decode": (kernel_err, gather_err), "window": (...)}.
    The rule (scripts/validate_paged_tpu.py): the kernel's error must be no
    worse than 2x the gather path's — a fixed kernel-vs-gather tolerance
    would measure rounding order, not bugs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_tensorflow_tpu.ops.attention import (
        NEG_INF, dot_product_attention, padding_mask)
    from distributed_tensorflow_tpu.ops.pallas.paged_attention import (
        page_walk, paged_decode_attention, paged_window_attention)

    c = model.config
    pps = max_len // page_size
    num_pages = num_slots * pps + 1
    layer = c.num_layers - 1
    kk, kv, kq, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (c.num_layers, num_pages, page_size, c.kv_heads * c.head_dim)
    pool = {"k": jax.random.normal(kk, shape, c.dtype),
            "v": jax.random.normal(kv, shape, c.dtype)}
    rng = np.random.default_rng(seed)
    tab = jnp.asarray(rng.permutation(num_pages - 1)[:num_slots * pps]
                      .reshape(num_slots, pps) + 1, jnp.int32)
    lens = rng.integers(1, max_len + 1, num_slots)
    valid = jnp.asarray(np.arange(max_len)[None, :] < lens[:, None])

    def truth(q, k, v, addmask):
        q, k, v = (np.asarray(t.astype(jnp.float32), np.float64)
                   for t in (q, k, v))
        logits = (np.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(c.head_dim)
                  + np.asarray(addmask, np.float64))
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bhqk,bkhd->bqhd", p, v)

    def errors(o_kernel, o_gather, want):
        def err(o):
            return float(np.abs(np.asarray(o.astype(jnp.float32),
                                           np.float64) - want).max())
        return err(o_kernel), err(o_gather)

    out = {}
    q = jax.random.normal(kq, (num_slots, 1, c.num_heads, c.head_dim),
                          c.dtype)
    k_g, v_g = model._paged_layer_kv(pool, layer, tab)
    mask = padding_mask(valid)
    out["decode"] = errors(
        jax.jit(lambda q, pool, tab, lens: paged_decode_attention(
            q, pool, layer, page_walk(pool, tab, jnp.zeros_like(lens), lens)
        ))(q, pool, tab, jnp.asarray(lens, jnp.int32)),
        dot_product_attention(q, k_g, v_g, mask=mask),
        truth(q, k_g, v_g, mask))

    pos = max_len // 2
    qw = jax.random.normal(kw, (1, window, c.num_heads, c.head_dim), c.dtype)
    k_g, v_g = model._paged_layer_kv(pool, layer, tab[:1])
    wmask = jnp.where(jnp.arange(max_len)[None, None, None, :]
                      <= pos + jnp.arange(window)[None, None, :, None],
                      0.0, NEG_INF)
    out["window"] = errors(
        jax.jit(lambda q, pool, row, end: paged_window_attention(
            q, pool, layer, page_walk(pool, row, jnp.zeros_like(end), end)
        ))(qw, pool, tab[:1], jnp.asarray([pos + window], jnp.int32)),
        dot_product_attention(qw, k_g, v_g, mask=wmask),
        truth(qw, k_g, v_g, wmask))
    return out


def phase_serve(ctx):
    import jax
    import numpy as np
    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models.gpt import GPT

    size = ctx.sizes["serve"]
    new = size["new_tokens"]
    config = _gpt_config(ctx, size["max_len"], use_flash="auto")
    model = GPT(config)
    params = jax.jit(model.init)(jax.random.PRNGKey(ctx.seed))
    engine = serve.Engine(model, params, num_slots=size["num_slots"],
                          max_len=size["max_len"])
    sched = engine.scheduler

    rng = np.random.default_rng(ctx.seed)

    def ids(n):
        return rng.integers(0, config.vocab_size, n).astype(np.int32)

    # warm-up: one request longer than a prefill window compiles all three
    # hot executables (mid window, last window + admit, decode tick)
    engine.submit(ids(size["warm_len"]), new).result()
    warm_compiles = ctx.meter.compiles

    prefix = ids(size["prefix"])
    prompts = [ids(n) for n in size["prompt_lens"]]
    shared = [i for i, n in enumerate(size["prompt_lens"])
              if n > size["prefix"]][:2]
    for i in shared:
        prompts[i][:size["prefix"]] = prefix
    # two waves: the first sharer has published its prefix pages by the
    # time the second is admitted
    first, second = shared
    wave1 = [i for i in range(len(prompts)) if i != second]
    handles = {i: engine.submit(prompts[i], new) for i in wave1}
    engine.drain()
    handles[second] = engine.submit(prompts[second], new)
    engine.drain()
    late = ctx.meter.compiles - warm_compiles
    stats = engine.stats()
    streams = [handles[i].tokens for i in range(len(prompts))]

    # the three hot programs as the scheduler itself describes them; on a
    # TPU the paged kernel must be IN them (interpret mode and the gather
    # path both lower to plain HLO)
    kernel_in = {t.name: KERNEL_MARK in t.fn.lower(*t.args).as_text()
                 for t in sched.graph_targets()}

    errs = _paged_read_errors(model, size["num_slots"], size["max_len"],
                              sched.page_size, sched.prefill_chunk,
                              ctx.seed)

    # reported, not gated: greedy agreement with the lock-step generate()
    # reference (bf16 near-ties between random-weight logits may diverge)
    plen = max(size["prompt_lens"])
    padded = np.zeros((len(prompts), plen), np.int32)
    pvalid = np.zeros((len(prompts), plen), bool)
    for i, p in enumerate(prompts):
        padded[i, plen - len(p):] = p
        pvalid[i, plen - len(p):] = True
    ref = np.asarray(jax.jit(lambda p, ids, valid: model.generate(
        p, ids, new, prompt_valid=valid))(params, padded, pvalid))[:, plen:]
    agree = float(np.mean([np.mean(np.asarray(s[:new]) == ref[i, :len(s)])
                           for i, s in enumerate(streams)]))

    return {
        "num_slots": size["num_slots"], "max_len": size["max_len"],
        "page_size": sched.page_size, "requests": len(prompts),
        "prompt_lens": list(size["prompt_lens"]), "new_tokens": new,
        "use_paged_kernel": sched.use_paged_kernel,
        "prefix_hits": stats.prefix_hits_total,
        "compiles_after_warmup": late, "kernel_in_program": kernel_in,
        "paged_read_err_vs_f64": {k: {"kernel": a, "gather": b}
                                  for k, (a, b) in errs.items()},
        "greedy_share_equal_to_generate": agree,
        "checks": {
            "all_requests_ok": all(h.status == "ok"
                                   for h in handles.values()),
            "every_request_full_length": all(len(s) == new
                                             for s in streams),
            "tokens_in_vocab": all(0 <= t < config.vocab_size
                                   for s in streams for t in s),
            "paged_kernel_dispatched": sched.use_paged_kernel is True,
            "paged_kernel_in_programs": all(kernel_in.values()),
            "prefix_hit": stats.prefix_hits_total > 0,
            "no_compile_after_warmup": late == 0,
            # inverted form so a NaN error fails
            "kernel_no_worse_than_2x_gather": all(
                a <= max(2.0 * b, 2e-4) for a, b in errs.values()),
        },
    }


def pool_moves(text: str, pool_shape) -> list:
    """The instructions of a compiled program that MOVE the page pool or one
    whole layer of it: every ``copy`` / ``dynamic-slice`` /
    ``dynamic-update-slice`` (fused ones too) whose result has the pool's
    ``[L, num_pages, page_size, width]`` or a layer's ``[num_pages,
    page_size, width]`` in it.  A hot program writes a few rows and reads a
    few pages; the scatter that writes them is in place and is not a move."""
    layer = ",".join(str(int(d)) for d in pool_shape[1:])
    moved = re.compile(rf"\[(\d+,)?{layer}\]")
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = (\S+) ([\w-]+)\(", line)
        if m and m.group(3) in ("copy", "copy-start", "dynamic-slice",
                                "dynamic-update-slice") \
                and moved.search(m.group(2)):
            found.append(f"{m.group(1)} = {m.group(2)} {m.group(3)}")
    return found


def phase_serve_pool(ctx):
    import jax
    import jax.numpy as jnp
    from distributed_tensorflow_tpu import serve
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig

    size = ctx.sizes["serve_pool"]
    model = GPT(GPTConfig(**size["model"], max_position=size["max_len"],
                          dtype=jnp.bfloat16, dropout_rate=0.0))
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(key)))(
            jax.random.PRNGKey(ctx.seed))
    engine = serve.Engine(model, params, num_slots=size["num_slots"],
                          max_len=size["max_len"])
    sched = engine.scheduler
    stats = engine.stats()
    targets = sched.graph_targets()
    pool_shape = targets[0].args[1]["kv"]["k"].shape   # args: params, cache
    texts = {t.name: t.fn.lower(*t.args).compile().as_text()
             for t in targets}
    moves = {name: pool_moves(text, pool_shape)
             for name, text in texts.items()}
    return {
        "num_slots": size["num_slots"], "max_len": size["max_len"],
        "page_size": sched.page_size, "pool_shape": list(pool_shape),
        "kv_pool_bytes": stats.kv_pool_bytes,
        "kv_pool_tiled_bytes": stats.kv_pool_tiled_bytes,
        "use_paged_kernel": sched.use_paged_kernel,
        "pool_moves": moves,
        "checks": {
            "paged_kernel_in_programs": all(KERNEL_MARK in text
                                            for text in texts.values()),
            "pool_tiles_within_5_percent":
                stats.kv_pool_tiled_bytes <= 1.05 * stats.kv_pool_bytes,
            "no_pool_sized_moves": not any(moves.values()),
        },
    }


# ----------------------------------------------------------------------- main

PHASES = {
    "device": phase_device,
    "train": phase_train,
    "train_long": phase_train_long,
    "serve": phase_serve,
    "serve_pool": phase_serve_pool,
    "dp4": phase_dp4,
}
ONE_CHIP = ("device", "train", "train_long", "serve", "serve_pool")
FOUR_CHIPS = ("dp4",)


def run_phase(name: str, ctx: Ctx, out) -> bool:
    """Run one phase, print its JSON line, return whether it passed.  A phase
    that raises is a failed phase — recorded, never swallowed into a pass."""
    log(f"phase {name}: start")
    before = ctx.meter.snapshot()
    t0 = time.perf_counter()
    line = {"phase": name, "ok": False}
    try:
        extras = PHASES[name](ctx)
        line.update(extras)
        line["ok"] = bool(extras["checks"]) and all(extras["checks"].values())
    except Exception as e:   # noqa: BLE001 - the phase boundary: record, go on
        traceback.print_exc(file=sys.stderr)
        line["error"] = (f"{type(e).__name__}: {e}".strip().splitlines()
                         or [type(e).__name__])[-1][:400]
    seconds = time.perf_counter() - t0
    compiles, csecs, hits, misses = (
        b - a for a, b in zip(before, ctx.meter.snapshot()))
    line.update(seconds=round(seconds, 3), compile_seconds=round(csecs, 3),
                run_seconds=round(seconds - csecs, 3), compiles=compiles,
                cache_hits=hits, cache_misses=misses,
                peak_bytes_in_use=_peak_bytes())
    print(json.dumps(line), file=out, flush=True)
    failed = [k for k, v in line.get("checks", {}).items() if not v]
    log(f"phase {name}: {'ok' if line['ok'] else 'FAILED'} in "
        f"{seconds:.1f}s ({csecs:.1f}s compiling)"
        + (f"; failed checks: {failed}" if failed else "")
        + (f"; {line['error']}" if "error" in line else ""))
    gc.collect()
    return line["ok"]


def main(argv, out) -> int:
    """Run the phases, then write the contract's line to ``out`` as the last
    act.  Returns the exit code: 0 only for a TPU run with every phase ok."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-chip data x fsdp phase")
    parser.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"),
                        help="scratch directory (checkpoints); emptied of "
                             "what the run wrote")
    args = parser.parse_args(argv)

    from distributed_tensorflow_tpu.utils import enable_compile_cache
    cache_dir = enable_compile_cache()
    device = describe_device()
    log(f"device: {device}; compile cache: {cache_dir}")
    os.makedirs(args.out, exist_ok=True)
    with CompileMeter() as meter:
        ctx = Ctx(sizes=FULL if device["platform"] == "tpu" else TINY,
                  out_dir=args.out, meter=meter)
        names = FOUR_CHIPS if args.chips == 4 else ONE_CHIP
        passed = [run_phase(name, ctx, out) for name in names]
    ok = device["platform"] == "tpu" and all(passed)
    print(final_line(ok, device), file=out, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    _out = claim_stdout()
    _code = main(sys.argv[1:], _out)
    _out.close()          # the contract's line was the last thing written
    sys.exit(_code)
