"""Compile the main-path Pallas kernels for a TPU v5e that is described, not
attached (``jax.experimental.topologies``), at GPT-2-small widths.

Interpret mode — what every other kernel test here runs — partitions, tiles
and allocates happily where Mosaic refuses: a slice off the (8, 128) tiling,
too much VMEM, a kernel XLA cannot partition over a mesh.  These compiles
are what the chip's own compiler says, about two seconds each, with no chip
time.  Nothing executes, so they say nothing about results.

The code under test asks ``jax.default_backend()`` (which is the CPU here)
whether to interpret; the cases steer that by passing the kernels' own
``interpret=False`` argument, never through a new option of the program.
"""
import contextlib
import hashlib
import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# ops/pallas/__init__ re-exports the FUNCTION flash_attention over the
# module of the same name, so attribute access finds the function
flash_mod = importlib.import_module(
    "distributed_tensorflow_tpu.ops.pallas.flash_attention")
paged_mod = importlib.import_module(
    "distributed_tensorflow_tpu.ops.pallas.paged_attention")

KERNEL_MARK = "tpu_custom_call"

# GPT-2-small serving shapes, as chip_smoke.py's serve phase builds them
LAYERS, HEADS, HEAD_DIM = 12, 12, 64
SLOTS, MAX_LEN, WINDOW = 16, 1024, 32
# ... and its long-sequence training shape
FLASH_SHAPE = (6, 2048, HEADS, HEAD_DIM)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu / unknown topology name
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True, scope="module")
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: the next run would warn and
    compile again.  Off around this file."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _pool(page_size, kv_heads, quantized, sharding, slots=SLOTS):
    """serve/pages.py's layout: a token's heads one flat row."""
    shape = (LAYERS, slots * (MAX_LEN // page_size) + 1, page_size,
             kv_heads * HEAD_DIM)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sharding)
    if quantized:
        return {"k": sds(shape, jnp.int8), "v": sds(shape, jnp.int8),
                "k_scale": sds(shape[:-1] + (kv_heads,), jnp.float32),
                "v_scale": sds(shape[:-1] + (kv_heads,), jnp.float32)}
    return {"k": sds(shape, jnp.bfloat16), "v": sds(shape, jnp.bfloat16)}


def _paged_decode(page_size=16, heads=HEADS, kv_heads=None, quantized=False,
                  slots=SLOTS):
    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])
        sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
        fn = lambda q, pool, layer, tab, lo, hi: \
            paged_mod.paged_decode_attention(
                q, pool, layer, paged_mod.page_walk(pool, tab, lo, hi),
                interpret=False)
        return fn, (sds((slots, 1, heads, HEAD_DIM), jnp.bfloat16),
                    _pool(page_size, kv_heads or heads, quantized, one,
                          slots),
                    sds((), jnp.int32),
                    sds((slots, MAX_LEN // page_size), jnp.int32),
                    sds((slots,), jnp.int32), sds((slots,), jnp.int32))
    return build


def _paged_window(heads=HEADS, quantized=False):
    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])
        sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
        fn = lambda q, pool, layer, row, end: \
            paged_mod.paged_window_attention(
                q, pool, layer,
                paged_mod.page_walk(pool, row, jnp.zeros_like(end), end),
                interpret=False)
        return fn, (sds((1, WINDOW, heads, HEAD_DIM), jnp.bfloat16),
                    _pool(16, heads, quantized, one), sds((), jnp.int32),
                    sds((1, MAX_LEN // 16), jnp.int32), sds((1,), jnp.int32))
    return build


def _flash(grad):
    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])
        qkv = (jax.ShapeDtypeStruct(FLASH_SHAPE, jnp.bfloat16,
                                    sharding=one),) * 3
        fwd = lambda q, k, v: flash_mod.flash_attention(
            q, k, v, causal=True, interpret=False)
        if not grad:
            return fwd, qkv
        loss = lambda q, k, v: jnp.sum(fwd(q, k, v).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2)), qkv
    return build


def _flash_on_mesh(axes, kv_heads=HEADS):
    """Forward + backward of the kernel on operands sharded over a four-chip
    mesh: what a data-parallel long-sequence train step contains.  Called
    directly under ``jit`` this is refused ("Mosaic kernels cannot be
    automatically partitioned"); ``mesh=`` puts it under shard_map."""
    def build(topo):
        from distributed_tensorflow_tpu import parallel
        mesh = parallel.make_mesh(axes, devices=topo.devices)
        batch = tuple(a for a in ("data", "fsdp") if a in axes)
        spec = P(batch, None, "tensor" if "tensor" in axes else None, None)
        sds = lambda heads: jax.ShapeDtypeStruct(
            (24, 2048, heads, HEAD_DIM), jnp.bfloat16,
            sharding=NamedSharding(mesh, spec))
        loss = lambda q, k, v: jnp.sum(flash_mod.flash_attention(
            q, k, v, causal=True, interpret=False, mesh=mesh
        ).astype(jnp.float32))
        return (jax.value_and_grad(loss, argnums=(0, 1, 2)),
                (sds(HEADS), sds(kv_heads), sds(kv_heads)))
    return build


CASES = {
    "paged_decode_bf16_page16": _paged_decode(),
    "paged_decode_bf16_page128": _paged_decode(page_size=128),
    "paged_decode_int8_planes": _paged_decode(quantized=True),
    "paged_decode_gqa4": _paged_decode(kv_heads=4),
    # GPT-2-XL's row: 25 heads x 64 = 1600 lanes, twelve and a half tiles
    "paged_decode_xl_heads25": _paged_decode(heads=25, slots=8),
    "paged_decode_xl_heads25_int8": _paged_decode(heads=25, slots=8,
                                                  quantized=True),
    "paged_window_32": _paged_window(),
    "paged_window_32_xl_heads25": _paged_window(heads=25),
    "paged_window_32_int8_planes": _paged_window(quantized=True),
    "flash_forward": _flash(grad=False),
    "flash_backward": _flash(grad=True),
    "flash_mesh_data4": _flash_on_mesh({"data": 4}),
    "flash_mesh_data2_fsdp2": _flash_on_mesh({"data": 2, "fsdp": 2}),
    "flash_mesh_data2_tensor2_gqa4": _flash_on_mesh(
        {"data": 2, "tensor": 2}, kv_heads=4),
}


# every pallas_call carries a stable ``name=``: it names the kernel's
# instruction in the compiled program (``%dttpu_paged_decode.1 = ...
# custom-call``), which is the name its events carry in a device trace
KERNEL_NAMES = {
    "paged_decode": ("dttpu_paged_decode",),
    "paged_window": ("dttpu_paged_window",),
    "flash_forward": ("dttpu_flash_fwd",),
    "flash_backward": ("dttpu_flash_fwd", "dttpu_flash_dkv",
                       "dttpu_flash_dq"),
    "flash_mesh": ("dttpu_flash_fwd", "dttpu_flash_dkv", "dttpu_flash_dq"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(topo, case):
    fn, args = CASES[case](topo)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert KERNEL_MARK in text, f"{case}: no Mosaic kernel in the program"
    names = next(v for k, v in KERNEL_NAMES.items() if case.startswith(k))
    for name in names:     # under grad: %jvp_<name>_.1, %transpose_jvp_<name>__.1
        assert re.search(rf"%\S*{name}\S* = ", text), \
            f"{case}: no instruction named after {name}"


# GPT-2-small widths, the benchmark's four-chip train deployment in small
FSDP_BATCH, FSDP_SEQ = 8, 1024


def test_fsdp_train_step_is_zero3_on_v5e(topo):
    """The fsdp train step (``shard_train_state`` placements, batch over
    ``("data", "fsdp")``, plain-jit ``make_custom_train_step``) compiled for
    the described four chips: weights are all-gathered at use, no all-reduce
    carries a whole-batch activation, attention runs at the local batch."""
    from hlo_collectives import activation_allreduces, collectives

    from distributed_tensorflow_tpu import optim, parallel, train
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig

    mesh = parallel.make_mesh({"data": 1, "fsdp": 4}, devices=topo.devices)
    model = GPT(GPTConfig(max_position=FSDP_SEQ, dropout_rate=0.0,
                          dtype=jnp.bfloat16, remat=True), mesh=mesh)
    optimizer = optim.adamw(1e-4)

    def make_state(key):
        p = model.init(key)
        return train.TrainState.create(p, optimizer.init(p))

    abstract = jax.eval_shape(make_state, jax.random.PRNGKey(0))
    param_sh = model.partition_rules(fsdp=True).tree_shardings(
        mesh, abstract.params)
    replicated = NamedSharding(mesh, P())
    moments = {k: param_sh for k in abstract.opt_state.inner}
    shardings = jax.tree.map(lambda _: replicated, abstract)._replace(
        params=param_sh, opt_state=abstract.opt_state._replace(
            count=replicated, inner=moments))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (FSDP_BATCH, FSDP_SEQ + 1), jnp.int32,
        sharding=NamedSharding(mesh, P(("data", "fsdp"))))}
    step = train.make_custom_train_step(model.lm_loss_fn(), optimizer,
                                        grad_clip_norm=1.0)
    text = step.lower(state, batch).compile().as_text()

    assert not activation_allreduces(text, FSDP_BATCH, FSDP_SEQ)
    gathered = [s for op, shapes in collectives(text) if op == "all-gather"
                for s in shapes]
    assert any(s[-2:] == (768, 3072) for s in gathered), gathered  # w_in
    local = FSDP_BATCH // 4
    assert re.search(rf"\[{local},{HEADS},{FSDP_SEQ},{FSDP_SEQ}\]", text)
    assert not re.search(
        rf"\[{FSDP_BATCH},{HEADS},{FSDP_SEQ},{FSDP_SEQ}\]", text)


def _scheduler_from_shapes(model, params, **kw):
    """A ``SlotScheduler`` whose cache and snapshot arrays are shapes, not
    gigabytes of zeros: it only ever asks them for shape and dtype here."""
    from distributed_tensorflow_tpu.serve import pages as pages_lib
    from distributed_tensorflow_tpu.serve.scheduler import SlotScheduler
    real = pages_lib.init_paged_cache, pages_lib.init_state_snapshots
    try:
        pages_lib.init_paged_cache = lambda *a: jax.eval_shape(
            lambda: real[0](*a))
        pages_lib.init_state_snapshots = lambda *a: jax.eval_shape(
            lambda: real[1](*a))
        return SlotScheduler(model, params, **kw)
    finally:
        pages_lib.init_paged_cache, pages_lib.init_state_snapshots = real


def _on_chip(one, tree):
    """``tree``'s leaves as shapes on the described chip ``one``; a leaf
    that names its device keeps its format (the weights as a scheduler
    built on the chip placed them: what its programs are dispatched on)."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        tuple(x.shape), x.dtype,
        sharding=one if getattr(x, "sharding", None) is None else x.format),
        tree)


_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1}


def _weight_copies(text, least=1 << 20):
    """``[(parameter, shape, bytes)]``: the compiled program's copies of a
    weight leaf (an entry parameter ``params__...``) of ``least`` bytes or
    more — a layout change the program pays for in every dispatch."""
    found = []
    for m in re.finditer(
            r"= (\w+)\[([\d,]*)\]\S* copy\(%?(params__[\w.]+)\)", text):
        size = _BYTES[m.group(1)]
        for d in filter(None, m.group(2).split(",")):
            size *= int(d)
        if size >= least:
            found.append((m.group(3), m.group(2), size))
    return found


def test_gpt2_xl_serving_programs_move_no_pool_sized_copy_on_v5e(one_chip):
    """The three hot programs of the GPT-2-XL serving cell (8 slots x 1024,
    25 heads x 64, the Mosaic kernel on) compiled for the described chip
    from shapes alone.  With ``[.., 25, 64]`` minor dimensions every one of
    them re-laid out the whole 2.5 GB pool on entry and exit and sliced a
    layer out and back per write (48 % of the cell's device time, ledger
    PR 30); with flat rows the compiled text holds no copy or slice the size
    of the pool or of a layer of it, the pool is updated in place, and the
    temporaries are far under a second pool."""
    from chip_smoke import pool_moves

    sched = _xl_serving_scheduler(one_chip)
    pool = sched._cache["kv"]["k"]
    assert pool.shape == (48, 8 * (MAX_LEN // 16) + 1, 16, 1600)
    with _mosaic():
        compiled = {t.name: t.fn.lower(*_on_chip(one_chip, t.args)).compile()
                    for t in sched.graph_targets()}
    pool_gb = 2 * pool.size * 2 / 1e9
    for name, program in compiled.items():
        text, memory = program.as_text(), program.memory_analysis()
        assert KERNEL_MARK in text, name
        assert pool_moves(text, pool.shape) == [], name
        assert memory.alias_size_in_bytes > pool_gb * 1e9, (name, memory)
        assert memory.temp_size_in_bytes < 0.5 * pool_gb * 1e9, (name,
                                                                 memory)


@contextlib.contextmanager
def _mosaic():
    """The paged kernels lowered for Mosaic, as on the chip: the code asks
    the backend, which is the CPU here."""
    real = paged_mod.use_interpret
    paged_mod.use_interpret = lambda: False
    try:
        yield
    finally:
        paged_mod.use_interpret = real


def _xl_serving_scheduler(one):
    """The GPT-2-XL serving cell's scheduler over bf16 weights placed on
    the described chip ``one``: built there, it compiles its decode tick
    and puts the weights in the formats that program reads, as on the
    chip."""
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig(vocab_size=50257, hidden_size=1600, num_layers=48,
                          num_heads=25, intermediate_size=6400,
                          max_position=MAX_LEN, dtype=jnp.bfloat16,
                          dropout_rate=0.0))
    params = _on_chip(one, jax.eval_shape(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(key)),
        jax.random.PRNGKey(0)))
    with _mosaic():
        return _scheduler_from_shapes(model, params, num_slots=8,
                                      max_len=MAX_LEN, use_paged_kernel=True)


def _lowered_for(one, sched):
    """{(program, rows): lowered} of a scheduler's hot programs, on the
    weights as it placed them — the two window programs at EVERY rung of
    its ladder, the decode tick (rows 0) — the Mosaic kernel in."""
    targets = {t.name: t for t in sched.graph_targets()}
    with _mosaic():
        tick = targets["decode_tick"]
        out = {("decode_tick", 0): tick.fn.lower(*_on_chip(one, tick.args))}
        for name, fns in (("prefill_window", sched._win_mid),
                          ("admit", sched._last_admit)):
            for rows, fn in fns.items():
                args = list(targets[name].args)
                # windows, page rows, what the rows are told[, adapter rows]
                args[2:5] = sched._padding_rows(rows)[:3]
                out[name, rows] = fn.lower(*_on_chip(one, tuple(args)))
        return out


# the lowered text of the three GPT-2-XL hot programs at commit 65f26fc
# (one page a grid step, the whole table walked), in bytes
XL_PARENT_TEXT = {"prefill_window": 67_976, "admit": 96_053,
                  "decode_tick": 93_354}
# the window programs' row counts at 8 slots
XL_RUNGS = (1, 4, 8)


def test_gpt2_xl_serving_programs_stay_cheap_to_set_up(one_chip):
    """What a run pays for the hot programs before its window opens, in
    every run, whatever the compile cache holds: the decode tick lowers to
    one program and each of the two window callables to one program a rung,
    at most three rungs (the ladder is the scheduler's, from its slots: the
    count of shapes it traces, lowers and compiles at construction is
    pinned here), every one with ONE kernel instance (no variant a length
    or a bucket of pages, no branch between instances), whose text — at
    EVERY rung — stays within 1.5 x of what the program's was with one page
    a grid step and one window a program (a kernel body unrolled over pages
    x lane blocks is seconds of tracing in every run: PR 33 was refused for
    them); the text says nothing of the process that made it, so a second
    scheduler's programs — and a second run's — are the first's byte for
    byte and the persistent cache serves them; and none holds a host
    callback, whose pointer the cache key would carry."""
    sched = _xl_serving_scheduler(one_chip)
    assert sched._rungs == XL_RUNGS and len(sched._rungs) <= 3
    first = {k: v.as_text() for k, v in _lowered_for(one_chip, sched).items()}
    second = {k: v.as_text() for k, v in _lowered_for(
        one_chip, _xl_serving_scheduler(one_chip)).items()}
    assert sorted(first) == sorted(
        [("decode_tick", 0)] + [(name, rows) for rows in XL_RUNGS
                                for name in ("prefill_window", "admit")])
    for (name, rows), text in first.items():
        assert text.count(KERNEL_MARK) == 1, (name, rows)
        assert "stablehlo.case" not in text, (name, rows)
        assert "callback" not in text, (name, rows)
        assert len(text) < 1.5 * XL_PARENT_TEXT[name], (name, rows,
                                                        len(text))
        assert text == second[name, rows], (name, rows)


def test_gpt2_xl_serving_programs_copy_no_weight_on_v5e(one_chip):
    """No hot program of the GPT-2-XL serving cell re-lays a weight out in
    every dispatch.  On the weights in the formats the chip gives such
    arrays the decode program copied, at its entry, the whole stack of FFN
    out-projections (``bf16[48,6400,1600]``, 0.98 GB: the chip's format
    puts 6400 minor, the program read 1600 minor; 3.2 ms of a ~49 ms tick,
    ledger PR 39) and the word embedding (161 MB), and each window program
    copied the embedding again.  The scheduler compiles its decode tick
    with the weights' formats left to the compiler and puts each weight the
    program wants in another format there, once; then the decode tick and
    both window programs, at every rung, hold no copy of a weight leaf of
    1 MB or more, the decode tick's temporaries fall by 0.9 GB or more,
    every program reads each weight in the format it was placed in, and
    what ``graph_targets()`` lowers is the program that is dispatched."""
    from distributed_tensorflow_tpu.serve.scheduler import _layout_of

    sched = _xl_serving_scheduler(one_chip)
    assert "['embeddings']['word']" in {name for name, *_ in sched._relaid}
    assert sched.stats().weights_relaid_bytes == sum(
        size for *_, size in sched._relaid)
    placed = [_layout_of(x) for x in jax.tree.leaves(sched.params)]
    tick = sched._tick._compiled                     # what is dispatched
    # what the benchmark's analysis compiles: the target as it stands
    decode = {t.name: t for t in sched.graph_targets()}["decode_tick"]
    with _mosaic():
        assert decode.fn.lower(*decode.args).compile().as_text() \
            == tick.as_text()
        # the same program on the weights in the formats they came in
        before = jax.jit(sched._tick._jitted.__wrapped__,
                         donate_argnums=(1, 3, 4, 5, 6)).lower(
            *_on_chip(one_chip, decode.args)).compile()
    copied = [name for name, *_ in _weight_copies(before.as_text())]
    assert any("w_out" in name for name in copied), copied
    assert any("word" in name for name in copied), copied
    assert (tick.memory_analysis().temp_size_in_bytes
            < before.memory_analysis().temp_size_in_bytes - 0.9e9)
    programs = {key: program.compile()
                for key, program in _lowered_for(one_chip, sched).items()
                if key[0] != "decode_tick"}
    programs["decode_tick", 0] = tick
    for key, program in programs.items():
        text = program.as_text()
        assert KERNEL_MARK in text, key
        assert _weight_copies(text) == [], key
        reads = [f.layout for f in jax.tree.leaves(program.input_formats[0][0])]
        assert len(reads) == len(placed), key
        # a window program does not read every weight (the final norm's
        # are a mid window's dead code): those it leaves out have no format
        assert all(layout == own for layout, own in zip(reads, placed)
                   if layout is not None), key
        assert key[0] != "decode_tick" or None not in reads, key


# (PR 37: the two window programs hold a batch of windows, here 8; the
# decode tick and the two copies are what they were.  PR 40: the decode
# tick leaves the weights' formats to the compiler, so its entry marks
# each weight parameter ``mhlo.layout_mode = "auto"``; nothing else in its
# text moved, and the windows' and the copies' texts are what they were)
HYBRID_TEXT_SHA256 = {"prefill_window": "bbeac5bb008b80fb", "admit": "0ce9bc7e9a648e55",
                      "decode_tick": "695251f020be7c28", "state_snapshot": "8ba7368593677a89",
                      "state_restore": "d0c9800092c0a6ae"}


def _fits_beside(in_use: float, memory, name: str) -> None:
    """A program's temporaries beside what the deployment holds on the chip
    (bytes in use as the benchmark's runs read them): under 90 % of the
    chip's 15.75 GB, the share the serving cells are held to."""
    assert in_use + memory.temp_size_in_bytes < 0.9 * 15.75e9, (name, memory)


# bytes in use in the two agent cells (weights, pool, slot state, snapshot
# rows; PERF.md section 5)
GRANITE_IN_USE, LONGCAT_IN_USE = 12.99e9, 13.06e9
# the weights a scheduler holds a second time, in the formats its decode
# program reads (the caller keeps its own): a sliver beside what these
# deployments hold (0 / 0.134 / 0.092 GB here, rehearsal; GPT-2-XL's
# 0.165 GB is its embedding)
RELAID_AT_MOST = 0.2e9


def test_state_space_serving_programs_fit_a_v5e_at_published_width(topo):
    """The three hot programs and the two state-snapshot copies of the
    decoder of state-space and attention layers, at the benchmark's
    published widths and deployment (32 slots x 4096 tokens, 40 snapshot
    rows), compiled for the described chip from shapes alone — the two
    window programs at the LARGEST rung of the ladder, 8 windows a program:
    every donated buffer is aliased (the 2.4 GB of recurrent state, which
    the windows now gather from and scatter into by slot, and the page pool
    are updated in place), no program holds a copy the size of the state or
    of the pool, and the temporaries stay small — 0.33 GB for 8 windows
    where one took 0.01 — and fit beside the 12.99 GB the deployment
    holds.  A matrix whose width
    is no multiple of a lane tile (the fused in-projection's 8512) cost the
    decode program a 1.25 GB re-laid-out copy of the weights on every
    dispatch (rehearsal, PR 30): this is the test that sees it."""
    import json
    from chip_smoke import pool_moves
    from distributed_tensorflow_tpu.models.hybrid import (HybridConfig,
                                                          HybridDecoder)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        c = json.load(f)
    model = HybridDecoder(HybridConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        layer_types=tuple(c["layer_types"]),
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        intermediate_size=c["shared_intermediate_size"],
        ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
        ssm_state=c["mamba_d_state"], conv_width=c["mamba_d_conv"],
        max_position=c["serve"]["max_len"], param_dtype=jnp.bfloat16))
    one = SingleDeviceSharding(topo.devices[0])
    params = _on_chip(one, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    sched = _scheduler_from_shapes(model, params,      # 6.6 GB of shapes
                                   num_slots=c["serve"]["num_slots"],
                                   max_len=c["serve"]["max_len"])
    assert sched.stats().weights_relaid_bytes < RELAID_AT_MOST
    state_gb = 0.0
    targets = sched.graph_targets()
    assert targets[0].args[2].shape == (8, 32)       # 8 windows of 32
    for target in targets:
        lowered = target.fn.lower(*_on_chip(one, target.args))
        # the programs this decoder has lowered to since PR 32: a change to
        # the tier it shares with GPT-2 (the scheduler, ``decode_paged_step``,
        # the page kernels) that is not meant for it leaves their text as it
        # was; one that is meant for it brings new digests
        assert hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16] \
            == HYBRID_TEXT_SHA256[target.name], target.name
        compiled = lowered.compile()
        memory = compiled.memory_analysis()
        text = compiled.as_text()
        assert KERNEL_MARK not in text, target.name
        assert _weight_copies(text) == [], target.name
        assert memory.temp_size_in_bytes < 0.5e9, (target.name, memory)
        _fits_beside(GRANITE_IN_USE, memory, target.name)
        if target.name in ("prefill_window", "admit"):
            # 8 rows gathered from and scattered into the 2.4 GB of
            # recurrent state by slot, 8 x 32 rows written to the pool:
            # neither is moved whole (the convolution's inputs, 30 MB with
            # rows of 3, are re-laid out on entry and exit: 0.15 ms)
            for leaf in (sched._cache["kv"]["k"],
                         sched._cache["state"]["ssm"]):
                assert pool_moves(text, leaf.shape) == [], target.name
        # what is donated comes back in place: the slot cache (3.5 GB) for
        # the three and the restore, cache + snapshots (6.6 GB) for the
        # snapshot copy
        assert memory.alias_size_in_bytes > 3.5e9, (target.name, memory)
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                < 15.75e9), (target.name, memory)
        state_gb = max(state_gb, memory.alias_size_in_bytes / 1e9)
    assert 6.5 < state_gb < 6.7


def test_latent_attention_expert_programs_fit_a_v5e_at_published_width(topo):
    """The three hot programs of the decoder of shortcut-connected expert
    layers over latent attention, at the benchmark's published widths and
    deployment (64 slots x 4096 tokens, 16 of 512 experts a layer), compiled
    for the described chip from shapes alone.  The pool's one leaf is 640
    lanes wide — a latent, the shared rotary key and zeros to a whole lane
    tile: with a 64-lane or a 576-lane leaf the compiler gave the pool a
    layout of its own and every program re-laid the WHOLE pool out on entry,
    between sublayers and on exit (2.7 GB of temporaries in the rehearsal,
    8 % of the device's time on the chip, PR 36); this is the test that sees
    it.  An expert's weights are sliced out of their bank inside the branch
    that runs it, fused into the matmul: no program holds a copy of an
    expert or of a bank.  Router statistics leave with the programs'
    outputs: no host callback."""
    import json
    from chip_smoke import pool_moves
    from distributed_tensorflow_tpu.models.longcat_flash import (
        LongcatFlash, LongcatFlashConfig)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "longcat-flash-chat.json")) as f:
        c = json.load(f)
    model = LongcatFlash(LongcatFlashConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        ffn_hidden_size=c["ffn_hidden_size"],
        expert_ffn_hidden_size=c["expert_ffn_hidden_size"],
        num_layers=c["num_layers"],
        num_attention_heads=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        n_routed_experts_published=c["cut"]["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"],
        zero_expert_num=c["zero_expert_num"], moe_topk=c["moe_topk"],
        routed_scaling_factor=c["routed_scaling_factor"],
        rope_theta=c["rope_theta"], max_position=c["serve"]["max_len"],
        param_dtype=jnp.bfloat16))
    one = SingleDeviceSharding(topo.devices[0])
    params = _on_chip(one, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    weights_gb = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(params)) / 1e9
    assert 10.3 < weights_gb < 10.4                 # the issue's 10.35 GB
    sched = _scheduler_from_shapes(model, params,
                                   num_slots=c["serve"]["num_slots"],
                                   max_len=c["serve"]["max_len"])
    assert sched.stats().weights_relaid_bytes < RELAID_AT_MOST
    pool = sched._cache["kv"]["latent_key"]
    assert pool.shape == (8, 64 * 256 + 1, 16, 640)
    assert sched._kv_pool_bytes[0] == sched._kv_pool_bytes[1]
    targets = sched.graph_targets()
    assert [t.name for t in targets] == ["prefill_window", "admit",
                                         "decode_tick"]
    assert targets[0].args[2].shape == (8, 32)       # 8 windows of 32
    pool_gb = pool.size * 2 / 1e9
    for target in targets:
        lowered = target.fn.lower(*_on_chip(one, target.args))
        assert "callback" not in lowered.as_text(), target.name
        compiled = lowered.compile()
        text, memory = compiled.as_text(), compiled.memory_analysis()
        assert KERNEL_MARK not in text, target.name
        assert _weight_copies(text) == [], target.name
        assert pool_moves(text, pool.shape) == [], target.name
        # no copy of an expert's matrices or of a bank of them
        assert not re.search(
            r"= bf16\[(16,)?(6144,4096|2048,6144)\]\S* copy\(", text), \
            target.name
        assert memory.alias_size_in_bytes > pool_gb * 1e9, (target.name,
                                                            memory)
        # 8 windows' float32 scores ``[8, 64, 32, 4096]`` are 0.27 GB of
        # the window programs' 0.62 (the decode program's 0.50 was the
        # largest before): R = 8 fits, beside the 13.06 GB in use
        assert memory.temp_size_in_bytes < 0.7e9, (target.name, memory)
        _fits_beside(LONGCAT_IN_USE, memory, target.name)
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                < 15.75e9), (target.name, memory)


# what the decoder-hybrid-decoder's deployment holds on the chip (weights
# 7.71 GB, layer 17's pool 1.34, 32 slot blocks 0.82, 40 snapshot rows 1.02)
# and the gathered view of its shared plane beside them: the configuration's
# 12.2 GB
SAMBAY_IN_USE = 12.2e9


def test_decoder_hybrid_decoder_programs_fit_a_v5e_at_published_width(topo):
    """The three hot programs and the two state copies of the SambaY
    decoder-hybrid-decoder at the benchmark's published widths and
    deployment (32 slots x 8192 tokens), compiled for the described chip
    from shapes alone.  The window layers' K/V live in rings of 544 rows in
    the slot block and nowhere else: the pool has ONE plane, layer 17's.
    The view of that plane is gathered once a program — a K and a V gather
    in the decode tick and in the admitting windows, read by layer 17 and
    the seven cross layers alike — and not at all in the mid windows, whose
    outputs no layer past 17 reaches (the compiler drops those layers).  No
    program copies the pool, a state leaf or a ring, the decode tick's
    single queries read the 1.34 GB view as it lies (the admitting windows'
    blocks of queries re-lay their 8-row view out once, 0.34 GB), and the
    temporaries fit beside what the deployment holds."""
    import json
    from chip_smoke import pool_moves
    from distributed_tensorflow_tpu.models.sambay import SambaY, SambaYConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        c = json.load(f)
    a = c["assumed"]
    model = SambaY(SambaYConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        intermediate_size=c["intermediate_size"],
        sliding_window=c["sliding_window"], d_state=a["mamba_d_state"],
        d_conv=a["mamba_d_conv"], expand=a["mamba_expand"],
        dt_rank=a["mamba_dt_rank"], max_position=c["serve"]["max_len"],
        param_dtype=jnp.bfloat16))
    one = SingleDeviceSharding(topo.devices[0])
    params = _on_chip(one, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    weights_gb = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(params)) / 1e9
    assert 7.70 < weights_gb < 7.71                  # 3,852,562,944 x 2 B
    sched = _scheduler_from_shapes(model, params,
                                   num_slots=c["serve"]["num_slots"],
                                   max_len=c["serve"]["max_len"])
    assert sched.stats().weights_relaid_bytes < RELAID_AT_MOST
    cache = sched._cache
    pool = cache["kv"]["k"]
    assert pool.shape == (1, 32 * 512 + 1, 16, 1280)
    assert {k: v.shape for k, v in cache["state"].items()} == {
        "ssm": (9, 32, 16, 5120), "conv": (9, 32, 3, 5120),
        "window_k": (8, 32, 544, 1280), "window_v": (8, 32, 544, 1280)}
    assert sched._snaps["window_k"].shape == (8, 40, 544, 1280)
    targets = sched.graph_targets()
    assert [t.name for t in targets] == ["prefill_window", "admit",
                                         "decode_tick", "state_snapshot",
                                         "state_restore"]
    assert targets[0].args[2].shape == (8, 32)       # 8 windows of 32
    view = {"prefill_window": 8, "admit": 8, "decode_tick": 32}
    for target in targets:
        compiled = target.fn.lower(*_on_chip(one, target.args)).compile()
        text, memory = compiled.as_text(), compiled.memory_analysis()
        assert KERNEL_MARK not in text, target.name
        assert _weight_copies(text) == [], target.name
        copied = [line for line in text.splitlines() if re.search(
            r"= bf16\[(1,16385,16|8,32,544|32,544|32,8192),1280\]\S* "
            r"copy\(|= f32\[9,32,16,5120\]\S* copy\(", line)]
        assert copied == [], (target.name, copied[:2])
        if target.name in view:
            gathers = re.findall(
                rf"= bf16\[{view[target.name]},512,16,1280\]\S* gather\(",
                text)
            mid = target.name == "prefill_window"
            assert len(gathers) == (0 if mid else 2), target.name
            # the device scopes a trace names the work by; the mid windows
            # hold no shared read and no memory unit
            for scope in ("ssm_selective_scan", "diff_attention",
                          "window_attention", "shared_attention", "gmu"):
                assert (scope in text) != (
                    mid and scope in ("shared_attention", "gmu")), (
                    target.name, scope)
            for leaf in (pool, cache["state"]["window_k"]):
                moves = pool_moves(text, leaf.shape)
                assert target.name == "decode_tick" or moves == [], (
                    target.name, moves)
        assert memory.temp_size_in_bytes < 1.6e9, (target.name, memory)
        _fits_beside(SAMBAY_IN_USE, memory, target.name)
        assert memory.alias_size_in_bytes > 2.1e9, (target.name, memory)
