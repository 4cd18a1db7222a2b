"""Hardware validation + crossover measurement for the paged-attention kernel.

Run ON A REAL TPU (no --device flag).  Two phases, mirroring
validate_flash_tpu.py:

1. **Correctness**: the fused page-walk kernel compiled by Mosaic (NOT
   interpret mode — interpret has hidden tiling violations before,
   docs/PERF.md) vs the XLA gather read path, at decode and
   prefill-window shapes covering GQA and int8 scale planes.  The gate
   is self-calibrating against a float64 HOST ground truth: the
   kernel's max-abs error must be no worse than 2x the gather path's
   own error (or inside the strict floor) — a fixed kernel-vs-gather
   tolerance would measure rounding-order noise, not bugs.
2. **Crossover**: decode-shaped timing (value-fetch closed, one scan
   dispatch) of the kernel vs gather+dense attention over a view_len
   sweep — the numbers behind ``_PAGED_KERNEL_MIN_VIEW``
   (ops/attention.py paged_kernel_wins), or that demote the kernel.

Prints one JSON line per measurement; paste results into docs/PERF.md.
Exit codes: 0 ok, 1 parity failure, 2 not a TPU.
"""
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    # --device=cpu: a smoke run of the harness itself; the real validation
    # runs with no flag.
    for arg in sys.argv[1:]:
        if arg.startswith("--device="):
            import jax
            jax.config.update("jax_platforms", arg.split("=", 1)[1])
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu.ops.attention import (
        dot_product_attention, padding_mask)
    from distributed_tensorflow_tpu.ops.pallas.paged_attention import (
        page_walk, paged_decode_attention, paged_window_attention)

    from flash_timing import require_tpu
    if not require_tpu():
        return 2

    rng = np.random.default_rng(20260805)

    def make_pool(L, NP, PG, kvh, hd, quantized):
        """The pool layout of serve/pages.py: a token's heads one flat
        row, scale planes one scale a head."""
        shape = (L, NP, PG, kvh * hd)
        if quantized:
            planes = (L, NP, PG, kvh)
            return {
                "k": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                "v": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                "k_scale": jnp.asarray(
                    rng.uniform(0.01, 0.05, planes), jnp.float32),
                "v_scale": jnp.asarray(
                    rng.uniform(0.01, 0.05, planes), jnp.float32),
            }
        return {"k": jnp.asarray(rng.standard_normal(shape), jnp.float32),
                "v": jnp.asarray(rng.standard_normal(shape), jnp.float32)}

    def gather(pool, layer, tab, PG):
        """The XLA gather read path at script scale: the rows' pages,
        their flat rows split into heads."""
        view = tab.shape[-1] * PG
        kvh = pool["k"].shape[-1] // hd
        def g(leaf):
            out = leaf[layer][tab.reshape(-1)]
            return out.reshape(tab.shape[0], view, kvh, -1)
        k, v = g(pool["k"]), g(pool["v"])
        if "k_scale" in pool:
            k = k.astype(jnp.float32) * g(pool["k_scale"])
            v = v.astype(jnp.float32) * g(pool["v_scale"])
        return k, v

    def gt_attention(q, k, v, addmask):
        """float64 host softmax attention (GQA by repeat)."""
        q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
        group = q.shape[2] // k.shape[2]
        if group > 1:
            k = np.repeat(k, group, axis=2)
            v = np.repeat(v, group, axis=2)
        scale = 1.0 / math.sqrt(q.shape[-1])
        logits = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
        logits = logits + np.asarray(addmask, np.float64)
        m = logits.max(-1, keepdims=True)
        p = np.exp(logits - m)
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bhqk,bkhd->bqhd", p, v)

    # ---- phase 1: compiled-kernel parity --------------------------------
    failures = 0
    cases = [
        ("decode_f32", dict(kvh=8, h=8, quantized=False)),
        # 5 x 64 = 320 lanes: a row that is no multiple of a lane tile
        ("decode_odd_width", dict(kvh=5, h=5, quantized=False)),
        ("decode_gqa", dict(kvh=2, h=8, quantized=False)),
        ("decode_int8", dict(kvh=2, h=8, quantized=True)),
    ]
    L, NP, PG, P, S, hd = 2, 40, 16, 4, 4, 64
    view = P * PG
    for name, ckw in cases:
        pool = make_pool(L, NP, PG, ckw["kvh"], hd, ckw["quantized"])
        tab = jnp.asarray(rng.choice(NP, (S, P), replace=False), jnp.int32)
        # a ragged column run a slot, the first from inside a page
        lo = jnp.asarray(rng.integers(0, view // 2, S), jnp.int32)
        hi = lo + 1 + jnp.asarray(rng.integers(0, view // 2, S), jnp.int32)
        cols = jnp.arange(view)[None, :]
        valid = (cols >= lo[:, None]) & (cols < hi[:, None])
        q = jnp.asarray(rng.standard_normal((S, 1, ckw["h"], hd)),
                        jnp.float32)
        try:
            o_kern = jax.jit(lambda q, pool, tab, lo, hi: paged_decode_attention(  # dtlint: disable=DT105
                q, pool, 1, page_walk(pool, tab, lo, hi),
                interpret=False))(q, pool, tab, lo, hi)
            k_g, v_g = gather(pool, 1, tab, PG)
            o_xla = dot_product_attention(q, k_g.astype(q.dtype),
                                          v_g.astype(q.dtype),
                                          mask=padding_mask(valid))
            gt = gt_attention(q, np.asarray(k_g, np.float64),
                              np.asarray(v_g, np.float64),
                              np.asarray(padding_mask(valid)))
            ek = float(np.abs(np.asarray(o_kern, np.float64) - gt).max())
            ex = float(np.abs(np.asarray(o_xla, np.float64) - gt).max())
            # inverted form so a NaN error FAILS (NaN <= x is False)
            ok = bool(ek <= max(2.0 * ex, 2e-4))
            if not ok:
                failures += 1
            print(json.dumps({"check": name, "ok": ok,
                              "kernel_vs_f64": round(ek, 7),
                              "xla_vs_f64": round(ex, 7)}), flush=True)
        except Exception as e:  # noqa: BLE001 - report and continue
            failures += 1
            print(json.dumps({"check": name, "ok": False,
                              "error": str(e)[:300]}), flush=True)

    # prefill window: causal against a traced origin
    try:
        pool = make_pool(L, NP, PG, 2, hd, False)
        row = jnp.asarray(rng.choice(NP, P, replace=False), jnp.int32)
        s, pos = 16, 9
        qw = jnp.asarray(rng.standard_normal((1, s, 8, hd)), jnp.float32)
        o_kern = jax.jit(lambda q, pool, row, end: paged_window_attention(  # dtlint: disable=DT105
            q, pool, 0, page_walk(pool, row, jnp.zeros_like(end), end),
            interpret=False))(qw, pool, row[None, :],
                              jnp.asarray([pos + s], jnp.int32))
        k_g, v_g = gather(pool, 0, row[None, :], PG)
        cols = jnp.arange(view)[None, None, None, :]
        rows = jnp.arange(s)[None, None, :, None]
        wmask = jnp.where(cols <= pos + rows, 0.0, -1e9)
        o_xla = dot_product_attention(qw, k_g, v_g, mask=wmask)
        gt = gt_attention(qw, np.asarray(k_g, np.float64),
                          np.asarray(v_g, np.float64), np.asarray(wmask))
        ek = float(np.abs(np.asarray(o_kern, np.float64) - gt).max())
        ex = float(np.abs(np.asarray(o_xla, np.float64) - gt).max())
        ok = bool(ek <= max(2.0 * ex, 2e-4))
        if not ok:
            failures += 1
        print(json.dumps({"check": "prefill_window", "ok": ok,
                          "kernel_vs_f64": round(ek, 7),
                          "xla_vs_f64": round(ex, 7)}), flush=True)
    except Exception as e:  # noqa: BLE001 - report and fail
        failures += 1
        print(json.dumps({"check": "prefill_window", "ok": False,
                          "error": str(e)[:300]}), flush=True)

    if failures:
        print(f"{failures} parity failures — DO NOT enable "
              "use_paged_kernel", file=sys.stderr)
        return 1

    # ---- phase 2: crossover timing --------------------------------------
    # Decode-shaped: S slots each reading view_len columns through the
    # page walk vs through gather+dense.  n steps in ONE compiled scan
    # dispatch chained by an output feedback (same PERF.md methodology
    # as flash_timing.time_fwd_bwd: one dispatch amortises the launch
    # cost n ways; fetching the last value closes the window).
    def time_read(fn, q, n=50):
        def step(carry, _):
            out = fn(carry)
            eps = jnp.asarray(1e-6, carry.dtype)
            return carry + eps * out, jnp.sum(out.astype(jnp.float32))

        @jax.jit
        def run(q):
            _, ys = jax.lax.scan(step, q, None, length=n)
            return ys[-1]

        float(run(q))                    # compile + first execute
        t0 = time.perf_counter()
        float(run(q))                    # fetch closes the window
        return (time.perf_counter() - t0) / n

    S2, kvh2, h2 = 8, 2, 8
    for view_len in (256, 512, 1024, 2048):
        P2 = view_len // PG
        NP2 = S2 * P2 + 1
        pool = make_pool(L, NP2, PG, kvh2, hd, False)
        tab = jnp.asarray(
            rng.permutation(NP2 - 1)[:S2 * P2].reshape(S2, P2) + 1,
            jnp.int32)
        full = jnp.full((S2,), view_len, jnp.int32)
        q = jnp.asarray(rng.standard_normal((S2, 1, h2, hd)), jnp.float32)

        t_kern = time_read(
            lambda qq: paged_decode_attention(
                qq, pool, 1, page_walk(pool, tab, jnp.zeros_like(full),
                                       full), interpret=False), q)
        mask = padding_mask(jnp.ones((S2, view_len), bool))
        t_gather = time_read(
            lambda qq: dot_product_attention(
                qq, *gather(pool, 1, tab, PG), mask=mask), q)
        print(json.dumps({
            "view_len": view_len,
            "kernel_reads_per_sec": round(S2 / t_kern, 1),
            "gather_reads_per_sec": round(S2 / t_gather, 1),
            "kernel_speedup": round(t_gather / t_kern, 3),
        }), flush=True)
    print("crossover rule: ops/attention.py _PAGED_KERNEL_MIN_VIEW is the "
          "first view_len with kernel_speedup >= 1.1 (record it in "
          "docs/PERF.md); if no view_len wins, keep the 'auto' gate "
          "pointing at the gather path and demote in PERF.md",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
