"""GPT serving demo: every decode path of the framework in one script.

Runs a small randomly-initialized GPT (structure, not quality — no
weights ship with the repo) through the serving tier:

  * greedy KV-cache ``generate`` (batched prompt prefill),
  * sampled generate (temperature / top_k / top_p),
  * ragged-prompt batch (LEFT-padded ``prompt_valid``),
  * beam search,
  * weight-only int8 decode (``ops.quant``, dequantize-inside-jit),
  * speculative decoding (layer-truncated draft; greedy exactness),

printing tokens/s for each.  On CPU the absolute numbers are
meaningless; the point is the surfaces and their composition.  Real
checkpoints drop in via ``models/convert.py`` (HF GPT-2) — see
examples/finetune_gpt2_hf.py.

While decoding, the demo serves live telemetry (obs/): ``/metrics``
exposes per-path token counters, decode-duration histograms, and
tokens/s gauges in Prometheus text format, ``/healthz`` a JSON liveness
doc — the same endpoint a production serving replica would register
with a scraper (docs/OBSERVABILITY.md).  ``--metrics_port=-1`` turns it
off; the default picks an ephemeral port and prints the URL.

Run: ``python examples/serve_gpt.py --device=cpu --new_tokens=32``
"""
from __future__ import annotations

import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_tensorflow_tpu.utils import flags as flags_lib

flags_lib.DEFINE_string("device", "", "cpu|tpu override (config-level)")
flags_lib.DEFINE_integer("new_tokens", 32, "tokens to generate per path")
flags_lib.DEFINE_integer("batch", 4, "batch size for the batched paths")
flags_lib.DEFINE_integer("seed", 0, "init/prompt seed")
flags_lib.DEFINE_integer("metrics_port", 0,
                         "serve /metrics + /healthz during the demo "
                         "(0 = ephemeral port, -1 = off)")
flags_lib.DEFINE_bool("engine", False,
                      "also run the greedy/sampled/ragged demos through "
                      "the continuous-batching engine (serve/) — same "
                      "tokens/s lines, lock-step paths stay as the "
                      "baseline; serve metrics land on /metrics")
flags_lib.DEFINE_integer("replicas", 1,
                         ">= 2: also run a FLEET demo — that many "
                         "engine replicas behind the fleet Router "
                         "(least-loaded placement, per-tenant "
                         "fair-share, a hot-swapped LoRA adapter), "
                         "with the dttpu_router_*/dttpu_tenant_* "
                         "gauges live on /metrics")
flags_lib.DEFINE_bool("shared_prefix", False,
                      "also run the paged-KV radix-cache demo: "
                      "requests sharing a system prompt map the same "
                      "read-only pages, skip those prefill windows, "
                      "and print the measured TTFT delta + prefix-hit "
                      "line (serve/pages.py)")
FLAGS = flags_lib.FLAGS


def main() -> int:
    if FLAGS.device:
        import jax
        jax.config.update("jax_platforms", FLAGS.device)
    from distributed_tensorflow_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu import obs
    from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
    from distributed_tensorflow_tpu.models.speculative import \
        generate_speculative
    from distributed_tensorflow_tpu.ops import quant

    telemetry = None
    if FLAGS.metrics_port >= 0:
        telemetry = obs.Telemetry(metrics_port=FLAGS.metrics_port,
                                  service="serve").start()
        print(f"telemetry: {telemetry.metrics_url()} (+ /healthz)",
              flush=True)

    new = FLAGS.new_tokens
    b = FLAGS.batch
    plen = 8
    max_len = plen + new + 8
    config = GPTConfig(vocab_size=512, hidden_size=128, num_layers=4,
                       num_heads=4, intermediate_size=512,
                       max_position=max_len + 8, dropout_rate=0.0)
    model = GPT(config)
    params = model.init(jax.random.PRNGKey(FLAGS.seed))
    rng = np.random.default_rng(FLAGS.seed)
    prompt = rng.integers(0, config.vocab_size, (b, plen)).astype(np.int32)

    def timed(name, fn, tokens_out):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = fn()
        out = jax.tree.map(np.asarray, out)     # value fetch
        dt = time.perf_counter() - t0
        print(f"{name:<28} {tokens_out / dt:10,.0f} tok/s", flush=True)
        if telemetry is not None:
            # one label value per decode path; static cardinality
            path = re.sub(r"[^a-z0-9]+", "_", name.lower()).strip("_")
            reg = telemetry.registry
            reg.counter("dttpu_decode_tokens_total",
                        "Tokens generated, by decode path.",
                        labels={"path": path}).inc(tokens_out)
            reg.histogram("dttpu_decode_seconds",
                          "Wall time per timed decode call.",
                          labels={"path": path}).observe(dt)
            reg.gauge("dttpu_decode_tokens_per_second",
                      "Decode throughput, by path.",
                      labels={"path": path}).set(tokens_out / dt)
        return out

    greedy = timed("greedy generate", jax.jit(
        lambda: model.generate(params, prompt, max_new_tokens=new,
                               temperature=0.0, max_len=max_len)),
        b * new)

    timed("sampled (T=0.8, top_p=0.9)", jax.jit(
        lambda: model.generate(params, prompt, max_new_tokens=new,
                               temperature=0.8, top_p=0.9,
                               rng=jax.random.PRNGKey(1),
                               max_len=max_len)), b * new)

    valid = np.ones((b, plen), np.int32)
    valid[0, : plen // 2] = 0                    # one shorter prompt,
    ragged_prompt = prompt.copy()                # LEFT-padded
    ragged_prompt[0, : plen // 2] = 0
    timed("ragged batch (prompt_valid)", jax.jit(
        lambda: model.generate(params, jnp.asarray(ragged_prompt),
                               max_new_tokens=new,
                               prompt_valid=jnp.asarray(valid),
                               max_len=max_len)), b * new)

    timed("beam search (beam=4)", jax.jit(
        lambda: model.beam_search(params, prompt, max_new_tokens=new,
                                  beam_size=4, max_len=max_len)), b * new)

    timed("chunked prefill (W=4)", jax.jit(
        lambda: model.generate(params, prompt, max_new_tokens=new,
                               temperature=0.0, max_len=max_len,
                               prefill_chunk=4)), b * new)

    qparams = quant.quantize_tree(params)
    q_out = timed("int8 weights", jax.jit(
        lambda: model.generate(quant.dequantize_tree(qparams), prompt,
                               max_new_tokens=new, temperature=0.0,
                               max_len=max_len)), b * new)
    agree = float(np.mean(np.asarray(greedy)[:, plen:]
                          == np.asarray(q_out)[:, plen:]))
    print(f"{'':<28} int8 greedy agreement {agree:.3f}", flush=True)

    kv8_model = GPT(dataclasses.replace(config, kv_cache_dtype="int8"))
    kv8_out = timed("int8 weights + int8 KV cache", jax.jit(
        lambda: kv8_model.generate(quant.dequantize_tree(qparams), prompt,
                                   max_new_tokens=new, temperature=0.0,
                                   max_len=max_len)), b * new)
    agree8 = float(np.mean(np.asarray(greedy)[:, plen:]
                           == np.asarray(kv8_out)[:, plen:]))
    print(f"{'':<28} full-int8 greedy agreement {agree8:.3f}", flush=True)

    if FLAGS.engine:
        # Continuous-batching engine (serve/): per-request slots, chunked
        # prefill, retrace-free admission.  Greedy must match the
        # lock-step greedy output token-for-token (the engine exactness
        # contract, docs/SERVING.md); the ragged path needs no padding at
        # all — unequal prompts are simply unequal requests.
        from distributed_tensorflow_tpu import serve

        reg = telemetry.registry if telemetry is not None else None

        def timed_engine(name, eng, plist, tokens_out):
            def run():
                handles = [eng.submit(p, new) for p in plist]
                eng.drain()          # drain fetches tokens: wall closes
                return handles
            run()                    # warmup: compiles the engine's jits
            t0 = time.perf_counter()
            handles = run()
            dt = time.perf_counter() - t0
            print(f"{name:<28} {tokens_out / dt:10,.0f} tok/s",
                  flush=True)
            if telemetry is not None:
                path = re.sub(r"[^a-z0-9]+", "_", name.lower()).strip("_")
                reg.counter("dttpu_decode_tokens_total",
                            "Tokens generated, by decode path.",
                            labels={"path": path}).inc(tokens_out)
                reg.gauge("dttpu_decode_tokens_per_second",
                          "Decode throughput, by path.",
                          labels={"path": path}).set(tokens_out / dt)
            return handles

        eng = serve.Engine(model, params, num_slots=b, max_len=max_len,
                           prefill_chunk=4, tick_steps=4, registry=reg)
        hs = timed_engine("engine greedy", eng, list(prompt), b * new)
        agree_eng = float(np.mean([
            h.tokens == np.asarray(greedy)[i, plen:].tolist()
            for i, h in enumerate(hs)]))
        print(f"{'':<28} engine==lock-step greedy {agree_eng:.3f}",
              flush=True)

        eng_s = serve.Engine(model, params, num_slots=b, max_len=max_len,
                             prefill_chunk=4, tick_steps=4, registry=reg,
                             temperature=0.8, top_p=0.9,
                             rng=jax.random.PRNGKey(1))
        timed_engine("engine sampled (T=0.8)", eng_s, list(prompt),
                     b * new)

        # ragged: the short prompt is just a shorter REQUEST — submit the
        # unpadded rows the lock-step path had to left-pad
        ragged_rows = [ragged_prompt[0, plen // 2:]] + list(prompt[1:])
        timed_engine("engine ragged", eng, ragged_rows, b * new)

    if FLAGS.shared_prefix:
        # Paged-KV radix cache (serve/pages.py): one SYSTEM PROMPT
        # shared by every request.  The first request prefills it cold
        # and publishes its full pages; every follower maps them
        # read-only and skips those prefill windows — the TTFT delta
        # printed below is that skipped work, and the hit tokens are
        # bit-identical to a cold cache (tests/test_pages.py pins it).
        from distributed_tensorflow_tpu import serve

        reg = telemetry.registry if telemetry is not None else None
        # page_size pinned small so a 2-page system prompt + tail +
        # budget fits the demo's max_len whatever --new_tokens is
        eng_sp = serve.Engine(model, params, num_slots=b,
                              max_len=max_len, prefill_chunk=4,
                              tick_steps=4,
                              page_size=serve.auto_page_size(max_len, 4),
                              registry=reg)
        # warmup compiles the paged executables (cold-compile must not
        # masquerade as the uncached TTFT)
        eng_sp.submit(rng.integers(0, config.vocab_size, 6).astype(
            np.int32), 2)
        eng_sp.drain()
        sys_prompt = rng.integers(0, config.vocab_size,
                                  2 * eng_sp.scheduler.page_size
                                  ).astype(np.int32)
        ttfts = []
        for i in range(b):
            tail = rng.integers(0, config.vocab_size,
                                2 + i).astype(np.int32)
            h = eng_sp.submit(np.concatenate([sys_prompt, tail]), new)
            eng_sp.drain()
            ttfts.append(h.ttft_s)
        st = eng_sp.stats()
        cold_ms = ttfts[0] * 1e3
        hit_ms = sum(ttfts[1:]) / max(len(ttfts) - 1, 1) * 1e3
        print(f"{'shared-prefix (paged KV)':<28} ttft cold "
              f"{cold_ms:7.1f} ms -> hit {hit_ms:7.1f} ms "
              f"({cold_ms / max(hit_ms, 1e-9):.1f}x faster)",
              flush=True)
        print(f"{'':<28} prefix hits {st.prefix_hits_total}/"
              f"{st.prefix_lookups_total}, "
              f"{st.prefill_windows_skipped_total} prefill windows "
              f"skipped, {st.prefix_tokens_reused_total} tokens "
              f"reused, {st.pages_free}/{st.pages_total} pages free",
              flush=True)

    if FLAGS.replicas >= 2:
        # Fleet demo (fleet/): N engine replicas behind one Router —
        # least-loaded placement off Engine.stats(), two tenants under
        # a deficit-weighted fair-share policy, and tenant "pro"
        # decoding under a hot-swapped LoRA adapter.  Greedy traffic
        # with adapter_id=None must still match the lock-step greedy
        # output (the fleet inherits the engine exactness contract).
        from distributed_tensorflow_tpu import fleet, serve

        reg = telemetry.registry if telemetry is not None else None
        policy = fleet.TenantPolicy(quantum=8)
        router = fleet.Router(
            [serve.Engine(model, params, num_slots=b, max_len=max_len,
                          prefill_chunk=4, tick_steps=4, registry=reg,
                          tenancy=policy, adapter_capacity=2,
                          adapter_rank=4)
             for _ in range(FLAGS.replicas)],
            registry=reg)
        router.load_adapter(
            "pro-tuned", model.init_lora(jax.random.PRNGKey(11), rank=4))

        def fleet_round():
            handles = []
            for i, p in enumerate(prompt):
                tenant = "pro" if i % 2 else "free"
                handles.append(router.submit(
                    p, new, tenant=tenant,
                    adapter_id="pro-tuned" if tenant == "pro" else None))
            router.drain()
            return handles

        fleet_round()                          # warmup: compiles all
        t0 = time.perf_counter()
        hs = fleet_round()
        dt = time.perf_counter() - t0
        print(f"{'fleet (%d replicas)' % FLAGS.replicas:<28} "
              f"{b * new / dt:10,.0f} tok/s", flush=True)
        base_rows = [i for i in range(b) if i % 2 == 0]
        agree_fleet = float(np.mean([
            hs[i].tokens == np.asarray(greedy)[i, plen:].tolist()
            for i in base_rows]))
        spread = {r: sum(1 for _, rid in router.placements if rid == r)
                  for r in router.replica_ids}
        print(f"{'':<28} fleet==lock-step greedy {agree_fleet:.3f} "
              f"(base-model rows), placements {spread}", flush=True)

    draft = GPT(dataclasses.replace(config, num_layers=2))
    d_params = dict(params)
    d_params["decoder"] = jax.tree.map(lambda a: a[:2], params["decoder"])
    spec_out, acc = timed("speculative (gamma=4)", jax.jit(
        lambda: generate_speculative(model, params, draft, d_params,
                                     prompt[:1], max_new_tokens=new,
                                     gamma=4)), new)
    match = float(np.mean(np.asarray(greedy)[:1, plen:]
                          == np.asarray(spec_out)[:, plen:]))
    print(f"{'':<28} spec acceptance {float(acc):.3f}, greedy match "
          f"{match:.3f}", flush=True)
    if telemetry is not None:
        # self-scrape: prove the endpoint a scraper would hit is live and
        # carrying the decode series recorded above
        import urllib.request
        with urllib.request.urlopen(telemetry.metrics_url(),
                                    timeout=5) as resp:
            text = resp.read().decode("utf-8")
        samples = [l for l in text.splitlines()
                   if l and not l.startswith("#")]
        print(f"{'':<28} /metrics scrape: {len(samples)} samples",
              flush=True)
        telemetry.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
