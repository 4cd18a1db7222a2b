"""Shared timing harness for the flash-attention hardware scripts.

Both ``validate_flash_tpu.py`` (crossover gate) and
``sweep_flash_blocks.py`` (block tuner) feed the same docs/PERF.md table,
so they must measure identically — one helper, imported by both.
"""
import sys
import time

import jax
import jax.numpy as jnp


def require_tpu() -> bool:
    """Print the backend; True iff it is a real TPU (numbers off-hardware
    are meaningless for kernel decisions — the caller should exit)."""
    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})", file=sys.stderr)
    if dev.platform != "tpu":
        print("NOT a TPU — refusing to measure; kernel decisions need "
              "hardware numbers", file=sys.stderr)
        return False
    return True


def time_fwd_bwd(attn_loss, q, k, v, n: int = 20) -> float:
    """Seconds per fwd+bwd step of ``attn_loss(q, k, v)``, value-fetch
    closed (a value fetch is a barrier on every backend).

    The n steps run inside ONE compiled ``lax.scan`` dispatch, chained by a
    tiny grad feedback so no step can be folded away: one dispatch
    amortises the per-launch host cost n ways, so the window measures the
    chip, not the host's dispatch loop."""
    g = jax.grad(attn_loss, argnums=(0, 1, 2))

    def step(carry, _):
        q, k, v = carry
        dq, dk, dv = g(q, k, v)
        eps = jnp.asarray(1e-6, q.dtype)
        return ((q + eps * dq, k + eps * dk, v + eps * dv),
                jnp.sum(dq.astype(jnp.float32)))

    @jax.jit
    def run(q, k, v):
        (_, _, _), ys = jax.lax.scan(step, (q, k, v), None, length=n)
        return ys[-1]

    float(run(q, k, v))                 # compile + first execute
    t0 = time.perf_counter()
    float(run(q, k, v))                 # fetch closes the window
    return (time.perf_counter() - t0) / n
