"""Pallas kernel parity tests (interpret mode on the CPU test mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops.attention import (
    dot_product_attention, padding_mask, causal_mask)
from distributed_tensorflow_tpu.ops.pallas import (
    MIN_PAGE_SIZE, flash_attention, make_flash_attention_fn,
    fused_adam_update, fused_layernorm, fused_rmsnorm,
    page_size_kernel_ok, page_walk, paged_decode_attention,
    paged_window_attention)


def _qkv(key, b=2, s=64, h=4, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, h, d), dtype)
    v = jax.random.normal(kv, (b, s, h, d), dtype)
    return q, k, v


class TestFlashAttention:
    def test_matches_reference_no_mask(self):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        got = flash_attention(q, k, v, block_q=32, block_k=32)
        want = dot_product_attention(q, k, v)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_causal(self):
        q, k, v = _qkv(jax.random.PRNGKey(1))
        got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        want = dot_product_attention(q, k, v, mask=causal_mask(q.shape[1]))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_padding_mask(self):
        q, k, v = _qkv(jax.random.PRNGKey(2))
        valid = jnp.asarray(
            np.random.default_rng(0).random((2, 64)) < 0.7, jnp.int32)
        valid = valid.at[:, 0].set(1)      # no fully-masked rows
        got = flash_attention(q, k, v, kv_valid=valid, block_q=32, block_k=32)
        want = dot_product_attention(q, k, v, mask=padding_mask(valid))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_ragged_seq_not_multiple_of_block(self):
        q, k, v = _qkv(jax.random.PRNGKey(3), s=50)
        got = flash_attention(q, k, v, block_q=16, block_k=16)
        want = dot_product_attention(q, k, v)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_causal_ragged(self):
        q, k, v = _qkv(jax.random.PRNGKey(4), s=40)
        got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        want = dot_product_attention(q, k, v, mask=causal_mask(40))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_bfloat16(self):
        q, k, v = _qkv(jax.random.PRNGKey(5), dtype=jnp.bfloat16)
        got = flash_attention(q, k, v, block_q=32, block_k=32)
        want = dot_product_attention(q, k, v)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(got.astype(np.float32),
                                   want.astype(np.float32),
                                   atol=3e-2, rtol=3e-2)

    def test_gradients_match_reference(self):
        q, k, v = _qkv(jax.random.PRNGKey(6), b=1, s=32, h=2, d=8)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=16, block_k=16) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dot_product_attention(
                q, k, v, mask=causal_mask(q.shape[1])) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    def test_jit_compiles(self):
        q, k, v = _qkv(jax.random.PRNGKey(7), s=32)
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v,
                                                    block_q=16, block_k=16))
        np.testing.assert_allclose(f(q, k, v),
                                   dot_product_attention(q, k, v),
                                   atol=1e-5, rtol=1e-5)

    def test_attention_fn_adapter(self):
        q, k, v = _qkv(jax.random.PRNGKey(8), s=32)
        valid = jnp.ones((2, 32), jnp.int32).at[:, 20:].set(0)
        fn = make_flash_attention_fn(block_q=16, block_k=16)
        got = fn(q, k, v, mask=padding_mask(valid))
        want = dot_product_attention(q, k, v, mask=padding_mask(valid))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_attention_fn_rejects_full_mask(self):
        q, k, v = _qkv(jax.random.PRNGKey(9), s=16)
        fn = make_flash_attention_fn()
        with pytest.raises(ValueError):
            fn(q, k, v, mask=causal_mask(16))

    # -- fused Pallas backward (dq/dk/dv kernels) parity ------------------
    def _grad_pair(self, q, k, v, flash_kwargs, ref_mask):
        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, **flash_kwargs)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            out = dot_product_attention(q, k, v, mask=ref_mask)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        return g1, g2

    def test_fused_backward_no_mask(self):
        q, k, v = _qkv(jax.random.PRNGKey(10), b=2, s=64, h=2, d=16)
        g1, g2 = self._grad_pair(q, k, v,
                                 dict(block_q=32, block_k=32), None)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    def test_fused_backward_causal_multiblock(self):
        """Causal with several q/k blocks: exercises the diagonal-skip
        guards of both backward kernels."""
        q, k, v = _qkv(jax.random.PRNGKey(11), b=1, s=64, h=2, d=8)
        g1, g2 = self._grad_pair(
            q, k, v, dict(causal=True, block_q=16, block_k=16),
            causal_mask(64))
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    def test_fused_backward_padding_and_ragged(self):
        """Padding mask + seq not a block multiple: padded q rows and
        masked k columns must contribute exactly zero gradient."""
        q, k, v = _qkv(jax.random.PRNGKey(12), b=2, s=50, h=2, d=8)
        valid = jnp.ones((2, 50), jnp.int32).at[:, 40:].set(0)
        g1, g2 = self._grad_pair(
            q, k, v, dict(kv_valid=valid, block_q=16, block_k=16),
            padding_mask(valid))
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        # masked-out key positions get zero dk/dv
        assert float(jnp.abs(g1[1][:, 40:]).max()) < 1e-6
        assert float(jnp.abs(g1[2][:, 40:]).max()) < 1e-6

    def test_fused_backward_bf16(self):
        q, k, v = _qkv(jax.random.PRNGKey(13), b=1, s=32, h=2, d=8,
                       dtype=jnp.bfloat16)
        g1, g2 = self._grad_pair(
            q, k, v, dict(causal=True, block_q=16, block_k=16),
            causal_mask(32))
        for a, b in zip(g1, g2):
            assert a.dtype == b.dtype == jnp.bfloat16
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32),
                                       atol=6e-2, rtol=6e-2)

    def test_fused_backward_under_jit_value_and_grad(self):
        q, k, v = _qkv(jax.random.PRNGKey(14), b=1, s=32, h=2, d=8)

        @jax.jit
        def vg(q, k, v):
            return jax.value_and_grad(
                lambda q: jnp.sum(flash_attention(q, k, v, causal=True,
                                                  block_q=16,
                                                  block_k=16) ** 2))(q)

        val, grad = vg(q, k, v)
        ref = jnp.sum(dot_product_attention(
            q, k, v, mask=causal_mask(32)) ** 2)
        np.testing.assert_allclose(float(val), float(ref), rtol=1e-5)
        assert bool(jnp.isfinite(grad).all())


class TestFlashGQA:
    """GQA/MQA run natively in the kernels: kv blocks are selected by
    q_head // group in the BlockSpec index maps (forward + both backward
    kernels), and per-q-head dk/dv reduce over the group afterwards."""

    def test_kernel_rejects_nondivisible_heads(self):
        q, _, _ = _qkv(jax.random.PRNGKey(20), s=16, h=4)
        _, k, v = _qkv(jax.random.PRNGKey(21), s=16, h=3)
        with pytest.raises(ValueError, match="multiple of the kv head"):
            flash_attention(q, k, v)

    @pytest.mark.parametrize("kv_heads", [1, 2])   # MQA and GQA
    def test_gqa_forward_matches_grouped_dense(self, kv_heads):
        q, _, _ = _qkv(jax.random.PRNGKey(20), b=2, s=48, h=4, d=8)
        _, k, v = _qkv(jax.random.PRNGKey(21), b=2, s=48, h=kv_heads, d=8)
        got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        want = dot_product_attention(q, k, v, mask=causal_mask(48))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_gqa_backward_matches_grouped_dense(self):
        """dk/dv accumulate over the whole query group (per-q-head kernel
        outputs reduced in XLA) — grads must match the grouped einsum's."""
        q, _, _ = _qkv(jax.random.PRNGKey(22), b=2, s=48, h=4, d=8)
        _, k, v = _qkv(jax.random.PRNGKey(23), b=2, s=48, h=2, d=8)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            out = dot_product_attention(q, k, v, mask=causal_mask(48))
            return jnp.sum(out.astype(jnp.float32) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        assert g1[1].shape == k.shape and g1[2].shape == v.shape
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    def test_gqa_padding_mask(self):
        q, _, _ = _qkv(jax.random.PRNGKey(24), b=2, s=40, h=4, d=8)
        _, k, v = _qkv(jax.random.PRNGKey(25), b=2, s=40, h=2, d=8)
        valid = jnp.ones((2, 40), jnp.int32).at[:, 30:].set(0)
        got = flash_attention(q, k, v, kv_valid=valid, block_q=16,
                              block_k=16)
        want = dot_product_attention(q, k, v, mask=padding_mask(valid))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_gpt_gqa_flash_matches_dense(self):
        """GQA + use_flash=True end-to-end through attention_core (which
        must NOT broadcast kv heads for a supports_gqa kernel): same
        hidden states as the dense grouped-einsum path."""
        import numpy as np
        from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
        base = dict(vocab_size=32, hidden_size=32, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=32,
                    max_position=32, dropout_rate=0.0)
        flash = GPT(GPTConfig(**base, use_flash=True))
        dense = GPT(GPTConfig(**base, use_flash=False))
        params = flash.init(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 32)
        h_flash = flash.apply(params, ids)
        h_dense = dense.apply(params, ids)
        np.testing.assert_allclose(np.asarray(h_flash),
                                   np.asarray(h_dense),
                                   atol=1e-5, rtol=1e-5)


class TestFlashOnMesh:
    """``mesh=``: the kernel call under shard_map (batch over data/fsdp,
    heads over tensor) — what lets it survive a multi-device ``jit``, where
    XLA refuses to partition a Mosaic kernel (tests/test_tpu_compile.py
    compiles the same call for the real chip).  Interpret mode partitions
    happily, so here only the VALUES are at stake: forward, gradients and
    output placement against the unsharded kernel."""

    @pytest.mark.parametrize("axes,batch,kv_heads", [
        ({"data": 2, "fsdp": 2}, 4, 4),
        ({"data": 2, "tensor": 2}, 4, 2),        # GQA, kv heads split too
        ({"data": 2, "fsdp": 2}, 3, 4),          # batch the mesh can't split
    ])
    def test_matches_unsharded_kernel(self, axes, batch, kv_heads):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from distributed_tensorflow_tpu import parallel
        mesh = parallel.make_mesh(axes, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(7), b=batch)
        k, v = k[:, :, :kv_heads], v[:, :, :kv_heads]
        valid = jnp.ones((batch, 64), jnp.int32).at[:, -5:].set(0)

        def loss(q, k, v, mesh):
            out = flash_attention(q, k, v, kv_valid=valid, causal=True,
                                  block_q=16, block_k=16, mesh=mesh)
            return jnp.sum(out ** 2), out

        (_, want), want_g = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v, None)
        shards = parallel.data_shards(mesh)
        spec = P(tuple(a for a in ("data", "fsdp") if a in axes)
                 if batch % shards == 0 else None,
                 None, "tensor" if "tensor" in axes else None, None)
        put = lambda t: jax.device_put(t, NamedSharding(mesh, spec))
        (_, got), got_g = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(q, k, v, mesh), argnums=(0, 1, 2),
            has_aux=True))(put(q), put(k), put(v))
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)
        assert got.sharding.spec == spec     # no gather on the way out

    def test_inside_the_pipeline_region(self):
        """Traced inside the pipeline's shard_map (manual over ``pipe``)
        the kernel takes only the axes still automatic there."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from distributed_tensorflow_tpu import parallel
        from distributed_tensorflow_tpu.models.gpt import GPT, GPTConfig
        mesh = parallel.make_mesh({"pipe": 2, "data": 2},
                                  devices=jax.devices()[:4])
        ids = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 128),
            NamedSharding(mesh, P("data")))
        outs = {}
        for use_flash in (True, False):
            model = GPT(GPTConfig(
                vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_position=32, dropout_rate=0.0,
                pipeline_stages=2, use_flash=use_flash), mesh=mesh)
            params = model.init(jax.random.PRNGKey(0))
            outs[use_flash] = jax.jit(model.apply)(params, ids)
        np.testing.assert_allclose(outs[True], outs[False], atol=1e-5)


class TestFlashAutoDispatch:
    def test_resolve_use_flash(self, monkeypatch):
        from distributed_tensorflow_tpu.ops import attention as attn_lib
        assert attn_lib.resolve_use_flash(True, 8) is True
        assert attn_lib.resolve_use_flash(False, 99999) is False
        # pin the backend so the assertions hold on TPU-attached hosts too
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert attn_lib.resolve_use_flash("auto", 99999) is False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert attn_lib.resolve_use_flash("auto", 2048) is True
        assert attn_lib.resolve_use_flash("auto", 512) is False

    def test_flash_min_seq_env(self, monkeypatch):
        from distributed_tensorflow_tpu.ops import attention as attn_lib
        monkeypatch.setenv("DTTPU_FLASH_MIN_SEQ", "64")
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        # still gated on the TPU backend even past the threshold
        assert attn_lib.flash_wins(128) is False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert attn_lib.flash_wins(128) is True
        assert attn_lib.flash_wins(32) is False


class TestFusedAdam:
    def _naive(self, p, g, m, v, t, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
               wd=0.0):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** t)
        vh = v / (1 - b2 ** t)
        p = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
        return p, m, v

    @pytest.mark.parametrize("shape", [(37,), (128, 130), (3, 5, 7)])
    def test_matches_naive(self, shape):
        key = jax.random.PRNGKey(0)
        kp, kg, km, kv = jax.random.split(key, 4)
        p = jax.random.normal(kp, shape)
        g = jax.random.normal(kg, shape)
        m = jax.random.normal(km, shape) * 0.1
        v = jax.random.uniform(kv, shape) * 0.01
        for t in (1, 10):
            got = fused_adam_update(p, g, m, v, jnp.asarray(t))
            want = self._naive(p, g, m, v, t)
            for a, b in zip(got, want):
                np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)

    def test_weight_decay(self):
        # Large wd + early steps: catches decay scaled by the bias-corrected
        # lr_t instead of plain lr (decoupled-AdamW semantics).
        p = jnp.ones((64,)) * 0.5
        g = jnp.ones((64,)) * 0.1
        m = jnp.zeros((64,))
        v = jnp.zeros((64,))
        for t in (1, 5):
            got = fused_adam_update(p, g, m, v, jnp.asarray(t),
                                    weight_decay=0.1)
            want = self._naive(p, g, m, v, t, wd=0.1)
            for a, b in zip(got, want):
                np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-6)

    def test_under_jit_with_traced_step(self):
        p = jnp.ones((100,))
        g = jnp.full((100,), 0.3)
        m = jnp.zeros((100,))
        v = jnp.zeros((100,))
        f = jax.jit(lambda p, g, m, v, t: fused_adam_update(p, g, m, v, t))
        got = f(p, g, m, v, jnp.asarray(3))
        want = self._naive(p, g, m, v, 3)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


class TestFusedLayerNorm:
    def _ref(self, x, gamma, beta, eps=1e-6):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * gamma + beta

    def test_matches_reference(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 96))
        gamma = jax.random.normal(jax.random.PRNGKey(1), (96,)) + 1.0
        beta = jax.random.normal(jax.random.PRNGKey(2), (96,))
        got = fused_layernorm(x, gamma, beta)
        np.testing.assert_allclose(got, self._ref(x, gamma, beta),
                                   atol=1e-5, rtol=1e-5)

    def test_bfloat16(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 64), jnp.bfloat16)
        gamma = jnp.ones((64,))
        beta = jnp.zeros((64,))
        got = fused_layernorm(x, gamma, beta)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            got.astype(np.float32),
            self._ref(x.astype(jnp.float32), gamma, beta),
            atol=3e-2, rtol=3e-2)

    def test_gradients(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (6, 32))
        gamma = jnp.ones((32,)) * 1.5
        beta = jnp.zeros((32,))

        g1 = jax.grad(lambda x, g, b: jnp.sum(fused_layernorm(x, g, b) ** 2),
                      argnums=(0, 1, 2))(x, gamma, beta)
        g2 = jax.grad(lambda x, g, b: jnp.sum(self._ref(x, g, b) ** 2),
                      argnums=(0, 1, 2))(x, gamma, beta)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


class TestFusedRmsNorm:
    def _ref(self, x, gamma, eps=1e-6):
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
        return (x32 * inv * gamma.astype(jnp.float32)).astype(x.dtype)

    def test_matches_reference(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 96))
        gamma = jax.random.normal(jax.random.PRNGKey(1), (96,)) + 1.0
        got = fused_rmsnorm(x, gamma)
        np.testing.assert_allclose(got, self._ref(x, gamma),
                                   atol=1e-5, rtol=1e-5)

    def test_bfloat16(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 64), jnp.bfloat16)
        gamma = jnp.ones((64,)) * 1.5
        got = fused_rmsnorm(x, gamma)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            got.astype(np.float32),
            self._ref(x, gamma).astype(np.float32),
            atol=3e-2, rtol=3e-2)

    def test_gradients(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (6, 32))
        gamma = jnp.ones((32,)) * 1.5
        g1 = jax.grad(lambda x, g: jnp.sum(fused_rmsnorm(x, g) ** 2),
                      argnums=(0, 1))(x, gamma)
        g2 = jax.grad(lambda x, g: jnp.sum(self._ref(x, g) ** 2),
                      argnums=(0, 1))(x, gamma)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_llama_model_parity(self):
        """fused_layernorm=True on a rmsnorm model must reproduce the
        unfused logits AND gradients — the whole _norm dispatch, not
        just the kernel in isolation."""
        from distributed_tensorflow_tpu.models.llama import llama_tiny
        ids = np.arange(24, dtype=np.int32).reshape(2, 12) % 512

        outs, grads = [], []
        for fused in (False, True):
            model = llama_tiny(fused_layernorm=fused)
            params = model.init(jax.random.PRNGKey(0))
            outs.append(model.apply(params, ids))
            loss = model.lm_loss_fn()
            g = jax.grad(lambda p: loss(
                p, {}, {"input_ids": ids}, jax.random.PRNGKey(1),
                False)[0])(params)
            grads.append(g)
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=2e-5)
        for a, b in zip(jax.tree.leaves(grads[0]),
                        jax.tree.leaves(grads[1])):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3)


class TestFlashShapeFuzz:
    def test_random_shape_parity(self):
        """Seeded fuzz over odd seq lengths / head counts / GQA ratios /
        mask kinds: the padded-block kernel must match dense attention on
        shapes that don't divide the (512, 1024) default blocks."""
        import numpy as np
        from distributed_tensorflow_tpu.ops import attention as attn_lib
        from distributed_tensorflow_tpu.ops.pallas.flash_attention import (
            flash_attention)

        rng = np.random.default_rng(20260731)
        for trial in range(6):
            b = int(rng.integers(1, 3))
            s = int(rng.integers(3, 97))
            groups = int(rng.choice([1, 2, 4]))
            kvh = int(rng.choice([1, 2]))
            h = kvh * groups
            d = int(rng.choice([8, 16]))
            causal = bool(rng.integers(0, 2))
            use_pad = bool(rng.integers(0, 2))
            ks = jax.random.split(jax.random.PRNGKey(trial), 3)
            q = jax.random.normal(ks[0], (b, s, h, d))
            k = jax.random.normal(ks[1], (b, s, kvh, d))
            v = jax.random.normal(ks[2], (b, s, kvh, d))
            kv_valid = None
            mask = attn_lib.causal_mask(s) if causal else None
            if use_pad and not causal:
                keep = max(1, s - int(rng.integers(0, s)))
                kv_valid = jnp.asarray(
                    np.arange(s)[None, :] < keep, jnp.int32
                ).repeat(b, axis=0)
                mask = attn_lib.padding_mask(kv_valid)
            got = flash_attention(q, k, v, kv_valid=kv_valid, causal=causal)
            if kvh != h:   # dense path wants broadcast kv heads
                k2 = jnp.repeat(k, groups, axis=2)
                v2 = jnp.repeat(v, groups, axis=2)
            else:
                k2, v2 = k, v
            want = attn_lib.dot_product_attention(q, k2, v2, mask=mask)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=2e-5,
                err_msg=f"trial {trial}: b={b} s={s} h={h} kvh={kvh} "
                        f"d={d} causal={causal} pad={use_pad}")


class TestPagedAttention:
    """The fused page-walk kernel vs the gather reference: same pool,
    same table, same masks — the kernel must agree to float round-off
    (token-level bit-identity is pinned at engine level in
    tests/test_pages.py)."""
    L, NP, PG, HD = 2, 14, 8, 16

    def _pool(self, key, kvh, quantized=False, hd=None):
        """serve/pages.py's layout: a token's heads one flat row
        ``[L, NP, PG, kvh * hd]``, scale planes ``[L, NP, PG, kvh]``."""
        kk, kv_, ks, vs = jax.random.split(key, 4)
        shape = (self.L, self.NP, self.PG, kvh * (hd or self.HD))
        if quantized:
            planes = shape[:-1] + (kvh,)
            pool = {
                "k": jax.random.randint(kk, shape, -127, 128, jnp.int8),
                "v": jax.random.randint(kv_, shape, -127, 128, jnp.int8),
                "k_scale": jax.random.uniform(
                    ks, planes, jnp.float32, 0.01, 0.05),
                "v_scale": jax.random.uniform(
                    vs, planes, jnp.float32, 0.01, 0.05),
            }
        else:
            pool = {"k": jax.random.normal(kk, shape),
                    "v": jax.random.normal(kv_, shape)}
        return pool

    def _dense_kv(self, pool, layer, tab, hd=None):
        """The gather read path at test scale: pages -> contiguous,
        flat rows -> heads."""
        view = tab.shape[-1] * self.PG
        kvh = pool["k"].shape[-1] // (hd or self.HD)
        def gather(leaf):
            g = leaf[layer][tab.reshape(-1)]
            return g.reshape(tab.shape[0], view, kvh, -1)
        k, v = gather(pool["k"]), gather(pool["v"])
        if "k_scale" in pool:
            k = k.astype(jnp.float32) * gather(pool["k_scale"])
            v = v.astype(jnp.float32) * gather(pool["v_scale"])
        return k, v

    @staticmethod
    def _runs(rng, S, view):
        """A ragged, non-empty column run ``[lo, hi)`` a slot."""
        lo = rng.integers(0, view // 2, S)
        hi = lo + 1 + rng.integers(0, view - lo)
        return jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)

    @staticmethod
    def _run_valid(lo, hi, view):
        cols = jnp.arange(view)[None, :]
        return (cols >= lo[:, None]) & (cols < hi[:, None])

    def _decode(self, q, pool, layer, tab, lo, hi):
        return paged_decode_attention(q, pool, layer,
                                      page_walk(pool, tab, lo, hi))

    def _window(self, q, pool, layer, row, pos):
        end = jnp.reshape(jnp.asarray(pos, jnp.int32) + q.shape[1], (1,))
        return paged_window_attention(
            q, pool, layer,
            page_walk(pool, row[None, :], jnp.zeros_like(end), end))

    @pytest.mark.parametrize("kvh,h,quantized", [
        (4, 4, False), (2, 4, False), (2, 4, True)],
        ids=["base", "gqa", "int8"])
    def test_decode_matches_gather(self, kvh, h, quantized):
        S, P = 3, 4
        key = jax.random.PRNGKey(7)
        pool = self._pool(key, kvh, quantized)
        rng = np.random.default_rng(11)
        tab = jnp.asarray(rng.choice(self.NP, size=(S, P), replace=False)
                          if S * P <= self.NP else
                          rng.integers(0, self.NP, (S, P)), jnp.int32)
        view = P * self.PG
        lo, hi = self._runs(rng, S, view)
        valid = self._run_valid(lo, hi, view)
        q = jax.random.normal(jax.random.PRNGKey(8), (S, 1, h, self.HD))
        for layer in range(self.L):
            got = self._decode(q, pool, layer, tab, lo, hi)
            k, v = self._dense_kv(pool, layer, tab)
            want = dot_product_attention(
                q, k.astype(q.dtype), v.astype(q.dtype),
                mask=padding_mask(valid))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-6, rtol=2e-6)

    # 16 pages a grid step at this page size (STEP_COLUMNS 128 / 8): a
    # 40-entry table is two and a half steps
    STEP, TABLE = 16, 40
    RAGGED = {
        # lengths of one batch, in columns (8 a page): a retired row
        # first, then a token, a page, a page + 1, a step's pages - 1 / +-
        # 0 / + 1, the whole table (which the step does not divide)
        "from_col0": ([0, 0, 0, 0, 0, 0, 0, 0],
                      [0, 1, 8, 9, 127, 128, 129, 320]),
        # runs that start past column 0: mid-page, on a page boundary, a
        # step's pages in, and one that ends where it starts
        "start_col_gt0": ([5, 8, 128, 131, 77, 40, 311, 9],
                          [6, 17, 129, 320, 77, 233, 320, 300]),
    }

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["f32", "int8"])
    @pytest.mark.parametrize("case", sorted(RAGGED))
    def test_decode_ragged_lengths_match_gather(self, case, quantized):
        """Rows of one batch that hold nothing, a token, a page, a page
        + 1, a grid step's pages +- 1 and the whole table, from column 0
        and from inside a page: each agrees with the gather read of its
        own run, and a row that holds nothing reads zeros.  Two rows
        share every page id (a shared prefix): a page fetched for one is
        not confused with the other's."""
        lo, hi = (jnp.asarray(x, jnp.int32) for x in self.RAGGED[case])
        S, P, kvh, h = lo.size, self.TABLE, 2, 4
        assert self.STEP * self.PG == 128 and P % self.STEP
        pool = self._pool(jax.random.PRNGKey(31), kvh, quantized)
        rng = np.random.default_rng(33)
        tab = rng.integers(1, self.NP, (S, P))
        tab[3] = tab[7]                      # a shared chain of pages
        tab = jnp.asarray(tab, jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(32), (S, 1, h, self.HD))
        got = np.asarray(self._decode(q, pool, 1, tab, lo, hi))
        k, v = self._dense_kv(pool, 1, tab)
        want = np.asarray(dot_product_attention(
            q, k.astype(q.dtype), v.astype(q.dtype),
            mask=padding_mask(self._run_valid(lo, hi, P * self.PG))))
        held = np.asarray(hi > lo)
        assert not held.all() and held.sum() >= 6
        np.testing.assert_allclose(got[held], want[held], atol=3e-6,
                                   rtol=3e-6)
        assert not got[~held].any()

    def test_page_walk_fetches_held_pages_only(self):
        """The walk has a grid step only where a row has pages to read,
        row after row, and names no page a run does not hold: a buffer's
        page is a held page of the step's row, the page that buffer is
        next used for, or the one it was last used for — so the pipeline
        copies exactly the held pages, each once."""
        lo = jnp.asarray([0, 0, 131, 0], jnp.int32)
        hi = jnp.asarray([129, 0, 200, 9], jnp.int32)
        S, P, n = 4, self.TABLE, self.STEP
        pool = self._pool(jax.random.PRNGKey(41), 2)
        tab = jnp.arange(S * P, dtype=jnp.int32).reshape(S, P) + 100
        walk = page_walk(pool, tab, lo, hi)
        # 17 pages, none, pages 16..24, 2 pages: 2 + 0 + 1 + 1 steps
        steps = int(walk.steps[0])
        assert steps == 4 and walk.rows.shape == (S * -(-P // n),)
        assert list(np.asarray(walk.rows[:steps])) == [0, 0, 2, 3]
        assert list(np.asarray(walk.groups[:steps])) == [0, 1, 0, 0]
        pages = np.asarray(walk.pages).reshape(-1, n)
        held = [100 + r * P + np.arange(int(l) // self.PG,
                                        -(-int(h) // self.PG))
                for r, (l, h) in enumerate(zip(lo, hi)) if h > l]
        assert set(pages.ravel()) == set(np.concatenate(held))
        # a buffer's page changes only to a held page it has not held,
        # and not at all past the last step
        assert sum(len(set(pages[:, j])) for j in range(n)) \
            == sum(len(x) for x in held)
        for j in range(n):
            col = pages[:, j]
            assert len(set(col)) == 1 + np.count_nonzero(col[1:] != col[:-1])
        assert (pages[steps - 1:] == pages[steps - 1]).all()
        # row 0: its first sixteen pages, then the 17th alone beside
        # what rows 2 and 3 will read
        assert list(pages[0]) == list(100 + np.arange(16))
        assert pages[1, 0] == 116 and pages[1, 1] == 100 + 2 * P + 17

    @pytest.mark.parametrize("kvh,h,hd,quantized", [
        (5, 5, 64, False),      # 320 lanes: two and a half lane tiles
        (25, 25, 64, False),    # GPT-2-XL's own row, 1600 lanes
        (5, 10, 64, False),     # grouped queries over an odd width
        (5, 10, 64, True),      # int8 planes, one scale a head
        (3, 3, 128, False),     # a head a lane tile
        (3, 6, 48, False),      # a head size that divides no lane tile
    ], ids=["w320", "w1600", "w320_gqa", "w320_int8", "hd128", "hd48"])
    def test_odd_widths_match_gather(self, kvh, h, hd, quantized):
        """Rows that are no multiple of 128 lanes — the layout exists
        for them — through both variants: the decode step over several
        slots (few query rows: the row contracted whole) and a causal
        window over one row (w1600's 200 query rows: by 128-lane
        blocks)."""
        S, P, s, pos = 2, 3, 8, 5
        pool = self._pool(jax.random.PRNGKey(21), kvh, quantized, hd=hd)
        rng = np.random.default_rng(23)
        tab = jnp.asarray(rng.choice(self.NP, size=(S, P), replace=False),
                          jnp.int32)
        view = P * self.PG
        lo, hi = self._runs(rng, S, view)
        valid = self._run_valid(lo, hi, view)
        q = jax.random.normal(jax.random.PRNGKey(22), (S, 1, h, hd))
        k, v = self._dense_kv(pool, 1, tab, hd=hd)
        np.testing.assert_allclose(
            np.asarray(self._decode(q, pool, 1, tab, lo, hi)),
            np.asarray(dot_product_attention(
                q, k.astype(q.dtype), v.astype(q.dtype),
                mask=padding_mask(valid))), atol=3e-6, rtol=3e-6)
        qw = jax.random.normal(jax.random.PRNGKey(24), (1, s, h, hd))
        cols = jnp.arange(view)[None, None, None, :]
        rows = jnp.arange(s)[None, None, :, None]
        np.testing.assert_allclose(
            np.asarray(self._window(qw, pool, 1, tab[0], pos)),
            np.asarray(dot_product_attention(
                qw, k[:1].astype(q.dtype), v[:1].astype(q.dtype),
                mask=jnp.where(cols <= pos + rows, 0.0, -1e9))),
            atol=3e-6, rtol=3e-6)

    @pytest.mark.parametrize("pos", [0, 5, 17, 24])
    def test_window_matches_reference(self, pos):
        """From column 0, from inside a page, across a page boundary,
        and with its last row on the table's last column."""
        kvh = h = 4
        P, s = 4, 8
        pool = self._pool(jax.random.PRNGKey(3), kvh)
        row = jnp.asarray([5, 2, 9, 0], jnp.int32)
        view = P * self.PG
        q = jax.random.normal(jax.random.PRNGKey(4), (1, s, h, self.HD))
        got = self._window(q, pool, 1, row, pos)
        k, v = self._dense_kv(pool, 1, row[None, :])
        cols = jnp.arange(view)[None, None, None, :]
        rows = jnp.arange(s)[None, None, :, None]
        mask = jnp.where(cols <= pos + rows, 0.0, -1e9)
        want = dot_product_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-6)

    @pytest.mark.parametrize("pos,quantized", [
        (0, False), (124, False), (128, False), (312, False), (124, True)],
        ids=["pos0", "across_steps", "step_boundary", "table_end",
             "across_steps_int8"])
    def test_window_over_several_steps_matches_reference(self, pos,
                                                         quantized):
        """A window on a table of two and a half grid steps: at column
        0, across the first step's last page into the second step, from
        the second step's first column, and ending on the table's last
        column."""
        kvh, h, s, P = 2, 4, 8, self.TABLE
        pool = self._pool(jax.random.PRNGKey(51), kvh, quantized)
        row = jnp.asarray(np.random.default_rng(53).integers(
            0, self.NP, P), jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(52), (1, s, h, self.HD))
        got = self._window(q, pool, 1, row, pos)
        k, v = self._dense_kv(pool, 1, row[None, :])
        cols = jnp.arange(P * self.PG)[None, None, None, :]
        rows = jnp.arange(s)[None, None, :, None]
        want = dot_product_attention(
            q, k.astype(q.dtype), v.astype(q.dtype),
            mask=jnp.where(cols <= pos + rows, 0.0, -1e9))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-6, rtol=3e-6)

    def test_gqa_window_matches_reference(self):
        kvh, h = 2, 4
        P, s, pos = 3, 6, 4
        pool = self._pool(jax.random.PRNGKey(5), kvh)
        row = jnp.asarray([1, 7, 3], jnp.int32)
        view = P * self.PG
        q = jax.random.normal(jax.random.PRNGKey(6), (1, s, h, self.HD))
        got = self._window(q, pool, 0, row, pos)
        k, v = self._dense_kv(pool, 0, row[None, :])
        cols = jnp.arange(view)[None, None, None, :]
        rows = jnp.arange(s)[None, None, :, None]
        mask = jnp.where(cols <= pos + rows, 0.0, -1e9)
        want = dot_product_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-6)

    def test_trash_pages_bitwise_inert(self):
        """Pages the table never references (the retirement trash
        mapping) must not perturb a single output bit."""
        S, P, kvh, h = 2, 3, 2, 4
        pool = self._pool(jax.random.PRNGKey(9), kvh)
        tab = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
        view = P * self.PG
        lo, hi = self._runs(np.random.default_rng(13), S, view)
        q = jax.random.normal(jax.random.PRNGKey(10), (S, 1, h, self.HD))
        base = np.asarray(self._decode(q, pool, 0, tab, lo, hi))
        trash = np.setdiff1d(np.arange(self.NP), np.asarray(tab))
        scrambled = dict(pool)
        for leaf in ("k", "v"):
            scrambled[leaf] = pool[leaf].at[:, trash].set(
                jax.random.normal(jax.random.PRNGKey(99),
                                  (self.L, trash.size, self.PG,
                                   kvh * self.HD)))
        got = np.asarray(self._decode(q, scrambled, 0, tab, lo, hi))
        assert np.array_equal(base, got)

    def test_under_jit_with_traced_layer(self):
        """The serve tier calls the kernel inside lax.scan with a traced
        layer index; pin that the scalar-prefetch operand tolerates it."""
        S, P, kvh, h = 2, 2, 2, 4
        pool = self._pool(jax.random.PRNGKey(12), kvh)
        tab = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        lo = jnp.zeros((S,), jnp.int32)
        hi = jnp.full((S,), P * self.PG, jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(13), (S, 1, h, self.HD))

        @jax.jit
        def both_layers(q, pool, tab, lo, hi):
            walk = page_walk(pool, tab, lo, hi)    # once for the scan

            def body(_, i):
                return None, paged_decode_attention(q, pool, i, walk)
            _, outs = jax.lax.scan(body, None, jnp.arange(self.L))
            return outs

        outs = both_layers(q, pool, tab, lo, hi)
        for layer in range(self.L):
            direct = self._decode(q, pool, layer, tab, lo, hi)
            np.testing.assert_allclose(np.asarray(outs[layer]),
                                       np.asarray(direct), atol=1e-6)

    def test_page_size_kernel_ok(self):
        assert page_size_kernel_ok(8) and page_size_kernel_ok(16)
        assert page_size_kernel_ok(MIN_PAGE_SIZE)
        assert not page_size_kernel_ok(4)
        assert not page_size_kernel_ok(10)
        assert not page_size_kernel_ok(0)


class TestPagedKernelDispatch:
    def test_resolve_use_paged_kernel(self, monkeypatch):
        from distributed_tensorflow_tpu.ops import attention as attn_lib
        assert attn_lib.resolve_use_paged_kernel(True, 8) is True
        assert attn_lib.resolve_use_paged_kernel(False, 99999) is False
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert attn_lib.resolve_use_paged_kernel("auto", 99999) is False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert attn_lib.resolve_use_paged_kernel("auto", 2048) is True
        assert attn_lib.resolve_use_paged_kernel("auto", 128) is False
