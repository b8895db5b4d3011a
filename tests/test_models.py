"""Per-architecture smoke tests (deliverable f): reduced same-family
variants run one forward + one Byzantine train step on CPU, asserting
output shapes and finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import AggSpec
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.dist.train import make_train_step
from repro.models import forward, init_model
from repro.models.attention import attention_blockwise, attention_naive
from repro.models.ssm import ssd_chunked
from repro.optim import get_optimizer

KEY = jax.random.PRNGKey(0)


def _inputs(cfg, b=2, s=64):
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    extra = None
    if cfg.arch_type == "audio":
        extra = jax.random.normal(KEY, (b, cfg.encoder_seq, cfg.d_model))
    elif cfg.arch_type == "vlm":
        extra = jax.random.normal(KEY, (b, cfg.vision_seq, cfg.d_model))
    return tokens, extra


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch):
        cfg = get_reduced(arch)
        params = init_model(KEY, cfg)
        tokens, extra = _inputs(cfg)
        logits, aux = forward(params, cfg, tokens, extra)
        assert logits.shape == (2, 64, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))
        assert bool(jnp.isfinite(aux))

    def test_one_byzantine_train_step(self, arch):
        cfg = get_reduced(arch)
        params = init_model(KEY, cfg)
        n, f, b, s = 7, 1, 1, 32
        batch = {
            "tokens": jax.random.randint(KEY, (n, b, s), 0, cfg.vocab_size),
            "labels": jax.random.randint(KEY, (n, b, s), 0, cfg.vocab_size),
        }
        if cfg.arch_type == "audio":
            batch["extra"] = jax.random.normal(
                KEY, (n, b, cfg.encoder_seq, cfg.d_model))
        elif cfg.arch_type == "vlm":
            batch["extra"] = jax.random.normal(
                KEY, (n, b, cfg.vision_seq, cfg.d_model))
        opt = get_optimizer("sgd", 1e-2)
        spec = AggSpec(f=f, gar="bulyan-krum",
                       attack="omniscient_linf")
        step = jax.jit(make_train_step(cfg, spec, opt))
        new_params, _, metrics = step(params, opt.init(params), batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert bool(jnp.isfinite(metrics["grad_norm"]))
        # params actually moved
        moved = any(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b_.astype(jnp.float32)))) > 0
            for a, b_ in zip(jax.tree_util.tree_leaves(params),
                             jax.tree_util.tree_leaves(new_params)))
        assert moved


class TestFullConfigsAnalytic:
    """Full configs are exercised via the dry-run; here we sanity-check the
    analytic parameter counts against the assignment's scale labels."""

    def test_param_counts_in_expected_range(self):
        expect = {
            "mixtral-8x22b": (120e9, 160e9),
            "mamba2-130m": (0.08e9, 0.2e9),
            "jamba-1.5-large-398b": (300e9, 480e9),
            "gemma-2b": (1.5e9, 3.5e9),
            "whisper-medium": (0.6e9, 0.9e9),  # 769M (enc+dec)
            "llama3.2-3b": (2.2e9, 4.5e9),
            "qwen1.5-4b": (2.5e9, 5e9),
            "gemma3-1b": (0.7e9, 1.7e9),
            "llama4-scout-17b-a16e": (90e9, 120e9),
            "llama-3.2-vision-11b": (8e9, 13e9),
        }
        for name, (lo, hi) in expect.items():
            n = get_config(name).param_count()
            assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]B"

    def test_moe_active_less_than_total(self):
        for name in ("mixtral-8x22b", "llama4-scout-17b-a16e",
                     "jamba-1.5-large-398b"):
            cfg = get_config(name)
            assert cfg.active_param_count() < 0.55 * cfg.param_count()


class TestAttentionVariants:
    def test_blockwise_matches_naive_causal(self):
        q = jax.random.normal(KEY, (2, 256, 8, 32))
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 256, 2, 32))
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 256, 2, 32))
        a = attention_naive(q, k, v, kind="attn")
        b = attention_blockwise(q, k, v, kind="attn", block_q=64, block_k=64)
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("kind,kw", [("swa", {"window": 96}),
                                         ("chunked", {"chunk": 128})])
    def test_blockwise_matches_naive_local(self, kind, kw):
        q = jax.random.normal(KEY, (1, 256, 4, 16))
        k = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 256, 4, 16))
        v = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 256, 4, 16))
        a = attention_naive(q, k, v, kind=kind, **kw)
        b = attention_blockwise(q, k, v, kind=kind, block_q=64, block_k=64,
                                **kw)
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


class TestSSD:
    def test_chunked_matches_sequential_recurrence(self):
        b, s, h, p, n = 2, 64, 3, 8, 16
        k = jax.random.PRNGKey(5)
        x = jax.random.normal(k, (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(k, 1),
                                               (b, s, h)))
        A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 2), (h,)))
        B = jax.random.normal(jax.random.fold_in(k, 3), (b, s, n))
        C = jax.random.normal(jax.random.fold_in(k, 4), (b, s, n))
        y = ssd_chunked(x, dt, A, B, C, chunk=16)
        # sequential oracle
        H = jnp.zeros((b, h, n, p))
        ys = []
        for t in range(s):
            decay = jnp.exp(dt[:, t] * A[None, :])
            inc = jnp.einsum("bn,bhp->bhnp", B[:, t],
                             x[:, t] * dt[:, t][..., None])
            H = H * decay[:, :, None, None] + inc
            ys.append(jnp.einsum("bn,bhnp->bhp", C[:, t], H))
        want = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(y, want, rtol=1e-3, atol=1e-3)

    def test_chunk_size_invariance(self):
        b, s, h, p, n = 1, 48, 2, 4, 8
        k = jax.random.PRNGKey(6)
        x = jax.random.normal(k, (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(k, (b, s, h)))
        A = -jnp.ones((h,))
        B = jax.random.normal(k, (b, s, n))
        C = jax.random.normal(k, (b, s, n))
        y1 = ssd_chunked(x, dt, A, B, C, chunk=8)
        y2 = ssd_chunked(x, dt, A, B, C, chunk=48)
        y3 = ssd_chunked(x, dt, A, B, C, chunk=32)  # forces padding
        np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(y1, y3, rtol=1e-4, atol=1e-4)
