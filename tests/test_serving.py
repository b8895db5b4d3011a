"""Serving-path integration: prefill + decode must reproduce the full
forward logits, per architecture family; engine end-to-end."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import AggSpec
from repro.configs import get_reduced
from repro.dist.serve_robust import (make_robust_prefill_step,
                                     poison_replicas, replicate_params)
from repro.models import decode_step, forward, init_cache, init_model, prefill
from repro.serving import Request, ServingEngine

KEY = jax.random.PRNGKey(0)

FAMILIES = ["llama3_2_3b", "mamba2_130m", "whisper_medium",
            "jamba_1_5_large", "mixtral_8x22b", "gemma3_1b",
            "llama4_scout", "llama3_2_vision", "gemma_2b", "qwen1_5_4b"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_prefill_decode_matches_forward(arch):
    # no-drop MoE capacity: capacity-based dispatch is batch-size dependent
    # by design; exact consistency requires drop-free routing
    cfg = dataclasses.replace(get_reduced(arch), capacity_factor=100.0)
    params = init_model(KEY, cfg)
    B, S0, steps = 2, 24, 3
    S = S0 + steps
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    extra = None
    if cfg.arch_type == "audio":
        extra = jax.random.normal(KEY, (B, cfg.encoder_seq, cfg.d_model))
    elif cfg.arch_type == "vlm":
        extra = jax.random.normal(KEY, (B, cfg.vision_seq, cfg.d_model))

    full_logits, _ = forward(params, cfg, tokens, extra)
    pre_logits, cache = prefill(params, cfg, tokens[:, :S0], extra,
                                cache_len=S)
    np.testing.assert_allclose(pre_logits, full_logits[:, :S0],
                               rtol=1e-3, atol=1e-3)
    for t in range(steps):
        pos = S0 + t
        logits1, cache = decode_step(params, cfg, cache,
                                     tokens[:, pos:pos + 1], pos)
        np.testing.assert_allclose(logits1[:, 0], full_logits[:, pos],
                                   rtol=2e-3, atol=2e-3)


def test_cache_shapes_bounded_for_local_attention():
    cfg = get_reduced("mixtral_8x22b")  # swa window 64
    cache = init_cache(cfg, batch=2, cache_len=4096)
    k = cache["periods"]["s0"]["k"]
    assert k.shape[2] == cfg.window  # ring cache, not 4096


def test_serving_engine_batched_requests():
    cfg = get_reduced("llama3_2_3b")
    params = init_model(KEY, cfg)
    engine = ServingEngine(params, cfg, n_slots=3, cache_len=64)
    reqs = [Request(rid=i,
                    prompt=np.arange(5 + i) % cfg.vocab_size,
                    max_new_tokens=4 + i) for i in range(5)]
    results = engine.run(reqs, max_steps=60)
    assert set(results) == {0, 1, 2, 3, 4}
    for i, toks in results.items():
        assert len(toks) == 4 + i
        assert all(0 <= t < cfg.vocab_size for t in toks)


def test_engine_matches_stepwise_decode():
    """Engine output == hand-rolled prefill + greedy decode."""
    cfg = get_reduced("gemma_2b")
    params = init_model(KEY, cfg)
    prompt = np.asarray([1, 2, 3, 4, 5], np.int32)
    engine = ServingEngine(params, cfg, n_slots=1, cache_len=32)
    out = engine.run([Request(rid=0, prompt=prompt, max_new_tokens=5)],
                     max_steps=20)[0]

    logits, cache = prefill(params, cfg, jnp.asarray(prompt)[None],
                            cache_len=32)
    cur = int(jnp.argmax(logits[0, -1]))
    want = [cur]
    pos = len(prompt)
    for _ in range(4):
        l1, cache = decode_step(params, cfg, cache,
                                jnp.asarray([[cur]], jnp.int32), pos)
        cur = int(jnp.argmax(l1[0, 0]))
        want.append(cur)
        pos += 1
    assert out == want


def test_engine_mixed_length_slots_are_position_correct():
    """Two slots with different prompt lengths must each match their own
    single-slot decode (per-slot positions, not a shared max)."""
    cfg = get_reduced("llama3_2_3b")
    params = init_model(KEY, cfg)
    prompts = [np.asarray([1, 2, 3], np.int32),
               np.asarray([4, 5, 6, 7, 8, 9, 10], np.int32)]

    # reference: each request served alone
    want = {}
    for rid, prompt in enumerate(prompts):
        eng = ServingEngine(params, cfg, n_slots=1, cache_len=32)
        want[rid] = eng.run([Request(rid=rid, prompt=prompt,
                                     max_new_tokens=5)], max_steps=20)[rid]

    # batched: both in flight simultaneously
    eng = ServingEngine(params, cfg, n_slots=2, cache_len=32)
    got = eng.run([Request(rid=0, prompt=prompts[0], max_new_tokens=5),
                   Request(rid=1, prompt=prompts[1], max_new_tokens=5)],
                  max_steps=20)
    assert got[0] == want[0]
    assert got[1] == want[1]


def _admission_engine(mode):
    """A 3-slot engine over a reduced model: one parameter set, or seven
    jittered replicas with the last sign-flipped (Bulyan-Krum, f=1)."""
    cfg = get_reduced("llama3_2_3b")
    params = init_model(KEY, cfg)
    if mode == "plain":
        return ServingEngine(params, cfg, n_slots=3, cache_len=32), cfg
    stacked = poison_replicas(
        replicate_params(params, 7, jitter=1e-2, key=jax.random.PRNGKey(1)),
        1, "signflip", scale=10.0)
    spec = AggSpec(f=1, gar="bulyan-krum")
    return ServingEngine(stacked, cfg, n_slots=3, cache_len=32,
                         ensemble=spec), cfg


@pytest.mark.parametrize("mode", ["plain", "ensemble"])
def test_jitted_admission_matches_the_unjitted_prefill(mode):
    """Admission's compiled prefill gives the first token and the spliced
    cache of the model's prefill (or the robust prefill step) run op by
    op."""
    eng, cfg = _admission_engine(mode)
    prompt = (np.arange(9) * 7 + 3) % cfg.vocab_size
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    if mode == "plain":
        logits, ref_cache = prefill(eng.params, cfg, tokens,
                                    cache_len=eng.cache_len)
        last = logits[0, -1]
    else:
        agg, ref_cache, _ = make_robust_prefill_step(
            cfg, eng.ensemble, cache_len=eng.cache_len)(eng.params, tokens)
        last = agg[0]
    slot = 1
    eng.active[0] = Request(rid=-1, prompt=prompt, max_new_tokens=1)
    before = eng.cache
    req = Request(rid=0, prompt=prompt, max_new_tokens=4)
    assert eng.admit(req)
    assert eng.active[slot] is req
    assert req.generated == [int(jnp.argmax(last))]
    assert eng.last_token[slot] == req.generated[0]
    want = ServingEngine._spliced(before, slot, ref_cache,
                                  replicated=mode == "ensemble")
    for got, ref in zip(jax.tree_util.tree_leaves(eng.cache),
                        jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)


@pytest.mark.parametrize("mode", ["plain", "ensemble"])
def test_admission_compiles_once_per_prompt_length(mode):
    """A prompt length already seen admits with no compile, in any slot;
    a new length compiles its own prefill."""
    eng, cfg = _admission_engine(mode)

    def compiles_for(length, rid):
        before = eng.counters["compiles"]
        assert eng.admit(Request(rid=rid, prompt=np.arange(length)
                                 % cfg.vocab_size, max_new_tokens=2))
        return eng.counters["compiles"] - before

    assert compiles_for(5, 0) >= 1
    assert compiles_for(5, 1) == 0          # same length, another slot
    eng.active[0] = None
    assert compiles_for(5, 2) == 0          # same length, a reused slot
    eng.active = [None] * eng.n_slots
    assert compiles_for(8, 3) >= 1
    assert compiles_for(8, 4) == 0
    assert eng.counters["admissions"] == 5
