"""Latent attention (``mla`` slots), leading dense layers and the dropless
held-share expert layer against the plain float32 reference
(``bench/reference/deepseek_v3.py``), at a small size on the CPU with
weights the benchmark's seeded generator draws from the reference's
layout."""
import copy
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "bench") not in sys.path:
    sys.path.insert(0, str(ROOT / "bench"))

from families import deepseek_v3 as family  # noqa: E402
from harness import weights  # noqa: E402
from reference import deepseek_v3 as ref  # noqa: E402

from repro.agg import AggSpec  # noqa: E402
from repro.dist.train import make_loss_fn, make_train_step  # noqa: E402
from repro.models import decode, moe  # noqa: E402
from repro.models.attention import (attention_blockwise,  # noqa: E402
                                    attention_naive, rope)
from repro.models.transformer import forward_with_loads  # noqa: E402
from repro.optim import get_optimizer  # noqa: E402

S = 24


def _cfg(**over):
    """The benchmark's Kanana-2 file at a small width: 2 held heads, 4 held
    experts of a 16-expert router, one dense layer then two expert layers."""
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "kanana-2-30b-a3b-5L.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["config"].update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, vocab_size=128, num_hidden_layers=3,
        n_routed_experts=4, router_width=16, held_expert_offset=4,
        num_experts_per_tok=3)
    cfg["config"].update(over)
    cfg["precision"] = {"param_dtype": "float32", "matmul_precision":
                        "highest"}
    return cfg


def _params(cfg, seed=3, bias_scale=0.05):
    """Seeded weights; ``e_score_correction_bias`` drawn non-zero so that
    the selection (score + bias) and the weights (score) differ."""
    c = cfg["config"]
    p = weights.make_params(ref, c, seed)
    key = jax.random.PRNGKey(seed + 100)
    bias = p["periods"]["s0"]["moe"]["e_score_correction_bias"]
    p["periods"]["s0"]["moe"]["e_score_correction_bias"] = (
        bias_scale * jax.random.normal(key, bias.shape))
    return p


def _tokens(cfg, seed=1, n=1):
    v = cfg["config"]["vocab_size"]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.randint(k1, (n, S), 0, v),
            jax.random.randint(k2, (n, S), 0, v))


def _program_loss(cfg):
    loss = make_loss_fn(family.program_config(cfg))
    return lambda p, t, l: loss(dict(p, tail={}), t[None], l[None])


@pytest.mark.parametrize("what", ["logits", "loss", "grads"])
def test_program_matches_reference(what):
    cfg = _cfg()
    mcfg, c = family.program_config(cfg), cfg["config"]
    p = _params(cfg)
    toks, labs = _tokens(cfg)
    with jax.default_matmul_precision("highest"):
        if what == "logits":
            got = forward_with_loads(dict(p, tail={}), mcfg, toks)[0][0]
            want = ref.logits(p, c, toks[0])
        elif what == "loss":
            got = _program_loss(cfg)(p, toks[0], labs[0])
            want = ref.loss(p, c, toks[0], labs[0])
        else:
            got = jax.grad(_program_loss(cfg))(p, toks[0], labs[0])
            want = jax.grad(ref.loss)(p, c, toks[0], labs[0])
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5 * scale, err_msg=str(path))


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """Each of 16 chips holds 2 of 32 experts: the routed parts of their
    outputs, plus the shared experts once, are the uncut layer's."""
    whole = _cfg(n_routed_experts=32, router_width=32, held_expert_offset=0)
    m = ref.dims(whole["config"])
    p = _params(whole)["periods"]["s0"]["moe"]
    p = jax.tree_util.tree_map(lambda t: t[0], p)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, S, m["d"]))
    shared = ref.swiglu(p["shared"], x[0])
    total = shared
    with jax.default_matmul_precision("highest"):
        for share in range(16):
            held = dict(p, experts=jax.tree_util.tree_map(
                lambda t, s=share: t[2 * s:2 * s + 2], p["experts"]))
            out, load = moe.moe_dropless(
                held, x, top_k=m["top_k"], act="swiglu", offset=2 * share,
                score="sigmoid", norm_topk=True, scale=m["scale"])
            total = total + (out[0] - shared)
        want = ref.expert_layer(p, x[0], m)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def _poisoned(real):
    """``ragged_dot`` whose rows past the groups are NaN, forward and in
    the input's cotangent, as a kernel that leaves them unwritten may."""
    def poison(x, gs):
        past = jnp.arange(x.shape[0]) >= jnp.sum(gs)
        return jnp.where(past[:, None], jnp.nan, x)

    @jax.custom_vjp
    def rd(lhs, rhs, gs):
        return poison(real(lhs, rhs, gs), gs)

    def fwd(lhs, rhs, gs):
        return rd(lhs, rhs, gs), (lhs, rhs, gs)

    def bwd(res, ct):
        lhs, rhs, gs = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, gs), lhs, rhs)[1](ct)
        return poison(d_lhs, gs), d_rhs, None

    rd.defvjp(fwd, bwd)
    return lambda lhs, rhs, group_sizes: rd(lhs, rhs, group_sizes)


def test_rows_past_the_groups_never_reach_the_results(monkeypatch):
    cfg = _cfg()
    p = _params(cfg)
    toks, labs = _tokens(cfg)
    loss = jax.value_and_grad(_program_loss(cfg))
    want = loss(p, toks[0], labs[0])
    monkeypatch.setattr(jax.lax, "ragged_dot", _poisoned(jax.lax.ragged_dot))
    got = loss(p, toks[0], labs[0])
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-8)


@pytest.mark.parametrize("dim", [8, 64])
def test_interleaved_rope_rotates_each_pair(dim):
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 7, 3, dim))
    pos = jnp.arange(7)
    got = np.asarray(rope(x, pos, 1e6, interleave=True))
    xs, want = np.asarray(x, np.float64), np.empty((1, 7, 3, dim))
    for s in range(7):
        for i in range(dim // 2):
            a = s * 1e6 ** (-2.0 * i / dim)
            e, o = xs[0, s, :, 2 * i], xs[0, s, :, 2 * i + 1]
            want[0, s, :, 2 * i] = e * math.cos(a) - o * math.sin(a)
            want[0, s, :, 2 * i + 1] = o * math.cos(a) + e * math.sin(a)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_attention_paths_agree_when_v_is_narrower():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 40, 4, 24))
    k = jax.random.normal(ks[1], (2, 40, 2, 24))
    v = jax.random.normal(ks[2], (2, 40, 2, 16))
    a = attention_naive(q, k, v)
    b = attention_blockwise(q, k, v, block_q=16, block_k=8)
    assert a.shape == b.shape == (2, 40, 4, 16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)


def test_unreached_expert_gets_exact_zeros_and_bf16_step_stays_finite():
    """A held expert no worker's tokens reach has exactly zero gradient
    rows, and Bulyan-Krum over a bf16 tree with such rows (and honest
    workers that differ there from the injected one) gives finite
    bf16 parameters; the step reports those rows as idle."""
    cfg = _cfg()
    cfg["precision"]["param_dtype"] = "bfloat16"
    mcfg = family.program_config(cfg)
    p = _params(cfg)
    bias = p["periods"]["s0"]["moe"]["e_score_correction_bias"]
    p["periods"]["s0"]["moe"]["e_score_correction_bias"] = bias.at[:, 5].set(
        -1e4)                                  # held expert 1: never chosen
    p = jax.tree_util.tree_map(lambda t: t.astype(jnp.bfloat16), p)
    p["tail"] = {}
    n = 7
    toks, labs = _tokens(cfg, n=n)
    batch = {"tokens": toks[:, None], "labels": labs[:, None]}

    grads = jax.grad(_program_loss(cfg))(
        {k: v for k, v in p.items() if k != "tail"}, toks[0], labs[0])
    ex = grads["periods"]["s0"]["moe"]["experts"]
    for name in ("wi", "wg", "wo"):
        assert ex[name].dtype == jnp.bfloat16
        assert not np.any(np.asarray(ex[name][:, 1], np.float32))
        assert np.any(np.asarray(ex[name][:, [0, 2, 3]], np.float32))

    opt = get_optimizer("adamw", 3e-4, weight_decay=0.01)
    spec = AggSpec(f=1, gar="bulyan-krum", attack="omniscient_linf",
                   attack_kwargs=(("margin", 3.0),))
    step = jax.jit(make_train_step(mcfg, spec, opt))
    new, _, metrics = step(p, opt.init(p), batch)
    for leaf in jax.tree_util.tree_leaves(new):
        assert leaf.dtype == jnp.bfloat16
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))
    layers_moe = mcfg.n_layers - mcfg.dense_lead
    assert float(metrics["moe_idle_experts"]) >= n * layers_moe
    assert 0 < float(metrics["moe_held_tokens"]) <= n * S * 3 * layers_moe
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0


def test_held_token_metrics_count_the_routed_pairs():
    """``moe_dropless``'s load is the number of (token, slot) pairs whose
    chosen expert is held, per held expert, as the routing gives it."""
    cfg = _cfg()
    m = ref.dims(cfg["config"])
    p = jax.tree_util.tree_map(lambda t: t[0],
                               _params(cfg)["periods"]["s0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, S, m["d"]))
    _, load = moe.moe_dropless(p, x, top_k=m["top_k"], act="swiglu",
                               offset=m["offset"], scale=m["scale"])
    idx, _ = ref.route(p, x[0], m)
    want = [int(jnp.sum(idx == m["offset"] + e)) for e in range(4)]
    assert load.tolist() == want

    from repro.obs.schema import moe_metrics
    loads = jnp.stack([load[None], jnp.zeros_like(load)[None]])
    got = moe_metrics(loads)
    assert float(got["moe_held_tokens"]) == sum(want)
    assert float(got["moe_idle_experts"]) == 4 + want.count(0)
    assert float(got["moe_load_max_over_mean"]) == pytest.approx(
        max(want) / (sum(want) / 8))


@pytest.mark.parametrize("over", [
    {}, {"first_k_dense_replace": 0, "num_hidden_layers": 2},
    {"first_k_dense_replace": 2, "num_hidden_layers": 4},
    {"n_routed_experts": 2, "router_width": 8, "held_expert_offset": 6}])
def test_param_count_is_the_references_leaf_count(over):
    cfg = _cfg(**over)
    count = sum(math.prod(s) for s in
                ref.param_shapes(cfg["config"]).values())
    assert family.program_config(cfg).param_count() == count


def test_the_benchmark_configuration_counts_its_stated_parameters():
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "kanana-2-30b-a3b-5L.json").read_text())
    assert family.program_config(cfg).param_count() == cfg["parameters"]


@pytest.mark.parametrize("entry", ["init_cache", "decode_step", "prefill",
                                   "verify_step"])
def test_decode_refuses_latent_attention(entry):
    mcfg = family.program_config(_cfg())
    calls = {
        "init_cache": lambda: decode.init_cache(mcfg, 1, 16),
        "decode_step": lambda: decode.decode_step({}, mcfg, {}, None, 0),
        "prefill": lambda: decode.prefill({}, mcfg, jnp.zeros((1, 4),
                                                             jnp.int32)),
        "verify_step": lambda: decode.verify_step({}, mcfg, {}, None, 0)}
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        calls[entry]()


def test_held_shares_need_the_dropless_layer():
    from repro.models import ModelConfig

    with pytest.raises(ValueError, match="dropless"):
        ModelConfig(name="x", arch_type="moe", n_layers=2, d_model=64,
                    n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                    moe_experts=4, moe_top_k=2, moe_router_experts=16,
                    moe_impl="scatter")
