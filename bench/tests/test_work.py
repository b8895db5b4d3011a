"""The work functions, from shapes, on the benchmark's configurations and
on reduced ones."""
import copy

import jax
import jax.numpy as jnp
import pytest

from conftest import config
from harness import common, weights

work = common.module("metrics", "_work")

CONFIGS = ["qwen1.5-4b-1L", "mamba2-130m"]


def _program_leaves(cfg):
    from repro.models import init_model

    mcfg = common.module("families", cfg["model_type"]).program_config(cfg)
    tree = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), mcfg))
    return jax.tree_util.tree_leaves(tree)


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_count_is_the_programs_and_the_files(name):
    cfg = config(name)
    count = sum(x.size for x in _program_leaves(cfg))
    assert work.parameter_count(cfg) == count == cfg["parameters"]


def _layers(cfg, n):
    cfg = copy.deepcopy(cfg)
    key = ("num_hidden_layers" if cfg["model_type"] == "qwen2"
           else "n_layer")
    cfg["config"][key] = n
    return cfg


@pytest.mark.parametrize("name", CONFIGS)
def test_a_layer_stack_is_counted_once_per_layer(name):
    """The program scans its layers, and XLA's cost analysis counts a
    scan's body once; these functions count every layer."""
    cfg = config(name)
    one, two, four = (work.forward_flops(_layers(cfg, n), 256)
                      for n in (1, 2, 4))
    assert four - two == pytest.approx(2 * (two - one))
    assert two > one
    assert work.train_flops(cfg, 256) == pytest.approx(
        3 * work.forward_flops(cfg, 256))


def test_matmul_weights_of_qwen_are_every_matrix_once():
    cfg = common.config("qwen1.5-4b-1L")
    ref = common.module("reference", "transformer")
    shapes = ref.param_shapes(cfg["config"])
    mats = sum(int(jnp.prod(jnp.array(s))) for p, s in shapes.items()
               if len(s) == 3 or p == "embed/table")
    assert work.matmul_weights(cfg) == mats


def test_forward_flops_agree_with_xla_on_the_unrolled_reference():
    """The reference loops over layers in Python, so XLA counts each; at
    16 tokens attention is a small share, counted causal here and whole
    by XLA, and elementwise work is small beside the matmuls."""
    cfg = copy.deepcopy(common.config("qwen1.5-4b-1L"))
    cfg["config"].update(hidden_size=256, intermediate_size=512,
                         num_attention_heads=4, num_key_value_heads=4,
                         num_hidden_layers=3, vocab_size=1024)
    ref = common.module("reference", "transformer")
    params = weights.make_params(ref, cfg["config"], 0)
    toks = jnp.arange(16, dtype=jnp.int32)
    lowered = jax.jit(lambda p, t: ref.logits(p, cfg["config"], t)).lower(
        params, toks)
    xla = lowered.compile().cost_analysis()["flops"]
    assert work.forward_flops(cfg, 16) == pytest.approx(xla, rel=0.05)


def test_aggregation_work_reads_the_stack_once():
    cfg = common.config("qwen1.5-4b-1L")
    d = work.parameter_count(cfg)
    nbytes, flops = work.agg_work(cfg, 7)
    assert nbytes == 7 * d * 4 + 4 * d
    assert flops == 2 * 49 * d
