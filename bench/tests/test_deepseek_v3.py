"""The Kanana-2 (DeepSeek-V3 family) cell's pieces: its work functions by
hand, its readers on a recorded trace and on made-up ones, and the
host-held committee against ``committee.run_steps``."""
import copy
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import common, weights
from harness import trace as tr
from harness.peaks import peaks
from reference import committee, committee_offload

CELL = "kanana-2-30b-a3b-5L.train.route1024"
DATA = pathlib.Path(__file__).parent / "data"
MS = 1_000_000
work = common.module("metrics", "_work_deepseek_v3")
READERS = ("mfu.moe.train", "moe_ms.train", "expert_roofline.train")


def _cfg(layers=None):
    cfg = copy.deepcopy(common.config("kanana-2-30b-a3b-5L"))
    if layers is not None:
        cfg["config"]["num_hidden_layers"] = layers
    return cfg


def test_parameter_count_is_the_programs_and_the_files():
    from repro.models import init_model

    cfg = _cfg()
    mcfg = common.module("families", "deepseek_v3").program_config(cfg)
    tree = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), mcfg))
    count = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert work.parameter_count(cfg) == count == cfg["parameters"] \
        == mcfg.param_count()


def test_one_expert_layer_by_hand():
    """FLOPs of one expert layer of 1,024 tokens: latent attention's four
    matrices (2048x384, 2048x576, 512x512, 256x2048), the router's 128
    outputs, the shared experts' 3x2048x1536 and 6 x 8/128 of one held
    expert's 3x2048x768 per token, twice each, plus per causal pair and
    head 2 x (192 + 128)."""
    t = 1024
    weights_per_token = (2048 * 384 + 2048 * 576 + 512 * 512 + 256 * 2048
                         + 2048 * 128 + 3 * 2048 * 1536
                         + 6 * 8 / 128 * 3 * 2048 * 768)
    assert weights_per_token == 14_221_312
    pairs = t * (t + 1) / 2
    by_hand = 2 * weights_per_token * t + 2 * pairs * 2 * (192 + 128)
    got = work.forward_flops(_cfg(3), t) - work.forward_flops(_cfg(2), t)
    assert got == pytest.approx(by_hand)
    assert work.train_flops(_cfg(), t) == pytest.approx(
        3 * work.forward_flops(_cfg(), t))


def test_expert_work_by_hand():
    """One step of 7 workers of 1,024 tokens: 10,752 expected pairs on the
    held experts; each worker reads the 8 held experts' bf16 weights
    (75.5 MB a layer) forward and backward and writes their gradient."""
    cfg = _cfg()
    assert 7 * work.held_pairs(cfg, 1024) == 10_752
    nbytes, flops = work.expert_work(cfg, 7, 1024)
    assert nbytes == 7 * 4 * 3 * 8 * 3 * 2048 * 768 * 2
    assert flops == 3 * 2 * 10_752 * 3 * 2048 * 768


def _ctx(trace, steps=1, tokens_per_s=30_000.0):
    w = common.workload(CELL)
    red = tr.reduce(trace)
    red["steps"] = steps
    return {"cell": CELL, "cfg": common.config(w["config"]),
            "mix": common.traffic(w["traffic"]), "trace": red,
            "peaks": peaks("TPU v5 lite"), "chips": 1,
            "e2e": {"train_tokens_per_s": (tokens_per_s, "tokens/s")}}


def _made_up():
    """One 200-ms step: 10 ms of grouped matmuls (4 under their scope, 6
    in a kernel that carries its own name instead), 6 of dispatch and
    combine, the rest outside the expert layer."""
    g = "jit(step)/train/grads/while/body/"
    ops = [[0, 4 * MS, "fusion.1", "jit_step", g + "jvp()/moe/route/dot"],
           [4 * MS, 3 * MS, "fusion.2", "jit_step",
            g + "jvp()/moe/dispatch/gather"],
           [7 * MS, 4 * MS, "fusion.3", "jit_step",
            g + "transpose(jvp())/moe/experts/mul"],
           [11 * MS, 6 * MS, "ragged-dot-none.7", "jit_step",
            "ragged-dot-none"],
           [17 * MS, 3 * MS, "fusion.4", "jit_step",
            g + "transpose(jvp())/moe/combine/scatter-add"],
           [20 * MS, 180 * MS, "fusion.5", "jit_step",
            "jit(step)/agg/gram/dot_general"]]
    return {"window": [0, 200 * MS], "devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": [[0, 200 * MS, "jit_step"]]},
            "host": [[0, 200 * MS, "bench/window"]]}


def _read(name, ctx):
    return common.module("metrics", name).read(ctx)


def test_readers_on_a_made_up_step():
    c = _ctx(_made_up())
    assert _read("moe_ms.train", c) == pytest.approx(20.0)
    least = 7 * 4 * 3 * 8 * 3 * 2048 * 768 * 2 / 819e9
    assert _read("expert_roofline.train", c) == pytest.approx(
        100 * least / 0.010)
    per_token = work.train_flops(c["cfg"], 1024) / 1024
    assert _read("mfu.moe.train", c) == pytest.approx(
        100 * per_token * 30_000 / 197e12)


def test_readers_on_the_recorded_step():
    """A seventh of a step of the cell as one v5e ran it, cut to the ops of
    the expert layer (``data/trace_moe.json``; see its ``note``): every
    op is the expert layer's, scoped or a grouped-matmul kernel, and the
    shares stay within 100%."""
    rec = json.loads((DATA / "trace_moe.json").read_text())
    c = _ctx(rec, steps=rec["steps"], tokens_per_s=rec["tokens_per_s"])
    ops = rec["devices"]["/device:TPU:0"]
    assert any(op[2].startswith("ragged-dot") and "moe/" not in op[4]
               for op in ops)
    every_op_ms = sum(op[1] for op in ops) / 1e6 / rec["steps"]
    assert _read("moe_ms.train", c) == pytest.approx(every_op_ms)
    for name in READERS[::2]:
        assert 0 < _read(name, c) <= 100.0, name


@pytest.mark.parametrize("name", READERS[1:])
def test_nothing_to_read_gives_nothing(name):
    empty = {"window": [0, MS], "devices": {}, "modules": {}, "host": []}
    assert _read(name, _ctx(empty)) is None


def test_readers_leave_other_families_alone():
    c = _ctx(_made_up())
    c["cfg"] = common.config("qwen1.5-4b-1L")
    assert _read("mfu.moe.train", c) is None
    assert _read("expert_roofline.train", c) is None


def test_host_held_committee_is_the_committee():
    """At a small size, where the committee's gradients fit the device,
    both give the same losses, aggregate norms and parameters."""
    ref = common.module("reference", "deepseek_v3")
    c = copy.deepcopy(_cfg()["config"])
    c.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=16,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=12, vocab_size=128, num_hidden_layers=3,
             n_routed_experts=4, router_width=16, num_experts_per_tok=3)
    params = weights.make_params(ref, c, 7)
    rng = np.random.default_rng(3)
    batches = [{"tokens": rng.integers(0, 128, (7, 1, 16)),
                "labels": rng.integers(0, 128, (7, 1, 16))}
               for _ in range(2)]
    opt = {"lr": 3e-4, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999,
           "eps": 1e-8}
    kw = dict(f=1, margin=3.0, opt=opt)
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, params)  # noqa: E731
    with jax.default_matmul_precision("highest"):                # (donated)
        want = committee.run_steps(ref, c, fresh(), batches, **kw)
        got = committee_offload.run_steps(ref, c, fresh(), batches, **kw)
    assert got["loss"] == want["loss"]
    assert got["agg_norms"] == pytest.approx(want["agg_norms"], rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got["params"]),
                    jax.tree_util.tree_leaves(want["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
    f8 = committee_offload.run_steps(ref, c, fresh(), batches[:1],
                                     dtype=jnp.float8_e4m3fn, **kw)
    assert all(np.isfinite(f8["loss"]))


@pytest.fixture
def tiny(monkeypatch):
    """The cell at a width of 256 and 64 tokens per worker."""
    config, traffic = common.config, common.traffic

    def small_config(name):
        cfg = copy.deepcopy(config(name))
        cfg["config"].update(
            hidden_size=256, intermediate_size=512, moe_intermediate_size=64,
            kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, vocab_size=1024, router_width=32)
        return cfg

    def small_traffic(name):
        mix = copy.deepcopy(traffic(name))
        mix["tokens_per_sequence"] = 64
        return mix

    monkeypatch.setattr(common, "config", small_config)
    monkeypatch.setattr(common, "traffic", small_traffic)


def _half_batch(cfg, spec, opt):
    from repro.dist.train import make_train_step

    real = make_train_step(cfg, spec, opt)

    def step(params, state, batch):
        half = batch["tokens"].shape[-1] // 2
        return real(params, state, {k: v[..., :half] for k, v in batch.items()})

    return step


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_whole_run_decides_correct(tiny, fault):
    """A run driven on the CPU past the look for a chip: sound, it is
    correct; with half of every sequence left out under the timed path,
    it is not."""
    import time

    from harness import train_offload

    w = common.workload(CELL)
    limits = common.load_json(common.BENCH / "limits" / f"{CELL}.json")
    res, checks, *_ = train_offload.run(
        CELL, common.config(w["config"]), common.traffic(w["traffic"]),
        limits, 2**33 + 5, 1.0, False, jax.devices()[:1], time.perf_counter(),
        step_factory=_half_batch if fault else None)
    assert res["correct"] is (fault is None), checks


def test_control_in_float8_is_not_correct(tiny):
    """The reference in float8 against the reference in float32."""
    from harness import train as t
    from harness import train_offload as off

    limits = common.load_json(common.BENCH / "limits" / f"{CELL}.json")
    w = common.workload(CELL)
    cell = t.Cell(common.config(w["config"]), common.traffic(w["traffic"]))
    host = [jax.device_get(b) for b in cell.feed(5)[:t.CHECK_STEPS]]
    ref = off.reference_readings(cell, 5, host)
    ctl = off.reference_readings(cell, 5, host, dtype=jnp.float8_e4m3fn)
    nums = t.compare(ctl, ref)
    assert any(nums[k] > limits[k] for k in limits)
