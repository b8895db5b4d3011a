"""What decides ``correct``: a whole run driven on the CPU at a reduced
size, past the look for a chip, with a fault planted under the timed
path, must come out not correct; so must the control, the reference in
the precision below the configuration's put in the program's place.
The limits are the cells' own (``bench/limits``)."""
import copy
import io
import json
from contextlib import redirect_stdout

import jax.numpy as jnp
import pytest

import run
from harness import common

TRAIN = "qwen1.5-4b-1L.train.short256"
SERVE = "qwen1.5-4b-1L.serve.chat8"


def _reduce(monkeypatch, width):
    """Reduced sizes for every configuration and mix of a run."""
    config, traffic = common.config, common.traffic

    def small_config(name):
        cfg = copy.deepcopy(config(name))
        cfg["config"].update(hidden_size=width, intermediate_size=2 * width,
                             num_attention_heads=width // 128,
                             num_key_value_heads=width // 128,
                             num_hidden_layers=1, vocab_size=4 * width)
        return cfg

    def small_traffic(name):
        mix = copy.deepcopy(traffic(name))
        if mix["kind"] == "train":
            mix["tokens_per_sequence"] = 64
        else:
            mix.update(cache_len=48, arrivals_per_s=0.5)
            mix["prompt"].update(median=16, buckets=[16, 32])
            mix["output"].update(median=8, min=4, max=16)
        return mix

    monkeypatch.setattr(common, "config", small_config)
    monkeypatch.setattr(common, "traffic", small_traffic)


@pytest.fixture
def tiny(monkeypatch):
    _reduce(monkeypatch, 256)


@pytest.fixture
def tiny_wide(monkeypatch):
    """Wide enough that the logits spread about as at full width, where
    the serving control is judged."""
    _reduce(monkeypatch, 1024)


def _run(cell, trace=0, **hooks):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(2**31 + 17),
                       "--seconds", "3", "--trace", str(trace)],
                      require_tpu=False, **hooks)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _faulty_step(fault):
    from repro.dist.train import make_train_step

    def factory(cfg, spec, opt):
        real = make_train_step(cfg, spec, opt)

        def step(params, state, batch):
            if fault == "unchanged":
                return params, state, real(params, state, batch)[2]
            half = batch["tokens"].shape[-1] // 2
            return real(params, state,
                        {k: v[..., :half] for k, v in batch.items()})

        return step

    return factory


def test_drivers_refuse_more_than_one_chip():
    common.one_chip(TRAIN, [object()])
    with pytest.raises(ValueError, match="one chip"):
        common.one_chip(TRAIN, [object()] * 4)


def test_sound_train_run_is_correct(tiny):
    res = _run(TRAIN)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_traced_run_checks_alike_and_reports_its_window(tiny, monkeypatch,
                                                        cell):
    """``--trace 1`` decides ``correct`` as ``--trace 0`` does and adds
    the traced window; the CPU has no device plane, so only the metrics
    read off the host clock and shapes are there."""
    from harness import peaks

    real = peaks.peaks
    monkeypatch.setattr(peaks, "peaks", lambda kind: real("TPU v5 lite"))
    res = _run(cell, trace=1)
    assert res["correct"] is True
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    want = {m["name"] for m in common.metrics_of(cell, "per_layer")}
    assert res["metrics"] and set(res["metrics"]) <= want
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_fault_is_not_correct(tiny, fault):
    res = _run(TRAIN, step_factory=_faulty_step(fault))
    assert res["correct"] is False


def test_train_control_is_not_correct(tiny):
    """The reference in bfloat16 against the reference in float32."""
    import jax

    from harness import train

    limits = common.load_json(common.BENCH / "limits" / f"{TRAIN}.json")
    w = common.workload(TRAIN)
    cell = train.Cell(common.config(w["config"]), common.traffic(w["traffic"]))
    host = [jax.device_get(b) for b in cell.feed(5)[:train.CHECK_STEPS]]
    ref = train.reference_readings(cell, 5, host)
    ctl = train.reference_readings(cell, 5, host, dtype=jnp.bfloat16)
    nums = train.compare(ctl, ref)
    assert any(nums[k] > limits[k] for k in limits)


def test_sound_serve_run_is_correct(tiny_wide):
    assert _run(SERVE)["correct"] is True


def test_serve_token_altered_is_not_correct(tiny_wide):
    def hook(engine):
        decode = engine._decode

        def altered(*args):
            logits, cache, res, state = decode(*args)
            return logits.at[:, 1].add(1e4), cache, res, state

        engine._decode = altered

    assert _run(SERVE, serve_hook=hook)["correct"] is False


def test_serve_control_is_not_correct(tiny_wide):
    """Tokens that the reference in bfloat16 puts first, judged by the
    reference in float32, over the first six requests served.  Answers
    of 16-64 tokens put some 200 tokens in the comparison, as a run on
    the chip puts several hundred: the widest gap grows with the tokens
    compared, and over a few dozen the control can read no gap at all."""
    from harness import serve

    limits = common.load_json(common.BENCH / "limits" / f"{SERVE}.json")
    w = common.workload(SERVE)
    cell = serve.Cell(common.config(w["config"]), common.traffic(w["traffic"]))
    cell.mix["cache_len"] = 112
    cell.mix["output"].update(median=32, min=16, max=64)
    engine = cell.engine(cell.params(5))
    serve.warm_up(engine, cell.mix)
    loop = serve.Loop(engine, serve.requests(cell.mix, cell.mcfg.vocab_size,
                                             5))
    first = range(6)
    opened = 0.0
    while not all(r in loop.done for r in first) and opened < 300:
        opened += 2.0
        loop.run(opened)
    served = [loop.done[r] for r in first]
    assert sum(len(t) for _, t in served) >= 150
    assert max(serve.reference_gaps(cell, 5, served)) <= limits["logit_gap"]
    gaps = serve.reference_gaps(cell, 5, served, jnp.bfloat16)
    assert max(gaps) > limits["logit_gap"]
