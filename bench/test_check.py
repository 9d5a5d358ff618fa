"""The correctness check must pass the program and fail its control and
every planted fault.

Each cell is run here at a size the CPU holds (its configuration's widths
cut, its cohort and population cut, one local step so that round-off
grows through the rounds alone), through the harness's own run with the
chip check skipped. The limits at this size come from readings on
another seed by the rule the committed limits come from
(``calibrate.set_limits``):

* the program as it is: ``correct`` true;
* the control, the reference one precision step below the
  configuration's put in the program's place: ``correct`` false;
* the timed path broken underneath, once per fault a training cell can
  have on one chip: the round returns its state unchanged; each
  minibatch's loss taken over half of it; one leaf's update applied
  twice (the answer altered where it is produced): ``correct`` false.
"""
import functools
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import harness
import reference as ref_lib
from repro.core import fedpt

CELLS = [w["name"] for w in harness.load_json(
    os.path.join(harness.ROOT, "BENCHMARK.json"))["workloads"]]
CONFIGS = sorted(glob.glob(os.path.join(harness.BENCH, "configs", "*.json")))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2 ** 31 + 12345
LIMIT_SEED = 2 ** 31 + 77


def small_config(cfg: dict) -> dict:
    """A configuration with its widths cut to a size the CPU holds."""
    cfg = dict(cfg, clients=16, examples_per_client=40, test_examples=64)
    if cfg["name"] == "emnist-cnn":
        cfg.update(conv_channels=[4, 8], dense_width=32)
    else:
        cfg.update(stem_channels=8, stages=[[8, 1], [16, 2], [64, 2],
                                            [64, 2]])
    return cfg


def small_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.cfg = small_config(cell.cfg)
    cell.mix = dict(cell.mix, cohort=min(cell.mix["cohort"], 4),
                    local_steps=1)
    return cell


def setup(cell, seed):
    data = harness.make_data(cell.cfg, seed)
    params = harness.make_params(cell, seed)
    host0 = {p: np.asarray(v) for p, v in
             harness.common.flatten(params).items()}
    return data, host0, harness.trainable_paths(cell, params)


def readings(cell, seed, runs):
    """The compared numbers of each of ``runs`` (name -> Reference
    keyword arguments, or ``None`` for the program) against the float32
    reference."""
    data, host0, trainable = setup(cell, seed)
    ys, noise = ref_lib.Reference(cell.model, cell.cfg, cell.mix, data,
                                  host0, trainable, seed).run(
        harness.CHECK_STEPS)
    y0 = {p: host0[p] for p in trainable}
    frozen = {p: v for p, v in host0.items() if p not in y0}
    out = {}
    for name, kw in runs.items():
        got = (calibrate.program_readings(
            cell, seed, cell.cfg["matmul_precision"], data, host0, trainable)
            if kw is None else ref_lib.Reference(
                cell.model, cell.cfg, cell.mix, data, host0, trainable, seed,
                **kw).run(harness.CHECK_STEPS)[0])
        out[name] = ref_lib.compare(cell.model, cell.cfg, y0, frozen, got,
                                    ys, noise, data.test_images,
                                    data.test_labels)
    return out


def control_kw(cell):
    return {"precision": calibrate.CONTROL[cell.cfg["matmul_precision"]]}


@functools.lru_cache(maxsize=None)
def small_limits(name: str) -> dict:
    """Limits at the test size, by ``calibrate.set_limits`` from the
    program, the control and the faults on ``LIMIT_SEED``."""
    cell = small_cell(name)
    runs = {"program": None, "control": control_kw(cell)}
    runs.update({f: {"fault": f} for f in calibrate.FAULTS})
    limits = calibrate.set_limits([readings(cell, LIMIT_SEED, runs)])
    assert any(limits[n]["limit"] is not None for n in harness.COMPARED), \
        json.dumps(limits)
    return limits


def break_round(monkeypatch, kind: str, first_leaf: str):
    orig = fedpt.make_round_fn

    def make(loss_fn, rc, **kw):
        step, sopt = orig(loss_fn, rc, **kw)

        def broken(y, s, frozen, batch, w, rng):
            if kind == "unchanged":
                return y, s, step(y, s, frozen, batch, w, rng)[2]
            if kind == "halfbatch":
                b = batch["labels"].shape[2] // 2
                return step(y, s, frozen,
                            {k: v[:, :, :b] for k, v in batch.items()}, w,
                            rng)
            y2, s2, m = step(y, s, frozen, batch, w, rng)
            flat, flat2 = harness.common.flatten(y), harness.common.flatten(
                y2)
            flat2[first_leaf] = flat[first_leaf] + 2 * (flat2[first_leaf]
                                                        - flat[first_leaf])
            return harness.common.nest(flat2), s2, m
        return broken, sopt

    monkeypatch.setattr(fedpt, "make_round_fn", make)


def run_small(cell):
    cell.limits = small_limits(cell.name)
    return harness.run(cell, SEED, 0.3, False, time.time(), PEAKS)


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    res = run_small(small_cell(name))
    assert res["correct"], json.dumps(res["checks"])


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = small_cell(name)
    got = readings(cell, SEED, {"control": control_kw(cell)})["control"]
    checks = harness.checks_from(got, small_limits(name))
    assert not harness.is_correct(checks), json.dumps(checks)


@pytest.mark.parametrize("kind", ["unchanged", "halfbatch", "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(monkeypatch, name, kind):
    cell = small_cell(name)
    params = jax.eval_shape(lambda: harness.make_params(cell, 0))
    break_round(monkeypatch, kind, harness.trainable_paths(cell, params)[0])
    res = run_small(cell)
    assert not res["correct"], json.dumps(res["checks"])


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_reference_forward_matches_the_program(path):
    """At float32 on the CPU the plain forward and the program's agree."""
    cfg, model = harness.load_config(path)
    cfg = small_config(cfg)
    cell = harness.Cell(cfg["name"], 1, cfg, model, {"freeze": "none"},
                        None, [], [])
    params = harness.make_params(cell, 3)
    x = jax.random.normal(jax.random.key(4), (4,) + tuple(cfg["image_shape"]))
    want = model.program_forward()(params, x)
    got = model.reference_logits(harness.common.flatten(params), x, cfg,
                                 jnp.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
