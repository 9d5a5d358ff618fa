"""The correctness check must pass the program and fail its control and
every planted fault.

Each cell is run here at a size the CPU holds (its configuration's
``small`` size, its cohort cut, one local step so that round-off grows
through the rounds alone), through the harness's own run with the chip
check skipped; so is the token configuration, which has no cell yet,
under a mix of this file's own. The limits at this size come from
readings on another seed by the rule the committed limits come from
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

# the token configuration through the whole check, before it has a cell:
# sync, int8 uplink, clip and DP noise, each block's first FFN dense frozen
TOKEN_CASE = "so-transformer.sync-int8dp"
TOKEN_MIX = {"mode": "sync", "cohort": 4, "local_steps": 1, "local_batch": 4,
             "client_opt": "sgd", "client_lr": 0.0316, "server_opt": "sgdm",
             "server_lr": 1.0, "server_momentum": 0.9, "uplink_bits": 8,
             "dp_clip_norm": 1.0, "dp_noise_multiplier": 0.1,
             "freeze": "fedpt", "fleet": "uniform", "warmup_updates": 5}
CASES = CELLS + [TOKEN_CASE]


def small_cell(name: str) -> harness.Cell:
    if name == TOKEN_CASE:
        cfg, model = harness.load_config(
            os.path.join(harness.BENCH, "configs", "so-transformer.json"))
        cell = harness.Cell(name, 1, cfg, model, TOKEN_MIX, None, [], [])
    else:
        cell = harness.load_cell(name)
    cell.cfg = cell.model.small(cell.cfg)
    cell.mix = dict(cell.mix, cohort=min(cell.mix["cohort"], 4),
                    local_steps=1)
    return cell


def setup(cell, seed):
    data = cell.model.TASK.make(cell.cfg, seed)
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
                                    ys, noise, cell.model.TASK.test(data))
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
                b = jax.tree.leaves(batch)[0].shape[2] // 2
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


@pytest.mark.parametrize("name", CASES)
def test_program_is_correct(name):
    res = run_small(small_cell(name))
    assert res["correct"], json.dumps(res["checks"])


@pytest.mark.parametrize("name", CASES)
def test_control_is_not_correct(name):
    cell = small_cell(name)
    got = readings(cell, SEED, {"control": control_kw(cell)})["control"]
    checks = harness.checks_from(got, small_limits(name))
    assert not harness.is_correct(checks), json.dumps(checks)


@pytest.mark.parametrize("kind", ["unchanged", "halfbatch", "altered"])
@pytest.mark.parametrize("name", CASES)
def test_fault_is_not_correct(monkeypatch, name, kind):
    cell = small_cell(name)
    params = jax.eval_shape(lambda: harness.make_params(cell, 0))
    break_round(monkeypatch, kind, harness.trainable_paths(cell, params)[0])
    res = run_small(cell)
    assert not res["correct"], json.dumps(res["checks"])


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_reference_forward_matches_the_program(path):
    """At float32 on the CPU the plain loss and its gradient agree with
    the program's, on held-out examples of the small size."""
    cfg, model = harness.load_config(path)
    cfg = model.small(cfg)
    cell = harness.Cell(cfg["name"], 1, cfg, model, {"freeze": "none"},
                        None, [], [])
    params = harness.make_params(cell, 3)
    batch = jax.tree.map(lambda v: jnp.asarray(v[:4]),
                         model.TASK.test(model.TASK.make(cfg, 4)))
    want, gwant = jax.value_and_grad(
        lambda p: model.program_loss()(p, batch)[0])(params)
    got, ggot = jax.value_and_grad(
        lambda p: model.reference_loss(p, batch, cfg, jnp.float32))(
        harness.common.flatten(params))
    np.testing.assert_allclose(got, want, rtol=2e-5)
    gwant = harness.common.flatten(gwant)
    assert sorted(ggot) == sorted(gwant)
    # each leaf is held to its own scale, but one whose reference gradient
    # is nought to rounding (a key's bias under softmax) to the median
    # leaf's
    scale = {k: float(np.abs(g).max()) for k, g in ggot.items()}
    med = float(np.median(list(scale.values())))
    roundoff = sorted(k for k, v in scale.items() if v < 1e-6 * med)
    for k, g in gwant.items():
        floor = med if k in roundoff else scale[k]
        np.testing.assert_allclose(
            ggot[k], g, rtol=2e-5, atol=2e-5 * floor,
            err_msg=f"{k}; held to the median: {roundoff}")


def stacked_mean(mix: dict, uploads):
    """The round's mean upload over the (cohort, ...) stack of every
    client's delta: quantized per client and leaf, clipped by the row's
    norm, contracted with the weights."""
    K = len(uploads)
    bits, clip = mix["uplink_bits"], mix["dp_clip_norm"]
    deltas = {k: jnp.stack([d[k] for d, _ in uploads]) for k in uploads[0][0]}
    if bits:
        deltas = {k: jax.vmap(lambda v: ref_lib.fake_quantize(v, bits))(d)
                  for k, d in deltas.items()}
    if clip > 0:
        sq = sum(jnp.sum(d.reshape(K, -1) ** 2, axis=1)
                 for d in deltas.values())
        w = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq), 1e-12))
        wsum = float(K)
    else:
        w = jnp.asarray([n for _, n in uploads], jnp.float32)
        wsum = jnp.sum(w)
    return {k: jnp.tensordot(w, d, axes=1) / wsum for k, d in deltas.items()}


@pytest.mark.parametrize("clip", [0.0, 1e-3])
def test_streamed_aggregate_matches_the_stacked_one(clip):
    """The reference folds each upload into the round's sum as it comes;
    that equals the mean over the stack, with the int8 uplink and, with a
    clip that binds every client, DP noise on."""
    cell = small_cell("emnist.sync.c256-ft-int8dp")
    cell.mix = dict(cell.mix, dp_clip_norm=clip, uplink_bits=8,
                    dp_noise_multiplier=0.1 if clip else 0.0)
    data, host0, trainable = setup(cell, SEED)
    ref = ref_lib.Reference(cell.model, cell.cfg, cell.mix, data, host0,
                            trainable, SEED)
    rng = np.random.default_rng(SEED)
    y = {k: jnp.asarray(v) for k, v in ref.y0.items()}
    cids = rng.choice(data.num_clients, cell.mix["cohort"], replace=False)
    with jax.default_matmul_precision("highest"):
        uploads = list(ref.uploads(rng, y, cids))
        streamed = ref.aggregate(uploads)
        stacked = stacked_mean(cell.mix, uploads)
        zero = {k: jnp.zeros_like(v) for k, v in y.items()}
        key = jax.random.key(5)
        # the server's momentum after one step from zero: minus the noised
        # mean (the new weights would round it against the old)
        steps = [ref._server(y, zero, zero, zero, agg, key)[1]
                 for agg in (streamed, stacked)]
    norms = [np.sqrt(sum(float(jnp.sum(v ** 2)) for v in d.values()))
             for d, _ in uploads]
    assert min(norms) > 10 * clip
    assert (ref._noise(key) is not None) == bool(clip)

    def rel(a, b):
        """The relative gap of two parameter trees, as flat vectors."""
        return float(np.sqrt(sum(jnp.sum((a[k] - b[k]) ** 2) for k in b))
                     / np.sqrt(sum(jnp.sum(b[k] ** 2) for k in b)))

    assert rel(streamed, stacked) <= 1e-6
    assert rel(*steps) <= 1e-6
