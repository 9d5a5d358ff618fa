#!/usr/bin/env python3
"""Chip smoke: FedPT federated training end to end on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip: kernels + training
    python chip_smoke.py --chips 4    # only the mesh phase, on 4 chips

One chip runs three phases at the paper's published widths, all in this
one process (a chip belongs to one process at a time):

* kernels — the aggregation-tail stats/pack/apply kernels, the int8
  fake-quantize and the DP clip, at the CIFAR ResNet-18 trainable width
  with K=16 client rows, each checked against its ``kernels/ref.py``
  oracle on the same chip;
* resnet18 — ResNet-18/GroupNorm (11,172,170 parameters, the last stage
  frozen: 2,914,634 trainable) on synthetic 24x24x3 data, cohort 16,
  int8 uplink + DP clip + DP noise: 3 sync rounds through
  ``run_federated`` (fused Pallas aggregation tail), one round fused vs
  staged, and 3 async flushes through ``run_grid``;
* emnist — the documented trainer, ``launch/train.run_paper_task``, on
  the EMNIST CNN (1,690,174 parameters) for 3 rounds.

``--chips 4`` runs the ResNet-18 int8+clip+DP grid, sync and async
(2 rounds, 2 flushes), on the (data=2, model=2) debug mesh and compares
it with the same run on one device, both at the "highest" matmul
precision, to the ``tests/test_multidevice.py`` contract: bookkeeping
exact, losses and parameters to fp32 round-off (``compare_mesh_run``).

Any failed check exits nonzero. The last line of standard output is one
JSON object naming the device, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.core.partition as part  # noqa: E402
from repro.core import fedpt  # noqa: E402
from repro.core import flat as flat_lib  # noqa: E402
from repro.data import synthetic as syn  # noqa: E402
from repro.fl import runtime  # noqa: E402
from repro.kernels import agg_tail, dp_clip, ops, quantize, ref  # noqa: E402
from repro.launch import train as train_lib  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import paper_models as pm  # noqa: E402
from repro.nn import basic  # noqa: E402
from repro.sim import grid as simgrid  # noqa: E402

COHORT = 16
RC = fedpt.RoundConfig(COHORT, 2, 32, "sgdm", 10 ** -0.5, "sgdm", 0.1,
                       uplink_bits=8, dp_clip_norm=1.0,
                       dp_noise_multiplier=0.1)
FREEZE = pm.resnet18_freeze_spec((3,))
STAGED = 1 << 62          # agg_tail_threshold that forces the staged tail
# the three fused-tail kernels, by the names their pallas_calls carry
FUSED_KERNELS = ("agg_tail_stats", "agg_tail_pack", "agg_tail_apply")


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def max_rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def resnet_loss(params, b):
    lp = jax.nn.log_softmax(pm.resnet18_forward(params, b["images"]))
    return -jnp.mean(jnp.take_along_axis(lp, b["labels"][:, None], 1)), {}


def resnet_data(seed: int):
    return syn.make_federated_images(2 * COHORT, 64, (24, 24, 3), 10,
                                     test_examples=64, seed=seed)


def trainable_layout():
    y, _ = part.partition(jax.eval_shape(lambda: pm.init_resnet18(0)),
                          FREEZE)
    return flat_lib.FlatLayout.of(y)


def params_close(a, b, rtol=1e-5, atol=1e-6):
    """(all leaves allclose, max |a - b| over leaves)."""
    worst, ok = 0.0, True
    for (_, va), (_, vb) in zip(basic.flatten_params(a),
                                basic.flatten_params(b)):
        va, vb = np.asarray(va), np.asarray(vb)
        worst = max(worst, float(np.max(np.abs(va - vb))))
        ok &= bool(np.allclose(va, vb, rtol=rtol, atol=atol))
    return ok, worst


def finite_losses(history, what):
    losses = [h["loss"] for h in history]
    check(all(math.isfinite(l) for l in losses), f"{what}: losses {losses}")
    return losses


# ---------------------------------------------------------------------------
# one chip


def phase_kernels(seed: int) -> None:
    layout = trainable_layout()
    bl = layout.block_leaf()
    nl = len(layout.sizes)
    K, N, B = COHORT, layout.size, layout.align
    mat = 0.01 * jax.random.normal(jax.random.key(seed), (K, N), jnp.float32)
    log(f"[kernels] K={K} size={N} blocks={layout.num_blocks} leaves={nl}")

    bmax, bss = jax.jit(agg_tail.block_stats)(mat)
    rmax, rss = jax.jit(lambda m: ref.agg_block_stats_ref(
        m, with_sumsq=True))(mat)
    check(np.array_equal(np.asarray(bmax), np.asarray(rmax)),
          "block_stats max-abs != ref")
    e = max_rel(bss, rss)
    check(e <= 1e-6, f"block_stats sumsq rel err {e}")
    log(f"[kernels] block_stats: max-abs bitwise, sumsq rel err {e:.3g}")

    sblock = ref.agg_scales_ref(rmax, bl, 8, nl)
    q, qss = jax.jit(agg_tail.pack)(mat, sblock)
    rq = jax.jit(lambda m, s: ref.agg_pack_ref(m, s, 8))(mat, sblock)
    check(np.array_equal(np.asarray(q), np.asarray(rq)), "pack codes != ref")
    e = max_rel(qss, ref.agg_quant_sumsq_ref(rq, sblock))
    check(e <= 1e-5, f"pack quantized sumsq rel err {e}")
    log(f"[kernels] pack: int8 codes bitwise, quantized sumsq rel err "
        f"{e:.3g}")

    w = jnp.linspace(0.5, 1.5, K)
    coeff = (w / jnp.sum(w))[:, None] * sblock
    noise = 1e-3 * jax.random.normal(jax.random.key(seed + 1), (N,))
    out = jax.jit(agg_tail.apply_coeff)(q, coeff, noise)
    want = jax.jit(ref.agg_apply_ref)(rq, coeff, noise)
    e = max_rel(out, want)
    check(e <= 1e-5, f"apply_coeff rel err {e}")
    log(f"[kernels] apply_coeff: rel err {e:.3g}")

    fq = jax.jit(lambda m: quantize.fake_quantize_flat(m, bl, nl))(mat)
    rfq = jax.jit(lambda m: ref.fake_quantize_flat_ref(
        m, bl, block=B, n_leaves=nl))(mat)
    check(np.array_equal(np.asarray(fq), np.asarray(rfq)),
          "fake_quantize_flat != ref")
    log("[kernels] fake_quantize_flat: bitwise")

    # one client's delta, and a lane of them as the async lane step vmaps it
    clip = lambda v: dp_clip.clip_flat(v, 1.0)  # noqa: E731
    rclip = lambda v: ref.flat_clip_ref(v, 1.0)  # noqa: E731
    for what, f, rf, x in (("clip_flat", clip, rclip, 10.0 * mat[0]),
                           ("clip_flat vmapped", jax.vmap(clip),
                            jax.vmap(rclip), 10.0 * mat)):
        (cl, nrm), (rcl, rnrm) = jax.jit(f)(x), jax.jit(rf)(x)
        e, en = max_rel(cl, rcl), max_rel(nrm, rnrm)
        check(e <= 1e-5 and en <= 1e-5, f"{what} rel err {e}, norm {en}")
        log(f"[kernels] {what}: rel err {e:.3g}, norm rel err {en:.3g}")


def round_program_text(ds, seed: int) -> str:
    """The compiled sync round program, built as the grid builds it."""
    params = pm.init_resnet18(seed)
    y, frozen = part.partition(params, FREEZE)
    round_fn, sopt = fedpt.make_round_fn(resnet_loss, RC)
    batch, w = syn.cohort_batch(ds, np.arange(COHORT), RC.local_steps,
                                RC.local_batch, np.random.default_rng(0))
    return jax.jit(round_fn, donate_argnums=(0, 1)).lower(
        y, sopt.init(y), frozen, batch, jnp.asarray(w),
        jax.random.key(0)).compile().as_text()


def phase_resnet18(seed: int) -> None:
    ds = resnet_data(seed)
    size = trainable_layout().size
    route = ops.agg_route(COHORT, size, RC.uplink_bits)
    log(f"[resnet18] tail route for ({COHORT}, {size}) int8: {route}")
    check(route == "fused", f"route {route}")

    t = time.time()
    res = runtime.run_federated(pm.init_resnet18, resnet_loss, ds, RC, 3,
                                freeze_spec=FREEZE, seed=seed)
    losses = finite_losses(res.history, "sync")
    log(f"[resnet18] run_federated 3 sync rounds: losses {losses} "
        f"({time.time() - t:.1f} s incl. compile)")

    hlo = round_program_text(ds, seed)
    n_calls = hlo.count('custom_call_target="tpu_custom_call"')
    found = [k for k in FUSED_KERNELS if f"/{k}/pallas_call" in hlo]
    check(n_calls > 0, "no tpu_custom_call in the round program")
    check(len(found) == len(FUSED_KERNELS), f"fused kernels found: {found}")
    log(f"[resnet18] round program: tpu_custom_call x{n_calls}, "
        f"fused kernels {found}")

    one = {}
    for name, thr in (("fused", None), ("staged", STAGED)):
        gc = simgrid.GridConfig(mode="sync", agg_tail_threshold=thr)
        one[name] = simgrid.run_grid(pm.init_resnet18, resnet_loss, ds, RC,
                                     1, grid=gc, freeze_spec=FREEZE,
                                     seed=seed)
    ok, worst = params_close(one["fused"].y, one["staged"].y)
    check(ok, f"fused vs staged round: max |dy| {worst}")
    log(f"[resnet18] one round fused vs staged: max |dy| {worst:.3g} "
        "(rtol 1e-5, atol 1e-6)")

    t = time.time()
    gc = simgrid.GridConfig(mode="async", concurrency=COHORT,
                            goal_count=COHORT)
    res = simgrid.run_grid(pm.init_resnet18, resnet_loss, ds, RC, 3,
                           grid=gc, freeze_spec=FREEZE, seed=seed)
    losses = finite_losses(res.history, "async")
    check(res.dp["flushes"] == 3, f"async DP ledger {res.dp}")
    log(f"[resnet18] run_grid async 3 flushes: losses {losses}, "
        f"epsilon {res.dp['epsilon']:.4g} ({time.time() - t:.1f} s)")


def phase_emnist(seed: int) -> None:
    t = time.time()
    res = train_lib.run_paper_task("emnist", rounds=3, seed=seed, log=False)
    losses = finite_losses(res.history, "emnist")
    log(f"[emnist] run_paper_task 3 rounds: losses {losses}, "
        f"accuracy {res.history[-1].get('accuracy')} "
        f"({time.time() - t:.1f} s)")


# ---------------------------------------------------------------------------
# four chips


def phase_mesh(seed: int) -> None:
    ds = resnet_data(seed)
    failures = []
    # f32 matmuls and convolutions default to one bf16 pass on a TPU, so
    # two partitionings of one step differ at bf16 level; at "highest"
    # both sides compute in f32, which the fp32 round-off bound assumes
    with jax.default_matmul_precision("highest"):
        for mode in ("sync", "async"):
            runs, ys = {}, {}
            for mesh in (None, "debug"):
                t = time.time()
                gc = simgrid.GridConfig(mode=mode, mesh=mesh,
                                        concurrency=COHORT,
                                        goal_count=COHORT)
                ys[mesh] = [leaves64(part.partition(
                    pm.init_resnet18(seed), FREEZE)[0])]
                runs[mesh] = simgrid.run_grid(
                    pm.init_resnet18, resnet_loss, ds, RC, 2, grid=gc,
                    freeze_spec=FREEZE, seed=seed, eval_every=1,
                    eval_fn=keep_trainable(ys[mesh]))
                log(f"[mesh] {mode} mesh={mesh}: losses "
                    f"{[h['loss'] for h in runs[mesh].history]} "
                    f"({time.time() - t:.1f} s)")
            failures += compare_mesh_run(mode, runs[None], runs["debug"],
                                         ys[None], ys["debug"])
    check(not failures, "; ".join(failures))


def leaves64(tree) -> list:
    return [np.asarray(v, np.float64) for _, v in basic.flatten_params(tree)]


def keep_trainable(out: list):
    """An eval_fn that appends the trainable leaves after each round or
    flush to ``out`` and reports no metric."""
    def eval_fn(params):
        out.append(leaves64(part.partition(params, FREEZE)[0]))
        return {}
    return eval_fn


# |dy| over the leaf's largest update, binned by decade: fp32 round-off
# lands below 1e-6; one int8 code flipped in one of 16 client rows moves
# an element by about 1/(127 * 16) of a typical row's leaf maximum
DECADES = (0.0, 1e-8, 1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, np.inf)


def update_diff_decades(prev, ya, yb) -> dict:
    """Histogram of |ya - yb| / max|ya - prev| per leaf, by decade."""
    r = np.concatenate([
        np.abs(a - b).ravel() / max(float(np.max(np.abs(a - p))), 1e-30)
        for p, a, b in zip(prev, ya, yb)])
    counts = np.bincount(np.searchsorted(DECADES, r),
                         minlength=len(DECADES))
    out = {"=0": int(counts[0])}
    for lo, hi, n in zip(DECADES[:-1], DECADES[1:], counts[1:]):
        out[f"({lo:g},{hi:g}]"] = int(n)
    return out


def compare_mesh_run(mode: str, ref_run, got, ys_ref, ys_got) -> list:
    """The tests/test_multidevice.py contract: virtual clock, buffer
    fill, staleness, scheduler stats, uplink bytes and the DP ledger
    exact; every loss to rel 1e-5 and every parameter to rtol 1e-5 /
    atol 1e-6 (fp32 round-off). Logs, for each round or flush, the loss
    difference and how the parameter difference falls by decade of the
    round's update (``update_diff_decades``): differences from round-off
    alone stay below 1e-6, flipped int8 codes sit apart from them.
    Returns what failed."""
    failures = []

    def need(ok, what):
        if not ok:
            failures.append(f"{mode}: {what}")

    need(len(ref_run.history) == len(got.history), "history length")
    for r, (ha, hb) in enumerate(zip(ref_run.history, got.history), 1):
        rel = abs(ha["loss"] - hb["loss"]) / abs(ha["loss"])
        prev, ya, yb = ys_ref[r - 1], ys_ref[r], ys_got[r]
        out = [int(np.count_nonzero(~np.isclose(b, a, rtol=1e-5,
                                                atol=1e-6)))
               for a, b in zip(ya, yb)]
        log(f"[mesh] {mode} round {r}: loss rel diff {rel!r}; params "
            f"outside rtol 1e-5/atol 1e-6: {sum(out)} of "
            f"{sum(a.size for a in ya)}, max |dy| "
            f"{max(float(np.max(np.abs(a - b))) for a, b in zip(ya, yb))!r}"
            f"; |dy|/max|update| by decade "
            f"{update_diff_decades(prev, ya, yb)}")
        for k in ("virtual_seconds", "buffer_fill", "staleness_mean",
                  "staleness_max"):
            need(ha.get(k) == hb.get(k), f"round {r} {k} differs")
        need(math.isclose(ha["loss"], hb["loss"], rel_tol=1e-5,
                          abs_tol=1e-6),
             f"round {r} loss {ha['loss']!r} vs {hb['loss']!r}")
        need(sum(out) == 0, f"round {r}: {sum(out)} params outside "
             "rtol 1e-5/atol 1e-6")
    need(ref_run.scheduler_stats == got.scheduler_stats,
         "scheduler stats differ")
    need(ref_run.comm.measured_up_bytes == got.comm.measured_up_bytes,
         "uplink bytes differ")
    need(ref_run.dp == got.dp, f"DP ledger {ref_run.dp} vs {got.dp}")
    log(f"[mesh] {mode}: " + ("; ".join(failures) if failures else
                              "agrees to fp32 round-off, clock, "
                              "staleness, bytes and DP ledger exact"))
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: jax found {devs[0].platform} devices")
        return 2
    if len(devs) < args.chips:
        log(f"need {args.chips} chips, jax found {len(devs)}")
        return 2
    log(f"cache: {enable_compile_cache()}")
    log(f"device: {devs[0].device_kind} x{len(devs)}")

    phases = ([phase_mesh] if args.chips == 4
              else [phase_kernels, phase_resnet18, phase_emnist])
    t0 = time.time()
    for phase in phases:
        t = time.time()
        phase(args.seed)
        log(f"[{phase.__name__}] ok in {time.time() - t:.1f} s")
    log(f"all phases ok in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
