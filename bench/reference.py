"""Plain reference of synchronous federated rounds, and the comparison
that decides ``correct``.

It imports nothing of the program. It follows the semantics the
simulation promises for a synchronous round on a uniform fleet
(``sim/grid.run_grid`` with ``GridConfig(mode="sync")``):

* cohort and minibatches from ``numpy.random.default_rng(seed + 77)``:
  each round draws ``choice(N, cohort, replace=False)``, then for each
  client in that order ``integers(0, n_client, (local_steps, batch))``;
* every client runs ``local_steps`` of SGD of the configuration's
  ``reference_loss`` on the trainable leaves, the frozen ones held
  fixed, and uploads its delta;
* with an int-k uplink, each (client, leaf) is fake-quantized with the
  scale max|delta| / (2^(k-1) - 1); with a DP clip the row is scaled to
  norm at most the clip; the mean is over the cohort (fixed denominator
  under DP, example-count weights otherwise), summed in cohort order as
  each client's delta is computed;
* DP noise ``sigma * normal(key(seed * 100003 + r), (size,))`` with
  ``sigma = z * clip / cohort`` over the flat buffer: leaves in the
  pytree order of nested dicts (sorted keys), each padded to 1024;
* the server runs SGD with momentum on minus the noised mean.

The reference computes in float32 at ``highest`` matmul precision. Its
``precision`` argument puts it a step lower, for the control (the
reference in the program's place one step of precision below the
configuration's): ``high`` rounds every matmul and convolution operand,
forward and backward, to a pair of bfloat16 terms (the operands of a
TPU's three-pass ``high``), ``default`` to one (its one-pass
``default``); products still accumulate in float32. The rounding is
explicit, so the control reads the same on any backend.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from configs import common

ALIGN = 1024
NOISE_KEY_STRIDE = 100_003
# bfloat16 terms per matmul operand at each precision (None: float32)
OPERAND_TERMS = {"highest": None, "high": 2, "default": 1}


def layout(paths: Sequence[str], shapes: Dict[str, tuple]):
    """[(path, offset, size)] of the flat buffer, in the order
    ``jax.tree_util`` flattens the nested dict of these paths."""
    order = sorted(paths, key=lambda p: tuple(p.split("/")))
    out, off = [], 0
    for p in order:
        n = int(np.prod(shapes[p])) if shapes[p] else 1
        out.append((p, off, n))
        off += (max(n, 1) + ALIGN - 1) // ALIGN * ALIGN
    return out, off


def fake_quantize(v, bits: int):
    """Symmetric int-``bits`` round trip with the scale max|v| / qmax."""
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(v)), 1e-12) / qmax
    return jnp.clip(jnp.round(v / s), -qmax, qmax) * s


class Reference:
    """``steps`` synchronous rounds from the benchmark's own weights.

    ``model`` is the configuration's module: its ``reference_loss`` and
    its ``TASK``'s ``examples`` and ``batch`` are all the reference knows
    of the task. ``fault`` plants one of the faults the comparison must
    catch, in the reference put in the program's place: ``"unchanged"``
    (the step returns its state), ``"halfbatch"`` (each minibatch's mean
    loss over the first half of its examples only), ``"altered"`` (the
    first trainable leaf's update applied twice)."""

    def __init__(self, model, cfg: dict, mix: dict, data, params0: dict,
                 trainable: Sequence[str], seed: int,
                 precision: str = "highest", fault: Optional[str] = None):
        self.model, self.mix, self.precision = model, mix, precision
        self.data, self.seed, self.fault = data, seed, fault
        self.trainable = list(trainable)
        self.y0 = {p: np.asarray(params0[p], np.float32)
                   for p in self.trainable}
        self.frozen = {p: jnp.asarray(v) for p, v in params0.items()
                       if p not in self.y0}
        shapes = {p: self.y0[p].shape for p in self.trainable}
        self.layout, self.size = layout(self.trainable, shapes)

        lr = mix["client_lr"]
        half = fault == "halfbatch"

        def loss(y, z, batch):
            return model.reference_loss({**y, **z}, batch, cfg, jnp.float32)

        def client(y, z, batch):
            def step(yy, mb):
                if half:
                    mb = jax.tree.map(lambda v: v[:v.shape[0] // 2], mb)
                g = jax.grad(loss)(yy, z, mb)
                return {k: yy[k] - lr * g[k] for k in yy}, None
            yt, _ = jax.lax.scan(step, y, batch)
            return {k: yt[k] - y[k] for k in y}

        self._client = jax.jit(client)
        self._fold = jax.jit(self._fold_delta)
        self._server = jax.jit(self._server_step)

    def _fold_delta(self, acc, delta, weight):
        """One client's upload added into the round's sum: each leaf
        fake-quantized with an int-k uplink; under a DP clip, the whole
        delta scaled to norm at most the clip, else weighted by the
        client's example count."""
        bits, clip = self.mix["uplink_bits"], self.mix["dp_clip_norm"]
        if bits:
            delta = {k: fake_quantize(d, bits) for k, d in delta.items()}
        if clip > 0:
            sq = sum(jnp.sum(d ** 2) for d in delta.values())
            weight = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq),
                                                         1e-12))
        return {k: acc[k] + weight * delta[k] for k in acc}

    def aggregate(self, uploads):
        """The round's mean upload from ``(delta, example count)`` pairs,
        each folded in as it comes, so no (cohort, ...) stack is held: a
        fixed denominator of the cohort under DP, the example counts
        otherwise."""
        acc = {k: jnp.zeros(v.shape, jnp.float32) for k, v in self.y0.items()}
        total = 0.0
        for delta, n in uploads:
            acc = self._fold(acc, delta, jnp.float32(n))
            total += n
        if self.mix["dp_clip_norm"] > 0:
            total = float(self.mix["cohort"])
        return {k: v / total for k, v in acc.items()}

    def _server_step(self, y, m, mn, nchange, agg, key):
        """Noise and the server optimizer, for one round's mean upload."""
        mix = self.mix
        noise = self._noise(key)
        mom = mix["server_momentum"] if mix["server_opt"] == "sgdm" else 0.0
        slr = mix["server_lr"]
        out = [{}, {}, {}, {}]
        for k in y:
            n_k = noise[k] if noise is not None else 0.0
            mk = mom * m[k] - (agg[k] + n_k)
            mnk = mom * mn[k] - n_k
            out[0][k] = y[k] - slr * mk
            out[1][k] = mk
            out[2][k] = mnk
            out[3][k] = nchange[k] - slr * mnk
        return tuple(out)

    def _noise(self, key) -> Optional[Dict[str, jnp.ndarray]]:
        mix = self.mix
        if not (mix["dp_clip_norm"] > 0 and mix["dp_noise_multiplier"] > 0):
            return None
        sigma = mix["dp_noise_multiplier"] * mix["dp_clip_norm"] / mix[
            "cohort"]
        flat = sigma * jax.random.normal(key, (self.size,), jnp.float32)
        return {p: flat[off:off + n].reshape(self.y0[p].shape)
                for p, off, n in self.layout}

    def run(self, steps: int):
        """Trainable leaves after each step, and the part of each step's
        total change from the initial weights that the DP noise alone
        contributes (zeros without noise): two lists of host dicts."""
        with jax.default_matmul_precision("highest"), common.operand_terms(
                OPERAND_TERMS[self.precision]):
            return self._run(steps)

    def uploads(self, rng, y, cids):
        """Each client's ``(delta, example count)`` in cohort order, its
        minibatches drawn from ``rng`` as the program draws them."""
        mix, task, data = self.mix, self.model.TASK, self.data
        for cid in cids:
            n = task.examples(data, cid)
            idx = rng.integers(0, n, (mix["local_steps"], mix["local_batch"]))
            batch = jax.tree.map(jnp.asarray, task.batch(data, cid, idx))
            yield self._client(y, self.frozen, batch), float(n)

    def _run(self, steps: int):
        rng = np.random.default_rng(self.seed + 77)
        N = self.data.num_clients
        y = {k: jnp.asarray(v) for k, v in self.y0.items()}
        m = {k: jnp.zeros_like(v) for k, v in y.items()}
        mn = {k: jnp.zeros_like(v) for k, v in y.items()}
        nchange = {k: jnp.zeros_like(v) for k, v in y.items()}
        ys, ns = [], []
        for r in range(steps):
            cids = rng.choice(N, size=self.mix["cohort"], replace=False)
            agg = self.aggregate(self.uploads(rng, y, cids))
            if self.fault != "unchanged":
                key = jax.random.key(self.seed * NOISE_KEY_STRIDE + r)
                prev = y
                y, m, mn, nchange = self._server(y, m, mn, nchange, agg, key)
                if self.fault == "altered":
                    k = self.trainable[0]
                    y = dict(y, **{k: prev[k] + 2 * (y[k] - prev[k])})
            del agg
            ys.append({k: np.asarray(v) for k, v in y.items()})
            ns.append({k: np.asarray(v) for k, v in nchange.items()})
        return ys, ns


def eval_loss_fn(model, cfg: dict):
    """The task's mean loss of a full parameter set (flat host dict) on a
    held-out batch, by the float32 reference forward."""
    f = jax.jit(lambda p, batch: model.reference_loss(p, batch, cfg,
                                                      jnp.float32))

    def loss(params, batch):
        with jax.default_matmul_precision("highest"):
            return float(f({k: jnp.asarray(v) for k, v in params.items()},
                           jax.tree.map(jnp.asarray, batch)))
    return loss


def leaf_norm_gaps(base: Dict[str, np.ndarray], got: Dict[str, np.ndarray],
                   want: Dict[str, np.ndarray], noise: Dict[str, np.ndarray],
                   keep: Sequence[str]) -> Dict[str, float]:
    """Per leaf in ``keep``: |‖got - base - noise‖ - ‖want - base -
    noise‖| over max(‖want - base - noise‖, the median leaf's)."""
    def norms(y):
        return {k: float(np.linalg.norm((y[k].astype(np.float64)
                                         - base[k] - noise[k]).ravel()))
                for k in keep}
    g, w = norms(got), norms(want)
    med = float(np.median(list(w.values())))
    return {k: abs(g[k] - w[k]) / max(w[k], med, 1e-30) for k in keep}


def moved_leaves(base, first, noise, rule: float = 1e-3) -> List[str]:
    """Leaves whose reference first-step change (noise removed) is at
    least ``rule`` times the median leaf's: the others move by round-off
    alone and are left out of the comparison."""
    n = {k: float(np.linalg.norm((first[k].astype(np.float64) - base[k]
                                  - noise[k]).ravel())) for k in base}
    med = float(np.median(list(n.values())))
    return sorted(k for k, v in n.items() if v >= rule * med)


def compare(model, cfg, y0, frozen, prog: List[Dict[str, np.ndarray]],
            ref: List[Dict[str, np.ndarray]],
            noise: List[Dict[str, np.ndarray]], test: dict) -> dict:
    """The numbers ``correct`` is decided on, for ``len(ref)`` steps:

    * ``loss_gap``: the largest relative gap, over the steps, between the
      held-out loss of the program's weights and of the reference's;
    * ``grad_gap``: the first step's gradient as the server optimizer
      got it (its change from the initial weights, DP noise removed), by
      the worst leaf's gap of norms;
    * ``change_gap``: the same for the change after the last step."""
    keep = moved_leaves(y0, ref[0], noise[0])
    loss = eval_loss_fn(model, cfg)
    out = {}
    gaps = []
    for yp, yr in zip(prog, ref):
        lp = loss({**frozen, **yp}, test)
        lref = loss({**frozen, **yr}, test)
        gaps.append(abs(lp - lref) / abs(lref))
    out["loss_gap"] = max(gaps)
    out["loss_gaps"] = gaps
    for name, i in (("grad_gap", 0), ("change_gap", -1)):
        per_leaf = leaf_norm_gaps(y0, prog[i], ref[i], noise[i], keep)
        worst = max(per_leaf, key=per_leaf.get)
        out[name], out[name + "_leaf"] = per_leaf[worst], worst
        out[name + "_median"] = float(np.median(list(per_leaf.values())))
    out["leaves_compared"] = len(keep)
    out["leaves"] = len(y0)
    return out
