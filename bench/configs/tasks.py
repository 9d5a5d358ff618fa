"""The tasks a configuration trains on: its data, made on the device from
the seed, and its loss, shared by the configurations of one kind.

A configuration module (``bench/configs/<name>.py``) binds one task and
defines the interface the harness, the reference and the calibration
read; nothing outside the module knows what an example holds:

* ``TASK``: a ``Task`` of this file (``IMAGES_TASK``, ``TOKENS_TASK``),
  which makes the population and hands out its batches;
* ``program_loss()``: ``loss(params, batch) -> (scalar, aux)``, the
  program's forward under the task's loss, over the minibatch dict
  ``run_grid`` hands to a client;
* ``reference_loss(p, batch, cfg, dtype)``: the same loss, plain jnp,
  over one minibatch dict with the same keys (``p`` a flat dict);
* ``small(cfg)``: the configuration cut to a size the CPU tests hold;
* ``specs``, ``init_params`` and ``layers``: the weights and the counted
  layers.

Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class Task:
    """What the harness and the reference know of a task's data.

    ``kind``: the ``data_kind`` that ``run_grid`` batches by;
    ``make(cfg, seed)``: the population, host-side, in the layout
    ``run_grid`` reads for ``kind``, with its ``num_clients``;
    ``examples(data, cid)``: a client's example count, the range of its
    minibatch draw and its aggregation weight; ``batch(data, cid, idx)``:
    the minibatches at ``idx`` (an int array of any shape), every leaf
    indexed on its example axis; ``test(data)``: the held-out examples of
    the check's loss, as a batch dict."""
    kind: str
    make: Callable
    examples: Callable
    batch: Callable
    test: Callable


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


def next_token_loss(logits, tokens):
    """Mean cross-entropy of predicting token t + 1 from positions up to
    t, over positions 1..S-1 of every sequence."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


# ---------------------------------------------------------------------------
# image classification: one label per example


CHUNK = 50       # clients made per call, so set-up holds little HBM


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _make_images(key, protos, clients, examples, alpha, noise):
    """``clients`` clients' images and labels: Dirichlet(alpha) label
    skew, each image its class prototype plus N(0, noise^2)."""
    kd, kl, kn = jax.random.split(key, 3)
    classes = protos.shape[0]
    p = jax.random.dirichlet(kd, jnp.full((classes,), alpha, jnp.float32),
                             (clients,))
    labels = jax.random.categorical(kl, jnp.log(p + 1e-30)[:, None, :],
                                    shape=(clients, examples))
    images = protos[labels] + noise * jax.random.normal(
        kn, (clients, examples) + protos.shape[1:], jnp.float32)
    return images, labels.astype(jnp.int32)


@dataclasses.dataclass
class Images:
    """The population, host-side, in the layout ``run_grid`` reads:
    ``client_images[c]`` is (n, H, W, C) float32, ``client_labels[c]``
    (n,) int32."""
    client_images: list
    client_labels: list
    test_images: np.ndarray
    test_labels: np.ndarray

    @property
    def num_clients(self) -> int:
        return len(self.client_images)


def make_images(cfg: dict, seed: int) -> Images:
    key = jax.random.fold_in(jax.random.key(seed), 1)
    kp, kt, kc = jax.random.split(key, 3)
    shape = tuple(cfg["image_shape"])
    protos = jax.random.normal(kp, (cfg["num_classes"],) + shape,
                               jnp.float32)
    alpha, noise = float(cfg["label_dirichlet_alpha"]), float(
        cfg["image_noise"])
    n, ex = cfg["clients"], cfg["examples_per_client"]
    images = np.empty((n, ex) + shape, np.float32)
    labels = np.empty((n, ex), np.int32)
    for c0 in range(0, n, CHUNK):
        k = min(CHUNK, n - c0)
        im, lb = _make_images(jax.random.fold_in(kc, c0), protos, CHUNK, ex,
                              alpha, noise)
        images[c0:c0 + k], labels[c0:c0 + k] = np.asarray(im)[:k], \
            np.asarray(lb)[:k]
    timages, tlabels = _make_images(kt, protos, 1, cfg["test_examples"],
                                    alpha, noise)
    return Images(list(images), list(labels), np.asarray(timages)[0],
                  np.asarray(tlabels)[0])


def image_examples(data: Images, cid: int) -> int:
    return len(data.client_labels[cid])


def image_batch(data: Images, cid: int, idx) -> dict:
    return {"images": data.client_images[cid][idx],
            "labels": data.client_labels[cid][idx]}


def image_test_batch(data: Images) -> dict:
    return {"images": data.test_images, "labels": data.test_labels}


def classifier_loss(forward):
    """The program's forward under the cross-entropy of its labels."""
    def loss(params, b):
        return cross_entropy(forward(params, b["images"]), b["labels"]), {}
    return loss


# ---------------------------------------------------------------------------
# next-token prediction: sequences of token ids


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _make_tokens(key, shared, clients, examples, seq, shared_weight):
    """``clients`` clients' (examples, seq) token sequences. Each token's
    successor comes, with probability ``shared_weight``, from the shared
    table (a random one of its ``branch`` entries), and otherwise from
    the client's own chain: ``(t + b_c + j * stride) mod vocab`` with the
    client's offset ``b_c`` and a random ``j < branch``."""
    vocab, branch = shared.shape
    stride = vocab // branch + 1
    kb, k0, ks = jax.random.split(key, 3)
    b = jax.random.randint(kb, (clients, 1), 0, vocab)
    first = shared[jax.random.randint(k0, (clients, examples), 0, vocab),
                   0]

    def step(tok, k):
        kj, ku = jax.random.split(k)
        j = jax.random.randint(kj, tok.shape, 0, branch)
        local = (tok + b + j * stride) % vocab
        nxt = jnp.where(jax.random.uniform(ku, tok.shape) < shared_weight,
                        shared[tok, j], local)
        return nxt, tok

    _, toks = lax.scan(step, first, jax.random.split(ks, seq))
    return jnp.moveaxis(toks, 0, -1).astype(jnp.int32)


@dataclasses.dataclass
class Tokens:
    """The population, host-side, in the layout ``run_grid`` reads for
    ``data_kind="tokens"``: ``client_tokens[c]`` is (n, seq) int32."""
    client_tokens: list
    test_tokens: np.ndarray

    @property
    def num_clients(self) -> int:
        return len(self.client_tokens)


def make_tokens(cfg: dict, seed: int) -> Tokens:
    """Markov-chain sequences over ``vocab`` ids: a shared successor
    table whose entries follow a Zipf law of exponent ``token_zipf`` (a
    few ids are frequent, as words are), mixed with each client's own
    chain; the held-out sequences are one more client's."""
    key = jax.random.fold_in(jax.random.key(seed), 1)
    kz, kt, kc = jax.random.split(key, 3)
    vocab, seq = cfg["vocab_size"], cfg["seq_len"]
    zipf = -cfg["token_zipf"] * jnp.log(jnp.arange(1, vocab + 1,
                                                   dtype=jnp.float32))
    shared = jax.random.categorical(kz, zipf, shape=(vocab, cfg[
        "chain_branch"])).astype(jnp.int32)
    w = float(cfg["shared_chain_weight"])
    toks = _make_tokens(kc, shared, cfg["clients"],
                        cfg["examples_per_client"], seq, w)
    test = _make_tokens(kt, shared, 1, cfg["test_examples"], seq, w)
    return Tokens(list(np.asarray(toks)), np.asarray(test)[0])


def token_examples(data: Tokens, cid: int) -> int:
    return len(data.client_tokens[cid])


def token_batch(data: Tokens, cid: int, idx) -> dict:
    return {"tokens": data.client_tokens[cid][idx]}


def token_test_batch(data: Tokens) -> dict:
    return {"tokens": data.test_tokens}


IMAGES_TASK = Task("images", make_images, image_examples, image_batch,
                   image_test_batch)
TOKENS_TASK = Task("tokens", make_tokens, token_examples, token_batch,
                   token_test_batch)
