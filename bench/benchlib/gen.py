"""A configuration's inputs from its seeds: MNIST-/CIFAR-shaped images or
token sequences, the paper's label-sorted non-IID split and the wireless
topology, all made on the device in one jitted call.

Copies of the generators in ``repro.data.synthetic``,
``repro.data.tokens`` (extended to topics) and ``repro.core.topology``
(same draws, same key schedule), kept here so that no change to the
program can move the inputs it is measured on.

``data.kind`` picks the samples: ``mnist-like`` and ``cifar-like`` images
with a class label each, or ``tokens``, sequences whose targets are the
next tokens.  A token sequence's topic plays the label's part in the
split, as the speaker or user does in LEAF's Shakespeare and Reddit sets
(arXiv 1812.01097).
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp


def _prototypes(key, d):
    low = jax.random.normal(key, (d["num_classes"], d["smoothness"],
                                  d["smoothness"], d["channels"]))
    protos = jax.image.resize(
        low, (d["num_classes"], d["height"], d["width"], d["channels"]),
        method="bilinear")
    return protos / jnp.maximum(jnp.std(protos), 1e-6)


def _images(key, d):
    k_proto, k_ytr, k_yte, k_ntr, k_nte = jax.random.split(key, 5)
    protos = _prototypes(k_proto, d)

    def sample(ky, kn, n):
        y = jax.random.randint(ky, (n,), 0, d["num_classes"])
        noise = d["noise_std"] * jax.random.normal(
            kn, (n, d["height"], d["width"], d["channels"]))
        return (protos[y] + noise).astype(jnp.float32), y

    return sample(k_ytr, k_ntr, d["num_train"]), sample(k_yte, k_nte,
                                                         d["num_test"])


def _tokens(key, d):
    """Sequences of ``seq_len + 1`` tokens over ``vocab_size`` ids, each from
    a first-order Markov chain of its topic: ``num_topics`` successor
    tables of ``branching`` successors per token, and at each step a
    ``reset_p`` chance of a uniform token instead.  Topics are drawn
    uniformly; the test set is drawn the same way.  Returns
    ``(train sequences, topics), (test sequences, topics)``."""
    V, S, B = d["vocab_size"], d["seq_len"], d["branching"]
    k_table, k_tr, k_te = jax.random.split(key, 3)
    tables = jax.random.randint(k_table, (d["num_topics"], V, B), 0, V)

    def sample(k, n):
        k_topic, k_start, k_choice, k_reset, k_resetv = jax.random.split(k, 5)
        topic = jax.random.randint(k_topic, (n,), 0, d["num_topics"])
        starts = jax.random.randint(k_start, (n,), 0, V)
        choices = jax.random.randint(k_choice, (n, S), 0, B)
        resets = jax.random.bernoulli(k_reset, d["reset_p"], (n, S))
        reset_vals = jax.random.randint(k_resetv, (n, S), 0, V)

        def gen(t, s, ch, rs, rv):
            def step(tok, inp):
                choice, reset, r = inp
                nxt = jnp.where(reset, r, tables[t, tok, choice])
                return nxt, nxt
            _, seq = jax.lax.scan(step, s, (ch, rs, rv))
            return jnp.concatenate([s[None], seq])

        seqs = jax.vmap(gen)(topic, starts, choices, resets, reset_vals)
        return seqs.astype(jnp.int32), topic

    return sample(k_tr, d["num_train"]), sample(k_te, d["num_test"])


def _noniid(key, x, y, clients, per_client, num_shards):
    """Sort by label, cut into ``num_shards`` shards, deal ``per_client``
    shards to each client (paper §V)."""
    order = jnp.argsort(y, stable=True)
    usable = (x.shape[0] // num_shards) * num_shards
    shards = order[:usable].reshape(num_shards, usable // num_shards)
    if clients * per_client > num_shards:
        raise ValueError(f"need {clients * per_client} shards, "
                         f"only {num_shards} exist")
    chosen = jax.random.permutation(key, num_shards)[:clients * per_client]
    idx = shards[chosen.reshape(clients, per_client)].reshape(clients, -1)
    return x[idx], y[idx]


def _topology(key, t):
    """Hotspot geometry, pathloss, symmetric Rayleigh fading and the
    outage-pruned graph (paper §III)."""
    K = t["num_clients"]
    k_pos, k_hot, k_re, k_im = jax.random.split(key, 4)
    hot = jax.random.uniform(k_hot, (t["num_hotspots"], 2)) * t["area_size"]
    assign = jax.random.randint(k_pos, (K,), 0, t["num_hotspots"])
    jitter = jax.random.normal(jax.random.fold_in(k_pos, 1),
                               (K, 2)) * t["hotspot_std"]
    positions = hot[assign] + jitter
    diff = positions[:, None, :] - positions[None, :, :]
    dist = jnp.maximum(jnp.sqrt(jnp.sum(diff ** 2, axis=-1) + 1e-9), t["d0"])
    amp = (dist / t["d0"]) ** (-t["pathloss_exp"] / 2.0)
    re = jax.random.normal(k_re, (K, K)) / jnp.sqrt(2.0)
    im = jax.random.normal(k_im, (K, K)) / jnp.sqrt(2.0)
    h = re + 1j * im
    h = jnp.where(jnp.triu(jnp.ones((K, K), bool), k=1), h, jnp.conj(h.T))
    link_gain = amp * h * (1.0 - jnp.eye(K))
    snr = (jnp.abs(link_gain) ** 2) * (t["total_power"] / K) / t["noise_var"]
    snr = snr * (1.0 - jnp.eye(K))
    snr_db = 10.0 * jnp.log10(jnp.maximum(snr, 1e-12))
    adjacency = (snr_db >= t["outage_snr_db"]) & ~jnp.eye(K, dtype=bool)
    return positions, link_gain, snr, adjacency


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(spec: str):
    conf = json.loads(spec)
    d, t = conf["data"], conf["topology"]

    def split(x, y):
        return _noniid(jax.random.PRNGKey(d["seed"] + 1), x, y,
                       t["num_clients"], d["shards_per_client"],
                       d["num_shards"])

    if d["kind"] == "tokens":
        (seq, topic), (seq_te, _) = _tokens(jax.random.PRNGKey(d["seed"]), d)
        seqs, _ = split(seq, topic)
        xs, ys = seqs[..., :-1], seqs[..., 1:]
        xte, yte = seq_te[:, :-1], seq_te[:, 1:]
    elif d["kind"] in ("mnist-like", "cifar-like"):
        (xtr, ytr), (xte, yte) = _images(jax.random.PRNGKey(d["seed"]), d)
        xs, ys = split(xtr, ytr)
    else:
        raise ValueError(f"unknown data kind {d['kind']!r}")
    positions, link_gain, snr, adjacency = _topology(
        jax.random.PRNGKey(t["seed"]), t)
    return {"xs": xs, "ys": ys, "xte": xte, "yte": yte,
            "positions": positions, "link_gain": link_gain,
            "link_snr": snr, "adjacency": adjacency}


def make_inputs(conf: dict) -> dict:
    """Device arrays of the configuration's deployment: client shards
    ``xs``/``ys`` (K, n_k, ...), the test set, and the topology.  For
    ``tokens`` the inputs and targets are int32 ``(..., seq_len)``, the
    targets the inputs shifted by one."""
    spec = json.dumps({"data": conf["data"], "topology": conf["topology"]},
                      sort_keys=True)
    return jax.block_until_ready(_make(spec))
