"""Token-sequence configurations through ``bench/``: the ``tokens`` data
kind and its topic split, the image kinds' inputs bit for bit as before,
the reference's eval computed in blocks against the eval computed at once,
and what ``harness.sizes`` counts in ``d``.

The token fixture is ``fixtures/tiny_lm_k6``: a causal model of one
attention layer over 64 token ids, K = 6 clients with 16-token sequences,
2 clusters, the eval block key set (``test_faults.py`` rehearses it as a
cell).
"""
import copy
import functools
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import gen, harness, reference

FIXTURE = harness.BENCH / "tests" / "fixtures" / "tiny_lm_k6.json"
INPUTS = ("xs", "ys", "xte", "yte", "positions", "link_gain", "link_snr",
          "adjacency")


def digest(inputs: dict) -> str:
    h = hashlib.sha256()
    for k in INPUTS:
        a = np.asarray(inputs[k])
        h.update(f"{k}{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@functools.cache
def fixture():
    model = harness.load_module(FIXTURE.with_suffix(".py"))
    return model, model.CONF, gen.make_inputs(model.CONF)


# ---------------------------------------------------------------------------
# The ``tokens`` data kind.
# ---------------------------------------------------------------------------

def test_token_shapes_and_targets():
    _, conf, inputs = fixture()
    d, K = conf["data"], conf["topology"]["num_clients"]
    n_k = d["num_train"] // d["num_shards"] * d["shards_per_client"]
    S = d["seq_len"]
    assert inputs["xs"].shape == inputs["ys"].shape == (K, n_k, S)
    assert inputs["xte"].shape == inputs["yte"].shape == (d["num_test"], S)
    for k in ("xs", "ys", "xte", "yte"):
        assert inputs[k].dtype == jnp.int32
        assert 0 <= int(inputs[k].min()) and int(inputs[k].max()) < (
            d["vocab_size"])
    # The targets are the inputs shifted by one token.
    np.testing.assert_array_equal(inputs["xs"][..., 1:],
                                  inputs["ys"][..., :-1])
    np.testing.assert_array_equal(inputs["xte"][:, 1:],
                                  inputs["yte"][:, :-1])


def test_each_client_holds_its_topic_shards():
    """A client's rows are ``shards_per_client`` whole shards of the train
    sequences sorted by topic, and no shard goes to two clients."""
    _, conf, inputs = fixture()
    d = conf["data"]
    (seq, topic), _ = gen._tokens(jax.random.PRNGKey(d["seed"]), d)
    seq, topic = np.asarray(seq), np.asarray(topic)
    order = np.argsort(topic, kind="stable")
    per = d["num_train"] // d["num_shards"]
    shards = order[:per * d["num_shards"]].reshape(d["num_shards"], per)
    held = np.concatenate([np.asarray(inputs["xs"]),
                           np.asarray(inputs["ys"])[..., -1:]], axis=-1)
    dealt = []
    for rows in held:
        blocks = rows.reshape(d["shards_per_client"], per, -1)
        for block in blocks:
            match = [j for j, s in enumerate(shards)
                     if np.array_equal(seq[s], block)]
            assert len(match) == 1
            dealt.append(match[0])
            # One shard spans at most two topics: it is a run of the sort.
            assert len(set(topic[shards[match[0]]])) <= 2
    assert len(set(dealt)) == len(dealt) == (
        conf["topology"]["num_clients"] * d["shards_per_client"])


def test_tokens_are_fixed_by_the_seed():
    _, conf, inputs = fixture()
    assert digest(inputs) == digest(gen.make_inputs(conf))
    assert digest(inputs) == (
        "286be7dbba25d4680be0282d75f06bd3af4185acfaf47097cdc652ab0c86e891")
    other = copy.deepcopy(conf)
    other["data"]["seed"] += 1
    moved = gen.make_inputs(other)
    assert not np.array_equal(moved["xs"], inputs["xs"])
    np.testing.assert_array_equal(moved["adjacency"], inputs["adjacency"])


# ---------------------------------------------------------------------------
# The image kinds: the same bits as the image generator had before the
# tokens kind, a copy of which follows.
# ---------------------------------------------------------------------------

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


def _noniid(key, x, y, clients, per_client, num_shards):
    order = jnp.argsort(y, stable=True)
    usable = (x.shape[0] // num_shards) * num_shards
    shards = order[:usable].reshape(num_shards, usable // num_shards)
    chosen = jax.random.permutation(key, num_shards)[:clients * per_client]
    idx = shards[chosen.reshape(clients, per_client)].reshape(clients, -1)
    return x[idx], y[idx]


def _topology(key, t):
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
def _image_inputs(spec: str):
    conf = json.loads(spec)
    d, t = conf["data"], conf["topology"]
    (xtr, ytr), (xte, yte) = _images(jax.random.PRNGKey(d["seed"]), d)
    xs, ys = _noniid(jax.random.PRNGKey(d["seed"] + 1), xtr, ytr,
                     t["num_clients"], d["shards_per_client"],
                     d["num_shards"])
    positions, link_gain, snr, adjacency = _topology(
        jax.random.PRNGKey(t["seed"]), t)
    return {"xs": xs, "ys": ys, "xte": xte, "yte": yte,
            "positions": positions, "link_gain": link_gain,
            "link_snr": snr, "adjacency": adjacency}


@pytest.mark.parametrize("name", ["mnist_mlp_k50", "cifar_cnn_k27"])
def test_image_inputs_are_unchanged(name):
    conf = harness.rehearsal_conf(json.loads(
        (harness.BENCH / "configs" / f"{name}.json").read_text()))
    spec = json.dumps({"data": conf["data"], "topology": conf["topology"]},
                      sort_keys=True)
    assert digest(gen.make_inputs(conf)) == digest(_image_inputs(spec))


def test_unknown_data_kind_is_refused():
    _, conf, _ = fixture()
    bad = copy.deepcopy(conf)
    bad["data"]["kind"] = "audio"
    with pytest.raises(ValueError, match="unknown data kind"):
        gen.make_inputs(bad)


# ---------------------------------------------------------------------------
# The reference in blocks, and what ``d`` counts.
# ---------------------------------------------------------------------------

def _both(model, conf, inputs, seed=4321):
    """The reference with the configuration's eval block, and without."""
    T = conf["fl"]["rounds"]
    plan_key = reference.program_keys(conf["fl"]["plan_seed"], T)[0]
    _, init_key, rkeys = reference.program_keys(seed, T)
    whole = copy.deepcopy(conf)
    whole["check"].pop("reference_eval_block", None)
    return [reference.trajectory(model, c, inputs, plan_key, init_key, rkeys)
            for c in (conf, whole)]


def _blocked_mnist():
    model = harness.load_module(harness.BENCH / "configs"
                                / "mnist_mlp_k50.py")
    conf = harness.rehearsal_conf(model.CONF)
    conf["check"]["reference_eval_block"] = 300
    return model, conf, gen.make_inputs(conf)


@pytest.mark.parametrize("case", ["tiny_lm_k6", "mnist_mlp_k50"])
def test_blocked_reference_agrees(case):
    """The eval block changes only how the accuracy is taken: each test
    sample's forward pass is the same operations, in batches of the
    block (the last one ragged) rather than all at once, and its argmax
    hits are counted as integers and divided by the count, which is
    their mean.  So the accuracy is equal.  The local steps and the sync
    are the same operations in both programs, and XLA may at most order
    a reduction differently where it compiles them: on the CPU both agree
    bit for bit.  The bounds, 5e-7 relative on every round's loss (four
    roundings) and 1e-6 of a leaf's largest entry on the state, sit under
    every limit on those numbers in either configuration (7e-7 and 1e-5
    for the fixture; 2e-6 and 5e-6 for MNIST)."""
    if case == "tiny_lm_k6":
        model, conf, inputs = fixture()
    else:
        model, conf, inputs = _blocked_mnist()
    assert len(inputs["yte"][:conf["fl"]["eval_samples"]]) % (
        conf["check"]["reference_eval_block"]) != 0
    blocked, whole = _both(model, conf, inputs)
    np.testing.assert_allclose(blocked["loss"], whole["loss"], rtol=5e-7)
    np.testing.assert_array_equal(blocked["acc"], whole["acc"])
    for b, w in zip(jax.tree.leaves(blocked["state1"]),
                    jax.tree.leaves(whole["state1"])):
        np.testing.assert_allclose(b, w, rtol=0,
                                   atol=1e-6 * np.max(np.abs(w)))


def test_d_counts_the_trained_params_only():
    model, conf, inputs = fixture()
    m = conf["model"]
    V, S, D = m["vocab_size"], m["seq_len"], m["d_model"]
    size = harness.sizes(conf, model, inputs)
    # embedding, four projections, head weights and bias: not the frozen
    # (S, D) positional matrix the apply closes over.
    assert size["d"] == V * D + 4 * D * D + D * V + V
    assert model.FROZEN.shape == (S, D)
    init, _, _ = model.program_model()
    synced = init(jax.random.PRNGKey(0))
    assert size["d"] == sum(x.size for x in jax.tree.leaves(synced))
    assert size["K"] == conf["topology"]["num_clients"]
    n_k = conf["data"]["num_train"] // conf["data"]["num_shards"] * (
        conf["data"]["shards_per_client"])
    assert size["steps"] == n_k // conf["fl"]["batch_size"]
    f = model.sample_flops()
    assert size["local_flops"] == (size["K"] * size["steps"]
                                   * conf["fl"]["batch_size"] * f["train"])
