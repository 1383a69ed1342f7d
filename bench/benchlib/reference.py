"""Plain reference of one CWFL trajectory (arXiv 2211.03363, Algorithm 1),
written from the paper and independent of ``repro``: it imports nothing of
the program and takes nothing the program made.

Inputs are the configuration's data and topology (``gen.make_inputs``),
the model's plain ``init``/``apply`` (``configs/<config>.py``) and three
keys: the offline clustering key, the init key and the per-round keys,
split as the program splits them, so that both draw the same minibatches
and the same receiver noise.  Every matmul and conv runs in float32 at
``highest`` precision; ``control(conf)`` gives the same arithmetic one
precision below what the configuration states.

One round:

    local:  E epochs of minibatch SGD per client (vmap over K)
    sync:   phase 1  θ̃ = Ã·S + n₁   (intra-cluster OTA MAC, eq. 8, precoded
                                     by eq. 5, rows renormalised)
            phase 2  θ̄ = B̃·θ̃ + n₂  (head consensus, eq. 9, rows renormalised)
            phase 3  θ_k ← θ̄_c(k)   (downlink),  consensus = mean_c θ̄_c
    eval:   accuracy of the consensus on the test set

A sample is an image with a class label, or a token sequence whose
targets are its next tokens: the loss and the accuracy are means over
every target position.  An optional key of the configuration's
``check``, ``reference_eval_block``, computes the accuracy in blocks of
that many test samples, summed and then divided by the count, so that
the eval's logits fit the device at a configuration's own size.  Without
it the reference evaluates the test set at once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Offline phase: SNR clustering, head election, water-filling, weights.
# ---------------------------------------------------------------------------

def cluster_plan(link_snr, adjacency, C: int, key, iters: int = 50):
    """K-means on each client's link-SNR profile (dB, outage links floored
    at -30 dB), farthest-point init from a random first centre; the member
    nearest each centroid is its head; ξ_c is the mean member→head SNR."""
    K = link_snr.shape[0]
    feats = jnp.maximum(jnp.where(
        adjacency, 10.0 * jnp.log10(jnp.maximum(link_snr, 1e-12)), -30.0),
        -30.0)

    def sqdist(centres):
        return jnp.sum((feats[:, None, :] - centres[None]) ** 2, axis=-1)

    idx = jnp.zeros((C,), jnp.int32).at[0].set(
        jax.random.randint(key, (), 0, K))
    for c in range(1, C):
        taken = jnp.where(jnp.arange(C)[None, :] >= c, jnp.inf, 0.0)
        idx = idx.at[c].set(jnp.argmax(jnp.min(sqdist(feats[idx]) + taken,
                                               axis=1)))
    centroids = feats[idx]
    for _ in range(iters):
        onehot = jax.nn.one_hot(jnp.argmin(sqdist(centroids), axis=1), C)
        count = onehot.sum(0)
        new = jnp.dot(onehot.T, feats) / jnp.maximum(
            count, 1.0)[:, None]
        centroids = jnp.where((count == 0)[:, None], centroids, new)
    d2 = sqdist(centroids)
    assign = jnp.argmin(d2, axis=1)
    member = assign[None, :] == jnp.arange(C)[:, None]               # (C, K)
    heads = jnp.argmin(jnp.where(member.T, d2, jnp.inf), axis=0)      # (C,)
    membership = member.astype(jnp.float32)
    head_onehot = jax.nn.one_hot(heads, K)
    others = membership * (1.0 - head_onehot)
    xi = (link_snr[heads] * others).sum(1) / jnp.maximum(others.sum(1), 1.0)
    xi = jnp.where(others.sum(1) > 0, xi, jnp.max(link_snr))
    return {"membership": membership, "heads": heads, "xi": xi,
            "head_mask": head_onehot.sum(0)}


def water_filling(g, total_power: float, iters: int = 60):
    """P_k = max(µ − 1/g_k, 0) with Σ P_k = P, µ by bisection."""
    inv_g = 1.0 / jnp.maximum(g, 1e-12)
    lo, hi = jnp.zeros(()), total_power + jnp.max(inv_g)
    for _ in range(iters):
        mu = 0.5 * (lo + hi)
        over = jnp.sum(jnp.maximum(mu - inv_g, 0.0)) > total_power
        lo, hi = jnp.where(over, lo, mu), jnp.where(over, mu, hi)
    p = jnp.maximum(0.5 * (lo + hi) - inv_g, 0.0)
    s = jnp.sum(p)
    return jnp.where(s > 0, p * (total_power / jnp.maximum(s, 1e-12)),
                     jnp.full_like(p, total_power / p.shape[0]))


def sync_state(plan, link_gain, total_power: float, noise_var: float):
    """Member→head powers (heads use the mean head↔head gain), the eq. (9)
    consensus weights, and the receiver noise std σ/√P."""
    K = link_gain.shape[0]
    heads, C = plan["heads"], plan["heads"].shape[0]
    head_of = heads[jnp.argmax(plan["membership"], axis=0)]
    to_head = jnp.abs(link_gain[jnp.arange(K), head_of]) ** 2
    h2h = (jnp.abs(link_gain[heads][:, heads]) ** 2).sum() / max(C * (C - 1),
                                                                 1)
    gain = jnp.where(plan["head_mask"] > 0, h2h, to_head) / noise_var
    off = 1.0 - jnp.eye(C)
    mix = off * plan["xi"][None, :] / jnp.maximum(
        (off * plan["xi"][None, :]).sum(1, keepdims=True), 1e-12)
    return {"power": water_filling(gain, total_power), "mix": mix,
            "std": jnp.sqrt(noise_var) / jnp.sqrt(total_power)}


# ---------------------------------------------------------------------------
# The round.
# ---------------------------------------------------------------------------

def _sync(stacked, plan, st, total_power, key, dtype, fault=None):
    leaves, treedef = jax.tree.flatten(stacked)
    K, C = leaves[0].shape[0], plan["heads"].shape[0]
    sizes = [x[0].size for x in leaves]
    S = jnp.concatenate([x.reshape(K, -1) for x in leaves], axis=1)
    d = S.shape[1]
    # eq. (5) precoding: P_k^t = min(P_k, P_k / max(‖θ_k‖²/d, 1)); heads,
    # whose contribution never crosses the channel, are exempt.
    power = st["power"]
    mean_sq = jnp.sum(jnp.square(S.astype(jnp.float32)), axis=1) / d
    pre = jnp.sqrt(jnp.minimum(power, power / jnp.maximum(mean_sq, 1.0))
                   / jnp.maximum(power, 1e-12))
    head = plan["head_mask"] > 0
    amp = jnp.where(head, 1.0, jnp.sqrt(power / total_power))
    A = plan["membership"] * (amp * jnp.where(head, 1.0, pre))[None, :]
    if fault == "half_clients":
        A = A * ((jnp.arange(K) < K // 2) | head)[None, :]
    rows = jnp.maximum(A.sum(1), 1e-12)
    A, std1 = A / rows[:, None], st["std"] / rows
    B = st["mix"] + jnp.eye(C)
    b_rows = B.sum(1)
    B, kappa = B / b_rows[:, None], (jnp.sqrt(jnp.sum(st["mix"] ** 2, 1))
                                     * st["std"] / b_rows)

    def noise(k, std):
        ks = jax.random.split(k, len(leaves))
        return jnp.concatenate(
            [std[:, None] * jax.random.normal(kk, (C, n), jnp.float32)
             for kk, n in zip(ks, sizes)], axis=1)

    k1, k2 = jax.random.split(key)
    tilde = jnp.dot(A.astype(dtype), S) + noise(
        k1, std1).astype(dtype)
    bar = jnp.dot(B.astype(dtype), tilde) + noise(
        k2, kappa).astype(dtype)
    new = jnp.dot(plan["membership"].T.astype(dtype), bar)
    cons = jnp.mean(bar.astype(jnp.float32), axis=0).astype(dtype)

    def unflat(flat, lead):
        out, off = [], 0
        for x, n in zip(leaves, sizes):
            out.append(flat[..., off:off + n].reshape(lead + x.shape[1:]))
            off += n
        return jax.tree.unflatten(treedef, out)

    return unflat(new, (K,)), unflat(cons, ())


def _nll(logp, y):
    """Mean NLL over every target position: ``y`` is ``logp``'s shape
    without its last (class) axis."""
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0])


def _cast(x, dtype):
    """Floating-point data in the reference's dtype; token ids as they are."""
    return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x


def _accuracy(apply, params, x, y, block):
    """Share of target positions whose argmax is the target, over all of
    ``x`` at once, or summed over blocks of ``block`` samples."""
    if block is None:
        return jnp.mean(jnp.argmax(apply(params, x), axis=-1) == y)
    hits = jax.lax.map(
        lambda xy: jnp.sum(jnp.argmax(apply(params, xy[0][None]), axis=-1)
                           == xy[1][None]),
        (x, y), batch_size=block)
    return jnp.sum(hits) / y.size


# Faults that a run of the program can have, planted here in the reference
# put in the program's place, to read how far each moves the compared
# numbers (``bench/calibrate.py``): half of each minibatch (of its images,
# or of its sequences) left out and the mean taken over the rest; half of
# the clients (the batch of the round) left out of the sync's sums, the
# mean taken over the rest; the sync (the exchange between clients) left
# out; every round's reported accuracy altered by ``ALTERED_ACC``; the
# call's state returned unchanged.
FAULTS = ("half_batch", "half_clients", "no_sync", "answer_altered",
          "state_unchanged")
ALTERED_ACC = 0.05


@functools.partial(jax.jit, static_argnames=(
    "apply", "C", "batch", "steps", "lr", "total_power", "noise_var",
    "dtype", "precision", "fault", "eval_block"))
def _trajectory(params0, data, topo, plan_key, round_keys, *, apply, C,
                batch, steps, lr, total_power, noise_var, dtype, precision,
                fault, eval_block):
    with jax.default_matmul_precision(precision):
        return _rounds(params0, data, topo, plan_key, round_keys,
                       apply=apply, C=C, batch=batch, steps=steps, lr=lr,
                       total_power=total_power, noise_var=noise_var,
                       dtype=dtype, fault=fault, eval_block=eval_block)


def _rounds(params0, data, topo, plan_key, round_keys, *, apply, C, batch,
            steps, lr, total_power, noise_var, dtype, fault, eval_block):
    xs, ys = _cast(data["xs"], dtype), data["ys"]
    xte, yte = _cast(data["xte"], dtype), data["yte"]
    K, n_k = ys.shape[:2]
    plan = cluster_plan(topo["link_snr"], topo["adjacency"], C, plan_key)
    st = sync_state(plan, topo["link_gain"], total_power, noise_var)
    loss_grad = jax.value_and_grad(
        lambda p, x, y: _nll(apply(p, x).astype(jnp.float32), y))
    lr = jnp.asarray(lr, dtype)

    def local(p, x, y, key):
        def step(p, k):
            idx = jax.random.randint(k, (batch,), 0, n_k)
            if fault == "half_batch":
                idx = idx[:batch // 2]
            loss, g = loss_grad(p, x[idx], y[idx])
            return jax.tree.map(lambda a, b: a - lr * b.astype(dtype), p,
                                g), loss
        p, losses = jax.lax.scan(step, p, jax.random.split(key, steps))
        return p, jnp.mean(losses)

    def round_(carry, rkey):
        stacked, _ = carry
        k_local, k_agg = jax.random.split(rkey)
        trained, losses = jax.vmap(local)(stacked, xs, ys,
                                          jax.random.split(k_local, K))
        if fault == "no_sync":
            stacked = trained
            cons = jax.tree.map(lambda x: jnp.mean(x, axis=0), trained)
        else:
            stacked, cons = _sync(trained, plan, st, total_power, k_agg,
                                  dtype, fault)
        acc = _accuracy(apply, cons, xte, yte, eval_block)
        if fault == "answer_altered":
            acc = acc + ALTERED_ACC
        return (stacked, cons), (jnp.mean(losses), acc)

    p0 = jax.tree.map(lambda x: x.astype(dtype), params0)
    stacked0 = jax.tree.map(lambda x: jnp.broadcast_to(x, (K,) + x.shape), p0)
    (stacked, cons), (loss, acc) = jax.lax.scan(round_, (stacked0, p0),
                                                round_keys)
    if fault == "state_unchanged":
        stacked, cons = stacked0, p0
    return {"loss": loss, "acc": acc,
            "state0": {"consensus": p0, "stacked": stacked0},
            "state1": {"consensus": cons, "stacked": stacked}}


def control(conf: dict) -> dict:
    """``trajectory``'s arguments for the control: one precision below the
    configuration's ``model.matmul_precision`` (float32 at ``highest``:
    ``high``, three bfloat16 passes; float32 at the TPU's one-pass
    ``default``: bfloat16)."""
    if conf["model"]["matmul_precision"] == "highest":
        return {"precision": "high"}
    return {"dtype": jnp.bfloat16}


def trajectory(model, conf: dict, inputs: dict, plan_key, init_key,
               round_keys, dtype=jnp.float32, precision="highest",
               fault=None) -> dict:
    """Run the reference over ``len(round_keys)`` rounds.  Returns, on
    the host, the per-round mean local loss and test accuracy, and the
    consensus and per-client params before (``state0``) and after
    (``state1``) the rounds.  The configuration's ``check`` may set
    ``reference_eval_block`` (module docstring)."""
    fl, total_power = conf["fl"], conf["topology"]["total_power"]
    n_k = inputs["ys"].shape[1]
    out = _trajectory(
        model.reference_init(init_key),
        {k: inputs[k] for k in ("xs", "ys")} | {
            "xte": inputs["xte"][:fl["eval_samples"]],
            "yte": inputs["yte"][:fl["eval_samples"]]},
        {k: inputs[k] for k in ("link_snr", "adjacency", "link_gain")},
        plan_key, round_keys, apply=model.reference_apply,
        C=fl["num_clusters"], batch=fl["batch_size"],
        steps=max(fl["local_epochs"] * (n_k // fl["batch_size"]), 1),
        lr=fl["lr"], total_power=float(total_power),
        noise_var=float(total_power / 10.0 ** (fl["snr_db"] / 10.0)),
        dtype=dtype, precision=precision, fault=fault,
        eval_block=conf["check"].get("reference_eval_block"))
    return jax.device_get(out)


def program_keys(seed: int, rounds: int):
    """(plan key, init key, round keys) as the program splits ``seed``:
    PRNGKey(seed) → (state, init, rounds), rounds → one key per round."""
    k_state, k_init, k_rounds = jax.random.split(jax.random.PRNGKey(seed), 3)
    return k_state, k_init, jax.random.split(k_rounds, rounds)
