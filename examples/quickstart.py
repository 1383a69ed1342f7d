"""Quickstart: CWFL end-to-end on a synthetic MNIST-like task (CPU, ~2 min).

Builds a 16-client wireless topology, clusters it by link SNR (paper §IV),
runs 12 federated rounds of CWFL vs the ideal FedAvg server, and prints the
accuracy trajectory plus the channel-use saving vs decentralized FL.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.core import TopologyConfig, make_topology, clustering
from repro.core.cwfl import channel_uses_per_round
from repro.data import SyntheticImageConfig, make_synthetic_images, partition_iid
from repro.models import make_mnist_mlp, nll_loss
from repro.training import FLConfig, run_federated


def main():
    key = jax.random.PRNGKey(0)
    K, clusters = 16, 3

    print("== topology & SNR clustering (offline phase) ==")
    topo = make_topology(key, TopologyConfig(num_clients=K, num_hotspots=3))
    plan = clustering.make_cluster_plan(topo.link_snr, topo.adjacency,
                                        clusters, key)
    print(f"clients: {K}, clusters: {plan.assignment.tolist()}")
    print(f"cluster heads: {plan.heads.tolist()}")
    print(f"cluster SNRs (dB): "
          f"{[round(float(10*jax.numpy.log10(x)), 1) for x in plan.cluster_snr]}")
    uses = channel_uses_per_round(K, clusters)
    print(f"channel uses/round: CWFL={uses['cwfl']} vs "
          f"decentralized={uses['decentralized']} "
          f"({uses['decentralized']/uses['cwfl']:.0f}x saving)\n")

    print("== data (synthetic MNIST-like, IID split) ==")
    dcfg = SyntheticImageConfig.mnist_like(num_train=6000, num_test=1500)
    (xtr, ytr), (xte, yte) = make_synthetic_images(jax.random.PRNGKey(1), dcfg)
    xs, ys = partition_iid(jax.random.PRNGKey(2), xtr, ytr, K)
    init, apply = make_mnist_mlp()
    loss = lambda p, x, y: nll_loss(apply(p, x), y)

    for strategy in ("cwfl", "fedavg"):
        print(f"== {strategy} ==")
        h = run_federated(
            init, apply, loss, topo, xs, ys, xte, yte,
            FLConfig(strategy=strategy, rounds=12, num_clusters=clusters,
                     snr_db=40.0, eval_samples=1024))
        for r, l, a in zip(h["round"], h["train_loss"], h["test_acc"]):
            print(f"  round {r:2d}  loss={l:.3f}  acc={a:.3f}")
        print(f"  final accuracy: {h['final_acc']:.3f}\n")


if __name__ == "__main__":
    main()
