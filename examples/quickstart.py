"""Quickstart: the paper's full loop in ~2 minutes on CPU.

    PYTHONPATH=src python examples/quickstart.py

Builds an 8-client non-i.i.d. world (synthetic FMNIST stand-in), runs
PCA -> K-means++ -> RL graph discovery -> AE-gated D2D exchange, then trains
unsupervised FL (FedAvg) on the raw vs exchanged data and prints the
reconstruction-loss comparison (paper Figs. 3-5 in miniature)."""
import jax
import numpy as np

from repro.core.pipeline import PipelineConfig, run_pipeline
from repro.core.qlearning import RLConfig
from repro.data import partition_by_classes
from repro.data.synthetic import fmnist_like_split
from repro.fl import FLConfig, fl_train, linear_evaluation
from repro.launch.compile_cache import enable_compile_cache
from repro.models.autoencoder import AEConfig


def main():
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    ae_cfg = AEConfig(28, 28, 1, widths=(8, 16), latent_dim=32)

    print("== building non-i.i.d. federated world (8 clients, 3 classes each)")
    ds, ev = fmnist_like_split(key, n_train_per_class=100,
                               n_eval_per_class=20)
    xs, ys, domains = partition_by_classes(0, ds.images, ds.labels,
                                           n_clients=8, classes_per_client=3,
                                           circular=True)
    print("   client label domains:", domains)

    print("== smart information exchange (PCA + K-means++ + RL, Alg. 1-2)")
    res = run_pipeline(key, xs, ys, ae_cfg,
                       PipelineConfig(rl=RLConfig(n_episodes=400,
                                                  buffer_size=50)))
    n = len(xs)
    pf = np.asarray(res.p_fail)
    print(f"   discovered links (receiver <- transmitter): "
          f"{list(enumerate(np.asarray(res.in_edge)))}")
    print(f"   mean lambda before={float(res.lam_before.mean()):.3f} "
          f"after={float(res.lam_after.mean()):.3f}  (paper Fig. 3: drops)")
    print(f"   chosen-link P_D={pf[np.arange(n), np.asarray(res.in_edge)].mean():.4f} "
          f"vs all-links mean={pf[pf < 1].mean():.4f}  (paper Fig. 4)")
    print(f"   datapoints received per client: {res.moved_counts}")

    print("== unsupervised FL (FedAvg, tau_a=10), raw vs exchanged data")
    fl_cfg = FLConfig(total_iters=200, tau_a=10, eval_every=50, batch_size=32)
    base = fl_train(jax.random.PRNGKey(5), xs, ae_cfg, fl_cfg, ev.images)
    smart = fl_train(jax.random.PRNGKey(5), res.datasets, ae_cfg, fl_cfg,
                     ev.images)
    for it, lb, ls in zip(base.eval_iters, base.eval_loss, smart.eval_loss):
        print(f"   iter {it:4d}  non-iid={lb:.5f}  smart-D2D={ls:.5f}")

    half = ev.images.shape[0] // 2
    acc_b, _ = linear_evaluation(key, base.global_params, ae_cfg,
                                 ev.images[:half], ev.labels[:half],
                                 ev.images[half:], ev.labels[half:])
    acc_s, _ = linear_evaluation(key, smart.global_params, ae_cfg,
                                 ev.images[:half], ev.labels[:half],
                                 ev.images[half:], ev.labels[half:])
    print(f"== linear evaluation: non-iid={acc_b:.3f}  smart-D2D={acc_s:.3f}")
    print("done.")


if __name__ == "__main__":
    main()
