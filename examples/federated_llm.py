"""The paper's technique on an assigned LLM architecture.

    PYTHONPATH=src python examples/federated_llm.py [--arch llama3.2-1b]

Six clients hold topic-skewed token data (the LLM analogue of non-i.i.d.
class skew).  The same core pipeline drives D2D exchange — features are
mean-pooled frozen-random embeddings (core.features), clustering/rewards/RL
identical to the image case — then each client trains its (reduced) LLM
locally with FedAvg aggregation every tau_a steps, and we compare held-out
perplexity with vs without the exchange."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs import ARCH_IDS, TrainConfig, get_smoke_config
from repro.core import channel as ch
from repro.core import dissimilarity as dis
from repro.core import features as feat
from repro.core import kmeans as km
from repro.core import pca as pca_lib
from repro.core import qlearning as ql
from repro.core import rewards as rw
from repro.core import trust as tr
from repro.data.tokens import make_client_token_data
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model, make_train_step

N_CLIENTS = 6
SEQ = 64


def discover_and_exchange(key, datasets, vocab):
    """Paper Alg. 1 on token data + sequence-level exchange."""
    table = feat.random_embed_table(jax.random.PRNGKey(1234), vocab, 64)
    flats = [feat.token_sequence_features(d, table) for d in datasets]
    pca = pca_lib.fit_pca_federated(flats, 16)
    cents, assigns = [], []
    for i, f in enumerate(flats):
        res = km.kmeans(jax.random.fold_in(key, i), pca.transform(f), 2)
        cents.append(res.centroids)
        assigns.append(res.assignments)
    trust = tr.make_trust(jax.random.fold_in(key, 7), N_CLIENTS, 2, 0.95)
    pf = ch.failure_prob(ch.make_rss(jax.random.fold_in(key, 8), N_CLIENTS))
    beta = dis.median_heuristic_beta(cents, 0.8)
    lam = dis.lambda_matrix(cents, trust, beta)
    local_r = rw.local_reward_matrix(lam, pf)
    graph = ql.discover_graph(jax.random.fold_in(key, 9), local_r, pf,
                              ql.RLConfig(n_episodes=300, buffer_size=50))
    print("   lambda matrix:\n", np.asarray(lam))
    print("   links (rx <- tx):", list(enumerate(np.asarray(graph.in_edge))))
    # sequence-level exchange: move 25% of each trusted far cluster
    new = [np.asarray(d) for d in datasets]
    for i in range(N_CLIENTS):
        j = int(graph.in_edge[i])
        take = np.asarray(assigns[j]) == int(
            np.argmax(np.linalg.norm(
                np.asarray(cents[j])[:, None]
                - np.asarray(cents[i]).mean(0)[None, None], axis=-1)))
        idx = np.nonzero(take)[0][: len(take) // 4]
        if idx.size and int(trust[j][i].max()) > 0:
            new[i] = np.concatenate([new[i], np.asarray(datasets[j])[idx]])
    return [jnp.asarray(d) for d in new], graph


def fed_train_llm(key, model, datasets, steps=30, tau_a=5, batch=4):
    tc = TrainConfig(optimizer="adamw", learning_rate=1e-3, total_steps=steps,
                     warmup_steps=5)
    step_fn = jax.jit(make_train_step(model, tc))
    g_params = model.init(key)
    params = [g_params] * N_CLIENTS
    opts = [optim.init_opt_state(g_params, tc.optimizer)] * N_CLIENTS
    for t in range(steps):
        for i in range(N_CLIENTS):
            kk = jax.random.fold_in(key, t * 100 + i)
            idx = jax.random.randint(kk, (batch,), 0, datasets[i].shape[0])
            toks = datasets[i][idx]
            b = {"tokens": toks, "labels": toks}
            params[i], opts[i], m = step_fn(params[i], opts[i], b)
        if (t + 1) % tau_a == 0:  # FedAvg aggregation + broadcast
            g_params = jax.tree.map(
                lambda *ps: sum(ps) / len(ps), *params)
            params = [g_params] * N_CLIENTS
    return g_params


def eval_ppl(model, params, key, vocab):
    from repro.data.tokens import topic_token_batch
    # held-out mix over ALL topics — the global objective
    toks = jnp.concatenate([
        topic_token_batch(jax.random.fold_in(key, 50 + t), batch=4,
                          seq_len=SEQ, vocab=vocab, topic=t)
        for t in range(8)])
    loss, _ = model.loss_fn(params, {"tokens": toks, "labels": toks})
    return float(jnp.exp(loss))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)

    print(f"== {N_CLIENTS} clients with topic-skewed token data "
          f"(arch={cfg.name}, reduced)")
    datasets, domains = make_client_token_data(
        key, n_clients=N_CLIENTS, n_seqs=64, seq_len=SEQ,
        vocab=cfg.vocab_size, topics_per_client=2)
    print("   topic domains:", domains)

    print("== RL graph discovery + sequence exchange (paper Alg. 1)")
    exchanged, graph = discover_and_exchange(key, datasets, cfg.vocab_size)

    print(f"== federated training ({args.steps} steps, tau_a=5)")
    p_base = fed_train_llm(jax.random.PRNGKey(3), model, datasets,
                           steps=args.steps)
    p_smart = fed_train_llm(jax.random.PRNGKey(3), model, exchanged,
                            steps=args.steps)
    ppl_base = eval_ppl(model, p_base, key, cfg.vocab_size)
    ppl_smart = eval_ppl(model, p_smart, key, cfg.vocab_size)
    print(f"== held-out (all-topic) perplexity: "
          f"non-iid={ppl_base:.2f}  smart-D2D={ppl_smart:.2f}")


if __name__ == "__main__":
    main()
