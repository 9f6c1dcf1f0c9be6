"""AE-gated data exchange (paper Sec. III-B): the anomaly gate accepts
unfamiliar data, rejects familiar data; trust blocks transfers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exchange as EX
from repro.models.autoencoder import AEConfig, init_ae, recon_loss


AE_CFG = AEConfig(28, 28, 1, widths=(8, 16), latent_dim=16)


def _class_images(key, proto_seed, n, mirrored=False):
    proto = jax.nn.sigmoid(
        jax.image.resize(jax.random.normal(jax.random.PRNGKey(proto_seed),
                                           (1, 4, 4, 1)) * 2,
                         (1, 28, 28, 1), "bicubic"))
    if mirrored:    # the same prototype rotated by 180 degrees
        proto = proto[:, ::-1, ::-1]
    noise = jax.random.normal(key, (n, 28, 28, 1)) * 0.05
    return jnp.clip(proto + noise, 0, 1)


def _train_ae(key, x, steps=80, lr=0.05):
    params = init_ae(key, AE_CFG)
    g = jax.jit(jax.grad(recon_loss), static_argnums=2)
    for _ in range(steps):
        params = jax.tree.map(lambda p, gg: p - lr * gg, params,
                              g(params, x, AE_CFG))
    return params


@pytest.fixture(scope="module")
def trained():
    # The two classes must be of equal *intrinsic* difficulty.  The paper's
    # gate compares raw mean MSE, so a much-easier class can out-reconstruct
    # the AE's own training class and flip the decision (a real, documented
    # property of the method).  Two independently drawn prototypes leave
    # that to the PRNG stream: the pair 210/280 had comparable difficulty
    # under one stream and not under the next.  Class b is therefore class
    # a's prototype rotated by 180 degrees: the same pixel statistics, so
    # the same difficulty, yet an image the other class's AE has not seen.
    xa = _class_images(jax.random.PRNGKey(0), proto_seed=210, n=64)
    xb = _class_images(jax.random.PRNGKey(1), proto_seed=210, n=64,
                       mirrored=True)
    params = _train_ae(jax.random.PRNGKey(2), xa)
    return params, xa, xb


def test_gate_scores_unfamiliar_higher(trained):
    params, xa, xb = trained
    la = float(recon_loss(params, xa, AE_CFG))
    lb = float(recon_loss(params, xb, AE_CFG))
    assert lb > la, (la, lb)


def test_run_exchange_moves_unfamiliar_data(trained):
    params, xa, xb = trained
    datasets = [xa, xb]
    labels = [jnp.zeros(64, jnp.int32), jnp.ones(64, jnp.int32)]
    assignments = [jnp.zeros(64, jnp.int32), jnp.zeros(64, jnp.int32)]
    trust = [jnp.ones((2, 1), jnp.int8), jnp.ones((2, 1), jnp.int8)]
    in_edge = jnp.asarray([1, 0])   # 0 receives from 1 and vice versa
    pf = jnp.zeros((2, 2))
    params_b = _train_ae(jax.random.PRNGKey(3), xb)
    # premise: each AE finds the other's class unfamiliar
    assert float(recon_loss(params_b, xa, AE_CFG)) > \
        float(recon_loss(params_b, xb, AE_CFG))
    res = EX.run_exchange(jax.random.PRNGKey(4), datasets, labels,
                          assignments, trust, in_edge, pf, AE_CFG,
                          EX.ExchangeConfig(reserve_per_cluster=16),
                          ae_params=[params, params_b])
    # both AEs are well-trained on their own class -> both accept the other's
    assert res.moved_counts[0] == 16 and res.moved_counts[1] == 16
    assert res.datasets[0].shape[0] == 80
    # labels moved along with the data
    assert int(jnp.sum(res.labels[0] == 1)) == 16


def test_trust_blocks_transfer(trained):
    params, xa, xb = trained
    datasets = [xa, xb]
    labels = [jnp.zeros(64, jnp.int32), jnp.ones(64, jnp.int32)]
    assignments = [jnp.zeros(64, jnp.int32), jnp.zeros(64, jnp.int32)]
    # client 1 does NOT trust client 0 with its only cluster
    trust = [jnp.ones((2, 1), jnp.int8),
             jnp.asarray([[0], [1]], jnp.int8)]
    in_edge = jnp.asarray([1, 0])
    params_b = _train_ae(jax.random.PRNGKey(5), xb)
    assert float(recon_loss(params_b, xa, AE_CFG)) > \
        float(recon_loss(params_b, xb, AE_CFG))
    res = EX.run_exchange(jax.random.PRNGKey(6), datasets, labels,
                          assignments, trust, in_edge, pf := jnp.zeros((2, 2)),
                          AE_CFG, EX.ExchangeConfig(reserve_per_cluster=16),
                          ae_params=[params, params_b])
    assert res.moved_counts[0] == 0     # blocked by trust
    assert res.moved_counts[1] == 16    # allowed direction still flows


@pytest.mark.parametrize("method", ["loop", "batched"])
def test_gate_rejects_familiar_data(trained, method):
    params, xa, _ = trained
    # both clients hold the SAME class: gate must reject (loss not worse).
    # reserve = whole cluster so score == base exactly; a strict 16-sample
    # random subset's mean sits a coin-flip away from the full mean.
    datasets = [xa, xa + 0.0]
    labels = [jnp.zeros(64, jnp.int32)] * 2
    assignments = [jnp.zeros(64, jnp.int32)] * 2
    trust = [jnp.ones((2, 1), jnp.int8)] * 2
    in_edge = jnp.asarray([1, 0])
    res = EX.run_exchange(jax.random.PRNGKey(7), datasets, labels,
                          assignments, trust, in_edge, jnp.zeros((2, 2)),
                          AE_CFG, EX.ExchangeConfig(reserve_per_cluster=64),
                          ae_params=[params, params], method=method)
    assert res.moved_counts[0] == 0 and res.moved_counts[1] == 0


# ---------------------------------------------------------------------------
# batched engine vs reference loop plane
# ---------------------------------------------------------------------------

def _random_world(key, n=6, k=3, apply_channel=True):
    ks = jax.random.split(key, n)
    datasets = [jax.random.uniform(ks[i], (28 + 4 * i, 28, 28, 1))
                for i in range(n)]
    labels = [jax.random.randint(jax.random.fold_in(key, 50 + i),
                                 (d.shape[0],), 0, 10)
              for i, d in enumerate(datasets)]
    assigns = [jax.random.randint(jax.random.fold_in(key, 100 + i),
                                  (d.shape[0],), 0, k)
               for i, d in enumerate(datasets)]
    trust = [(jax.random.uniform(jax.random.fold_in(key, 150 + j),
                                 (n, k)) < 0.8).astype(jnp.int8)
             for j in range(n)]
    # include one self-edge (no transfer) to cover that branch
    in_edge = jnp.asarray([(i + 3) % n if i != 5 else 5 for i in range(n)])
    p_fail = jax.random.uniform(jax.random.fold_in(key, 2), (n, n)) * 0.5
    cfg = EX.ExchangeConfig(reserve_per_cluster=10,
                            apply_channel_failure=apply_channel)
    return datasets, labels, assigns, trust, in_edge, p_fail, cfg


@pytest.mark.parametrize("apply_channel", [False, True])
def test_batched_matches_loop_exactly(apply_channel):
    """The device-resident engine must reproduce the reference loop plane's
    gate decisions, moved_counts and post-exchange datasets bit-for-bit on a
    fixed seed (shared reserve selection + channel draws + pretrain keys)."""
    world = _random_world(jax.random.PRNGKey(11), apply_channel=apply_channel)
    datasets, labels, assigns, trust, in_edge, p_fail, cfg = world
    key = jax.random.PRNGKey(12)
    r_loop = EX.run_exchange(key, datasets, labels, assigns, trust, in_edge,
                             p_fail, AE_CFG, cfg, method="loop")
    r_bat = EX.run_exchange(key, datasets, labels, assigns, trust, in_edge,
                            p_fail, AE_CFG, cfg, method="batched")
    assert r_loop.gate_decisions == r_bat.gate_decisions
    np.testing.assert_array_equal(r_loop.moved_counts, r_bat.moved_counts)
    for a, b in zip(r_loop.datasets, r_bat.datasets):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(r_loop.labels, r_bat.labels):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_accepts_stacked_or_listed_params(trained):
    params, xa, xb = trained
    params_b = _train_ae(jax.random.PRNGKey(3), xb)
    from repro.core import batching
    datasets = [xa, xb]
    labels = [jnp.zeros(64, jnp.int32), jnp.ones(64, jnp.int32)]
    assigns = [jnp.zeros(64, jnp.int32)] * 2
    trust = [jnp.ones((2, 1), jnp.int8)] * 2
    in_edge = jnp.asarray([1, 0])
    args = (datasets, labels, assigns, trust, in_edge, jnp.zeros((2, 2)),
            AE_CFG, EX.ExchangeConfig(reserve_per_cluster=16))
    r_list = EX.run_exchange(jax.random.PRNGKey(4), *args,
                             ae_params=[params, params_b], method="batched")
    r_stack = EX.run_exchange(
        jax.random.PRNGKey(4), *args,
        ae_params=batching.stack_pytrees([params, params_b]),
        method="batched")
    assert r_list.gate_decisions == r_stack.gate_decisions
    np.testing.assert_array_equal(r_list.moved_counts, r_stack.moved_counts)


def test_reserve_selection_is_seeded_subset():
    """Reserves are a seeded random subset of the cluster, not the
    enumeration-order prefix; clusters at or under the budget contribute
    every member."""
    key = jax.random.PRNGKey(21)
    assigns = [jnp.zeros(100, jnp.int32), jnp.zeros(8, jnp.int32)]
    sel = EX._select_reserves(key, assigns, [1, 1], 16)
    idx = sel[0][0]
    assert idx.size == 16 and np.all(np.diff(idx) > 0)
    assert not np.array_equal(idx, np.arange(16))   # not the biased prefix
    np.testing.assert_array_equal(sel[1][0], np.arange(8))
    # deterministic in the key, different across keys
    sel2 = EX._select_reserves(key, assigns, [1, 1], 16)
    np.testing.assert_array_equal(sel2[0][0], idx)
    sel3 = EX._select_reserves(jax.random.PRNGKey(22), assigns, [1, 1], 16)
    assert not np.array_equal(sel3[0][0], idx)


def test_batched_pretrain_matches_loop_pretrain():
    """Vmapped masked-mean pretraining must agree with the per-client
    reference (same per-client keys, exact grads through the padding)."""
    key = jax.random.PRNGKey(31)
    ks = jax.random.split(key, 3)
    datasets = [jax.random.uniform(ks[i], (20 + 6 * i, 28, 28, 1))
                for i in range(3)]
    cfg = EX.ExchangeConfig(pretrain_steps=2)
    p_loop = EX.pretrain_autoencoders(key, datasets, AE_CFG, cfg)
    p_bat = EX.pretrain_autoencoders_batched(key, datasets, AE_CFG, cfg)
    for i, pl in enumerate(p_loop):
        pb = jax.tree.map(lambda x: x[i], p_bat)
        for a, b in zip(jax.tree.leaves(pl), jax.tree.leaves(pb)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)


def test_select_reserves_device_properties():
    """The on-device selector's contract, over a ragged random world: per
    (transmitter, cluster) exactly min(r, |members|) picks, members only,
    ascending valid-prefix layout, deterministic in the key.  (It draws a
    different subset than ``_select_reserves`` for the same key — the
    host-selector parity suite above pins that stream separately.)"""
    rng = np.random.default_rng(3)
    n, cap, k_max, r = 7, 9, 3, 4
    sizes = rng.integers(1, cap + 1, size=n)
    assigns = rng.integers(0, k_max, size=(n, cap)).astype(np.int32)
    # transmitter 0: one oversubscribed cluster (9 members, budget 4), so
    # the key actually has a subset to choose
    sizes[0], assigns[0, :] = cap, 0
    key = jax.random.PRNGKey(5)

    idx, mask = EX.select_reserves_device(key, assigns, sizes, k_max, r)
    idx, mask = np.asarray(idx), np.asarray(mask)
    assert idx.shape == (n, k_max, r) and mask.shape == (n, k_max, r)

    for j in range(n):
        for m in range(k_max):
            members = np.nonzero(assigns[j, :sizes[j]] == m)[0]
            got = idx[j, m][mask[j, m] > 0]
            # count: the whole cluster at or under budget, else r
            assert got.size == min(r, members.size), (j, m)
            # members only, no duplicates, ascending (host-order layout)
            assert np.all(np.isin(got, members)), (j, m)
            assert np.all(np.diff(got) > 0), (j, m)
            # dead slots are a suffix with index 0 (the padding contract)
            assert np.all(mask[j, m][:got.size] == 1.0)
            assert np.all(idx[j, m][got.size:] == 0)

    # deterministic in the key; a different key moves some oversubscribed
    # cluster's subset
    idx2, mask2 = EX.select_reserves_device(key, assigns, sizes, k_max, r)
    np.testing.assert_array_equal(idx, np.asarray(idx2))
    np.testing.assert_array_equal(mask, np.asarray(mask2))
    idx3, _ = EX.select_reserves_device(jax.random.PRNGKey(6), assigns,
                                        sizes, k_max, r)
    assert not np.array_equal(idx, np.asarray(idx3))


def test_select_reserves_device_pads_small_cap():
    """cap < r: every pick fits, the extra budget is dead padded slots."""
    assigns = np.zeros((2, 3), np.int32)
    idx, mask = EX.select_reserves_device(jax.random.PRNGKey(0), assigns,
                                          np.array([3, 2]), 2, 5)
    assert idx.shape == (2, 2, 5) and mask.shape == (2, 2, 5)
    np.testing.assert_array_equal(np.asarray(idx[0, 0]),
                                  np.array([0, 1, 2, 0, 0]))
    np.testing.assert_array_equal(np.asarray(mask[0, 0]),
                                  np.array([1, 1, 1, 0, 0], np.float32))
    np.testing.assert_array_equal(np.asarray(mask[0, 1]), np.zeros(5))
    np.testing.assert_array_equal(np.asarray(mask[1, 0]),
                                  np.array([1, 1, 0, 0, 0], np.float32))
