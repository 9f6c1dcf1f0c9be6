"""Sharded vs single-device parity of the client-stacked device planes.

A child process runs under ``--xla_force_host_platform_device_count=4`` (the
parent's device count is already frozen) and reports digests/deltas for the
clustering program, exchange engine, AE pretraining, one FL segment and the
RL discovery bursts (mixed policy, UCB, and a warm-started resume) at mesh
sizes 1 and 4 against the plain unsharded program
(``repro.meshlab.parity_report``).

Contract:
  * mesh=1 placement is **bit-identical** to the single-device path for all
    programs (the acceptance bar for enabling sharding by default);
  * at mesh=4 the gate and pretraining stay bit-identical — per-client work
    has no cross-client reduction, so shards compute the same bits;
  * the FL round's FedAvg mean is a cross-shard all-reduce whose float sums
    reassociate — parity there is a ~1e-7 param delta, not bit equality;
  * the discovery plane's two collectives (episode-mean reward, r_net)
    reassociate the same way and the deltas feed back through the Q-table
    accumulation, so parity at mesh=4 is a small Q delta plus agreement of
    the final Eq. 7 links;
  * the clustering program (stacked federated PCA + vmapped K-means++) is
    bit-identical to the per-client host-loop reference on a single device
    and at mesh=1; at mesh=4 its one collective (the PCA moment
    ``client_sum``) reassociates, so the bar is a <=1e-6 centroid delta
    with every cluster assignment unchanged.
"""
import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.mesh, pytest.mark.slow]

_TAG = "MESH_PARITY "


@pytest.fixture(scope="module")
def report():
    # CPU only: the parent may hold an accelerator, and the child needs
    # forced host devices anyway
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    child = os.path.join(os.path.dirname(__file__), "mesh_parity_child.py")
    proc = subprocess.run([sys.executable, child], env=env,
                          capture_output=True, text=True, timeout=1500)
    rep = None
    for line in proc.stdout.splitlines():
        if line.startswith(_TAG):
            rep = json.loads(line[len(_TAG):])
    assert rep is not None, (
        f"mesh parity child failed (rc={proc.returncode}):\n"
        f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    if rep["device_count"] < 4:
        pytest.skip("--xla_force_host_platform_device_count not honoured "
                    f"(got {rep['device_count']} devices)")
    return rep


def test_mesh1_bit_identical_to_single_device(report):
    """Sharding rules on a 1-device mesh change nothing, bit for bit."""
    for path in ("gate", "pretrain", "fl", "cluster",
                 "disc", "disc_ucb", "disc_warm"):
        assert report[f"{path}_digest_mesh1"] == \
            report[f"{path}_digest_base"], path


def test_cluster_stacked_matches_host_loop_bitwise(report):
    """The jitted stacked clustering program equals the per-client host
    loop bit-for-bit (masked moments, seeding draws, Lloyd updates)."""
    assert report["cluster_loop_bitwise"]


def test_cluster_sharded_parity(report):
    """mesh=4: only the PCA moment all-reduce reassociates — centroids
    near the single-device program, assignments unchanged.

    Tolerance 5e-6, not an ulp bound: the reassociated moment sums shift
    the Gram matrix by an ulp or two, and ``eigh``'s iteration amplifies
    that through the projection (observed drift ~2.5e-6 on CPU, varying
    with the XLA reduction order the host count induces).  Assignment
    agreement below is the exact invariant; the centroid bound only needs
    to catch a broken collective, not reduction-order noise."""
    assert report["cluster_cents_maxdiff_mesh4"] <= 5e-6
    assert report["cluster_assign_agree_mesh4"] == \
        report["cluster_assign_total_mesh4"]


def test_gate_sharded_bit_parity(report):
    assert report["gate_digest_mesh4"] == report["gate_digest_base"]
    assert report["gate_maxdiff_mesh4"] == 0.0


def test_pretrain_sharded_bit_parity(report):
    assert report["pretrain_digest_mesh4"] == report["pretrain_digest_base"]
    assert report["pretrain_maxdiff_mesh4"] == 0.0


def test_fl_segment_sharded_parity(report):
    """The all-reduced FedAvg mean reassociates float sums across shards;
    anything beyond ~1e-5 would be a real partitioning bug."""
    assert report["fl_maxdiff_mesh4"] < 1e-5


def test_discovery_sharded_parity(report):
    """Each episode folds the two all-reduced scalars back into the Q
    accumulation, so reassociation deltas compound over the burst — but
    stay orders of magnitude below reward scale; the discovered graph
    (Eq. 7 argmax) should be unaffected."""
    n = 8  # LabConfig().n_clients
    for name in ("disc", "disc_ucb", "disc_warm"):
        assert report[f"{name}_q_maxdiff_mesh4"] < 1e-3, name
        assert report[f"{name}_edge_agree_mesh4"] == n, name
