"""Mesh-owning sidecar (crypto/sidecar.py devices=N + ops/sharded.py pack/
dispatch split): bit-exact parity vs the single-device and host tiers,
pad-lane masking and per-device occupancy attribution, graceful degrade when
the mesh cannot be built, and the adaptive coalesce_us policy.

Runs on the conftest's virtual 8-device CPU mesh — no hardware needed; the
CPU backend is the conformance twin of the TPU path.
"""

import os
import shutil
import tempfile
import time

import numpy as np
import pytest

import jax

from corda_tpu.crypto import sidecar as sc
from corda_tpu.crypto.keys import KeyPair
from corda_tpu.crypto.provider import CpuVerifier, MeshVerifier, VerifyJob
from corda_tpu.crypto.sidecar import SidecarServer
from corda_tpu.node.verify_client import SidecarVerifier, fetch_sidecar_stats
from corda_tpu.ops import buckets

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the conftest's 8-device virtual CPU mesh")


@pytest.fixture
def sock_path():
    # Short /tmp path: AF_UNIX caps at ~108 bytes, pytest tmp_path nests deep.
    d = tempfile.mkdtemp(prefix="scm-", dir="/tmp")
    try:
        yield os.path.join(d, "s.sock")
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _jobs(n, reject_every=5):
    """n well-formed ed25519 jobs, every reject_every-th sig corrupted —
    accept AND reject lanes so pad masking can't hide a wrong answer."""
    out = []
    for i in range(n):
        kp = KeyPair.generate(bytes([(i % 250) + 1]) * 32)
        msg = (b"mesh-%04d" % i).ljust(32, b".")
        sig = bytes(kp.sign(msg).bytes)
        if i % reject_every == reject_every - 1:
            sig = sig[:7] + bytes([sig[7] ^ 0x20]) + sig[8:]
        out.append(VerifyJob(bytes(kp.sign(msg).by.encoded), msg, sig))
    return out


def _wait_gate(address, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        stats = fetch_sidecar_stats(address)
        if stats.get("device_ready") or stats.get("warm_error"):
            return stats
        time.sleep(0.02)
    raise AssertionError("sidecar warm gate never settled")


# ---------------------------------------------------------------------------
# The mesh path end to end: parity, pad masking, occupancy attribution
# ---------------------------------------------------------------------------


@needs_mesh
def test_mesh_sidecar_parity_pad_masking_and_stats(sock_path):
    srv = SidecarServer(
        sock_path,
        verifier=MeshVerifier(n_devices=8, device_min_sigs=0),
        coalesce_us=0, devices=8).start()
    try:
        stats = _wait_gate(sock_path)
        assert stats["warm_error"] is None
        assert stats["mesh_devices"] == 8  # PROVEN by the warm thread

        # 19 lanes -> bucket 64 on an 8-wide mesh: 45 pad lanes that must
        # verify False without leaking into (or out of) the real lanes.
        jobs = _jobs(19)
        want = CpuVerifier().verify_batch(jobs)
        assert want.any() and not want.all()  # accepts AND rejects
        cli = SidecarVerifier(sock_path, device_min_sigs=0,
                              deadline_ms=120_000.0, devices=8)
        out = cli.verify_batch(jobs)
        assert np.array_equal(out, want), (out.tolist(), want.tolist())
        assert cli.fallbacks == 0
        assert cli.last_tier == "device"

        stats = srv.stats()
        assert stats["device_batches"] == 1
        assert stats["host_batches"] == 0
        assert stats["device_occupancy"] == 1.0
        # The scheduler packed it (pipelined path), the executor dispatched.
        assert stats["packed_batches"] == 1
        assert stats["pack_s_total"] > 0.0
        # Exact pad attribution from the packed handle.
        assert stats["device_lanes"] == 64
        assert stats["pad_lanes"] == 64 - 19
        assert stats["pad_fraction"] == round(45 / 64, 4)
        assert stats["per_device_occupancy"] == round(19 / 64, 4)
        # 64 lanes / 8 devices = 8 lanes per device, once.
        assert stats["per_device_batch_sigs_hist"] == {"8": 1}
        assert stats["devices"] == 8

        # Client-side stamp embeds the server snapshot for node_metrics.
        side = cli.sidecar_stats()
        assert side["devices"] == 8
        assert side["server"]["mesh_devices"] == 8
        assert side["server"]["per_device_occupancy"] == round(19 / 64, 4)
    finally:
        srv.stop()


@needs_mesh
def test_mesh_matches_single_device_tier_bit_exact(sock_path):
    # Same corpus through the mesh sidecar and the single-device verifier:
    # verdicts must be IDENTICAL (the sharded graph reuses the single-chip
    # graph functions — drift would mean the tiers forked).
    from corda_tpu.crypto.provider import JaxVerifier

    jobs = _jobs(37, reject_every=4)
    single = JaxVerifier(device_min_sigs=0).verify_batch(jobs)
    srv = SidecarServer(
        sock_path,
        verifier=MeshVerifier(n_devices=8, device_min_sigs=0),
        coalesce_us=0, devices=8).start()
    try:
        _wait_gate(sock_path)
        cli = SidecarVerifier(sock_path, device_min_sigs=0,
                              deadline_ms=120_000.0)
        out = cli.verify_batch(jobs)
        assert np.array_equal(out, single)
        assert np.array_equal(out, CpuVerifier().verify_batch(jobs))
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Degrade lanes: mesh unavailable / devices=1
# ---------------------------------------------------------------------------


def test_unbuildable_mesh_degrades_to_exact_host_tier(sock_path):
    # 64 devices don't exist: the warm thread must record WHY, keep the
    # gate closed forever, and every batch must host-route to the
    # oracle-exact tier — degraded throughput, never a wrong answer.
    srv = SidecarServer(
        sock_path,
        verifier=MeshVerifier(n_devices=64, device_min_sigs=0),
        coalesce_us=0, devices=64).start()
    try:
        stats = _wait_gate(sock_path)
        assert stats["warm_error"] and "64" in stats["warm_error"]
        assert stats["mesh_devices"] is None
        assert stats["device_ready"] is False

        jobs = _jobs(12)
        cli = SidecarVerifier(sock_path, device_min_sigs=0,
                              deadline_ms=60_000.0)
        out = cli.verify_batch(jobs)
        assert np.array_equal(out, CpuVerifier().verify_batch(jobs))
        assert cli.fallbacks == 0  # the SERVER answered (host tier)
        assert cli.last_tier == "host"

        stats = srv.stats()
        assert stats["device_batches"] == 0
        assert stats["host_batches"] == 1
        assert stats["packed_batches"] == 0  # gate closed -> pack refused
        assert stats["device_lanes"] == 0 and stats["pad_lanes"] == 0
    finally:
        srv.stop()


def test_devices_one_keeps_single_device_verifier():
    # devices<=1 must keep the PR-5 tiers bit-identical; only devices>1
    # upgrades a jax tier to the mesh; cpu ignores devices entirely.
    make = SidecarServer._make_server_verifier
    assert make("jax", 1).name == "jax-batch"
    assert make("jax", 0).name == "jax-batch"
    assert make("jax", 8).name == "jax-sharded"
    assert make("jax", 8).n_devices == 8
    assert make("jax-shadow", 4).shadow_rate == 0.05
    assert make("jax-sharded", 2).n_devices == 2
    assert make("cpu", 8).name == "cpu-openssl"


@pytest.mark.parametrize("n, n_devices, want", [
    (19, 8, 24), (64, 8, 64), (1, 8, 8), (0, 8, 8), (65, 8, 72),
    (100, 1, 100), (8, 8, 8), (9, 8, 16),
    # Every rung of the ladder is already a multiple of 1, 2, 4 and 8:
    # power-of-two meshes pad nothing beyond the ladder.
    *[(b, ndev, b) for b in buckets.LADDER for ndev in (1, 2, 4, 8)]])
def test_pad_to_devices_arithmetic(n, n_devices, want):
    assert buckets.pad_to_devices(n, n_devices) == want


# ---------------------------------------------------------------------------
# Adaptive coalesce_us (no timing: the policy is driven directly)
# ---------------------------------------------------------------------------


def _adapt_server(coalesce_us, max_sigs=4096):
    # __init__ binds nothing; start() is never called — pure policy unit.
    return SidecarServer("/tmp/unused-adapt.sock", verifier=CpuVerifier(),
                         coalesce_us=coalesce_us, max_sigs=max_sigs,
                         adaptive_coalesce=True)


def _feed(srv, n_requests, n_sigs, batches=sc.ADAPT_WINDOW):
    for _ in range(batches):
        srv._adapt_observe(n_requests, n_sigs)


def test_adaptive_coalesce_shrinks_when_batches_fill_early():
    srv = _adapt_server(1000)
    _feed(srv, n_requests=4, n_sigs=2048)  # mean >= max_sigs/2
    assert srv.coalesce_us == 750  # 1000 * ADAPT_SHRINK
    assert srv.coalesce_adjustments == 1
    assert srv.coalesce_us_initial == 1000  # the initial value is stamped


def test_adaptive_coalesce_grows_only_while_coalescing():
    srv = _adapt_server(1000)
    # Small batches but NO cross-request coalescing (1 request per batch):
    # a longer window would not attract company — no change.
    _feed(srv, n_requests=1, n_sigs=100)
    assert srv.coalesce_us == 1000
    # Same fill WITH coalescing: grow toward the ceiling.
    _feed(srv, n_requests=3, n_sigs=100)
    assert srv.coalesce_us == 1500  # 1000 * ADAPT_GROW
    # From zero, growth seeds at ADAPT_SEED_US (0 * anything stays 0).
    srv0 = _adapt_server(0)
    _feed(srv0, n_requests=2, n_sigs=64)
    assert srv0.coalesce_us == sc.ADAPT_SEED_US


def test_adaptive_coalesce_hysteresis_band_and_ceiling():
    srv = _adapt_server(1000)
    # Between max_sigs/4 and max_sigs/2: the hysteresis band — no change.
    _feed(srv, n_requests=4, n_sigs=1500)
    assert srv.coalesce_us == 1000
    assert srv.coalesce_adjustments == 0
    # Growth is capped at ADAPT_CEILING_US.
    srv_hi = _adapt_server(19_000)
    _feed(srv_hi, n_requests=2, n_sigs=64)
    assert srv_hi.coalesce_us == sc.ADAPT_CEILING_US


def test_adaptive_coalesce_off_by_default(sock_path):
    srv = SidecarServer(sock_path, verifier=CpuVerifier(), coalesce_us=0)
    try:
        assert srv.adaptive_coalesce is False
        stats_keys = srv.stats()
        assert stats_keys["adaptive_coalesce"] is False
        assert stats_keys["coalesce_adjustments"] == 0
    finally:
        pass  # never started — nothing to stop
