"""Benchmark: batched Ed25519 verification + notarisation round trip.

Runs on a TPU and nowhere else: the phases that drive the chip run in one
child process (a chip belongs to one process at a time), the multiprocess
cluster configs from this JAX-free parent afterwards. Without a TPU, or
when any phase raises, it exits non-zero. Prints ONE JSON line:

  {"metric": "verified_sigs_per_sec", "value": N, "unit": "sigs/sec",
   "vs_baseline": N, ...}

vs_baseline is value / 50_000 — the BASELINE.md north-star target
(>= 50k verified sigs/sec on one TPU v5e-1 chip).  The workload mirrors the
reference's raft-notary-demo driven through NotaryFlow (reference:
samples/raft-notary-demo/src/main/kotlin/net/corda/notarydemo/NotaryDemo.kt:
14-29, core/.../flows/NotaryFlow.kt:96-147): every signature rides the batch
axis of the JAX verify kernel instead of the reference's sequential
EdDSAEngine loop (core/.../transactions/SignedTransaction.kt:83-87).

Measurements:
  kernel_sigs_per_sec[bucket]  device graph only (arrays resident, jit warm)
  e2e_sigs_per_sec[bucket]     host packing (SHA-512 challenge, bit unpack,
                               transfer) + kernel + readback
  sha256_hashes_per_sec        batched 64-byte Merkle-node hashing kernel
  notary_roundtrip             MockNetwork notarisation flows with the
                               JaxVerifier: tx/sec and per-flow p50/p99
  cpu_oracle_sigs_per_sec      the pure-Python conformance oracle, for scale
"""

from __future__ import annotations

import json
import statistics
import time

import numpy as np


BASELINE_SIGS_PER_SEC = 50_000.0
BUCKETS = (4096, 16384, 65536)
N_DISTINCT = 64  # distinct (pk, msg, sig) tuples, tiled to bucket size


def make_corpus(n_distinct: int = N_DISTINCT):
    """n distinct signatures, 1 in 8 corrupted (notaries see mostly-valid)."""
    from corda_tpu.crypto import ref_ed25519 as ref

    pks, msgs, sigs, valid = [], [], [], []
    for i in range(n_distinct):
        sk = bytes([(i % 255) + 1]) * 32
        pk = ref.public_key(sk)
        m = (b"bench-tx-id-%06d" % i).ljust(32, b".")  # tx ids are 32 bytes
        s = ref.sign(sk, m)
        ok = i % 8 != 7
        if not ok:
            s = s[:10] + bytes([s[10] ^ 0x40]) + s[11:]
        pks.append(pk)
        msgs.append(m)
        sigs.append(s)
        valid.append(ok)
    return pks, msgs, sigs, valid


def tile(xs, n):
    return [xs[i % len(xs)] for i in range(n)]


def _time_median(fn, repeats=5):
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def warm_buckets(pks, msgs, sigs):
    """Compile the verify backends (Pallas kernel + device-hash route) at
    every bucket outside any timed region; the persistent compile cache
    keeps them for the next process."""
    import jax

    from corda_tpu.ops import ed25519_jax

    for bucket in BUCKETS:
        bp, bm, bs = tile(pks, bucket), tile(msgs, bucket), tile(sigs, bucket)
        arrays, _ = ed25519_jax.precompute_batch(bp, bm, bs, bucket=bucket)
        ed25519_jax.verify_arrays_auto(
            *jax.device_put(arrays)).block_until_ready()
        darrays, _ = ed25519_jax.precompute_batch_device(bp, bm, bs,
                                                         bucket=bucket)
        np.asarray(ed25519_jax.verify_arrays_hashed(*darrays))


def bench_kernel(pks, msgs, sigs, valid):
    """Device-only and end-to-end verify throughput per bucket size.
    Returns (kernel, e2e, devhash, backends) — backends records which
    backend (pallas/xla) produced each timed number."""
    import jax

    from corda_tpu.ops import ed25519_jax

    kernel, e2e, devhash = {}, {}, {}
    backends = {"kernel": {}, "e2e": {}, "e2e_devhash": {}}
    for bucket in BUCKETS:
        bp = tile(pks, bucket)
        bm = tile(msgs, bucket)
        bs = tile(sigs, bucket)
        arrays, _ = ed25519_jax.precompute_batch(bp, bm, bs, bucket=bucket)
        arrays = jax.device_put(arrays)

        def run_kernel():
            ed25519_jax.verify_arrays_auto(*arrays).block_until_ready()

        run_kernel()  # compile
        out = np.asarray(ed25519_jax.verify_arrays_auto(*arrays))
        expect = tile(valid, bucket)
        assert out.tolist() == expect, "kernel diverged from oracle expectation"
        kernel[bucket] = bucket / _time_median(run_kernel)
        backends["kernel"][bucket] = ed25519_jax.last_backend()

        def run_e2e():
            a, _ = ed25519_jax.precompute_batch(bp, bm, bs, bucket=bucket)
            np.asarray(ed25519_jax.verify_arrays_auto(*a))

        run_e2e()
        e2e[bucket] = bucket / _time_median(run_e2e, repeats=3)
        backends["e2e"][bucket] = ed25519_jax.last_backend()
        del arrays  # cap device residency before the next phase

        def run_devhash():
            a, _ = ed25519_jax.precompute_batch_device(bp, bm, bs,
                                                       bucket=bucket)
            np.asarray(ed25519_jax.verify_arrays_hashed(*a))

        run_devhash()  # compile
        out = np.asarray(ed25519_jax.verify_arrays_hashed(
            *ed25519_jax.precompute_batch_device(bp, bm, bs,
                                                 bucket=bucket)[0]))
        assert out.tolist() == expect, "device-hash path diverged from oracle"
        devhash[bucket] = bucket / _time_median(run_devhash, repeats=3)
        backends["e2e_devhash"][bucket] = ed25519_jax.last_backend()
    return kernel, e2e, devhash, backends


def bench_stream(pks, msgs, sigs, valid, bucket=65536, batches=5,
                 repeats=3):
    """Sustained throughput with the depth-2 stream pipeline: host packing
    and transfer of the next batches overlap device execution of the
    current one (the notary-pump steady state).

    Best of `repeats` timed passes, with every pass reported so the
    run-to-run spread stays visible."""
    from corda_tpu.ops import ed25519_jax

    bp, bm, bs = tile(pks, bucket), tile(msgs, bucket), tile(sigs, bucket)
    expect = tile(valid, bucket)

    def gen(k):
        for _ in range(k):
            yield bp, bm, bs

    for out in ed25519_jax.verify_stream(gen(2), bucket=bucket):  # warm
        assert out.tolist() == expect, "stream diverged from oracle"
    rates = []
    backends_per_pass = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        consumed = 0
        for out in ed25519_jax.verify_stream(gen(batches), bucket=bucket):
            consumed += len(out)
        dt = time.perf_counter() - t0
        assert consumed == batches * bucket
        rates.append(consumed / dt)
        # Stamp per pass: a mid-repeats Pallas trip must not attribute the
        # winning (earlier, Pallas) pass to the XLA fallback or vice versa.
        backends_per_pass.append(ed25519_jax.last_backend())
    best = max(range(repeats), key=lambda i: rates[i])
    return (rates[best], [round(r, 1) for r in rates],
            backends_per_pass[best])


def bench_sha256(n=16384):
    """Batched Merkle-node (64-byte) hashing throughput."""
    import jax

    from corda_tpu.ops import sha256_jax

    msgs = np.arange(n * 64, dtype=np.uint64).view(np.uint8)[: n * 64]
    msgs = msgs.reshape(n, 64)
    blocks = jax.device_put(sha256_jax.pack_messages(msgs))

    def run():
        sha256_jax.sha256_blocks(blocks).block_until_ready()

    run()
    return n / _time_median(run)


def bench_cpu_oracle(pks, msgs, sigs, seconds=2.0):
    from corda_tpu.crypto import ref_ed25519 as ref

    count = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        i = count % len(sigs)
        ref.verify(pks[i], msgs[i], sigs[i])
        count += 1
    return count / (time.perf_counter() - t0)


def bench_notary_roundtrip(n_flows=64, verifier=None):
    """End-to-end notarisation over MockNetwork with the JAX verifier:
    issue -> move -> NotaryClientFlow per transaction, all concurrent, one
    pump; reports tx/sec and per-flow p50/p99 (the BASELINE.md latency
    metric, measured over the deterministic in-process network)."""
    from corda_tpu.crypto.provider import (
        CpuVerifier, JaxVerifier, set_verifier)
    from corda_tpu.flows.notary import NotaryClientFlow
    from corda_tpu.testing.dummies import DummyContract
    from corda_tpu.testing.mock_network import MockNetwork

    verifier = verifier or JaxVerifier()
    set_verifier(verifier)
    try:
        net = MockNetwork(verifier=verifier)
        notary = net.create_notary_node("Notary", validating=False)
        alice = net.create_node("Alice")

        stxs = []
        for i in range(n_flows):
            builder = DummyContract.generate_initial(
                alice.identity.ref(bytes([i % 256])), i, notary.identity)
            builder.sign_with(alice.key)
            issue_stx = builder.to_signed_transaction()
            alice.record_transaction(issue_stx)
            move = DummyContract.move(
                issue_stx.tx.out_ref(0), alice.identity.owning_key)
            move.sign_with(alice.key)
            stxs.append(
                move.to_signed_transaction(check_sufficient_signatures=False))

        # Warm the pump-path executable OUTSIDE the timed region (the CPU
        # verifier never touches the device).
        if not isinstance(verifier, CpuVerifier):
            _warm_verify_kernel()

        t0 = time.perf_counter()
        done_at = []
        handles = []
        for stx in stxs:
            h = alice.start_flow(NotaryClientFlow(stx))
            h.result.add_done_callback(
                lambda _f: done_at.append(time.perf_counter() - t0))
            handles.append(h)
        net.run_network()
        total = time.perf_counter() - t0
        for h in handles:
            h.result.result()  # raise on any failure
        lat = sorted(done_at)
        return {
            "tx_per_sec": round(n_flows / total, 1),
            "p50_ms": round(1e3 * lat[len(lat) // 2], 2),
            "p99_ms": round(
                1e3 * lat[min(len(lat) - 1, int(len(lat) * 0.99))], 2),
            **_verifier_stamp(verifier),
        }
    finally:
        set_verifier(None)


def _verifier_stamp(verifier) -> dict:
    """Self-describing config stamp (round-4 verdict weak #4): every
    framework number records WHICH verifier produced it, and — for jax
    verifiers only — which kernel backend served the newest call (via
    ops.last_backend_if_loaded, which never imports the kernel module
    into a host-only run)."""
    from corda_tpu.ops import last_backend_if_loaded

    name = getattr(verifier, "name", type(verifier).__name__)
    backend = None
    if isinstance(name, str) and name.startswith("jax"):
        backend = last_backend_if_loaded()
    stamp = {"verifier": name, "backend": backend}
    # Size-crossover routing counters (JaxVerifier.device_min_sigs): where
    # did the batches actually go — a "jax-batch" stamp whose work all
    # routed to the host tier must say so.
    if getattr(verifier, "device_batches", None) is not None:
        stamp["device_batches"] = verifier.device_batches
        stamp["host_batches"] = verifier.host_batches
        stamp["device_min_sigs"] = verifier.device_min_sigs
        total = verifier.device_batches + verifier.host_batches
        # Occupancy at a glance: the r05 regression class (device_batches=0
        # buried in a long stamp) reads as 0.0 here instead of hiding.
        stamp["device_occupancy"] = (
            round(verifier.device_batches / total, 3) if total else 0.0)
        if verifier.device_batches == 0 and verifier.host_batches > 0:
            # The kernel backend did not produce THIS config's numbers —
            # every batch took the host tier (last_backend would report
            # whatever the warm-up compiled, a misattribution).
            stamp["backend"] = "host-routed"
    return stamp


def _warm_verify_kernel():
    """Compile the pump-path executable (device-hash route for 32-byte tx
    ids at the small bucket) outside any timed/deadlined region. Production
    nodes warm at boot the same way."""
    from corda_tpu.ops import ed25519_jax as _ej

    _ej.verify_batch([bytes(32)], [bytes(32)], [bytes(64)])


def _churn_flows():
    """Module-level (qualname-stable) flow pair for bench_flow_churn —
    flow names are registry keys, so they must not be function-local."""
    from corda_tpu.flows.api import FlowLogic, flow_registry, register_flow

    existing = flow_registry.get("ChurnPing")
    if existing is not None:
        return existing, flow_registry.get("ChurnPong")

    @register_flow(name="ChurnPing")
    class ChurnPing(FlowLogic):
        def __init__(self, other, payload):
            self.other = other
            self.payload = payload

        def call(self):
            reply = yield self.send_and_receive(self.other, self.payload)
            return reply.unwrap()

    @register_flow(name="ChurnPong")
    class ChurnPong(FlowLogic):
        def __init__(self, other):
            self.other = other

        def call(self):
            got = yield self.receive(self.other)
            yield self.send(self.other, got.unwrap() * 2)

    return ChurnPing, ChurnPong


def bench_flow_churn(n_flows=512):
    """Flow-machinery throughput: request/response flow pairs per second
    over MockNetwork, checkpointing at every suspension. The reference
    whitepaper names fiber checkpointing (stack walk + Kryo + DB write per
    suspend) as the node's main bottleneck
    (corda-technical-whitepaper.tex:1630-1638); this measures our
    replay-log checkpoint design on the same shape of workload."""
    from corda_tpu.testing.mock_network import MockNetwork

    ChurnPing, ChurnPong = _churn_flows()
    net = MockNetwork()
    try:
        a = net.create_node("ChurnA")
        b = net.create_node("ChurnB")
        b.smm.register_flow_initiator(
            "ChurnPing", lambda party: ChurnPong(party))
        # warm one round (session handshake code paths)
        h = a.start_flow(ChurnPing(b.identity, 1))
        net.run_network()
        assert h.result.result() == 2
        base = (a.smm.metrics.get("checkpointing_rate", 0)
                + b.smm.metrics.get("checkpointing_rate", 0))
        t0 = time.perf_counter()
        handles = [a.start_flow(ChurnPing(b.identity, i))
                   for i in range(n_flows)]
        net.run_network()
        dt = time.perf_counter() - t0
        for i, h in enumerate(handles):
            assert h.result.result() == 2 * i
        checkpoints = (a.smm.metrics.get("checkpointing_rate", 0)
                       + b.smm.metrics.get("checkpointing_rate", 0)) - base
        return {"flow_pairs_per_sec": round(n_flows / dt, 1),
                "checkpoints_recorded": checkpoints}
    finally:
        net.stop_nodes()


def bench_trades(n_trades=6, verifier=None):
    """BASELINE config 2 (trader-demo): DvP CommercialPaper-for-cash trades
    through the validating notary over MockNetwork. Issues happen outside
    the timed region; each timed trade is the full SellerFlow/BuyerFlow
    composition (resolution, contract verify, notarise, broadcast)."""
    from corda_tpu.contracts.structures import Issued, Timestamp, now_micros
    from corda_tpu.crypto.provider import (
        CpuVerifier, JaxVerifier, set_verifier)
    from corda_tpu.finance import Amount, Cash
    from corda_tpu.finance.commercial_paper import CommercialPaper
    from corda_tpu.finance.trade import BuyerFlow, SellerFlow
    from corda_tpu.flows.notary import NotaryClientFlow
    from corda_tpu.testing.mock_network import MockNetwork

    WEEK = 7 * 86_400 * 1_000_000
    verifier = verifier or JaxVerifier()
    set_verifier(verifier)
    try:
        # Warm the kernel FIRST: a cold jit compile mid-issue would stall
        # past the notary's timestamp tolerance window.
        if not isinstance(verifier, CpuVerifier):
            _warm_verify_kernel()
        net = MockNetwork(verifier=verifier)
        notary = net.create_notary_node("Notary", validating=True)
        seller = net.create_node("Seller")
        buyer = net.create_node("Buyer")
        papers = []
        for i in range(n_trades):
            ref = seller.identity.ref(bytes([i + 1]))
            issue = CommercialPaper.generate_issue(
                ref, Amount(900, Issued(ref, "USD")),
                now_micros() + WEEK, notary.identity)
            issue.set_time(Timestamp.around(now_micros(), 30_000_000))
            issue.sign_with(seller.key)
            stx = issue.to_signed_transaction(
                check_sufficient_signatures=False)
            h = seller.start_flow(NotaryClientFlow(stx))
            net.run_network()
            stx = stx.with_additional_signature(h.result.result())
            seller.record_transaction(stx)
            papers.append(stx.tx.out_ref(0))
            cash = Cash.generate_issue(
                Amount(800, "USD"), buyer.identity.ref(bytes([i + 1])),
                buyer.identity.owning_key, notary.identity, nonce=i)
            cash.sign_with(buyer.key)
            buyer.record_transaction(cash.to_signed_transaction())
        buyer.register_initiated_flow(
            "SellerFlow",
            lambda party: BuyerFlow(party, Amount(750, "USD"),
                                    notary.identity))
        durations = []
        t0 = time.perf_counter()
        for paper in papers:
            t1 = time.perf_counter()
            h = seller.start_flow(SellerFlow(
                buyer.identity, paper, Amount(750, "USD")))
            net.run_network()
            h.result.result()
            durations.append(time.perf_counter() - t1)
        dt = time.perf_counter() - t0
        return {"trades_per_sec": round(n_trades / dt, 2),
                "trade_median_ms": round(
                    1e3 * statistics.median(durations), 1),
                **_verifier_stamp(verifier)}
    finally:
        set_verifier(None)


def bench_multisig(n_distinct=64, tile_to=2048, verifier=None):
    """BASELINE config 4: 3-of-3 CompositeKey multi-sig fan-out — kernel
    verify of all constituent signatures plus the host-side composite
    fulfilment walk per transaction."""
    from corda_tpu.crypto.composite import CompositeKey
    from corda_tpu.crypto.keys import KeyPair
    from corda_tpu.crypto.provider import JaxVerifier, VerifyJob

    signers = [KeyPair.generate(bytes([0x31 + i]) * 32) for i in range(3)]
    composite = CompositeKey.Builder().add_keys(
        *[CompositeKey.leaf(kp.public) for kp in signers]).build(threshold=3)
    txs = []
    rng = np.random.default_rng(5)
    for i in range(n_distinct):
        msg = rng.integers(0, 256, 32, np.uint8).tobytes()
        sigs = [kp.sign(msg) for kp in signers]
        if i % 8 == 7:  # drop a signature: fulfilment must fail
            sigs = sigs[:2]
        txs.append((msg, sigs))
    txs = [txs[i % n_distinct] for i in range(tile_to)]

    verifier = verifier or JaxVerifier()
    jobs = [VerifyJob(sig.by.encoded, msg, sig.bytes)
            for msg, sigs in txs for sig in sigs]
    spans = []
    start = 0
    for msg, sigs in txs:
        spans.append((start, start + len(sigs)))
        start += len(sigs)

    def run():
        ok = verifier.verify_batch(jobs)
        fulfilled = 0
        for (msg, sigs), (lo, hi) in zip(txs, spans):
            valid = {sigs[k - lo].by for k in range(lo, hi) if ok[k]}
            if composite.is_fulfilled_by(valid):
                fulfilled += 1
        return fulfilled

    fulfilled = run()  # compile + correctness
    assert fulfilled == sum(1 for m, s in txs if len(s) == 3), fulfilled
    dt = _time_median(run, repeats=3)
    return {"sigs_per_sec": round(len(jobs) / dt, 1),
            "tx_per_sec": round(len(txs) / dt, 1),
            **_verifier_stamp(verifier)}


def bench_partial_merkle(n_cmds=8, repeats=2000):
    """BASELINE config 5 (simm-valuation shape): FilteredTransaction
    tear-off proof verification rate (host-side partial-Merkle walk, the
    oracle's per-request hot path)."""
    from corda_tpu.contracts.structures import Command
    from corda_tpu.crypto.keys import KeyPair
    from corda_tpu.crypto.party import Party
    from corda_tpu.flows.oracle import Fix, FixOf
    from corda_tpu.testing.dummies import DummyContract
    from corda_tpu.transactions.builder import TransactionBuilder
    from corda_tpu.transactions.filtered import (
        FilteredTransaction, FilterFuns)

    notary = Party.of("N", KeyPair.generate(b"\x41" * 32).public)
    party = Party.of("P", KeyPair.generate(b"\x42" * 32).public)
    builder = DummyContract.generate_initial(party.ref(b"\x01"), 1, notary)
    for i in range(n_cmds):
        builder.add_command(Command(Fix(FixOf("LIBOR", 20_000 + i, "3M"),
                                        42_500 + i),
                                    (party.owning_key,)))
    wtx = builder.to_wire_transaction()
    ftx = FilteredTransaction.build_merkle_transaction(
        wtx, FilterFuns(filter_commands=lambda c: isinstance(c.value, Fix)))
    assert ftx.verify(wtx.id)
    t0 = time.perf_counter()
    for _ in range(repeats):
        ftx.verify(wtx.id)
    dt = time.perf_counter() - t0
    return {"proofs_per_sec": round(repeats / dt, 1),
            "revealed_commands": n_cmds}


def bench_raft_cluster(n_tx=1000, width=32, verifier="cpu",
                       notary_device="cpu", notary="raft", sidecar=False,
                       sidecar_devices=0, adaptive_coalesce=False):
    """BASELINE config 1 (raft-notary-demo) at BASELINE size: a real 3-node
    Raft notary cluster, every node its OWN OS process (own GIL, TCP
    sockets, sqlite), firehosed by two client processes running the
    width-N multisig FirehoseFlow (reference: LoadTest.kt:39-144's
    remote-nodes shape + NotaryDemo.kt:14-29).

    TWO configs report:
      * raft_notary_3node — raft-SIMPLE, host crypto: the r1-r4 trend line
        (a non-validating notary verifies no signatures itself, so the
        clients' verification dominates).
      * raft_validating_3node — raft-VALIDATING, the reference demo's
        actual service type (samples/raft-notary-demo/.../Main.kt:11
        starts RaftValidatingNotaryService), with
        notary_device="accelerator": the FIRST member (the usual leader)
        owns the real device — the production topology, with the TPU
        inside the measurement. The node boot-warms the kernel behind a
        host-gate (node.py _warm_verifier_maybe) so backend init/compile
        never stalls the run loop; under backlog the leader's verify pump
        accumulates >= device_min_sigs and engages the kernel, light
        rounds route to the host tier — node_stamps + routing counters
        attribute exactly where batches went.
    loadtest_sigs_per_sec counts every pump verification across client
    AND notary processes via RPC metric deltas.

    sidecar=True spawns the host's ONE device-owning verification server
    (crypto/sidecar.py) and points every raft member at it, so verify
    micro-batches coalesce ACROSS processes — the fix for the r05 flagship
    shape where every member's batches sat below device_min_sigs and
    device_batches stayed 0. The "sidecar" field carries the server's
    stats (batch-size histogram, cross-request coalescing, device/host
    batches); device_occupancy aggregates the members' routing either way
    so host-only runs report the same schema."""
    from corda_tpu.tools.loadtest import run_loadtest_multiprocess

    res = run_loadtest_multiprocess(
        n_tx=n_tx, width=width, clients=2, notary=notary,
        verifier=verifier, notary_device=notary_device, max_seconds=420.0,
        sidecar=sidecar, sidecar_devices=sidecar_devices,
        adaptive_coalesce=adaptive_coalesce)
    dev_b = sum((s or {}).get("device_batches") or 0
                for s in res.node_stamps.values())
    host_b = sum((s or {}).get("host_batches") or 0
                 for s in res.node_stamps.values())
    return {"harness": "multiprocess-driver", "n_tx": n_tx, "width": width,
            "notary": notary,
            "tx_per_sec": res.tx_per_sec,
            "loadtest_sigs_per_sec": res.sigs_per_sec,
            "sigs_verified": res.sigs_verified,
            "committed": res.tx_committed,
            "p50_ms": res.p50_ms, "p99_ms": res.p99_ms,
            "verifier": verifier, "notary_device": notary_device,
            "device_warm_wait_s": res.device_warm_wait_s,
            "device_batches": dev_b,
            "host_batches": host_b,
            "device_occupancy": (round(dev_b / (dev_b + host_b), 3)
                                 if (dev_b + host_b) else 0.0),
            "sidecar": res.sidecar,
            "sidecar_devices": sidecar_devices or None,
            "adaptive_coalesce": adaptive_coalesce,
            "node_stamps": res.node_stamps}


def bench_validating_flagship(**kw):
    """The raft_validating_3node flagship, run as a STATIC/ADAPTIVE
    coalesce-window A/B (ROADMAP item 1 leftover: the adaptive controller
    shipped in PR 7 off by default — this arms it in the flagship path and
    stamps the verdict instead of leaving the flag dead). The returned
    dict IS the armed (adaptive) run, so the flagship keys keep their
    grep-able shape; the static counterpart and the verdict ride under
    "adaptive_coalesce_ab"."""
    kw.setdefault("n_tx", 400)
    kw.setdefault("notary", "raft-validating")
    kw.setdefault("sidecar", True)
    before = bench_raft_cluster(adaptive_coalesce=False, **kw)
    after = bench_raft_cluster(adaptive_coalesce=True, **kw)

    def _hoist(run):
        return {k: run.get(k) for k in (
            "tx_per_sec", "p50_ms", "p99_ms", "loadtest_sigs_per_sec")}

    b_tx, a_tx = before.get("tx_per_sec") or 0.0, after.get("tx_per_sec") or 0.0
    b_p99, a_p99 = before.get("p99_ms") or 0.0, after.get("p99_ms") or 0.0
    after["adaptive_coalesce_ab"] = {
        "static": _hoist(before),
        "adaptive": _hoist(after),
        "static_sidecar": before.get("sidecar"),
        "tx_per_sec_ratio": round(a_tx / b_tx, 3) if b_tx else None,
        "p99_ratio": round(a_p99 / b_p99, 3) if b_p99 else None,
        # The arming bar: adaptive must not cost meaningful throughput
        # (>= 95% of static) nor blow the tail (<= 120% of static p99) —
        # the controller's job is to EARN its shorter windows under gaps.
        "adaptive_no_worse": bool(
            b_tx and a_tx >= 0.95 * b_tx
            and (not b_p99 or a_p99 <= 1.2 * b_p99)),
    }
    return after


def bench_resolve_ids(n_tx=2048, outputs_per_tx=8, host_only=False):
    """Resolve-path id recomputation (reference hot spot:
    MerkleTransaction.kt:26-38 driven by ResolveTransactionsFlow): a wave of
    downloaded transactions has every component leaf hashed in bulk via
    SignedTransaction.prime_ids. Measures the SAME work on the host
    (hashlib) and device (sha256_jax) backends; hash_many_auto's crossover
    constant decides which serves production traffic."""
    from corda_tpu.crypto.keys import KeyPair
    from corda_tpu.crypto.party import Party
    from corda_tpu.serialization.codec import deserialize, serialize
    from corda_tpu.testing.dummies import DummyContract, DummySingleOwnerState
    from corda_tpu.transactions.signed import SignedTransaction

    notary = Party.of("N", KeyPair.generate(b"\x61" * 32).public)
    party = Party.of("P", KeyPair.generate(b"\x62" * 32).public)
    key = KeyPair.generate(b"\x62" * 32)
    blobs = []
    n_leaves = 0
    for i in range(n_tx):
        b = DummyContract.generate_initial(
            party.ref(i.to_bytes(4, "big")), i, notary)
        for j in range(outputs_per_tx - 1):
            b.add_output_state(DummySingleOwnerState(
                i * 1000 + j, party.owning_key))
        b.sign_with(key)
        stx = b.to_signed_transaction(check_sufficient_signatures=False)
        n_leaves += len(stx.tx.all_leaves_hashes)
        blobs.append(serialize(stx).bytes)

    out = {"n_tx": n_tx, "leaves": n_leaves}
    backends = ((("host", 1 << 62),) if host_only
                else (("host", 1 << 62), ("device", 0)))
    for label, device_min in backends:
        batch = [deserialize(raw) for raw in blobs]  # cold caches
        t0 = time.perf_counter()
        backend = SignedTransaction.prime_ids(batch, device_min=device_min)
        dt = time.perf_counter() - t0
        assert backend == label, backend
        out[f"{label}_leaves_per_sec"] = round(n_leaves / dt, 1)
        out[f"{label}_tx_per_sec"] = round(n_tx / dt, 1)
    from corda_tpu.ops.sha256_jax import DEVICE_MIN_HASHES_DEFAULT

    out["auto_crossover_hashes"] = DEVICE_MIN_HASHES_DEFAULT
    return out


def bench_open_loop_latency():
    """Open-loop tail latency at stated offered loads (BASELINE metric 2 is
    p99 notarise latency): the firehose paced by rate_tx_s, per-tx latency
    measured from scheduled submission. Two max_wait_ms settings show the
    micro-batch knob's latency/throughput trade."""
    from corda_tpu.tools.loadtest import run_latency_sweep

    out = {}
    # Round-15 ladder: the vectorized ingest plane (columnar build +
    # native batch sign) moved the per-client pacing ceiling from ~150
    # tx/s to the multi-thousand range, so the old (30, 90, 150) rungs
    # all sat under the knee — 720 offered now reaches it.
    for max_wait in (2.0, 20.0):
        sweep = run_latency_sweep(rates=(60.0, 240.0, 720.0), n_tx=250,
                                  max_wait_ms=max_wait)
        out[f"max_wait_{max_wait:g}ms"] = {
            f"{rate:g}_tx_s": {
                "p50_ms": r.p50_ms, "p90_ms": r.p90_ms, "p99_ms": r.p99_ms,
                "tx_per_sec": r.tx_per_sec, "committed": r.committed}
            for rate, r in sweep.items()}
    return out


def bench_raft_open_loop(rates=(60.0, 240.0, 720.0, 1800.0), n_tx=200,
                         verifier="cpu", notary_device="cpu",
                         sidecar=False, clients=3):
    """Open-loop tail latency for the FLAGSHIP config: the 3-member raft
    cluster through real OS processes, firehose paced at stated offered
    loads (round-4 VERDICT item 4 — BASELINE metric 2, p99 notarise
    latency, was only ever measured closed-loop for raft, which reports
    pure queueing delay instead of latency at load). Same width/rates as
    the simple-notary sweep so the two configs compare directly.
    node_stamps attribute each member's verify routing for the sweep —
    device_batches, pipeline depth, overlap ratio (the async-pipeline
    numbers the flagship config is judged on) — plus the commit-pipeline
    stamps, summarised once under "replication" from the leader's view:
    entries_per_batch, replication RTT, reply-coalesce ratio, and the
    transport burst sizes (ARCHITECTURE.md "Commit pipeline").

    The sweep runs with the tracing subsystem armed (corda_tpu/obs/) and
    emits stage_breakdown: p50/p99/mean per notarise stage (queue_wait,
    verify_wait, device_verify, raft_append, fsync, replication, reply)
    across every traced transaction — WHERE the p99 lives, not just what
    it is. stage_sum_over_e2e near 1.0 certifies the stages account for
    the measured end-to-end latency."""
    from corda_tpu.obs import collect as obs_collect
    from corda_tpu.tools.loadtest import run_latency_sweep

    # clients=3 splits each offered rate across three generator processes.
    # Round 15 retired the old ~150 tx/s per-client GIL ceiling: prepare
    # is columnar (build_chunk_columnar + the native batch signer), so a
    # single client builds thousands of tx/s and the drive loop paces far
    # past the old 360 ceiling. The ladder now matches the simple-notary
    # sweep's rungs (60/240/720) plus an 1800 saturation rung — every
    # rung past the cluster's measured committed rate (~40 tx/s at
    # host parity) measures the NOTARY, which is the point; the ingest
    # plane's own capability is measured separately by bench_ingest_sweep.
    sweep = run_latency_sweep(rates=rates, n_tx=n_tx, width=4,
                              clients=clients,
                              notary="raft-validating", coalesce_ms=10.0,
                              verifier=verifier, notary_device=notary_device,
                              trace=True, sidecar=sidecar)
    try:
        breakdown = obs_collect.stage_breakdown(sweep.trace_snapshots)
    except Exception as e:  # a malformed snapshot costs the breakdown only
        breakdown = {"error": f"{type(e).__name__}: {e}"}
    dev_b = sum((s or {}).get("device_batches") or 0
                for s in sweep.node_stamps.values())
    host_b = sum((s or {}).get("host_batches") or 0
                 for s in sweep.node_stamps.values())
    return {"harness": "multiprocess-driver", "width": 4, "n_tx": n_tx,
            "clients": clients,
            "notary": "raft-validating", "verifier": verifier,
            "notary_device": notary_device,
            "coalesce_ms": 10.0,
            "device_batches": dev_b,
            "host_batches": host_b,
            "device_occupancy": (round(dev_b / (dev_b + host_b), 3)
                                 if (dev_b + host_b) else 0.0),
            "sidecar": sweep.sidecar,
            "node_stamps": sweep.node_stamps,
            "replication": _replication_summary(sweep.node_stamps),
            "stage_breakdown": breakdown,
            "rates": {
                f"{rate:g}_tx_s": {
                    "p50_ms": r.p50_ms, "p90_ms": r.p90_ms,
                    "p99_ms": r.p99_ms, "tx_per_sec": r.tx_per_sec,
                    "committed": r.committed}
                for rate, r in sweep.items()}}


def _replication_summary(node_stamps):
    """One commit-pipeline summary from the member that actually drove
    replication: prefer the stamp whose raft role is "leader", fall back
    to the member with the most append frames (a leader change mid-sweep
    leaves two partial leader views; the busier one wrote the batches).
    Returns None when no member carries a raft stamp — the guard test and
    the bench contract both treat that as "replication stamps missing"."""
    best_name, best, best_frames = None, None, -1
    for name, stamp in (node_stamps or {}).items():
        raft = (stamp or {}).get("raft") or {}
        if not raft:
            continue
        frames = raft.get("append_frames") or 0
        lead = raft.get("role") == "leader"
        if best is None or (lead and best.get("role") != "leader") \
                or (lead == (best.get("role") == "leader")
                    and frames > best_frames):
            best_name, best, best_frames = name, raft, frames
    if best is None:
        return None
    transport = (node_stamps.get(best_name) or {}).get("transport") or {}
    return {"member": best_name,
            "role": best.get("role"),
            "group_commit": best.get("group_commit"),
            "group_commits": best.get("group_commits"),
            "entries_per_batch": best.get("entries_per_batch"),
            "append_frames": best.get("append_frames"),
            "append_entries_sent": best.get("append_entries_sent"),
            "replication_rtt_ms_avg": best.get("replication_rtt_ms_avg"),
            "reply_coalesce_ratio": best.get("reply_coalesce_ratio"),
            "outbox_burst_avg": transport.get("outbox_burst_avg"),
            "bridge_flush_avg": transport.get("bridge_flush_avg")}


def bench_slo_sweep(rates=(120.0, 240.0, 480.0), n_tx=240, width=4,
                    clients=2, interactive_frac=0.25, slo_ms=250.0,
                    queue_watermark=48, flagship_tx_s=40.0,
                    notary="simple", verifier="cpu", notary_device="cpu",
                    sidecar=False, flight_dir=None):
    """The QoS plane's SLO section (round 12, ROADMAP open item 4): the
    mixed-lane offered-load sweep run TWICE over the same rates — once
    with the plane armed ([qos] enabled on every node: lane-ordered SMM
    scheduling, deadline early-flush at the three batching points, bulk
    watermark shedding at the notarise entry) and once with qos=false,
    which is bit-identical to the pre-QoS tree. At each offered load every
    client process drives an interactive firehose (interactive_frac of the
    rate, deadline = slo_ms per tx) and a bulk firehose (the remainder)
    CONCURRENTLY, so the lanes contend at the notary.

    The verdict is the explicit SLO line: at the top offered rate —
    chosen ≥ 5× the flagship cluster's measured committed rate
    (~40 tx/s host-parity, see raft_validating_3node), i.e. well past
    saturation — armed interactive p99 must stay within slo_ms while bulk
    absorbs the overload as admission sheds; the no-QoS baseline shows
    both lanes collapsing together. slo_ms defaults to 250 ms: the
    1-core driver host's simple-notary p99 at mid load is ~50 ms, so
    250 ms is "flat through saturation", not "fast" — the claim under
    test is the SHAPE (flat vs collapsing), the bound makes it
    falsifiable on this hardware."""
    from corda_tpu.tools.loadtest import run_slo_sweep

    def _lane_stats(sweep):
        return {f"{rate:g}_tx_s": {
                    lane: {"p50_ms": r.p50_ms, "p90_ms": r.p90_ms,
                           "p99_ms": r.p99_ms, "tx_per_sec": r.tx_per_sec,
                           "requested": r.requested,
                           "committed": r.committed, "shed": r.shed}
                    for lane, r in by_lane.items()}
                for rate, by_lane in sweep.items()}

    out = {"harness": "multiprocess-driver", "notary": notary,
           "width": width, "n_tx": n_tx, "clients": clients,
           "interactive_frac": interactive_frac, "slo_ms": slo_ms,
           "queue_watermark": queue_watermark,
           "verifier": verifier, "notary_device": notary_device,
           "rates_tx_s": list(rates)}
    # Flight recorder (obs/telemetry.py): the armed sweep runs with the
    # driver-side recorder on — if any rung breaches the interactive SLO
    # the breaching window dumps exactly one artifact here, and the
    # report says where. (The baseline sweep runs unarmed: it EXISTS to
    # collapse, dumping its expected breach would be noise.)
    import tempfile as _tempfile

    if flight_dir is None:
        flight_dir = _tempfile.mkdtemp(prefix="corda-tpu-flight-")
    armed = run_slo_sweep(
        rates=rates, n_tx=n_tx, width=width, clients=clients,
        interactive_frac=interactive_frac, slo_ms=slo_ms,
        queue_watermark=queue_watermark, notary=notary, verifier=verifier,
        notary_device=notary_device, sidecar=sidecar, qos=True,
        flight_dir=flight_dir)
    out["qos"] = _lane_stats(armed)
    out["member_qos"] = armed.qos
    out["sidecar"] = armed.sidecar
    out["flight"] = {"dir": flight_dir,
                     "artifacts": getattr(armed, "flight", None) or []}
    # Cluster telemetry fold (obs/export.collect_cluster): the merged
    # per-phase counters across members — round_breakdown at sweep scope.
    out["cluster_telemetry"] = (getattr(armed, "telemetry", None)
                                or {}).get("merged")
    baseline = run_slo_sweep(
        rates=rates, n_tx=n_tx, width=width, clients=clients,
        interactive_frac=interactive_frac, slo_ms=slo_ms,
        queue_watermark=queue_watermark, notary=notary, verifier=verifier,
        notary_device=notary_device, sidecar=sidecar, qos=False)
    out["no_qos_baseline"] = _lane_stats(baseline)
    top = max(rates)
    a_int, a_bulk = armed[top]["interactive"], armed[top]["bulk"]
    b_int = baseline[top]["interactive"]
    within = a_int.p99_ms <= slo_ms
    shed = a_bulk.shed > 0
    out["verdict"] = {
        "offered_top_tx_s": top,
        "flagship_committed_tx_s": flagship_tx_s,
        "offered_over_flagship": round(top / flagship_tx_s, 1),
        "interactive_p99_ms": a_int.p99_ms,
        "interactive_p99_within_slo": within,
        "bulk_shed": a_bulk.shed,
        "bulk_shed_nonzero": shed,
        "baseline_interactive_p99_ms": b_int.p99_ms,
        "interactive_vs_baseline": (round(b_int.p99_ms / a_int.p99_ms, 2)
                                    if a_int.p99_ms else None),
        "slo_met": bool(within and shed),
    }
    # Measured-saturation admission: derive the per-lane rates the static
    # TOML used to guess from THIS armed sweep (qos/calibrate.py). Stamped
    # beside the sweep so the knobs always travel with the observations
    # that produced them; apply_calibration pushes them into a live
    # controller. Round 15 raised the default ladder (vectorized ingest
    # paces it now), so the calibration provenance is re-derived from the
    # new, deeper-saturation rungs on every run.
    try:
        from corda_tpu.qos import calibrate_admission

        out["calibration"] = calibrate_admission(
            {rate: by_lane for rate, by_lane in armed.items()},
            slo_ms=slo_ms)
    except Exception as e:
        out["calibration"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def bench_telemetry(n_tx=80):
    """The always-on telemetry plane's own section (round 16): run the
    in-process loadtest against a FRESH registry and report what the
    plane measured about it — the round profiler's phase breakdown (the
    block that decomposes the ingest sweep's ``first_bottleneck =
    "rounds"`` verdict into poll/verify_wait/seal/replicate/apply/reply
    shares), plus a self-check that the Prometheus exposition the node
    and sidecar endpoints serve round-trips through the parser with
    every registered metric present. Host-only safe by construction:
    nothing here touches a device — which is exactly the claim
    ("always-on" must mean on THIS path too)."""
    from corda_tpu.obs import telemetry as _tm
    from corda_tpu.obs.export import parse_prometheus, render_prometheus
    from corda_tpu.tools.loadtest import run_loadtest

    reg = _tm.ACTIVE if _tm.ACTIVE is not None else _tm.arm()
    reg.reset()
    res = run_loadtest(n_tx=n_tx, notary="simple")
    c = reg.snapshot()["counters"]
    rounds = int(c["rounds_total"])
    wall = c["round_wall_seconds_total"]
    rp = {p: c[f"round_phase_{p}_seconds_total"] for p in _tm.ROUND_PHASES}
    breakdown = _tm.format_breakdown(rp | {"wall": wall, "rounds": rounds})
    coverage = (breakdown or {}).get("coverage")
    text = render_prometheus(reg)
    parsed = parse_prometheus(text)
    return {
        "harness": "in-process",
        "n_tx": n_tx,
        "committed": res.tx_committed,
        "tx_per_sec": res.tx_per_sec,
        # The acceptance bound: named sub-phases must attribute >= 90%
        # of measured round wall time (measured here across BOTH
        # in-process nodes — client and notary share the registry).
        "round_breakdown": breakdown,
        "breakdown_ok": bool(coverage is not None and coverage >= 0.9),
        # /metrics validity: every registered series present and parseable.
        "prometheus_bytes": len(text),
        "prometheus_ok": bool(
            set(parsed["counters"]) == set(_tm.COUNTER_NAMES)
            and set(parsed["histograms"]) == set(_tm.HISTOGRAM_NAMES)),
        "flows_started": int(c["flows_started_total"]),
        "flows_completed": int(c["flows_completed_total"]),
        "verify_batches": int(c["verify_batches_total"]),
        "verify_sigs": int(c["verify_sigs_total"]),
    }


def bench_doctor(report):
    """The performance doctor's section (round 17): diagnose THIS report
    and stamp the verdict into it — the roofline (committed/e2e rates vs
    the measured kernel-stream ceiling, gap factored per layer) and the
    evidence-ranked ``bottlenecks`` list with a suggested next experiment
    per entry (obs/doctor). Then feed the trajectory store: normalize the
    report into one schema-versioned record, compare it against the last
    record of its kind (delta + regression gate under the default
    tolerance policy), and append it to ``artifacts/TRAJECTORY.jsonl``
    (``CORDA_TPU_TRAJECTORY`` overrides the path; append is best-effort —
    a read-only checkout costs the append, never the verdict).

    Runs LAST on both phase paths on purpose: the verdict must see every
    section the run managed to produce, including the host-only path's
    ``cpu_oracle_sigs_per_sec`` ceiling fallback."""
    import os as _os

    from corda_tpu.obs import doctor as _doctor
    from corda_tpu.obs import telemetry as _tm

    _tm.inc("doctor_runs_total")
    verdict = _doctor.diagnose(_doctor.extract_signals(report))
    record = _doctor.normalize_record(report, source="bench_run")
    path = _os.environ.get("CORDA_TPU_TRAJECTORY") or _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)),
        "artifacts", "TRAJECTORY.jsonl")
    out = {"verdict": verdict, "record": record,
           "trajectory": {"path": path}}
    try:
        prior = _doctor.load_trajectory(path)
        out["trajectory"]["delta"] = _doctor.trajectory_delta(prior, record)
        gate = _doctor.gate(prior + [record])
        out["trajectory"]["gate"] = gate
        if not gate["ok"]:
            _tm.inc("doctor_gate_regressions_total",
                    len(gate["regressions"]))
        _doctor.append_trajectory(path, record)
        out["trajectory"]["appended"] = True
    except (OSError, ValueError) as e:
        out["trajectory"]["error"] = f"{type(e).__name__}: {e}"
        out["trajectory"]["appended"] = False
    return out


def bench_autotune(rate_tx_s=2400.0, n_tx=400, workers=2, budget=3,
                   seed=7):
    """The autotune plane's section (round 21): the closed loop finding
    a config that beats the hand-tuned default. One baseline ingest run
    at defaults produces a REAL doctor verdict (stamp_attribution over
    the member stamps); the controller maps its top bottleneck's
    structured experiment spec to a sweep, evaluates ``budget`` gated
    candidates through the same multiprocess harness (config knobs ride
    CORDA_TPU_CONFIG_OVERLAY to every spawned node), and commits the
    winner as a TOML overlay. The headline is best_value vs
    baseline_value on the swept metric — ">= the hand-tuned default" by
    construction, because a loop that finds nothing better commits
    nothing and the incumbent stands.

    The search is replayable: the stamped seed + decision_sequence
    replay the identical decisions against the same measurements. The
    run's ``autotune`` provenance record (verdict consumed, every
    candidate's values/metrics/gate outcome) appends to the trajectory
    store exactly like bench_doctor's (CORDA_TPU_TRAJECTORY overrides
    the path; append is best-effort — a read-only checkout costs the
    append, never the section)."""
    import os as _os

    from corda_tpu.autotune import controller as _ctl
    from corda_tpu.obs import doctor as _doctor
    from corda_tpu.tools.loadtest import run_ingest_sweep

    sweep = run_ingest_sweep(rates=(rate_tx_s,), n_tx=n_tx, width=1,
                             workers=workers, max_seconds=240.0)
    rows = [r for r in sweep.results.values()
            if isinstance(r, dict) and "error" not in r]
    if not rows:
        return {"error": "baseline ingest run failed every rate",
                "rates": {f"{k:g}_tx_s": v
                          for k, v in sweep.results.items()}}
    peak = max(rows, key=lambda r: r.get("achieved_tx_s") or 0.0)
    baseline_metrics = {
        "peak_achieved_tx_s": peak.get("achieved_tx_s"),
        "p99_ms": peak.get("p99_ms"),
        "exactly_once_all": all(bool(r.get("exactly_once"))
                                for r in rows),
    }
    verdict = sweep.doctor or {}
    try:
        spec = _ctl.spec_from_verdict(verdict)
    except ValueError:
        # The short baseline abstained (or implicated an un-sweepable
        # experiment): sweep the default exploratory knobs instead of
        # producing no section.
        spec = _ctl.exploratory_spec()
    runner = _ctl.make_ingest_runner(rates=(rate_tx_s,), n_tx=n_tx,
                                     workers=workers, max_seconds=240.0)
    result = _ctl.run_autotune(
        spec, runner, budget=budget, seed=seed,
        baseline_metrics=baseline_metrics,
        verdict_consumed={
            "source": "bench_autotune_baseline",
            "first_bottleneck": verdict.get("first_bottleneck"),
            "experiment_id": spec.experiment_id,
        })
    section = {
        "harness": "multiprocess-driver",
        "rate_tx_s": rate_tx_s, "n_tx": n_tx, "workers": workers,
        "seed": seed, "budget": budget,
        "experiment_id": result["experiment_id"],
        "cause": result["cause"],
        "knobs": result["knobs"],
        "metric": result["metric"],
        "first_bottleneck": verdict.get("first_bottleneck"),
        "baseline_value": result["baseline_value"],
        "best_value": result["best_value"],
        "improved": result["improved"],
        "improvement_pct": result["improvement_pct"],
        "candidates_evaluated": result["candidates_evaluated"],
        "gate_rejections": result["gate_rejections"],
        "decision_sequence": result["decision_sequence"],
        "committed_values": (result["overlay"] or {}).get("values"),
        "committed_overlay": (result["overlay"] or {}).get("toml"),
        "candidates": result["candidates"],
        "doctor": verdict,
    }
    record = _doctor.normalize_record(result, source="bench_autotune")
    path = _os.environ.get("CORDA_TPU_TRAJECTORY") or _os.path.join(
        _os.path.dirname(_os.path.abspath(__file__)),
        "artifacts", "TRAJECTORY.jsonl")
    section["trajectory"] = {"path": path}
    try:
        _doctor.append_trajectory(path, record)
        section["trajectory"]["appended"] = True
    except (OSError, ValueError) as e:
        section["trajectory"]["error"] = f"{type(e).__name__}: {e}"
        section["trajectory"]["appended"] = False
    return section


def bench_vault_scaling(sizes=(10_000, 100_000, 1_000_000), queries=48,
                        selections=48, boot_batch=2048, parity_n=300):
    """The indexed vault plane's scale proof (round 22): coin selection,
    pushdown queries and balances against stores of 10k/100k/1M
    unconsumed states, all host-path in-process (the claim is index
    behaviour, not crypto).

    Per size the section seeds a fresh sqlite vault (vault.seed_states —
    the bank-day bulk path), then measures keyset-paginated VaultQuery
    pages, soft-locked select_coins walks (reservations released after
    each round so the store is identical for every sample) and the O(1)
    balances aggregate. The headline
    ``vault_coin_selection_p99_ratio`` is the largest store's selection
    p99 over the smallest's — sublinear_ok pins it within 10x across a
    100x size spread, the difference between an index walk and the scan
    the in-memory engine would do.

    A boot leg replays the same ledger twice: a fresh in-memory engine
    streaming every transaction (what legacy boot does) vs a restarted
    indexed engine whose persisted watermark says the store is current —
    ``vault_boot_speedup`` is full-replay over incremental, the round-22
    restart claim.

    A parity leg drives one issue+spend stream through both engines and
    pins identical unconsumed refs, blobs and balances
    (``vault_parity_ok`` — perfdoctor gates it as a hard flag)."""
    import os
    import tempfile

    from corda_tpu.contracts.structures import (
        Issued,
        StateAndRef,
        StateRef,
        TransactionState,
    )
    from corda_tpu.crypto.hashes import SecureHash
    from corda_tpu.crypto.party import PartyAndReference
    from corda_tpu.finance.amount import Amount
    from corda_tpu.finance.cash import CashState
    from corda_tpu.node.services.inmemory import NodeVaultService
    from corda_tpu.node.services.persistence import NodeDatabase
    from corda_tpu.node.services.vault import (
        IndexedVaultService,
        VaultQuery,
        seed_states,
    )
    from corda_tpu.serialization.codec import serialize
    from corda_tpu.testing.identities import ALICE, DUMMY_NOTARY, MEGA_CORP
    from corda_tpu.utils.bytes import OpaqueBytes

    token = Issued(PartyAndReference(MEGA_CORP, OpaqueBytes(b"\x01")),
                   "USD")
    notary = DUMMY_NOTARY

    def our_keys():
        return set(ALICE.owning_key.keys)

    def tx_hash(i: int) -> SecureHash:
        # Unique 32 bytes without a sha256 per row (million-row seeds).
        return SecureHash(i.to_bytes(16, "big") + b"vault-bench-pad!")

    def state_at(i: int) -> TransactionState:
        # LCG amounts: deterministic spread so the amount index is real.
        qty = 1 + (i * 6364136223846793005 + 1442695040888963407) % 9973
        return TransactionState(CashState(Amount(int(qty), token),
                                          ALICE.owning_key), notary)

    def p99_ms(lat: list) -> float:
        lat = sorted(lat)
        return round(1e3 * lat[min(len(lat) - 1, int(len(lat) * 0.99))], 4)

    class _SeedTx:
        """Signed-tx shim: .tx/.id/inputs/outputs/out_ref — everything
        notify_all touches, none of the Merkle cost."""

        __slots__ = ("id", "inputs", "outputs")

        def __init__(self, id, outputs, inputs=()):
            self.id = id
            self.outputs = tuple(outputs)
            self.inputs = tuple(inputs)

        @property
        def tx(self):
            return self

        def out_ref(self, i):
            return StateAndRef(self.outputs[i], StateRef(self.id, i))

    class _SeedStorage:
        """stream_since twin over an in-memory tx list whose position
        mirrors the transactions-table rowid (rows inserted in order)."""

        def __init__(self, txs):
            self._txs = list(txs)

        def stream_since(self, after_rowid=0, batch=512):
            start = int(after_rowid)
            for i, stx in enumerate(self._txs[start:], start=start + 1):
                yield i, stx

    per_size = {}
    select_p99 = {}
    query_p99 = {}
    for n in sizes:
        with tempfile.TemporaryDirectory() as tmp:
            db = NodeDatabase(os.path.join(tmp, "vault.db"))
            vault = IndexedVaultService(db, our_keys)
            t0 = time.perf_counter()
            seed_states(vault, (
                StateAndRef(state_at(i), StateRef(tx_hash(i), 0))
                for i in range(n)))
            seed_s = time.perf_counter() - t0
            q_lat, cursor = [], None
            for _ in range(queries):
                t = time.perf_counter()
                page = vault.query(VaultQuery(currency="USD",
                                              after=cursor, page_size=256))
                q_lat.append(time.perf_counter() - t)
                cursor = page.next_cursor
            s_lat = []
            for _ in range(selections):
                t = time.perf_counter()
                coins = vault.select_coins("USD", 25_000, holder=b"bench")
                s_lat.append(time.perf_counter() - t)
                vault.release_coins([c.ref for c in coins],
                                    holder=b"bench")
            t = time.perf_counter()
            balances = vault.balances()
            balance_ms = round(1e3 * (time.perf_counter() - t), 4)
            db.close()
        select_p99[n] = p99_ms(s_lat)
        query_p99[n] = p99_ms(q_lat)
        per_size[f"{n}_states"] = {
            "states": n, "seed_s": round(seed_s, 2),
            "query_p99_ms": query_p99[n],
            "select_p99_ms": select_p99[n],
            "balance_ms": balance_ms,
            "balance_usd": balances.get("USD"),
        }

    lo, hi = min(sizes), max(sizes)
    ratio = round(select_p99[hi] / max(select_p99[lo], 1e-4), 2)

    # Boot leg: full replay vs watermark-incremental on the middle store.
    boot_n = sorted(sizes)[1] if len(sizes) > 1 else sizes[0]
    txs = [_SeedTx(tx_hash(i), (state_at(i),)) for i in range(boot_n)]
    storage = _SeedStorage(txs)
    with tempfile.TemporaryDirectory() as tmp:
        db = NodeDatabase(os.path.join(tmp, "boot.db"))
        with db.lock:
            db.conn.executemany(
                "INSERT INTO transactions (tx_id, blob) VALUES (?, ?)",
                ((stx.id.bytes, b"") for stx in txs))
            db.commit()
        vault = IndexedVaultService(db, our_keys)
        vault.rebuild_from(storage, batch=boot_batch)  # initial build
        t0 = time.perf_counter()
        legacy = NodeVaultService(our_keys)
        chunk = []
        for _rowid, stx in storage.stream_since(0, batch=boot_batch):
            chunk.append(stx)
            if len(chunk) >= boot_batch:
                legacy.notify_all(chunk)
                chunk = []
        if chunk:
            legacy.notify_all(chunk)
        full_replay_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        reopened = IndexedVaultService(db, our_keys)  # "restart"
        replayed = reopened.rebuild_from(storage, batch=boot_batch)
        incremental_s = time.perf_counter() - t0
        watermark = reopened.watermark
        db.close()
    boot_speedup = round(full_replay_s / max(incremental_s, 1e-6), 1)

    # Parity leg: one issue+spend stream, both engines, identical sets.
    par_txs = [_SeedTx(tx_hash(i), (state_at(i),)) for i in range(parity_n)]
    spends = [
        _SeedTx(tx_hash(parity_n + k), (state_at(parity_n + k),),
                inputs=(StateRef(tx_hash(i), 0),))
        for k, i in enumerate(range(0, parity_n, 3))]
    mem = NodeVaultService(our_keys)
    with tempfile.TemporaryDirectory() as tmp:
        db = NodeDatabase(os.path.join(tmp, "parity.db"))
        idx = IndexedVaultService(db, our_keys)
        for engine in (mem, idx):
            engine.notify_all(par_txs)
            engine.notify_all(spends)

        def snapshot(engine):
            return sorted(
                ((s.ref.txhash.bytes, s.ref.index,
                  serialize(s.state).bytes)
                 for s in engine.iter_unconsumed()))

        parity_ok = (snapshot(mem) == snapshot(idx)
                     and mem.balances() == idx.balances())
        db.close()

    return {
        "harness": "in-process",
        "sizes": list(sizes),
        "per_size": per_size,
        "vault_query_p99_ms": query_p99[hi],
        "vault_coin_selection_p99_ratio": ratio,
        "sublinear_ok": ratio <= 10.0,
        "boot": {
            "states": boot_n,
            "full_replay_s": round(full_replay_s, 3),
            "incremental_s": round(incremental_s, 4),
            "replayed_on_reopen": replayed,
            "watermark": watermark,
        },
        "vault_boot_speedup": boot_speedup,
        "vault_parity_ok": bool(parity_ok),
    }


def bench_ingest_sweep(rates=(1200.0, 3600.0, 10000.0), n_tx=2000,
                       width=1, workers=3, chaos_rate=1200.0,
                       chaos_n_tx=600, pipeline_rate=2400.0,
                       pipeline_n_tx=600):
    """The vectorized ingest plane's capability section (round 15, ROADMAP
    item 2): ONE builder process columnar-builds + batch-signs + serializes
    the whole corpus (loadgen.IngestBuildFlow -> a CTI1 multi-tx frame),
    then `workers` replay processes drive disjoint slices open-loop at the
    stated offered rates — no per-tx Python rebuild anywhere in the driven
    path, so the offered ladder reaches 10k where the PR 9 generator
    ceiling was ~360 tx/s.

    Per rate the row reports offered vs achieved tx/s, latency
    percentiles, frames-per-tx (the send_many amortization, from worker
    transport deltas), the builder's ingest attribution block
    (tx_built_per_s / sigs_signed_per_s / serialize_ms / client cpu_s) and
    the exactly-once audit. first_bottleneck is the top of the perf
    doctor's evidence-ranked attribution over the member stamps
    (obs/doctor.stamp_attribution; the full ranked list rides under
    "doctor") — at offered rates the client plane can now pace, the
    residual ceiling is SERVER-side and this says where.

    A separate chaos leg re-runs one mid-ladder rate under the lossy plan
    (transport.send drop p=0.05, armed in members + workers): the durable
    outbox's fallback re-poll redelivers, so the audit must stay
    exactly-once — loss costs latency, never transactions.

    A pipeline-delta leg (round 18) runs the SAME raft workload twice —
    serial reference ([raft] pipeline=false) vs pipelined commit plane —
    and stamps committed-tx/s for both plus their ratio as
    pipeline_speedup, which perfdoctor --gate bands (higher-is-better):
    a regression that silently flattens the overlap win fails CI even
    when the simple-notary ladder above still looks healthy."""
    from corda_tpu.obs import doctor as _doctor
    from corda_tpu.tools.loadtest import run_ingest_sweep

    def _rows(sweep):
        return {f"{rate:g}_tx_s": r for rate, r in sweep.items()}

    sweep = run_ingest_sweep(rates=rates, n_tx=n_tx, width=width,
                             workers=workers)
    # Sweeps stamp their own doctor attribution; a monkeypatched/legacy
    # SweepResult without one gets attributed here from its stamps.
    attribution = (getattr(sweep, "doctor", None)
                   or _doctor.stamp_attribution(sweep.node_stamps))
    ok = [r for r in sweep.results.values() if "error" not in r]
    out = {"harness": "multiprocess-driver", "notary": "simple",
           "n_tx": n_tx, "width": width, "workers": workers,
           # The offered ladder in sweep order: the report contract checks
           # this trend is monotonic (the sweep is a ladder, not a bag).
           "offered_rates_tx_s": list(rates),
           "rates": _rows(sweep),
           "peak_offered_tx_s": max(
               (r["offered_tx_s"] for r in ok), default=None),
           "peak_achieved_tx_s": max(
               (r["achieved_tx_s"] for r in ok), default=None),
           "exactly_once_all": (bool(ok) and len(ok) == len(sweep.results)
                                and all(r["exactly_once"] for r in ok)),
           "first_bottleneck": attribution.get("first_bottleneck"),
           "doctor": attribution,
           "node_stamps": sweep.node_stamps}
    try:
        chaos = run_ingest_sweep(rates=(chaos_rate,), n_tx=chaos_n_tx,
                                 width=width, workers=workers,
                                 chaos="lossy")
        crow = chaos.results.get(chaos_rate) or {}
        out["chaos"] = {"plan": "lossy", "rate_tx_s": chaos_rate,
                        "n_tx": chaos_n_tx,
                        "exactly_once": crow.get("exactly_once", False),
                        "row": _rows(chaos)}
    except Exception as e:
        out["chaos"] = {"error": f"{type(e).__name__}: {e}"}
    try:
        legs = {}
        for label, piped in (("serial", False), ("pipelined", True)):
            leg = run_ingest_sweep(
                rates=(pipeline_rate,), n_tx=pipeline_n_tx, width=width,
                workers=workers, notary="raft", pipeline=piped)
            legs[label] = leg.results.get(pipeline_rate) or {}
        s = legs["serial"].get("achieved_tx_s")
        p = legs["pipelined"].get("achieved_tx_s")
        out["pipeline_delta"] = {
            "notary": "raft", "rate_tx_s": pipeline_rate,
            "n_tx": pipeline_n_tx,
            "committed_tx_s_serial": s,
            "committed_tx_s_pipelined": p,
            "pipeline_speedup": (round(p / s, 3) if s and p else None),
            "exactly_once_both": bool(
                legs["serial"].get("exactly_once")
                and legs["pipelined"].get("exactly_once"))}
    except Exception as e:
        out["pipeline_delta"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def bench_shard_scaling(shard_counts=(1, 2, 4), n_tx=240, width=4,
                        verifier="cpu", notary_device="cpu"):
    """Sharded-notary scaling (round 9): committed tx/s and tail latency
    vs the number of StateRef-partitioned raft groups, real OS-process
    nodes throughout (node/services/sharding.py). Two sections:

    * shards — the single-shard-dominant mix (cross_frac=0, every move
      routes straight to its owning group's leader: the fast path whose
      semantics match the unsharded notary). One-member groups keep the
      per-group replication cost constant so the trend isolates the
      partitioning win; the acceptance bar is tx/s monotonically
      non-decreasing 1 -> 2 -> 4.
    * cross_shard_mix — the adversarial mix: half the moves consume
      inputs owned by TWO different groups, forcing the reserve/commit
      two-phase path under contention. The headline here is not
      throughput but the ledger audit: committed_states rows across all
      groups must equal committed + cross_committed (each two-input move
      spends one extra ref) with zero reservation rows leaked —
      exactly_once=True or the section fails its contract."""
    from corda_tpu.tools.loadtest import run_loadtest_multiprocess

    out = {"harness": "multiprocess-driver", "width": width, "n_tx": n_tx,
           "cluster_size_per_group": 1,
           "mix": "single-shard-dominant (cross_frac=0)", "shards": {}}
    for count in shard_counts:
        r = run_loadtest_multiprocess(
            n_tx=n_tx, width=width, clients=2, notary="raft",
            cluster_size=1, verifier=verifier, notary_device=notary_device,
            inflight=32, shards=count)
        out["shards"][str(count)] = {
            "tx_per_sec": r.tx_per_sec, "p50_ms": r.p50_ms,
            "p99_ms": r.p99_ms, "committed": r.tx_committed,
            "rejected": r.tx_rejected,
            "per_group_committed": r.per_group_committed,
            "exactly_once": r.exactly_once}
    r = run_loadtest_multiprocess(
        n_tx=120, width=width, clients=2, notary="raft", cluster_size=1,
        verifier=verifier, notary_device=notary_device, inflight=16,
        shards=2, cross_frac=0.5)
    out["cross_shard_mix"] = {
        "shards": 2, "cross_frac": 0.5,
        "cross_requested": r.cross_requested,
        "cross_committed": r.cross_committed,
        "tx_per_sec": r.tx_per_sec, "p99_ms": r.p99_ms,
        "committed": r.tx_committed, "rejected": r.tx_rejected,
        "ledger_committed": r.ledger_committed,
        "ledger_expected": r.ledger_expected,
        "reserved_leaked": r.reserved_leaked,
        "exactly_once": r.exactly_once}
    return out


def _mesh_sidecar_round(devices, n_sigs=4096, rounds=5,
                        notary_device="cpu", warm_timeout_s=240.0):
    """ONE multichip_scaling config: spawn a sidecar owning a
    `devices`-wide mesh (the real accelerator slice when
    notary_device="accelerator"; a VIRTUAL host mesh via
    --xla_force_host_platform_device_count otherwise), firehose it with
    tiled make_corpus batches through the real wire client
    (node/verify_client.py), parity-check EVERY verdict against the
    corpus truth, and report aggregate sigs/s + per-round latency plus
    the server's own pad/occupancy attribution.

    Warm-up is untimed on purpose: the first dispatch at a bucket pays
    the sharded executable's compile (amortised by the persistent cache
    across runs but not across mesh widths), and the timed rounds must
    measure the steady-state mesh, not a compile. On the chip, a sidecar
    whose warm-up fails exits and this round raises, as does any client
    fallback to the host tier; on a virtual CPU mesh an unbuildable mesh
    keeps the server's gate closed and the section says so via
    warm_error/mesh_devices."""
    import tempfile
    from pathlib import Path

    from corda_tpu.crypto.provider import VerifyJob
    from corda_tpu.node.verify_client import (SidecarVerifier,
                                              fetch_sidecar_stats)
    from corda_tpu.testing.driver import driver

    pks, msgs, sigs, valid = make_corpus()
    jobs = [VerifyJob(pk, m, s) for pk, m, s in
            zip(tile(pks, n_sigs), tile(msgs, n_sigs), tile(sigs, n_sigs))]
    expected = np.asarray(tile(valid, n_sigs), bool)
    with tempfile.TemporaryDirectory(prefix="bench-mesh-") as td:
        with driver(Path(td)) as d:
            side = d.start_sidecar(
                name=f"mesh{devices}", verifier="jax",
                device=("accelerator" if notary_device == "accelerator"
                        else "cpu"),
                coalesce_us=200, max_sigs=max(n_sigs, 4096),
                devices=devices)
            client = SidecarVerifier(
                side.address, deadline_ms=warm_timeout_s * 1e3,
                device_min_sigs=0, devices=devices)
            # Wait out the boot-warm gate (mesh build happens in the
            # server's warm thread). On the chip a failed warm ends the
            # sidecar and this wait raises; on a virtual CPU mesh an
            # unbuildable mesh records warm_error and keeps the gate shut.
            snap = {}
            if notary_device == "accelerator":
                from corda_tpu.tools.loadtest import _await_device_warm

                _await_device_warm(side, None)
            else:
                deadline = time.monotonic() + warm_timeout_s
                while time.monotonic() < deadline:
                    try:
                        snap = fetch_sidecar_stats(side.address)
                    except Exception:
                        snap = {}
                    if snap.get("device_ready") or snap.get("warm_error"):
                        break
                    time.sleep(0.25)
            # Untimed warm dispatch: pays the per-bucket mesh compile.
            warm_ok = client.verify_batch(jobs)
            parity_ok = bool(np.array_equal(np.asarray(warm_ok, bool),
                                            expected))
            times = []
            t_all = time.perf_counter()
            for _ in range(rounds):
                t0 = time.perf_counter()
                ok = client.verify_batch(jobs)
                times.append(time.perf_counter() - t0)
                parity_ok = parity_ok and bool(
                    np.array_equal(np.asarray(ok, bool), expected))
            wall = time.perf_counter() - t_all
            if notary_device == "accelerator" and client.fallbacks:
                raise RuntimeError(
                    f"mesh{devices} sidecar answered from the host tier "
                    f"({client.fallbacks} client fallbacks)")
            try:
                snap = fetch_sidecar_stats(side.address)
            except Exception:
                pass
            times.sort()
            return {
                "devices": devices, "n_sigs": n_sigs, "rounds": rounds,
                "sigs_per_sec": round(rounds * n_sigs / wall, 1),
                "p50_ms": round(times[len(times) // 2] * 1e3, 2),
                "p99_ms": round(times[min(len(times) - 1,
                                          int(len(times) * 0.99))] * 1e3, 2),
                "parity_ok": parity_ok,
                "client_fallbacks": client.fallbacks,
                "mesh_devices": snap.get("mesh_devices"),
                "warm_error": snap.get("warm_error"),
                "verifier": snap.get("verifier"),
                "device_batches": snap.get("device_batches"),
                "host_batches": snap.get("host_batches"),
                "packed_batches": snap.get("packed_batches"),
                "pack_s_total": snap.get("pack_s_total"),
                "pad_fraction": snap.get("pad_fraction"),
                "per_device_occupancy": snap.get("per_device_occupancy"),
                "per_device_batch_sigs_hist":
                    snap.get("per_device_batch_sigs_hist"),
            }


def bench_multichip_scaling(device_counts=(1, 2, 4, 8), n_sigs=4096,
                            rounds=5, notary_device="cpu", flagship=False):
    """Data-parallel verify-plane scaling (round 10): aggregate sigs/s and
    tail latency vs the mesh width the sidecar owns, 1 -> 2 -> 4 -> 8
    devices, every verdict parity-checked against the corpus truth. Two
    harness shapes share the schema:

    * notary_device="accelerator" — the real multi-chip slice: near-linear
      scaling 1 -> 8 is the acceptance bar (>= 6x aggregate at 8), and
      flagship=True adds the production topology (raft-validating cluster,
      every member feeding ONE mesh-owning sidecar).
    * notary_device="cpu" (host-only bench) — a VIRTUAL host mesh
      (xla_force_host_platform_device_count): sigs/s is NOT expected to
      scale (the "devices" share one CPU) but the parity + pad/occupancy
      contract is exercised end to end, so the section proves the mesh
      code path works on any harness.

    sigs_per_sec_by_devices is hoisted flat for the monotonicity guard in
    tests/test_bench_report.py (mirrors shard_scaling's contract)."""
    mesh_kind = ("device" if notary_device == "accelerator"
                 else "virtual-cpu")
    out = {"harness": "multiprocess-driver", "mesh": mesh_kind,
           "n_sigs": n_sigs, "rounds": rounds, "devices": {}}
    # On the chip a failed width is a device failure and ends the run
    # (main() exits 1); only the virtual CPU mesh records it and goes on.
    on_chip = notary_device == "accelerator"
    trend = {}
    for count in device_counts:
        try:
            r = _mesh_sidecar_round(count, n_sigs=n_sigs, rounds=rounds,
                                    notary_device=notary_device)
        except Exception as e:
            if on_chip:
                raise
            out["devices"][str(count)] = {
                "error": f"{type(e).__name__}: {e}"}
            continue
        out["devices"][str(count)] = r
        if "sigs_per_sec" in r:
            trend[str(count)] = r["sigs_per_sec"]
    out["sigs_per_sec_by_devices"] = trend
    lo, hi = str(min(device_counts)), str(max(device_counts))
    if lo in trend and hi in trend and trend[lo]:
        out["scaling_1_to_max"] = round(trend[hi] / trend[lo], 2)
    if flagship:
        out["flagship_mesh_sidecar"] = bench_raft_cluster(
            n_tx=400, notary="raft-validating", verifier="jax",
            notary_device=notary_device, sidecar=True,
            sidecar_devices=max(device_counts))
    return out


def _federation_round(hosts, n_sigs=16, seconds=3.0, workers=None,
                      coalesce_us=120000, kill_after_s=None):
    """ONE multihost_scaling config: spawn `hosts` sidecar servers as
    simulated hosts (Driver.start_federation), route tiled make_corpus
    batches through the real FederatedVerifier from `workers` concurrent
    feeder threads, parity-check EVERY verdict against the corpus truth,
    and report aggregate sigs/s + per-batch latency plus the router's own
    routing-share/hedge/degrade attribution.

    The scaling mechanism is LATENCY HIDING, not CPU parallelism: each
    host channel serialises one framed round trip, and a single host's
    throughput is bounded by its coalesce window (cycle ~ window +
    verify); K channels overlap K windows, so aggregate sigs/s grows
    ~K-fold until the one real CPU saturates. The sidecars verify on the
    native host tier (verifier="cpu" — GIL-released libcrypto), which is
    what keeps K windows' worth of verify work under one core.

    workers=None scales the feed with capacity (2 per host) so every
    width runs the identical per-host load and the trend isolates the
    width axis. The defaults keep the verify burst (~0.8 ms/sig native)
    well under window/K so the K bursts interleave on one core.

    kill_after_s kills host 0 mid-measure (SIGKILL, no restart): the
    exactly-once audit then requires every submitted batch to answer
    exactly once and parity-clean — via the survivors or the oracle-exact
    local host tier — and the report carries the survivors' post-kill
    routing share."""
    import tempfile
    import threading
    from pathlib import Path

    from corda_tpu.crypto.federation import FederatedVerifier
    from corda_tpu.crypto.provider import VerifyJob
    from corda_tpu.testing.driver import driver

    if workers is None:
        workers = 2 * hosts
    pks, msgs, sigs, valid = make_corpus()
    jobs = [VerifyJob(pk, m, s) for pk, m, s in
            zip(tile(pks, n_sigs), tile(msgs, n_sigs), tile(sigs, n_sigs))]
    expected = np.asarray(tile(valid, n_sigs), bool)
    with tempfile.TemporaryDirectory(prefix="bench-fed-") as td:
        with driver(Path(td)) as d:
            handles = d.start_federation(
                count=hosts, verifier="cpu", coalesce_us=coalesce_us,
                max_sigs=max(n_sigs * workers, 4096))
            fed = FederatedVerifier([h.address for h in handles],
                                    device_min_sigs=0)
            fed.warm()
            agg_lock = threading.Lock()
            agg = {"batches": 0, "sigs": 0, "parity_ok": True}
            times = []
            stop = threading.Event()

            def feeder(offset_s):
                # Staggered start: feeders launched in phase would open
                # every host's coalesce window simultaneously, piling K
                # verify bursts onto the same instant of the shared CPU.
                # The cycle-locked feed preserves the initial phase, so
                # spreading the K first-wave workers coalesce/K apart
                # keeps the verify bursts disjoint for the whole run —
                # and every LATER wave must launch after all K hosts are
                # busy, or least-depth routing would aim it at a host
                # whose window was deliberately not anchored yet and
                # re-synchronise the phases it exists to spread.
                if stop.wait(offset_s):
                    return
                while not stop.is_set():
                    t0 = time.perf_counter()
                    ok = fed.verify_batch(jobs)
                    dt = time.perf_counter() - t0
                    good = bool(np.array_equal(np.asarray(ok, bool),
                                               expected))
                    with agg_lock:
                        agg["batches"] += 1
                        agg["sigs"] += len(jobs)
                        agg["parity_ok"] = agg["parity_ok"] and good
                        times.append(dt)

            threads = [threading.Thread(
                target=feeder,
                args=((i % hosts) * coalesce_us / 1e6 / hosts
                      + (i // hosts) * coalesce_us / 1e6,),
                daemon=True, name=f"fed-feed{i}")
                       for i in range(workers)]
            t_all = time.perf_counter()
            for t in threads:
                t.start()
            kill_info = None
            if kill_after_s is not None and hosts >= 2:
                time.sleep(kill_after_s)
                at_kill = [c.dispatches for c in fed.channels]
                handles[0].kill()
                kill_info = {"killed_host": handles[0].address,
                             "at_kill_dispatches": at_kill}
            time.sleep(max(0.0, seconds - (kill_after_s or 0.0)))
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
            wall = time.perf_counter() - t_all
            out = {
                "hosts": hosts, "n_sigs": n_sigs, "workers": workers,
                "coalesce_us": coalesce_us,
                "batches": agg["batches"],
                "sigs_per_sec": round(agg["sigs"] / wall, 1),
                "parity_ok": agg["parity_ok"],
                "fallbacks": fed.fallbacks,
                "hedges": fed.hedges,
                "host_degraded": fed.host_degraded,
                "federation": fed.federation_stats(),
            }
            if times:
                times.sort()
                out["p50_ms"] = round(times[len(times) // 2] * 1e3, 2)
                out["p99_ms"] = round(
                    times[min(len(times) - 1,
                              int(len(times) * 0.99))] * 1e3, 2)
            if kill_info is not None:
                post = [c.dispatches - k for c, k in
                        zip(fed.channels, kill_info["at_kill_dispatches"])]
                total_post = sum(post)
                out["host_kill"] = {
                    "killed_host": kill_info["killed_host"],
                    # Every submission answered exactly once (each
                    # verify_batch returned one verdict array) and every
                    # verdict matched the corpus truth — across the kill.
                    "exactly_once": agg["parity_ok"],
                    "answered_batches": agg["batches"],
                    "post_kill_dispatches_by_host": post,
                    "survivor_share_post_kill": (
                        round(sum(post[1:]) / total_post, 4)
                        if total_post else None),
                    "host_degraded": fed.host_degraded,
                    "local_fallbacks": fed.fallbacks,
                }
            return out


def bench_multihost_scaling(host_counts=(1, 2, 4), n_sigs=16,
                            seconds=3.0, workers=None, coalesce_us=120000,
                            kill_leg=True):
    """Federated verify-plane scaling (round 19): aggregate cross-host
    sigs/s vs the number of per-host sidecars the federation router
    (crypto/federation.py) feeds, 1 -> 2 -> 4 simulated hosts, every
    verdict parity-checked against the corpus truth. The hosts are
    SIMULATED — sidecar processes on one box (mesh label "virtual-cpu"),
    so the section proves the routing/latency-hiding contract, not
    multi-machine bandwidth: near-linear scaling comes from overlapping
    K coalesce windows (see _federation_round), with the acceptance bar
    >= 1.7x aggregate at 2 hosts and >= 3x at 4.

    kill_leg adds a 2-host run that SIGKILLs one host mid-measure and
    audits the exactly-once + survivor-absorption contract.

    sigs_per_sec_by_hosts is hoisted flat for the monotonicity guard in
    tests/test_bench_report.py (mirrors multichip_scaling's contract)."""
    out = {"harness": "multiprocess-driver", "mesh": "virtual-cpu",
           "simulated_hosts": True, "n_sigs": n_sigs,
           "workers": workers or "2x-hosts",
           "coalesce_us": coalesce_us, "seconds": seconds, "hosts": {}}
    trend = {}
    for count in host_counts:
        try:
            r = _federation_round(count, n_sigs=n_sigs, seconds=seconds,
                                  workers=workers, coalesce_us=coalesce_us)
            out["hosts"][str(count)] = r
            if "sigs_per_sec" in r:
                trend[str(count)] = r["sigs_per_sec"]
        except Exception as e:
            out["hosts"][str(count)] = {"error": f"{type(e).__name__}: {e}"}
    out["sigs_per_sec_by_hosts"] = trend
    lo, hi = str(min(host_counts)), str(max(host_counts))
    if lo in trend and hi in trend and trend[lo]:
        out["scaling_1_to_max"] = round(trend[hi] / trend[lo], 2)
    if kill_leg:
        try:
            out["host_kill"] = _federation_round(
                2, n_sigs=n_sigs, seconds=seconds, workers=workers,
                coalesce_us=coalesce_us,
                kill_after_s=seconds * 0.4)["host_kill"]
        except Exception as e:
            out["host_kill"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def bench_chaos(n_tx=60, cluster_size=3, rate_tx_s=120.0):
    """Chaos section (round 7): measured recovery under deterministic fault
    injection. Two runs over the in-process raft cluster (real TCP +
    sqlite), clients notarising through the deadline-bounded retry flow:

    * leader_kill — the raft LEADER is killed mid-burst and rebuilt from
      disk; recovery is the gap from the kill to the first completion
      after it, and the exactly-once audit (client outcomes AND the
      cluster's committed_states row count) must hold across the change.
    * lossy_open_loop — the builtin "lossy" plan (seeded 5% transport.send
      drop) armed, open-loop paced; p99 shows what redelivery costs.

    Headline keys are hoisted to the section top so the bench contract
    (leader_kill_recovery_s, faults_injected, lossy p99) greps flat."""
    from corda_tpu.tools.loadtest import run_chaos_loadtest

    out = {}
    kill = run_chaos_loadtest(n_tx=n_tx, cluster_size=cluster_size,
                              kill_leader=True, rate_tx_s=rate_tx_s)
    out["leader_kill"] = {
        "exactly_once": kill.exactly_once,
        "tx_committed": kill.tx_committed,
        "tx_rejected": kill.tx_rejected,
        "tx_unresolved": kill.tx_unresolved,
        "cluster_committed": kill.cluster_committed,
        "recovery_s": kill.leader_kill_recovery_s,
        "p99_ms": kill.p99_ms,
        "disruptions": kill.disruptions,
    }
    lossy = run_chaos_loadtest(plan="lossy", n_tx=n_tx,
                               cluster_size=cluster_size,
                               rate_tx_s=rate_tx_s)
    out["lossy_open_loop"] = {
        "exactly_once": lossy.exactly_once,
        "tx_committed": lossy.tx_committed,
        "rate_tx_s": rate_tx_s,
        "p50_ms": lossy.p50_ms,
        "p99_ms": lossy.p99_ms,
    }
    out["leader_kill_recovery_s"] = kill.leader_kill_recovery_s
    out["faults_injected"] = lossy.faults_injected
    out["lossy_open_loop_p99_ms"] = lossy.p99_ms
    return out


def bench_reshard(n_tx=200, rate_tx_s=80.0, shards=2, to_shards=4,
                  cross_frac=0.2):
    """Elastic resharding section (round 13): the group count DOUBLES
    mid-sweep — a live split under open-loop load with the builtin
    "reshard" chaos plan armed (lossy transport + dropped handoff frames
    + stale netmap refreshes) — and then halves back in a clean merge run.
    The claim under test is a p99 blip, not an outage: the split must
    complete with exactly_once=true (every tx committed exactly once,
    ledger rows across the NEW groups totalling exactly the consumed
    refs, zero leaked reservations), client retries bounded (the
    wrong_epoch bounce count), and the latency windows split at the
    plan-publish / cutover marks showing where the tail went.

    Headline keys hoisted flat for the bench contract: exactly_once,
    wrong_epoch_bounces, reshard_window_s, p99_before/during/after_ms."""
    from corda_tpu.tools.loadtest import run_reshard_loadtest

    out = {"harness": "inproc-reshard", "n_tx": n_tx,
           "rate_tx_s": rate_tx_s, "plan": "reshard"}
    split = run_reshard_loadtest(
        plan="reshard", n_tx=n_tx, shards=shards, to_shards=to_shards,
        rate_tx_s=rate_tx_s, cross_frac=cross_frac)
    out["split"] = dict(split.__dict__)
    merge = run_reshard_loadtest(
        plan=None, n_tx=max(40, n_tx // 2), shards=to_shards,
        to_shards=shards, rate_tx_s=rate_tx_s)
    out["merge"] = dict(merge.__dict__)
    out["exactly_once"] = bool(split.exactly_once and merge.exactly_once)
    out["wrong_epoch_bounces"] = split.wrong_epoch_bounces
    out["handoff_frames"] = split.handoff_frames
    out["faults_injected"] = split.faults_injected
    out["reshard_window_s"] = (
        round(split.reshard_completed_s - split.reshard_started_s, 3)
        if (split.reshard_completed_s is not None
            and split.reshard_started_s is not None) else None)
    out["p99_before_ms"] = split.p99_before_ms
    out["p99_during_ms"] = split.p99_during_ms
    out["p99_after_ms"] = split.p99_after_ms
    return out


def bench_durability(n_tx=60, cluster_size=3, rate_tx_s=120.0,
                     micro_rows=2000):
    """Durability section (round 14): storage-corruption detection and
    self-healing repair, measured. Two sub-runs, error-isolated so a
    failure in one still reports the other:

    * bitrot_chaos — the builtin "bitrot" plan (seeded read-path bit-flips
      on the raft log + injected disk-full write failures) armed over the
      in-process 3-member cluster. The claim: corruption is DETECTED
      (integrity_errors > 0), healed through consensus (truncate +
      re-replicate), and the exactly-once ledger audit still holds; the
      post-run fsck gate proves the stored bytes stayed clean.
    * detect_repair_micro — a cold store with `micro_rows` framed raft
      rows, one corrupted on disk; measures fsck detection latency over
      the whole store (detect_ms) and the truncate-style repair
      (repair_s), then verifies the repaired store scans clean.

    Headline keys hoisted flat for the bench contract: exactly_once,
    integrity_errors, detect_ms, repair_s, fsck_clean."""
    out = {"plan": "bitrot", "n_tx": n_tx}
    try:
        from corda_tpu.tools.loadtest import run_chaos_loadtest

        chaos = run_chaos_loadtest(plan="bitrot", n_tx=n_tx,
                                   cluster_size=cluster_size,
                                   rate_tx_s=rate_tx_s)
        out["bitrot_chaos"] = {
            "exactly_once": chaos.exactly_once,
            "tx_committed": chaos.tx_committed,
            "integrity_errors": chaos.integrity_errors,
            "fsck_clean": chaos.fsck_clean,
            "faults_injected": chaos.faults_injected,
            "p99_ms": chaos.p99_ms,
        }
        out["exactly_once"] = chaos.exactly_once
        out["integrity_errors"] = chaos.integrity_errors
        out["fsck_clean"] = chaos.fsck_clean
    except Exception as e:
        out["bitrot_chaos"] = {"error": f"{type(e).__name__}: {e}"}
    try:
        import sqlite3
        import tempfile
        from pathlib import Path

        from corda_tpu.node.services import integrity as _integrity
        from corda_tpu.node.services.persistence import NodeDatabase
        from corda_tpu.tools.fsck import fsck_db

        tmp = Path(tempfile.mkdtemp(prefix="corda-tpu-durab-"))
        db = NodeDatabase(tmp / "node.db")
        with db.lock:
            db.conn.executescript(
                "CREATE TABLE IF NOT EXISTS raft_log ("
                "idx INTEGER PRIMARY KEY, term INTEGER, blob BLOB, "
                "crc INTEGER)")
            rows = [(i, 1, b"entry-%08d" % i) for i in range(1, micro_rows)]
            db.conn.executemany(
                "INSERT INTO raft_log (idx, term, blob, crc) "
                "VALUES (?, ?, ?, ?)",
                [(i, t, b, _integrity.log_crc(i, t, b))
                 for i, t, b in rows])
            db.set_setting("raft_last_applied", str(micro_rows // 2))
            db.commit()
        db.close()
        # One bit of on-disk damage past the applied prefix.
        conn = sqlite3.connect(str(tmp / "node.db"))
        victim = micro_rows // 2 + 10
        conn.execute("UPDATE raft_log SET blob = ? WHERE idx = ?",
                     (b"damaged!", victim))
        conn.commit()
        conn.close()
        t0 = time.monotonic()
        detect = fsck_db(tmp / "node.db")
        detect_ms = round(1e3 * (time.monotonic() - t0), 3)
        t0 = time.monotonic()
        fsck_db(tmp / "node.db", repair=True)
        repair_s = round(time.monotonic() - t0, 6)
        verify = fsck_db(tmp / "node.db")
        out["detect_repair_micro"] = {
            "rows": micro_rows,
            "corrupt_found": detect["corrupt"],
            "detect_ms": detect_ms,
            "repair_s": repair_s,
            "clean_after_repair": verify["clean"],
        }
        out["detect_ms"] = detect_ms
        out["repair_s"] = repair_s
    except Exception as e:
        out["detect_repair_micro"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def bench_partition_chaos(n_tx=36, cluster_size=3, cut_hold_s=4.0):
    """Partition section (round 20): deterministic split-brain over the
    in-process TCP cluster, audited by the history checker
    (testing/history.py). Three error-isolated legs:

    * split_leader — leader isolated, prevote ON: check-quorum must cede
      the quorumless leadership, the majority keeps committing, and the
      heal-to-first-commit recovery is measured (recovery_s).
    * split_follower_prevote / split_follower_noprevote — a follower
      isolated, prevote ON vs OFF: the A/B for term inflation. With
      pre-vote the cut-off member canvasses without persisting a term
      (bounded inflation); without it every futile timeout inflates the
      term and the rejoiner disrupts the healthy side at heal.

    Headline keys hoisted flat for the bench contract: recovery_s,
    max_term_inflation (prevote on) vs max_term_inflation_noprevote,
    history_linearizable (AND over every leg), minority_commits,
    lost_acks, partition_cuts, checkquorum_stepdowns."""
    out = {"plan": "split-hold", "n_tx": n_tx}
    legs = (
        ("split_leader", "leader", True),
        ("split_follower_prevote", "follower", True),
        ("split_follower_noprevote", "follower", False),
    )
    linearizable = True
    for key, isolate, prevote in legs:
        try:
            from corda_tpu.tools.loadtest import run_partition_loadtest

            r = run_partition_loadtest(
                n_tx=n_tx, cluster_size=cluster_size, prevote=prevote,
                isolate=isolate, cut_hold_s=cut_hold_s)
            out[key] = {
                "prevote": r.prevote,
                "isolate": r.isolate,
                "tx_committed": r.tx_committed,
                "tx_unresolved": r.tx_unresolved,
                "recovery_s": r.recovery_s,
                "max_term_inflation": r.max_term_inflation,
                "minority_commits_during_cut": r.minority_commits_during_cut,
                "checkquorum_stepdowns": r.checkquorum_stepdowns,
                "prevotes": r.prevotes,
                "prevote_rejections": r.prevote_rejections,
                "partition_cuts": r.partition_cuts,
                "partition_drops": r.partition_drops,
                "history_linearizable": r.history_linearizable,
                "lost_acks": r.lost_acks,
                "double_spends": r.double_spends,
            }
            linearizable = linearizable and r.history_linearizable
        except Exception as e:
            out[key] = {"error": f"{type(e).__name__}: {e}"}
            linearizable = False
    lead = out.get("split_leader", {})
    on = out.get("split_follower_prevote", {})
    off = out.get("split_follower_noprevote", {})
    out["recovery_s"] = lead.get("recovery_s")
    out["checkquorum_stepdowns"] = lead.get("checkquorum_stepdowns")
    out["max_term_inflation"] = on.get("max_term_inflation")
    out["max_term_inflation_noprevote"] = off.get("max_term_inflation")
    out["history_linearizable"] = linearizable
    out["minority_commits"] = sum(
        leg.get("minority_commits_during_cut", 0) for leg in
        (lead, on, off))
    out["lost_acks"] = sum(
        leg.get("lost_acks", 0) for leg in (lead, on, off))
    out["partition_cuts"] = sum(
        leg.get("partition_cuts", 0) for leg in (lead, on, off))
    return out


class _PhaseClock:
    """Per-phase wall clocks riding the report, so a slow or failed run is
    attributable from the JSON alone (report["phase"] names the phase in
    flight when one raised)."""

    def __init__(self, report: dict, first: str):
        self.seconds = report.setdefault("phase_seconds", {})
        self.report = report
        self._t = time.monotonic()
        self._name = first
        report["phase"] = first

    def set(self, name: str) -> None:
        now = time.monotonic()
        self.seconds[self._name] = round(
            self.seconds.get(self._name, 0.0) + (now - self._t), 1)
        self._t, self._name = now, name
        self.report["phase"] = name


def _device_phases(send=None) -> dict:
    """Every phase that drives the chip from ITS OWN process: runs in a
    child (main() -> _in_child) that exits before any device-owning
    cluster phase starts, because a chip belongs to one process at a
    time. Raises unless JAX finds a TPU, and on any phase failure."""
    from corda_tpu.testing.chip import require_tpu

    report = {"device": require_tpu()}
    clock = _PhaseClock(report, "warm")
    set_phase = clock.set

    from corda_tpu.ops import ed25519_jax

    pks, msgs, sigs, valid = make_corpus()
    # Compile every bucket BEFORE anything is timed.
    _warm_verify_kernel()
    warm_buckets(pks, msgs, sigs)

    # Roundtrip FIRST: it uses small (1024-lane) buckets, and running it
    # after the 64k-bucket phases was measured to suffer a multi-second
    # device-allocator stall that has nothing to do with the protocol.
    set_phase("notary_roundtrip")
    report["notary_roundtrip"] = bench_notary_roundtrip()

    set_phase("kernel_buckets")
    kernel, e2e, devhash, backends = bench_kernel(pks, msgs, sigs, valid)
    report["kernel_sigs_per_sec"] = {
        str(k): round(v, 1) for k, v in kernel.items()}
    report["e2e_sigs_per_sec"] = {str(k): round(v, 1) for k, v in e2e.items()}
    report["e2e_devhash_sigs_per_sec"] = {
        str(k): round(v, 1) for k, v in devhash.items()}

    set_phase("stream")
    stream, passes, stream_backend = bench_stream(
        pks, msgs, sigs, valid, repeats=4)
    backends["stream"] = stream_backend
    report["e2e_stream_sigs_per_sec"] = round(stream, 1)
    report["e2e_stream_passes"] = passes
    set_phase("sha256")
    report["sha256_64B_hashes_per_sec"] = round(bench_sha256(), 1)
    set_phase("cpu_oracle")
    report["cpu_oracle_sigs_per_sec"] = round(
        bench_cpu_oracle(pks, msgs, sigs), 1)

    best = {**e2e, **{k: max(e2e[k], devhash[k]) for k in devhash}}
    best_bucket = max(best, key=lambda b: best[b], default=None)
    if best_bucket is None or stream >= best.get(best_bucket, 0.0):
        headline, headline_backend = stream, backends.get("stream")
    else:
        headline = best[best_bucket]
        which = ("e2e" if e2e[best_bucket] >= devhash.get(best_bucket, 0)
                 else "e2e_devhash")
        headline_backend = backends[which][best_bucket]
    report.update({
        "value": round(headline, 1),
        "vs_baseline": round(headline / BASELINE_SIGS_PER_SEC, 3),
        "backend": headline_backend,
        "backend_by_phase": {
            phase: ({str(k): v for k, v in b.items()}
                    if isinstance(b, dict) else b)
            for phase, b in backends.items()},
        "pallas_failures_total": ed25519_jax.pallas_failures_total(),
        "best_bucket": best_bucket,
    })

    # The BASELINE configs that verify or hash on the device in THIS
    # process (MockNetwork + JaxVerifier, device sha256).
    configs = report["baseline_configs"] = {}
    for name, fn in (("resolve_ids", bench_resolve_ids),
                     ("trader_dvp", bench_trades),
                     ("composite_3of3", bench_multisig)):
        set_phase(name)
        configs[name] = fn()
    set_phase("done")
    report.pop("phase")
    return report


def _in_child(fn):
    """fn() in a spawned child that has exited when this returns."""
    from corda_tpu.testing.chip import run_in_child

    return run_in_child(fn)


def _require_jax_free(phase: str) -> None:
    """A device-owning child is about to start: this process must not
    hold the chip (it would, had anything here imported JAX)."""
    import sys

    if "jax" in sys.modules:
        raise RuntimeError(f"{phase}: the bench parent imported jax and "
                           "would hold the chip its device children need")


def _run_cluster_phases(report: dict, clock: _PhaseClock,
                        device_count: int) -> None:
    """The multiprocess configs, from this JAX-free parent. The ones that
    give the chip to a child process (a sidecar or a notary member) run
    first; then the host-path configs."""
    set_phase = clock.set
    configs = report.setdefault("baseline_configs", {})
    mesh_widths = tuple(w for w in (1, 2, 4, 8) if w <= device_count)
    # The flagship device phases run with the verification sidecar: ONE
    # device-owning server all members feed, coalescing micro-batches
    # across processes (crypto/sidecar.py).
    device_owning = (
        # Armed adaptive-coalesce flagship (static A/B rides under
        # adaptive_coalesce_ab — round 13).
        ("raft_validating_3node", lambda: bench_validating_flagship(
            verifier="jax", notary_device="accelerator")),
        ("raft_open_loop_latency", lambda: bench_raft_open_loop(
            verifier="jax", notary_device="accelerator", sidecar=True)),
        ("multichip_scaling", lambda: bench_multichip_scaling(
            device_counts=mesh_widths, notary_device="accelerator",
            flagship=True)))
    host_path = (
        ("raft_notary_3node", bench_raft_cluster),
        ("open_loop_latency", bench_open_loop_latency),
        # Sidecar-fed so the deadline scheduler's early-flush is in the
        # measured loop; the sweep stays on host crypto (the SLO claim is
        # about scheduling, not kernels).
        ("slo_sweep", lambda: bench_slo_sweep(sidecar=True)),
        # The ingest sweep measures the CLIENT plane (and names the first
        # server-side stage it saturates).
        ("ingest_sweep", bench_ingest_sweep),
        # Round profiler coverage + the Prometheus render/parse contract.
        ("telemetry", bench_telemetry),
        ("shard_scaling", bench_shard_scaling),
        # Group count doubles mid-sweep under the lossy reshard plan;
        # exactly_once + a bounded p99 blip.
        ("reshard", bench_reshard),
        # Federated verify plane: simulated hosts on host crypto (the
        # claim is cross-host ROUTING; the chip belongs to the multichip
        # section).
        ("multihost_scaling", bench_multihost_scaling),
        ("partial_merkle", bench_partial_merkle),
        ("flow_churn", bench_flow_churn),
        # Autotune closed loop: verdict -> gated knob sweep -> committed
        # overlay (the claim is the LOOP, not kernels).
        ("autotune", bench_autotune),
        # Indexed vault plane at full spread: the 1M-state store proves
        # the 100x-size/10x-p99 sublinearity claim and the 100k watermark
        # boot speedup.
        ("vault_scaling", bench_vault_scaling))
    for name, fn in device_owning:
        set_phase(name)
        _require_jax_free(name)
        configs[name] = fn()
    for name, fn in host_path:
        set_phase(name)
        configs[name] = fn()
    for name, fn in (("chaos", bench_chaos),
                     ("durability", bench_durability),
                     ("partition_chaos", bench_partition_chaos)):
        set_phase(name)
        report[name] = fn()
    # The doctor diagnoses the finished report — last, so its roofline
    # sees every section (kernel ceiling, flagship, chaos) this run
    # produced.
    set_phase("doctor")
    report["doctor"] = bench_doctor(report)
    set_phase("done")
    report.pop("phase")


def main() -> int:
    """Run every phase; print ONE JSON line (everything that finished,
    and the failure if one phase raised). Exits non-zero when JAX finds no
    TPU or any phase raises: no path measures on the CPU in place of the
    chip."""
    import os
    import traceback

    report = {
        "metric": "verified_sigs_per_sec",
        "value": 0.0,
        "unit": "sigs/sec",
        "vs_baseline": 0.0,
    }
    # Invariant-analyzer stamp: live finding count over the shipped tree
    # (0 == every machine-checked contract holds for the code measured).
    from corda_tpu.analysis import analyze_paths

    report["analysis_findings"] = len(analyze_paths(
        [os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "corda_tpu")]).findings)
    rc = 0
    clock = _PhaseClock(report, "device_phases")
    try:
        device = _in_child(_device_phases)
        report["phase_seconds"].update(device.pop("phase_seconds"))
        report.update(device)
        _run_cluster_phases(report, clock, device["device"]["count"])
    except Exception as e:
        traceback.print_exc()
        report["error"] = f"{type(e).__name__}: {e}"
        report["error_phase"] = report.pop("phase", None)
        rc = 1
    print(json.dumps(report), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
