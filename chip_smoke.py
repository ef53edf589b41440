"""Chip smoke: the notary's signature-verification path, once, on the chip.

    python chip_smoke.py             # one TPU chip: notary + kernel phases
    python chip_smoke.py --chips 4   # four chips: the mesh sidecar path only

One chip, three phases, each in processes of its own (a chip belongs to
one process at a time, and this parent never imports JAX):

1. **kernel, cold** — a child process verifies a seeded corpus of 65,536
   distinct signatures (the largest bench bucket) through the provider
   seam the node uses (``JaxVerifier``) and through the fully-on-device
   hash path (``verify_arrays_hashed``). Every lane must agree with the
   host tier (``CpuVerifier``, computed here meanwhile), a seeded sample
   of 1,024 lanes with the pure-Python oracle, and the kernel backend must
   be ``pallas`` with no failures and no degrade. Then one call of 70,000
   live lanes (the well-formed corpus, cycled) in the 131,072 bucket: the
   kernel skips the 59 blocks past them, every live lane must agree with
   the host tier and every dead lane must read reject.
2. **notary** — the validating Raft notary as users deploy it: three
   members, one verification sidecar that owns the chip, two client
   processes offering the raft-notary-demo load of 1,000 transactions
   (tools/loadtest.run_loadtest_multiprocess, the ``--processes --sidecar``
   entry point). Every transaction must commit exactly once, the sidecar
   must be device-ready and serve at least one batch with the Pallas
   kernel, and no member may fall back to its host tier.
3. **kernel, warm** — phase 1 again in a new process: every one of its
   compiles must be a persistent-cache hit, and both processes' compile
   seconds are printed.

``--chips 4`` runs one child: the same corpus through a mesh-owning
sidecar (``MeshVerifier(n_devices=4)``, the plain XLA graph sharded over
the batch axis), compared lane for lane with the single-chip
``JaxVerifier`` answer on device 0, with each device's 16,384-lane shard
checked from the output's sharding and the sidecar's stats.

Earlier lines report what was found; the last line is one JSON object,
``{"ok": true, "device": {...}}``. Any failed check exits non-zero and
prints no such line — as does a host where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from corda_tpu.testing.chip import ChipError, require_tpu, run_in_child

N_LANES = 65536  # the largest bucket of the bench ladder (bench.BUCKETS)
CORRUPT_EVERY = 8  # 1 in 8 signatures is damaged (R, S or message)
ORACLE_SAMPLE = 1024
SEED = 21
PADDED_LIVE = 70000  # live lanes of the padded call: 69 of 128 blocks
PADDED_BUCKET = 131072
NOTARY_TX = 1000  # BASELINE.json raft-notary-demo
# Offered loads for the notary phase, tried in order until the sidecar
# serves a device batch: the first is the loadtest's default shape.
NOTARY_LOADS = ({"clients": 2, "width": 32, "inflight": 64},
                {"clients": 4, "width": 32, "inflight": 128})


class SmokeFailure(ChipError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def build_corpus(n: int, seed: int):
    """n seeded verify jobs with distinct keys and distinct 32-byte tx ids,
    signed by the columnar host signer. Every CORRUPT_EVERY-th lane is
    damaged (a flipped bit in R, in S or in the message, in turn), and a
    few lanes are malformed: wrong-length keys and signatures, which every
    tier must reject, and junk 32-byte keys (0xff * 32)."""
    import numpy as np

    from corda_tpu.crypto import batch_sign, fast_ed25519
    from corda_tpu.crypto.provider import VerifyJob

    rng = np.random.default_rng(seed)
    seed_buf, msg_buf = rng.bytes(32 * n), rng.bytes(32 * n)
    seeds = [seed_buf[32 * i:32 * i + 32] for i in range(n)]
    msgs = [msg_buf[32 * i:32 * i + 32] for i in range(n)]
    sigs = batch_sign.sign_batch(seeds, msgs)
    pks = [fast_ed25519.public_key(s) for s in seeds]
    check(len(set(pks)) == n and len(set(msgs)) == n,
          "corpus keys and tx ids must be distinct")

    def flip(b: bytes, at: int) -> bytes:
        return b[:at] + bytes([b[at] ^ 0x10]) + b[at + 1:]

    jobs = []
    for i in range(n):
        pk, m, s = pks[i], msgs[i], sigs[i]
        if i % CORRUPT_EVERY == CORRUPT_EVERY - 1:
            kind = (i // CORRUPT_EVERY) % 3
            if kind == 0:
                s = flip(s, 3)  # R
            elif kind == 1:
                s = flip(s, 40)  # S
            else:
                m = flip(m, 17)  # the tx id
        jobs.append(VerifyJob(pk, m, s))
    malformed = rng.choice(n, size=12, replace=False)
    for k, i in enumerate(malformed.tolist()):
        j = jobs[i]
        if k % 3 == 0:
            jobs[i] = VerifyJob(j.pubkey[:31], j.message, j.sig)
        elif k % 3 == 1:
            jobs[i] = VerifyJob(j.pubkey, j.message, j.sig + b"\0")
        else:
            jobs[i] = VerifyJob(b"\xff" * 32, j.message, j.sig)
    return jobs


class _CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit records its retrieval time under the
    same compile event)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}


def well_formed(jobs) -> list:
    """Indices of the jobs with a 32-byte key and a 64-byte signature."""
    return [i for i, j in enumerate(jobs)
            if len(j.pubkey) == 32 and len(j.sig) == 64]


def padded_sources(good: list) -> list:
    """The corpus lane behind each live lane of the padded call."""
    return [good[k % len(good)] for k in range(PADDED_LIVE)]


def kernel_phase(send, seed: int) -> dict:
    """Child body: the 65,536-lane corpus through JaxVerifier and through
    verify_arrays_hashed, then the padded call. Returns the answers and
    the counters."""
    import numpy as np

    from corda_tpu.crypto.provider import JaxVerifier
    from corda_tpu.ops import compile_cache_dir, ed25519_jax

    out = {"device": require_tpu(1), "cache_dir": compile_cache_dir()}
    send({"device": out["device"]})
    meter = _CompileMeter()
    t0 = time.perf_counter()
    jobs = build_corpus(N_LANES, seed)
    out["corpus_s"] = time.perf_counter() - t0

    verifier = JaxVerifier()  # device_min_sigs left at its default
    t0 = time.perf_counter()
    provider_ok = verifier.verify_batch(jobs)
    out["provider_first_s"] = time.perf_counter() - t0
    out["provider_compile"] = meter.snapshot()
    t0 = time.perf_counter()
    again = verifier.verify_batch(jobs)
    out["provider_steady_s"] = time.perf_counter() - t0
    check(np.array_equal(provider_ok, again),
          "JaxVerifier answered the same corpus differently twice")
    out["provider_backend"] = ed25519_jax.last_backend()
    out["provider_batches"] = {"device": verifier.device_batches,
                               "host": verifier.host_batches}
    out["degraded"] = verifier.degraded

    # The fully-on-device path: SHA-512 challenge + mod L + verify, with
    # the well-formed lanes packed into one 65,536-lane bucket.
    good = well_formed(jobs)
    arrays, n = ed25519_jax.precompute_batch_device(
        [jobs[i].pubkey for i in good], [jobs[i].message for i in good],
        [jobs[i].sig for i in good], bucket=N_LANES)
    before = meter.seconds
    t0 = time.perf_counter()
    lanes = np.asarray(ed25519_jax.verify_arrays_hashed(*arrays))
    out["hashed_first_s"] = time.perf_counter() - t0
    out["hashed_compile_s"] = meter.seconds - before
    hashed_ok = np.zeros(len(jobs), bool)
    hashed_ok[good] = lanes[:n]
    out["hashed_backend"] = ed25519_jax.last_backend()

    src = padded_sources(good)
    arrays, _ = ed25519_jax.precompute_batch_device(
        [jobs[i].pubkey for i in src], [jobs[i].message for i in src],
        [jobs[i].sig for i in src], bucket=PADDED_BUCKET)
    skipped = ed25519_jax.pallas_blocks_skipped_total()
    t0 = time.perf_counter()
    padded = np.asarray(ed25519_jax.verify_arrays_hashed(
        *arrays, live=PADDED_LIVE))
    out["padded_first_s"] = time.perf_counter() - t0
    out["padded_backend"] = ed25519_jax.last_backend()
    out["padded_blocks_skipped"] = (
        ed25519_jax.pallas_blocks_skipped_total() - skipped)
    out["padded_ok"] = np.packbits(padded).tobytes()
    out["pallas_failures_total"] = ed25519_jax.pallas_failures_total()
    out["compile"] = meter.snapshot()
    out["provider_ok"] = np.packbits(provider_ok).tobytes()
    out["hashed_ok"] = np.packbits(hashed_ok).tobytes()
    return out


def mesh_phase(send, seed: int, n_devices: int = 4) -> dict:
    """Child body for --chips 4: the corpus through a mesh-owning sidecar,
    and through the single-chip JaxVerifier on device 0."""
    import os
    import tempfile

    import numpy as np

    from corda_tpu.crypto.provider import JaxVerifier, MeshVerifier
    from corda_tpu.crypto.sidecar import SidecarServer
    from corda_tpu.node.verify_client import SidecarVerifier
    from corda_tpu.ops import ed25519_jax, sharded

    out = {"device": require_tpu(n_devices)}
    send({"device": out["device"]})
    meter = _CompileMeter()
    jobs = build_corpus(N_LANES, seed)

    single = JaxVerifier()
    t0 = time.perf_counter()
    single_ok = single.verify_batch(jobs)
    out["single_first_s"] = time.perf_counter() - t0
    out["single_backend"] = ed25519_jax.last_backend()
    out["single_batches"] = {"device": single.device_batches,
                             "host": single.host_batches}

    mesh_verifier = MeshVerifier(n_devices=n_devices)
    sock = os.path.join(tempfile.mkdtemp(prefix="smoke-mesh-"), "sc.sock")
    server = SidecarServer(sock, verifier=mesh_verifier, coalesce_us=0,
                           max_sigs=N_LANES, devices=n_devices)
    # No boot warm: the warm-up compiles the two pump buckets, which this
    # check never sends; its one 65,536-lane request compiles in line.
    server.start(warm=False)
    try:
        client = SidecarVerifier(sock, deadline_ms=900_000.0,
                                 device_min_sigs=0, devices=n_devices)
        t0 = time.perf_counter()
        mesh_ok = client.verify_batch(jobs)
        out["mesh_first_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = client.verify_batch(jobs)
        out["mesh_steady_s"] = time.perf_counter() - t0
        check(np.array_equal(mesh_ok, again),
              "the mesh answered the same corpus differently twice")
        stats = server.stats()
        out["client_fallbacks"] = client.fallbacks
        out["client_last_tier"] = client.last_tier
    finally:
        server.stop()
    out["sidecar"] = {k: stats[k] for k in (
        "device_batches", "host_batches", "packed_batches", "device_lanes",
        "pad_lanes", "per_device_occupancy", "per_device_batch_sigs_hist",
        "errors", "verifier")}

    # Where the lanes went: the mesh executable's output shards.
    good = well_formed(jobs)
    packed = sharded.pack_batch_sharded(
        [jobs[i].pubkey for i in good], [jobs[i].message for i in good],
        [jobs[i].sig for i in good], mesh_verifier.mesh)
    lanes = packed.fn(*packed.arrays)
    out["shards"] = [{"device": str(s.device), "lanes": int(s.data.shape[0])}
                     for s in lanes.addressable_shards]
    out["compile"] = meter.snapshot()
    out["single_ok"] = np.packbits(single_ok).tobytes()
    out["mesh_ok"] = np.packbits(mesh_ok).tobytes()
    return out


def run_child(body, seed: int, while_waiting=None) -> dict:
    """``body`` in its own process (corda_tpu.testing.chip.run_in_child);
    ``while_waiting`` runs here once the child has found its TPU."""
    side = {}

    def on_message(msg):
        if "device" in msg and while_waiting is not None:
            side.update(while_waiting())

    check("jax" not in sys.modules,
          "the smoke parent imported jax: it would hold the chip")
    t0 = time.perf_counter()
    result = run_in_child(body, seed, on_message=on_message)
    result["wall_s"] = time.perf_counter() - t0
    if side:
        result["parent"] = side
    return result


def host_answers(seed: int) -> dict:
    """The references, computed in this (JAX-free) parent: every lane on
    the host tier, and a seeded sample on the pure-Python oracle."""
    import numpy as np

    from corda_tpu.crypto import ref_ed25519
    from corda_tpu.crypto.provider import CpuVerifier

    t0 = time.perf_counter()
    jobs = build_corpus(N_LANES, seed)
    host = CpuVerifier().verify_batch(jobs)
    host_s = time.perf_counter() - t0
    sample = np.random.default_rng(seed + 1).choice(
        N_LANES, size=ORACLE_SAMPLE, replace=False)
    oracle = {int(i): ref_ed25519.verify(jobs[i].pubkey, jobs[i].message,
                                         jobs[i].sig) for i in sample}
    check(all(bool(host[i]) == ok for i, ok in oracle.items()),
          "host tier disagrees with the oracle")
    expect_reject = sum(1 for i in range(N_LANES)
                        if i % CORRUPT_EVERY == CORRUPT_EVERY - 1)
    check(int((~host).sum()) >= expect_reject,
          "host tier accepted damaged signatures")
    return {"host": host, "oracle": oracle, "host_s": host_s,
            "host_rejects": int((~host).sum()), "good": well_formed(jobs)}


def _unpack(bits: bytes):
    import numpy as np

    return np.unpackbits(np.frombuffer(bits, np.uint8))[:N_LANES].astype(
        bool)


def check_lanes(name: str, got, refs: dict) -> dict:
    import numpy as np

    host = refs["host"]
    mismatch = np.flatnonzero(got != host)
    check(mismatch.size == 0,
          f"{name}: {mismatch.size} lanes disagree with the host tier "
          f"(first {mismatch[:8].tolist()})")
    oracle_bad = [i for i, ok in refs["oracle"].items() if bool(got[i]) != ok]
    check(not oracle_bad, f"{name}: oracle sample mismatch at {oracle_bad}")
    return {"lanes": int(got.size), "agree_host": int(got.size),
            "agree_oracle": len(refs["oracle"]),
            "accepted": int(got.sum())}


def check_padded(name: str, bits: bytes, refs: dict) -> dict:
    """The padded call: live lanes as the host tier answers their corpus
    lanes, dead lanes reject."""
    import numpy as np

    got = np.unpackbits(np.frombuffer(bits, np.uint8))[:PADDED_BUCKET]
    got = got.astype(bool)
    want = refs["host"][padded_sources(refs["good"])]
    bad = np.flatnonzero(got[:PADDED_LIVE] != want)
    check(bad.size == 0,
          f"{name}: {bad.size} live lanes of the padded call disagree with "
          f"the host tier (first {bad[:8].tolist()})")
    dead = int(got[PADDED_LIVE:].sum())
    check(dead == 0, f"{name}: {dead} dead lanes of the padded call accepted")
    return {"live": PADDED_LIVE, "bucket": PADDED_BUCKET,
            "accepted": int(got.sum())}


def notary_phase() -> dict:
    """The validating Raft notary, sidecar-fed, through the multiprocess
    loadtest; retried at a higher offered load only if the default load
    never reaches the device crossover."""
    from corda_tpu.tools.loadtest import run_loadtest_multiprocess

    attempts = []
    for load in NOTARY_LOADS:
        t0 = time.perf_counter()
        r = run_loadtest_multiprocess(
            n_tx=NOTARY_TX, notary="raft-validating", cluster_size=3,
            verifier="jax", notary_device="accelerator", sidecar=True,
            **load)
        side = r.sidecar or {}
        members = {name: {"fallbacks": (st.get("sidecar") or {}).get(
                              "fallbacks"),
                          "degraded": (st.get("sidecar") or {}).get(
                              "degraded"),
                          "sidecar_sigs": (st.get("sidecar") or {}).get(
                              "sigs"),
                          "host_batches": st.get("host_batches")}
                   for name, st in r.node_stamps.items()}
        attempt = {
            "load": load, "wall_s": time.perf_counter() - t0,
            "warm_wait_s": r.device_warm_wait_s,
            "committed": r.tx_committed, "requested": r.tx_requested,
            "rejected": r.tx_rejected, "exactly_once": r.exactly_once,
            "ledger_committed": r.ledger_committed,
            "tx_per_sec": r.tx_per_sec, "sigs_per_sec": r.sigs_per_sec,
            "p50_ms": r.p50_ms, "p99_ms": r.p99_ms,
            "sidecar": {k: side.get(k) for k in (
                "device_ready", "warm_error", "kernel_backend",
                "device_batches", "host_batches", "batches", "sigs",
                "batch_sigs_hist", "errors", "error")},
            "members": members}
        attempts.append(attempt)
        report("notary_attempt", **attempt)
        check(r.tx_committed == NOTARY_TX and r.tx_rejected == 0,
              f"notary committed {r.tx_committed}/{NOTARY_TX} "
              f"({r.tx_rejected} rejected)")
        check(r.exactly_once is True, "notary exactly-once audit failed")
        check(side.get("device_ready") is True and not side.get("warm_error"),
              f"sidecar not device-ready: {side.get('warm_error')}")
        check(side.get("errors") == 0, "sidecar replied with errors")
        for name, m in members.items():
            check(m["fallbacks"] == 0 and m["degraded"] == 0,
                  f"{name} fell back to its host tier: {m}")
        if (side.get("device_batches") or 0) > 0:
            check(side.get("kernel_backend") == "pallas",
                  f"sidecar kernel backend {side.get('kernel_backend')}")
            return {"attempts": attempts}
    raise SmokeFailure("no offered load sent a batch to the device "
                       f"({[a['load'] for a in attempts]})")


def _kernel_run(name: str, seed: int, refs: dict) -> dict:
    def references():
        refs.update(host_answers(seed))
        return {"host_s": refs["host_s"], "host_rejects": refs["host_rejects"]}

    res = run_child(kernel_phase, seed,
                    while_waiting=None if refs else references)
    for key in ("provider_ok", "hashed_ok"):
        res[key.replace("_ok", "_lanes")] = check_lanes(
            f"{name}/{key}", _unpack(res.pop(key)), refs)
    res["padded_lanes"] = check_padded(f"{name}/padded",
                                       res.pop("padded_ok"), refs)
    backends = [res[k] for k in ("provider_backend", "hashed_backend",
                                 "padded_backend")]
    check(backends == ["pallas"] * 3,
          f"{name}: kernel backends {backends}, want pallas")
    dead_blocks = (PADDED_BUCKET - PADDED_LIVE) // 1024
    check(res["padded_blocks_skipped"] == dead_blocks,
          f"{name}: the padded call skipped {res['padded_blocks_skipped']} "
          f"blocks, want {dead_blocks}")
    check(res["pallas_failures_total"] == 0,
          f"{name}: {res['pallas_failures_total']} Pallas failures")
    check(res["degraded"] == 0, f"{name}: device tier degraded")
    check(res["provider_batches"] == {"device": 2, "host": 0},
          f"{name}: JaxVerifier routed {res['provider_batches']}")
    report(name, **res)
    return res


def smoke_one_chip(seed: int) -> dict:
    """kernel (cold) -> notary -> kernel (warm): the first child proves
    the TPU at once, and the second kernel child starts long after the
    first exited, so its compile seconds measure the persistent cache."""
    refs: dict = {}
    cold = _kernel_run("kernel_cold", seed, refs)
    t0 = time.perf_counter()
    notary = notary_phase()
    report("notary", wall_s=time.perf_counter() - t0,
           attempts=len(notary["attempts"]))
    warm = _kernel_run("kernel_warm", seed, refs)
    cold_c, warm_c = cold["compile"], warm["compile"]
    report("compile_cache", cold_compile_s=cold_c["compile_s"],
           warm_compile_s=warm_c["compile_s"],
           cold_cache_hits=cold_c["cache_hits"],
           warm_cache_hits=warm_c["cache_hits"], compiles=warm_c["compiles"],
           cache_dir=warm["cache_dir"])
    # The second process must find every compile in the persistent cache.
    # (The first one may too, when the cache directory came warm from an
    # earlier run: then both compile times are cache reads.)
    check(warm_c["compiles"] > 0
          and warm_c["cache_hits"] == warm_c["compiles"],
          f"warm kernel process compiled {warm_c['compiles']} graphs with "
          f"{warm_c['cache_hits']} persistent-cache hits")
    return warm["device"]


def smoke_four_chips(seed: int) -> dict:
    import numpy as np

    refs = {}

    def references():
        refs.update(host_answers(seed))
        return {"host_s": refs["host_s"]}

    res = run_child(mesh_phase, seed, while_waiting=references)
    single, mesh = _unpack(res.pop("single_ok")), _unpack(res.pop("mesh_ok"))
    diff = np.flatnonzero(single != mesh)
    check(diff.size == 0, f"mesh and single chip disagree on {diff.size} "
                          f"lanes (first {diff[:8].tolist()})")
    res["single_lanes"] = check_lanes("single", single, refs)
    res["mesh_lanes"] = check_lanes("mesh", mesh, refs)
    check(res["single_backend"] == "pallas",
          f"single-chip backend {res['single_backend']}")
    side = res["sidecar"]
    check(side["device_batches"] == 2 and side["host_batches"] == 0
          and side["errors"] == 0 and res["client_fallbacks"] == 0,
          f"mesh sidecar did not serve from the device: {side}")
    share = N_LANES // 4
    check(side["per_device_batch_sigs_hist"] == {str(share): 2},
          f"per-device lanes {side['per_device_batch_sigs_hist']}")
    check(side["per_device_occupancy"] > 0.99,
          f"per-device occupancy {side['per_device_occupancy']}")
    shards = res["shards"]
    check(len({s["device"] for s in shards}) == 4
          and all(s["lanes"] == share for s in shards),
          f"output shards {shards}: want 4 devices x {share} lanes")
    report("mesh", **res)
    return res["device"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-chip mesh sidecar path")
    args = parser.parse_args(argv)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            device = smoke_four_chips(SEED)
        else:
            device = smoke_one_chip(SEED)
    except ChipError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    report("total", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
