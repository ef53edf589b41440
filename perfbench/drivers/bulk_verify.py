"""Traffic driver `bulk_verify`: one caller, back to back, sends batches
of signature checks through the provider the node uses
(`make_verifier(<config verifier>).verify_batch`), in this process, which
holds the chip.

Set-up: JAX-free workers, one per batch, sign the seeded corpus with
OpenSSL (the `cryptography` package, nothing of the program) while this
process warms the one bucket the traffic uses. The corpus follows
chip_smoke.build_corpus: distinct tx ids, signers from a seeded pool,
every `damage_every`-th lane damaged in R, S or the message in turn, a
few malformed lanes; OpenSSL verifies every lane and must agree with the
verdict the lane was built to have. Window: `verify_batch` on the
pre-signed batches in rotation for `--seconds`. After it: every lane of
every call against that verdict, and a seeded sample of lanes against the
plain reference (lib/ed25519_ref.py) in JAX-free worker processes.
"""

from __future__ import annotations

import json
import multiprocessing
import os
import sys
import time

import numpy as np

# Faults that break the timed path for the control and the rehearsal
# tests (harness.run_cell(fault=...)); the command never sets one.
FAULTS = ("control", "flip", "half", "stale")


def _rng(seed: int, *keys: int):
    return np.random.default_rng([seed % 2 ** 63, *keys])


def make_batch(seed: int, index: int, t: dict) -> dict:
    """Batch `index` of the corpus: packed keys, tx ids and signatures,
    the malformed lanes, and the verdict each lane was built to have,
    which OpenSSL confirms lane by lane."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey)

    n, every = t["batch_sigs"], t["damage_every"]
    pool = _rng(seed, 0).bytes(32 * t["signer_pool"])
    pool_keys = [Ed25519PrivateKey.from_private_bytes(pool[32 * i:32 * i + 32])
                 for i in range(t["signer_pool"])]
    pool_pubs = [k.public_key() for k in pool_keys]
    pool_pks = [p.public_bytes_raw() for p in pool_pubs]
    rng = _rng(seed, 1, index)
    msg_buf = rng.bytes(32 * n)
    msgs = [msg_buf[32 * i:32 * i + 32] for i in range(n)]
    if len(set(msgs)) != n:
        raise RuntimeError("corpus tx ids must be distinct")
    signer = [int(k) for k in rng.integers(0, t["signer_pool"], n)]
    sigs = [pool_keys[k].sign(m) for k, m in zip(signer, msgs)]
    pks = [pool_pks[k] for k in signer]
    truth = np.ones(n, bool)

    def flip(b: bytes, at: int) -> bytes:
        return b[:at] + bytes([b[at] ^ 0x10]) + b[at + 1:]

    for i in range(every - 1, n, every):
        kind = (i // every) % 3
        if kind == 0:
            sigs[i] = flip(sigs[i], 3)  # R
        elif kind == 1:
            sigs[i] = flip(sigs[i], 40)  # S
        else:
            msgs[i] = flip(msgs[i], 17)  # the tx id
        truth[i] = False
    malformed = {}
    for k, i in enumerate(rng.choice(n, t["malformed"], replace=False)):
        i = int(i)
        if k % 3 == 0:
            malformed[i] = (pks[i][:31], sigs[i])
        elif k % 3 == 1:
            malformed[i] = (pks[i], sigs[i] + b"\0")
        else:
            malformed[i] = (b"\xff" * 32, sigs[i])
        truth[i] = False
    openssl = np.array([
        _openssl_verify(None, *malformed[i], msgs[i]) if i in malformed
        else _openssl_verify(pool_pubs[signer[i]], pks[i], sigs[i], msgs[i])
        for i in range(n)])
    if not np.array_equal(openssl, truth):
        raise RuntimeError(
            f"corpus batch {index}: OpenSSL disagrees with the built verdict "
            f"on {int((openssl != truth).sum())} lanes")
    return {"pks": b"".join(pks), "msgs": b"".join(msgs),
            "sigs": b"".join(sigs), "malformed": malformed,
            "truth": np.packbits(truth).tobytes(), "n": n}


def _openssl_verify(pub, pk: bytes, sig: bytes, msg: bytes) -> bool:
    """OpenSSL's verdict on one lane; `pub` is `pk`'s key object, or None
    to load it from `pk`."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey)

    if len(pk) != 32 or len(sig) != 64:
        return False
    try:
        (pub or Ed25519PublicKey.from_public_bytes(pk)).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def _corpus_worker(args) -> dict:
    return make_batch(*args)


def jobs_of(batch: dict) -> list:
    from corda_tpu.crypto.provider import VerifyJob

    n, pks, msgs, sigs = batch["n"], batch["pks"], batch["msgs"], batch["sigs"]
    jobs = [VerifyJob(pks[32 * i:32 * i + 32], msgs[32 * i:32 * i + 32],
                      sigs[64 * i:64 * i + 64]) for i in range(n)]
    for i, (pk, sig) in batch["malformed"].items():
        jobs[i] = VerifyJob(pk, jobs[i].message, sig)
    return jobs


def _unpack(bits: bytes, n: int) -> np.ndarray:
    return np.unpackbits(np.frombuffer(bits, np.uint8))[:n].astype(bool)


def _faulty(verify, fault: str):
    """The timed path with one fault planted (control and tests)."""
    last = []

    def broken(jobs):
        if fault == "control":  # accepts every well-formed signature
            return np.array([len(j.pubkey) == 32 and len(j.sig) == 64
                             for j in jobs])
        if fault == "half":
            out = np.zeros(len(jobs), bool)
            out[:len(jobs) // 2] = verify(jobs[:len(jobs) // 2])
            return out
        if fault == "stale" and last:
            return last[0]
        out = verify(jobs)
        if fault == "flip":
            out = out.copy()
            out[0] = ~out[0]
        last[:] = [out]
        return out

    return broken


def run(cell) -> dict:
    from perfbench.lib import device as dev
    from perfbench.lib import kernel_ops
    from perfbench.lib import trace as tr

    t, cfg = cell.traffic, cell.config
    info = dev.require(cell.chips, allow_cpu=cell.allow_cpu)
    # The numerator of a future roofline share (PERF.md section 7).
    print(f"perfbench: verify kernel work per signature: "
          f"{kernel_ops.field_muls_per_sig()} field multiplications, "
          f"{kernel_ops.int32_mul_ops_per_sig()} int32 limb products, "
          f"{kernel_ops.bytes_in_per_sig()} bytes in", flush=True)
    # One JAX-free worker per batch signs while this process warms up.
    workers = multiprocessing.get_context("spawn").Pool(t["batches"])
    try:
        pending = workers.map_async(
            _corpus_worker, [(cell.seed, k, t) for k in range(t["batches"])])
        workers.close()
        from corda_tpu.crypto.provider import VerifyJob, make_verifier

        verifier = make_verifier(cfg["verifier"])
        # Warm the one bucket the traffic uses while the workers sign:
        # well-formed lanes only reach the device, so warm with as many.
        n_good = t["batch_sigs"] - sum(1 for k in range(t["malformed"])
                                       if k % 3 != 2)
        verifier.verify_batch([VerifyJob(bytes(32), bytes(32), bytes(64))]
                              * n_good)
        corpus = pending.get()
    finally:
        workers.terminate()
        workers.join()
    batches = [jobs_of(b) for b in corpus]
    truth = [_unpack(b["truth"], b["n"]) for b in corpus]
    verify = verifier.verify_batch
    if cell.fault:
        verify = _faulty(verify, cell.fault)
    verify(batches[0])  # host-side caches and the first readback

    trace_dir = os.path.join(cell.work_dir, "trace")
    if cell.trace:
        tr.start(trace_dir)
    outs, ends = [], []
    host0 = _host_counters()
    t_w0 = time.perf_counter()
    deadline = t_w0 + cell.seconds
    with _span(cell.trace, tr.WINDOW_SPAN):
        while True:
            with _span(cell.trace, "perfbench.verify_batch"):
                ok = verify(batches[len(outs) % len(batches)])
            outs.append(np.packbits(ok))
            ends.append(time.perf_counter())
            if ends[-1] >= deadline:
                break
    t_w1 = ends[-1]
    host = _host_delta(host0, _host_counters(), t_w0, ends)
    print(f"perfbench: host in the window: {json.dumps(host)}",
          file=sys.stderr, flush=True)
    red = None
    if cell.trace:
        import jax

        jax.profiler.stop_trace()
        red = tr.reduce(tr.collect(trace_dir, cpu_ops=cell.allow_cpu),
                        cfg.get("kernels"))
    info["memory_peak_bytes"] = dev.memory_peak_bytes(cell.chips)

    n = t["batch_sigs"]
    got = [_unpack(o, n) for o in outs]
    wrong = sum(int((g != truth[i % len(truth)]).sum())
                for i, g in enumerate(got))
    disagree = _reference_sample(cell, corpus, got)
    submitted = n * len(outs)
    return {
        "setup_s": t_w0 - cell.t0,
        "window_s": t_w1 - t_w0,
        "calls": len(outs),
        "lanes_submitted": submitted,
        "lanes_correct": submitted - wrong,
        "attempted": submitted,
        "failed": wrong,
        "device": info,
        "host": host,
        "trace": red,
        "checks": [
            {"name": "lanes_wrong", "value": wrong, "limit": 0},
            {"name": "reference_disagreements", "value": disagree,
             "limit": 0},
        ],
    }


def _host_counters() -> dict:
    """This process's CPU time and garbage collections, read at the
    window's edges to tell a slow host from a slow program."""
    import gc
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_s": ru.ru_utime + ru.ru_stime,
            "gc": [g["collections"] for g in gc.get_stats()]}


def _host_delta(a: dict, b: dict, t_w0: float, ends: list) -> dict:
    calls = np.diff([t_w0, *ends])
    median = float(np.median(calls))
    return {"process_cpu_share": (b["cpu_s"] - a["cpu_s"]) / (ends[-1] - t_w0),
            "gc": [y - x for x, y in zip(a["gc"], b["gc"])],
            "call_s_q": [float(q) for q in np.quantile(calls,
                                                      [0.1, 0.5, 0.9])],
            "call_s_max": float(calls.max()),
            "calls_over_1.5x_median": int((calls > 1.5 * median).sum())}


def _span(on: bool, name: str):
    """A host span in the trace (traced runs only)."""
    import contextlib

    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def _reference_sample(cell, corpus, got) -> int:
    """Seeded sample of `reference_sample` lanes of each batch (its first
    call in the window), verified by the plain reference in JAX-free
    workers. Returns the lanes where the program and the reference
    disagree."""
    from perfbench.lib import ed25519_ref

    t = cell.traffic
    jobs, where = [], []
    for k, b in enumerate(corpus[:len(got)]):
        idx = _rng(cell.seed, 2, k).choice(b["n"], t["reference_sample"],
                                          replace=False)
        for i in sorted(int(i) for i in idx):
            pk, sig = b["malformed"].get(
                i, (b["pks"][32 * i:32 * i + 32], b["sigs"][64 * i:64 * i + 64]))
            jobs.append((pk, b["msgs"][32 * i:32 * i + 32], sig))
            where.append((k, i))
    workers = max(1, min(8, (os.cpu_count() or 2) - 1))
    chunks = [jobs[w::workers] for w in range(workers)]
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        parts = pool.map(ed25519_ref.verify_many, chunks)
    verdict = [None] * len(jobs)
    for w, part in enumerate(parts):
        verdict[w::workers] = part
    return sum(1 for (k, i), ok in zip(where, verdict)
               if bool(got[k][i]) != ok)
