"""VMEM-resident Pallas kernel for batched Ed25519 verification.

The plain-XLA verify graph (corda_tpu/ops/ed25519_jax.py) decomposes into
tens of thousands of small elementwise ops on (N,) lanes; at notary batch
sizes XLA's fusion boundaries leave it kernel-launch/HBM bound, an order of
magnitude off VPU peak. This kernel runs the SAME field math (it composes
ed25519_jax's shape-polymorphic pieces: decompress_neg_a, the windowed
Strauss loop, encode_compare) inside one `pl.pallas_call`: each grid step
loads a (8, 128)-lane block's words into VMEM, and every intermediate limb
array lives in VMEM/VREGs for the whole verification — no HBM round trips
between field ops.

Mosaic-specific shapes of the shared code:
  * the 64-window loop is a fori_loop reading per-window nibbles from VMEM
    scratch refs (lax.scan lowers to dynamic_slice, which Mosaic lacks);
  * the field convolution uses the streaming "rows" lowering (fe.CONV_MODE);
  * the B table arrives as a kernel input (Pallas kernels cannot close over
    array constants).

Block anatomy (per 1024-lane block):
  * inputs: 4 x (8, 8, 128) uint32 word arrays (A, R, S, h) = 128 KiB
  * the -A window table: 16 entries x 4 coords x (20, 8, 128) int32 ~ 5 MiB
  * nibble scratch: 2 x (64, 8, 128) int32 = 512 KiB
  * output: (8, 128) int32 accept mask

Padding blocks cost no field work. The live count (the first `live` lanes
carry signatures) reaches the kernel by scalar prefetch; a grid step past
the last live block writes reject and re-reads no input (its index_map
stays on the last live block). A 100,000-signature call in the 131,072
bucket runs 98 of 128 blocks; a full bucket runs them all.

Semantics are bit-identical to the oracle and to verify_arrays. The CPU
tests compile this kernel for a described v5e (tests/test_chip_compile.py);
chip_smoke.py runs it on the chip and checks every lane against the host
tier and a sample against the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ed25519_jax as ej
from . import fe25519 as fe

__all__ = ["verify_arrays_pallas", "LANES_PER_BLOCK"]

SUBLANES = 8
LANES = 128
LANES_PER_BLOCK = SUBLANES * LANES  # 1024
_BATCH = (SUBLANES, LANES)


def _kernel(live_ref, a_ref, r_ref, s_ref, h_ref, btab_ref, ok_ref,
            snib_ref, hnib_ref):
    # The block holds a live lane iff its first lane is one of the first
    # `live`.
    live = pl.program_id(0) * LANES_PER_BLOCK < live_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():  # padding only: reject, no field work
        ok_ref[0] = jnp.zeros(_BATCH, jnp.int32)

    @pl.when(live)
    def _():
        _verify_block(a_ref, r_ref, s_ref, h_ref, btab_ref, ok_ref,
                      snib_ref, hnib_ref)


def _verify_block(a_ref, r_ref, s_ref, h_ref, btab_ref, ok_ref,
                  snib_ref, hnib_ref):
    # Trace-time switch: inside the kernel every value lives in VMEM, so the
    # streaming "rows" convolution is strictly better than the gather form
    # (Mosaic has no XLA-simplifier pathology on the unrolled adds).
    prev, fe.CONV_MODE = fe.CONV_MODE, "rows"
    try:
        y, a_sign = ej._unpack_limbs(a_ref[0])
        r_limbs, r_sign = ej._unpack_limbs(r_ref[0])
        snib_ref[:] = ej._nibbles_msb(s_ref[0])
        hnib_ref[:] = ej._nibbles_msb(h_ref[0])
        btab = btab_ref  # SMEM ref; _b_entry reads scalars from it directly

        point_ok, neg_a = ej.decompress_neg_a(y, a_sign)
        a_table = ej._build_a_table(neg_a)
        one = fe.fill_limbs(1, _BATCH)
        zero = fe.fill_limbs(0, _BATCH)

        def window(t, acc):
            for _ in range(4):
                acc = ej._ext_dbl(acc)
            s_nib = snib_ref[pl.ds(t, 1)][0]  # dynamic VMEM load, not slice
            h_nib = hnib_ref[pl.ds(t, 1)][0]
            acc = ej._ext_add(acc, ej._b_entry(s_nib, one, btab))
            acc = ej._ext_add(acc, ej._masked_sum_entry(a_table, h_nib))
            return acc

        rpoint = jax.lax.fori_loop(0, 64, window, (zero, one, one, zero))
        ok = ej.encode_compare(rpoint, r_limbs, r_sign, point_ok)
        ok_ref[0] = ok.astype(jnp.int32)
    finally:
        fe.CONV_MODE = prev


@functools.partial(jax.jit, static_argnames=("interpret",))
def verify_arrays_pallas(a_words, r_words, s_words, h_words, live=None,
                         interpret: bool = False):
    """Same contract as ed25519_jax.verify_arrays — (8, N) uint32 words in,
    bool (N,) out — executed as one VMEM-resident kernel per 1024-lane block.
    N must be a multiple of 1024 (pick_bucket sizes >= 1024 all are).

    `live` (an int32 scalar operand, None for all N) counts the live lanes,
    the first `live` of the batch. Blocks past the last live one read
    reject and do no field work; one executable serves every count.
    """
    n = a_words.shape[1]
    if n % LANES_PER_BLOCK:
        raise ValueError(f"batch {n} not a multiple of {LANES_PER_BLOCK}")
    nb = n // LANES_PER_BLOCK
    live = jnp.asarray(n if live is None else live, jnp.int32).reshape(1)

    def shape_in(w):  # (8, N) -> (nb, 8, 8, 128), blocks major
        return w.reshape(8, nb, SUBLANES, LANES).transpose(1, 0, 2, 3)

    def in_block(i, live_ref):  # a dead step keeps the last live block
        last = jnp.maximum(live_ref[0] - 1, 0) // LANES_PER_BLOCK
        return jnp.minimum(i, last), 0, 0, 0

    ins = [shape_in(w) for w in (a_words, r_words, s_words, h_words)]
    in_spec = pl.BlockSpec((1, 8, SUBLANES, LANES), in_block,
                           memory_space=pltpu.VMEM)
    btab_spec = pl.BlockSpec((3, 16, 20), lambda i, live_ref: (0, 0, 0),
                             memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[in_spec] * 4 + [btab_spec],
            out_specs=pl.BlockSpec((1, SUBLANES, LANES),
                                   lambda i, live_ref: (i, 0, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((64, SUBLANES, LANES), jnp.int32),
                pltpu.VMEM((64, SUBLANES, LANES), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nb, SUBLANES, LANES), jnp.int32),
        interpret=interpret,
    )(live, *ins, jnp.asarray(ej._B_TABLE))
    return out.reshape(n).astype(bool)
