"""The benchmark finds the verify kernels in the device trace by module
name: each configuration's "kernels" regexes (perfbench/configs/*.json)
must match the jit module name of the function the verify path
dispatches, or the kernel's time reads zero after a rename."""

import glob
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from corda_tpu.ops import ed25519_jax, ed25519_pallas, sha512_jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(ROOT, "perfbench", "configs",
                                        "*.json")))

# What ed25519_jax.verify_arrays_hashed dispatches on a TPU, in order.
DISPATCHED = (sha512_jax.challenge_words, ed25519_pallas.verify_arrays_pallas)


def _jit_module_name(fn) -> str:
    return f"jit_{fn.__name__}"


def test_jit_module_name_is_jit_and_the_function_name():
    w = jax.ShapeDtypeStruct((8, 64), jnp.uint32)
    text = sha512_jax.challenge_words.lower(w, w, w).as_text()
    assert text.startswith(
        f"module @{_jit_module_name(sha512_jax.challenge_words)} ")


def test_the_hashed_path_dispatches_these_kernels(monkeypatch):
    called = []
    words = np.zeros((8, ed25519_pallas.LANES_PER_BLOCK), np.uint32)

    def stand_in(name, out):
        def run(*args):
            called.append(name)
            return out
        return run

    monkeypatch.setitem(ed25519_jax._PALLAS_STATE, "available", True)
    monkeypatch.setitem(ed25519_jax._PALLAS_STATE, "last_backend", None)
    for fn, out in zip(DISPATCHED, (words, np.ones(words.shape[1], bool))):
        monkeypatch.setattr(sys.modules[fn.__module__], fn.__name__,
                            stand_in(fn.__name__, out))
    ed25519_jax.verify_arrays_hashed(words, words, words, words)
    assert called == [fn.__name__ for fn in DISPATCHED]


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_config_kernel_regexes_match_the_dispatched_modules(path):
    with open(path) as f:
        kernels = json.load(f).get("kernels", {})
    assert kernels, f"{path} names no kernels"
    names = [_jit_module_name(fn) for fn in DISPATCHED]
    for label, pattern in kernels.items():
        hits = [n for n in names if re.search(pattern, n)]
        assert len(hits) == 1, (label, pattern, names)
    # Every dispatched kernel is counted by exactly one label.
    for n in names:
        assert sum(bool(re.search(p, n)) for p in kernels.values()) == 1, n
