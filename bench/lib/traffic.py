"""Traffic generators: each reads one traffic file's parameters and a seed.

A traffic mix is a data file under ``bench/traffic/``; its ``kind`` names
one of the generators below.  The same seed gives the same inputs, and every
seed gives the same sizes: only the values and the order change.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def derive_seed(seed: int, *salt: int) -> int:
    """A 31-bit seed for one stream of a run, from the run's ``--seed``
    (any whole number, larger than 32 bits allowed) and a salt."""
    ss = np.random.SeedSequence([seed % (1 << 64), *salt])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


# ---------------------------------------------------------------------------
# curve_sweep: the paper's relational patch task, made on the device
# ---------------------------------------------------------------------------

def p_miss_lanes(traffic: dict) -> np.ndarray:
    lanes = traffic["p_miss"]
    return np.linspace(lanes["lo"], lanes["hi"], lanes["lanes"],
                       dtype=np.float64).astype(np.float32)


def make_patch_task(config: dict, traffic: dict):
    """``gen(key) -> (views, labels, val_views, val_labels)``, jitted.

    The paper §IV-B stand-in: patch ``i`` of an image shows pattern
    ``k_i`` of a bank fixed by the key's first split, plus Gaussian noise,
    and the label is ``sum_i k_i mod n_classes``; no single patch decides
    it.  Views are worker-leading ``(N, M, patch_dim)`` float32."""
    import jax
    import jax.numpy as jnp

    n = config["grid"] ** 2
    ph = config["hw"] // config["grid"]
    c = config["n_classes"]
    sigma = traffic["sigma"]

    def split(key, m):
        k_idx, k_noise = jax.random.split(key)
        ks = jax.random.randint(k_idx, (n, m), 0, c)
        return ks, k_noise

    def gen(bank_key, key):
        bank = jax.random.normal(bank_key, (c, ph * ph), jnp.float32)
        out = []
        for i, m in enumerate((traffic["n_train"], traffic["n_val"])):
            ks, k_noise = split(jax.random.fold_in(key, i), m)
            noise = jax.random.normal(k_noise, (n, m, ph * ph), jnp.float32)
            out.append(bank[ks] + sigma * noise)
            out.append(jnp.mod(jnp.sum(ks, axis=0), c).astype(jnp.int32))
        return tuple(out)

    return jax.jit(gen)


# ---------------------------------------------------------------------------
# serve_backlog: a standing queue of fixed-length prompts
# ---------------------------------------------------------------------------

def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """``n`` output lengths at the stratified quantiles ``(i + 1/2)/n`` of
    a lognormal, clipped to ``[lo, hi]``: the same multiset for every seed."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(
        np.int64)


def serve_backlog(traffic: dict, vocab_size: int, seed: int
                  ) -> List[Tuple[np.ndarray, int]]:
    """``[(prompt, max_new_tokens)]`` in arrival order, all due at once.

    Prompts are uniform random token ids of one length.  Output lengths
    come in blocks of ``out_len.block`` requests, each block the same
    stratified lognormal multiset in its own seeded order, so any stretch
    of the queue a window drains holds nearly the same work."""
    rng = np.random.default_rng(derive_seed(seed, 1))
    n = traffic["backlog"]
    out = traffic["out_len"]
    block = lognormal_lengths(out["block"], out["median"], out["sigma"],
                              out["min"], out["max"])
    lengths = np.concatenate([block[rng.permutation(block.size)]
                              for _ in range(-(-n // block.size))])[:n]
    prompts = rng.integers(0, vocab_size, (n, traffic["prompt_len"]),
                           dtype=np.int64).astype(np.int32)
    return [(prompts[i], int(lengths[i])) for i in range(n)]
