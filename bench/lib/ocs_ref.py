"""Plain reference of the OCS channel (arXiv:2209.01682 §III, Alg. 1,
with imperfect carrier sensing), shared by the configurations' references.

It imports nothing of the system under test.  Codes are the top ``bits``
bits of the IEEE-754 order-embedding of a float (paper Eq. 7, footnote 2);
workers contend on ``[code | complement of worker index]`` bit by bit, most
significant first; a sensing worker leaves only when someone transmitted
and it heard it, which it fails to do with probability ``p_miss``;
``max_rounds`` rounds re-contend the survivors and the lowest surviving
index wins.  The sensing draws of round ``r``, sub-slot ``d`` are
``bernoulli(fold_in(fold_in(key, r), d), 1 - p_miss, (N, K))`` over the
whole frame: the stream the channel draws.  Where only some columns of a
frame are compared, :func:`heard_columns` computes just their draws: the
Threefry-2x32 hash of each element's flat index (JAX's partitionable
``random_bits``), turned into a uniform and compared with the hear
probability as ``jax.random.bernoulli`` does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def codes(x, bits: int):
    """Top ``bits`` bits of the float32 order-embedding code."""
    b = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    sign = jnp.uint32(1 << 31)
    full = jnp.where((b & sign) != 0, ~b, b | sign)
    return full >> jnp.uint32(32 - bits)


def dequant(code, bits: int, width: int):
    """Representative float of a code in a ``width``-bit float format
    (32: float32, 16: bfloat16): the code's bits on top, the rest zero."""
    if width == 32:
        full = code.astype(jnp.uint32) << jnp.uint32(32 - bits)
        sign = jnp.uint32(1 << 31)
        b = jnp.where((full & sign) == 0, ~full, full & ~sign)
        out = jax.lax.bitcast_convert_type(b, F32)
    else:
        full = (code.astype(jnp.uint32) << jnp.uint32(16 - bits)) \
            & jnp.uint32(0xFFFF)
        sign = jnp.uint32(1 << 15)
        b = jnp.where((full & sign) == 0, ~full & jnp.uint32(0xFFFF),
                      full & ~sign)
        out = jax.lax.bitcast_convert_type(b.astype(jnp.uint16),
                                           jnp.bfloat16).astype(F32)
    return jnp.where(jnp.isnan(out), -jnp.inf, out)


_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry2x32(key, x0, x1):
    """Threefry-2x32 (20 rounds) of the counter pairs ``(x0, x1)`` under
    the raw uint32 key pair ``key``."""
    k1, k2 = key[0], key[1]
    ks = (k1, k2, k1 ^ k2 ^ jnp.uint32(0x1BD11BDA))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << jnp.uint32(r)) | (x1 >> jnp.uint32(32 - r))
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def heard_columns(key, p_keep, n: int, frame_k: int, col0, c: int):
    """``bernoulli(key, p_keep, (n, frame_k))[:, col0:col0 + c]`` without
    drawing the other columns."""
    dt = jnp.dtype(p_keep.dtype)
    flat = (jnp.arange(n, dtype=jnp.uint32)[:, None] * jnp.uint32(frame_k)
            + jnp.asarray(col0, jnp.uint32)
            + jnp.arange(c, dtype=jnp.uint32)[None])
    b0, b1 = threefry2x32(key, jnp.zeros_like(flat), flat)
    nmant = jnp.finfo(dt).nmant
    if dt == jnp.float32:
        one, utype, rng_bits = 0x3F800000, jnp.uint32, 32
        bits = b0 ^ b1
    else:                        # 16-bit floats take 8 random bits
        one, utype, rng_bits = 0x3F80, jnp.uint16, 8
        bits = ((b0 ^ b1) & jnp.uint32(0xFF)).astype(jnp.uint16)
    fbits = (bits >> utype(rng_bits - nmant)) | utype(one)
    u = jax.lax.bitcast_convert_type(fbits, dt) - jnp.asarray(1.0, dt)
    return jnp.maximum(jnp.asarray(0.0, dt), u) < p_keep


def id_bits(n: int) -> int:
    return max(1, math.ceil(math.log2(max(n, 2))))


def contend(code, key, p_keep, bits: int, max_rounds: int,
            frame_k: int = None, col0=0):
    """Winner per sub-frame of a noisy OCS contention.

    ``code`` is (N, C): the compared sub-frames, columns ``col0 ..
    col0 + C`` of a frame of ``frame_k`` sub-frames (default C) whose
    sensing draws are made whole, as the channel makes them."""
    n, c = code.shape
    frame_k = c if frame_k is None else frame_k
    idb = id_bits(n)
    ids = jnp.uint32((1 << idb) - 1) - jnp.arange(n, dtype=jnp.uint32)
    word = (code.astype(jnp.uint32) << jnp.uint32(idb)) | ids[:, None]
    total = bits + idb
    alive = jnp.ones((n, c), bool)
    for r in range(max_rounds):
        kr = jax.random.fold_in(key, r)
        for d in range(total):
            bit = (word >> jnp.uint32(total - 1 - d)) & jnp.uint32(1)
            tx = alive & (bit == 1)
            any_tx = jnp.any(tx, axis=0, keepdims=True)
            kd = jax.random.fold_in(kr, d)
            if frame_k == c:
                heard = jax.random.bernoulli(kd, p_keep, (n, c))
            else:
                heard = heard_columns(kd, jnp.asarray(p_keep), n, frame_k,
                                      col0, c)
            alive = alive & (tx | ~(any_tx & heard))
    return jnp.argmax(alive, axis=0)


@jax.custom_vjp
def route(h, onehot, pooled):
    """``pooled`` forward; the cotangent goes to the workers marked in
    ``onehot`` (the winner) and nowhere else (paper Eq. 5-6)."""
    return pooled


def route_fwd(h, onehot, pooled):
    return pooled, onehot


def route_bwd(onehot, g):
    return g[None] * onehot, None, None


route.defvjp(route_fwd, route_bwd)
