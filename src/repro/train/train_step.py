"""Train-step construction: loss + grad (+ microbatch accumulation) + update.

Microbatch gradient accumulation is a ``lax.scan`` over microbatches, which
lets the XLA latency-hiding scheduler overlap microbatch i+1's compute with
microbatch i's DP gradient all-reduce (reduce-scatter under ZeRO), the
standard comm/compute overlap structure.
"""

from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro.optim.compressed_allreduce import CompressedAllReduce


def make_train_step(loss_fn: Callable, optimizer, microbatches: int = 1,
                    compress_k: Optional[Union[float,
                                               CompressedAllReduce]] = None,
                    with_rng: bool = False,
                    donate: bool = False,
                    dp_axis: Optional[str] = None) -> Callable:
    """loss_fn(values, batch) -> (loss, metrics dict).

    Returns train_step(values, opt_state, batch, err) ->
        (values, opt_state, err, metrics)
    ``err`` is the error-feedback memory when compress_k is set (else None —
    pass jnp.zeros(()) sentinel-free via the same pytree each call).
    ``compress_k`` is either a kept-fraction float (sugar for
    ``CompressedAllReduce.topk(k)``) or a full
    :class:`repro.optim.compressed_allreduce.CompressedAllReduce` policy;
    compressed steps report the measured ``dp_payload_bits`` /
    ``dp_kept_elems`` in the metrics dict.  ``dp_axis`` names a mapped
    data-parallel axis (``shard_map`` or ``vmap(axis_name=...)``) to
    all-reduce the compressed gradients over — the reduced gradient is the
    rank **mean** and the payload counters are totals across ranks.

    ``with_rng=True`` switches the contract to a stochastic forward (e.g. the
    channel-in-the-loop OCS aggregation): ``loss_fn(values, batch, rng)``
    and ``train_step(values, opt_state, batch, rng[, err])``.  ``rng`` is
    any pytree of traced arrays — a PRNG key, or a ``(key,
    repro.protocol.Protocol)`` channel-state tuple as the curve engine
    passes — under microbatching each microbatch receives ``fold_in``-style
    decorrelated keys via the scan index (key-typed leaves are folded;
    float leaves like the protocol's ``p_miss`` pass through untouched).

    ``donate=True`` returns the step pre-jitted with the train-state carries
    (``values``, ``opt_state``) donated, so params/optimizer moments are
    updated in place instead of double-buffering across dispatches.  The
    caller's input buffers are consumed: rebind them from the step's outputs
    (the usual ``values, opt_state, ... = step(values, opt_state, ...)``
    loop) and copy any initial state that must survive the first call.
    """
    if with_rng:
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    else:
        grad_fn = jax.value_and_grad(
            lambda values, batch, rng: loss_fn(values, batch), has_aux=True)

    def compute_grads(values, batch, rng):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(values, batch, rng)
            return grads, loss, metrics

        def split(x):
            b = x.shape[0]
            assert b % microbatches == 0, (b, microbatches)
            return x.reshape((microbatches, b // microbatches) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def is_key_like(r):
            dtype = jnp.asarray(r).dtype
            if jnp.issubdtype(dtype, jax.dtypes.prng_key):
                return True                   # new-style typed PRNG keys
            return jnp.issubdtype(dtype, jnp.integer)   # legacy uint32 keys

        def fold_rng(i):
            if not with_rng:
                return rng
            # decorrelate microbatches: fold the scan index into every
            # key-typed leaf (legacy uint32 or typed PRNG keys); float
            # leaves (p_miss) pass through untouched.
            return jax.tree.map(
                lambda r: jax.random.fold_in(r, i) if is_key_like(r) else r,
                rng)

        def body(carry, im):
            i, mb = im
            acc, loss_acc = carry
            (loss, metrics), grads = grad_fn(values, mb, fold_rng(i))
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), metrics

        zeros = jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32),
                             values)
        (acc, loss_sum), metrics = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)),
            (jnp.arange(microbatches), micro))
        grads = jax.tree.map(lambda g: g / microbatches, acc)
        last_metrics = jax.tree.map(lambda m: m[-1], metrics)
        return grads, loss_sum / microbatches, last_metrics

    def apply_update(values, opt_state, grads, loss, metrics):
        values, opt_state, stats = optimizer.update(grads, opt_state, values)
        metrics = dict(metrics)
        metrics.update(stats)
        metrics["loss_mean"] = loss
        return values, opt_state, metrics

    def finalize(step):
        # the first two positions are the train-state carries in every
        # contract variant: updated in place when donated
        return jax.jit(step, donate_argnums=(0, 1)) if donate else step

    if compress_k is not None:
        compress = (compress_k if isinstance(compress_k, CompressedAllReduce)
                    else CompressedAllReduce.topk(float(compress_k)))

        def reduce_grads(grads, err, metrics):
            grads, err, acct = compress.reduce(grads, err, axis_name=dp_axis)
            if dp_axis is not None:
                n_ranks = jax.lax.psum(jnp.int32(1), dp_axis)
                grads = jax.tree.map(lambda g: g / n_ranks, grads)
            metrics = dict(metrics)
            metrics["dp_payload_bits"] = acct.payload_bits
            metrics["dp_kept_elems"] = acct.kept_elems
            return grads, err, metrics

    if compress_k is not None and with_rng:
        def train_step(values, opt_state, batch, rng, err):
            grads, loss, metrics = compute_grads(values, batch, rng)
            grads, err, metrics = reduce_grads(grads, err, metrics)
            values, opt_state, metrics = apply_update(values, opt_state,
                                                      grads, loss, metrics)
            return values, opt_state, err, metrics
        return finalize(train_step)

    if compress_k is not None:
        def train_step(values, opt_state, batch, err):
            grads, loss, metrics = compute_grads(values, batch, None)
            grads, err, metrics = reduce_grads(grads, err, metrics)
            values, opt_state, metrics = apply_update(values, opt_state,
                                                      grads, loss, metrics)
            return values, opt_state, err, metrics
        return finalize(train_step)

    if with_rng:
        def train_step(values, opt_state, batch, rng):
            grads, loss, metrics = compute_grads(values, batch, rng)
            return apply_update(values, opt_state, grads, loss, metrics)
        return finalize(train_step)

    def train_step(values, opt_state, batch):
        grads, loss, metrics = compute_grads(values, batch, None)
        return apply_update(values, opt_state, grads, loss, metrics)

    return finalize(train_step)
