"""Pipeline parallelism: GPipe-style microbatched schedule over a ``stage``
mesh axis, realized with ``shard_map`` + ``ppermute``.

Off by default in the 40-cell sweep (the assigned production mesh has no
stage axis); provided — and covered by ``tests/test_pipeline.py`` on a forced
multi-device host — as the depth-parallel option for 1000+-node deployments
where (pod, data, model) alone leaves layers too deep for one stage's HBM.

Schedule: ``n_micro + n_stages - 1`` ticks; at tick t, stage s processes
microbatch ``t - s`` (bubble fraction ``(S-1)/(M+S-1)``).  Activations hop
stages via ``collective_permute``; autodiff through the whole schedule gives
the matching 1F1B-equivalent backward (bubbles included) for training.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as sh


def gpipe(stage_fn: Callable, mesh, axis: str = "stage"):
    """Build a pipelined apply: (stage_params, x_micro) -> y_micro.

    stage_fn(params, x) -> y is ONE stage's computation (same shape in/out).
    stage_params: leaves with leading stage axis, sharded over `axis`.
    x_micro: (n_micro, mb, ...) — microbatched input, replicated.
    """
    n_stages = mesh.shape[axis]

    def pipelined(stage_params, x_micro):
        n_micro = x_micro.shape[0]
        steps = n_micro + n_stages - 1

        def body(carry, t):
            # carry: (incoming activation buffer (mb, ...), outputs (n_micro, mb, ...))
            acts, outs = carry
            s = jax.lax.axis_index(axis)
            # stage 0 ingests microbatch t (when available); others use the
            # activation that arrived from stage s-1 last tick
            feed = jnp.where(t < n_micro, t, 0)
            inp = jnp.where(s == 0, x_micro[feed], acts)
            out = stage_fn(stage_params, inp)
            # last stage commits microbatch (t - (n_stages-1)) when valid
            mb_idx = t - (n_stages - 1)
            valid = jnp.logical_and(s == n_stages - 1, mb_idx >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.maximum(mb_idx, 0)].set(out),
                lambda o: o,
                outs)
            # shift activations one stage forward
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            acts = jax.lax.ppermute(out, axis, perm)
            return (acts, outs), None

        acts0 = jnp.zeros_like(x_micro[0])
        outs0 = jnp.zeros_like(x_micro)
        (_, outs), _ = jax.lax.scan(body, (acts0, outs0),
                                    jnp.arange(steps))
        # only the last stage holds the committed outputs; broadcast them
        # so the replicated out_spec is well-defined on every shard
        return jax.lax.psum(outs, axis)

    # stage params sharded over `axis` (leading dim == n_stages, local slice
    # squeezed inside), activations replicated
    def stage_local(params, x_micro):
        params_local = jax.tree.map(lambda p: p[0], params)
        return pipelined(params_local, x_micro)

    return sh.shard_map(stage_local, mesh, in_specs=(P(axis), P()),
                        out_specs=P())


def sequential_reference(stage_fn: Callable, stage_params, x_micro):
    """Oracle: run the stages back-to-back without pipelining."""
    def one_micro(x):
        n_stages = jax.tree.leaves(stage_params)[0].shape[0]
        for s in range(n_stages):
            p = jax.tree.map(lambda q: q[s], stage_params)
            x = stage_fn(p, x)
        return x
    return jax.vmap(one_micro)(x_micro)
