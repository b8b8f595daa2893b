"""Shared 1-D `shard_map` machinery for batched-lane axes.

Both grid runners shard one leading "lane" axis over the host's local
devices: ``repro.sim.sweep`` shards the *scenario* axis, and
``repro.sim.train_curves`` shards the *p_miss lane* axis of the fused curve
engine.  The lane meshes (``Auto`` axes, built by
``repro.parallel.sharding.make_mesh``) and the ``shard_map`` wrappers live
here so every runner gets the identical placement semantics — and the
identical bit-for-bit-vs-vmap property that ``tests/test_sweep.py`` and
``tests/test_train_curves.py`` assert with forced host devices.

Sharding only changes placement, never results: callers pad the lane axis up
to a device-count multiple (:func:`pad_lanes`) and drop the padding rows
after the dispatch.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import PartitionSpec as P  # noqa: F401  (re-exported)

from repro.parallel import sharding as sh


@functools.lru_cache(maxsize=None)
def mesh_1d(n_devices: int, axis: str = "s"):
    """1-D device mesh for a lane axis (cached: jit keys on identity)."""
    return sh.make_mesh((n_devices,), (axis,))


@functools.lru_cache(maxsize=None)
def mesh_2d(n_s: int, n_d: int, axes=("s", "d")):
    """2-D device mesh: ``axes[0]`` lanes x ``axes[1]`` data-parallel ranks
    (cached: jit keys on mesh identity)."""
    return sh.make_mesh((n_s, n_d), tuple(axes))


def shard_1d(fn, n_devices: int, in_specs, out_specs, axis: str = "s"):
    """Wrap ``fn`` in shard_map over a 1-D ``axis`` mesh.

    ``in_specs``/``out_specs`` follow the shard_map contract (pytree
    prefixes of the arguments/results); pass ``P(axis)`` for lane-leading
    arguments and ``P()`` for replicated ones.
    """
    return sh.shard_map(fn, mesh_1d(n_devices, axis), in_specs, out_specs)


def shard_2d(fn, n_s: int, n_d: int, in_specs, out_specs, axes=("s", "d")):
    """Wrap ``fn`` in shard_map over a 2-D (lanes x DP ranks) mesh.

    The DP axis name (``axes[1]``) is visible to collectives inside ``fn``
    (``lax.all_gather``/``lax.psum``), which is how the 2-D curve engine
    all-reduces compressed gradients inside the fused scan.
    """
    return sh.shard_map(fn, mesh_2d(n_s, n_d, tuple(axes)), in_specs,
                        out_specs)


def dp_mesh_shape(n_devices, n_lanes: int, dp_shards: int):
    """Split ``n_devices`` into (lane-mesh size, DP-mesh size).

    The DP axis is either placed *entirely* on the mesh (``n_d ==
    dp_shards``) or *entirely* vmapped on-device (``n_d == 1``) — never a
    partial block — so the all_gather stacking order is trivially identical
    across topologies and the bit-for-bit parity property holds.  Lanes take
    whatever devices remain.
    """
    if n_devices is None:
        n_devices = jax.local_device_count()
    n_devices = int(n_devices)
    n_d = dp_shards if 1 < dp_shards <= n_devices else 1
    n_s = max(1, min(n_devices // n_d, n_lanes))
    return n_s, n_d


def lane_devices(n_devices, n_lanes: int) -> int:
    """Devices actually used for ``n_lanes`` lanes (``None`` = all local)."""
    if n_devices is None:
        n_devices = jax.local_device_count()
    return max(1, min(int(n_devices), n_lanes))


def pad_lanes(x: np.ndarray, n_devices: int) -> np.ndarray:
    """Pad axis 0 up to a device-count multiple by repeating row 0.

    Padding rows ride along as inert extra lanes (lane computations are
    independent) and are sliced off by the caller after the dispatch.
    """
    pad = (-x.shape[0]) % n_devices
    if not pad:
        return x
    return np.concatenate([x, np.repeat(x[:1], pad, axis=0)], axis=0)
