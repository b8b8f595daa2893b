"""Production mesh construction + per-cell sharding rules.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — the ``pod``
axis extends data parallelism across the ICI-connected superpod (DCN in
practice; the dry-run proves the program shards over it).
"""

from __future__ import annotations

from repro.parallel import sharding as sh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return sh.make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small host-device mesh for subprocess distribution tests."""
    return sh.make_mesh((data, model), ("data", "model"))


def rules_for(shape_name: str, global_batch: int, mesh) -> dict:
    """Per-cell logical-axis rule table.

    long-context decode cells cannot shard their batch (B=1); the KV cache
    sequence is sharded over the data(+pod) axes instead (flash-decode-style
    sequence parallelism).  Other cells shard batch over (pod, data) and
    keep kv_seq local.
    """
    rules = dict(sh.DEFAULT_RULES)
    sizes = sh.mesh_axis_sizes(mesh)
    batch_ways = sizes.get("pod", 1) * sizes.get("data", 1)
    if global_batch % batch_ways != 0 or shape_name == "long_500k":
        rules["batch"] = None
        rules["kv_seq"] = ("pod", "data") if "pod" in sizes else ("data",)
    else:
        rules["kv_seq"] = None
    return rules
