"""Top-level model API: init / loss / prefill / decode_step / input_specs.

Pure functions over a ``ModelConfig``; ``build(cfg)`` binds them into a
lightweight namespace.  All functions operate on the *value* tree (plain
arrays); ``init`` returns the Tagged tree carrying logical sharding axes.

Batch conventions
-----------------
train (token frontend)   {"tokens": (B,S) i32, "targets": (B,S) i32}
train (patch/audio)      {"feats": (B,S,Df) bf16, "targets": (B,S) i32}
                         enc-dec adds {"tokens": (B,S_dec) i32} and targets
                         align with decoder tokens.
prefill                  same as train minus targets -> (last_logits, cache)
decode                   (token (B,1) i32, positions (B,) i32, cache)

Layers: a config's leading dense layers (``first_dense_layers``) are a
scanned stack of their own, ``"lead"``, ahead of the main stack
``"blocks"`` in the parameter tree and, for such a config, in the decode
cache (``{"lead": ..., "blocks": ...}``; otherwise the cache is the main
stack's alone).
"""

from __future__ import annotations

import dataclasses
import functools
import operator
import types
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import fusion, layers, transformer
from repro.parallel.sharding import Tagged, constrain, split_tree

WHISPER_DECODER_LEN = 448   # whisper's real positional cap for train targets
_LEAD_KEY = 0x1EAD          # folded into the lead stack's keys


def _stacks(cfg):
    """The decoder's scanned stacks in order: ``(key, plan, n_periods)``."""
    lead = ((("lead", cfg.lead_plan(), cfg.first_dense_layers),)
            if cfg.first_dense_layers else ())
    return lead + (("blocks", cfg.layer_plan(), cfg.n_periods),)


def _pack(cfg, caches: dict) -> dict:
    """The decode cache from each stack's cache, by stack key."""
    return caches if cfg.first_dense_layers else caches["blocks"]


def _unpack(cfg, cache: dict) -> dict:
    return dict(cache) if cfg.first_dense_layers else {"blocks": cache}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(cfg: ModelConfig, rng: jax.Array) -> dict:
    r = layers.rsplit(rng, 6)
    p: Dict[str, Any] = {
        "embed": layers.embed_init(cfg, r[0]),
        "blocks": transformer.stack_init(cfg, r[1], cfg.layer_plan(),
                                         cfg.n_periods,
                                         cross=cfg.encoder_decoder),
        "final_norm": layers.norm_init(cfg, r[2]),
    }
    if cfg.first_dense_layers:
        p["lead"] = transformer.stack_init(
            cfg, jax.random.fold_in(r[1], _LEAD_KEY), cfg.lead_plan(),
            cfg.first_dense_layers)
    p.update(layers.unembed_init(cfg, r[3]))
    if cfg.encoder_decoder:
        enc_plan = cfg.encoder_layer_plan()
        assert cfg.n_encoder_layers % len(enc_plan) == 0
        p["encoder"] = transformer.stack_init(
            cfg, r[4], enc_plan, cfg.n_encoder_layers // len(enc_plan))
        p["encoder_norm"] = layers.norm_init(cfg, r[5])
    return p


# ---------------------------------------------------------------------------
# embedding helpers
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, v, batch) -> Tuple[jax.Array, jax.Array]:
    """Returns (x (B,S,d), positions (B,S))."""
    if cfg.encoder_decoder or cfg.frontend == "token":
        key = "tokens"
        tokens = batch[key]
        x = layers.embed_tokens(cfg, v["embed"], tokens)
        b, s = tokens.shape
    else:
        feats = batch["feats"]
        x = layers.embed_frontend(cfg, v["embed"], feats)
        b, s = feats.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.use_abs_pos:
        pe = layers.sinusoidal_positions(s, cfg.d_model).astype(x.dtype)
        x = x + pe[None]
    return x, positions


def _encode(cfg, v, feats) -> jax.Array:
    x = layers.embed_frontend(cfg, v["embed"], feats)
    b, s = feats.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.use_abs_pos:
        x = x + layers.sinusoidal_positions(s, cfg.d_model).astype(x.dtype)[None]
    enc_plan = cfg.encoder_layer_plan()
    x, _ = transformer.stack_full(cfg, v["encoder"], x, positions, enc_plan)
    return layers.norm_apply(cfg, v["encoder_norm"], x)


# ---------------------------------------------------------------------------
# training loss
# ---------------------------------------------------------------------------

def _xent(cfg, v, x: jax.Array, targets: jax.Array) -> jax.Array:
    """Chunked cross-entropy over the (vocab-sharded) unembedding.

    Chunking the sequence bounds the live fp32 logits to (B, chunk, V)
    instead of (B, S, V) — a large activation-memory saving at equal FLOPs.
    """
    b, s, d = x.shape
    chunk = getattr(cfg, "loss_chunk", 512)
    if s % chunk != 0:
        chunk = s
    n_chunks = s // chunk
    xc = x.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)
    tc = targets.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    def one(carry, xt):
        xch, tch = xt
        logits = layers.unembed_apply(cfg, {k: v[k] for k in ("head",)
                                            if k in v}, v["embed"], xch)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tch[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        return carry + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (xc, tc))
    return total / (b * s)


def forward(cfg, v, batch) -> Tuple[jax.Array, jax.Array]:
    """Full forward to final hidden states. Returns (x, aux_loss)."""
    enc_out = None
    if cfg.encoder_decoder:
        enc_out = _encode(cfg, v, batch["feats"])
    x, positions = _embed_inputs(cfg, v, batch)
    x = constrain(x, ("batch", "seq", "embed"))
    auxes = []
    for key, plan, _ in _stacks(cfg):
        x, a = transformer.stack_full(cfg, v[key], x, positions, plan,
                                      enc_out=enc_out)
        auxes.append(a)
    x = layers.norm_apply(cfg, v["final_norm"], x)
    return x, functools.reduce(operator.add, auxes)


def logits_fn(cfg, v, batch) -> jax.Array:
    x, _ = forward(cfg, v, batch)
    return layers.unembed_apply(cfg, {k: v[k] for k in ("head",) if k in v},
                                v["embed"], x)


def loss_fn(cfg, v, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x, aux = forward(cfg, v, batch)
    nll = _xent(cfg, v, x, batch["targets"])
    loss = nll + cfg.router_aux_weight * aux
    return loss, {"nll": nll, "aux": aux, "loss": loss}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def prefill(cfg, v, batch, max_seq: Optional[int] = None
            ) -> Tuple[jax.Array, dict]:
    """Returns (last-position logits (B,V), decode cache)."""
    enc_out = None
    if cfg.encoder_decoder:
        enc_out = _encode(cfg, v, batch["feats"])
    x, positions = _embed_inputs(cfg, v, batch)
    max_seq = max_seq or x.shape[1]
    caches = {}
    for key, plan, _ in _stacks(cfg):
        x, caches[key], _ = transformer.stack_prefill(
            cfg, v[key], x, positions, plan, max_seq, enc_out=enc_out)
    x = layers.norm_apply(cfg, v["final_norm"], x)
    last = x[:, -1:]
    logits = layers.unembed_apply(cfg, {k: v[k] for k in ("head",) if k in v},
                                  v["embed"], last)[:, 0]
    return logits, _pack(cfg, caches)


def decode_step(cfg, v, token: jax.Array, positions: jax.Array, cache: dict
                ) -> Tuple[jax.Array, dict]:
    """token: (B,1) i32; positions: (B,) current write index."""
    x = layers.embed_tokens(cfg, v["embed"], token)
    if cfg.use_abs_pos:
        # gather the sinusoidal row for each position
        pe = layers.sinusoidal_positions(
            int(_max_pos(cfg, cache)), cfg.d_model).astype(x.dtype)
        x = x + pe[positions][:, None]
    caches = _unpack(cfg, cache)
    for key, plan, _ in _stacks(cfg):
        x, caches[key], _ = transformer.stack_step(cfg, v[key], x, positions,
                                                   caches[key], plan)
    new_cache = _pack(cfg, caches)
    x = layers.norm_apply(cfg, v["final_norm"], x)
    logits = layers.unembed_apply(cfg, {k: v[k] for k in ("head",) if k in v},
                                  v["embed"], x)[:, 0]
    return logits, new_cache


def decode_step_channel(cfg, v, token: jax.Array, positions: jax.Array,
                        cache: dict, protocol, rng: jax.Array
                        ) -> Tuple[jax.Array, dict, dict]:
    """:func:`decode_step` with the wireless channel in the loop.

    Every worker-factored FFN in the stack (each mlp FFN and each MoE
    layer's shared expert) aggregates the per-worker partials through
    ``protocol`` (a traced ``repro.protocol.Protocol``
    pytree — rebinding ``p_miss`` never recompiles) under the sensing key
    ``rng``; mixer fusions stay on the ideal ``tp_fusion`` collective.
    Returns ``(logits, new_cache, chan)`` where ``chan`` is the summed
    channel-accounting dict (``fusion.chan_zeros()`` layout) over the
    tick's :func:`channel_sites` aggregate calls.
    """
    x = layers.embed_tokens(cfg, v["embed"], token)
    if cfg.use_abs_pos:
        pe = layers.sinusoidal_positions(
            int(_max_pos(cfg, cache)), cfg.d_model).astype(x.dtype)
        x = x + pe[positions][:, None]
    caches = _unpack(cfg, cache)
    chans = []
    for key, plan, _ in _stacks(cfg):
        k = rng if key == "blocks" else jax.random.fold_in(rng, _LEAD_KEY)
        x, caches[key], _, ch = transformer.stack_step(
            cfg, v[key], x, positions, caches[key], plan,
            protocol=protocol, rng=k)
        chans.append(ch)
    chan = functools.reduce(fusion.chan_merge, chans)
    new_cache = _pack(cfg, caches)
    x = layers.norm_apply(cfg, v["final_norm"], x)
    logits = layers.unembed_apply(cfg, {k: v[k] for k in ("head",) if k in v},
                                  v["embed"], x)[:, 0]
    return logits, new_cache, chan


def channel_sites(cfg) -> int:
    """Channel aggregate calls per decode tick: one per worker-factored
    FFN, that is each mlp layer and each MoE layer's shared expert (routed
    experts stay whole on their shard)."""
    def sites(plan):
        return sum(1 for _, ffn in plan
                   if ffn == "mlp" or (ffn == "moe" and cfg.moe_shared_expert))

    return sum(n * sites(plan) for _, plan, n in _stacks(cfg))


def _max_pos(cfg, cache) -> int:
    # self-attention KV cache: (layers, B, S_max, n_kv_heads, head_dim)
    for leaf in jax.tree.leaves(cache):
        if (leaf.ndim == 5 and leaf.shape[-2] == cfg.n_kv_heads
                and leaf.shape[-1] == cfg.head_dim_):
            return leaf.shape[2]
    return 32768


def cache_init(cfg, batch: int, max_seq: int, cross_len: int = 0) -> dict:
    return _pack(cfg, {key: transformer.stack_cache_init(
        cfg, plan, n, batch, max_seq, cfg.dtype, cross_len=cross_len)
        for key, plan, n in _stacks(cfg)})


def cache_axes(cfg) -> dict:
    """Logical axes of every leaf of :func:`cache_init`'s tree (each a
    tuple naming ``"batch"`` among them)."""
    return _pack(cfg, {key: transformer.stack_cache_axes(
        cfg, plan, cfg.encoder_decoder) for key, plan, _ in _stacks(cfg)})


# ---------------------------------------------------------------------------
# input specs (dry-run: ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig
                ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (specs, logical_axes) for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    specs: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}

    def token_inputs(with_targets: bool):
        if cfg.encoder_decoder:
            sd = min(WHISPER_DECODER_LEN, s)
            specs["feats"] = sds((b, s, cfg.frontend_dim), jnp.bfloat16)
            axes["feats"] = ("batch", "seq", None)
            specs["tokens"] = sds((b, sd), i32)
            axes["tokens"] = ("batch", "seq")
            if with_targets:
                specs["targets"] = sds((b, sd), i32)
                axes["targets"] = ("batch", "seq")
        elif cfg.frontend == "token":
            specs["tokens"] = sds((b, s), i32)
            axes["tokens"] = ("batch", "seq")
            if with_targets:
                specs["targets"] = sds((b, s), i32)
                axes["targets"] = ("batch", "seq")
        else:
            specs["feats"] = sds((b, s, cfg.frontend_dim), jnp.bfloat16)
            axes["feats"] = ("batch", "seq", None)
            if with_targets:
                specs["targets"] = sds((b, s), i32)
                axes["targets"] = ("batch", "seq")

    if shape.kind == "train":
        token_inputs(with_targets=True)
    elif shape.kind == "prefill":
        token_inputs(with_targets=False)
    elif shape.kind == "decode":
        specs["token"] = sds((b, 1), i32)
        axes["token"] = ("batch", None)
        specs["positions"] = sds((b,), i32)
        axes["positions"] = ("batch",)
        cross_len = s if cfg.encoder_decoder else 0
        cache = jax.eval_shape(
            lambda: cache_init(cfg, b, s, cross_len=cross_len))
        specs["cache"] = cache
        axes["cache"] = cache_axes(cfg)
    else:
        raise ValueError(shape.kind)
    return specs, axes


def build(cfg: ModelConfig) -> types.SimpleNamespace:
    return types.SimpleNamespace(
        cfg=cfg,
        init=functools.partial(init, cfg),
        loss=functools.partial(loss_fn, cfg),
        logits=functools.partial(logits_fn, cfg),
        forward=functools.partial(forward, cfg),
        prefill=functools.partial(prefill, cfg),
        decode_step=functools.partial(decode_step, cfg),
        decode_step_channel=functools.partial(decode_step_channel, cfg),
        channel_sites=functools.partial(channel_sites, cfg),
        cache_init=functools.partial(cache_init, cfg),
        cache_axes=functools.partial(cache_axes, cfg),
        input_specs=functools.partial(input_specs, cfg),
        split=split_tree,
    )
