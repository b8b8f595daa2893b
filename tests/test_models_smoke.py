"""Deliverable (f): per-architecture reduced-config smoke tests.

Each assigned arch instantiates a reduced config of the same family and runs
one forward + one train step on CPU, asserting output shapes and no NaNs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.models import model as M
from repro.parallel.sharding import split_tree

pytestmark = pytest.mark.slow    # end-to-end: excluded from the tier-1 CI job


def _batch_for(cfg, b=2, s=16, sd=8, seed=0):
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    if cfg.encoder_decoder:
        dec = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, sd)), jnp.int32)
        return {"feats": jnp.asarray(
                    rng.standard_normal((b, s, cfg.frontend_dim)),
                    jnp.float32),
                "tokens": dec, "targets": dec}
    if cfg.frontend != "token":
        return {"feats": jnp.asarray(
                    rng.standard_normal((b, s, cfg.frontend_dim)),
                    jnp.float32),
                "targets": toks}
    return {"tokens": toks, "targets": toks}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_smoke_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    m = M.build(cfg)
    values, axes = split_tree(m.init(jax.random.PRNGKey(0)))
    batch = _batch_for(cfg, seed=hash(arch) % 2**31)

    logits = m.logits(values, batch)
    s_out = batch["tokens"].shape[1] if cfg.encoder_decoder else 16
    assert logits.shape == (2, s_out, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: NaN logits"

    loss, metrics = m.loss(values, batch)
    assert np.isfinite(float(loss)), f"{arch}: NaN loss"

    grads = jax.grad(lambda v: m.loss(v, batch)[0])(values)
    flat = [np.asarray(g, np.float32) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(g).all() for g in flat), f"{arch}: NaN grads"
    total = sum(float((g ** 2).sum()) for g in flat)
    assert total > 0, f"{arch}: zero gradient"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The exact assigned hyperparameters (source-of-truth check)."""
    spec = {
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
        "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
    }[arch]
    cfg = get_config(arch)
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff if cfg.family != "moe" else cfg.moe_d_ff,
           cfg.vocab_size)
    assert got == spec, f"{arch}: {got} != {spec}"


def test_moe_configs():
    q3 = get_config("qwen3-moe-30b-a3b")
    assert (q3.n_experts, q3.experts_per_token) == (128, 8)
    l4 = get_config("llama4-scout-17b-a16e")
    assert (l4.n_experts, l4.experts_per_token) == (16, 1)
    assert l4.moe_shared_expert
    jb = get_config("jamba-1.5-large-398b")
    assert (jb.n_experts, jb.experts_per_token) == (16, 2)


def test_jamba_interleave_ratio():
    cfg = get_config("jamba-1.5-large-398b")
    plan = [m for m, _ in cfg.layer_plan()]
    assert plan.count("attn") == 1 and plan.count("mamba") == 7
    ffns = [f for _, f in cfg.layer_plan()]
    assert ffns.count("moe") == 4 and ffns.count("mlp") == 4


def test_param_counts_sane():
    """Param counting should land near the nameplate sizes."""
    cases = {
        "glm4-9b": (9e9, 0.5),
        "qwen2.5-32b": (32e9, 0.3),
        "qwen1.5-0.5b": (0.5e9, 0.4),
        "minicpm-2b": (2.7e9, 0.5),
        "jamba-1.5-large-398b": (398e9, 0.3),
        "xlstm-125m": (125e6, 0.8),
    }
    for arch, (target, tol) in cases.items():
        n = get_config(arch).param_count()
        assert abs(n - target) / target < tol, (arch, n, target)


def test_active_params_less_than_total_for_moe():
    for arch in ("qwen3-moe-30b-a3b", "llama4-scout-17b-a16e",
                 "jamba-1.5-large-398b"):
        cfg = get_config(arch)
        assert cfg.param_count(active_only=True) < cfg.param_count()
