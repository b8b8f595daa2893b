"""Prefill + decode must reproduce the full-forward logits (teacher forcing).

This validates every cache path: attention KV (incl. GQA + plain layout),
the latent MLA cache behind a leading dense layer, mamba conv/ssm state,
mLSTM/sLSTM state, and whisper's cross-attention cache.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import model as M
from repro.parallel.sharding import split_tree

pytestmark = pytest.mark.slow    # end-to-end: excluded from the tier-1 CI job

DECODE_ARCHS = ["glm4-9b", "qwen2.5-32b", "minicpm-2b", "xlstm-125m",
                "jamba-1.5-large-398b", "qwen3-moe-30b-a3b",
                "moonlight-16b-a3b"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_matches_full_forward(arch):
    # MoE archs: capacity-based token dropping is seq-length dependent by
    # design (training drops, decode never does); no-drop capacity isolates
    # the cache-path equivalence this test is about.
    cfg = get_reduced(arch, capacity_factor=64.0)
    m = M.build(cfg)
    values, _ = split_tree(m.init(jax.random.PRNGKey(1)))
    rng = np.random.default_rng(3)
    b, s_pre, s_dec = 2, 12, 4
    total = s_pre + s_dec
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, total)),
                         jnp.int32)

    full_logits = m.logits(values, {"tokens": tokens})       # (B, T, V)

    logits, cache = m.prefill(values, {"tokens": tokens[:, :s_pre]},
                              max_seq=total)
    errs = [float(jnp.max(jnp.abs(logits - full_logits[:, s_pre - 1])))]
    for t in range(s_pre, total):
        tok = tokens[:, t:t + 1]
        pos = jnp.full((b,), t, jnp.int32)
        logits, cache = m.decode_step(values, tok, pos, cache)
        errs.append(float(jnp.max(jnp.abs(logits - full_logits[:, t]))))
    worst = max(errs)
    assert worst < 2e-2 if cfg.dtype == jnp.float32 else worst < 1e-1, \
        f"{arch}: teacher-forced decode diverged, max err {worst} ({errs})"


def test_whisper_prefill_decode_consistency():
    cfg = get_reduced("whisper-base")
    m = M.build(cfg)
    values, _ = split_tree(m.init(jax.random.PRNGKey(2)))
    rng = np.random.default_rng(5)
    b, s_enc, s_pre, s_dec = 2, 16, 6, 3
    feats = jnp.asarray(rng.standard_normal((b, s_enc, cfg.frontend_dim)),
                        jnp.float32)
    total = s_pre + s_dec
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, total)),
                         jnp.int32)
    full = m.logits(values, {"feats": feats, "tokens": tokens})

    logits, cache = m.prefill(values,
                              {"feats": feats, "tokens": tokens[:, :s_pre]},
                              max_seq=total)
    errs = [float(jnp.max(jnp.abs(logits - full[:, s_pre - 1])))]
    for t in range(s_pre, total):
        pos = jnp.full((b,), t, jnp.int32)
        logits, cache = m.decode_step(values, tokens[:, t:t + 1], pos, cache)
        errs.append(float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert max(errs) < 2e-2, f"whisper decode err {errs}"
