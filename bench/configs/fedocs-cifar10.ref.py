"""Plain float32 reference of the vertical FedOCS learner over the noisy
OCS channel (arXiv:2209.01682 §II-§III, §IV-B geometry).

It imports nothing of the system under test.  It follows the method as the
paper states it, with the repository's documented choices where the paper
leaves one open (each is a semantic of the channel or the job, not code):

* Encoders ``f_n`` are ReLU MLPs per worker, the fusion head a ReLU MLP;
  the loss is the mean cross-entropy of the head's logits.
* Pooling: each worker's embedding element is quantized to a ``bits``-bit
  monotone code (the top ``bits`` bits of the IEEE-754 order-embedding of
  the float, paper Eq. 7 and footnote 2).  Workers contend bit by bit,
  most significant first, followed by ``ceil(log2 N)`` tie-break slots on
  the complement of the worker index; a worker that senses stays in only
  if it hears no blocking signal, and it misses a signal with probability
  ``p_miss``.  ``max_rounds`` rounds re-contend the survivors; the lowest
  surviving index wins.  The pooled value is the representative float of
  the winner's code (its low bits zero), and the gradient flows to the
  winner alone (paper Eq. 5-6).  The ideal lane pools the maximal code
  with the gradient to the lowest-indexed worker that holds it.
* Random streams: batch rows of step ``s`` are
  ``randint(fold_in(k_data, s), (batch,), 0, n_train)``; lane ``l``'s
  sensing key of step ``s`` is ``fold_in(lane_keys[l], s)``, round ``r``
  of a contention draws with ``fold_in(key, r)`` and its sub-slot ``d``
  hears a worker's blocking signal by ``bernoulli(fold_in(key_r, d),
  1 - p_miss, (N, K))`` over the flattened ``(batch * K)`` sub-frames, in
  the embedding's dtype.  These are the streams the program draws, so the
  reference meets the same misses.
* AdamW with decoupled weight decay, global-norm gradient clipping, and a
  linear-warmup cosine learning rate, as the configuration states.
* After the run each lane is read on the validation rows through its own
  channel: the noisy lanes pool with the sensing key of step ``steps``.

Matrix products run at ``precision="highest"`` (true float32).  With
``precision="fp8"`` every product's operands are first rounded to
float8_e4m3fn: the control, one step of precision below the bfloat16
operands that float32 products take at the TPU's default precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.lib.ocs_ref import codes, contend, dequant, route

F32 = jnp.float32


def _mm(a, b, precision: str):
    if precision == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(F32)
        b = b.astype(jnp.float8_e4m3fn).astype(F32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _dims(config):
    n = config["grid"] ** 2
    patch = (config["hw"] // config["grid"]) ** 2
    enc = [patch, *config["encoder_dims"], config["embed_dim"]]
    head = [config["embed_dim"], *config["head_dims"], config["n_classes"]]
    return n, enc, head


def init_params(config, key):
    """He-normal weights and zero biases; encoders carry a leading worker
    axis.  The layout the program's vertical learner takes."""
    n, enc, head = _dims(config)
    k_enc, k_head = jax.random.split(key)
    ke = jax.random.split(k_enc, len(enc) - 1)
    kh = jax.random.split(k_head, len(head) - 1)
    encoders = [{"w": jax.random.normal(ke[i], (n, a, b), F32)
                 * math.sqrt(2.0 / a), "b": jnp.zeros((n, b), F32)}
                for i, (a, b) in enumerate(zip(enc[:-1], enc[1:]))]
    heads = [{"w": jax.random.normal(kh[i], (a, b), F32)
              * math.sqrt(2.0 / a), "b": jnp.zeros((b,), F32)}
             for i, (a, b) in enumerate(zip(head[:-1], head[1:]))]
    return {"encoders": encoders, "head": heads}


# ---------------------------------------------------------------------------
# the channel
# ---------------------------------------------------------------------------

def pool_ocs(h, key, p_miss, bits: int, max_rounds: int):
    """Noisy-OCS max-pool of worker embeddings h: (N, B, K) float32."""
    n = h.shape[0]
    flat = h.reshape(n, -1)
    code = codes(jax.lax.stop_gradient(flat), bits)
    winner = contend(code, key, 1.0 - jnp.asarray(p_miss, F32), bits,
                     max_rounds)
    onehot = (jnp.arange(n)[:, None] == winner[None]).astype(F32)
    pooled = dequant(jnp.take_along_axis(code, winner[None], 0)[0], bits, 32)
    return route(flat, onehot, pooled).reshape(h.shape[1:])


def pool_ideal(h, bits: int):
    """Error-free quantized max-pool, gradient to the first holder."""
    n = h.shape[0]
    flat = h.reshape(n, -1)
    code = codes(jax.lax.stop_gradient(flat), bits)
    top = jnp.max(code, axis=0)
    first = jnp.argmax(code == top[None], axis=0)
    onehot = (jnp.arange(n)[:, None] == first[None]).astype(F32)
    return route(flat, onehot, dequant(top, bits, 32)).reshape(h.shape[1:])


# ---------------------------------------------------------------------------
# the learner, its loss and AdamW
# ---------------------------------------------------------------------------

def _mlp(layers, x, precision):
    for i, layer in enumerate(layers):
        x = _mm(x, layer["w"], precision) + layer["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def logits_of(params, views, pool, precision):
    h = jax.vmap(lambda enc, v: _mlp(enc, v, precision))(
        params["encoders"], views)                       # (N, B, K)
    return _mlp(params["head"], pool(h), precision)


def loss(params, views, labels, pool, precision):
    logp = jax.nn.log_softmax(logits_of(params, views, pool, precision),
                              axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def evaluate(params, views, labels, pool, precision):
    """Mean cross-entropy and accuracy of the head's logits."""
    logp = jax.nn.log_softmax(logits_of(params, views, pool, precision),
                              axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return nll, jnp.mean(jnp.argmax(logp, -1) == labels)


def lr_at(opt: dict, steps: int, step):
    """Linear warmup over ``warmup_frac`` of the steps, then cosine decay
    to ``final_frac`` of the peak; ``step`` counts from 1."""
    warmup = max(1, int(steps * opt["warmup_frac"]))
    s = jnp.asarray(step, F32)
    warm = opt["lr"] * jnp.minimum(s / warmup, 1.0)
    t = jnp.clip((s - warmup) / max(steps - warmup, 1), 0, 1)
    cos = opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * t))
    return jnp.where(s < warmup, warm, opt["lr"] * cos)


def adamw_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"m": z, "v": z, "step": jnp.zeros((), jnp.int32)}


def adamw_update(opt: dict, steps: int, state, params, grads):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    step = state["step"] + 1
    lr = lr_at(opt, steps, step)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)
    c1 = 1 - b1 ** step.astype(F32)
    c2 = 1 - b2 ** step.astype(F32)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, m, v)
    return {"m": m, "v": v, "step": step}, params


def leaf_norms(tree):
    """The L2 norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(tree)])


def train(config: dict, job: dict, params0, views, labels, vviews, vlabels,
          k_data, lane_keys, p_miss, precision: str = "highest"):
    """A whole curve run of every noisy lane and of the ideal lane: every
    step's loss, the trained parameters, the first step's gradient norm
    of every leaf, and the validation ``nll`` and ``acc`` after the run,
    read through the lane's channel (the noisy lanes' sensing key of step
    ``steps``).  Noisy lanes' outputs carry a leading lane axis.

    ``job`` holds ``batch``, ``n_train`` and ``steps``."""
    agg, opt = config["aggregation"], config["optimizer"]
    bits, rounds = agg["bits"], agg["max_rounds"]
    steps = job["steps"]

    def run(pool_at):
        def body(carry, s):
            params, state = carry
            idx = jax.random.randint(jax.random.fold_in(k_data, s),
                                     (job["batch"],), 0, job["n_train"])
            val, grads = jax.value_and_grad(loss)(
                params, views[:, idx], labels[idx], pool_at(s), precision)
            state, params = adamw_update(opt, steps, state, params, grads)
            return (params, state), (val, leaf_norms(grads))

        (params, _), (losses, gnorms) = jax.lax.scan(
            body, (params0, adamw_init(params0)),
            jnp.arange(steps, dtype=jnp.int32))
        nll, acc = evaluate(params, vviews, vlabels, pool_at(steps),
                            precision)
        return {"loss": losses, "params": params, "grad0": gnorms[0],
                "nll": nll, "acc": acc}

    def lane(key, p):
        return run(lambda s: lambda h: pool_ocs(
            h, jax.random.fold_in(key, s), p, bits, rounds))

    with jax.default_matmul_precision("highest"):
        noisy = jax.vmap(lane)(lane_keys, p_miss)
        ideal = run(lambda s: lambda h: pool_ideal(h, bits))
    return noisy, ideal
