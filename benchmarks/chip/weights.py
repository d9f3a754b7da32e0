"""Seeded random weights, made on the device in the type they are served.

Each leaf is drawn from its own key: ``fold_in(fold_in(seed_key, leaf),
layer)``. So the whole stack comes out of one jitted call for the engine
(``served_params``), and the reference can draw any single layer again
(``layer_leaves``) without holding the rest or taking anything the
engine made.

The engine's layout departs from the published models in two ways that
are pure reparameterizations, so the leaves are drawn in the engine's
form and the reference maps them to the published form exactly
(``reference.published_layer``):

* its RMSNorm multiplies by ``1 + w``, where the published model has
  ``w``;
* it scales token embeddings by ``sqrt(hidden_size)`` (the published
  models do not), so the published embedding is the served table times
  ``sqrt(hidden_size)``, and with tied embeddings the published final
  norm absorbs the matching ``1 / sqrt(hidden_size)``.

The scales are those of a freshly initialised model (linear weights of
std ``1 / sqrt(fan_in)``), with logits of std ``LOGIT_STD`` over random
hidden states, so that greedy tokens vary and near-ties occur as in a
trained model's logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.counts import Dims

NORM_STD = 0.05        # served-form norm weights: published 1 + N(0, .05)
BIAS_STD = 0.1         # q/k/v biases (larger ones fix the attention pattern)
LOGIT_STD = 2.5        # std of a logit over random hidden states
TIED_EMB_RMS = 0.05    # rms of a tied model's input embedding

_LEAF_IDS = {"ln1": 1, "ln2": 2, "wq": 3, "bq": 4, "wk": 5, "bk": 6,
             "wv": 7, "bv": 8, "wo": 9, "up": 10, "down": 11,
             "emb": 20, "final_norm": 21, "head": 22}


def seed_key(seed: int):
    """PRNG key of any non-negative seed (more than 32 bits allowed)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _draw(key, name: str, layer, shape, std: float, mean: float = 0.0):
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS[name]), layer)
    x = jax.random.normal(k, shape, jnp.float32) * std + mean
    return x.astype(jnp.bfloat16)


def layer_shapes(m: Dims):
    """name -> (shape, std) of one layer's served-form leaves."""
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    out = {"ln1": ((m.d,), NORM_STD), "ln2": ((m.d,), NORM_STD),
           "wq": ((m.d, q), m.d ** -0.5), "wk": ((m.d, kv), m.d ** -0.5),
           "wv": ((m.d, kv), m.d ** -0.5), "wo": ((q, m.d), q ** -0.5),
           "up": ((m.d, 2 * m.d_ff), m.d ** -0.5),
           "down": ((m.d_ff, m.d), m.d_ff ** -0.5)}
    if m.qkv_bias:
        out.update(bq=((q,), BIAS_STD), bk=((kv,), BIAS_STD),
                   bv=((kv,), BIAS_STD))
    return out


def emb_std(m: Dims) -> float:
    """Served-form embedding std: the served table times sqrt(d) has rms
    1 (untied) or ``TIED_EMB_RMS`` (tied). A tied table of random rows is
    also the output head, and a token whose own row dominates the
    residual stream predicts itself by a margin that grows as sqrt(d):
    greedy decoding then repeats one token and no rounding can change
    it. A small input embedding keeps the next token in play; the final
    norm (``final_norm_mean``) restores the logit spread."""
    return (TIED_EMB_RMS if m.tied else 1.0) * m.d ** -0.5


def final_norm_mean(m: Dims) -> float:
    """Served-form final norm offset: with tied embeddings, logits of std
    ``LOGIT_STD`` from a head whose rows have rms ``TIED_EMB_RMS``."""
    return LOGIT_STD / TIED_EMB_RMS - 1.0 if m.tied else 0.0


def layer_leaves(m: Dims, key, layer):
    """One layer's served-form leaves (``layer`` may be traced)."""
    return {n: _draw(key, n, layer, s, std)
            for n, (s, std) in layer_shapes(m).items()}


def global_leaves(m: Dims, key):
    out = {"emb": _draw(key, "emb", 0, (m.vocab, m.d), emb_std(m)),
           "final_norm": _draw(key, "final_norm", 0, (m.d,), NORM_STD,
                               final_norm_mean(m))}
    if not m.tied:
        out["head"] = _draw(key, "head", 0, (m.d, m.vocab),
                            LOGIT_STD * m.d ** -0.5)
    return out


def _dense(w, b=None):
    return {"w": w} if b is None else {"w": w, "b": b}


def served_params(m: Dims, seed: int, *, sharding=None):
    """The engine's parameter pytree for ``seed``, in one jitted call;
    with ``sharding`` (replicated over the engine's mesh) every chip
    draws its own copy, so no chip ever holds two."""

    def make(key):
        g = global_leaves(m, key)
        st = jax.vmap(lambda l: layer_leaves(m, key, l))(
            jnp.arange(m.layers))
        attn = {"wq": _dense(st["wq"], st.get("bq")),
                "wk": _dense(st["wk"], st.get("bk")),
                "wv": _dense(st["wv"], st.get("bv")),
                "wo": _dense(st["wo"])}
        params = {"embed": {"emb": g["emb"]}, "final_norm": g["final_norm"],
                  "stack": {"ln1": st["ln1"], "ln2": st["ln2"], "attn": attn,
                            "mlp": {"up": _dense(st["up"]),
                                    "down": _dense(st["down"])}}}
        if not m.tied:
            params["lm_head"] = _dense(g["head"])
        return params

    if sharding is None:
        return jax.jit(make)(seed_key(seed))
    return jax.jit(make, out_shardings=sharding)(seed_key(seed))
