"""Synthetic(alpha, beta) softmax regression (paper section 4.1).

Client k draws u_k ~ N(0, alpha), B_k ~ N(0, beta), v_k ~ N(B_k, 1),
W_k ~ N(u_k, 1), b_k ~ N(u_k, 1); features x ~ N(v_k, diag(j^-1.2)) and
labels y = argmax(W_k^T x + b_k).  The model is w (dim, classes) and
b (classes,) under mean cross-entropy plus l2/2 (|w|^2 + |b|^2).

The on-demand labels are the argmax of logits from the configuration's
``default`` matrix product (one bfloat16 pass on a TPU, float32 on a CPU):
an argmax flips at near-ties under any other rounding, so the dataset is
defined at the precision it is made in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def program_loss(cfg):
    from repro.models import softmax_reg
    return functools.partial(
        softmax_reg.loss_fn,
        softmax_reg.SoftmaxRegConfig(dim=cfg["dim"], n_classes=cfg["n_classes"],
                                     l2=cfg["l2"]))


def init_params(cfg, key):
    kw, kb = jax.random.split(key)
    d, c = cfg["dim"], cfg["n_classes"]
    return {"w": 0.01 * jax.random.normal(kw, (d, c), jnp.float32),
            "b": 0.01 * jax.random.normal(kb, (c,), jnp.float32)}


def reference_loss(cfg, params, batch, dot=jnp.dot):
    w, b = params["w"], params["b"]
    x = batch["x"].astype(w.dtype)
    logits = dot(x, w) + b
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(logits, batch["y"][..., None], axis=-1)[..., 0]
    reg = 0.5 * cfg["l2"] * (jnp.sum(w * w) + jnp.sum(b * b))
    return jnp.mean(logz - gold) + reg


def make_data(cfg, n_clients: int, kind: str):
    """Staged: every client's samples as (N, S, dim) f32 and (N, S) i32."""
    if kind == "on_demand":
        return None
    rng = np.random.default_rng(cfg["data_seed"])
    n, s, d, c = n_clients, cfg["samples_per_client"], cfg["dim"], cfg["n_classes"]
    u = rng.normal(0.0, cfg["alpha"], n)
    b_mean = rng.normal(0.0, cfg["beta_data"], n)
    v = rng.normal(b_mean[:, None], 1.0, (n, d))
    w = rng.normal(u[:, None, None], 1.0, (n, d, c))
    b = rng.normal(u[:, None], 1.0, (n, c))
    diag_sqrt = np.sqrt((np.arange(d) + 1.0) ** -1.2)
    x = v[:, None, :] + rng.normal(0.0, 1.0, (n, s, d)) * diag_sqrt
    y = (np.einsum("nsd,ndc->nsc", x, w) + b[:, None, :]).argmax(-1)
    return ({"x": x.astype(np.float32), "y": y.astype(np.int32)},
            np.full(n, s, np.int32))


def program_synth(cfg, n_clients: int):
    from repro.data.synthetic import SynthTask
    return SynthTask(n_clients=n_clients, dim=cfg["dim"],
                     n_classes=cfg["n_classes"], alpha=cfg["alpha"],
                     beta=cfg["beta_data"],
                     samples_per_client=cfg["samples_per_client"],
                     seed=cfg["data_seed"])


def reference_block(cfg, ids):
    """Client ``k``'s samples as a function of fold_in(PRNGKey(seed), k):
    the keyed definition of the on-demand dataset."""
    d, c, s = cfg["dim"], cfg["n_classes"], cfg["samples_per_client"]
    base = jax.random.PRNGKey(cfg["data_seed"])
    scale = jnp.sqrt((jnp.arange(d, dtype=jnp.float32) + 1.0) ** -1.2)

    def one(cid):
        k_u, k_b, k_v, k_w, k_bias, k_x = jax.random.split(
            jax.random.fold_in(base, cid), 6)
        u = cfg["alpha"] * jax.random.normal(k_u)
        b_mean = cfg["beta_data"] * jax.random.normal(k_b)
        v = b_mean + jax.random.normal(k_v, (d,))
        w = u + jax.random.normal(k_w, (d, c))
        b = u + jax.random.normal(k_bias, (c,))
        x = v[None, :] + jax.random.normal(k_x, (s, d)) * scale[None, :]
        logits = jnp.dot(x, w, precision=jax.lax.Precision.DEFAULT) + b[None, :]
        y = jnp.argmax(logits, -1).astype(jnp.int32)
        return {"x": x, "y": y}

    return jax.vmap(one)(ids)


def round_counts(cfg, n_clients: int, k: int):
    """Least work of one round.  FLOPs: forward logits and the weight
    gradient, 2 * 2 * dim * classes per sample, over K clients x E steps x
    B samples.  Bytes: the rate EMA read and written for every client
    (8 B each), each sample's features and label read once, the global
    model read and written."""
    d, c = cfg["dim"], cfg["n_classes"]
    samples = k * cfg["local_steps"] * cfg["local_batch"]
    flops = samples * 4.0 * d * c
    params_bytes = 4.0 * (d * c + c)
    bytes_ = 8.0 * n_clients + samples * 4.0 * (d + 1) + 2.0 * params_bytes
    return flops, bytes_
