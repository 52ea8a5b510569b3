"""Shakespeare next-character LSTM (paper Table 6).

tokens (B, S) -> embedding (vocab, embed) -> two LSTM layers of ``hidden``
units (gates f, i, o, g from one (in + hidden, 4 hidden) projection,
forget-gate bias 1) -> dense logits over the vocabulary; the loss is the
mean cross-entropy of each next character, positions 1..S-1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _lstm_cfg(cfg):
    from repro.models.rnn import LstmConfig
    return LstmConfig(vocab=cfg["vocab"], embed_dim=cfg["embed_dim"],
                      hidden=cfg["hidden"], n_layers=cfg["n_layers"],
                      seq_len=cfg["seq_len"])


def program_loss(cfg):
    from repro.models import rnn
    return functools.partial(rnn.loss_fn, _lstm_cfg(cfg))


def init_params(cfg, key):
    v, e, h = cfg["vocab"], cfg["embed_dim"], cfg["hidden"]
    keys = jax.random.split(key, 2 + 2 * cfg["n_layers"])
    layers, d_in = [], e
    for i in range(cfg["n_layers"]):
        s = float(1.0 / np.sqrt(d_in + h))
        layers.append({
            "wx": s * jax.random.normal(keys[2 + 2 * i], (d_in, 4 * h)),
            "wh": s * jax.random.normal(keys[3 + 2 * i], (h, 4 * h)),
            "b": jnp.zeros((4 * h,), jnp.float32).at[:h].set(1.0)})
        d_in = h
    return {"embed": 0.1 * jax.random.normal(keys[0], (v, e)),
            "out_w": jax.random.normal(keys[1], (h, v)) / float(np.sqrt(h)),
            "out_b": jnp.zeros((v,), jnp.float32),
            "lstm": layers}


def reference_loss(cfg, params, batch, dot=jnp.dot):
    tokens = batch["tokens"]
    x = params["embed"][tokens]                       # (B, S, embed)
    for layer in params["lstm"]:
        h_dim = layer["wh"].shape[0]

        def cell(carry, x_t, layer=layer, h_dim=h_dim):
            h, c = carry
            z = dot(x_t, layer["wx"]) + dot(h, layer["wh"]) + layer["b"]
            f = jax.nn.sigmoid(z[:, :h_dim])
            i = jax.nn.sigmoid(z[:, h_dim:2 * h_dim])
            o = jax.nn.sigmoid(z[:, 2 * h_dim:3 * h_dim])
            g = jnp.tanh(z[:, 3 * h_dim:])
            c = f * c + i * g
            h = o * jnp.tanh(c)
            return (h, c), h

        zero = jnp.zeros((tokens.shape[0], h_dim), x.dtype)
        _, hs = jax.lax.scan(cell, (zero, zero), jnp.swapaxes(x, 0, 1))
        x = jnp.swapaxes(hs, 0, 1)
    logits = dot(x[:, :-1], params["out_w"]) + params["out_b"]
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def make_data(cfg, n_clients: int, kind: str):
    """One speaking role per client: a Markov character stream whose
    transition matrix mixes a shared one (weight 0.5-0.95) with the role's
    own sparse one; 8 to 64 sentences of ``seq_len`` characters each.
    Returns ({"tokens": (N, S_max, seq_len) i32}, counts (N,) i32), zero
    past each client's count."""
    if kind != "staged":
        raise ValueError(f"shakespeare_lstm has staged data only, not {kind!r}")
    rng = np.random.default_rng(cfg["data_seed"])
    n, v, length = n_clients, cfg["vocab"], cfg["seq_len"]
    lo, hi = cfg["sentences_per_client"]
    shared = rng.dirichlet(np.full(v, 0.3), size=v)
    mix = rng.uniform(0.5, 0.95, n)[:, None, None]
    role = rng.dirichlet(np.full(v, 0.05), size=(n, v))
    trans = mix * shared + (1.0 - mix) * role
    cdf = np.cumsum(trans / trans.sum(-1, keepdims=True), axis=-1)
    counts = rng.integers(lo, hi + 1, n).astype(np.int32)
    state = rng.integers(0, v, (n, hi))
    draws = rng.random((n, hi, length))
    tokens = np.empty((n, hi, length), np.int32)
    rows = np.arange(n)[:, None]
    for i in range(length):
        tokens[:, :, i] = state
        state = np.minimum((cdf[rows, state] < draws[:, :, i, None]).sum(-1),
                           v - 1)
    tokens[np.arange(hi)[None, :] >= counts[:, None]] = 0
    return {"tokens": tokens}, counts


def round_counts(cfg, n_clients: int, k: int):
    """Least work of one round.  FLOPs: forward and backward (3 x 2 x MACs)
    of both LSTM projections and the output layer, over the S - 1 positions
    the loss needs, for B sequences x E steps x K clients.  Bytes: the rate
    EMA read and written for every client (8 B each), the tokens read
    once, the global model read and written."""
    v, e, h, s = cfg["vocab"], cfg["embed_dim"], cfg["hidden"], cfg["seq_len"]
    macs, d_in = h * v, e
    for _ in range(cfg["n_layers"]):
        macs += (d_in + h) * 4 * h
        d_in = h
    seqs = k * cfg["local_steps"] * cfg["local_batch"]
    flops = seqs * (s - 1) * 6.0 * macs
    n_params = v * e + h * v + v
    d_in = e
    for _ in range(cfg["n_layers"]):
        n_params += (d_in + h) * 4 * h + 4 * h
        d_in = h
    bytes_ = 8.0 * n_clients + seqs * s * 4.0 + 2.0 * 4.0 * n_params
    return flops, bytes_
