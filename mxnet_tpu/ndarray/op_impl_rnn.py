"""Fused RNN operator (LSTM/GRU/vanilla) — the cuDNN RNN analog.

Reference: src/operator/rnn.cc + rnn-inl.h (RNNOp stateful op behind
gluon.rnn.LSTM; cuDNN path via cudnn_rnn-inl.h `cudnnRNNForwardTraining`
with a single packed parameter vector). TPU-native design per SURVEY §7
phase 6: one ``lax.scan`` over time per layer/direction with the gate
matmuls batched into a single (G·H × I+H) MXU matmul per step; the
packed parameter layout (all i2h/h2h weights layer-major then all
biases — the cuDNN canonical layout) is preserved so checkpoint and op
signatures match the reference. XLA unrolls nothing: scan keeps compile
time flat and lets the MXU pipeline steps.

Gate order matches cuDNN/MXNet: LSTM [i, f, g, o]; GRU [r, z, n];
vanilla relu/tanh single gate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .register import register_op

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(num_layers, input_size, state_size, bidirectional, mode):
    """Total packed parameter count (reference GetRnnParamSize)."""
    gates = _GATES[mode]
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        isz = input_size if layer == 0 else state_size * dirs
        size += dirs * gates * state_size * (isz + state_size)  # weights
    size += num_layers * dirs * gates * state_size * 2  # biases
    return size


def _unpack_params(params, num_layers, input_size, state_size, bidirectional, mode):
    gates = _GATES[mode]
    dirs = 2 if bidirectional else 1
    H = state_size
    idx = 0
    weights = []  # [(W_i2h, W_h2h)] per (layer, dir)
    for layer in range(num_layers):
        isz = input_size if layer == 0 else H * dirs
        per_dir = []
        for _ in range(dirs):
            w_i2h = params[idx: idx + gates * H * isz].reshape(gates * H, isz)
            idx += gates * H * isz
            w_h2h = params[idx: idx + gates * H * H].reshape(gates * H, H)
            idx += gates * H * H
            per_dir.append((w_i2h, w_h2h))
        weights.append(per_dir)
    biases = []
    for layer in range(num_layers):
        per_dir = []
        for _ in range(dirs):
            b_i2h = params[idx: idx + gates * H]
            idx += gates * H
            b_h2h = params[idx: idx + gates * H]
            idx += gates * H
            per_dir.append((b_i2h, b_h2h))
        biases.append(per_dir)
    return weights, biases


def _cell_step(mode, H):
    if mode == "lstm":
        def step(carry, gin):
            h, c = carry
            i, f, g, o = jnp.split(gin, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            g = jnp.tanh(g)
            o = jax.nn.sigmoid(o)
            new_c = f * c + i * g
            new_h = o * jnp.tanh(new_c)
            return (new_h, new_c), new_h
        return step
    if mode == "gru":
        def step(carry, gin_pair):
            h = carry
            gin_x, (w_h2h, b_h2h) = gin_pair
            hg = jnp.matmul(h, w_h2h.T) + b_h2h
            rx, zx, nx = jnp.split(gin_x, 3, axis=-1)
            rh, zh, nh = jnp.split(hg, 3, axis=-1)
            r = jax.nn.sigmoid(rx + rh)
            z = jax.nn.sigmoid(zx + zh)
            n = jnp.tanh(nx + r * nh)
            new_h = (1.0 - z) * n + z * h
            return new_h, new_h
        return step
    act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu

    def step(carry, gin):
        h = carry
        new_h = act(gin)
        return new_h, new_h
    return step


def _run_layer(x, w_i2h, w_h2h, b_i2h, b_h2h, h0, c0, mode, reverse=False):
    """One direction of one layer. x: (T, N, I) → (T, N, H)."""
    H = h0.shape[-1]
    if reverse:
        x = jnp.flip(x, axis=0)
    # batch all input projections into one big MXU matmul: (T*N, I)·(I, G·H)
    gin_x = jnp.einsum("tni,gi->tng", x, w_i2h) + b_i2h

    if mode == "gru":
        step = _cell_step(mode, H)

        def scan_fn(h, gx):
            return step(h, (gx, (w_h2h, b_h2h)))

        h_last, out = lax.scan(scan_fn, h0, gin_x)
        c_last = None
    elif mode == "lstm":
        step = _cell_step(mode, H)

        def scan_fn(carry, gx):
            h, c = carry
            gin = gx + jnp.matmul(h, w_h2h.T) + b_h2h
            return step((h, c), gin)

        (h_last, c_last), out = lax.scan(scan_fn, (h0, c0), gin_x)
    else:
        step = _cell_step(mode, H)

        def scan_fn(h, gx):
            gin = gx + jnp.matmul(h, w_h2h.T) + b_h2h
            return step(h, gin)

        h_last, out = lax.scan(scan_fn, h0, gin_x)
        c_last = None
    if reverse:
        out = jnp.flip(out, axis=0)
    return out, h_last, c_last


@register_op("RNN", wrap=False,
             infer_num_outputs=lambda params:
             3 if str(params.get("mode", "lstm")) == "lstm" else 2)
def rnn(data, parameters, state, state_cell=None, sequence_length=None,
        state_size=0, num_layers=1, bidirectional=False, mode="lstm",
        p=0.0, state_outputs=False, projection_size=None,
        lstm_state_clip_min=None, lstm_state_clip_max=None,
        lstm_state_clip_nan=False, use_sequence_length=False,
        _training=False, _rng_key=None):
    """data: (T, N, I); parameters: packed flat vector; state: (L*D, N, H).
    Returns (output, state_out[, statecell_out])."""
    T, N, input_size = data.shape
    H = int(state_size)
    L = int(num_layers)
    dirs = 2 if bidirectional else 1
    weights, biases = _unpack_params(parameters, L, input_size, H,
                                     bidirectional, mode)
    x = data
    h_states = []
    c_states = []
    key = _rng_key
    for layer in range(L):
        outs = []
        for d in range(dirs):
            sidx = layer * dirs + d
            h0 = state[sidx]
            c0 = state_cell[sidx] if state_cell is not None else None
            w_i2h, w_h2h = weights[layer][d]
            b_i2h, b_h2h = biases[layer][d]
            out, h_last, c_last = _run_layer(
                x, w_i2h, w_h2h, b_i2h, b_h2h, h0, c0, mode, reverse=(d == 1))
            outs.append(out)
            h_states.append(h_last)
            if c_last is not None:
                c_states.append(c_last)
        x = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0.0 and _training and layer < L - 1:
            if key is None:
                from .. import random as _random
                key = _random._next_key()
            key, sub = jax.random.split(key)
            mask = jax.random.bernoulli(sub, 1.0 - p, x.shape).astype(x.dtype)
            x = x * mask / (1.0 - p)
    h_out = jnp.stack(h_states, axis=0)
    if mode == "lstm":
        c_out = jnp.stack(c_states, axis=0)
        if lstm_state_clip_min is not None and lstm_state_clip_max is not None:
            c_out = jnp.clip(c_out, lstm_state_clip_min, lstm_state_clip_max)
        return x, h_out, c_out
    return x, h_out


def pack_rnn_params(layer_params, mode):
    """Concatenate per-layer (w_i2h, w_h2h) + biases into the packed
    vector (gluon rnn_layer does this each forward; XLA fuses it away)."""
    ws = []
    bs = []
    for (w_i2h, w_h2h, b_i2h, b_h2h) in layer_params:
        ws.append(w_i2h.reshape(-1))
        ws.append(w_h2h.reshape(-1))
        bs.append(b_i2h.reshape(-1))
        bs.append(b_h2h.reshape(-1))
    return jnp.concatenate(ws + bs)
