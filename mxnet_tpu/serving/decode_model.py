"""Reference decode model: a paged-KV causal LM the decode engine drives.

The encoder serving path adapts gluon blocks (``bert_serving_entry``);
autoregressive decode needs a model that THREADS THE KV CACHE through
every step, which the encoder CachedOp contract has no slot for. This
module provides the decode-side contract plus a self-contained
GPT-style reference implementation (:class:`PagedCausalLM`) the decode
engine, bench leg and tests drive:

- ``prefill(caches, ids, length, phys, off)`` — one padded prompt row
  in, the first generated token out; per-position K/V are scattered
  into the paged pool THROUGH the precomputed page coordinates
  (``serving/kvcache.py`` emits them; tail padding lands on the
  scratch page).
- ``prefill_chunk(caches, ids, start, valid, table, ...)`` — one
  kernel-sized SLICE of a prompt: ``valid`` tokens at positions
  ``start..start+valid-1`` (front-aligned in the padded ``ids`` row),
  K/V scattered into the sequence's pages, attention over the whole
  written history through the paged kernel (Sq = chunk length). The
  decode engine interleaves these at iteration boundaries so a long
  prompt never stalls the running batch for more than one chunk.
- ``decode_step(caches, ids, positions, tables)`` — one iteration of
  the continuous decode batch: (R,) current tokens in, (R,) next
  tokens out, each row reading its own history through its page-table
  row (``ops.pallas.flash_attention.paged_flash_attention`` on TPU /
  interpret, the dense reference off it) and writing its new K/V page
  slot in place.

All are ``jax.jit`` steps taking the weights as their first argument
(never as closed-over constants) with the cache pytree donated — the decode analog of the encoder path's per-shape CachedOp
executables (one compile per (rows, table-width) bucket, cached by
jax) — so the page pool updates IN PLACE: steady-state decode performs
no per-step cache-sized allocation (``MXNET_TPU_DECODE_DONATE=0``
disables donation for A/B; the resource-watermark test pins the
default).

Sampling is greedy argmax by DEFAULT — deterministic by construction,
what makes the solo-parity goldens byte-exact — with seeded
temperature/top-k/top-p layered on per request: the PRNG key is
``fold_in(PRNGKey(seed), position)``, a pure function of the request's
seed and the sampled position, NEVER of batch composition or iteration
timing — so a stream replayed on another seat after failover resamples
the identical tokens (the part-index dedupe / canary-golden contract).
"""
from __future__ import annotations

import warnings

import numpy as np

from .. import envvars

__all__ = ["PagedCausalLM"]

# XLA CPU cannot honor buffer donation (TPU/GPU can); jax warns once
# per compile — expected off-chip, pure noise in CPU test logs
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def _layer_norm(x, g, b, eps=1e-5):
    import jax.numpy as jnp

    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _sample_row(logits, temp, top_k, top_p, seed, pos):
    """Draw ONE token from one logits row. ``temp <= 0`` is greedy
    argmax — bitwise the pre-sampling behavior, kept as the default
    and the solo-parity lever. Otherwise: temperature-scale, keep the
    ``top_k`` highest logits (0 = all), keep the smallest
    highest-probability set whose mass reaches ``top_p``, draw from
    the rest. The PRNG key is ``fold_in(PRNGKey(seed), pos)`` — a pure
    function of the request's seed and the SEQUENCE position the
    logits came from, so the draw is independent of batch composition,
    chunking and which seat runs it: deterministic replay under
    failover and identical sequences for identical seeds."""
    import jax
    import jax.numpy as jnp

    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits).astype(jnp.int32)
    lg = logits.astype(jnp.float32) \
        / jnp.maximum(temp.astype(jnp.float32), np.float32(1e-6))
    order = jnp.argsort(-lg)                    # token ids, best first
    ranks = jnp.argsort(order)                  # rank of each token
    kk = jnp.where(top_k > 0, top_k.astype(jnp.int32), np.int32(vocab))
    lg = jnp.where(ranks < kk, lg, np.float32(-1e30))
    probs = jax.nn.softmax(lg)
    sp = probs[order]                           # descending by rank
    cum = jnp.cumsum(sp)
    # a token survives top-p if the mass STRICTLY above it is < top_p
    # (the best token always survives, whatever its probability)
    keep = jnp.maximum(
        jnp.sum((cum - sp) < top_p.astype(jnp.float32)), 1)
    lg = jnp.where(ranks < keep, lg, np.float32(-1e30))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
    sampled = jax.random.categorical(key, lg).astype(jnp.int32)
    return jnp.where(temp > np.float32(0.0), sampled, greedy)


class PagedCausalLM:
    """GPT-small-shaped causal LM with a paged decode path.

    Weights are freshly initialized (seeded ``Normal(0.02)``) — the
    serving plane under test is scheduling/transport/caching, not
    model quality; greedy argmax over deterministic weights gives
    byte-reproducible token sequences, which is exactly what the
    parity goldens need.

    Parameters mirror the bench legs: ``vocab``/``units``/``layers``/
    ``heads`` plus ``max_len`` (position-table size — the admission
    bound on prompt + generated length).
    """

    def __init__(self, vocab=256, units=64, layers=2, heads=4,
                 max_len=1024, seed=0, dtype="float32", donate=None,
                 interpret=None):
        import jax
        import jax.numpy as jnp

        if units % heads:
            raise ValueError(f"units {units} not divisible by heads "
                             f"{heads}")
        self.vocab = int(vocab)
        self.units = int(units)
        self.layers = int(layers)
        self.heads = int(heads)
        self.head_dim = self.units // self.heads
        self.max_len = int(max_len)
        self._interpret = interpret
        donate = (envvars.get("MXNET_TPU_DECODE_DONATE")
                  if donate is None else bool(donate))
        self.donate = donate
        rng = np.random.RandomState(seed)
        dt = jnp.dtype(dtype)

        def w(*shape):
            return jnp.asarray(rng.normal(0.0, 0.02, shape), dt)

        U, V, L = self.units, self.vocab, self.layers
        p = {"embed": w(V, U), "pos": w(self.max_len, U),
             "lnf_g": jnp.ones((U,), dt), "lnf_b": jnp.zeros((U,), dt),
             "head": w(U, V)}
        for i in range(L):
            p[f"l{i}_ln1_g"] = jnp.ones((U,), dt)
            p[f"l{i}_ln1_b"] = jnp.zeros((U,), dt)
            p[f"l{i}_ln2_g"] = jnp.ones((U,), dt)
            p[f"l{i}_ln2_b"] = jnp.zeros((U,), dt)
            for n in ("wq", "wk", "wv", "wo"):
                p[f"l{i}_{n}"] = w(U, U)
            p[f"l{i}_w1"] = w(U, 4 * U)
            p[f"l{i}_b1"] = jnp.zeros((4 * U,), dt)
            p[f"l{i}_w2"] = w(4 * U, U)
            p[f"l{i}_b2"] = jnp.zeros((U,), dt)
        self.params = p
        # The weights are an ARGUMENT of every compiled step, never a
        # closed-over constant: a closure bakes them into each
        # executable — at 12x768 every (rows, width) bucket then
        # carried its own 760 MB copy (too big for the persistent
        # cache, a copy each in HBM, and 40 GiB of host memory gone
        # compiling a 13-shape warm-up).
        kw = {"donate_argnums": (1,)} if donate else {}
        self._prefill = jax.jit(self._prefill_impl, **kw)
        self._chunk = jax.jit(self._prefill_chunk_impl, **kw)
        self._decode = jax.jit(self._decode_impl, **kw)

    @property
    def spec(self):
        """The KV geometry the engine sizes its page pool from."""
        return {"n_layers": self.layers, "n_heads": self.heads,
                "head_dim": self.head_dim, "vocab": self.vocab,
                "max_len": self.max_len}

    # -- shared pieces ------------------------------------------------------
    def _qkv(self, p, h, i):
        """(..., U) -> three (..., H, D) projections."""
        shape = h.shape[:-1] + (self.heads, self.head_dim)
        return ((h @ p[f"l{i}_wq"]).reshape(shape),
                (h @ p[f"l{i}_wk"]).reshape(shape),
                (h @ p[f"l{i}_wv"]).reshape(shape))

    def _mlp(self, p, x, i):
        import jax

        return jax.nn.gelu(
            x @ p[f"l{i}_w1"] + p[f"l{i}_b1"]) @ p[f"l{i}_w2"] \
            + p[f"l{i}_b2"]

    @staticmethod
    def _ln(p, x, name):
        return _layer_norm(x, p[f"{name}_g"], p[f"{name}_b"])

    def _write(self, caches, i, phys, off, k, v):
        """Scatter per-position K/V into layer ``i``'s page arrays.
        ``phys``/``off`` are (T,) page coordinates, ``k``/``v``
        (T, H, D)."""
        kc, vc = caches[2 * i], caches[2 * i + 1]
        kc = kc.at[phys, :, off, :].set(k)
        vc = vc.at[phys, :, off, :].set(v)
        return caches[:2 * i] + (kc, vc) + caches[2 * i + 2:]

    # -- prefill ------------------------------------------------------------
    def _prefill_impl(self, p, caches, ids, length, phys, off,
                      temp, top_k, top_p, seed):
        """One padded prompt row: ids (Lp,) int32, length scalar int32,
        phys/off (Lp,) page coordinates. Returns (first generated
        token (), updated caches). Dense causal self-attention (the
        whole prompt is visible at once — the encoder-shaped phase);
        K/V land in the pages for the decode steps to read back."""
        import jax.numpy as jnp

        lp = ids.shape[0]
        positions = jnp.minimum(jnp.arange(lp, dtype=jnp.int32),
                                np.int32(self.max_len - 1))
        x = p["embed"][ids] + p["pos"][positions]
        col = jnp.arange(lp, dtype=jnp.int32)[None, :]
        row = jnp.arange(lp, dtype=jnp.int32)[:, None]
        causal = col <= row
        scale = np.float32(1.0 / np.sqrt(self.head_dim))
        for i in range(self.layers):
            h = self._ln(p, x, f"l{i}_ln1")
            q, k, v = self._qkv(p, h, i)          # (Lp, H, D)
            caches = self._write(caches, i, phys, off, k, v)
            s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32) * scale,
                           k.astype(jnp.float32))
            s = jnp.where(causal[None], s, np.float32(-1e30))
            s = s - jnp.max(s, axis=-1, keepdims=True)
            w_ = jnp.exp(s)
            w_ = w_ / jnp.sum(w_, axis=-1, keepdims=True)
            o = jnp.einsum("hqk,khd->qhd", w_, v.astype(jnp.float32))
            x = x + o.reshape(lp, self.units).astype(x.dtype) \
                @ p[f"l{i}_wo"]
            x = x + self._mlp(p, self._ln(p, x, f"l{i}_ln2"), i)
        h_last = x[length - 1]
        logits = self._ln(p, h_last, "lnf") @ p["head"]
        tok = _sample_row(logits, temp, top_k, top_p, seed, length - 1)
        return tok, caches

    # -- chunked prefill ----------------------------------------------------
    def _prefill_chunk_impl(self, p, caches, ids, start, valid, table,
                            temp, top_k, top_p, seed):
        """One prompt SLICE through the paged kernel: ids (C,) int32
        with the ``valid`` real tokens FRONT-aligned (positions
        ``start..start+valid-1``; the tail is padding), table (W,)
        int32 the sequence's padded page-table row. Each token's K/V
        is scattered into its page slot (padding to the scratch page),
        then the whole chunk attends over the written history with
        Sq = C and ``kv_len = start + C`` — row ``i`` of the chunk is
        position ``start + i``, so the kernel's causal mask
        ``col <= kv_len - Sq + row`` lands exactly on ``col <=
        start + i``: a chunk token sees every earlier position
        (including earlier tokens of its own chunk, already written
        this step) and nothing later. Padding rows attend into
        unwritten columns — garbage in, but row-wise ops keep it in
        the discarded rows. Returns (token sampled at position
        ``start + valid - 1``, caches) — only the chunk containing
        the prompt's last token turns that into the first generated
        token; earlier chunks' is dropped by the engine."""
        import jax.numpy as jnp

        from ..ops import pallas as _pallas
        from ..ops.pallas.flash_attention import (
            paged_attention_reference, paged_flash_attention)

        c = ids.shape[0]
        width = table.shape[0]
        page_size = caches[0].shape[2]
        scratch = np.int32(caches[0].shape[0] - 1)
        idx = jnp.arange(c, dtype=jnp.int32)
        pos = start + idx
        live = idx < valid
        pos_c = jnp.minimum(pos, np.int32(self.max_len - 1))
        x = p["embed"][ids] + p["pos"][pos_c]       # (C, U)
        page_idx = jnp.minimum(pos // np.int32(page_size),
                               np.int32(width - 1))
        phys = jnp.where(live, table[page_idx], scratch)
        off = pos % np.int32(page_size)
        kvl = (start + np.int32(c))[None]           # (1,)
        attend = (paged_flash_attention
                  if _pallas.pallas_ok_for(caches[0])
                  else paged_attention_reference)
        for i in range(self.layers):
            h = self._ln(p, x, f"l{i}_ln1")
            q, k, v = self._qkv(p, h, i)               # (C, H, D)
            caches = self._write(caches, i, phys, off, k, v)
            o = attend(jnp.transpose(q, (1, 0, 2))[None],   # (1,H,C,D)
                       caches[2 * i], caches[2 * i + 1],
                       table[None], kvl)
            o = jnp.transpose(o[0], (1, 0, 2)).reshape(c, self.units)
            x = x + o.astype(x.dtype) @ p[f"l{i}_wo"]
            x = x + self._mlp(p, self._ln(p, x, f"l{i}_ln2"), i)
        h_last = x[valid - 1]
        logits = self._ln(p, h_last, "lnf") @ p["head"]
        tok = _sample_row(logits, temp, top_k, top_p, seed,
                          start + valid - 1)
        return tok, caches

    # -- decode -------------------------------------------------------------
    def _decode_impl(self, p, caches, ids, positions, tables,
                     temps, top_ks, top_ps, seeds):
        """One continuous-batch iteration: ids/positions (R,) int32,
        tables (R, W) int32 page-table rows. Each row writes its new
        K/V at ``positions[r]`` and attends over its own pages up to
        ``positions[r] + 1`` — rows are numerically independent, which
        is what makes join/leave invisible to the sequences already
        running (the solo-parity contract)."""
        import jax.numpy as jnp

        from ..ops import pallas as _pallas
        from ..ops.pallas.flash_attention import (
            paged_attention_reference, paged_flash_attention)

        r = ids.shape[0]
        pos_c = jnp.minimum(positions, np.int32(self.max_len - 1))
        x = p["embed"][ids] + p["pos"][pos_c]       # (R, U)
        page_size = caches[0].shape[2]
        phys = jnp.take_along_axis(
            tables, (positions // np.int32(page_size))[:, None],
            axis=1)[:, 0]
        off = positions % np.int32(page_size)
        kvl = positions + np.int32(1)
        attend = (paged_flash_attention
                  if _pallas.pallas_ok_for(caches[0])
                  else paged_attention_reference)
        for i in range(self.layers):
            h = self._ln(p, x, f"l{i}_ln1")
            q, k, v = self._qkv(p, h, i)               # (R, H, D)
            caches = self._write(caches, i, phys, off, k, v)
            o = attend(q[:, :, None, :], caches[2 * i],
                       caches[2 * i + 1], tables, kvl)
            x = x + o[:, :, 0, :].reshape(r, self.units).astype(x.dtype) \
                @ p[f"l{i}_wo"]
            x = x + self._mlp(p, self._ln(p, x, f"l{i}_ln2"), i)
        logits = self._ln(p, x, "lnf") @ p["head"]
        import jax

        toks = jax.vmap(_sample_row)(logits, temps, top_ks, top_ps,
                                     seeds, positions)
        return toks.astype(jnp.int32), caches

    # -- public steps -------------------------------------------------------
    def prefill(self, caches, ids, length, phys, off,
                temperature=0.0, top_k=0, top_p=1.0, seed=0):
        import jax.numpy as jnp

        return self._prefill(self.params, caches,
                             jnp.asarray(ids, jnp.int32),
                             jnp.asarray(length, jnp.int32),
                             jnp.asarray(phys, jnp.int32),
                             jnp.asarray(off, jnp.int32),
                             jnp.asarray(temperature, jnp.float32),
                             jnp.asarray(top_k, jnp.int32),
                             jnp.asarray(top_p, jnp.float32),
                             jnp.asarray(seed, jnp.int32))

    def prefill_chunk(self, caches, ids, start, valid, table,
                      temperature=0.0, top_k=0, top_p=1.0, seed=0):
        import jax.numpy as jnp

        return self._chunk(self.params, caches,
                           jnp.asarray(ids, jnp.int32),
                           jnp.asarray(start, jnp.int32),
                           jnp.asarray(valid, jnp.int32),
                           jnp.asarray(table, jnp.int32),
                           jnp.asarray(temperature, jnp.float32),
                           jnp.asarray(top_k, jnp.int32),
                           jnp.asarray(top_p, jnp.float32),
                           jnp.asarray(seed, jnp.int32))

    def decode_step(self, caches, ids, positions, tables,
                    temperatures=None, top_ks=None, top_ps=None,
                    seeds=None):
        import jax.numpy as jnp

        ids = jnp.asarray(ids, jnp.int32)
        r = ids.shape[0]

        def _vec(v, fill, dt):
            if v is None:
                return jnp.full((r,), fill, dt)
            return jnp.asarray(v, dt)

        return self._decode(self.params, caches, ids,
                            jnp.asarray(positions, jnp.int32),
                            jnp.asarray(tables, jnp.int32),
                            _vec(temperatures, 0.0, jnp.float32),
                            _vec(top_ks, 0, jnp.int32),
                            _vec(top_ps, 1.0, jnp.float32),
                            _vec(seeds, 0, jnp.int32))
