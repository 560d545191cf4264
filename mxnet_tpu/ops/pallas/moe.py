"""The experts a chip holds: routing tables at static shapes and a grouped
matmul over the held experts' rows.

An expert layer under expert parallelism is told which experts it holds
(``[lo, lo + E_held)`` of the router's ``E``). It routes every token over
all E, keeps the chosen (token, expert) slots whose expert is here, sorts
them by expert into a row buffer in which each expert's group is padded to
a whole tile of ``TILE`` rows, multiplies tile by tile with that tile's
expert (``grouped_matmul``: Pallas ``mxtpu_moe_gmm`` / ``mxtpu_moe_tgmm`` on
the chip, an einsum over gathered weights elsewhere), and adds the weighted
rows back to their tokens. What the absent experts would add is left out on
purpose: the result is this chip's partial sum.

Dropless at static shapes. The row buffer holds as many rows as there are
tokens (four times the load a balanced router sends here at 8 of 256
experts held), or twice the balanced load where that is more (16 of 128
held at 8 a token is one slot a token: a buffer of one row a token would
send every step whose router leans towards the held experts down the dense
branch; PERF.md section 6, PR 33), plus one tile of padding an expert; the
tiles past the rows in use are skipped by the kernels. The worst case, ``tokens x min(top_k,
E_held)`` rows, is seven times that buffer at 8 a token: by count about
2 GB more of temporaries a layer at 8,192 tokens x 2,304 (the gathered rows,
their hidden rows, the float32 rows of the combine), which does not fit
beside the training state on a 16 GB chip. So a step whose routing sends more rows
here than the buffer holds takes the other branch of one ``lax.cond`` inside
the same program: every held expert over every token, masked, which needs
no buffer. No slot is dropped on either branch and no shape depends on the
routing, so nothing re-traces. Both branches run under the name scope
``mxtpu_moe``, which XLA keeps in each instruction's ``op_name``: a device
trace finds the layer's routed part there whichever branch ran, where the
``mxtpu_moe_gmm`` / ``_tgmm`` events exist on the sorted branch only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import resolve_interpret, x32
from .flash_attention import _dot_precision as _precision

TILE = 128


def _width_tile(n, cap=768):
    """The widest multiple of 128 that divides ``n`` and is at most ``cap``;
    ``n`` itself where it is no multiple of 128 (toy widths)."""
    if n % 128:
        return n
    best = 128
    for t in range(128, min(n, cap) + 1, 128):
        if n % t == 0:
            best = t
    return best


# ---- routing tables ----------------------------------------------------------------

def dispatch_tables(expert_ids, lo, n_held, capacity_rows, tile=TILE):
    """From the chosen experts (T, k) int32: where each held slot's row sits.

    Returns a dict of int32 arrays: ``counts`` (E_held,) slots per held
    expert; ``row_slot`` (R,) the flat slot (token * k + choice) each buffer
    row computes, and ``row_valid`` (R,) bool; ``tile_expert`` (R / tile,)
    the local expert of each row tile (that of the last tile in use for the
    tiles past it); ``tiles_used`` (); ``rows_needed`` () the rows the
    routing asks for, padding included; R = capacity_rows + n_held * tile
    rounded up to a tile."""
    t, k = expert_ids.shape
    rows = -(-(capacity_rows + n_held * tile) // tile) * tile
    local = expert_ids.reshape(-1) - lo
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None],
                     axis=0, dtype=jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)   # held slots first, by expert
    padded = -(-counts // tile) * tile
    group_end = jnp.cumsum(padded)
    group_start = group_end - padded
    sorted_start = jnp.cumsum(counts) - counts
    rows_needed = group_end[-1]
    n_tiles = rows // tile
    tiles_used = jnp.minimum(rows_needed // tile, n_tiles)
    tile_first = jnp.arange(n_tiles, dtype=jnp.int32) * tile
    tile_expert = jnp.minimum(
        jnp.searchsorted(group_end, tile_first, side="right").astype(jnp.int32),
        n_held - 1)
    last_used = tile_expert[jnp.maximum(tiles_used - 1, 0)]
    tile_expert = jnp.where(jnp.arange(n_tiles) < tiles_used, tile_expert, last_used)
    row = jnp.arange(rows, dtype=jnp.int32)
    e = jnp.repeat(tile_expert, tile)
    within = row - group_start[e]
    row_valid = (row < rows_needed) & (within < counts[e])
    src = jnp.clip(sorted_start[e] + within, 0, t * k - 1)
    row_slot = jnp.where(row_valid, order[src], 0)
    return {"counts": counts, "row_slot": row_slot, "row_valid": row_valid,
            "tile_expert": tile_expert, "tiles_used": tiles_used.astype(jnp.int32),
            "rows_needed": rows_needed.astype(jnp.int32)}


# ---- the grouped matmul -------------------------------------------------------------

def _gmm_kernel(te_ref, nu_ref, x_ref, w_ref, o_ref, *, dims, precision):
    i = pl.program_id(1)

    @pl.when(i < nu_ref[0])
    def _():
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], (dims, ((), ())), precision=precision,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= nu_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _tgmm_kernel(te_ref, nu_ref, dy_ref, x_ref, o_ref, acc, *, precision):
    i = pl.program_id(1)
    nu = nu_ref[0]
    last = pl.num_programs(1) - 1
    prev = te_ref[jnp.maximum(i - 1, 0)]
    nxt = te_ref[jnp.minimum(i + 1, last)]
    here = te_ref[i]
    used = i < nu

    @pl.when(used & ((i == 0) | (prev != here)))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(used)
    def _():
        acc[...] += lax.dot_general(
            dy_ref[...], x_ref[...], (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    @pl.when(used & ((i == nu - 1) | (nxt != here)))
    def _():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _row_block(i, nu):
    return jnp.maximum(jnp.minimum(i, nu[0] - 1), 0)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=48 * 1024 * 1024)


@x32
def _gmm_pallas(x, w, tile_expert, tiles_used, transpose_rhs, tile, interpret):
    """x (R, K) by each row tile's expert. ``transpose_rhs``: w is
    (E, N, K) and the product x w^T; else w is (E, K, N)."""
    r, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tn = _width_tile(n)
    if transpose_rhs:
        w_spec = pl.BlockSpec((1, tn, k), lambda j, i, te, nu: (te[i], j, 0))
        dims = ((1,), (1,))
    else:
        w_spec = pl.BlockSpec((1, k, tn), lambda j, i, te, nu: (te[i], 0, j))
        dims = ((1,), (0,))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, dims=dims, precision=_precision(x.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, r // tile),
            in_specs=[pl.BlockSpec((tile, k),
                                   lambda j, i, te, nu: (_row_block(i, nu), 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tile, tn), lambda j, i, te, nu: (i, j))),
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        compiler_params=_PARAMS, interpret=interpret, name="mxtpu_moe_gmm",
    )(tile_expert, tiles_used.reshape(1), x, w)


@x32
def _tgmm_pallas(dy, x, tile_expert, tiles_used, n_experts, tile, interpret):
    """(E, N, K): each expert's dy^T x over its own rows."""
    r, n = dy.shape
    k = x.shape[1]
    tn = _width_tile(n, cap=256)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, precision=_precision(x.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, r // tile),
            in_specs=[pl.BlockSpec((tile, tn),
                                   lambda j, i, te, nu: (_row_block(i, nu), j)),
                      pl.BlockSpec((tile, k),
                                   lambda j, i, te, nu: (_row_block(i, nu), 0))],
            out_specs=pl.BlockSpec((1, tn, k), lambda j, i, te, nu: (te[i], j, 0)),
            scratch_shapes=[pltpu.VMEM((tn, k), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_experts, n, k), x.dtype),
        compiler_params=_PARAMS, interpret=interpret, name="mxtpu_moe_tgmm",
    )(tile_expert, tiles_used.reshape(1), dy, x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gmm_nt(x, w, tile_expert, tiles_used, counts, tile, interpret):
    return _gmm_pallas(x, w, tile_expert, tiles_used, True, tile, interpret)


def _gmm_nt_fwd(x, w, tile_expert, tiles_used, counts, tile, interpret):
    y = _gmm_pallas(x, w, tile_expert, tiles_used, True, tile, interpret)
    return y, (x, w, tile_expert, tiles_used, counts)


def _gmm_nt_bwd(tile, interpret, res, dy):
    x, w, tile_expert, tiles_used, counts = res
    dx = _gmm_pallas(dy, w, tile_expert, tiles_used, False, tile, interpret)
    dw = _tgmm_pallas(dy, x, tile_expert, tiles_used, w.shape[0], tile, interpret)
    dw = jnp.where((counts > 0)[:, None, None], dw, jnp.zeros_like(dw))
    return dx, dw, None, None, None


_gmm_nt.defvjp(_gmm_nt_fwd, _gmm_nt_bwd)


def grouped_matmul(x, w, tables, tile=TILE, use_kernel=False, interpret=None):
    """Rows ``x`` (R, K), sorted by expert in whole tiles as ``tables`` says,
    times their expert's ``w`` (E_held, N, K), transposed: (R, N). Rows of
    the tiles past ``tiles_used`` give zeros."""
    if use_kernel:
        return _gmm_nt(x, w, tables["tile_expert"], tables["tiles_used"],
                       tables["counts"], tile, resolve_interpret(interpret))
    n_tiles = x.shape[0] // tile
    xt = x.reshape(n_tiles, tile, x.shape[1])
    y = jnp.einsum("tmk,tnk->tmn", xt, w[tables["tile_expert"]],
                   precision=_precision(x.dtype),
                   preferred_element_type=jnp.float32)
    used = jnp.arange(n_tiles) < tables["tiles_used"]
    y = jnp.where(used[:, None, None], y, 0.0)
    return y.reshape(x.shape[0], -1).astype(x.dtype)


# ---- the layer's arithmetic ----------------------------------------------------------

def route(x, router_weight, score_bias, top_k, scaling, renormalize=True,
          score="sigmoid"):
    """The router in float32: scores over all experts (``score``: each
    expert's ``sigmoid``, or a ``softmax`` over them), the top ``top_k`` of
    score + bias (``score_bias`` None: of the score), and the chosen scores
    as weights (normalised over the chosen, times ``scaling``). Returns
    (expert ids (T, k), weights (T, k))."""
    f32 = jnp.float32
    logits = jnp.matmul(x.astype(f32), router_weight.astype(f32).T,
                        precision=lax.Precision.HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"route: score {score!r} is neither sigmoid nor softmax")
    biased = scores if score_bias is None \
        else scores + score_bias.astype(f32)[None]
    _, ids = lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    if renormalize:
        chosen = chosen / jnp.sum(chosen, axis=1, keepdims=True)
    return ids.astype(jnp.int32), chosen * scaling


def _gated(h, width):
    return (jax.nn.silu(h[..., :width].astype(jnp.float32))
            * h[..., width:].astype(jnp.float32)).astype(h.dtype)


@jax.named_scope("mxtpu_moe")
def experts_held(x, ids, weights, gate_up, down, lo, use_kernel=False,
                 interpret=None, tile=TILE, capacity_rows=None):
    """The held experts' part of the layer: sum over the chosen slots whose
    expert is in [lo, lo + E_held) of weight * E(x). x (T, D); gate_up
    (E_held, 2F, D), rows [0, F) the gate and [F, 2F) the up projection;
    down (E_held, D, F). Returns (y (T, D), counts (E_held,) int32,
    unplaced () int32: the held slots that the branch taken did not
    compute, a check of the tables that reads 0). ``capacity_rows``: the
    sorted branch's row buffer before padding (default: a row a token)."""
    t, d = x.shape
    n_held, two_f, _ = gate_up.shape
    f = two_f // 2
    k = ids.shape[1]
    tables = dispatch_tables(ids, lo, n_held, capacity_rows or t, tile)
    rows = tables["row_slot"].shape[0]
    flat_w = weights.reshape(-1)

    def sorted_rows(_):
        token = tables["row_slot"] // k
        xs = x[token]
        h = grouped_matmul(xs, gate_up, tables, tile, use_kernel, interpret)
        ys = grouped_matmul(_gated(h, f), down, tables, tile, use_kernel, interpret)
        wrow = jnp.where(tables["row_valid"], flat_w[tables["row_slot"]], 0.0)
        ys = jnp.where(tables["row_valid"][:, None], ys.astype(jnp.float32), 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[token].add(ys * wrow[:, None])
        return y, jnp.sum(tables["row_valid"], dtype=jnp.int32)

    @jax.named_scope("mxtpu_moe_dense")     # a trace tells the branch taken
    def every_expert(_):
        local = ids - lo
        prec = _precision(x.dtype)

        @jax.checkpoint     # the backward rebuilds an expert's hidden rows
        def term(e):
            w_tok = jnp.sum(jnp.where(local == e, weights, 0.0), axis=1)
            h = jnp.matmul(x, gate_up[e].T, precision=prec,
                           preferred_element_type=jnp.float32).astype(x.dtype)
            out = jnp.matmul(_gated(h, f), down[e].T, precision=prec,
                             preferred_element_type=jnp.float32)
            return out * w_tok[:, None]

        def one(y, e):
            return y + term(e), None

        y, _ = lax.scan(one, jnp.zeros((t, d), jnp.float32),
                        jnp.arange(n_held, dtype=jnp.int32))
        return y, jnp.sum(tables["counts"], dtype=jnp.int32)

    fits = tables["rows_needed"] <= rows
    y, computed = lax.cond(fits, sorted_rows, every_expert, None)
    unplaced = jnp.sum(tables["counts"], dtype=jnp.int32) - computed
    return y.astype(x.dtype), tables["counts"], unplaced
