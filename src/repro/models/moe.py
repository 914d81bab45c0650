"""Mixture-of-Experts: explicit expert-parallel dispatch (shard_map) on a
mesh, sort-based ragged-free routing, capacity drop.

Three code paths (DESIGN.md §5):

1. `_moe_local` (rules is None) — single-device reference: sort-based
   dispatch + three batched einsums. The oracle for the distributed paths.

2. EP **all-to-all** (`e % dp == 0`, kimi: 384 experts / 16 data shards):
   tokens are SP-all-gathered over the model axis, routed locally, exchanged
   to their expert's owner with ONE `lax.all_to_all` over the data axis,
   computed with (expert->data, d_ff->model)-sharded weights, exchanged
   back, and the partial (over model) outputs return to sequence-parallel
   layout with a single `psum_scatter`. This is the production EP pattern —
   the dispatch never materializes a (tokens, E, capacity) one-hot and no
   token buffer is ever replicated.

3. EP **gathered-weights** (few experts, grok: 8 experts < 16 shards):
   every (data, model) rank keeps its own (batch x seq)-sharded tokens and
   transiently all-gathers the (d_ff over data x model)-sharded expert
   weights (ZeRO-3 style, 2-3 layer-sized all-gathers per block); no token
   movement at all. Chosen when the expert count cannot tile the mesh.

Gradients flow through both paths (all_to_all / all_gather transpose to
all_to_all / psum_scatter under AD).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.common import activation, is_glu, normal_init
from repro.sharding.rules import maybe_shard


def init_moe(key, cfg, dtype):
    d, ff, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "router": normal_init(k1, (d, e), d ** -0.5, jnp.float32),
        "wi": normal_init(k2, (e, d, ff), d ** -0.5, dtype),
        "wo": normal_init(k3, (e, ff, d), ff ** -0.5, dtype),
    }
    if is_glu(cfg.act):
        p["wg"] = normal_init(k4, (e, d, ff), d ** -0.5, dtype)
    return p


# ---------------------------------------------------------------------------
# shared routing pieces
# ---------------------------------------------------------------------------

def _route(router, cfg, xf):
    """xf (T, D) -> (gates (T,k), expert_ids (T,k), aux scalar)."""
    e, k = cfg.n_experts, cfg.experts_per_token
    logits = xf.astype(jnp.float32) @ router               # (T, E)
    gates, eids = lax.top_k(logits, k)
    gates = jax.nn.softmax(gates, axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    t = xf.shape[0]
    frac = jnp.zeros((e,), jnp.float32).at[eids.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(frac * probs.mean(0))
    return gates, eids, aux


def _dispatch(cfg, xf, eids, capacity):
    """Sort-based dispatch: returns (buf (E, C, D), keep, slot, token_of)."""
    e, k = cfg.n_experts, cfg.experts_per_token
    t, d = xf.shape
    flat_e = eids.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(t * k) - starts[sorted_e]
    keep = pos_in_e < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos_in_e, e * capacity)
    token_of = order // k
    buf = jnp.zeros((e * capacity + 1, d), xf.dtype)
    buf = buf.at[slot].set(xf[token_of], mode="drop")
    return buf[: e * capacity].reshape(e, capacity, d), keep, slot, token_of


def _expert_ffn(cfg, buf, wi, wg, wo):
    act = activation(cfg.act)
    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    if wg is not None:
        h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * h
    else:
        h = act(h)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _capacity(cfg, t: int) -> int:
    return int(max(1, math.ceil(
        cfg.capacity_factor * t * cfg.experts_per_token / cfg.n_experts)))


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def quantized_all_to_all(x, axis):
    """int8-payload all_to_all (split=concat=0): the wire carries int8 codes
    + one f32 scale per slot (beyond-paper §Perf: the paper's quantization
    theme applied to the EP dispatch). Backward carries full-width
    cotangents (a2a(0,0) is its own transpose)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-9) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    q8 = lax.all_to_all(q.astype(jnp.int8), axis, split_axis=0, concat_axis=0)
    s = lax.all_to_all(scale, axis, split_axis=0, concat_axis=0)
    return (q8.astype(jnp.float32) * s).astype(x.dtype)


def _qa2a_fwd(x, axis):
    return quantized_all_to_all(x, axis), None


def _qa2a_bwd(axis, _, g):
    return (lax.all_to_all(g, axis, split_axis=0, concat_axis=0),)


quantized_all_to_all.defvjp(_qa2a_fwd, _qa2a_bwd)


# ---------------------------------------------------------------------------
# path 1: local reference (rules=None; also the smoke-test oracle)
# ---------------------------------------------------------------------------

def _moe_local(params, cfg, x):
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    gates, eids, aux = _route(params["router"], cfg, xf)
    capacity = _capacity(cfg, t)
    buf, keep, slot, token_of = _dispatch(cfg, xf, eids, capacity)
    out_buf = _expert_ffn(cfg, buf, params["wi"], params.get("wg"),
                          params["wo"])
    out_flat = out_buf.reshape(-1, d)
    gathered = jnp.where(keep[:, None],
                         out_flat[jnp.minimum(slot, out_flat.shape[0] - 1)],
                         0.0)
    order = jnp.argsort(eids.reshape(-1), stable=True)
    w = gates.reshape(-1)[order][:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[token_of].add(gathered * w)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# paths 2 & 3: expert-parallel on the mesh
# ---------------------------------------------------------------------------

def _dispatch_size(rules) -> int:
    if not rules.expert:
        return 1
    n = 1
    for a in rules.expert:
        n *= int(rules.mesh.shape[a])
    return n


def _ep_mode(cfg, rules) -> str:
    dp = _dispatch_size(rules)
    if cfg.n_experts >= dp and cfg.n_experts % dp == 0 and dp > 1:
        return "alltoall"
    return "gathered"


def _moe_ep(params, cfg, x, rules):
    mesh = rules.mesh
    b, s, d = x.shape
    tp = rules.tp
    seq_sharded = s % tp == 0 and s > 1
    mode = _ep_mode(cfg, rules)
    dp_ax = tuple(rules.expert)  # a2a spans every expert axis (incl. pods)
    dp = _dispatch_size(rules)
    all_axes = tuple(mesh.axis_names)
    glu = is_glu(cfg.act)

    x_in_spec = P(rules.batch, rules.model if seq_sharded else None, None)
    if mode == "alltoall":
        w_spec = {"router": P(), "wi": P(rules.expert, None, rules.model),
                  "wo": P(rules.expert, rules.model, None)}
    else:
        w_spec = {"router": P(), "wi": P(None, None, rules.ff_wide),
                  "wo": P(None, rules.ff_wide, None)}
    if glu:
        w_spec["wg"] = w_spec["wi"]
    if mode == "alltoall" and seq_sharded:
        x_out_spec = P(rules.batch, rules.model, None)
    elif mode == "gathered" and seq_sharded:
        x_out_spec = P(rules.batch, rules.model, None)
    else:
        x_out_spec = P(rules.batch, None, None)

    def body(x_l, p_l):
        if mode == "alltoall" and seq_sharded:
            x_l = lax.all_gather(x_l, rules.model, axis=1, tiled=True)
        bl, sl, _ = x_l.shape
        t = bl * sl
        xf = x_l.reshape(t, d)
        gates, eids, aux = _route(p_l["router"], cfg, xf)
        aux = lax.pmean(aux, all_axes)
        capacity = _capacity(cfg, t)
        buf, keep, slot, token_of = _dispatch(cfg, xf, eids, capacity)

        if mode == "alltoall":
            e_loc = cfg.n_experts // dp
            # layout-preserving exchange: buf rows are expert-major
            # (e = src_dev * e_loc + j), so (dp, e_loc, C, d) is a free view
            # and the expert FFN runs directly on the exchanged layout with
            # j as the batch dim — no 2+ GiB transposes (§Perf iteration).
            send = buf.reshape(dp, e_loc, capacity, d)
            if cfg.moe_a2a_int8:
                recv = quantized_all_to_all(send, dp_ax)
            else:
                recv = lax.all_to_all(send, dp_ax, split_axis=0,
                                      concat_axis=0)
            act = activation(cfg.act)
            h = jnp.einsum("sjcd,jdf->sjcf", recv, p_l["wi"])
            if glu:
                h = act(jnp.einsum("sjcd,jdf->sjcf", recv, p_l["wg"])) * h
            else:
                h = act(h)
            out = jnp.einsum("sjcf,jfd->sjcd", h, p_l["wo"])  # partial/model
            if cfg.moe_a2a_int8 and not seq_sharded:
                # return path can only be quantized when outputs are NOT
                # partial sums over the model axis (quantizing partials
                # before the psum_scatter would compound error) — decode.
                out_buf = quantized_all_to_all(out, dp_ax)
            else:
                out_buf = lax.all_to_all(out, dp_ax, split_axis=0,
                                         concat_axis=0)
            out_buf = out_buf.reshape(cfg.n_experts, capacity, d)
        else:
            wi = lax.all_gather(p_l["wi"], rules.ff_wide, axis=2, tiled=True)
            wo = lax.all_gather(p_l["wo"], rules.ff_wide, axis=1, tiled=True)
            wg = lax.all_gather(p_l["wg"], rules.ff_wide, axis=2,
                                tiled=True) if glu else None
            out_buf = _expert_ffn(cfg, buf, wi, wg, wo)  # complete

        out_flat = out_buf.reshape(-1, d)
        gathered = jnp.where(
            keep[:, None],
            out_flat[jnp.minimum(slot, out_flat.shape[0] - 1)], 0.0)
        order = jnp.argsort(eids.reshape(-1), stable=True)
        w = gates.reshape(-1)[order][:, None].astype(x_l.dtype)
        y = jnp.zeros((t, d), x_l.dtype).at[token_of].add(gathered * w)
        y = y.reshape(bl, sl, d)

        if mode == "alltoall":
            if seq_sharded:   # partial over model -> back to SP in one op
                y = lax.psum_scatter(y, rules.model, scatter_dimension=1,
                                     tiled=True)
            else:
                y = lax.psum(y, rules.model)
        return y, aux

    wrapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_in_spec, w_spec),
        out_specs=(x_out_spec, P()),
        check_vma=False,
    )
    p_used = {k: params[k] for k in w_spec.keys()}
    return wrapped(x, p_used)


def moe_block(params, cfg, x, rules=None):
    """x (B, S, D) -> ((B, S, D), aux_loss)."""
    if rules is not None and getattr(rules, "mesh", None) is not None:
        return _moe_ep(params, cfg, x, rules)
    y, aux = _moe_local(params, cfg, x)
    batch_ax = rules.batch if rules else None
    y = maybe_shard(y, (batch_ax, None, None), rules)
    return y, aux
