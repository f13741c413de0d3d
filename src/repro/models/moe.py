"""Mixture-of-Experts: a router over every published expert, the part of
the result that the experts held here give, and shared experts.

A layer holds ``num_experts`` of the router's ``num_experts_total``
experts, from ``first_expert`` on (one chip's share under expert
parallelism; all of them by default).  Served, every held expert runs on
every row, weighted by its gate (zero where it was not chosen): no token
drops, so a row's output is its own whatever else is in the batch.
Training keeps the sort-based capacity dispatch below (GShard-style
capacity, Megablocks-style sorted grouping — TPU-friendly static shapes).

Expert GEMMs are batched (E, C, d) x (E, d, ff) einsums; with 64-160
experts the per-expert token count C is small — exactly the tall-and-
skinny regime, which is why the paper's technique is first-class here
(see DESIGN.md §4).  Experts shard over the TP axis ('experts' logical
axis); the skinny capacity dim C is never sharded (the no-shard rule).

Dispatch is HIERARCHICAL (per data-shard groups): scatters/sorts run
per-group with G = |dp axes|, so SPMD keeps them fully local to each
device, and the only cross-device traffic is the (G, E, C, d) buffer's
data->model all-to-all.  The flat global-scatter formulation forced XLA
to replicate an O(T*k) x d buffer and all-reduce it (~10^13 bytes/step
for olmoe train_4k — EXPERIMENTS.md §Perf iteration A documents the
before/after).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.linear import in_serving_ctx, linear
from repro.models.layers import silu
from repro.models.param import ParamTree
from repro.sharding.context import get_ctx, shard_act


def init_moe(rng, cfg):
    d, ff, e = cfg.d_model, cfg.d_ff_expert, cfg.num_experts
    pt = ParamTree(rng, cfg.dtype)
    pt.dense("router", (d, cfg.num_experts_total), ("embed", "experts"),
             dtype="float32")
    pt.dense("w_gate", (e, d, ff), ("experts", "embed", "mlp"), fan_in=d)
    pt.dense("w_up", (e, d, ff), ("experts", "embed", "mlp"), fan_in=d)
    pt.dense("w_down", (e, ff, d), ("experts", "mlp", "embed"), fan_in=ff)
    if cfg.num_shared_experts:
        sff = ff * cfg.num_shared_experts
        pt.dense("ws_gate", (d, sff), ("embed", "mlp"))
        pt.dense("ws_up", (d, sff), ("embed", "mlp"))
        pt.dense("ws_down", (sff, d), ("mlp", "embed"))
    return pt.build()


def _capacity(tokens: int, e: int, k: int, factor: float) -> int:
    c = int(tokens * k * factor / e) + 1
    return max(8, -(-c // 8) * 8)  # sublane-align the skinny dim


def _dp_groups(t: int) -> int:
    """Dispatch-group count = data-parallel shard count (1 off-mesh)."""
    ctx = get_ctx()
    if ctx is None:
        return 1
    from repro.sharding.rules import axis_size
    dp = tuple(a for a in ctx.opts.dp_axes if a in ctx.mesh.shape)
    if not dp:
        return 1
    n = axis_size(ctx.mesh, dp)
    return n if n > 1 and t % n == 0 and t >= n else 1


@jax.named_scope("router")
def route(p, cfg, x):
    """x (..., d) -> (probs (..., E_total) f32, top-k weights, top-k
    expert ids): softmax over every published expert, the top
    ``experts_per_token`` of them, renormalised if ``norm_topk_prob``."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


@jax.named_scope("experts")
def held_experts(p, cfg, x, top_p, top_e):
    """The held experts' part of the routed output, dropping no token.

    x (t, d); top_p/top_e (t, k).  Every held expert runs on every row
    and each row's result is weighted by the gate its router gave that
    expert (0 where the expert is not among its top-k), so a row's output
    depends on that row alone.  Experts outside the held range
    ``[first_expert, first_expert + num_experts)`` add nothing here: they
    live on other chips."""
    e = cfg.num_experts
    local = top_e - cfg.first_expert                            # (t, k)
    gates = jnp.sum(jnp.where(local[..., None] == jnp.arange(e),
                              top_p[..., None], 0.0), axis=1)   # (t, e) f32
    xe = jnp.broadcast_to(x, (e,) + x.shape)                    # (e, t, d)
    h = (silu(jnp.einsum("etd,edf->etf", xe, p["w_gate"]))
         * jnp.einsum("etd,edf->etf", xe, p["w_up"]))
    y = jnp.einsum("etf,efd->etd", h, p["w_down"],
                   preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, gates).astype(x.dtype)


def _capacity_dispatch(p, cfg, x, top_p, top_e, capacity_factor: float):
    """Training's sort-based capacity dispatch: x (b, s, d) -> (b, s, d).
    A held expert takes at most its capacity of rows; the rest, and rows
    routed to experts not held here, drop."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.experts_per_token
    g = _dp_groups(t)
    tg = t // g
    cap = _capacity(tg, e, k, capacity_factor)

    xg = x.reshape(g, tg, d)
    xg = shard_act(xg, "batch", None, "embed")
    local = top_e.reshape(g, tg, k) - cfg.first_expert
    # an expert not held sorts after every held one and is dropped
    local = jnp.where((local >= 0) & (local < e), local, e)

    # ---- per-group sort-based dispatch (vmapped over groups) -----------
    def dispatch(xf, ef, wf):
        """xf (tg,d)  ef (tg,k)  wf (tg,k)."""
        flat_e = ef.reshape(-1)                              # (tg*k,)
        order = jnp.argsort(flat_e, stable=True)
        e_sorted = flat_e[order]
        rank = jnp.arange(tg * k) - jnp.searchsorted(e_sorted, e_sorted,
                                                     side="left")
        keep = (rank < cap) & (e_sorted < e)
        slot = jnp.where(keep, e_sorted * cap + rank, e * cap)
        tok = order // k
        buf = jnp.zeros((e * cap + 1, d), x.dtype)
        buf = buf.at[slot].set(jnp.where(keep[:, None], xf[tok], 0))
        return buf[:-1], slot, tok, keep, wf.reshape(-1)[order]

    buf, slot, tok, keep, w_sorted = jax.vmap(dispatch)(
        xg, local, top_p.reshape(g, tg, k))
    buf = buf.reshape(g, e, cap, d)
    # the data->model all-to-all happens HERE (G stays on dp, E moves to tp)
    buf = shard_act(buf, "batch", "experts", None, "embed")

    # ---- expert computation (batched TSMM-shaped GEMMs) ----------------
    h = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    h2 = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    h = silu(h) * h2
    h = shard_act(h, "batch", "experts", None, "mlp")
    y = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    y = shard_act(y, "batch", "experts", None, "embed")

    # ---- combine (per group, local again after the reverse all-to-all) --
    def combine(yf, slot_, tok_, keep_, ws):
        flat = yf.reshape(e * cap, d)
        gath = jnp.where(keep_[:, None],
                         flat[jnp.clip(slot_, 0, e * cap - 1)], 0)
        return jnp.zeros((tg, d), x.dtype).at[tok_].add(
            gath * ws[:, None].astype(x.dtype))

    out = jax.vmap(combine)(y, slot, tok, keep, w_sorted)
    return shard_act(out, "batch", None, "embed").reshape(b, s, d)


@jax.named_scope("mlp")
def moe_apply(p, cfg, x, *, capacity_factor: float = 0.0):
    """x: (B, S, d) -> (out, aux_loss).

    The router scores every published expert (``num_experts_total``);
    this layer computes the part of the result that its held experts
    give, plus the shared experts.  Served (``core.linear.serving_ctx``)
    it drops no token (:func:`held_experts`); training keeps the
    capacity dispatch."""
    b, s, d = x.shape
    t = b * s
    probs, top_p, top_e = route(p, cfg, x)
    if in_serving_ctx():
        out = held_experts(p, cfg, x.reshape(t, d), top_p.reshape(t, -1),
                           top_e.reshape(t, -1)).reshape(b, s, d)
    else:
        out = _capacity_dispatch(p, cfg, x, top_p, top_e,
                                 capacity_factor or cfg.capacity_factor)

    if cfg.num_shared_experts:
        hs = (linear(x, p["ws_gate"], act="silu", name="ws_gate")
              * linear(x, p["ws_up"], name="ws_up"))
        out = out + linear(hs, p["ws_down"], name="ws_down")

    # ---- load-balance aux loss (Switch/GShard form) ---------------------
    et, k = cfg.num_experts_total, cfg.experts_per_token
    me = probs.reshape(t, et).mean(0)
    ce = jnp.zeros((et,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (t * k)
    aux = et * jnp.sum(me * ce) * cfg.router_aux_coef

    return out, aux
