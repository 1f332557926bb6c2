"""Mixture-of-Experts layer — the substrate for the paper's Exp4 study.

Four execution strategies (RunCtx.moe_strategy):

  "dropless"     exact token-choice routing: sort by expert + ragged gmm
                 (Pallas kernel on TPU). Used by the serving engine.
  "capacity"     local capacity-buffer dispatch (scatter, no giant one-hot
                 einsum) + dense per-expert matmuls. Pure-local: used on CPU
                 tests and as the building block of the sharded paths.
  "tp_shardmap"  the paper's *baseline* "original TP solution": experts
                 replicated across the data axis, expert FFN sharded on the
                 model axis; down-proj partials psum over TP. No all-to-all.
  "ep_shardmap"  the paper's *hybrid TP x EP*: experts sharded over the EP
                 axis (all-to-all dispatch/return), expert FFN sharded over
                 the TP axis. Explicit lax.all_to_all => collective bytes are
                 visible to the roofline.

All strategies share the router and are validated against each other.
Each takes the routed experts' ``wg``/``wu``/``wd`` as ``(L, E, ...)`` stacks
of every layer of the scan, with the layer to run: dropless hands both to the
kernel, which reads the layer's blocks in place; the others slice the layer
out themselves.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.moe_gmm import gmm
from repro.models.common import RunCtx, act_fn


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# --------------------------------------------------------------------------
# Router
# --------------------------------------------------------------------------
def router_topk(xf, router_w, k: int, norm: bool = True):
    """xf (T, d) -> (topw (T,k) f32, topi (T,k) i32, aux scalar). ``norm``
    renormalizes the k softmax weights to sum 1 (``MoEConfig.norm_topk``);
    without it they stay the full softmax's probabilities."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    if norm:
        topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True), 1e-9)
    E = probs.shape[-1]
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    f = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(axis=1), axis=0) / k
    pmean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f * pmean)
    return topw, topi.astype(jnp.int32), aux


def experts_reached(topi, E: int, live=None):
    """Experts that at least one live token was routed to: ``topi`` (T, k)
    from ``router_topk``; ``live`` (T,) marks the tokens that count (None:
    every token). An int32 scalar."""
    w = None if live is None else jnp.repeat(live.astype(jnp.int32), topi.shape[1])
    return jnp.count_nonzero(jnp.bincount(topi.reshape(-1), weights=w, length=E)
                             ).astype(jnp.int32)


# --------------------------------------------------------------------------
# Capacity dispatch/combine (scatter-based; no (T,E,C) one-hot einsum)
# --------------------------------------------------------------------------
def capacity_dispatch(xf, topi, E: int, cap: int):
    T, K = topi.shape
    e = topi.reshape(-1)                                       # (TK,)
    oh = jax.nn.one_hot(e, E, dtype=jnp.int32)                 # id >= E (trash) -> all-zero row
    pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1    # (TK,) slot in expert
    keep = (pos >= 0) & (pos < cap)
    e_safe = jnp.where(keep, e, E)                             # trash row E
    p_safe = jnp.where(keep, pos, 0)
    tok = jnp.arange(T * K) // K
    ebuf = jnp.zeros((E + 1, cap, xf.shape[-1]), xf.dtype).at[e_safe, p_safe].set(xf[tok])
    return ebuf[:E], (e_safe, p_safe, keep)


def capacity_combine(ye, info, topw):
    """ye (E, cap, d) expert outputs -> (T, d) weighted combine."""
    e_safe, p_safe, keep = info
    T, K = topw.shape
    ybuf = jnp.concatenate([ye, jnp.zeros((1,) + ye.shape[1:], ye.dtype)], axis=0)
    rows = ybuf[e_safe, p_safe].astype(jnp.float32)            # (TK, d)
    w = topw.reshape(-1)[:, None] * keep[:, None]
    return (rows * w).reshape(T, K, -1).sum(axis=1)


def expert_ffn_dense(ebuf, wg, wu, wd):
    """(E, C, d) x (E, d, f) -> (E, C, d). Dense, MXU-aligned."""
    h1 = jnp.einsum("ecd,edf->ecf", ebuf, wg)
    h2 = jnp.einsum("ecd,edf->ecf", ebuf, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h1) * h2, wd)


def _shared_ffn(p_shared, xf, act_name):
    h = jnp.einsum("td,df->tf", xf, p_shared["wi"])
    g = jnp.einsum("td,df->tf", xf, p_shared["wg"])
    return jnp.einsum("tf,fd->td", act_fn(act_name)(g) * h, p_shared["wo"])


# --------------------------------------------------------------------------
# Strategy: dropless (sort + ragged gmm) — serving engine path
# --------------------------------------------------------------------------
def moe_dropless(p, xf, cfg: ModelConfig, ctx: RunCtx, layer, live=None):
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    T, d = xf.shape
    topw, topi, aux = router_topk(xf, p["router"], K, m.norm_topk)
    e = topi.reshape(-1)
    tok = jnp.arange(T * K) // K
    order = jnp.argsort(e)
    xs = xf[tok[order]]
    gs = jnp.bincount(e, length=E).astype(jnp.int32)
    kw = dict(backend=ctx.attn_backend, interpret=ctx.interpret)
    h1 = gmm(xs, p["wg"], gs, layer, **kw)
    h2 = gmm(xs, p["wu"], gs, layer, **kw)
    ys = gmm((jax.nn.silu(h1.astype(jnp.float32)) * h2.astype(jnp.float32)).astype(xs.dtype),
             p["wd"], gs, layer, **kw)
    w_flat = topw.reshape(-1)[order]
    y = jnp.zeros((T, d), jnp.float32).at[tok[order]].add(ys.astype(jnp.float32) * w_flat[:, None])
    if "shared" in p:
        y = y + _shared_ffn(p["shared"], xf, cfg.act).astype(jnp.float32)
    return y.astype(xf.dtype), aux, experts_reached(topi, E, live)


# --------------------------------------------------------------------------
# Strategy: capacity (pure local)
# --------------------------------------------------------------------------
def moe_capacity(p, xf, cfg: ModelConfig, ctx: RunCtx, layer, live=None):
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    T, d = xf.shape
    cap = _round_up(max(int(math.ceil(T * K / E * m.capacity_factor)), 8), 8)
    topw, topi, aux = router_topk(xf, p["router"], K, m.norm_topk)
    ebuf, info = capacity_dispatch(xf, topi, E, cap)
    ye = expert_ffn_dense(ebuf, p["wg"][layer], p["wu"][layer], p["wd"][layer])
    y = capacity_combine(ye, info, topw)
    if "shared" in p:
        y = y + _shared_ffn(p["shared"], xf, cfg.act).astype(jnp.float32)
    return y.astype(xf.dtype), aux, experts_reached(topi, E, live)


# --------------------------------------------------------------------------
# Strategies: tp_shardmap / ep_shardmap (explicit collectives)
# --------------------------------------------------------------------------
def _moe_local_tp(xf, router_w, wg, wu, wd, shared, cfg, tp_axis, cf):
    """Inside shard_map: experts REPLICATED on the ep axis, FFN dim sharded on
    tp. xf (T_l, d). Down-proj partials psum over tp."""
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    T = xf.shape[0]
    cap = _round_up(max(int(math.ceil(T * K / E * cf)), 8), 8)
    topw, topi, aux = router_topk(xf, router_w, K, m.norm_topk)
    ebuf, info = capacity_dispatch(xf, topi, E, cap)
    h1 = jnp.einsum("ecd,edf->ecf", ebuf, wg)
    h2 = jnp.einsum("ecd,edf->ecf", ebuf, wu)
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h1) * h2, wd)     # partial over f
    ye = jax.lax.psum(ye, tp_axis)
    y = capacity_combine(ye, info, topw)
    if shared is not None:
        hs = jnp.einsum("td,df->tf", xf, shared["wi"])
        gs_ = jnp.einsum("td,df->tf", xf, shared["wg"])
        ys = jnp.einsum("tf,fd->td", act_fn(cfg.act)(gs_) * hs, shared["wo"])
        y = y + jax.lax.psum(ys, tp_axis).astype(jnp.float32)
    return y.astype(xf.dtype), aux


def _a2a_int8(buf, axis_name):
    """int8-compressed all-to-all (beyond-paper): quantize rows per-row
    absmax, exchange int8 payload + f32 scales — halves the dispatch bytes on
    the ICI. Exact to ~0.4% per row (validated in tests)."""
    amax = jnp.max(jnp.abs(buf.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(buf.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    q = jax.lax.all_to_all(q, axis_name, 0, 0)
    scale = jax.lax.all_to_all(scale, axis_name, 0, 0)
    return (q.astype(jnp.float32) * scale).astype(buf.dtype)


def _moe_local_ep(xf, router_w, wg, wu, wd, shared, cfg, ep_axis, tp_axis, cf,
                  a2a_quant: bool = False):
    """Inside shard_map: hybrid TP x EP. Experts sharded on ep axis (explicit
    all-to-all dispatch/return), FFN dim sharded on tp axis."""
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    dp = jax.lax.axis_size(ep_axis)
    E_l = E // dp
    T, d = xf.shape
    topw, topi, aux = router_topk(xf, router_w, K, m.norm_topk)
    e = topi.reshape(-1)                                          # (TK,) global expert
    tok = jnp.arange(T * K) // K

    # --- stage 1: dispatch to the shard owning each expert ------------------
    dest = e // E_l
    ohd = jax.nn.one_hot(dest, dp, dtype=jnp.int32)
    posd = jnp.sum(jnp.cumsum(ohd, axis=0) * ohd, axis=-1) - 1
    # min-capacity 4 (not 8): at decode (few tokens/device) the dispatch is
    # padding-dominated — §Perf cell A iter 4 measured the a2a halving.
    cap_s = _round_up(max(int(math.ceil(T * K * cf / dp)), 4), 4)
    keep = posd < cap_s
    d_safe = jnp.where(keep, dest, dp)
    p_safe = jnp.where(keep, posd, 0)
    sx = jnp.zeros((dp + 1, cap_s, d), xf.dtype).at[d_safe, p_safe].set(xf[tok])
    se = jnp.zeros((dp + 1, cap_s), jnp.int32).at[d_safe, p_safe].set((e % E_l).astype(jnp.int32))
    sv = jnp.zeros((dp + 1, cap_s), jnp.int32).at[d_safe, p_safe].set(keep.astype(jnp.int32))
    if a2a_quant:
        rx = _a2a_int8(sx[:dp], ep_axis)                          # (dp, cap_s, d)
    else:
        rx = jax.lax.all_to_all(sx[:dp], ep_axis, 0, 0)
    re = jax.lax.all_to_all(se[:dp], ep_axis, 0, 0)
    rv = jax.lax.all_to_all(sv[:dp], ep_axis, 0, 0)

    # --- stage 2: local expert FFN over received rows (capacity buffers) ----
    R = dp * cap_s
    rxf, ref_, rvf = rx.reshape(R, d), re.reshape(R), rv.reshape(R) > 0
    e2 = jnp.where(rvf, ref_, E_l)                                # invalid -> trash id
    cap2 = _round_up(max(int(math.ceil(R / E_l * cf)), 8), 8)
    ebuf2, info2 = capacity_dispatch(rxf, e2[:, None], E_l, cap2)
    h1 = jnp.einsum("ecd,edf->ecf", ebuf2, wg)                    # f_l local (TP)
    h2 = jnp.einsum("ecd,edf->ecf", ebuf2, wu)
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h1) * h2, wd)     # partial over f
    ye = jax.lax.psum(ye, tp_axis)
    y_rows = capacity_combine(ye, info2, jnp.ones((R, 1), jnp.float32))   # (R, d) f32
    ybuf = y_rows.reshape(dp, cap_s, d).astype(xf.dtype)

    # --- stage 3: return + combine ------------------------------------------
    if a2a_quant:
        yret = _a2a_int8(ybuf, ep_axis)                           # rows for my sends
    else:
        yret = jax.lax.all_to_all(ybuf, ep_axis, 0, 0)
    yret = jnp.concatenate([yret, jnp.zeros((1, cap_s, d), yret.dtype)], axis=0)
    rows = yret[d_safe, p_safe].astype(jnp.float32)               # (TK, d)
    w = topw.reshape(-1)[:, None] * keep[:, None]
    y = (rows * w).reshape(T, K, d).sum(axis=1)
    if shared is not None:
        hs = jnp.einsum("td,df->tf", xf, shared["wi"])
        gs_ = jnp.einsum("td,df->tf", xf, shared["wg"])
        ys2 = jnp.einsum("tf,fd->td", act_fn(cfg.act)(gs_) * hs, shared["wo"])
        y = y + jax.lax.psum(ys2, tp_axis).astype(jnp.float32)
    return y.astype(xf.dtype), aux


def moe_sublayer(p: Dict[str, Any], h, cfg: ModelConfig, ctx: RunCtx, layer,
                 live=None) -> Tuple[Any, Any, Any]:
    """h: (B, S, d) normed input; ``p``'s ``wg``/``wu``/``wd`` stack the
    routed experts of every layer, ``layer`` (int32 scalar) the one to run;
    live (B, S) marks the tokens the experts counter counts (None: all).
    Returns (out (B,S,d), aux loss scalar, experts reached by live tokens;
    None on the sharded strategies)."""
    B, S, d = h.shape
    xf = h.reshape(B * S, d)
    live = None if live is None else live.reshape(B * S)
    strategy = ctx.moe_strategy
    if strategy in ("dropless", "capacity") or ctx.mesh is None:
        fn = moe_dropless if strategy == "dropless" else moe_capacity
        y, aux, reached = fn(p, xf, cfg, ctx, layer, live)
        return y.reshape(B, S, d), aux, reached

    from jax.experimental.shard_map import shard_map

    mesh = ctx.mesh
    ep_ax, tp_ax = ctx.ep_axis, ctx.tp_axis
    dp = mesh.shape[ep_ax]
    m = cfg.moe
    # batch shards on the mesh's data axis when divisible; otherwise tokens
    # replicated (decode at B=1). On the fixed production mesh the data axis
    # IS the ep axis; on the factored Exp4 mesh they differ.
    b_ax = "data" if "data" in mesh.axis_names else ep_ax
    bsz = mesh.shape[b_ax]
    bspec = b_ax if (B % bsz == 0 and B >= bsz) else None
    ep = strategy == "ep_shardmap" and m.num_experts % dp == 0 and m.num_experts >= dp
    espec = ep_ax if ep else None   # experts dim of wg/wu/wd

    shared = p.get("shared")
    shared_specs = (
        {"wi": P(None, tp_ax), "wg": P(None, tp_ax), "wo": P(tp_ax, None)}
        if shared is not None else None
    )
    in_specs = (
        P(bspec, None, None),                 # x (B,S,d)
        P(None, None),                        # router
        P(espec, None, tp_ax),                # wg (E, d, f)
        P(espec, None, tp_ax),                # wu
        P(espec, tp_ax, None),                # wd (E, f, d)
        shared_specs,
    )
    out_specs = (P(bspec, None, None), P())

    def local(x_l, router_w, wg, wu, wd, shared_l):
        xf_l = x_l.reshape(-1, d)
        if ep:
            y, aux = _moe_local_ep(xf_l, router_w, wg, wu, wd, shared_l, cfg,
                                   ep_ax, tp_ax, m.capacity_factor,
                                   a2a_quant=ctx.quant == "a2a_int8")
        else:
            y, aux = _moe_local_tp(xf_l, router_w, wg, wu, wd, shared_l, cfg,
                                   tp_ax, m.capacity_factor)
        aux = jax.lax.pmean(jax.lax.pmean(aux, ep_ax), tp_ax)
        return y.reshape(x_l.shape), aux

    y, aux = shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_rep=False,
    )(h, p["router"], p["wg"][layer], p["wu"][layer], p["wd"][layer], shared)
    return y, aux, None
