"""The LM: scanned layer-group decoder covering every assigned family.

dense / moe   : decoder-only, GQA attention, SwiGLU or MoE MLP
ssm           : mamba2 (attention-free)
hybrid        : jamba (period-8 mamba/attention pattern, alternating MoE)
encdec        : seamless (bidirectional encoder + cross-attention decoder)
vlm           : phi-3-vision (patch embeddings prepended via a real projector)

Repeated layers are stacked and executed with ``lax.scan`` over the group's
repeats (small HLO, fast 512-device compiles, remat-friendly). The decode path
consumes either dense ring-buffer caches or the paged KV pool.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerGroup, ModelConfig
from repro.models import params as params_lib
from repro.models.attention import attention_sublayer
from repro.models.common import RunCtx, dense_mlp, rmsnorm, shard_act
from repro.models.mamba import mamba_sublayer
from repro.models.mla import mla_sublayer, pool_width
from repro.models.moe import moe_sublayer

_ROUTED = ("wg", "wu", "wd")


def _split_routed(gp):
    """Take the routed experts out of a group's per-position params: returns
    (the rest, per position its ``wg``/``wu``/``wd`` stacks or None). The
    layer loop slices the rest a layer at a time; the stacks reach the experts
    whole, with the layer's index, so the kernel reads the layer's blocks in
    place and no layer's experts are copied out of the stack."""
    rest, stacks = [], []
    for pp in gp:
        moe = pp.get("moe")
        if moe is None:
            rest.append(pp)
            stacks.append(None)
        else:
            rest.append({**pp, "moe": {k: v for k, v in moe.items() if k not in _ROUTED}})
            stacks.append({k: moe[k] for k in _ROUTED})
    return rest, stacks


def _with_routed(p_sl, stacks):
    """One layer's params (``p_sl``, per position) with the routed stacks put back."""
    return [pp if st is None else {**pp, "moe": {**pp["moe"], **st}}
            for pp, st in zip(p_sl, stacks)]


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ params
    def param_specs(self):
        return params_lib.param_specs(self.cfg)

    def init_params(self, rng, dtype=jnp.float32):
        return params_lib.init_params(self.cfg, rng, dtype)

    def abstract_params(self, dtype=jnp.bfloat16):
        return params_lib.abstract_params(self.cfg, dtype)

    # ------------------------------------------------------------------ layers
    def _apply_layer(self, p, x, c, *, kind: str, ctx: RunCtx, layer,
                     positions, memory, page_table, lengths, chunk=None):
        """``layer``: this layer's index in its group's stacks, where ``p``'s
        routed experts are the whole stacks (``_split_routed``)."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        reached = None
        new_c: Dict[str, Any] = {} if c is not None else None

        # sequence-parallel placement: constraining each sublayer OUTPUT to
        # the seq-sharded layout (before the residual add) lets GSPMD turn the
        # TP partial-sum all-reduce into a reduce-scatter (no-op when the
        # "seq" rule is off).
        seq_sharded = ("batch", "seq", None)
        h = rmsnorm(x, p["ln1"], cfg.rms_eps)
        if kind == "M":
            sub, cm = mamba_sublayer(p["ssm"], h, cfg, ctx,
                                     cache=c.get("ssm") if c else None,
                                     chunk=chunk)
            if new_c is not None:
                new_c["ssm"] = cm
        else:
            kw = dict(cache=c.get("attn") if c else None, positions=positions,
                      page_table=page_table, lengths=lengths, chunk=chunk)
            if cfg.mla is not None:
                sub, ca = mla_sublayer(p["attn"], h, ctx, cfg, **kw)
            else:
                sub, ca = attention_sublayer(p["attn"], h, ctx, cfg, kind, **kw)
            if new_c is not None and ca is not None:
                new_c["attn"] = ca
        x = x + shard_act(sub, seq_sharded)

        if "cross" in p:
            hx = rmsnorm(x, p["ln_x"], cfg.rms_eps)
            sub, cx = attention_sublayer(
                p["cross"], hx, ctx, cfg, "X",
                cache=c.get("cross") if c else None, memory=memory, chunk=chunk)
            if new_c is not None and cx is not None:
                new_c["cross"] = cx
            x = x + shard_act(sub, seq_sharded)

        if "moe" in p:
            h2 = rmsnorm(x, p["ln2"], cfg.rms_eps)
            mo, aux, reached = moe_sublayer(p["moe"], h2, cfg, ctx, layer,
                                            live=chunk["valid"] if chunk is not None else None)
            x = x + shard_act(mo, seq_sharded)
        elif "mlp" in p:
            h2 = rmsnorm(x, p["ln2"], cfg.rms_eps)
            x = x + shard_act(dense_mlp(p["mlp"], h2, cfg.act), seq_sharded)
        return x, new_c, aux, reached

    def _run_groups(self, groups_params, x, cache, *, ctx: RunCtx, layer_groups,
                    positions=None, memory=None, page_table=None, lengths=None,
                    kinds_override: Optional[str] = None, chunk=None):
        """Scan each layer group. Returns (x, new_cache, aux_sum, experts):
        ``experts`` (n_moe_layers,) int32 holds, in layer order, the experts
        each MoE layer's live tokens reached (``moe.experts_reached``; live
        is the chunk's ``valid`` mask; the sharded strategies count none)."""
        aux_total = jnp.zeros((), jnp.float32)
        experts: List[Any] = []
        new_groups_cache: List[Any] = []
        for gi, g in enumerate(layer_groups):
            gp, stacks = _split_routed(groups_params[gi]["layers"])
            gc = cache["groups"][gi] if cache is not None else None
            pattern = kinds_override or g.pattern

            if not ctx.scan_layers:
                # unrolled python loop (roofline cost lowering: XLA counts
                # loop bodies once, so the cost model must not use scan)
                new_gc = gc
                for r in range(g.repeats):
                    p_sl = _with_routed(jax.tree.map(lambda x: x[r], gp), stacks)
                    c_sl = (jax.tree.map(lambda x: x[r], new_gc)
                            if new_gc is not None else None)
                    for pos, kind in enumerate(pattern):
                        cpos = c_sl[pos] if c_sl is not None else None

                        def run_layer(pp, xx, cc, kind=kind):
                            return self._apply_layer(
                                pp, xx, cc, kind=kind, ctx=ctx, layer=r,
                                positions=positions, memory=memory,
                                page_table=page_table, lengths=lengths,
                                chunk=chunk)

                        if ctx.remat:
                            run_layer = jax.checkpoint(run_layer)
                        x, cnew, aux, reached = run_layer(p_sl[pos], x, cpos)
                        x = shard_act(x, ("batch", "seq", None))
                        aux_total = aux_total + aux
                        if reached is not None:
                            experts.append(reached[None])
                        if new_gc is not None and cnew is not None:
                            new_gc = [
                                (jax.tree.map(lambda full, new: full.at[r].set(new),
                                              new_gc[pp], cnew) if pp == pos else new_gc[pp])
                                for pp in range(len(pattern))
                            ]
                new_groups_cache.append(new_gc)
                continue

            def body(carry, xs, pattern=pattern, stacks=stacks):
                xcur = carry
                layer, p_sl, c_sl = xs
                p_sl = _with_routed(p_sl, stacks)
                auxes = jnp.zeros((), jnp.float32)
                new_cs, reached_by_pos = [], []
                for pos, kind in enumerate(pattern):
                    cpos = c_sl[pos] if c_sl is not None else None
                    xcur, cnew, aux, reached = self._apply_layer(
                        p_sl[pos], xcur, cpos, kind=kind, ctx=ctx, layer=layer,
                        positions=positions, memory=memory,
                        page_table=page_table, lengths=lengths, chunk=chunk)
                    # residual stream seq-sharded between layers under the
                    # sequence-parallel rules (no-op otherwise)
                    xcur = shard_act(xcur, ("batch", "seq", None))
                    new_cs.append(cnew)
                    auxes = auxes + aux
                    if reached is not None:
                        reached_by_pos.append(reached)
                return xcur, (new_cs, auxes, jnp.stack(reached_by_pos)
                              if reached_by_pos else jnp.zeros((0,), jnp.int32))

            if ctx.remat:
                body = jax.checkpoint(body)
            xs = (jnp.arange(g.repeats, dtype=jnp.int32), gp, gc)
            x, (stacked_cache, auxes, reached) = jax.lax.scan(body, x, xs)
            new_groups_cache.append(stacked_cache)
            aux_total = aux_total + jnp.sum(auxes)
            experts.append(reached.reshape(-1))        # (repeats, moe positions)
        new_cache = {"groups": new_groups_cache} if cache is not None else None
        experts = jnp.concatenate(experts) if experts else jnp.zeros((0,), jnp.int32)
        return x, new_cache, aux_total, experts

    # ------------------------------------------------------------------ embed
    def _embed(self, params, batch, ctx: RunCtx):
        """Returns (x (B,S,d), text_offset) — text_offset = #prefix positions
        (vision patches) preceding the first text token."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = params["embed"]["w"][tokens]
        if cfg.scale_embedding:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        offset = 0
        if cfg.vision is not None and "patches" in batch:
            proj = (jnp.einsum("bpk,kd->bpd", batch["patches"].astype(x.dtype),
                               params["vision_proj"]["w"].astype(x.dtype))
                    + params["vision_proj"]["b"].astype(x.dtype))
            x = jnp.concatenate([proj, x], axis=1)
            offset = proj.shape[1]
        return shard_act(x, ("batch", "seq", None)), offset

    def _head(self, params, x):
        cfg = self.cfg
        w = params["embed"]["w"].T if cfg.tie_embeddings else params["lm_head"]["w"]
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
        if cfg.logit_softcap > 0:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return shard_act(logits, ("batch", "seq", "vocab"))

    def _encode(self, params, frames, ctx: RunCtx):
        """Encoder for encdec models. frames: (B, M, d) stub frontend output."""
        enc = params["encoder"]
        x, _, _, _ = self._run_groups(
            enc["groups"], frames, None, ctx=ctx,
            layer_groups=(LayerGroup("E", self.cfg.encoder.n_layers),),
            positions=jnp.arange(frames.shape[1]))
        return rmsnorm(x, enc["final_norm"]["w"], self.cfg.rms_eps)

    # ------------------------------------------------------------------ api
    def forward(self, params, batch, ctx: RunCtx):
        """Teacher-forced full-sequence logits. Returns (logits, aux)."""
        cfg = self.cfg
        x, _ = self._embed(params, batch, ctx)
        memory = None
        if cfg.encoder is not None:
            memory = self._encode(params, batch["frames"].astype(x.dtype), ctx)
        positions = jnp.arange(x.shape[1])
        x, _, aux, _ = self._run_groups(
            params["groups"], x, None, ctx=ctx, layer_groups=cfg.layer_groups,
            positions=positions, memory=memory)
        x = rmsnorm(x, params["final_norm"]["w"], cfg.rms_eps)
        return self._head(params, x), aux

    def loss(self, params, batch, ctx: RunCtx, aux_weight: float = 0.01,
             xent_chunk: int = 0):
        """``xent_chunk`` > 0 enables sequence-chunked cross-entropy: the
        (B, S, vocab) f32 logits never materialize at once — the head matmul
        + logsumexp run per seq-chunk under remat. Cuts the train-step temp
        memory by the vocab-logits term (the dominant one for 150k-256k
        vocabs); a beyond-paper memory optimization (EXPERIMENTS.md §Perf)."""
        cfg = self.cfg
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels_safe = jnp.maximum(labels, 0)

        if xent_chunk <= 0:
            logits, aux = self.forward(params, batch, ctx)
            if logits.shape[1] != labels.shape[1]:       # vlm: drop patch positions
                logits = logits[:, logits.shape[1] - labels.shape[1]:]
            logits = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0] - logz
            xent = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            total = xent + aux_weight * aux
            return total, {"xent": xent, "aux": aux}

        # chunked path: trunk features once, head+xent per sequence chunk
        x, offset = self._embed(params, batch, ctx)
        memory = None
        if cfg.encoder is not None:
            memory = self._encode(params, batch["frames"].astype(x.dtype), ctx)
        positions = jnp.arange(x.shape[1])
        x, _, aux, _ = self._run_groups(
            params["groups"], x, None, ctx=ctx, layer_groups=cfg.layer_groups,
            positions=positions, memory=memory)
        x = rmsnorm(x, params["final_norm"]["w"], cfg.rms_eps)
        x = x[:, x.shape[1] - labels.shape[1]:]          # drop patch positions
        B, S, _ = x.shape
        C = xent_chunk
        pad = (-S) % C
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels_safe = jnp.pad(labels_safe, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nc = x.shape[1] // C
        xc = x.reshape(B, nc, C, -1).transpose(1, 0, 2, 3)
        lc = labels_safe.reshape(B, nc, C).transpose(1, 0, 2)
        mc = mask.reshape(B, nc, C).transpose(1, 0, 2)

        def chunk_ll(args):
            xi, li, mi = args
            logits = self._head(params, xi).astype(jnp.float32)   # (B, C, V)
            logz = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0] - logz
            return jnp.sum(ll * mi)

        lls = jax.lax.map(jax.checkpoint(chunk_ll), (xc, lc, mc))
        xent = -jnp.sum(lls) / jnp.maximum(jnp.sum(mask), 1.0)
        total = xent + aux_weight * aux
        return total, {"xent": xent, "aux": aux}

    def prefill(self, params, batch, cache, ctx: RunCtx, last_pos=None):
        """Full-sequence pass that also fills the cache. ``last_pos`` (B,)
        selects the logits position (true prompt end when the engine pads to a
        bucket); defaults to the final position.
        Returns (last_logits (B, vocab), cache)."""
        cfg = self.cfg
        ctx = ctx.with_mode("prefill")
        x, offset = self._embed(params, batch, ctx)
        memory = None
        if cfg.encoder is not None:
            memory = self._encode(params, batch["frames"].astype(x.dtype), ctx)
        positions = jnp.arange(x.shape[1])
        x, new_cache, _, _ = self._run_groups(
            params["groups"], x, cache, ctx=ctx, layer_groups=cfg.layer_groups,
            positions=positions, memory=memory)
        x = rmsnorm(x, params["final_norm"]["w"], cfg.rms_eps)
        if last_pos is None:
            last = x[:, -1:]
        else:
            last = jnp.take_along_axis(x, (last_pos + offset)[:, None, None], axis=1)
        logits = self._head(params, last)
        return logits[:, 0], new_cache

    def decode_chunk(self, params, tokens, cache, starts, nvalid, slots, first,
                     ctx: RunCtx, page_table, frames=None, patches=None,
                     all_logits: bool = False, counters: Optional[Dict[str, Any]] = None):
        """Unified serving iteration over a paged cache (DESIGN.md §2): each
        batch row feeds a chunk of up to C tokens of one sequence — C == 1 is
        decode, C > 1 is a prefill chunk. KV goes straight into the paged
        pool; there is no dense intermediate cache and no scatter copy.

        tokens (B, C); starts (B,) absolute position of each row's first
        token (pre-vision-offset); nvalid (B,) live tokens per row (0 =
        inactive row); slots (B,) engine slot per row (must be distinct);
        first (B,) True on a sequence's first chunk (resets SSM/conv state);
        page_table (B, max_pages); frames (B, M, d) raw encoder frames for
        encdec prefill chunks (encoded here, cross-KV persisted per slot);
        patches (B, n_patches, d_patch) for VLM chunk calls —
        the patch prefix is embedded into rows with starts == 0 and its KV
        occupies kv positions [0, n_patches).

        Returns (logits (B, vocab) at each row's last valid position,
        new_cache). With ``all_logits`` the head runs on every token
        position instead — (B, C, vocab), patch-prefix positions dropped —
        which is the verify step of speculative decoding (DESIGN.md §3):
        position j scores the token fed at index j+1. A ``counters`` dict,
        where given, is filled as the call is traced: ``experts``
        (n_moe_layers,) int32, the experts each MoE layer's live tokens
        reached (padding rows and positions past ``nvalid`` left out).
        """
        cfg = self.cfg
        if cfg.vision is not None and any("M" in g.pattern for g in cfg.layer_groups):
            # SSM chunk masking is indexed by nvalid over the token axis and
            # would treat a patch prefix as live tokens — refuse loudly
            # rather than corrupt state (no current config hits this).
            raise NotImplementedError(
                "chunk mode: vision patch prefix + SSM layers is unsupported")
        ctx = ctx.with_mode("chunk")
        B, C = tokens.shape
        x = params["embed"]["w"][tokens]
        if cfg.scale_embedding:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        offset = cfg.vision.n_patches if cfg.vision is not None else 0
        positions = offset + starts[:, None] + jnp.arange(C)[None, :]
        valid = jnp.arange(C)[None, :] < nvalid[:, None]
        n_prefix = 0
        if cfg.vision is not None and patches is not None:
            proj = (jnp.einsum("bpk,kd->bpd", patches.astype(x.dtype),
                               params["vision_proj"]["w"].astype(x.dtype))
                    + params["vision_proj"]["b"].astype(x.dtype))
            n_prefix = proj.shape[1]
            x = jnp.concatenate([proj, x], axis=1)
            pre_pos = jnp.broadcast_to(jnp.arange(n_prefix)[None, :], (B, n_prefix))
            pre_valid = jnp.broadcast_to(((starts == 0) & (nvalid > 0))[:, None],
                                         (B, n_prefix))
            positions = jnp.concatenate([pre_pos, positions], axis=1)
            valid = jnp.concatenate([pre_valid, valid], axis=1)
        lengths = offset + starts + nvalid
        memory = None
        if cfg.encoder is not None and frames is not None:
            memory = self._encode(params, frames.astype(x.dtype), ctx)
        pack = {"slots": slots, "nvalid": nvalid, "first": first, "valid": valid,
                "prefix": n_prefix > 0}
        x, new_cache, _, reached = self._run_groups(
            params["groups"], x, cache, ctx=ctx, layer_groups=cfg.layer_groups,
            positions=positions, memory=memory, page_table=page_table,
            lengths=lengths, chunk=pack)
        if counters is not None:
            counters["experts"] = reached
        x = rmsnorm(x, params["final_norm"]["w"], cfg.rms_eps)
        if all_logits:
            return self._head(params, x[:, n_prefix:]), new_cache
        last = n_prefix + jnp.maximum(nvalid, 1) - 1
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
        logits = self._head(params, x_last)
        return logits[:, 0], new_cache

    def decode_step(self, params, tokens, cache, positions, ctx: RunCtx,
                    page_table=None, lengths=None):
        """tokens (B,1); positions (B,) absolute position of the new token.
        Returns (logits (B, vocab), new_cache)."""
        cfg = self.cfg
        ctx = ctx.with_mode("decode")
        x = params["embed"]["w"][tokens]
        if cfg.scale_embedding:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if lengths is None:
            lengths = positions + 1
        x, new_cache, _, _ = self._run_groups(
            params["groups"], x, cache, ctx=ctx, layer_groups=cfg.layer_groups,
            positions=positions, page_table=page_table, lengths=lengths)
        x = rmsnorm(x, params["final_norm"]["w"], cfg.rms_eps)
        logits = self._head(params, x)
        return logits[:, 0], new_cache

    # ------------------------------------------------------------------ cache
    def init_cache(self, B: int, max_seq: int, dtype=jnp.float32, *,
                   kind: str = "dense", page_size: int = 16,
                   num_pages: int = 0, memory_len: int = 0):
        """Build the cache pytree (call under jax.eval_shape for the dry-run).

        kind="dense": per-layer ring buffers (window layers get W=window).
        kind="paged": per-layer physical page pools (engine supplies
                      page_table/lengths at decode time); a latent layer
                      holds one pool "lat" (R, P, page_size, latent + rope
                      padded to whole lane tiles, ``mla.pool_width``).
        """
        cfg = self.cfg
        Hkv, hd = cfg.n_kv_heads, cfg.head_dim
        groups_cache = []
        for g in cfg.layer_groups:
            R = g.repeats
            per_pos = []
            for pos, k in enumerate(g.pattern):
                c: Dict[str, Any] = {}
                if k == "M":
                    ssm = cfg.ssm
                    c["ssm"] = {
                        "state": jnp.zeros((R, B, cfg.ssm_heads, ssm.head_dim, ssm.d_state), jnp.float32),
                        "conv": jnp.zeros((R, B, cfg.d_inner + 2 * ssm.n_groups * ssm.d_state,
                                           ssm.d_conv - 1), dtype),
                    }
                elif cfg.mla is not None:
                    if kind != "paged":
                        raise NotImplementedError("latent attention caches in the paged pool only")
                    # one pool of each token's latent and rope key, shared by all heads
                    c["attn"] = {"lat": jnp.zeros((R, num_pages, page_size, pool_width(cfg)),
                                                  dtype)}
                else:
                    W = min(max_seq, cfg.sliding_window) if (k == "L" and cfg.sliding_window) else max_seq
                    if kind == "paged":
                        c["attn"] = {
                            "kp": jnp.zeros((R, num_pages, Hkv, page_size, hd), dtype),
                            "vp": jnp.zeros((R, num_pages, Hkv, page_size, hd), dtype),
                        }
                    else:
                        c["attn"] = {
                            "k": jnp.zeros((R, B, W, Hkv, hd), dtype),
                            "v": jnp.zeros((R, B, W, Hkv, hd), dtype),
                            "slot_pos": jnp.full((R, B, W), -1, jnp.int32),
                        }
                if cfg.family == "encdec":
                    M = memory_len or cfg.encoder.cross_attn_memory
                    c["cross"] = {
                        "ck": jnp.zeros((R, B, M, Hkv, hd), dtype),
                        "cv": jnp.zeros((R, B, M, Hkv, hd), dtype),
                    }
                per_pos.append(c)
            groups_cache.append(per_pos)
        return {"groups": groups_cache}


def build_model(cfg: ModelConfig) -> LM:
    return LM(cfg)
