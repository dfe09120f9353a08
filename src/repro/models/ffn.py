"""Feed-forward layers: gated dense MLP and mixture-of-experts.

MoE dispatch is sort-based (no (tokens, E, C) one-hot einsums, which inflate
FLOPs by orders of magnitude): entries are ranked within their expert via an
argsort + running-count, dropped beyond capacity, scatter-added into an
(B, E, C, d) buffer, processed by batched expert matmuls, and gathered back.
Compiled FLOPs therefore track ACTIVE expert compute (x capacity factor),
which is what the roofline's MODEL_FLOPS/HLO_FLOPs ratio checks.

Sharding: the ShardCtx rule table sends "experts" to the model axis when the
expert count divides it (expert parallelism — deepseek's 64), and otherwise
falls through to sharding the expert hidden dim (tensor parallelism inside
each expert — grok's 8).  Both use the same constraint strings here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .base import ACTIVATIONS, P, ShardCtx, dense
from .config import ModelConfig, MoEConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# Dense gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def decls_mlp(d_model: int, d_ff: int, gated: bool = True) -> dict:
    decls = {
        "w_up": P((d_model, d_ff), ("embed", "mlp")),
        "w_down": P((d_ff, d_model), ("mlp", "embed")),
    }
    if gated:
        decls["w_gate"] = P((d_model, d_ff), ("embed", "mlp"))
    return decls


def mlp_forward(p: dict, x: Array, act: str, ctx: ShardCtx) -> Array:
    if "w_gate" in p:
        h = ACTIVATIONS[act](dense(x, p["w_gate"])) * dense(x, p["w_up"])
    else:
        h = ACTIVATIONS[act](dense(x, p["w_up"]))
    h = ctx.constrain(h, "batch", None, "mlp")
    out = dense(h, p["w_down"])
    return ctx.constrain(out, "batch", "seq", None)


# ---------------------------------------------------------------------------
# Mixture of experts
# ---------------------------------------------------------------------------

def decls_moe(cfg: ModelConfig) -> dict:
    moe = cfg.moe
    d, f = cfg.d_model, moe.d_ff_expert
    decls = {
        "router": P((d, moe.n_experts), ("embed", None), scale=0.02),
        "w_gate": P((moe.n_experts, d, f), ("experts", "embed", "moe_mlp")),
        "w_up": P((moe.n_experts, d, f), ("experts", "embed", "moe_mlp")),
        "w_down": P((moe.n_experts, f, d), ("experts", "moe_mlp", "embed")),
    }
    if moe.n_shared:
        decls["shared"] = decls_mlp(d, moe.n_shared * f)
    return decls


def _capacity(tokens_per_group: int, moe: MoEConfig) -> int:
    c = math.ceil(tokens_per_group * moe.top_k * moe.capacity_factor
                  / moe.n_experts)
    return max(min(c, tokens_per_group * moe.top_k), 1)


MOE_GROUP_TOKENS = 4096   # dispatch-group size: bounds the (G,E,C,d) buffers


def _ep_sharded(cfg: ModelConfig, ctx: ShardCtx) -> bool:
    """True when experts divide the model axis (expert parallelism) and we
    can take the shard_map fast path (local-expert combine + psum)."""
    if ctx.mesh is None:
        return False
    model_size = ctx.mesh.shape.get("model", 1)
    return model_size > 1 and cfg.moe.n_experts % model_size == 0


def moe_forward(p: dict, x: Array, cfg: ModelConfig,
                ctx: ShardCtx) -> tuple[Array, Array]:
    """x (B, S, d) -> (out (B, S, d), aux load-balance loss scalar).

    Dispatch groups are <=4096-token sequence slices (GShard-style
    per-group capacity): the (G, E, C, d) expert buffers stay bounded at
    long prefill lengths, and groups remain local to their data shard so
    the only cross-shard traffic is the expert combine.

    Combine paths (hillclimb iteration 1, see EXPERIMENTS.md §Perf):
    * EP (E %% model == 0): shard_map — every model shard runs its local
      experts and contributes a PARTIAL combined output; one psum of
      (B, S, d) replaces the (B, E, C, d) all-gather (~30x fewer link
      bytes for deepseek).
    * otherwise (grok's 8 experts on a 16-wide axis): expert-hidden-dim
      tensor parallelism through plain GSPMD.
    """
    moe = cfg.moe
    B, S, d = x.shape
    routed = _routed_ep if _ep_sharded(cfg, ctx) else _routed
    if S > MOE_GROUP_TOKENS and S % MOE_GROUP_TOKENS == 0:
        n = S // MOE_GROUP_TOKENS
        out, aux = routed(p, x.reshape(B * n, MOE_GROUP_TOKENS, d), cfg,
                          ctx)
        out = out.reshape(B, S, d)
    else:
        out, aux = routed(p, x, cfg, ctx)
    if moe.n_shared:
        out = out + mlp_forward(p["shared"], x, cfg.act, ctx)
    return ctx.constrain(out, "batch", "seq", None), aux


def _dispatch_plan(x: Array, router: Array, moe: MoEConfig):
    """Shared routing math: top-k, capacity ranks, slot ids.

    Returns (probs (B,S,E) f32, top_p, top_e, keep, slot) with
    slot = e*C + rank (E*C = drop bin)."""
    B, S, d = x.shape
    E, K = moe.n_experts, moe.top_k
    C = _capacity(S, moe)
    T = S * K
    logits = jnp.einsum("bsd,de->bse", x, router.astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    e_flat = top_e.reshape(B, T)
    order = jnp.argsort(e_flat, axis=1, stable=True)
    e_sorted = jnp.take_along_axis(e_flat, order, axis=1)
    counts = jax.vmap(lambda e: jnp.zeros((E,), jnp.int32).at[e].add(1))(
        e_flat)
    starts = jnp.cumsum(counts, axis=1) - counts
    rank_sorted = (jnp.arange(T)[None, :]
                   - jnp.take_along_axis(starts, e_sorted, axis=1))
    inv = jnp.argsort(order, axis=1)
    rank = jnp.take_along_axis(rank_sorted, inv, axis=1).reshape(B, S, K)
    keep = rank < C
    slot = jnp.where(keep, top_e * C + rank, E * C)
    return probs, top_p, top_e, keep, slot, C


def _routed_ep(p: dict, x: Array, cfg: ModelConfig,
               ctx: ShardCtx) -> tuple[Array, Array]:
    """Expert-parallel fast path: shard_map over (data..., model)."""
    moe = cfg.moe
    B, S, d = x.shape
    E, K = moe.n_experts, moe.top_k
    mesh = ctx.mesh
    model_size = mesh.shape.get("model", 1)
    e_loc = E // model_size
    dp = tuple(n for n in ("pod", "data") if n in mesh.shape)
    P = jax.sharding.PartitionSpec

    def local_moe(xb, router, w_gate, w_up, w_down):
        # xb (B_loc, S, d) replicated over model; w_* (E_loc, ...) local.
        probs, top_p, top_e, keep, slot, C = _dispatch_plan(xb, router, moe)
        Bl = xb.shape[0]
        buf = jnp.zeros((Bl, E * C + 1, d), xb.dtype)
        scatter_g = jax.vmap(lambda bg, sg, ug: bg.at[sg].add(ug))
        for j in range(K):
            buf = scatter_g(buf, slot[:, :, j],
                            xb * keep[:, :, j:j + 1].astype(xb.dtype))
        # My experts: [lo, lo + e_loc) on the model axis.
        midx = jax.lax.axis_index("model")
        lo = midx * e_loc
        my = jax.lax.dynamic_slice_in_dim(
            buf[:, :E * C].reshape(Bl, E, C, d), lo, e_loc, axis=1)
        h = (ACTIVATIONS[cfg.act](
                jnp.einsum("becd,edf->becf", my, w_gate.astype(xb.dtype)))
             * jnp.einsum("becd,edf->becf", my, w_up.astype(xb.dtype)))
        out_loc = jnp.einsum("becf,efd->becd", h,
                             w_down.astype(xb.dtype))   # (Bl,e_loc,C,d)
        out_flat = jnp.concatenate(
            [out_loc.reshape(Bl, e_loc * C, d),
             jnp.zeros((Bl, 1, d), xb.dtype)], axis=1)
        # Partial combine: only slots belonging to my experts contribute.
        gather_g = jax.vmap(lambda og, sg: og[sg])
        out = jnp.zeros((Bl, S, d), xb.dtype)
        for j in range(K):
            sj = slot[:, :, j]
            mine = (sj >= lo * C) & (sj < (lo + e_loc) * C) & keep[:, :, j]
            sj_loc = jnp.where(mine, sj - lo * C, e_loc * C)
            gathered = gather_g(out_flat, sj_loc)
            w = (top_p[:, :, j] * mine).astype(xb.dtype)
            out = out + gathered * w[:, :, None]
        out = jax.lax.psum(out, "model")
        me = probs.mean(axis=(0, 1))
        assign = jax.nn.one_hot(top_e[..., 0], E).mean(axis=(0, 1))
        aux = moe.aux_loss_weight * E * jnp.sum(me * assign)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return out, aux

    fn = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(dp if dp else None, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp if dp else None, None, None), P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _routed(p: dict, x: Array, cfg: ModelConfig,
            ctx: ShardCtx) -> tuple[Array, Array]:
    moe = cfg.moe
    B, S, d = x.shape
    E, K = moe.n_experts, moe.top_k
    # Dispatch is group-local: undo sequence parallelism here (one SP
    # all-gather, the Megatron MoE pattern) so routing/scatter/gather all
    # stay on the data shard.
    x = ctx.constrain(x, "batch", None, None)
    probs, top_p, top_e, keep, slot, C = _dispatch_plan(x, p["router"], moe)

    # --- dispatch: scatter tokens into the (B, E*C, d) buffer -------------
    # vmapped over groups => a batched scatter GSPMD shards along the
    # (data-parallel) group dim instead of replicating the updates.
    buf = jnp.zeros((B, E * C + 1, d), x.dtype)
    scatter_g = jax.vmap(lambda bg, sg, ug: bg.at[sg].add(ug))
    for j in range(K):
        buf = scatter_g(buf, slot[:, :, j],
                        x * keep[:, :, j:j + 1].astype(x.dtype))
    buf = buf[:, :E * C, :].reshape(B, E, C, d)
    buf = ctx.constrain(buf, "batch", "experts", None, None)

    # --- expert FFN (batched over E) ---------------------------------------
    h = (ACTIVATIONS[cfg.act](
            jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(x.dtype)))
         * jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(x.dtype)))
    h = ctx.constrain(h, "batch", "experts", None, "moe_mlp")
    out_buf = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(x.dtype))
    # EP combine: gather needs every expert's rows -> all-gather over model.
    out_buf = ctx.constrain(out_buf, "batch", None, None, None)
    out_flat = out_buf.reshape(B, E * C, d)
    out_flat = jnp.concatenate(
        [out_flat, jnp.zeros((B, 1, d), x.dtype)], axis=1)  # drop bin

    # --- combine: gather own slots, weight by router probs ----------------
    gather_g = jax.vmap(lambda og, sg: og[sg])
    out = jnp.zeros((B, S, d), x.dtype)
    for j in range(K):
        gathered = gather_g(out_flat, slot[:, :, j])       # (B, S, d)
        w = (top_p[:, :, j] * keep[:, :, j]).astype(x.dtype)
        out = out + gathered * w[:, :, None]

    # --- aux load-balance loss (Switch/GShard style) -----------------------
    me = probs.mean(axis=(0, 1))                           # (E,)
    assign = jax.nn.one_hot(top_e[..., 0], E).mean(axis=(0, 1))
    aux = moe.aux_loss_weight * E * jnp.sum(me * assign)
    return out, aux
