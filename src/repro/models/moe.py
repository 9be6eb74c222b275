"""Mixture-of-Experts blocks (granite-moe, deepseek-v3) + MLA attention.

Token→expert dispatch deliberately reuses the PI query-routing shape
(core.distributed.dispatch_plan): tokens = queries, experts = key-range
shards, capacity factor = the self-adjusted-threading analogue.  Sorted
dispatch + capacity-bounded per-expert buffers is exactly the paper's
Alg. 1/3 applied to MoE — this is where the paper's technique is a
first-class feature of the LM framework (DESIGN.md §3).

DeepSeek-V3 specifics implemented: MLA (low-rank Q/KV with decoupled RoPE
head), 1 shared + 256 routed experts with top-8 sigmoid-score routing,
first-k dense layers, and a depth-1 MTP head.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.distributed import dispatch_plan
from repro.models.base import Layout, ModelConfig, ParamDef
from repro.models.transformer import (attn_apply, attn_layout, flash_attention,
                                      mlp_apply, mlp_layout, norm, rope)
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_layout(cfg: ModelConfig, prefix: str, layers: int) -> Layout:
    d = cfg.d_model
    H = cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    L, ll = (layers,), ("layers",)
    return {
        f"{prefix}/wq_a": ParamDef(L + (d, qr), ll + ("fsdp", None)),
        f"{prefix}/q_a_norm": ParamDef(L + (qr,), ll + (None,), "zeros"),
        f"{prefix}/wq_b": ParamDef(L + (qr, H * (dn + dr)),
                                   ll + (None, "heads")),
        f"{prefix}/wkv_a": ParamDef(L + (d, kvr + dr), ll + ("fsdp", None)),
        f"{prefix}/kv_a_norm": ParamDef(L + (kvr,), ll + (None,), "zeros"),
        f"{prefix}/wkv_b": ParamDef(L + (kvr, H * (dn + dv)),
                                    ll + (None, "heads")),
        f"{prefix}/wo": ParamDef(L + (H * dv, d), ll + ("heads", "fsdp")),
    }


def mla_apply(cfg: ModelConfig, p: Dict, x, positions, cache=None):
    """DeepSeek MLA.  Cache stores the *compressed* c_kv + shared k_rope —
    (kv_lora + rope_dim) per token instead of 2·H·hd (the paper's KV-cache
    reduction), expanded per-head on read."""
    B, S, d = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank

    cq = norm(cfg, x @ p["wq_a"], p["q_a_norm"])
    q = (cq @ p["wq_b"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    kv_a = x @ p["wkv_a"]                       # (B,S,kvr+dr)
    c_kv = norm(cfg, kv_a[..., :kvr], p["kv_a_norm"])
    k_rope = rope(kv_a[..., kvr:][..., None, :], positions,
                  cfg.rope_theta)               # (B,S,1,dr) shared head

    if cache is not None:
        c_kv = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv, (0, cache["idx"], 0))
        k_rope = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope, (0, cache["idx"], 0, 0))
    kv = (c_kv @ p["wkv_b"]).reshape(B, -1, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (dr,))], -1)
    qq = jnp.concatenate([q_nope, q_rope], -1)
    qq = constrain(qq, "batch", "seq", "heads", None)
    if cache is None:
        o = flash_attention(qq, k, v, causal=True)
        new_cache = (c_kv, k_rope)
    else:
        T = k.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", qq / math.sqrt(dn + dr), k,
                       preferred_element_type=jnp.float32)
        mask = jnp.arange(T)[None, :] <= (cache["idx"] + jnp.arange(S))[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.moveaxis(jnp.einsum("bhqk,bkhd->bhqd", w, v,
                                    preferred_element_type=jnp.float32), 1, 2
                         ).astype(x.dtype)
        new_cache = (c_kv, k_rope)
    return o.reshape(B, S, H * dv) @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# expert layer — PI-style sorted dispatch
# ---------------------------------------------------------------------------

def experts_layout(cfg: ModelConfig, prefix: str, layers: int) -> Layout:
    d, fe = cfg.d_model, cfg.d_ff_expert
    Ep = cfg.experts_padded       # EP-shardable (dummies take no tokens)
    L, ll = (layers,), ("layers",)
    out = {
        f"{prefix}/router": ParamDef(L + (d, cfg.n_experts),
                                     ll + (None, None)),
        f"{prefix}/w_gate": ParamDef(L + (Ep, d, fe),
                                     ll + ("experts", "fsdp", "expert_mlp")),
        f"{prefix}/w_up": ParamDef(L + (Ep, d, fe),
                                   ll + ("experts", "fsdp", "expert_mlp")),
        f"{prefix}/w_down": ParamDef(L + (Ep, fe, d),
                                     ll + ("experts", "expert_mlp", "fsdp")),
    }
    if cfg.n_shared_experts:
        out.update(mlp_layout(cfg, f"{prefix}/shared", layers,
                              width=cfg.d_ff_expert * cfg.n_shared_experts))
    return out


def _route(cfg: ModelConfig, p: Dict, xf):
    """Router scores → (gate_vals, expert_ids, lb_loss)."""
    E, K = cfg.n_experts, cfg.top_k
    scores = (xf @ p["router"]).astype(jnp.float32)          # (N, E)
    probs = jax.nn.sigmoid(scores) if cfg.family == "mla_moe" \
        else jax.nn.softmax(scores, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)          # (N, K)
    if cfg.family == "mla_moe":                              # deepseek norm
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    me = jnp.mean(jax.nn.softmax(scores, -1), axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_ids, E).sum(1), axis=0)
    lb_loss = E * jnp.sum(me * ce) / K
    return gate_vals, expert_ids, lb_loss


def moe_apply_shardmap(cfg: ModelConfig, p: Dict, x, capacity_factor=None):
    """EP dispatch as an explicit shard_map — the PI routing pattern.

    Activations are replicated across the model axis (TP), so each expert
    shard already *has* every token: it filters the tokens routed to its
    own experts locally (PI: a NUMA node answers only its key range),
    runs its expert GEMMs, and a single psum over the model axis combines
    per-token contributions — the only collective, identical in size to a
    Megatron TP MLP all-reduce.  This replaces the GSPMD-auto dispatch
    whose data-dependent global scatter all-gathered the full token
    buffer (≈14× collective blow-up; see EXPERIMENTS.md §Perf it.3).
    """
    from repro import sharding as shd

    mesh = shd.current_mesh()
    model_axes = shd.physical_axes("experts", cfg.experts_padded)
    if mesh is None or not model_axes:
        return moe_apply(cfg, p, x, capacity_factor)
    model_ax = model_axes[0]
    B, S, d = x.shape
    E, K, Ep = cfg.n_experts, cfg.top_k, cfg.experts_padded
    N = B * S
    xf = x.reshape(N, d)
    gate_vals, expert_ids, lb_loss = _route(cfg, p, xf)

    from jax.sharding import PartitionSpec as P
    batch_axes = shd.physical_axes("batch", N)
    bspec = batch_axes if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    n_b = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in batch_axes:
        n_b *= sizes[a]
    N_loc = N // n_b
    E_local = Ep // sizes[model_ax]
    cf = capacity_factor if capacity_factor is not None else cfg.moe_capacity
    cap = int(math.ceil(N_loc * K / Ep * cf))
    cap = max(8, min(cap, N_loc))

    def local(xf_l, gv_l, ids_l, wg, wu, wd):
        midx = jax.lax.axis_index(model_ax)
        lo = midx * E_local
        dest = ids_l.reshape(-1).astype(jnp.int32) - lo
        valid = (dest >= 0) & (dest < E_local)
        dest_c = jnp.where(valid, dest, E_local)     # overflow bucket
        order, slot, keep, _ = dispatch_plan(dest_c, E_local + 1, cap)
        live = keep & valid[order]
        slot = jnp.where(live, slot, E_local * cap)  # bucket rows drop
        tok_of = (jnp.arange(N_loc * K, dtype=jnp.int32) // K)[order]
        xbuf = jnp.zeros((E_local * cap, d), xf_l.dtype).at[slot].set(
            xf_l[tok_of], mode="drop").reshape(E_local, cap, d)
        h = jnp.einsum("ecd,edf->ecf", xbuf, wg)
        u = jnp.einsum("ecd,edf->ecf", xbuf, wu)
        h = jax.nn.silu(h) * u
        y = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_local * cap, d)
        g = jnp.where(live, gv_l.reshape(-1)[order], 0.0).astype(xf_l.dtype)
        contrib = y[jnp.where(live, slot, 0)] * g[:, None]
        out = jnp.zeros((N_loc, d), xf_l.dtype).at[tok_of].add(contrib)
        return jax.lax.psum(out, model_ax)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec),
                  P(model_ax), P(model_ax), P(model_ax)),
        out_specs=P(bspec), check_vma=False)(
        xf, gate_vals.astype(x.dtype), expert_ids,
        p["w_gate"], p["w_up"], p["w_down"])
    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + mlp_apply(cfg, p["shared"], x)
    return out, lb_loss


def moe_apply(cfg: ModelConfig, p: Dict, x, capacity_factor=None):
    """Top-k routed experts via sorted, capacity-bounded dispatch.

    N = B·S tokens are replicated top_k times, sorted by destination expert
    (dispatch_plan — the same primitive that routes PI queries to NUMA
    shards), executed as one (E, cap, d) batched GEMM per projection, and
    combined with the router gates.  Per-expert capacity plays the paper's
    load-balancing role; overflowing tokens are dropped (residual passes
    them through), mirroring capacity-factor MoE *and* PI's bounded
    send buffers.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * S
    xf = x.reshape(N, d)
    gate_vals, expert_ids, lb_loss = _route(cfg, p, xf)

    cf = capacity_factor if capacity_factor is not None else cfg.moe_capacity
    Ep = cfg.experts_padded
    cap = int(math.ceil(N * K / E * cf))
    cap = max(8, min(cap, N))
    dest = expert_ids.reshape(-1).astype(jnp.int32)          # (N*K,)
    order, slot, keep, _ = dispatch_plan(dest, Ep, cap)
    tok_of = (jnp.arange(N * K, dtype=jnp.int32) // K)[order]
    xbuf = jnp.zeros((Ep * cap, d), x.dtype).at[slot].set(
        xf[tok_of], mode="drop").reshape(Ep, cap, d)
    # shard experts over "model" (EP) AND the capacity rows over the data
    # axis — otherwise every device computes the full global expert batch
    # (the 0.01 useful-ratio pathology in the baseline roofline table)
    xbuf = constrain(xbuf, "experts", "batch", None)

    h = jnp.einsum("ecd,edf->ecf", xbuf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xbuf, p["w_up"])
    h = jax.nn.silu(h) * u
    h = constrain(h, "experts", "batch", "expert_mlp")
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    y = constrain(y, "experts", "batch", None).reshape(Ep * cap, d)

    # combine: gather each surviving copy back to its token, scale by gate
    gflat = gate_vals.reshape(-1).astype(x.dtype)
    contrib = y[jnp.where(keep, slot, 0)] * \
        jnp.where(keep, gflat[order], 0.0)[:, None]
    out = jnp.zeros((N, d), x.dtype).at[tok_of].add(contrib)
    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        out = out + mlp_apply(cfg, p["shared"], x)
    return out, lb_loss


# ---------------------------------------------------------------------------
# block assembly
# ---------------------------------------------------------------------------

def block_layout(cfg: ModelConfig) -> Layout:
    """MoE families: optional leading dense layers + scanned MoE layers."""
    out: Layout = {}
    n_moe = cfg.n_layers - cfg.first_k_dense
    attn_fn = mla_layout if cfg.use_mla else attn_layout
    if cfg.first_k_dense:
        for k, v in attn_fn(cfg, "attn", cfg.first_k_dense).items():
            out[f"dense/{k}"] = v
        for k, v in mlp_layout(cfg, "mlp", cfg.first_k_dense,
                               width=cfg.d_ff_dense or cfg.d_ff).items():
            out[f"dense/{k}"] = v
        out["dense/ln1"] = ParamDef((cfg.first_k_dense, cfg.d_model),
                                    ("layers", None), "zeros")
        out["dense/ln2"] = ParamDef((cfg.first_k_dense, cfg.d_model),
                                    ("layers", None), "zeros")
    for k, v in attn_fn(cfg, "attn", n_moe).items():
        out[f"moe/{k}"] = v
    for k, v in experts_layout(cfg, "experts", n_moe).items():
        out[f"moe/{k}"] = v
    out["moe/ln1"] = ParamDef((n_moe, cfg.d_model), ("layers", None), "zeros")
    out["moe/ln2"] = ParamDef((n_moe, cfg.d_model), ("layers", None), "zeros")
    return out


def _attn(cfg, p, x, positions, cache=None):
    if cfg.use_mla:
        return mla_apply(cfg, p, x, positions, cache=cache)
    return attn_apply(cfg, p, x, positions, cache=cache,
                      window=cfg.sliding_window)


def dense_layer(cfg, p, x, positions, cache=None):
    h, kv = _attn(cfg, p["attn"], norm(cfg, x, p["ln1"]), positions, cache)
    x = x + h
    x = x + mlp_apply(cfg, p["mlp"], norm(cfg, x, p["ln2"]))
    return constrain(x, "batch", "seq", "embed"), kv


def moe_layer(cfg, p, x, positions, cache=None):
    h, kv = _attn(cfg, p["attn"], norm(cfg, x, p["ln1"]), positions, cache)
    x = x + h
    fn = moe_apply if cfg.moe_impl == "gspmd" else moe_apply_shardmap
    y, lb = fn(cfg, p["experts"], norm(cfg, x, p["ln2"]))
    return constrain(x + y, "batch", "seq", "embed"), (kv, lb)


def forward_blocks(cfg: ModelConfig, params, x, positions):
    aux = {"lb_loss": jnp.zeros((), jnp.float32)}

    if cfg.first_k_dense:
        fn = partial(dense_layer, cfg)
        if cfg.remat:
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable)

        def dbody(h, p_l):
            h, _ = fn(p_l, h, positions)
            return h, None
        x, _ = jax.lax.scan(dbody, x, params["dense"])

    fn = partial(moe_layer, cfg)
    if cfg.remat:
        fn = jax.checkpoint(fn,
                            policy=jax.checkpoint_policies.nothing_saveable)

    def mbody(carry, p_l):
        h, lb = carry
        h, (_, lb_l) = fn(p_l, h, positions)
        return (h, lb + lb_l), None
    (x, lb), _ = jax.lax.scan(mbody, (x, aux["lb_loss"]), params["moe"])
    n_moe = cfg.n_layers - cfg.first_k_dense
    aux["lb_loss"] = lb / max(n_moe, 1)
    aux["h_final"] = x
    return x, aux


# ---------------------------------------------------------------------------
# MTP (deepseek multi-token prediction, depth 1)
# ---------------------------------------------------------------------------

def mtp_layout(cfg: ModelConfig) -> Layout:
    out: Layout = {"proj": ParamDef((2 * cfg.d_model, cfg.d_model),
                                    ("fsdp", None))}
    attn_fn = mla_layout if cfg.use_mla else attn_layout
    for k, v in attn_fn(cfg, "attn", 1).items():
        out[k] = v
    for k, v in experts_layout(cfg, "experts", 1).items():
        out[k] = v
    out["ln1"] = ParamDef((1, cfg.d_model), ("layers", None), "zeros")
    out["ln2"] = ParamDef((1, cfg.d_model), ("layers", None), "zeros")
    out["ln_in"] = ParamDef((cfg.d_model,), (None,), "zeros")
    return out


def mtp_loss(cfg: ModelConfig, params, batch, h_final):
    """Depth-1 MTP: predict token t+2 from (h_t, emb(t+1))."""
    from repro.models.transformer import embed_tokens, norm as _n, unembed

    p = params["mtp"]
    tokens, labels = batch["tokens"], batch["labels"]
    # shift: combine hidden at t with embedding of token t+1
    emb_next = embed_tokens(cfg, params, jnp.roll(tokens, -1, axis=1))
    h = jnp.concatenate([_n(cfg, h_final, p["ln_in"]), emb_next], -1)
    h = h @ p["proj"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    p_l = jax.tree.map(lambda a: a[0], {k: p[k] for k in
                                        ("attn", "experts", "ln1", "ln2")})
    h, _ = moe_layer(cfg, p_l, h, positions)
    logits = unembed(cfg, params, h)
    lf = logits.astype(jnp.float32)
    # labels for t+2 == labels shifted by one more step
    lbl = jnp.roll(labels, -1, axis=1)
    logz = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, lbl[..., None], axis=-1)[..., 0]
    mask = jnp.ones_like(gold).at[:, -2:].set(0.0)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
