"""Dense MLP and Mixture-of-Experts layers.

MoE uses capacity-bounded scatter dispatch (Switch-style, expressed with
cumsum ranking + scatter-add instead of the (N, E, C) one-hot tensor, which
would not fit at DeepSeek scale).  Experts are sharded over "model" (expert
parallelism); the (E, C, D) buffers shard capacity over the batch axes, so
GSPMD lowers dispatch/combine to the EP all-to-all pattern.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding_rules import batch_axes, shard
from repro.models import common
from repro.models.config import ModelConfig


# ------------------------------------------------------------------- dense
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    ks = common.keygen(key)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = common.dtype_of(cfg.dtype)
    p = {"w1": common.dense_init(next(ks), d, (f,), dt),
         "w2": common.dense_init(next(ks), f, (d,), dt)}
    if cfg.gated_mlp:
        p["w3"] = common.dense_init(next(ks), d, (f,), dt)
    return p


def mlp_forward(p, x, cfg: ModelConfig):
    act = common.activation_fn(cfg.activation)
    h = act(x @ p["w1"])
    if cfg.gated_mlp:
        h = h * (x @ p["w3"])
    h = shard(h, batch_axes(), None, "model")
    return h @ p["w2"]


# --------------------------------------------------------------------- MoE
def init_moe(key, cfg: ModelConfig):
    ks = common.keygen(key)
    d, e = cfg.d_model, cfg.num_experts
    fe = cfg.moe_d_ff or cfg.d_ff
    dt = common.dtype_of(cfg.dtype)
    p = {
        "router": common.dense_init(next(ks), d, (e,), jnp.float32),
        "experts_w1": common.dense_init(next(ks), d, (e, fe), dt
                                        ).transpose(1, 0, 2),
        "experts_w2": common.dense_init(next(ks), fe, (e, d), dt
                                        ).transpose(1, 0, 2),
    }
    if cfg.gated_mlp:
        p["experts_w3"] = common.dense_init(next(ks), d, (e, fe), dt
                                            ).transpose(1, 0, 2)
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(key, cfg, cfg.moe_d_ff * cfg.num_shared_experts
                               if cfg.moe_d_ff else cfg.d_ff)
    return p


def moe_forward(p, x, cfg: ModelConfig):
    """MoE dispatcher: picks the implementation (module docstring).

    * ``a2a``     — shard_map expert parallelism with explicit
      ``all_to_all`` dispatch/combine (§Perf iteration D1: the GSPMD
      scatter lowered to full-buffer all-reduces, ~160× more collective
      bytes).  Requires a mesh with a "model" axis that divides L and E.
    * ``scatter`` — the GSPMD capacity-scatter formulation (baseline).
    """
    from repro.distributed.sharding_rules import get_mesh
    mesh = get_mesh()
    if (cfg.moe_impl == "a2a" and mesh is not None
            and "model" in mesh.axis_names):
        s = mesh.shape["model"]
        if (x.shape[1] % s == 0 and cfg.num_experts % s == 0 and s > 1):
            return _moe_forward_a2a(p, x, cfg, mesh)
    return _moe_forward_scatter(p, x, cfg)


def _moe_forward_scatter(p, x, cfg: ModelConfig):
    """x: (B, L, D) → (B, L, D), aux load-balance loss.

    Dispatch: rank tokens per expert by routing order (cumsum over the
    flattened (token, slot) stream); tokens past an expert's capacity are
    dropped (their combine weight is 0) — the standard bounded-buffer MoE.
    """
    b, L, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    fe = cfg.moe_d_ff or cfg.d_ff
    n = b * L
    cap = max(int(n * k / e * cfg.capacity_factor), 1)
    xt = x.reshape(n, d)

    logits = (xt.astype(jnp.float32) @ p["router"])         # (N, E)
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, k)                     # (N, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # aux loss (Switch): e · Σ_e f_e · P_e
    token_frac = jnp.mean(
        jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1), 0)
    prob_frac = jnp.mean(probs, 0)
    aux = e * jnp.sum(token_frac * prob_frac)

    flat_e = idx.reshape(-1)                                # (N·k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, 0) - onehot                    # rank in expert
    pos = jnp.sum(pos * onehot, -1)                         # (N·k,)
    keep = pos < cap
    tok = jnp.repeat(jnp.arange(n), k)

    buf = jnp.zeros((e, cap, d), xt.dtype)
    buf = shard(buf, "model", batch_axes(), None)
    buf = buf.at[flat_e, jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xt[tok], 0))
    buf = shard(buf, "model", batch_axes(), None)

    act = common.activation_fn(cfg.activation)
    h = act(jnp.einsum("ecd,edf->ecf", buf, p["experts_w1"]))
    if cfg.gated_mlp:
        h = h * jnp.einsum("ecd,edf->ecf", buf, p["experts_w3"])
    h = shard(h, "model", batch_axes(), None)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["experts_w2"])
    out_buf = shard(out_buf, "model", batch_axes(), None)

    gathered = out_buf[flat_e, jnp.where(keep, pos, 0)]     # (N·k, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    w = (gate.reshape(-1) * keep).astype(gathered.dtype)
    out = jnp.zeros((n, d), gathered.dtype).at[tok].add(gathered * w[:, None])

    if cfg.num_shared_experts:
        out = out + mlp_forward(p["shared"], xt, cfg)
    return out.reshape(b, L, d).astype(x.dtype), aux


# ----------------------------------------------------- shard_map EP (a2a)
def _local_dispatch(xt, gate, idx, e, cap):
    """Capacity-bounded local dispatch (per-device).  xt: (T, D);
    gate/idx: (T, k).  Returns (buf (E, cap, D), flat_e, pos, keep, tok)."""
    t, d = xt.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, 0) - onehot
    pos = jnp.sum(pos * onehot, -1)
    keep = pos < cap
    tok = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e, cap, d), xt.dtype)
    buf = buf.at[flat_e, jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xt[tok], 0))
    return buf, flat_e, pos, keep, tok


def _moe_forward_a2a(p, x, cfg: ModelConfig, mesh):
    """Expert parallelism with explicit all_to_all (classic EP — what the
    paper's Frontier codes would call the MPI_Alltoallv step).

    Layout inside shard_map: tokens sharded over (data…, model) — sequence
    split across the model axis for dispatch balance; experts over model;
    expert weights all-gathered over the FSDP axes on entry (ZeRO).
    dispatch: local (E, capₗ, D) buffers → all_to_all(model) → each shard
    holds (E/S, S·capₗ, D) for ITS experts; combine is the transpose.
    """
    from jax.sharding import PartitionSpec as P

    b, L, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    s = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    act = common.activation_fn(cfg.activation)
    t_loc = (b * L) // (s * dp_size)
    cap = max(int(t_loc * k / e * cfg.capacity_factor), 1)

    weights = {"router": p["router"], "w1": p["experts_w1"],
               "w2": p["experts_w2"]}
    w_specs = {"router": P(), "w1": P("model"), "w2": P("model")}
    if cfg.gated_mlp:
        weights["w3"] = p["experts_w3"]
        w_specs["w3"] = P("model")
    if cfg.num_shared_experts:
        weights["shared"] = p["shared"]
        w_specs["shared"] = jax.tree.map(lambda _: P(), p["shared"])

    def body(xs, w):
        # xs: (B_loc, L/S, D); router: (D, E); w1/w2/w3: (E/S, D|Fe, Fe|D)
        bl, ll, _ = xs.shape
        xt = xs.reshape(bl * ll, d)
        logits = xt.astype(jnp.float32) @ w["router"]
        probs = jax.nn.softmax(logits, -1)
        gate, idx = jax.lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        token_frac = jnp.mean(
            jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1), 0)
        aux = e * jnp.sum(token_frac * jnp.mean(probs, 0))
        aux = jax.lax.pmean(aux, ("model",) + dp)

        buf, flat_e, pos, keep, tok = _local_dispatch(xt, gate, idx, e, cap)
        # (E, cap, D) → (S, E/S, cap, D) → a2a → recv[j] = shard j's rows
        # for MY experts → (E/S, S·cap, D)
        buf = buf.reshape(s, e // s, cap, d)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                                  tiled=False)
        recv = jnp.moveaxis(recv, 0, 1).reshape(e // s, s * cap, d)

        h = act(jnp.einsum("ecd,edf->ecf", recv, w["w1"]))
        if cfg.gated_mlp:
            h = h * jnp.einsum("ecd,edf->ecf", recv, w["w3"])
        out_buf = jnp.einsum("ecf,efd->ecd", h, w["w2"])  # (E/S, S·cap, D)

        # combine: transpose route back to source shards
        out_buf = jnp.moveaxis(
            out_buf.reshape(e // s, s, cap, d), 1, 0)   # (S, E/S, cap, D)
        back = jax.lax.all_to_all(out_buf, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        back = back.reshape(e, cap, d)                  # == buf layout
        gathered = back[flat_e, jnp.where(keep, pos, 0)]
        gathered = jnp.where(keep[:, None], gathered, 0)
        gw = (gate.reshape(-1) * keep).astype(gathered.dtype)
        out = jnp.zeros_like(xt).at[tok].add(gathered * gw[:, None])
        if cfg.num_shared_experts:
            sh = w["shared"]
            sh_out = act(xt @ sh["w1"])
            if "w3" in sh:
                sh_out = sh_out * (xt @ sh["w3"])
            out = out + sh_out @ sh["w2"]
        return out.reshape(bl, ll, d), aux

    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(dp_spec, "model", None), w_specs),
                       out_specs=(P(dp_spec, "model", None), P()),
                       check_vma=False)
    out, aux = fn(x, weights)
    return out.astype(x.dtype), aux
