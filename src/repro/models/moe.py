"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Top-k routing (Switch/GShard lineage) with the memory-lean dispatch: tokens
are sorted by expert id within a *group* (one group per sequence, so sorts
stay local to the batch shard) and placed into (E, C) capacity slots; both
dispatch and combine are gathers/scatters of O(T·k·d) — never the
O(T·E·C) one-hot tensors of the classic einsum formulation, which blow up
at olmoe's 64-expert/top-8 configuration.

Experts are sharded over 'model' (expert parallelism); the per-expert FFN
is one batched einsum over the expert axis. Load-balancing auxiliary loss
is the standard Switch formulation.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.layers import dense_init
from repro.utils import rank_within_run


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0            # shared (always-on) experts, llama4-style
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


def moe_init(key, d_model: int, cfg: MoEConfig, act: str,
             dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    E, F = cfg.n_experts, cfg.d_ff_expert
    p = {
        "router": dense_init(ks[0], d_model, E, jnp.float32),
        "w_up": jax.vmap(lambda k: dense_init(k, d_model, F, dtype))(
            jax.random.split(ks[1], E)),
        "w_down": jax.vmap(lambda k: dense_init(k, F, d_model, dtype))(
            jax.random.split(ks[2], E)),
    }
    if act == "swiglu":
        p["w_gate"] = jax.vmap(lambda k: dense_init(k, d_model, F, dtype))(
            jax.random.split(ks[3], E))
    if cfg.n_shared:
        Fs = cfg.d_ff_expert * cfg.n_shared
        p["shared"] = {
            "w_up": dense_init(ks[4], d_model, Fs, dtype),
            "w_down": dense_init(jax.random.fold_in(ks[4], 1), Fs, d_model,
                                 dtype),
        }
        if act == "swiglu":
            p["shared"]["w_gate"] = dense_init(
                jax.random.fold_in(ks[4], 2), d_model, Fs, dtype)
    return p


def moe_axes(cfg: MoEConfig, act: str) -> dict:
    a = {
        # router is 328 KB — replicate it. Sharding it invites GSPMD to
        # all-gather the full-seq f32 activations instead (a 1.3 GB/layer
        # collective; EXPERIMENTS.md llama4 iteration 3).
        "router": (None, None),
        "w_up": ("experts", "w_fsdp", "w_mlp"),
        "w_down": ("experts", "w_mlp", "w_fsdp"),
    }
    if act == "swiglu":
        a["w_gate"] = ("experts", "w_fsdp", "w_mlp")
    if cfg.n_shared:
        a["shared"] = {"w_up": ("w_fsdp", "w_mlp"),
                       "w_down": ("w_mlp", "w_fsdp")}
        if act == "swiglu":
            a["shared"]["w_gate"] = ("w_fsdp", "w_mlp")
    return a


def _expert_ffn(params: dict, x: jax.Array, act: str) -> jax.Array:
    """x: (B, E, C, D) -> (B, E, C, D): one batched einsum pair over the
    expert axis, *outside* any vmap so the expert dim really shards over
    'model' (expert parallelism). A sharding constraint inside a vmapped
    body cannot name the expert axis of the batched intermediate — that
    layout replicates every expert's FFN across all model ranks, a 16x
    compute/memory regression caught by the §Perf roofline loop (see
    EXPERIMENTS.md llama4 iteration 1)."""
    x = constrain(x, "batch", "experts", "expert_cap", "embed")
    up = jnp.einsum("becd,edf->becf", x, params["w_up"])
    up = constrain(up, "batch", "experts", "expert_cap", "mlp")
    if act == "swiglu":
        gate = jnp.einsum("becd,edf->becf", x, params["w_gate"])
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    out = jnp.einsum("becf,efd->becd", h, params["w_down"])
    return constrain(out, "batch", "experts", "expert_cap", "embed")


def _dispatch_one_group(x: jax.Array, gates: jax.Array, idx: jax.Array,
                        E: int, C: int):
    """Sort-based capacity placement for one token group.

    x (T, D), gates/idx (T, k). Returns (expert_in (E, C, D), combine info).
    """
    T, K = idx.shape
    flat_e = idx.reshape(-1)                                  # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    pos = rank_within_run(se)
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)               # drop slot
    expert_in = jnp.zeros((E * C + 1, x.shape[-1]), x.dtype)
    expert_in = expert_in.at[slot].set(x[st])
    return expert_in[: E * C].reshape(E, C, -1), (st, sg, slot, keep)


def _combine_one_group(expert_out: jax.Array, info, T: int) -> jax.Array:
    st, sg, slot, keep = info
    E, C, D = expert_out.shape
    flat = expert_out.reshape(E * C, D)
    picked = flat[jnp.minimum(slot, E * C - 1)]
    w = jnp.where(keep, sg, 0.0).astype(flat.dtype)[:, None]
    out = jnp.zeros((T, D), expert_out.dtype)
    return out.at[st].add(picked * w)


def _a2a_path_available(cfg: MoEConfig, B: int, S: int) -> bool:
    """True when the explicit expert-parallel all-to-all path applies:
    a mesh with a 'model' axis is installed, experts divide across it,
    and the activation grid divides the mesh."""
    from repro.distributed.sharding import current_rules
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return False
    names = rules.mesh.axis_names
    if "model" not in names:
        return False
    sizes = dict(zip(names, rules.mesh.devices.shape))
    mp = sizes.get("model", 1)
    dp = 1
    for a in ("pod", "data"):
        dp *= sizes.get(a, 1)
    return (cfg.n_experts % mp == 0 and B % dp == 0 and S % mp == 0
            and mp > 1)


def _moe_weight_dims_divide(params: dict, mesh) -> bool:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = 1
    for a in ("pod", "data"):
        dp *= sizes.get(a, 1)
    return (params["w_up"].shape[1] % dp == 0
            and params["w_down"].shape[2] % dp == 0)


def _apply_moe_a2a(params: dict, x: jax.Array, gates: jax.Array,
                   idx: jax.Array, cfg: MoEConfig, act: str) -> jax.Array:
    """Expert-parallel MoE via shard_map + all_to_all (GShard lineage,
    TPU-native).

    GSPMD reshards the (batch, seq, embed) activations through a full
    all-gather + all-reduce per MoE layer when the gather/scatter
    dispatch crosses the 'model' axis (~22 GB/device/layer at llama4
    train_4k scale — the dominant roofline term; EXPERIMENTS.md llama4
    iteration 2). The information that actually has to move is one
    token-shard each way: dispatch tokens to their expert's owner rank,
    bring the FFN outputs back — two ~50 MB all-to-alls. shard_map makes
    those collectives explicit:

      per (data x model) shard: local top-k routing -> capacity-sort the
      local tokens by expert (_dispatch_one_group) -> all_to_all over
      'model' to the expert owners -> local expert FFN (weights
      FSDP-gathered over 'data' explicitly) -> reverse all_to_all ->
      local combine.

    Capacity is enforced per source shard (tokens_local * K / E * cf),
    so drop behaviour matches the reference path per-shard rather than
    per-sequence; Prop-style routing semantics are unchanged.
    """
    from repro.distributed.sharding import current_rules
    rules = current_rules()
    mesh = rules.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    mp = sizes["model"]
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    E, K = cfg.n_experts, cfg.top_k
    e_local = E // mp
    B, S, D = x.shape

    from jax.sharding import PartitionSpec as P

    def local(w_up, w_gate, w_down, xl, gl, il):
        # xl: (B_l, S_l, D); gl/il: (B_l, S_l, K) — this shard's tokens
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        C = max(1, int(T * K / E * cfg.capacity_factor))
        send, info = _dispatch_one_group(
            xl.reshape(T, D), gl.reshape(T, K), il.reshape(T, K), E, C)
        # (E, C, D) -> (mp, e_local * C, D): destination-major for a2a
        send = send.reshape(mp, e_local * C, D)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        # recv: (mp * e_local * C, D) grouped by source rank; regroup by
        # local expert: (src, e_local, C, D) -> (e_local, src * C, D)
        recv = recv.reshape(mp, e_local, C, D).transpose(1, 0, 2, 3)
        recv = recv.reshape(e_local, mp * C, D)

        # explicit FSDP: gather the weight shards over the data axes.
        # Cast to the compute dtype BEFORE gathering — collecting the f32
        # master copy doubles the wire bytes for nothing.
        def fsdp(w, axis):
            w = w.astype(xl.dtype)
            for a in data_axes:
                w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
            return w

        up = jnp.einsum("ecd,edf->ecf", recv, fsdp(w_up, 1))
        if act == "swiglu":
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv,
                                       fsdp(w_gate, 1))) * up
        else:
            h = jax.nn.gelu(up)
        eo = jnp.einsum("ecf,efd->ecd", h, fsdp(w_down, 2))

        # reverse: (e_local, mp, C, D) -> (mp, e_local * C, D) -> a2a back
        eo = eo.reshape(e_local, mp, C, D).transpose(1, 0, 2, 3)
        eo = eo.reshape(mp, e_local * C, D)
        back = jax.lax.all_to_all(eo, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        out = _combine_one_group(back.reshape(E, C, D), info, T)
        return out.reshape(Bl, Sl, D)

    act_spec = P(data_axes, "model", None)
    k_spec = P(data_axes, "model", None)
    # weight shards: experts over 'model', input dim FSDP over data axes
    w_spec = P("model", data_axes, None)
    w_gate = params.get("w_gate", params["w_up"])
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(w_spec, w_spec, P("model", None, data_axes),
                  act_spec, k_spec, k_spec),
        out_specs=act_spec, check_vma=False)
    return fn(params["w_up"], w_gate, params["w_down"], x,
              gates.astype(x.dtype), idx)


def apply_moe(params: dict, x: jax.Array, cfg: MoEConfig,
              act: str) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss). Groups = sequences (local sorts)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = max(1, int(S * K / E * cfg.capacity_factor))
    use_a2a = _a2a_path_available(cfg, B, S)
    if use_a2a:
        from repro.distributed.sharding import current_rules
        use_a2a = _moe_weight_dims_divide(params, current_rules().mesh)
    if not use_a2a:
        # the residual stream arrives sequence-sharded; dispatch sorts span
        # the whole sequence group, so reshard to batch-only first
        x = constrain(x, "batch", "seq_kv", "embed")

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)                   # (B, S, E)
    gates, idx = jax.lax.top_k(probs, K)                      # (B, S, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    if use_a2a:
        out = _apply_moe_a2a(params, x, gates, idx, cfg, act)
    else:
        # reference path: dispatch per group (sorts stay local to a
        # sequence), expert FFN batched across groups so experts shard
        # over 'model' under plain GSPMD
        expert_in, info = jax.vmap(
            lambda xg, gg, ig: _dispatch_one_group(xg, gg, ig, E, C))(
            x, gates.astype(x.dtype), idx)                # (B, E, C, D)
        expert_out = _expert_ffn(params, expert_in, act)  # (B, E, C, D)
        out = jax.vmap(lambda eo, st, sg, slot, keep:
                       _combine_one_group(eo, (st, sg, slot, keep), S))(
            expert_out, *info)

    if cfg.n_shared:
        # same layout discipline as the dense-FFN path (apply_mlp): keep
        # the sequence axis sharded, gather weights — without the
        # constraint GSPMD gathers full-seq activations instead.
        sp = params["shared"]
        up = constrain(x @ sp["w_up"], "batch", "seq", "mlp")
        h = jax.nn.silu(x @ sp["w_gate"]) * up if "w_gate" in sp \
            else jax.nn.gelu(up)
        out = out + constrain(h @ sp["w_down"], "batch", "seq", "embed")

    # Switch load-balance loss: E * sum_e f_e * p_e
    f = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                 axis=(0, 1))
    pbar = jnp.mean(probs, axis=(0, 1))
    aux = cfg.aux_loss_weight * E * jnp.sum(f * pbar)
    return constrain(out, "batch", "seq", "embed"), aux
