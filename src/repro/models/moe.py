"""Token-choice top-k Mixture-of-Experts with expert parallelism.

Two execution paths:

- ``ep_moe`` (production): a ``jax.shard_map`` region.  Activations are
  sharded over the batch axes and *replicated* over ``model``; experts are
  sharded over ``model`` (EP).  Each model-rank routes the local tokens,
  scatters the ones assigned to *its* experts into an (E_local, C, d) buffer
  (sort-free cumsum dispatch — no (T,E,C) one-hot einsum, so dispatch adds no
  matmul FLOPs), runs the expert GEMMs, gathers results back per token, adds
  the shared-expert partial product and psums over ``model`` — a single
  all-reduce per MoE layer, exactly like a Megatron TP FFN.
- ``dense_moe`` (fallback for tests / no-mesh execution): mathematically
  identical capacity-less routing via masked per-expert compute.

Capacity: ``C = ceil(top_k·T·cf/E)`` (GShard-style, overflow dropped) for
large T; for small-T decode shapes C is set to ``top_k·T`` so routing is
provably dropless (inference must not drop tokens).

Expert weights may additionally be sharded over the ``data`` axis
(FSDP-style, needed by kimi-k2's 1T params); they are all-gathered per layer
inside the shard_map region.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common import DTypePolicy, ParamSpec
from repro.models.layers import DATA_AXES, mlp_specs, apply_mlp


def moe_specs(cfg, tp: int, fsdp: bool = False):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    dt = cfg.params_dtype
    # experts sharded over model; optionally FSDP over data on the ff axis
    ff_ax = "data" if fsdp else None
    s = {
        "router": ParamSpec((d, e), jnp.float32, P(), init="small"),
        "w_in": ParamSpec((e, d, f), dt, P("model", None, ff_ax)),
        "w_gate": ParamSpec((e, d, f), dt, P("model", None, ff_ax)),
        "w_out": ParamSpec((e, f, d), dt, P("model", ff_ax, None)),
    }
    if m.n_shared:
        fs = f * m.n_shared
        s["shared"] = {
            "w_in": ParamSpec((d, fs), dt, P(None, "model")),
            "w_gate": ParamSpec((d, fs), dt, P(None, "model")),
            "w_out": ParamSpec((fs, d), dt, P("model", None)),
        }
    return s


def _route(cfg, p, x2d):
    """x2d (T, d) -> gates (T, k) fp32, experts (T, k) int32, aux loss scalar."""
    m = cfg.moe
    logits = x2d.astype(jnp.float32) @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    f_e = jnp.zeros((m.n_experts,), jnp.float32)
    for k in range(m.top_k):
        f_e = f_e + jnp.bincount(
            experts[:, k], length=m.n_experts, minlength=m.n_experts
        ).astype(jnp.float32)
    f_e = f_e / (x2d.shape[0] * m.top_k)
    aux = m.n_experts * jnp.sum(f_e * probs.mean(0)) * m.router_aux_coef
    return gates, experts, aux


def _positions_in_expert(experts, n_experts):
    """Per-(token,k) slot index within its expert (cumsum dispatch, no sort).

    Token-major, k-minor arrival order; memory O(T·E) int32 per k-slice.
    """
    t, kk = experts.shape
    base = jnp.zeros((n_experts,), jnp.int32)
    pos = []
    for k in range(kk):
        oh = jax.nn.one_hot(experts[:, k], n_experts, dtype=jnp.int32)  # (T, E)
        within = jnp.cumsum(oh, axis=0) - 1  # occurrence index per expert
        pos.append((within * oh).sum(-1) + jnp.take(base, experts[:, k]))
        base = base + oh.sum(0)
    return jnp.stack(pos, axis=1)  # (T, k)


def _capacity(cfg, t_local: int) -> int:
    m = cfg.moe
    if m.top_k * t_local <= 4096:  # decode-ish: make routing dropless
        return m.top_k * t_local
    c = math.ceil(m.top_k * t_local * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)


def _expert_ffn(cfg, w_in, w_gate, w_out, buf, cdt):
    h_in = jnp.einsum("ecd,edf->ecf", buf, w_in.astype(cdt))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(cdt))) * h_in
    return jnp.einsum("ecf,efd->ecd", h, w_out.astype(cdt))


def ep_moe(cfg, p, x, policy: DTypePolicy, mesh, fsdp: bool = False):
    """Expert-parallel MoE via shard_map. x (B, S, d) sharded over batch axes."""
    m = cfg.moe
    cdt = policy.compute
    e_total = m.n_experts
    tp = mesh.shape["model"]
    assert e_total % tp == 0, (e_total, tp)
    e_loc = e_total // tp
    ff_ax = "data" if fsdp else None

    def local_moe(p, x):
        b, s, d = x.shape
        t = b * s
        x2 = x.reshape(t, d)
        gates, experts, aux = _route(cfg, p, x2)
        cap = _capacity(cfg, t)
        pos = _positions_in_expert(experts, e_total)  # (T, k)
        rank = jax.lax.axis_index("model")
        e_lo = rank * e_loc
        local = (experts >= e_lo) & (experts < e_lo + e_loc) & (pos < cap)
        slot = jnp.where(local, (experts - e_lo) * cap + pos, e_loc * cap)  # dummy row
        # dispatch: scatter token rows into (E_local*C (+1 dummy), d)
        buf = jnp.zeros((e_loc * cap + 1, d), cdt)
        for k in range(m.top_k):
            buf = buf.at[slot[:, k]].add(jnp.where(local[:, k, None], x2.astype(cdt), 0))
        w_in, w_gate, w_out = p["w_in"], p["w_gate"], p["w_out"]
        if fsdp:  # gather the data-sharded ff axis of this layer's experts
            w_in = jax.lax.all_gather(w_in, "data", axis=2, tiled=True)
            w_gate = jax.lax.all_gather(w_gate, "data", axis=2, tiled=True)
            w_out = jax.lax.all_gather(w_out, "data", axis=1, tiled=True)
        out_rows = _expert_ffn(
            cfg, w_in, w_gate, w_out, buf[:-1].reshape(e_loc, cap, d), cdt
        ).reshape(e_loc * cap, d)
        out_rows = jnp.concatenate([out_rows, jnp.zeros((1, d), cdt)], axis=0)
        # combine: gather each (token, k)'s row, weight by gate
        y = jnp.zeros((t, d), cdt)
        for k in range(m.top_k):
            contrib = jnp.take(out_rows, slot[:, k], axis=0)
            y = y + contrib * (gates[:, k, None].astype(cdt) * local[:, k, None])
        if m.n_shared:
            y = y + apply_mlp(cfg, p["shared"], x2, policy)  # partial over ff shards
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, all_axes)  # replicated across the whole mesh
        return y.reshape(b, s, d), aux

    pspecs = {
        "router": P(),
        "w_in": P("model", None, ff_ax),
        "w_gate": P("model", None, ff_ax),
        "w_out": P("model", ff_ax, None),
    }
    if m.n_shared:
        pspecs["shared"] = {
            "w_in": P(None, "model"),
            "w_gate": P(None, "model"),
            "w_out": P("model", None),
        }
    avail = set(mesh.axis_names)
    baxes = tuple(a for a in DATA_AXES if a in avail)
    all_axes = tuple(a for a in ("pod", "data", "model") if a in avail)
    fn = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(pspecs, P(baxes, None, None)),
        out_specs=(P(baxes, None, None), P()),
        check_vma=False,
    )
    return fn(p, x)


def ep_moe_decode(cfg, p, x, policy: DTypePolicy, mesh, fsdp: bool):
    """Decode-shape MoE: replicated-token 2-D expert tensor parallelism.

    §Perf hillclimb (kimi-k2 / llama4 decode): the FSDP train layout shards
    expert ff over 'data'; gathering weights per layer at decode moves GBs
    per token step.  Tokens are tiny at decode — so move *tokens* instead:
    all-gather the (≤128 × d_model) token batch over 'data', let every chip
    compute its (expert-subset × ff-slice) contribution with its resident
    weight shard (the silu gate is elementwise in ff, so ff-slicing is
    exact), psum over (data, model), and slice back the local rows.
    Weight traffic: zero.  Collective traffic: MBs instead of GBs.
    """
    m = cfg.moe
    cdt = policy.compute
    tp = mesh.shape["model"]
    dp = mesh.shape.get("data", 1)
    e_loc = m.n_experts // tp
    avail = set(mesh.axis_names)
    baxes = tuple(a for a in DATA_AXES if a in avail)
    all_axes = tuple(a for a in ("pod", "data", "model") if a in avail)

    def local(p, x):
        b, s, d = x.shape  # local rows
        x2 = x.reshape(b * s, d)
        x_all = jax.lax.all_gather(x2, "data", axis=0, tiled=True)  # (T_pod, d)
        t_all = x_all.shape[0]
        gates, experts, aux = _route(cfg, p, x_all)
        # capacity: 8× the balanced expectation (bounded-overflow — routing
        # hot-spots beyond 8× drop, as production decode engines accept);
        # the fully-dropless cap (top_k·T) blew the dispatch buffers up 16×
        # and put the memory term above the weights themselves (§Perf)
        expected = -(-m.top_k * t_all // m.n_experts)
        cap = min(m.top_k * t_all, max(32, 8 * expected))
        pos = _positions_in_expert(experts, m.n_experts)
        rank_m = jax.lax.axis_index("model")
        e_lo = rank_m * e_loc
        mine = (experts >= e_lo) & (experts < e_lo + e_loc)
        slot = jnp.where(mine, (experts - e_lo) * cap + pos, e_loc * cap)
        buf = jnp.zeros((e_loc * cap + 1, d), cdt)
        for k in range(m.top_k):
            buf = buf.at[slot[:, k]].add(jnp.where(mine[:, k, None], x_all.astype(cdt), 0))
        buf = buf[:-1].reshape(e_loc, cap, d)
        # resident ff slice (fsdp: f/dp per chip; else full f)
        h_in = jnp.einsum("ecd,edf->ecf", buf, p["w_in"].astype(cdt))
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(cdt))) * h_in
        out_rows = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(cdt)).reshape(e_loc * cap, d)
        if not fsdp and dp > 1:
            out_rows = out_rows / dp  # full-f replicas would be summed dp times
        out_rows = jnp.concatenate([out_rows, jnp.zeros((1, d), cdt)], axis=0)
        y = jnp.zeros((t_all, d), cdt)
        for k in range(m.top_k):
            contrib = jnp.take(out_rows, slot[:, k], axis=0)
            y = y + contrib * (gates[:, k, None].astype(cdt) * mine[:, k, None])
        if m.n_shared:
            ysh = apply_mlp(cfg, p["shared"], x_all, policy)  # partial over model-ff
            y = y + (ysh / dp if dp > 1 else ysh)
        y = jax.lax.psum(y, ("data", "model") if dp > 1 else ("model",))
        # slice back this data-rank's rows
        rank_d = jax.lax.axis_index("data") if dp > 1 else 0
        y_loc = jax.lax.dynamic_slice_in_dim(y, rank_d * b * s, b * s, axis=0)
        aux = jax.lax.pmean(aux, all_axes)
        return y_loc.reshape(b, s, d), aux

    ff_ax = "data" if fsdp else None
    pspecs = {
        "router": P(),
        "w_in": P("model", None, ff_ax),
        "w_gate": P("model", None, ff_ax),
        "w_out": P("model", ff_ax, None),
    }
    if m.n_shared:
        pspecs["shared"] = {
            "w_in": P(None, "model"),
            "w_gate": P(None, "model"),
            "w_out": P("model", None),
        }
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspecs, P(baxes, None, None)),
        out_specs=(P(baxes, None, None), P()),
        check_vma=False,
    )
    return fn(p, x)


def dense_moe(cfg, p, x, policy: DTypePolicy):
    """Reference path: per-expert masked dense compute (no capacity, no drop)."""
    m = cfg.moe
    cdt = policy.compute
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    gates, experts, aux = _route(cfg, p, x2)
    weight = jnp.zeros((b * s, m.n_experts), jnp.float32)
    for k in range(m.top_k):
        weight = weight + jax.nn.one_hot(experts[:, k], m.n_experts) * gates[:, k, None]
    h_in = jnp.einsum("td,edf->tef", x2.astype(cdt), p["w_in"].astype(cdt))
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x2.astype(cdt), p["w_gate"].astype(cdt))) * h_in
    y_e = jnp.einsum("tef,efd->ted", h, p["w_out"].astype(cdt))
    y = jnp.einsum("ted,te->td", y_e, weight.astype(cdt))
    if m.n_shared:
        y = y + apply_mlp(cfg, p["shared"], x2, policy)
    return y.reshape(b, s, d), aux


def apply_moe(cfg, p, x, policy, mesh=None, fsdp=False, decode=False):
    if mesh is not None and "model" in mesh.axis_names and cfg.moe.n_experts % mesh.shape["model"] == 0:
        if decode and x.shape[0] * x.shape[1] <= 4096:
            return ep_moe_decode(cfg, p, x, policy, mesh, fsdp=fsdp)
        return ep_moe(cfg, p, x, policy, mesh, fsdp=fsdp)
    return dense_moe(cfg, p, x, policy)
