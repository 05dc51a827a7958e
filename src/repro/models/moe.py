"""Mixture-of-Experts layer with expert parallelism and cost-model placement.

Execution scheme ("replicated-dispatch EP", DESIGN.md §6): activations are
batch-sharded over the data axes and *replicated* over the model axis, while
experts are sharded over the model axis.  Inside a shard_map every model
rank routes its local tokens, gathers the subset destined for *its* experts
into a capacity-padded (E_local, C, D) block, applies the expert FFNs as
batched GEMMs, scatters weighted results back, and a single psum over the
model axis combines contributions — exactly one all-reduce per MoE layer
(the same collective cost as a Megatron TP FFN), zero all-to-alls.

The paper's technique enters through ``expert_placement``: expert->rank
assignment is a weighted-graph partition (core/partition.py) where vertex
weights are observed expert token loads and edges are co-activation counts,
so hot experts spread across ranks — the FMM subtree load-balancing model
transplanted to MoE (DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .config import ModelConfig
from ..core.partition import Graph, partition


def make_fsdp_gather_q8(axes, compute_dtype):
    """int8-quantized FSDP all-gather with straight-through backward.

    Forward: per-expert absmax int8 quantization of the local dim-1 shard,
    all-gather of the int8 payload (+ tiny per-(expert, shard) scales),
    dequantize to the compute dtype — the wire carries 1 byte/element
    instead of 2.  Backward: the exact adjoint of a tiled all-gather
    (psum_scatter), i.e. the quantizer is treated as identity (STE).
    """

    def _quantized_gather(w):
        scale = jnp.max(jnp.abs(w), axis=(1, 2), keepdims=True) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        qg = jax.lax.all_gather(q, axes, axis=1, tiled=True)
        sg = jax.lax.all_gather(scale, axes, axis=1, tiled=True)  # (E, nsh, 1)
        e, d_full, f = qg.shape
        nsh = sg.shape[1]
        blocks = qg.reshape(e, nsh, d_full // nsh, f).astype(compute_dtype)
        return (blocks * sg[..., None].astype(compute_dtype)).reshape(e, d_full, f)

    @jax.custom_vjp
    def gather(w):
        return _quantized_gather(w)

    def _fwd(w):
        return _quantized_gather(w), None

    def _bwd(_, g):
        gl = jax.lax.psum_scatter(g.astype(jnp.float32), axes,
                                  scatter_dimension=1, tiled=True)
        return (gl,)

    gather.defvjp(_fwd, _bwd)
    return gather


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    D, E, F = cfg.d_model, m.num_experts, m.expert_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": jax.random.normal(k1, (D, E), dtype) * D ** -0.5,
        "experts_gate": jax.random.normal(k2, (E, D, F), dtype) * D ** -0.5,
        "experts_in": jax.random.normal(k3, (E, D, F), dtype) * D ** -0.5,
        "experts_out": jax.random.normal(k4, (E, F, D), dtype) * F ** -0.5,
    }


def _moe_local(x, router, wg, wi, wo, *, top_k: int, num_experts: int,
               capacity: int, e_start, axis_name: Optional[str]):
    """Per-device MoE body.  x: (N, D) local tokens; wg/wi/wo: local experts.

    Routes all N tokens, keeps only those destined for this rank's experts
    [e_start, e_start + E_local), computes, and returns the partial output
    (psum over ``axis_name`` completes it).
    """
    N, D = x.shape
    E_local = wg.shape[0]
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))      # (N, E)
    gate_w, gate_e = jax.lax.top_k(logits, top_k)                      # (N, k)
    gate_w = jax.nn.softmax(gate_w, axis=-1)

    flat_e = gate_e.reshape(-1)                                        # (N*k,)
    flat_w = gate_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(N), top_k)

    local_e = flat_e - e_start
    mine = (local_e >= 0) & (local_e < E_local)
    local_e = jnp.where(mine, local_e, 0)

    # rank of each (token, choice) within its expert, among *my* assignments
    onehot = jnp.where(mine[:, None],
                       jax.nn.one_hot(local_e, E_local, dtype=jnp.int32), 0)
    rank = jnp.cumsum(onehot, axis=0) - onehot                         # exclusive
    rank = (rank * onehot).sum(-1)                                     # (N*k,)
    keep = mine & (rank < capacity)

    slot = local_e * capacity + rank                                   # (N*k,)
    slot = jnp.where(keep, slot, E_local * capacity)                   # overflow bin
    # gather tokens into (E_local*capacity+1, D) then drop the bin
    xe = jnp.zeros((E_local * capacity + 1, D), x.dtype).at[slot].set(
        jnp.where(keep[:, None], x[flat_tok], 0))
    xe = xe[:-1].reshape(E_local, capacity, D)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(x.dtype))) * \
        jnp.einsum("ecd,edf->ecf", xe, wi.astype(x.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, wo.astype(x.dtype))             # (E_local, C, D)

    yflat = jnp.concatenate([ye.reshape(-1, D), jnp.zeros((1, D), ye.dtype)])
    ytok = yflat[slot] * flat_w[:, None].astype(ye.dtype)              # (N*k, D)
    out = jnp.zeros_like(x).at[flat_tok].add(jnp.where(keep[:, None], ytok, 0))
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out


def moe_layer(p, x, cfg: ModelConfig, mesh: Optional[Mesh] = None,
              placement: Optional[np.ndarray] = None):
    """x: (B, T, D) -> (B, T, D).

    ``placement``: optional permutation of expert ids (cost-model expert
    placement); expert weights are pre-permuted at load/update time so rank
    r's shard holds the experts assigned to it.
    """
    B, T, D = x.shape
    m = cfg.moe
    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        # single-rank path (smoke tests): all experts local
        cap = int(np.ceil(B * T * m.top_k / m.num_experts * m.capacity_factor))
        out = _moe_local(x.reshape(B * T, D), p["router"], p["experts_gate"],
                         p["experts_in"], p["experts_out"], top_k=m.top_k,
                         num_experts=m.num_experts, capacity=max(cap, 1),
                         e_start=0, axis_name=None)
        return out.reshape(B, T, D)

    tp = mesh.shape["model"]
    assert m.num_experts % tp == 0, (m.num_experts, tp)
    e_local = m.num_experts // tp
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    n_local = (B // dp if B % dp == 0 else B) * T
    cap = int(np.ceil(n_local * m.top_k / m.num_experts * m.capacity_factor))
    cap = max(cap, 1)
    # FSDP for expert weights: dim 1 sharded over the data axes when it
    # divides; the body gathers it back per layer (in compute dtype, so the
    # wire format is bf16 — half the f32 master-weight traffic).  The
    # fallback chain mirrors parallel.sharding.param_spec so storage and
    # shard_map specs agree (no hidden resharding).
    dim1 = p["experts_gate"].shape[-2]
    if dp > 1 and dim1 % dp == 0:
        fsdp_ax = dp_axes
    elif "data" in mesh.axis_names and mesh.shape["data"] > 1 \
            and dim1 % mesh.shape["data"] == 0:
        fsdp_ax = ("data",)
    else:
        fsdp_ax = None

    def body(xs, router, wg, wi, wo):
        rank = jax.lax.axis_index("model")
        Bl, Tl, _ = xs.shape
        if fsdp_ax is not None:
            if cfg.moe_gather_bits == 8:
                gather = make_fsdp_gather_q8(fsdp_ax, xs.dtype)
                wg, wi, wo = gather(wg), gather(wi), gather(wo)
            else:
                wg = jax.lax.all_gather(wg.astype(xs.dtype), fsdp_ax, axis=1,
                                        tiled=True)
                wi = jax.lax.all_gather(wi.astype(xs.dtype), fsdp_ax, axis=1,
                                        tiled=True)
                wo = jax.lax.all_gather(wo.astype(xs.dtype), fsdp_ax, axis=1,
                                        tiled=True)
        out = _moe_local(xs.reshape(Bl * Tl, D), router, wg, wi, wo,
                         top_k=m.top_k, num_experts=m.num_experts,
                         capacity=cap, e_start=rank * e_local,
                         axis_name="model")
        return out.reshape(Bl, Tl, D)

    x_spec = P(dp_axes if dp_axes else None, None, None)
    e_spec = P("model", fsdp_ax, None)
    # the layer shards by hand, so it runs on the mesh's devices with Auto
    # axes: over jax.make_mesh's default Explicit axes, JAX refuses an eager
    # call (and eager ops on its output) outside a jax.set_mesh context
    mesh = Mesh(mesh.devices, mesh.axis_names)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(x_spec, P(None, None), e_spec, e_spec, e_spec),
                       out_specs=x_spec)
    return fn(x, p["router"], p["experts_gate"], p["experts_in"],
              p["experts_out"])


def moe_param_specs(mesh: Mesh) -> dict:
    """PartitionSpecs for MoE params (experts over the model axis = EP)."""
    return {
        "router": P(None, None),
        "experts_gate": P("model", None, None),
        "experts_in": P("model", None, None),
        "experts_out": P("model", None, None),
    }


# ---------------------------------------------------------------------------
# Cost-model expert placement (the paper's technique, transplanted)
# ---------------------------------------------------------------------------


def expert_placement(token_counts: np.ndarray, coactivation: np.ndarray,
                     num_ranks: int) -> np.ndarray:
    """Assign experts to EP ranks balancing load and minimizing co-traffic.

    token_counts: (E,) observed tokens routed per expert (vertex weights =
    the paper's per-subtree work estimate); coactivation: (E, E) counts of
    experts co-selected for the same token (edge weights = the paper's
    inter-subtree communication estimate).  Returns (E,) rank per expert.
    """
    E = len(token_counts)
    adjacency = [[] for _ in range(E)]
    for i in range(E):
        for j in range(i + 1, E):
            if coactivation[i, j] > 0:
                adjacency[i].append((j, float(coactivation[i, j])))
                adjacency[j].append((i, float(coactivation[i, j])))
    g = Graph(vertex_weight=np.asarray(token_counts, np.float64), adjacency=adjacency)
    assign = partition(g, num_ranks, method="model",
                       order=np.argsort(-np.asarray(token_counts)))
    return assign


def placement_permutation(assign: np.ndarray, num_ranks: int) -> np.ndarray:
    """Expert-id permutation so rank r's contiguous shard = its experts.

    Pads ranks to equal expert counts by stealing from the least-loaded
    ranks is NOT done here — callers should ensure |experts per rank| is
    uniform (capacity-style placement); we round-robin any remainder.
    """
    E = len(assign)
    per = E // num_ranks
    buckets = [list(np.where(assign == r)[0]) for r in range(num_ranks)]
    # rebalance counts to exactly `per` per rank (EP shards must be equal)
    overflow = []
    for b in buckets:
        while len(b) > per:
            overflow.append(b.pop())
    for b in buckets:
        while len(b) < per:
            b.append(overflow.pop())
    return np.concatenate([np.asarray(b, np.int64) for b in buckets])
