"""Mesh-scale step builders: stacked-client DisPFL training and personalized
sparse serving.

The whole decentralized system is one SPMD program: client models are
stacked on a leading K dim (sharded over the client mesh axes), the
intersection gossip is an adjacency einsum over that dim (GSPMD emits the
collectives), and the local masked-SGD step is a vmap over clients.

``plan_for`` decides the client mapping per (arch x input-shape x mesh):
  * normal archs: K = client capacity of the mesh (16 / 32), per-client
    batch = global_batch // K;
  * jamba-scale archs (``fsdp2d``): K = 1 per pod (2 on the multi-pod mesh),
    weights 2-D sharded (FSDP 'data' x TP 'model') inside the pod;
  * long_500k (global_batch=1): K = 1, 2-D weights, KV-cache seq dim sharded
    over 'data' (context parallelism).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.launch.mesh import client_capacity
from repro.models.registry import ModelAPI, bind
from repro.sharding.rules import (
    tree_batch_shardings,
    tree_cache_shardings,
    tree_param_shardings,
)

PyTree = Any

WEIGHT_DECAY = 5e-4
FSDP2D_ARCHS = ("jamba-1.5-large-398b",)


@dataclasses.dataclass
class ScalePlan:
    arch: ModelConfig
    shape: InputShape
    mesh: Mesh
    n_clients: int
    per_client_batch: int
    fsdp2d: bool
    seq_data: bool          # context-parallel KV cache (long-context K=1)
    dtype: Any = jnp.bfloat16

    @property
    def max_cache_len(self) -> int:
        return self.shape.seq_len


def plan_for(arch: ModelConfig, shape: InputShape, mesh: Mesh,
             dtype=jnp.bfloat16) -> ScalePlan:
    gb = shape.global_batch
    big = arch.name in FSDP2D_ARCHS
    if big:
        k = 2 if "pod" in mesh.axis_names else 1
        k = min(k, gb)
    else:
        k = client_capacity(mesh)
        if gb < k or gb % k:
            k = 1                      # long_500k path: single sharded client
    fsdp2d = big or k == 1
    seq_data = shape.mode == "decode" and k == 1 and shape.seq_len >= 65536
    return ScalePlan(arch=arch, shape=shape, mesh=mesh, n_clients=k,
                     per_client_batch=gb // k, fsdp2d=fsdp2d,
                     seq_data=seq_data, dtype=dtype)


# ---------------------------------------------------------------------------
# Abstract state construction (ShapeDtypeStruct only — no allocation)
# ---------------------------------------------------------------------------


def _stack_specs(tree: PyTree, k: int) -> PyTree:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((k,) + tuple(s.shape), s.dtype), tree)


def abstract_params(api: ModelAPI, plan: ScalePlan) -> PyTree:
    shapes = jax.eval_shape(
        lambda: api.init(jax.random.PRNGKey(0), plan.dtype))
    return _stack_specs(shapes, plan.n_clients)


def abstract_masks(params_spec: PyTree) -> PyTree:
    """Masks stored as int8 (w ⊙ m casts at use sites)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.int8), params_spec)


def abstract_cache(api: ModelAPI, plan: ScalePlan) -> PyTree:
    shapes = jax.eval_shape(
        lambda: api.init_cache(plan.per_client_batch, plan.max_cache_len,
                               plan.dtype))
    return _stack_specs(shapes, plan.n_clients)


def input_specs(api: ModelAPI, plan: ScalePlan) -> PyTree:
    """Stacked (K, ...) batch ShapeDtypeStructs for the plan's shape."""
    per = api.input_specs(plan.shape, plan.dtype, batch=plan.per_client_batch)
    stacked = _stack_specs(per, plan.n_clients)
    if plan.shape.mode == "decode":
        stacked["pos"] = jax.ShapeDtypeStruct((plan.n_clients,), jnp.int32)
    return stacked


def adjacency_spec(plan: ScalePlan) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((plan.n_clients, plan.n_clients), jnp.float32)


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------


def make_train_step(api: ModelAPI, plan: ScalePlan, gossip: str = "einsum"):
    """One DisPFL round step: intersection gossip + one masked-SGD step.

    gossip: 'einsum' (adjacency matmul over the stacked client dim — the
    baseline, ``repro.core.gossip.masked_gossip_stacked``, the one stacked
    gossip, shared with ``ScaleEngine``), 'none' (ablation / non-FL
    training), or 'ppermute' (neighbor exchange via shard_map
    collective_permute, see launch/gossip_opt.py).
    """
    from repro.core.gossip import masked_gossip_stacked

    wd = WEIGHT_DECAY

    def train_step(params, masks, batch, adjacency, lr):
        if gossip in ("einsum", "einsum_bf16") and plan.n_clients == 1:
            # adjacency is the 1x1 identity: the intersection average
            # reduces exactly to w (already masked) — skip the mixing pass
            # ('einsum_noopt' keeps it, as the §Perf before-measurement)
            pass
        elif gossip in ("einsum", "einsum_bf16", "einsum_noopt"):
            acc_dt = jnp.bfloat16 if gossip == "einsum_bf16" else jnp.float32
            params = masked_gossip_stacked(params, masks, adjacency,
                                           reduction="einsum",
                                           accum_dtype=acc_dt)
        elif gossip == "ppermute":
            from repro.launch.gossip_opt import ppermute_gossip
            params = ppermute_gossip(params, masks, plan)

        def total_loss(ps):
            losses, _ = jax.vmap(lambda p, b: api.train_loss(p, b))(ps, batch)
            return jnp.sum(losses), losses

        (_, losses), grads = jax.value_and_grad(total_loss, has_aux=True)(params)

        def upd(w, g, m):
            mf = m.astype(jnp.float32)
            wf = w.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            return ((wf - lr * (gf + wd * wf) * mf) * mf).astype(w.dtype)

        params = jax.tree.map(upd, params, grads, masks)
        return params, losses

    return train_step


def make_mask_update_step(api: ModelAPI, plan: ScalePlan, density: float = 0.5):
    """Once-per-round mask search (Alg. 2) as one SPMD program.

    Per client: dense gradient on one batch, then the threshold-based
    stacked prune/regrow of ``repro.scale.stacked_prune_regrow_threshold``
    (kth order statistics via sort — ``core.evolve.evolve_mask_layer``'s
    selection, up to ties).  Layer budgets are static (``density`` x
    numel), so the program is shape-static and lowers like the train step.
    Practical for <=30B-param archs (the sort is O(n log n) per leaf).
    """
    from repro.scale.stacked import stacked_prune_regrow_threshold

    def mask_update(params, masks, batch, prune_rate):
        def dense_grad(p, b):
            return jax.grad(lambda q: api.train_loss(q, b)[0])(p)

        grads = jax.vmap(dense_grad)(params, batch)
        new_masks, new_params = stacked_prune_regrow_threshold(
            params, masks, grads, prune_rate, density)
        return new_params, new_masks

    return mask_update


def make_prefill_step(api: ModelAPI, plan: ScalePlan):
    def prefill_step(params, batch, cache):
        return jax.vmap(api.prefill)(params, batch, cache)

    return prefill_step


def make_decode_step(api: ModelAPI, plan: ScalePlan):
    def decode_step(params, batch, cache):
        tokens = batch["tokens"]
        pos = batch["pos"]
        logits, cache = jax.vmap(api.decode)(params, tokens, pos, cache)
        next_tok = jnp.argmax(logits[..., -1, :], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return decode_step


# ---------------------------------------------------------------------------
# Sharding assembly + lowering
# ---------------------------------------------------------------------------


def state_shardings(api: ModelAPI, plan: ScalePlan):
    params_spec = abstract_params(api, plan)
    mesh = plan.mesh
    p_sh = tree_param_shardings(params_spec, mesh, plan.fsdp2d)
    m_sh = p_sh  # masks mirror their parameters
    return params_spec, p_sh, m_sh


def lower_train(api: ModelAPI, plan: ScalePlan, gossip: str = "einsum"):
    mesh = plan.mesh
    params_spec, p_sh, m_sh = state_shardings(api, plan)
    masks_spec = abstract_masks(params_spec)
    batch_spec_tree = input_specs(api, plan)
    b_sh = tree_batch_shardings(batch_spec_tree, mesh, plan.fsdp2d)
    adj = adjacency_spec(plan)
    repl = NamedSharding(mesh, P())
    k_sh = NamedSharding(mesh, P())
    step = make_train_step(api, plan, gossip)
    from repro.sharding import use_mesh_rules
    with use_mesh_rules(mesh):
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, m_sh, b_sh, repl, repl),
            out_shardings=(p_sh, k_sh),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(params_spec, masks_spec, batch_spec_tree, adj,
                               jax.ShapeDtypeStruct((), jnp.float32))
    return lowered


def lower_serve(api: ModelAPI, plan: ScalePlan):
    mesh = plan.mesh
    params_spec, p_sh, _ = state_shardings(api, plan)
    cache_spec_tree = abstract_cache(api, plan)
    c_sh = tree_cache_shardings(cache_spec_tree, mesh, plan.seq_data,
                                fsdp2d=plan.fsdp2d)
    batch_spec_tree = input_specs(api, plan)
    b_sh = tree_batch_shardings(batch_spec_tree, mesh, plan.fsdp2d)
    from repro.sharding import use_mesh_rules
    overrides = {"kv_seq": ("data",)} if plan.seq_data else {"kv_seq": ()}
    with use_mesh_rules(mesh, overrides):
        if plan.shape.mode == "prefill":
            step = make_prefill_step(api, plan)
            logits_sh = NamedSharding(mesh, P())
            jitted = jax.jit(step,
                             in_shardings=(p_sh, b_sh, c_sh),
                             out_shardings=(logits_sh, c_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_spec, batch_spec_tree, cache_spec_tree)
        else:
            step = make_decode_step(api, plan)
            tok_sh = NamedSharding(mesh, P())
            jitted = jax.jit(step,
                             in_shardings=(p_sh, b_sh, c_sh),
                             out_shardings=(tok_sh, c_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_spec, batch_spec_tree, cache_spec_tree)
    return lowered


def lower_for(arch: ModelConfig, shape: InputShape, mesh: Mesh,
              gossip: str = "einsum", dtype=jnp.bfloat16, remat: bool = True,
              unroll: bool = False, remat_policy: str = "full"):
    """Entry point used by dryrun.py: returns (plan, lowered).

    unroll=True unrolls the layer scans so ``cost_analysis()`` counts every
    block (XLA costs a while-loop body once); used for the roofline pass.
    """
    plan = plan_for(arch, shape, mesh, dtype)
    api = bind(arch, remat=remat, unroll=unroll, remat_policy=remat_policy)
    if shape.mode == "train":
        return plan, lower_train(api, plan, gossip)
    return plan, lower_serve(api, plan)
