"""Mesh-sharded distributed runtime.

The core GARs (``repro.core``) operate on a flat ``(n, d)`` matrix — fine
for one device, fatal at scale: materializing every worker's full gradient
vector in one array defeats model-parallel sharding.  This package is the
production path:

  mesh.py      device meshes (host smoke meshes + the production pods)
  sharding.py  NamedSharding/PartitionSpec rules for params, optimizer
               state, worker-stacked batches, and KV caches
  robust.py    the tree-aware aggregation *engine*: per-leaf partial
               Gram matrices (the (n, n) distance matrix is the only
               global object), the distance_backend= xla/pallas/auto
               dispatch (shard-mapped Pallas kernel on the sharded
               path), windowed coordinate phase, per-leaf attacks —
               rule bodies resolve through the ``repro.agg`` registry
  train.py     the jit-able sharded Byzantine train step
  async_train.py  the asynchronous bounded-staleness runtime: the
               versioned GradientBus, deterministic delay schedules,
               and the async train step aggregating the slot stack
               through the same registry (docs/async-runtime.md)
  serve.py     prefill/decode steps consumed by the dry-run and engine
  serve_robust.py  Byzantine-resilient ensemble serving: replica param
               stacks (axis mapped onto ``data``), per-token logits
               aggregation through the ``repro.agg`` registry, AggState
               carried across the decode stream (docs/serving.md)

Everything is plain jit-compatible jnp: sharding enters exclusively via
the input/output shardings (XLA GSPMD propagation), so the same step
function runs unsharded on one device and sharded on a pod — which is
exactly what ``tests/test_dist.py`` pins down.  The one deliberate
exception is the Pallas distance backend, whose ``shard_map`` block pins
the kernel's layout explicitly; see docs/dist-runtime.md.
"""
from repro.dist.mesh import (make_host_mesh, make_production_mesh,
                             mesh_axis_sizes)
from repro.dist.robust import (DistAggResult, coordinate_phase_nd,
                               distributed_aggregate, inject_byzantine,
                               pairwise_sq_dists_tree,
                               resolve_distance_backend)
from repro.dist.sharding import (batch_pspec, cache_shardings,
                                 ensemble_cache_shardings,
                                 ensemble_param_shardings, gram_pspec,
                                 param_shardings)
from repro.dist.train import init_agg_state, make_loss_fn, make_train_step
from repro.dist.async_train import (GradientBus, delivery_mask,
                                    init_async_state, init_bus,
                                    make_async_train_step, resolve_tau,
                                    update_bus)
from repro.dist.serve import make_prefill_step, make_serve_step
from repro.dist.serve_robust import (aggregate_logits, init_ensemble_state,
                                     make_robust_prefill_step,
                                     make_robust_serve_step,
                                     poison_replicas, replicate_cache,
                                     replicate_params, stack_replicas)

__all__ = [
    "DistAggResult", "GradientBus", "aggregate_logits",
    "batch_pspec", "cache_shardings", "coordinate_phase_nd",
    "delivery_mask", "distributed_aggregate", "ensemble_cache_shardings",
    "ensemble_param_shardings", "gram_pspec", "init_agg_state",
    "init_async_state", "init_bus", "init_ensemble_state",
    "inject_byzantine", "make_async_train_step", "make_host_mesh",
    "make_loss_fn", "make_prefill_step", "make_production_mesh",
    "make_robust_prefill_step", "make_robust_serve_step", "make_serve_step",
    "make_train_step", "mesh_axis_sizes", "pairwise_sq_dists_tree",
    "param_shardings", "poison_replicas", "replicate_cache",
    "replicate_params", "resolve_distance_backend", "resolve_tau",
    "stack_replicas", "update_bus",
]
