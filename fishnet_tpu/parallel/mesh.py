"""Mesh construction and sharded dispatch.

The reference scales within a host by one engine process per core
(reference: src/main.rs:151-161) and across hosts by server-mediated work
stealing. Here the within-host axis is a `jax.sharding.Mesh`: search lanes
are embarrassingly parallel, so the batch dimension shards over all chips
("dp"), with NNUE weights replicated in every chip's HBM — collectives only
appear in training (psum of grads over dp, all_gather over tp).

Every in/out spec below derives from the partition-rule registry
(parallel/partition.py) rather than hand-built literals, so a single-host
shard_map, a forced-multi-device CPU mesh and a multi-host
jax.distributed mesh (parallel/distributed.py builds that one) are ONE
data-driven code path; fishnet-lint's mesh-unregistered-spec rule keeps
it that way.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from ..aot import registry as _aot_registry
from ..utils import sanitize as _sanitize
from . import partition as _partition



def make_mesh(n_devices: Optional[int] = None, axis: str = "dp") -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


def make_2d_mesh(dp: int, tp: int) -> Mesh:
    devices = np.array(jax.devices()[: dp * tp]).reshape(dp, tp)
    return Mesh(devices, ("dp", "tp"))


def shard_batch(mesh: Mesh, tree, axis: str = "dp"):
    """Place a pytree of batched arrays with the leading dim sharded.

    Routed through distributed.put_global so the same call works when
    the mesh spans jax.distributed processes (each host contributes its
    addressable shards from identical host-side values)."""
    from . import distributed as _distributed

    def put(x):
        return _distributed.put_global(
            mesh, x, _partition.batch_spec(getattr(x, "ndim", 1), axis)
        )

    return jax.tree_util.tree_map(put, tree)


def replicate(mesh: Mesh, tree):
    from . import distributed as _distributed

    def put(x):
        return _distributed.put_global(
            mesh, x, _partition.replicated_spec()
        )

    return jax.tree_util.tree_map(put, tree)


@functools.lru_cache(maxsize=None)
def _segment_callable(mesh: Mesh, axis: str, has_tt: bool,
                      variant: str = "standard", deep_tt: bool = False,
                      prefer_deep: bool = False):
    """shard_map'd search segment: each device advances ITS lanes with ITS
    transposition-table shard, fully locally — no collectives, and a device
    whose lanes all park in DONE exits its while_loop early instead of
    spinning in lockstep with slower devices. This is the TPU-native
    equivalent of the reference's independent engine processes per core
    (reference: src/main.rs:151-161).

    segment_steps is a TRACED replicated scalar (retuning never recompiles)
    and tt_gen a per-lane (B,) sharded array. The per-shard packed boundary
    summary comes back stacked as (ndev, local_B+1, 4) so a no-finish
    boundary is one small host fetch, and state+TT are donated — a
    boundary rebinds shard handles instead of copying them."""
    from ..ops.search import _run_segment

    def seg(params, state, ttab, segment_steps, tt_gen):
        if ttab is not None:
            ttab = jax.tree.map(lambda a: a[0], ttab)  # (1, N) block → (N,)
        state, ttab, n, summ = _run_segment(
            params, state, ttab, segment_steps, variant, deep_tt,
            prefer_deep, tt_gen,
        )
        if ttab is not None:
            ttab = jax.tree.map(lambda a: a[None], ttab)
        return state, ttab, n.reshape(1), summ[None]

    in_specs, out_specs = _partition.segment_specs(has_tt, axis)
    fn = jax.shard_map(
        seg,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    # AOT-wrapped (fishnet_tpu/aot/): the shard_map closure's compile
    # flags become extra key material — all call arguments are dynamic.
    # The donation guard is a no-op unless FISHNET_TPU_SANITIZE is set,
    # and lru_cache means it wraps once per mesh config, not per call.
    return _sanitize.guard_donation(
        "parallel/mesh.py::mesh_segment",
        _aot_registry.wrap(
            "mesh_segment", jax.jit(fn, donate_argnums=(1, 2)), seg,
            extra_static={
                "mesh": "x".join(str(d) for d in mesh.devices.shape),
                "axis": axis, "has_tt": has_tt, "variant": variant,
                "deep_tt": deep_tt, "prefer_deep": prefer_deep,
            },
        ),
        argnums=(1, 2),
    )


def run_segment_sharded(mesh: Mesh, params, state, ttab, segment_steps: int,
                        axis: str = "dp", variant: str = "standard",
                        deep_tt: bool = False, prefer_deep: bool = False,
                        tt_gen=0):
    """Advance a sharded search ≤ segment_steps on every device.

    state: SearchState with lane dim divisible by mesh size. ttab: TTable
    whose arrays carry a leading (n_devices,) shard dim (see
    make_sharded_table), or None. Returns (state, ttab, steps (ndev,),
    summary (ndev, B/ndev + 1, 4)) — the packed per-shard boundary
    summary of ops/search._run_segment, stacked over shards.

    state and ttab are DONATED: the handles passed in are dead after the
    call and the caller must rebind to the outputs. segment_steps is
    traced, so retuning the segment length reuses the compiled program.
    prefer_deep/tt_gen: helper-lane TT store policy (ops/tt.py store);
    tt_gen may be a scalar or a per-lane (B,) array."""
    import jax.numpy as jnp

    from . import distributed as _distributed

    fn = _segment_callable(
        mesh, axis, ttab is not None, variant, deep_tt, prefer_deep,
    )
    B = int(state.lane.shape[0])
    gen = jnp.asarray(tt_gen, jnp.int32)
    if gen.ndim == 0:
        gen = jnp.full((B,), gen, jnp.int32)
    steps = jnp.int32(segment_steps)
    if _distributed.spans_processes(mesh):
        # host-local scalars/arrays must be promoted to global arrays
        # before a multi-host dispatch (every process holds identical
        # values, so this is pure placement, no communication)
        gen = _distributed.put_global(
            mesh, gen, _partition.spec_for("tt_gen", axis))
        steps = _distributed.put_global(
            mesh, steps, _partition.spec_for("segment_steps", axis))
    return fn(params, state, ttab, steps, gen)


@functools.lru_cache(maxsize=None)
def _splice_callable(mesh: Mesh, axis: str, variant: str):
    """shard_map'd refill splice (ops/search._splice_lanes): init_state
    and the masked select are per lane, so each shard rebuilds and
    merges its own slice of the lanes from its slice of the width-B
    operands — values change, shapes and shardings never, and the
    segment program keeps running with zero recompiles. The running
    state is donated (the splice rebinds, never copies)."""
    from ..ops.search import _splice_lanes

    # parameters spelled out: the AOT wrapper keys a call by binding it
    # to this signature, and leaves a *args program plain JIT for good
    def splice(params, state, roots, depth, node_budget, hist_hash,
               hist_halfmove, root_alpha, root_beta, order_jitter, group,
               mask):
        return _splice_lanes(
            params, state, roots, depth, node_budget, hist_hash,
            hist_halfmove, root_alpha, root_beta, order_jitter, group,
            mask, variant)

    in_specs, out_specs = _partition.splice_specs(axis)
    fn = jax.shard_map(
        splice,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return _sanitize.guard_donation(
        "parallel/mesh.py::mesh_splice",
        _aot_registry.wrap(
            "mesh_splice", jax.jit(fn, donate_argnums=(1,)), splice,
            extra_static={
                "mesh": "x".join(str(d) for d in mesh.devices.shape),
                "axis": axis, "variant": variant,
            },
        ),
        argnums=(1,),
    )


def refill_lanes_sharded(mesh: Mesh, params, state, new_roots, lane_idx,
                         depth, node_budget, *, axis: str = "dp",
                         variant: str = "standard", hist_hash=None,
                         hist_halfmove=None, root_alpha=None, root_beta=None,
                         order_jitter=None, group=None):
    """Splice replacement positions into DONE lanes of a SHARDED state.

    Same contract as ops/search.refill_lanes, with the splice routed
    through shard_map: the width-B operands go to the devices sharded
    by lane, and each device rewrites only its own lanes, locally.
    `state` is donated (rebind to the return value). lane_idx is global
    lane numbering — the host assigns lanes, the shard split falls out
    of the sharding."""
    from ..ops.search import _refill_inputs

    operands = _refill_inputs(
        state, new_roots, lane_idx, depth, node_budget,
        hist_hash=hist_hash, hist_halfmove=hist_halfmove,
        root_alpha=root_alpha, root_beta=root_beta,
        order_jitter=order_jitter, group=group,
    )
    if operands is None:
        return state
    return _splice_callable(mesh, axis, variant)(
        params, state, *shard_batch(mesh, operands, axis))


def make_sharded_table(mesh: Mesh, size_log2: int):
    """Per-device TT shards as one (ndev, N) array pair, placed sharded.

    Each device hashes into its private shard (ops/tt.py masks by the
    LOCAL size under shard_map) — cross-lane sharing happens within a
    device's lanes, which is where the lockstep phase offsets are anyway."""
    from ..ops import tt as tt_mod

    n = mesh.devices.size
    base = tt_mod.make_table(size_log2)
    import jax.numpy as jnp

    t = tt_mod.TTable(
        data=jnp.zeros((n, base.size, 4), jnp.int32),
    )
    return shard_batch(mesh, t)


def sharded_search(params, roots, depth, node_budget, max_ply: int,
                   mesh: Optional[Mesh] = None, tt=None, **kw):
    """Run the batched search with lanes sharded across the mesh.

    Thin wrapper over ops.search.search_batch_resumable(mesh=...) — the
    same code path the production TpuEngine uses (segments, deadline and
    the shared table all work sharded)."""
    from ..ops.search import search_batch_resumable

    mesh = mesh or make_mesh()
    B = int(roots.stm.shape[0])
    n = mesh.devices.size
    if B % n != 0:
        raise ValueError(f"lane count {B} must divide over {n} devices")
    return search_batch_resumable(
        params, roots, depth, node_budget, max_ply=max_ply, mesh=mesh,
        tt=tt, **kw
    )
