"""Grid-parallel Pallas launcher for the simulator hot loop.

The sim-step kernel is unusual for this repo: the unit of work is not a
tile of a large array but a *whole simulated sweep point* — the
request-stream scan (``simulator._run_impl``), optionally fused with the
on-device workload generator.  The ref tier maps points to the batch
axis with ``vmap``; this tier maps them to a 1-D Pallas grid instead,
one point per grid step:

* every per-point input (stacked ``MechParams`` leaves, the hoisted
  ``next_same`` row index, per-point workload/interleave params and
  warm-ups) arrives as a ``(1, ...)`` block selected by the grid index,
  so a point's bank-state carry, HCRAC table, and accumulators live
  entirely in VMEM/registers for the duration of its scan — nothing
  round-trips through HBM between steps;
* inputs shared by every point (the trace arrays, the per-distinct-
  geometry ``next_same`` tables) are broadcast blocks (zero index map),
  loaded once and reused by each grid step;
* grid steps are independent by construction (points never communicate),
  so the sweep dimension is declared ``parallel`` to the TPU compiler
  and interpret mode (on the CPU) simply runs them sequentially —
  with *identical* jnp semantics to the ref engine, which is what makes
  the bitwise-parity contract testable.  Mosaic accepts this block
  layout but not the scan body it wraps (see ``ops.MOSAIC_REFUSAL``).

The launcher below is generic over pytrees so the trace-driven and the
fused-synthesis entry points share one code path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grid_step_call"]


def _point_layout(shape: tuple) -> tuple:
    """Per-point block shape with the grid axis kept out of the last two
    dimensions (Mosaic tiles those; a ``(1,)``-blocked rank-1 leaf is
    illegal there): one point's ``shape`` is laid out as ``(1, *shape)``
    padded with unit dims to rank >= 3."""
    return (1,) * max(1, 3 - len(shape)) + tuple(shape)


def _stacked_spec(point_shape):
    blk = _point_layout(point_shape)
    return pl.BlockSpec(blk, lambda i, _nd=len(blk) - 1: (i,) + (0,) * _nd)


def _shared_spec(x):
    nd = x.ndim
    return pl.BlockSpec(x.shape, lambda i, _nd=nd: (0,) * _nd)


def grid_step_call(stacked, shared, body_fn, *, interpret: bool):
    """Run ``body_fn(point, shared)`` once per sweep point on a 1-D
    Pallas grid.

    ``stacked`` is a pytree whose leaves carry a leading ``[G]`` axis
    (one block per grid step, the vmap-axis analogue); ``shared`` is a
    pytree broadcast whole to every step.  Returns ``body_fn``'s output
    pytree with a leading ``[G]`` axis — shape-compatible with
    ``jax.vmap(body_fn, in_axes=(0, None))``, which is exactly the ref
    engine's launch and the parity oracle.  Leaves must be ``ndim >= 1``
    (wrap scalars as shape-(1,) arrays; 0-d blocks are not portable
    Pallas refs)."""
    s_leaves, s_def = jax.tree_util.tree_flatten(stacked)
    sh_leaves, sh_def = jax.tree_util.tree_flatten(shared)
    assert s_leaves, "grid_step_call needs at least one stacked leaf"
    assert all(x.ndim >= 1 for x in s_leaves + sh_leaves)
    n_grid = s_leaves[0].shape[0]
    assert all(x.shape[0] == n_grid for x in s_leaves)

    # zero-size leaves (e.g. absent-mechanism pad hints: [G, 0] NUAT bin
    # arrays) carry no data but are illegal Pallas blocks — reconstruct
    # them as empty jnp.zeros on either side of the call instead
    s_live = [x for x in s_leaves if x.size]
    sh_live = [x for x in sh_leaves if x.size]
    n_s = len(s_live)

    point0 = jax.tree_util.tree_unflatten(
        s_def, [x[0] for x in s_leaves])
    out_struct = jax.eval_shape(body_fn, point0, shared)
    o_leaves, o_def = jax.tree_util.tree_flatten(out_struct)
    o_live = [s for s in o_leaves if 0 not in s.shape]

    def _rebuild(tree_def, live_vals, all_leaves, point: bool):
        it = iter(live_vals)
        vals = [next(it) if x.size else
                jnp.zeros(x.shape[1:] if point else x.shape, x.dtype)
                for x in all_leaves]
        return jax.tree_util.tree_unflatten(tree_def, vals)

    def kern(*refs):
        in_refs, out_refs = refs[:n_s + len(sh_live)], refs[n_s + len(sh_live):]
        point = _rebuild(s_def, [r[...].reshape(x.shape[1:])
                                 for r, x in zip(in_refs[:n_s], s_live)],
                         s_leaves, point=True)
        shr = _rebuild(sh_def, [r[...] for r in in_refs[n_s:]],
                       sh_leaves, point=False)
        out = body_fn(point, shr)
        live = [v for v in jax.tree_util.tree_leaves(out)
                if jnp.asarray(v).size]
        for r, v in zip(out_refs, live):
            r[...] = jnp.asarray(v).reshape(r.shape)

    grid_shape = lambda shape: (n_grid,) + _point_layout(shape)[1:]
    res = pl.pallas_call(
        kern,
        grid=(n_grid,),
        in_specs=[_stacked_spec(x.shape[1:]) for x in s_live]
        + [_shared_spec(x) for x in sh_live],
        out_specs=[_stacked_spec(s.shape) for s in o_live],
        out_shape=[jax.ShapeDtypeStruct(grid_shape(s.shape), s.dtype)
                   for s in o_live],
        interpret=interpret,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",))}),
    )(*[x.reshape(grid_shape(x.shape[1:])) for x in s_live], *sh_live)
    it = iter(list(res))
    out_vals = [next(it).reshape((n_grid,) + s.shape) if 0 not in s.shape
                else jnp.zeros((n_grid,) + s.shape, s.dtype)
                for s in o_leaves]
    return jax.tree_util.tree_unflatten(o_def, out_vals)
