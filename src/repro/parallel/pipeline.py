"""GPipe-style pipeline parallelism over a mesh axis (the "pod" axis).

`gpipe_apply` runs a stage function over S pipeline stages living on the
`axis` mesh dimension, streaming M microbatches through a fill/compute/drain
schedule implemented with `jax.lax.ppermute` inside `shard_map`.  Reverse-
mode AD through the schedule yields the backward pipeline automatically
(ppermute transposes to the reverse permutation), so the same primitive
serves training.

Schedule (classic GPipe):  time t ∈ [0, M+S-1);  stage s computes microbatch
t−s (garbage during fill/drain — the standard bubble, fraction (S−1)/(M+S−1));
the last stage emits microbatch t−(S−1) at time t.

This composes with the in-pod rules of parallel/sharding.py: the pod axis
carries stages, data/model axes keep DP/TP within each stage — the
configuration a 1000+-node deployment would use when cross-pod DCN bandwidth
is too thin for gradient all-reduce (pipeline the layers across pods
instead; only activations cross the boundary).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_apply(
    stage_fn: Callable,
    stage_params,
    x_microbatches: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pod",
):
    """Run the pipeline.

    stage_fn:       (params_one_stage, activation) -> activation
    stage_params:   pytree with a leading stage dim of size S == mesh.shape[axis]
    x_microbatches: (M, mb, ...) — M microbatches
    returns         (M, mb, ...) outputs (as computed by the last stage)
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]
    assert M >= 1

    other_axes = [a for a in mesh.axis_names if a != axis]

    def shard_body(params_local, x_all):
        # params_local: leading stage dim of size 1 (this stage's slice)
        idx = jax.lax.axis_index(axis)
        params_here = jax.tree.map(lambda t: t[0], params_local)
        total = M + S - 1
        zero = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros_like(x_all)

        def step(t, carry):
            state_in, outputs = carry
            # stage 0 ingests microbatch t (clamped during drain)
            x_t = x_all[jnp.minimum(t, M - 1)]
            inp = jnp.where(idx == 0, x_t, state_in)
            out = stage_fn(params_here, inp)
            # last stage emits microbatch j = t - (S-1)
            j = t - (S - 1)
            take = jnp.logical_and(j >= 0, idx == S - 1)
            j_c = jnp.clip(j, 0, M - 1)
            upd = jnp.where(take, out, outputs[j_c])
            outputs = jax.lax.dynamic_update_index_in_dim(outputs, upd, j_c, 0)
            # hand activations to the next stage
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return (nxt, outputs)

        _, outputs = jax.lax.fori_loop(0, total, step, (zero, outputs))
        # broadcast the last stage's outputs to every stage (replicated out):
        # psum of a one-hot contribution (ppermute can't fan out 1->N)
        outputs = jax.lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        return outputs

    pspec_params = jax.tree.map(lambda _: P(axis), stage_params)
    in_specs = (pspec_params, P())
    out_specs = P()
    return jax.shard_map(
        shard_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(stage_params, x_microbatches)


def bubble_fraction(n_microbatches: int, n_stages: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
