"""GPipe-style pipeline parallelism over a mesh axis.

Stages live one-per-device-group along the ``pipe`` axis; microbatches stream
through the ring: at step ``t`` stage ``s`` computes microbatch ``t - s`` and
``ppermute``s its activation to stage ``s+1`` (XLA lowers the neighbor send to
ICI). The classic pipeline bubble costs ``S-1`` of ``M+S-1`` steps, so
efficiency is ``M/(M+S-1)`` — pick ``n_microbatches >> n_stages``.

The whole schedule is a differentiable ``lax.scan`` (masked selects instead of
data-dependent control flow), so ``jax.grad`` through a pipelined forward
produces the reverse schedule automatically — XLA sees one fused program.

Use under ``jax.shard_map`` with stage-stacked params sharded
``P('pipe', ...)``; :func:`make_pipeline_fn` wraps that plumbing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def pipeline_apply(stage_fn, stage_params, microbatches, axis_name: str):
    """Run ``microbatches`` through the pipeline inside a shard_map context.

    :param stage_fn: ``(params_for_one_stage, x) -> y`` with ``y.shape ==
        x.shape`` (inter-stage activations must be shape-stable).
    :param stage_params: this stage's params (leading stage axis already
        squeezed away by the shard_map in_spec).
    :param microbatches: ``(n_micro, mb, ...)`` array, identical on every stage
        (replicated in_spec); only stage 0 actually consumes it.
    :returns: ``(n_micro, mb, ...)`` outputs, identical on every stage.
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def step(carry, t):
        incoming, outputs = carry
        # stage 0 injects microbatch t; others consume the ring activation
        x0 = microbatches[jnp.clip(t, 0, n_micro - 1)]
        x_in = jnp.where(stage == 0, x0, incoming)
        y = stage_fn(stage_params, x_in)
        # bubble steps compute garbage; mask them out of the output buffer
        out_idx = t - (n_stages - 1)
        is_last = stage == n_stages - 1
        valid = is_last & (out_idx >= 0) & (out_idx < n_micro)
        idx = jnp.clip(out_idx, 0, n_micro - 1)
        outputs = outputs.at[idx].set(
            jnp.where(valid, y, outputs[idx]))
        # hand the activation to the next stage (wrap-around send from the
        # last stage is ignored by stage 0's inject select)
        incoming = jax.lax.ppermute(y, axis_name, perm)
        return (incoming, outputs), None

    init_in = jnp.zeros_like(microbatches[0])
    outputs0 = jnp.zeros_like(microbatches)
    (final_in, outputs), _ = jax.lax.scan(
        step, (init_in, outputs0), jnp.arange(n_micro + n_stages - 1))
    # outputs are populated only on the last stage; share them with every
    # stage so the loss is computable anywhere (single cheap collective)
    outputs = jax.lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)
    return outputs


def make_pipeline_fn(stage_fn, mesh, pipe_axis: str = 'pipe',
                     batch_axis: str = None):
    """Wrap :func:`pipeline_apply` in shard_map over ``mesh``.

    Returns ``fn(stacked_params, microbatches) -> outputs`` where
    ``stacked_params`` has a leading ``n_stages`` axis on every leaf (sharded
    over ``pipe_axis``) and ``microbatches`` is ``(n_micro, mb, ...)``.
    With ``batch_axis``, the per-microbatch dim is additionally sharded over
    that axis — pipeline (pp) composed with data parallelism (dp).
    """
    from jax.sharding import PartitionSpec as P

    mb_spec = P(None, batch_axis) if batch_axis else P()

    def fn(stacked_params, microbatches):
        pspecs = jax.tree_util.tree_map(lambda _: P(pipe_axis), stacked_params)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(pspecs, mb_spec), out_specs=mb_spec)
        def run(stacked, mb):
            # squeeze this stage's slot of the stacked params
            my_params = jax.tree_util.tree_map(lambda a: a[0], stacked)
            mb = jax.lax.pcast(mb, (pipe_axis,), to='varying')
            return pipeline_apply(stage_fn, my_params, mb, pipe_axis)

        return run(stacked_params, microbatches)

    return fn
