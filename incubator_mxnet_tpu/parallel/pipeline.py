"""Pipeline parallelism (GPipe schedule) over a `pp` mesh axis.

Reference role: MXNet's model-parallel story is manual device placement
(`example/model-parallel/`, ctx lists per layer) with the engine's
dependency graph overlapping the stages. The TPU-native design is an SPMD
pipeline: stage parameters are SHARDED over the `pp` axis (each device
holds one stage), microbatches circulate stage-to-stage over ICI with
`lax.ppermute`, and the whole schedule is ONE `lax.scan` inside
`shard_map` — XLA overlaps the permute collectives with stage compute,
the same overlap the reference gets from its threaded engine.

Schedule: classic GPipe fill-drain. For S stages and M microbatches the
scan runs S+M-1 ticks; tick t has stage s working on microbatch t-s
(bubble fraction (S-1)/(S+M-1)).

The per-stage function must be shape-preserving ((microbatch, ...) ->
(microbatch, ...)), the natural shape for stacked transformer blocks —
scan-over-layers composes: `stage_fn` itself may be a `lax.scan` over the
layers within the stage.
"""
from __future__ import annotations

__all__ = ["PipelineParallel", "pipeline_apply", "pipeline_stage_params"]


def pipeline_stage_params(params_per_layer, n_stages):
    """Stack per-layer param pytrees into per-stage stacks: layers are
    split contiguously into `n_stages` groups of L/S layers; leaf arrays
    gain a leading (S, L/S) pair of axes, ready to shard axis 0 over pp."""
    import jax
    import jax.numpy as jnp

    n_layers = len(params_per_layer)
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} layers not divisible into "
                         f"{n_stages} stages")
    per = n_layers // n_stages
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params_per_layer)
    return jax.tree.map(
        lambda x: x.reshape((n_stages, per) + x.shape[1:]), stacked)


def pipeline_apply(stage_fn, stage_params, x, axis_name="pp"):
    """Run the GPipe schedule inside shard_map over `axis_name`.

    - `stage_fn(params, act) -> act`: one stage's forward on ONE
      microbatch (already holding only this device's stage params).
    - `stage_params`: this device's slice (leading stage axis removed by
      shard_map's in_spec).
    - `x`: (n_micro, micro_batch, ...) — the full minibatch split into
      microbatches, replicated across pp (each stage reads only the
      microbatch it needs at fill time; XLA DCEs the rest).
    Returns (n_micro, micro_batch, ...) outputs (valid on the LAST stage;
    callers all-gather or read from stage S-1).
    """
    import jax.numpy as jnp
    from jax import lax

    from . import collectives

    n_stages = collectives.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    n_micro = x.shape[0]
    ticks = n_stages + n_micro - 1

    def tick(carry, t):
        recv, outputs = carry
        # stage 0 injects microbatch t (while it exists); later stages
        # consume what the previous stage sent last tick
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        injected = lax.dynamic_index_in_dim(x, mb_idx, 0, keepdims=False)
        act_in = jnp.where(stage == 0, injected, recv)
        act_out = stage_fn(stage_params, act_in)
        # last stage banks its result for microbatch t-(S-1)
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        take = jnp.logical_and(stage == n_stages - 1,
                               t >= n_stages - 1)
        current = lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                           keepdims=False)
        banked = jnp.where(take, act_out, current)
        outputs = lax.dynamic_update_index_in_dim(outputs, banked,
                                                  out_idx, 0)
        sent = collectives.ring_permute(act_out, axis_name)
        return (sent, outputs), None

    # the carry becomes device-varying (ppermute/axis_index inside the
    # body); under shard_map's varying-manual-axes typing the INITIAL
    # carry must be marked varying too
    zero = collectives.pvary(jnp.zeros_like(x[0]), axis_name)
    outputs0 = collectives.pvary(jnp.zeros_like(x), axis_name)
    (_, outputs), _ = lax.scan(tick, (zero, outputs0),
                               jnp.arange(ticks))
    return outputs


class PipelineParallel:
    """GPipe TRAINER over a `pp` mesh axis — fwd + bwd + optimizer step
    through the pipeline schedule, compiled as one XLA program.

    The backward pass is `jax.grad` straight through `pipeline_apply`:
    the scan differentiates into the reversed drain schedule and every
    `ppermute` transposes into the inverse ring hop, so stage cotangents
    flow last-stage -> first-stage exactly like a hand-written GPipe
    backward; microbatch gradient ACCUMULATION falls out of the scan's
    vjp summing over ticks. (Reference role: MXNet model-parallel
    training via per-layer ctx placement + the engine's dependency
    overlap, `example/model-parallel/`.)

    Usage::

        stage_params = pipeline_stage_params(layer_params, n_stages)
        pp = PipelineParallel(stage_fn, stage_params, loss_fn,
                              optimizer.SGD(learning_rate=0.1), mesh)
        loss = pp.step(x_micro, y)    # x_micro: (n_micro, micro_b, ...)

    `stage_fn(params, act) -> act` applies ONE stage (its stacked layers)
    to one microbatch. `loss_fn(outs, y)` maps the (n_micro, ...) pipeline
    outputs to a scalar.
    """

    def __init__(self, stage_fn, stage_params, loss_fn, optimizer,
                 mesh, axis_name="pp"):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from . import collectives
        from ..ndarray.ndarray import NDArray

        self.mesh = mesh
        self.axis_name = axis_name
        self.optimizer = optimizer
        n_stages = mesh.shape[axis_name]
        self._t = 0

        # per-leaf optimizer states, stacked over the stage axis like the
        # params (each device updates its own stage's slice)
        leaves = jax.tree.leaves(stage_params)
        states = [optimizer.create_state(i, NDArray(a))
                  for i, a in enumerate(leaves)]
        self._state_treedef = jax.tree.structure(stage_params)
        self.params = jax.device_put(
            stage_params, NamedSharding(mesh, P(axis_name)))
        self.opt_states = jax.device_put(
            states, NamedSharding(mesh, P(axis_name)))

        def device_fn(params, opt_states, x, y, t):
            def loss_of(p):
                # shard_map's P(pp) slice keeps a leading stage axis of
                # size 1 — stage_fn sees the bare per-stage params
                p_local = jax.tree.map(lambda a: a[0], p)
                outs = pipeline_apply(stage_fn, p_local, x, axis_name)
                stage_loss = loss_fn(outs, y)
                last = lax.axis_index(axis_name) == n_stages - 1
                # only the LAST stage banked real outputs; keep the
                # scalar per-device here — this build's shard_map psum
                # transpose over-counts the cotangent by the axis size,
                # so the global reduce happens OUTSIDE value_and_grad
                # (ppermute transposes already route stage cotangents)
                return jnp.where(last, stage_loss, 0.0)

            loss, grads = jax.value_and_grad(loss_of)(params)
            loss = collectives.all_reduce(loss, axis_name)
            p_leaves = jax.tree.leaves(params)
            g_leaves = jax.tree.leaves(grads)
            new_p, new_s = [], []
            for i, (w, g) in enumerate(zip(p_leaves, g_leaves)):
                w2, s2 = optimizer.step(w, g, opt_states[i],
                                        optimizer.learning_rate,
                                        optimizer.wd, t)
                new_p.append(w2)
                new_s.append(s2)
            return (loss,
                    jax.tree.unflatten(self._state_treedef, new_p),
                    new_s)

        psp = P(axis_name)
        from ..telemetry.compiles import ledgered_jit

        self._jit = ledgered_jit(jax.shard_map(
            device_fn, mesh=mesh,
            in_specs=(psp, psp, P(), P(), P()),
            out_specs=(P(), psp, psp)), family="train.pipeline.step")

    def step(self, x, y):
        """One GPipe train step; returns the scalar loss (NDArray)."""
        import jax.numpy as jnp

        from ..ndarray.ndarray import NDArray

        x = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        y = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        self._t += 1
        loss, self.params, self.opt_states = self._jit(
            self.params, self.opt_states, x, y, jnp.float32(self._t))
        return NDArray(loss)
