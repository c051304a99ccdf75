"""Sharded training steps (the TPU-native equivalent of the reference's
data-parallel Trainer+KVStore pipeline, SURVEY.md §2.4).

Design: instead of per-device parameter copies + explicit allreduce
(`CommDevice::Reduce`, `src/kvstore/comm.h:482`), the WHOLE train step
(forward, backward, optimizer) is one jit program over a `Mesh`. Batch
arrays are sharded over the 'dp' axis, parameters are replicated (pure DP)
or sharded over 'tp' (tensor parallel); XLA inserts the psum/all-gathers on
ICI and overlaps them with compute — subsuming the reference's P3
priority-overlap scheme (`src/kvstore/p3store_dist.h`)."""
from __future__ import annotations

from .. import util
from ..ndarray.ndarray import NDArray

__all__ = ["DataParallel", "shard_train_step"]


def _build_pure_step(net, loss_fn, optimizer, remat_spec=None):
    """(param_vals, opt_states, t, x, y) -> (loss, new_params, new_states).

    Pure function suitable for jit: parameters are substituted into the
    gluon net during tracing (same mechanism as the CachedOp), the loss is
    differentiated with jax.grad, and the optimizer's pure `step` applies
    updates — everything fuses into one XLA program."""
    import jax

    from .. import autograd
    from ..random import trace_key_scope
    from ..utils.trace import TraceContext

    params = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    frozen = [p for p in net.collect_params().values()
              if p.grad_req == "null"]
    param_arrays = [p.data() for p in params]
    frozen_arrays = [p.data() for p in frozen]
    # Identities of the aux arrays whose functionalized updates the traced
    # step returns; populated at trace time (jit re-traces set it again).
    aux_arrays_cell: list = []
    # [tuple-of-bools] — which per-param optimizer states travel stacked
    # (one leaf instead of n_slots); set by DataParallel BEFORE the first
    # call, read at trace time.
    stacked_mask_cell: list = []

    def forward_loss(param_vals, frozen_vals, key, x, y):
        saved = [(a, a._data) for a in param_arrays + frozen_arrays]
        for a, v in zip(param_arrays, param_vals):
            a._data = v
        for a, v in zip(frozen_arrays, frozen_vals):
            a._data = v
        tc = TraceContext()
        try:
            with tc, trace_key_scope(key), autograd.pause(train_mode=True):
                out = net.forward(NDArray(x))
                loss = loss_fn(out, NDArray(y))
        finally:
            for a, v in saved:
                a._data = v
        aux_pairs = list(tc.updates.values())
        aux_arrays_cell[:] = [a for a, _ in aux_pairs]
        aux_new = tuple(nv for _, nv in aux_pairs)
        return loss.mean()._data, aux_new

    from .. import remat as _remat

    forward_loss = _remat.wrap(forward_loss, remat_spec)

    # Multi-tensor fusion for SMALL parameters (the reference's
    # aggregate_num fused updates, `src/operator/optimizer_op.cc`
    # multi-sgd/multi-adam): BERT-base has ~150 LN gammas/betas/biases of
    # a few KB each — updating them as one concatenated vector collapses
    # ~150 tiny per-param fusions into one kernel. Safe only for
    # ELEMENTWISE rules (LARS/LAMB take per-tensor norms) over plain
    # list-of-like-shaped states.
    _SMALL = 1 << 14
    # MXNET_OPTIMIZER_AGGREGATION_SIZE (env_var.md, default 4): 0/1
    # disables multi-tensor aggregation; our grouping is one concatenated
    # segment rather than count-sized batches, so >1 leaves it on
    import os as _os

    _agg = _os.environ.get("MXNET_OPTIMIZER_AGGREGATION_SIZE")
    _fusion_off = _agg is not None and _agg.isdigit() and int(_agg) <= 1

    def _fusable(i):
        if _fusion_off:
            return False
        a = param_arrays[i]
        # cheap filters FIRST: create_state allocates real device buffers
        # (Adam m/v), which must not happen for every multi-MB weight
        if not getattr(optimizer, "elementwise", False):
            return False
        if a.size > _SMALL or str(a.dtype) != "float32":
            return False
        try:
            s = optimizer.create_state(i, a)
        except Exception:
            return False
        return (isinstance(s, list)
                and all(getattr(x, "shape", None) == a._data.shape
                        for x in s))

    fused_idx = [i for i in range(len(param_arrays)) if _fusable(i)]
    if len(fused_idx) < 2:
        fused_idx = []
    fused_set = frozenset(fused_idx)
    fused_sizes = [int(param_arrays[i].size) for i in fused_idx]
    fused_shapes = [tuple(param_arrays[i].shape) for i in fused_idx]
    fused_bounds = []
    off = 0
    for n in fused_sizes[:-1]:
        off += n
        fused_bounds.append(off)

    def step(param_vals, frozen_vals, opt_states, t, lr, wd, base_key, x, y):
        import jax.numpy as jnp

        # t arrives as a device scalar and the per-step RNG key derives
        # from (base_key, t) ON DEVICE: the host never uploads a counter
        # or splits a key eagerly, so a steady-state step costs ONE
        # program launch and no host->device scalar upload
        key = jax.random.fold_in(base_key, t)
        # per-param [slot0, slot1, ...] state lists arrive STACKED as one
        # (n_slots, *shape) array per param where stacked_mask_cell says
        # so (set by DataParallel; see _stack_state): host-side dispatch
        # cost is per-LEAF, so halving the state leaves shaves ~1 ms off
        # every step on a ~260-param net. Unstack inside the program
        # (free slices) for the optimizer's list contract.
        mask = stacked_mask_cell[0] if stacked_mask_cell else ()
        opt_states = [list(s) if i < len(mask) and mask[i] else s
                      for i, s in enumerate(opt_states)]
        (loss, aux_new), grads = jax.value_and_grad(
            forward_loss, has_aux=True)(param_vals, frozen_vals, key, x, y)
        new_params = [None] * len(param_vals)
        new_states = [None] * len(param_vals)
        if fused_idx:
            w_cat = jnp.concatenate([param_vals[i].ravel()
                                     for i in fused_idx])
            g_cat = jnp.concatenate([grads[i].ravel() for i in fused_idx])
            n_slots = len(opt_states[fused_idx[0]])
            s_cat = [jnp.concatenate([opt_states[i][k].ravel()
                                      for i in fused_idx])
                     for k in range(n_slots)]
            nw_cat, ns_cat = optimizer.step(w_cat, g_cat, s_cat, lr, wd, t)
            w_parts = jnp.split(nw_cat, fused_bounds)
            s_parts = [jnp.split(ns_cat[k], fused_bounds)
                       for k in range(n_slots)]
            for j, i in enumerate(fused_idx):
                new_params[i] = w_parts[j].reshape(fused_shapes[j])
                new_states[i] = [s_parts[k][j].reshape(fused_shapes[j])
                                 for k in range(n_slots)]
        for i, (w, g, s) in enumerate(zip(param_vals, grads, opt_states)):
            if i in fused_set:
                continue
            nw, ns = optimizer.step(w, g, s, lr, wd, t)
            new_params[i] = nw
            new_states[i] = ns
        # re-stack the masked state lists so the OUTPUT side returns one
        # leaf per param too
        new_states = [_stack_state(s) if i < len(mask) and mask[i] else s
                      for i, s in enumerate(new_states)]
        return loss, new_params, new_states, aux_new, t + 1

    return (step, params, param_arrays, frozen_arrays, aux_arrays_cell,
            stacked_mask_cell)


def _observed_step_jit(fn):
    """Compile-observatory wrapper for the train-step program family: the
    warmup compile and any later recompile (shape/dtype churn in the batch,
    a static-arg change) land in the ledger with forensics."""
    from ..telemetry import compiles

    return compiles.instrument_jit(fn, "train.DataParallel.step",
                                   donate=(0, 2, 3))


def _stack_state(s):
    """Stack a per-param [slot, slot, ...] optimizer state (same-shaped
    slots, e.g. adam's m/v) into ONE (n_slots, *shape) array; anything
    else passes through untouched. Inverse: list(s) — jnp unstacking is a
    free view inside jit."""
    import jax.numpy as jnp

    if (isinstance(s, list) and len(s) >= 2
            and all(getattr(x, "shape", None) == getattr(s[0], "shape", ())
                    and getattr(x, "dtype", None) == getattr(s[0], "dtype", 0)
                    for x in s)):
        return jnp.stack(s)
    return s


class DataParallel:
    """Compiled data-parallel trainer for a gluon net.

    Usage::

        dp = DataParallel(net, loss_fn, optimizer, mesh=make_mesh({'dp': 8}))
        loss = dp.step(x_batch, y_batch)   # updates net parameters in place
    """

    def __init__(self, net, loss_fn, optimizer, mesh=None, data_axis="dp",
                 param_shardings=None, remat=None):
        import jax

        from .mesh import current_mesh

        if mesh is None:
            # honor an ambient `with mesh_scope(...)` — callers installing
            # a mesh for sharding_constraint expect the trainer to see it
            mesh = current_mesh()
        self.net = net
        self.optimizer = optimizer
        self.mesh = mesh
        self._t = 0
        # kept so rebuild() can re-run this constructor on a NEW mesh
        # after an elastic topology transition (fault/elastic.py)
        self._loss_fn = loss_fn
        self._remat = remat
        (step, params, param_arrays, frozen_arrays,
         aux_arrays_cell, stacked_mask_cell) = _build_pure_step(
            net, loss_fn, optimizer, remat_spec=remat)
        self.params = params
        self.param_arrays = param_arrays
        self.frozen_arrays = frozen_arrays
        self._aux_arrays_cell = aux_arrays_cell
        raw_states = [optimizer.create_state(i, a)
                      for i, a in enumerate(param_arrays)]
        if mesh is None:
            # single-chip: stack same-shaped state slot lists (adam m/v)
            # into one leaf each — host dispatch cost is per leaf. On a
            # mesh the per-slot arrays keep their param-matched
            # shardings, so they stay unstacked.
            # SMALL params only: re-stacking inside the step is a device
            # copy of the state bytes, so stacking a 23M-param embedding's
            # adam m/v would add ~180 MB of traffic per step — for the
            # ~185 few-KB biases/gammas the copy is noise and the leaf
            # saving is the point (measured: stacking everything made the
            # step 3.5 ms SLOWER; small-only removes ~0.6 ms of dispatch)
            stacked = [_stack_state(s) if a.size <= (1 << 14) else s
                       for s, a in zip(raw_states, param_arrays)]
            self._stacked = tuple(ns is not s
                                  for ns, s in zip(stacked, raw_states))
            self.opt_states = stacked
        else:
            self._stacked = tuple(False for _ in raw_states)
            self.opt_states = raw_states
        stacked_mask_cell[:] = [self._stacked]

        if mesh is not None:
            P = jax.sharding.PartitionSpec
            NS = jax.sharding.NamedSharding
            repl = NS(mesh, P())
            batch_sh = NS(mesh, P(data_axis))
            if param_shardings is None:
                param_sh = [repl] * len(param_arrays)
            else:
                param_sh = [NS(mesh, ps) for ps in param_shardings]
            # optimizer-state leaves matching the param shape (adam m/v,
            # momentum buffers) shard like the param; scalars replicate
            state_sh = [
                jax.tree.map(
                    lambda leaf, _sh=sh, _shape=tuple(a.shape):
                        _sh if tuple(getattr(leaf, "shape", ())) == _shape
                        else repl,
                    s)
                for s, sh, a in zip(self.opt_states, param_sh, param_arrays)
            ]
            # params/states are fed back in every step: outputs must carry
            # the SAME shardings as the declared inputs, or the second call
            # fails with a committed-sharding mismatch
            # frozen params (incl. BN aux stats) start committed to a single
            # device; replicate them onto the mesh ONCE here. Their
            # in_sharding stays None (= follow the arg) because aux updates
            # come back with compiler-chosen shardings and re-enter.
            for a in frozen_arrays:
                a._set_data(jax.device_put(a._data, repl))
            # donate params + optimizer states: they are consumed and
            # rebound every step, so XLA updates them in place instead of
            # materializing copies
            self._jit = _observed_step_jit(jax.jit(
                step,
                in_shardings=(param_sh, None, state_sh,
                              None, None, None, repl, batch_sh, batch_sh),
                out_shardings=(None, param_sh, state_sh, None, None),
                donate_argnums=(0, 2, 3)))
            self._batch_sharding = batch_sh
        else:
            self._jit = _observed_step_jit(
                jax.jit(step, donate_argnums=(0, 2, 3)))
            self._batch_sharding = None
        self._register_hbm_owners()
        # device-resident step counter + cached lr/wd uploads (see step())
        self._t_dev = None
        self._lr_dev = (None, None)
        self._wd_dev = (None, None)
        self._base_key = None
        self._key_epoch = None
        # kept for the sharding pre-flight (shardcheck_report)
        self._step_fn = step
        self._data_axis = data_axis
        self._param_specs = (list(param_shardings)
                             if param_shardings is not None else None)
        mode = (util.getenv("MXNET_SHARDCHECK") or "").strip().lower()
        if mode in ("warn", "raise") and mesh is not None:
            # pre-flight the declared layout before the first step can
            # commit it to chips; batch shapes are unknown here, so this
            # is the spec tier only (call shardcheck_report(x, y) for the
            # full simulated-mesh pass)
            self.shardcheck_report(mode=mode)

    def _register_hbm_owners(self):
        """HBM-census attribution (`telemetry.hbm`): params (incl. frozen)
        and optimizer state. Donation re-binds these arrays every step, so
        the probes read the live handles through a trainer weakref rather
        than capturing the construction-time arrays."""
        import weakref

        import jax.tree_util as jtu

        ref = weakref.ref(self)

        def _params_probe():
            tr = ref()
            if tr is None:
                return None
            return {"arrays": [a._data for a in tr.param_arrays]
                    + [a._data for a in tr.frozen_arrays]}

        def _opt_probe():
            tr = ref()
            if tr is None:
                return None
            return {"arrays": [leaf for leaf in jtu.tree_leaves(
                tr.opt_states) if hasattr(leaf, "nbytes")]}

        from ..telemetry import hbm

        hbm.register_owner("train.params", _params_probe)
        hbm.register_owner("train.optimizer", _opt_probe)

    def shardcheck_report(self, x=None, y=None, hbm_budget_gb=None,
                          mode=None, compile=True):
        """Static sharding pre-flight over this trainer's step program
        (`mx.analysis.shardcheck`). With a sample batch ``(x, y)`` the
        step is abstract-traced and — given a real mesh — compiled under
        the declared shardings for the collective-cost audit; without one
        only the param/optimizer-state layout is checked."""
        import contextlib

        import jax

        from ..analysis.shardcheck import shardcheck
        from .mesh import mesh_scope

        P = jax.sharding.PartitionSpec
        param_vals = [a._data for a in self.param_arrays]
        frozen_vals = [a._data for a in self.frozen_arrays]
        p_specs = (self._param_specs if self._param_specs is not None
                   else [None] * len(param_vals))
        # state leaves shaped like their param shard like the param;
        # everything else (scalars, counters) is unconstrained
        s_specs = [
            jax.tree.map(
                lambda leaf, _sp=sp, _shape=tuple(a.shape):
                    (_sp if tuple(getattr(leaf, "shape", ())) == _shape
                     else None), s)
            for s, sp, a in zip(self.opt_states, p_specs, self.param_arrays)
        ]
        mesh_kw = dict(mesh=self.mesh, hbm_budget_gb=hbm_budget_gb,
                       mode=mode, compile=compile,
                       name="DataParallel.step")
        if x is None or y is None:
            return shardcheck(None, param_vals, frozen_vals,
                              self.opt_states,
                              specs=(p_specs, None, s_specs), **mesh_kw)

        from ..random import next_key

        xv = x._data if isinstance(x, NDArray) else x
        yv = y._data if isinstance(y, NDArray) else y
        batch_spec = P(self._data_axis) if self.mesh is not None else None
        scalar = jax.ShapeDtypeStruct((), "int32")
        fscalar = jax.ShapeDtypeStruct((), "float32")
        step = self._step_fn

        def fn(*args):
            with (mesh_scope(self.mesh) if self.mesh is not None
                  else contextlib.nullcontext()):
                return step(*args)

        fn.__name__ = "DataParallel.step"
        return shardcheck(
            fn, param_vals, frozen_vals, self.opt_states, scalar, fscalar,
            fscalar, next_key(), xv, yv,
            specs=(p_specs, None, s_specs, None, None, None, P(),
                   batch_spec, batch_spec),
            out_specs=(None, p_specs, s_specs, None, None),
            donate_argnums=(0, 2, 3), **mesh_kw)

    def _dev_scalar(self, value, cache_name, dtype):
        """Upload a python scalar only when it CHANGED since the last step —
        steady-state training pays zero host->device transfers for lr/wd."""
        import jax.numpy as jnp

        cached_val, cached_buf = getattr(self, cache_name)
        if cached_buf is None or cached_val != value:
            cached_buf = jnp.asarray(value, dtype)
            setattr(self, cache_name, (value, cached_buf))
        return cached_buf

    def step(self, x, y):
        import jax.numpy as jnp

        from ..random import next_key

        self._t += 1
        # Mirror Trainer semantics: lr/wd are re-evaluated every update (the
        # scheduler sees the bumped num_update) and enter the compiled step
        # as traced scalars, so set_learning_rate/lr_scheduler take effect
        # without retracing.
        self.optimizer.num_update += 1
        lr = float(self.optimizer.learning_rate)
        wd = float(self.optimizer.wd)
        xv = x._data if isinstance(x, NDArray) else x
        yv = y._data if isinstance(y, NDArray) else y
        param_vals = [a._data for a in self.param_arrays]
        frozen_vals = [a._data for a in self.frozen_arrays]
        if self._t_dev is None:
            self._t_dev = jnp.asarray(self._t, jnp.int32)
        from ..random import seed_epoch

        if self._base_key is None or self._key_epoch != seed_epoch():
            # refresh after mx.random.seed() so reseeding mid-training
            # changes the dropout streams (reference semantics)
            self._base_key = next_key()
            self._key_epoch = seed_epoch()
        lr_dev = self._dev_scalar(lr, "_lr_dev", jnp.float32)
        wd_dev = self._dev_scalar(wd, "_wd_dev", jnp.float32)
        # the mesh is active during tracing so npx.sharding_constraint
        # (sequence/tensor-parallel activation hints) can resolve axes
        import contextlib

        from .mesh import mesh_scope

        from ..telemetry import tracing

        # the launch of the step program as a span in a live profiler
        # session's trace (a no-op otherwise); no read-back inside
        with (mesh_scope(self.mesh) if self.mesh is not None
              else contextlib.nullcontext()), tracing.phase("mx.train.step"):
            loss, new_params, new_states, aux_new, self._t_dev = self._jit(
                param_vals, frozen_vals, self.opt_states, self._t_dev,
                lr_dev, wd_dev, self._base_key, xv, yv)
        for a, nv in zip(self.param_arrays, new_params):
            a._set_data(nv)
        for a, nv in zip(self._aux_arrays_cell, aux_new):
            a._set_data(nv)
        self.opt_states = new_states
        return NDArray(loss)

    def rebuild(self, mesh, data_axis=None, param_shardings=None):
        """Re-construct the compiled step on a NEW mesh, carrying
        parameters, optimizer state (momenta), and the step counter
        across — the trainer half of an elastic topology transition
        (`fault.elastic.ElasticController`). Values round-trip through
        HOST memory: after a real shrink the departed ranks' devices are
        gone, so a device-to-device reshard has nothing to read from.
        The optimizer state tree is value-restored after the constructor
        re-creates it (a bare ``create_state`` would silently zero adam
        momenta and dent the loss trajectory)."""
        import jax
        import numpy as onp

        from ..telemetry import tracing

        if mesh is None:
            raise ValueError("DataParallel.rebuild requires a target mesh")
        t = self._t
        specs = (list(param_shardings) if param_shardings is not None
                 else self._param_specs)
        with tracing.span("elastic.rebuild",
                          devices=int(mesh.devices.size)):
            old_states = jax.tree.map(
                lambda leaf: (onp.asarray(leaf)
                              if hasattr(leaf, "shape") else leaf),
                self.opt_states)
            # re-commit trainable params onto the new mesh under their
            # declared specs BEFORE the constructor re-collects them —
            # arrays committed to the old mesh would fail the new jit's
            # in_shardings
            P = jax.sharding.PartitionSpec
            NS = jax.sharding.NamedSharding
            for i, a in enumerate(self.param_arrays):
                spec = specs[i] if specs is not None else None
                sh = NS(mesh, spec if spec is not None else P())
                a._set_data(jax.device_put(onp.asarray(a._data), sh))
            for a in self.frozen_arrays:
                a._set_data(jax.device_put(onp.asarray(a._data),
                                           NS(mesh, P())))
            self.__init__(self.net, self._loss_fn, self.optimizer,
                          mesh=mesh,
                          data_axis=data_axis or self._data_axis,
                          param_shardings=specs, remat=self._remat)
            if (jax.tree.structure(old_states)
                    == jax.tree.structure(self.opt_states)):
                self.opt_states = jax.tree.map(
                    lambda old, new: (jax.device_put(old, new.sharding)
                                      if hasattr(new, "sharding")
                                      else old),
                    old_states, self.opt_states)
            else:
                import logging

                logging.getLogger("incubator_mxnet_tpu.parallel").warning(
                    "DataParallel.rebuild: optimizer-state layout changed "
                    "across the mesh transition — state re-initialized")
        self._t = t
        self._t_dev = None          # re-upload on the next step
        return self


def shard_train_step(step_fn, mesh, in_specs, out_specs):
    """shard_map a raw per-device step over the mesh (for SPMD code that
    calls collectives explicitly — ring attention, expert parallel, etc.)."""
    import jax

    P = jax.sharding.PartitionSpec
    in_specs = tuple(s if isinstance(s, P) else P(*s) if s else P()
                     for s in in_specs)
    out_specs = (out_specs if isinstance(out_specs, P)
                 else P(*out_specs) if out_specs else P())
    from ..telemetry import compiles

    return compiles.ledgered_jit(
        jax.shard_map(step_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs),
        family="train.shard_map_step")
