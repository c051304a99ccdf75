"""Pod-scale sharded serving: mesh-placed `SlotDecoder` replicas.

The single-chip serving engine (`serve.engine.SlotDecoder`) compiles two
program families (chunked prefill + decode) over per-layer paged KV
pools. This module scales one replica *within* a host by tensor
parallelism: a :class:`ServeLayout` of partition rules places every
param and pool leaf onto a device mesh, and :class:`ShardedSlotDecoder`
threads those placements through the inherited program families via the
seams the base engine exposes (`_refresh_params`, `_place_pools`,
`_constrain_pools`, `_pin_tokens`, `_shardcheck_specs`) — the programs
themselves are
untouched, so every single-chip invariant survives sharding:

- exactly two compiled program families per replica (prefill growth by
  chunk bucket only), gated by the compile ledger;
- all ``2L`` per-layer pool leaves donated AND aliased — the output
  pools are pinned to their input shardings with
  ``with_sharding_constraint`` so XLA's donation map still holds;
- prefix cache + int8 KV are orthogonal (host-side token matching and
  in-program quantization never see the mesh);
- on a 1-device mesh the placements are no-ops and greedy output is
  bit-identical to the unsharded engine.

Layout (the `ServeLayout` defaults, after SNIPPETS.md [2] fmengine
``match_partition_rules`` and [3] fsdp×tp ``SpecLayout``):

- every leaf of the pools pytree (`serve/pages.py`: pages ``(n_pages, H,
  ...)``, int8 scale planes ``(n_pages, H)``) → ``P(None, tp)``:
  heads-sharded, so each device holds its heads' pages for the WHOLE
  pool — per-device KV HBM drops by the TP degree;
- matmuls Megatron-style with one deliberate twist: ffn1 is
  column-parallel / ffn2 row-parallel (the classic pair, one
  all-reduce), but the FUSED qkv matmul runs row-parallel rather than
  column-parallel — its output axis is ``[q|k|v]``-contiguous and the
  gluon ``(3, H, d)`` split can never align with a contiguous tp
  sharding of ``3C``, so sharding it would buy an all-gather on the
  decode hot path (shardcheck SC005 catches exactly this). Row-parallel
  qkv keeps q/k/v replicated (tiny at decode shapes) while the heavy
  state — weights and KV pools — stays fully sharded; proj is
  row-parallel over the head-sharded attention context. The ``fsdp``
  axis rides the complementary dim for pod layouts;
- embeddings / positional tables / norms / page tables replicated —
  explicitly (``P()``), so shardcheck's SC001 "silently replicated
  ≥1 MiB leaf" rule stays meaningful for everything else (a layer's
  leaves are stored one dict a layer, each matrix ``(in, out)``:
  `models.decoding.GPTDecoder`).

Every leaf MUST match a rule: an unmatched leaf raises instead of
falling back to replication (lint FL017 enforces the same discipline
statically — serve/ code may not hand bare ``PartitionSpec`` /
``NamedSharding`` literals to placement calls; specs flow from layout
rules).

Scaling *across* hosts is replication: `serve.router.ReplicaRouter`
plus the gateway's ``replicas=N`` front N independent engines (each its
own mesh slice, prefix cache, and page pool) behind least-loaded +
prefix-affinity dispatch. See SERVING.md §"Pod-scale sharded serving".
"""
from __future__ import annotations

import os
import re

from ..parallel.mesh import make_mesh
from .engine import SlotDecoder

__all__ = ["ServeLayout", "ShardedSlotDecoder", "parse_mesh_spec",
           "serve_mesh"]


def _j():
    import jax

    return jax


def parse_mesh_spec(spec):
    """Parse a mesh spec into ``{"axis": size}``.

    Accepts a dict (returned as-is), an int / numeric string ``"4"``
    (tensor-parallel degree), or ``"tp=4"`` / ``"fsdp=2,tp=4"`` — the
    grammar of the ``MXNET_SERVE_MESH`` env knob."""
    if isinstance(spec, dict):
        return dict(spec)
    if isinstance(spec, int):
        return {"tp": int(spec)}
    s = str(spec).strip()
    if not s:
        return {"tp": 1}
    if s.isdigit():
        return {"tp": int(s)}
    axes = {}
    for part in s.split(","):
        if "=" not in part:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected 'axis=size[,..]' "
                f"(e.g. 'tp=4' or 'fsdp=2,tp=4')")
        k, v = part.split("=", 1)
        axes[k.strip()] = int(v)
    return axes


def serve_mesh(spec=None, devices=None):
    """Build a serving mesh from `spec` (default: the
    ``MXNET_SERVE_MESH`` env knob, else ``tp=1``). Unlike
    `parallel.make_mesh` alone, this takes the FIRST ``prod(sizes)``
    devices instead of requiring the spec to cover every device — a
    replica's mesh is a slice of the host, not the host."""
    if spec is None:
        spec = os.environ.get("MXNET_SERVE_MESH", "") or {"tp": 1}
    axes = parse_mesh_spec(spec)
    need = 1
    for v in axes.values():
        need *= int(v)
    if devices is None:
        devices = _j().devices()
    if len(devices) < need:
        raise ValueError(
            f"serve_mesh: spec {axes} needs {need} devices, have "
            f"{len(devices)}")
    return make_mesh(axes, devices=list(devices)[:need])


def _path_str(path):
    """'layers/0/qkv_w'-style rule key for one pytree leaf path."""
    parts = []
    for p in path:
        for attr in ("key", "name", "idx"):
            v = getattr(p, attr, None)
            if v is not None:
                parts.append(str(v))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


class ServeLayout:
    """Partition rules mapping every serving param/pool leaf to a
    `PartitionSpec` on `mesh`.

    ``rules`` is an ordered ``(regex, spec)`` sequence matched (first
    hit wins, `re.search`) against the '/'-joined pytree path of each
    param leaf — the fmengine ``match_partition_rules`` idiom. A leaf no
    rule matches raises `ValueError`: silent replication of an unplaced
    leaf is exactly the failure mode shardcheck SC001 exists to catch,
    so the layout refuses to manufacture it."""

    def __init__(self, mesh, rules=None, tp_axis="tp", fsdp_axis="fsdp"):
        self.mesh = mesh
        self.tp_axis = tp_axis
        axes = set(dict(mesh.shape))
        if tp_axis not in axes:
            raise ValueError(
                f"ServeLayout: mesh axes {sorted(axes)} lack "
                f"{tp_axis!r} (build the mesh with serve_mesh)")
        # pure-tp serving meshes (the replica_meshes default) simply
        # leave the fsdp dim unsharded
        self.fsdp_axis = fsdp_axis if fsdp_axis in axes else None
        self.rules = tuple(rules) if rules is not None \
            else self._default_rules()
        self._compiled = tuple((re.compile(rx), spec)
                               for rx, spec in self.rules)

    # -- rule table ---------------------------------------------------------

    def _default_rules(self):
        P = _j().sharding.PartitionSpec
        tp, fs = self.tp_axis, self.fsdp_axis
        # Weights are stored a layer at a time, (in, out), and applied as
        # ``y = x @ w`` (`models.decoding._dense`), so "row-parallel" = tp
        # on the FIRST dim (input features) and "column-parallel" = tp on
        # the last dim (output features).
        return (
            # attention: the fused qkv output axis is [q|k|v]-contiguous
            # and `_split_qkv` reshapes it to (3, H, d) — a contiguous
            # tp-sharding of 3C can never align with heads, so qkv runs
            # ROW-parallel (contract over tp-sharded input features,
            # one all-reduce, replicated q/k/v — tiny at decode shapes)
            # and its bias stays replicated with the output. proj is
            # row-parallel too: its input is the attention context,
            # which lands head-sharded (= feature-sharded once
            # flattened) straight out of the H-sharded KV pools.
            (r"^layers/\d+/qkv_w$", P(tp, fs)),
            (r"^layers/\d+/qkv_b$", P()),
            (r"^layers/\d+/proj_w$", P(tp, fs)),
            (r"^layers/\d+/proj_b$", P()),
            # MLP: the classic Megatron pair — ffn1 column-parallel
            # (output features on tp, bias sharded along), gelu local,
            # ffn2 row-parallel (all-reduce back to replicated)
            (r"^layers/\d+/ffn1_w$", P(fs, tp)),
            (r"^layers/\d+/ffn1_b$", P(tp)),
            (r"^layers/\d+/ffn2_w$", P(tp, fs)),
            (r"^layers/\d+/ffn2_b$", P()),
            # small per-layer norm vectors: replicated, explicitly
            (r"^layers/\d+/ln[0-9]+_[gb]$", P()),
            # embeddings / positional / final norm / untied head:
            # replicated (page tables ride along as plain host arrays)
            (r"^embed$", P()),
            (r"^pos$", P()),
            (r"^lnf_[gb]$", P()),
            (r"^head$", P()),
        )

    def pool_spec(self):
        """Every leaf of the pools pytree (`serve/pages.py`), pages
        ``(n_pages, H, ...)`` and int8 scale planes ``(n_pages, H)``
        alike: heads on the TP axis."""
        P = _j().sharding.PartitionSpec
        return P(None, self.tp_axis)

    # -- matching -----------------------------------------------------------

    def spec_for(self, path):
        for rx, spec in self._compiled:
            if rx.search(path):
                return spec
        raise ValueError(
            f"ServeLayout: no partition rule matches param leaf "
            f"{path!r} — add an explicit rule (silent replicated "
            f"fallback is not allowed; see SERVING.md pod-scale notes)")

    def param_specs(self, params):
        """Spec pytree mirroring `params`; raises on any unmatched
        leaf."""
        jax = _j()
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        return jax.tree_util.tree_unflatten(
            treedef, [self.spec_for(_path_str(p)) for p, _ in flat])

    def sharding(self, spec):
        """`NamedSharding` for `spec` with trailing None dims stripped.

        The strip is load-bearing, not cosmetic: GSPMD normalizes specs
        the same way on program OUTPUTS, and the jit cache compares
        NamedShardings by spec. Placing the pools with the unnormalized
        ``P(None, tp, None, None)`` would make the first program — the
        only one ever traced against freshly `device_put` pools — carry
        a different input sharding than every later call on
        program-output pools (``P(None, tp)``), costing one spurious
        recompile per engine. The steady-state gates in
        tests/test_sharded_serve.py and bench_gpt_serve_sharded hold
        only because placement and program outputs agree exactly."""
        jax = _j()
        entries = tuple(spec)
        while entries and entries[-1] is None:
            entries = entries[:-1]
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(*entries))

    # -- placement ----------------------------------------------------------

    def place_params(self, params):
        """device_put every param leaf per its matched rule (committed
        shardings — the compiled programs then see stable layouts)."""
        jax = _j()
        specs = self.param_specs(params)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, self.sharding(s)),
            params, specs)

    def place_pools(self, pools):
        """device_put every leaf of the pools pytree."""
        jax = _j()
        ps = self.sharding(self.pool_spec())
        return jax.tree.map(lambda x: jax.device_put(x, ps), pools)

    def constrain_pools(self, pools):
        """Inside a traced program: pin updated pool leaves back to the
        input placement so donation aliasing survives compilation."""
        jax = _j()
        ps = self.sharding(self.pool_spec())
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, ps), pools)

    def describe(self):
        """Human-readable rule table (docs/tests)."""
        return [(rx, str(spec)) for rx, spec in self.rules]


class ShardedSlotDecoder(SlotDecoder):
    """A `SlotDecoder` whose params and KV pools live on a device mesh.

    Same constructor as the base engine plus ``mesh=`` (a
    `jax.sharding.Mesh`, a mesh spec for :func:`serve_mesh`, or None to
    read ``MXNET_SERVE_MESH``) and ``layout=`` (a prebuilt
    :class:`ServeLayout`; overrides ``mesh``). All four inherited
    program families compile against the mesh; the engine's host API
    (scheduler, gateway, prefix cache) is unchanged."""

    def __init__(self, source, mesh=None, layout=None, hbm_budget_gb=None,
                 **engine_kwargs):
        if getattr(source, "family", None) == "pangu_moe":
            raise NotImplementedError(
                "the pangu_moe family is not served sharded: `ServeLayout` "
                "has no rule for a latent pool shared by all heads nor for "
                "experts held a share a chip, and the exchange between the "
                "shares is not written (ROADMAP R1)")
        if getattr(source, "family", None) == "nemotron_h":
            raise NotImplementedError(
                "the nemotron_h family is not served sharded: `ServeLayout` "
                "has no rule for state leaves indexed by slot nor for "
                "experts held a share a chip, and the exchange between the "
                "shares is not written (ROADMAP R1, R4)")
        if layout is None:
            if not hasattr(mesh, "shape") or not hasattr(mesh, "devices"):
                mesh = serve_mesh(mesh)
            layout = ServeLayout(mesh)
        self.layout = layout
        self.hbm_budget_gb = hbm_budget_gb
        self._placed_ids = None
        super().__init__(source, **engine_kwargs)
        self._check_divisibility()
        self._place_params()

    # -- mesh plumbing ------------------------------------------------------

    def _check_divisibility(self):
        mesh_shape = dict(self.layout.mesh.shape)
        tp = int(mesh_shape.get(self.layout.tp_axis, 1))
        H = self._dec._n_heads
        if H % tp:
            raise ValueError(
                f"ShardedSlotDecoder: n_heads={H} not divisible by "
                f"tp={tp} — the K/V pools shard on the head axis")
        layer = self._dec._params["layers"][0]
        # row-parallel matmuls shard input features (first dim of the
        # (in, out) weight); column-parallel ffn1 shards its output
        for name, dim in (("qkv_w", 0), ("proj_w", 0),
                          ("ffn1_w", 1), ("ffn2_w", 0)):
            size = int(layer[name].shape[dim])
            if size % tp:
                raise ValueError(
                    f"ShardedSlotDecoder: {name} sharded dim {size} "
                    f"not divisible by tp={tp}")

    def _place_params(self):
        """(Re-)place decoder params onto the mesh iff the source
        block's weights changed since the last placement — the
        hot-swap path: `GPTDecoder._auto_refresh` makes the per-layer
        leaves anew (while the old ones live), then this pins them to
        the layout. Replacing
        ``dec._params`` does not touch the model's own buffers, so the
        id fingerprint stays stable until the next real swap."""
        dec = self._dec
        dec._auto_refresh()
        if dec._param_ids == self._placed_ids:
            return False
        dec._params = self.layout.place_params(dec._params)
        self._placed_ids = dec._param_ids
        return True

    # -- seams the base engine routes through -------------------------------

    def _refresh_params(self):
        self._place_params()

    def _mesh_scope(self):
        """Kernel sites see the engine's mesh: under more than one device
        they take their XLA expression (GSPMD cannot partition a Mosaic
        kernel), on a one-device mesh the kernel, like the base engine."""
        from ..parallel.mesh import mesh_scope

        return mesh_scope(self.layout.mesh)

    def _place_pools(self, pools):
        """Fresh pools, and a disagg page-migration's eager scatters
        (whose outputs carry whatever sharding the eager op picked): pin
        them to the pool layout, or the next donated program would see
        mismatched input placements (the same trap `ServeLayout.sharding`
        closes)."""
        return self.layout.place_pools(pools)

    def _constrain_pools(self, pools):
        return self.layout.constrain_pools(pools)

    def _pin_tokens(self, tokens):
        jax = _j()
        return jax.lax.with_sharding_constraint(
            tokens, self.layout.sharding(jax.sharding.PartitionSpec()))

    def _shardcheck_specs(self):
        """Explicit spec entries for ``(params, pools)`` so the
        shardcheck pre-flight judges the REAL layout (SC001 silent
        replication, SC006 per-device HBM) instead of assuming
        single-chip, and for the builders' ``(pools, tok)`` outputs —
        without them the donation audit (SC004) would compare the pinned
        input pools against unconstrained outputs and cry wolf."""
        pool = self.layout.pool_spec()
        return ((self.layout.param_specs(self._dec._params), pool),
                (pool, None))

    def shardcheck_report(self, mesh=None, hbm_budget_gb=None, bucket=None):
        if mesh is None:
            mesh = self.layout.mesh
        if hbm_budget_gb is None:
            hbm_budget_gb = self.hbm_budget_gb
        return super().shardcheck_report(
            mesh=mesh, hbm_budget_gb=hbm_budget_gb, bucket=bucket)
