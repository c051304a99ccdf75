"""Tools tests: parse_log, bandwidth measure (reference model: the tools/
utilities shipped alongside the framework)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_log(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import parse_log
    finally:
        sys.path.pop(0)
    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] Train-accuracy=0.91\n"
        "INFO Epoch[0] Validation-accuracy=0.88\n"
        "INFO Epoch[0] Time cost=12.3\n"
        "INFO Epoch[1] Train-accuracy=0.95\n")
    data = parse_log.parse(log.read_text().splitlines(), ["accuracy"])
    assert data[0]["train-accuracy"] == 0.91
    assert data[0]["val-accuracy"] == 0.88
    assert data[0]["time"] == 12.3
    assert data[1]["train-accuracy"] == 0.95
    md = parse_log.to_markdown(data, ["accuracy"])
    assert "| epoch |" in md and "0.91" in md


def test_parse_log_metric_name_boundary(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import parse_log
    finally:
        sys.path.pop(0)
    data = parse_log.parse(["Epoch[0] Train-accuracy=0.70",
                            "Epoch[0] Train-accuracy-top5=0.95"],
                           ["accuracy"])
    assert data[0]["train-accuracy"] == 0.70


def test_parse_log_estimator_format(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import parse_log
    finally:
        sys.path.pop(0)
    # one LoggingHandler epoch_end line carries time + train + validation
    lines = ["[Epoch 2] Finished in 3.211s, train accuracy: 0.7712, "
             "validation accuracy: 0.7001"]
    data = parse_log.parse(lines, ["accuracy"])
    assert data[2]["train-accuracy"] == 0.7712
    assert data[2]["val-accuracy"] == 0.7001
    assert data[2]["time"] == 3.211


def test_parse_log_escapes_metric_names():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import parse_log
    finally:
        sys.path.pop(0)
    # regex metachars in a metric name must not crash pattern building
    data = parse_log.parse(["Epoch[0] Train-top_k(5)=0.9"], ["top_k(5)"])
    assert data[0]["train-top_k(5)"] == 0.9


def test_bandwidth_measure_runs():
    sys.path.insert(0, os.path.join(REPO, "tools", "bandwidth"))
    try:
        import measure
    finally:
        sys.path.pop(0)
    bw = measure.measure(size_mb=1.0, repeat=2)
    assert bw > 0


def test_diagnose_runs():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py")],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    assert "Framework Info" in proc.stdout


# ---------------------------------------------------------------------------
# framework_lint FL007 — serving-loop TPU hazards (scoped to serve/)
# ---------------------------------------------------------------------------

def _lint(src, path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    return framework_lint.lint_source(src, path)


_SERVE_PATH = "incubator_mxnet_tpu/serve/engine.py"


def test_fl007_flags_undonated_jit_in_serve():
    src = ("import jax\n"
           "def build(fn):\n"
           "    return jax.jit(fn, static_argnames=('k',))\n")
    hits = [f for f in _lint(src, _SERVE_PATH) if f.rule == "FL007"]
    assert len(hits) == 1
    assert "donate" in hits[0].message


def test_fl007_accepts_donated_jit_and_other_paths():
    donated = ("import jax\n"
               "def build(fn):\n"
               "    return jax.jit(fn, donate_argnums=(1, 2))\n")
    assert not [f for f in _lint(donated, _SERVE_PATH)
                if f.rule == "FL007"]
    by_name = ("import jax\n"
               "def build(fn):\n"
               "    return jax.jit(fn, donate_argnames=('ck', 'cv'))\n")
    assert not [f for f in _lint(by_name, _SERVE_PATH)
                if f.rule == "FL007"]
    # the rule is scoped: the same undonated jit OUTSIDE serve/ is fine
    undonated = ("import jax\n"
                 "def build(fn):\n"
                 "    return jax.jit(fn)\n")
    assert not [f for f in _lint(undonated,
                                 "incubator_mxnet_tpu/models/decoding.py")
                if f.rule == "FL007"]


def test_fl007_flags_device_branching_in_step_loop():
    src = ("def step(active, engine):\n"
           "    if active.any():\n"
           "        engine.decode()\n"
           "    while engine.mask.all():\n"
           "        engine.decode()\n")
    hits = [f for f in _lint(src, _SERVE_PATH) if f.rule == "FL007"]
    assert len(hits) == 2
    assert all("host" in f.message for f in hits)
    # host-side control flow (ints, lens) stays clean
    clean = ("def step(self):\n"
             "    if self.n_active == 0:\n"
             "        return False\n"
             "    while self.queue:\n"
             "        self.admit()\n")
    assert not [f for f in _lint(clean, _SERVE_PATH) if f.rule == "FL007"]


def test_fl007_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    serve_dir = os.path.join(REPO, "incubator_mxnet_tpu", "serve")
    findings = [f for f in framework_lint.lint_paths([serve_dir])
                if f.rule == "FL007"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# framework_lint FL008 — span-tracing hygiene
# ---------------------------------------------------------------------------

_ANY_PATH = "incubator_mxnet_tpu/gluon/trainer.py"


def test_fl008_flags_bare_start_span():
    src = ("from incubator_mxnet_tpu.telemetry import tracing\n"
           "t = tracing.Tracer()\n"
           "def f():\n"
           "    s = t.start_span('work')\n"
           "    return s\n")
    hits = [f for f in _lint(src, _ANY_PATH) if f.rule == "FL008"]
    assert len(hits) == 1
    assert "with" in hits[0].message


def test_fl008_accepts_with_and_open_span():
    good = ("from incubator_mxnet_tpu.telemetry import tracing\n"
            "t = tracing.Tracer()\n"
            "def f(req):\n"
            "    with t.start_span('work'):\n"
            "        pass\n"
            "    with tracing.span('other', x=1):\n"
            "        pass\n"
            "    req.span = tracing.open_span('request')\n"
            "    req.span.close()\n")
    assert not [f for f in _lint(good, _ANY_PATH) if f.rule == "FL008"]


def test_fl008_flags_span_creation_in_ops_bodies():
    src = ("from ..telemetry import tracing\n"
           "def kernel(x):\n"
           "    with tracing.span('k'):\n"
           "        return x\n")
    hits = [f for f in _lint(src, "incubator_mxnet_tpu/ops/k.py")
            if f.rule == "FL008"]
    assert len(hits) == 1
    assert "jit-traced" in hits[0].message
    # the same source OUTSIDE ops/ is fine
    assert not [f for f in _lint(src, _ANY_PATH) if f.rule == "FL008"]
    # module-level span use in ops/ (not in a function body) is not
    # kernel-reachable — same scoping as FL003/FL005
    top = ("from ..telemetry import tracing\n"
           "with tracing.span('import'):\n"
           "    pass\n")
    assert not [f for f in _lint(top, "incubator_mxnet_tpu/ops/k.py")
                if f.rule == "FL008"]


def test_fl008_ignores_unrelated_span_names():
    # .span()/.start_span-free code and foreign attrs named 'span' on
    # non-tracing receivers must not fire (only start_span is
    # unambiguous by name alone)
    src = ("def f(soup):\n"
           "    return soup.span('x')\n")
    assert not [f for f in _lint(src, _ANY_PATH) if f.rule == "FL008"]


def test_fl008_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu"),
         os.path.join(REPO, "tools"),
         os.path.join(REPO, "bench.py")]) if f.rule == "FL008"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# FL009 — paged-serving hazards (ISSUE 6: page-table gather discipline)
# ---------------------------------------------------------------------------

def test_fl009_flags_host_iteration_over_pool():
    src = ("def drain(self):\n"
           "    for page in self._pool_k:\n"
           "        self.copy_out(page)\n")
    hits = [f for f in _lint(src, _SERVE_PATH) if f.rule == "FL009"]
    assert len(hits) == 1 and "gather" in hits[0].message
    # host page LISTS iterate freely (allocator bookkeeping)
    clean = ("def free(self, pages):\n"
             "    for p in pages:\n"
             "        self.refs[p] -= 1\n")
    assert not [f for f in _lint(clean, _SERVE_PATH) if f.rule == "FL009"]


def test_fl009_flags_dynamic_shape_take_and_scatter():
    take = ("import jax.numpy as jnp\n"
            "def view(pool, pages):\n"
            "    return jnp.take(pool, [int(p) for p in pages], axis=0)\n")
    hits = [f for f in _lint(take, _SERVE_PATH) if f.rule == "FL009"]
    assert len(hits) == 1 and "static-shape" in hits[0].message
    scatter = ("def write(pool, pages, vals):\n"
               "    return pool.at[list(pages)].set(vals)\n")
    hits = [f for f in _lint(scatter, _SERVE_PATH) if f.rule == "FL009"]
    assert len(hits) == 1
    # static-shape arrays (the page table) pass; constant literals pass
    clean = ("import jax.numpy as jnp\n"
             "def view(pool, table, vals):\n"
             "    v = jnp.take(pool, table, axis=0)\n"
             "    return pool.at[table].set(vals), v\n")
    assert not [f for f in _lint(clean, _SERVE_PATH) if f.rule == "FL009"]
    # scoped to serve/: the same code elsewhere is not the rule's business
    assert not [f for f in _lint(take, "incubator_mxnet_tpu/ops/take.py")
                if f.rule == "FL009"]


def test_fl009_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu"),
         os.path.join(REPO, "tools"),
         os.path.join(REPO, "bench.py")]) if f.rule == "FL009"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# FL010 — sharding-spec hygiene (ISSUE 8)

_PARALLEL_PATH = "incubator_mxnet_tpu/parallel/foo.py"


def test_fl010_flags_axis_not_in_any_mesh():
    src = ("from jax.sharding import PartitionSpec as P\n"
           "def f():\n"
           "    return P('dq', None)\n")
    hits = [f for f in _lint(src, _PARALLEL_PATH) if f.rule == "FL010"]
    assert len(hits) == 1
    assert "'dq'" in hits[0].message


def test_fl010_accepts_axes_drawn_from_mesh_in_scope():
    # axis universe: make_mesh dict keys, Mesh axis_names, and *axis*
    # parameter defaults all legitimize the literal
    src = ("from jax.sharding import PartitionSpec as P\n"
           "from .mesh import make_mesh\n"
           "import jax\n"
           "def f(x, data_axis='sp'):\n"
           "    mesh = make_mesh({'dp': 2, 'tp': 4})\n"
           "    m2 = jax.sharding.Mesh(x, ('host', 'local'))\n"
           "    return (P('dp', 'tp'), P(('host', 'local')),\n"
           "            P('sp'), P(data_axis), P())\n")
    assert not [f for f in _lint(src, _PARALLEL_PATH)
                if f.rule == "FL010"]


def test_fl010_flags_constraint_outside_mesh_scope():
    src = ("import jax\n"
           "from jax.sharding import PartitionSpec as P\n"
           "from .mesh import make_mesh, mesh_scope\n"
           "def f(x):\n"
           "    mesh = make_mesh({'dp': 2})\n"
           "    return jax.lax.with_sharding_constraint(x, P('dp'))\n")
    hits = [f for f in _lint(src, _PARALLEL_PATH) if f.rule == "FL010"]
    assert len(hits) == 1
    assert "mesh_scope" in hits[0].message
    # same call under the scope (incl. the conditional idiom) is fine
    ok = ("import jax, contextlib\n"
          "from jax.sharding import PartitionSpec as P\n"
          "from .mesh import make_mesh, mesh_scope\n"
          "def f(x, m):\n"
          "    mesh = make_mesh({'dp': 2})\n"
          "    with (mesh_scope(mesh) if m else contextlib.nullcontext()):\n"
          "        return jax.lax.with_sharding_constraint(x, P('dp'))\n")
    assert not [f for f in _lint(ok, _PARALLEL_PATH) if f.rule == "FL010"]


def test_fl010_scoped_to_parallel_and_serve():
    src = ("from jax.sharding import PartitionSpec as P\n"
           "def f():\n"
           "    return P('anything')\n")
    assert not [f for f in _lint(src, "incubator_mxnet_tpu/models/foo.py")
                if f.rule == "FL010"]


def test_fl010_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu"),
         os.path.join(REPO, "tools"),
         os.path.join(REPO, "bench.py")]) if f.rule == "FL010"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# FL011 — gateway/serving boundedness (ISSUE 9)
# ---------------------------------------------------------------------------

def test_fl011_flags_unbounded_queues_in_serve():
    src = ("import collections\n"
           "import queue\n"
           "pending = collections.deque()\n"
           "stream = queue.Queue()\n"
           "sq = queue.SimpleQueue()\n")
    hits = [f for f in _lint(src, _SERVE_PATH) if f.rule == "FL011"]
    assert len(hits) == 3
    assert any("deque" in f.message for f in hits)
    assert any("Queue" in f.message for f in hits)
    assert any("SimpleQueue" in f.message for f in hits)


def test_fl011_accepts_bounded_noqa_and_other_paths():
    bounded = (
        "import collections\n"
        "import queue\n"
        "a = collections.deque(maxlen=64)\n"
        "b = collections.deque([], 64)\n"
        "c = queue.Queue(8)\n"
        "d = queue.Queue(maxsize=8)\n"
        "e = collections.deque()  # noqa: FL011 - admission-bounded\n")
    assert not [f for f in _lint(bounded, _SERVE_PATH)
                if f.rule == "FL011"]
    # the rule is scoped: the same unbounded deque OUTSIDE serve/ is fine
    outside = "import collections\nq = collections.deque()\n"
    assert not [f for f in _lint(outside,
                                 "incubator_mxnet_tpu/gluon/trainer.py")
                if f.rule == "FL011"]


def test_fl011_flags_timeoutless_blocking_waits():
    src = ("def pump(q, ev):\n"
           "    tok = q.get()\n"
           "    ev.wait()\n")
    hits = [f for f in _lint(src, _SERVE_PATH) if f.rule == "FL011"]
    assert len(hits) == 2
    assert all("timeout" in f.message for f in hits)
    clean = ("def pump(q, ev):\n"
             "    tok = q.get(timeout=1.0)\n"
             "    ev.wait(0.5)\n"
             "    tok2 = q.get_nowait()\n")
    assert not [f for f in _lint(clean, _SERVE_PATH)
                if f.rule == "FL011"]


def test_fl011_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu"),
         os.path.join(REPO, "tools"),
         os.path.join(REPO, "bench.py")]) if f.rule == "FL011"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# run-metadata stamping (VERDICT Weak #5: stale-rerun detectability)
# ---------------------------------------------------------------------------

def test_run_metadata_stamps_sha_and_round():
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as ge
    finally:
        sys.path.pop(0)
    meta = ge.run_metadata(round_id=7)
    assert meta["round"] == "7"
    assert meta["git_sha"] and " " not in meta["git_sha"]
    # env fallback, and 'unset' (never a wall clock) when absent
    old = os.environ.pop("MXNET_RUN_ROUND", None)
    try:
        os.environ["MXNET_RUN_ROUND"] = "r42"
        assert ge.run_metadata()["round"] == "r42"
        del os.environ["MXNET_RUN_ROUND"]
        assert ge.run_metadata()["round"] == "unset"
    finally:
        if old is not None:
            os.environ["MXNET_RUN_ROUND"] = old


# ---------------------------------------------------------------------------
# FL012 — compile-observatory coverage (ISSUE 10)
# ---------------------------------------------------------------------------

_OPS_PATH = "incubator_mxnet_tpu/ops/linalg.py"


def test_fl012_flags_raw_jit_outside_entry_points():
    src = ("import jax\n"
           "f = jax.jit(lambda x: x + 1)\n"
           "g = jit(lambda x: x * 2)\n")
    hits = [f for f in _lint(src, _OPS_PATH) if f.rule == "FL012"]
    assert len(hits) == 2
    assert all("ledger" in f.message for f in hits)


def test_fl012_accepts_entry_points_noqa_and_outside_tree():
    src = "import jax\nf = jax.jit(lambda x: x + 1)\n"
    # every registered observatory entry point is exempt
    for ep in ("incubator_mxnet_tpu/ndarray/ndarray.py",
               "incubator_mxnet_tpu/gluon/block.py",
               "incubator_mxnet_tpu/serve/engine.py",
               "incubator_mxnet_tpu/parallel/sharded.py",
               "incubator_mxnet_tpu/telemetry/compiles.py"):
        assert not [f for f in _lint(src, ep) if f.rule == "FL012"], ep
    # the noqa escape carries a justification
    noqa = ("import jax\n"
            "f = jax.jit(fn)  # noqa: FL012 - trace-time inner jit\n")
    assert not [f for f in _lint(noqa, _OPS_PATH) if f.rule == "FL012"]
    # scoped to the framework tree: tools/ and tests/ are not flagged
    assert not [f for f in _lint(src, "tools/bench_something.py")
                if f.rule == "FL012"]
    # ledgered_jit is the sanctioned spelling and is not a jit call
    ok = ("from incubator_mxnet_tpu.telemetry.compiles import ledgered_jit\n"
          "f = ledgered_jit(lambda x: x, family='ops.f')\n")
    assert not [f for f in _lint(ok, _OPS_PATH) if f.rule == "FL012"]


def test_fl012_mirror_matches_compiles_registry():
    """The lint's entry-point list is a mirror of
    telemetry.compiles.OBSERVATORY_ENTRY_POINTS — drift would silently
    widen or narrow the rule."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    from incubator_mxnet_tpu.telemetry import compiles

    assert tuple(framework_lint._OBSERVATORY_ENTRY_POINTS) \
        == tuple(compiles.OBSERVATORY_ENTRY_POINTS)


def test_fl012_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL012"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# FL013 — serve/ KV-pool aliasing (ISSUE 11)
# ---------------------------------------------------------------------------

def test_fl013_flags_undonated_pool_param():
    src = ("import jax\n"
           "def decode(params, pk, pv, table, tok):\n"
           "    return tok\n"
           "f = jax.jit(decode, donate_argnums=(1,))\n")
    hits = [f for f in _lint(src, _SERVE_PATH) if f.rule == "FL013"]
    assert len(hits) == 1
    assert "`pv`" in hits[0].message and "donate" in hits[0].message


def test_fl013_flags_scan_over_pool():
    src = ("from jax import lax\n"
           "def step(c, xs):\n"
           "    return c, None\n"
           "def run(x, pk, pv):\n"
           "    out, _ = lax.scan(step, x, (pk, pv))\n"
           "    return out\n")
    hits = [f for f in _lint(src, _SERVE_PATH) if f.rule == "FL013"]
    assert len(hits) == 1
    assert "re-stacks" in hits[0].message


def test_fl013_accepts_donated_noqa_and_outside_serve():
    # fully donated pools (fp and int8 signatures) are the idiom
    ok = ("import jax\n"
          "def decode(params, pk, pv, sk, sv, table):\n"
          "    return table\n"
          "f = jax.jit(decode, donate_argnums=(1, 2, 3, 4))\n")
    assert not [f for f in _lint(ok, _SERVE_PATH) if f.rule == "FL013"]
    # the noqa escape carries a justification
    noqa = ("import jax\n"
            "def audit(pk, pv):\n"
            "    return pk\n"
            "f = jax.jit(audit)  # noqa: FL013 - read-only analysis pass\n")
    assert not [f for f in _lint(noqa, _SERVE_PATH) if f.rule == "FL013"]
    # scans whose xs carries no pool are untouched
    scan_ok = ("from jax import lax\n"
               "def run(x, layers):\n"
               "    out, _ = lax.scan(lambda c, l: (c, None), x, layers)\n"
               "    return out\n")
    assert not [f for f in _lint(scan_ok, _SERVE_PATH)
                if f.rule == "FL013"]
    # scoped to serve/: the same source outside serve/ is not flagged
    bad = ("import jax\n"
           "def decode(params, pk, pv):\n"
           "    return params\n"
           "f = jax.jit(decode, donate_argnums=(1,))\n")
    assert not [f for f in _lint(bad, _OPS_PATH) if f.rule == "FL013"]
    # non-literal donate_argnums can't be checked statically: no flag
    dyn = ("import jax\n"
           "def decode(params, pk, pv):\n"
           "    return params\n"
           "donate = (1, 2)\n"
           "f = jax.jit(decode, donate_argnums=donate)\n")
    assert not [f for f in _lint(dyn, _SERVE_PATH) if f.rule == "FL013"]


def test_fl013_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL013"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# FL014 — collective hygiene (ISSUE 12)
# ---------------------------------------------------------------------------

_PAR_PATH = "incubator_mxnet_tpu/parallel/moe.py"
_COLL_PATH = "incubator_mxnet_tpu/parallel/collectives.py"


def test_fl014_flags_raw_lax_collectives():
    # every import spelling: `from jax import lax`, `jax.lax.`, and a
    # direct prim import
    src = ("import jax\n"
           "from jax import lax\n"
           "from jax.lax import all_gather as ag\n"
           "def f(x):\n"
           "    a = lax.psum(x, 'dp')\n"
           "    b = jax.lax.ppermute(x, 'dp', [(0, 1)])\n"
           "    c = ag(x, 'dp')\n"
           "    return a + b + c\n")
    hits = [f for f in _lint(src, _PAR_PATH) if f.rule == "FL014"]
    assert len(hits) == 3
    assert all("census" in h.message for h in hits)


def test_fl014_flags_adhoc_clock_around_dist():
    src = ("import time\n"
           "from . import dist\n"
           "def sync(x):\n"
           "    t0 = time.perf_counter()\n"
           "    out = dist.allreduce(x)\n"
           "    return out, time.perf_counter() - t0\n")
    hits = [f for f in _lint(src, _PAR_PATH) if f.rule == "FL014"]
    assert len(hits) == 2
    assert "mx_collective_seconds" in hits[0].message


def test_fl014_accepts_wrappers_noqa_and_scoping():
    # collectives.py itself is the census point: raw prims allowed
    raw = ("import jax\n"
           "def all_reduce(v, axis_name):\n"
           "    return jax.lax.psum(v, axis_name)\n")
    assert not [f for f in _lint(raw, _COLL_PATH) if f.rule == "FL014"]
    # routed through the wrappers: clean
    ok = ("from . import collectives\n"
          "def f(x):\n"
          "    return collectives.all_reduce(x, 'dp')\n")
    assert not [f for f in _lint(ok, _PAR_PATH) if f.rule == "FL014"]
    # axis_index / axis_size are queries, not comms: never flagged
    q = ("from jax import lax\n"
         "def f(x):\n"
         "    return lax.axis_index('dp')\n")
    assert not [f for f in _lint(q, _PAR_PATH) if f.rule == "FL014"]
    # noqa escape with a reason
    noqa = ("from jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'dp')  # noqa: FL014 - rep typing\n")
    assert not [f for f in _lint(noqa, _PAR_PATH) if f.rule == "FL014"]
    # scoped to parallel//serve/: ops/ modules are out of scope
    assert not [f for f in _lint(
        "from jax import lax\ndef f(x):\n    return lax.psum(x, 'd')\n",
        _OPS_PATH) if f.rule == "FL014"]
    # a clock in a function with no dist calls is FL014-silent
    clock = ("import time\n"
             "def f():\n"
             "    return time.perf_counter()\n")
    assert not [f for f in _lint(clock, _PAR_PATH) if f.rule == "FL014"]


def test_fl014_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL014"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# FL015 — membership-epoch guard (ISSUE 13)
# ---------------------------------------------------------------------------

_FAULT_PATH = "incubator_mxnet_tpu/fault/elastic.py"
_DIST_PATH = "incubator_mxnet_tpu/parallel/dist.py"


def test_fl015_flags_unguarded_dist_collectives():
    src = ("from ..parallel import dist\n"
           "def sync(x, gen):\n"
           "    a = dist.allreduce(x)\n"
           "    dist.barrier()\n"
           "    b = dist.broadcast(x, root=0)\n"
           "    objs = dist.exchange_objs({'r': 0})\n"
           "    return a, b, objs\n")
    hits = [f for f in _lint(src, _FAULT_PATH) if f.rule == "FL015"]
    assert len(hits) == 4
    assert all("StaleGenerationError" in h.message for h in hits)
    # parallel/ modules are in scope too
    hits = [f for f in _lint(src, _PAR_PATH) if f.rule == "FL015"]
    assert len(hits) == 4


def test_fl015_accepts_threaded_generation_noqa_and_scoping():
    # generation= threaded: clean
    ok = ("from ..parallel import dist\n"
          "def sync(x, gen):\n"
          "    dist.barrier(generation=gen)\n"
          "    return dist.allreduce(x, generation=dist.generation())\n")
    assert not [f for f in _lint(ok, _FAULT_PATH) if f.rule == "FL015"]
    # a **kwargs splat can't be seen through statically: no flag
    splat = ("from ..parallel import dist\n"
             "def sync(x, **kw):\n"
             "    return dist.allreduce(x, **kw)\n")
    assert not [f for f in _lint(splat, _FAULT_PATH) if f.rule == "FL015"]
    # noqa escape with a reason
    noqa = ("from ..parallel import dist\n"
            "def sync(x):\n"
            "    return dist.allreduce(x)  # noqa: FL015 - single-epoch\n")
    assert not [f for f in _lint(noqa, _FAULT_PATH) if f.rule == "FL015"]
    # dist.py itself (the guard's home) is exempt
    bare = ("def barrier(tag='b'):\n"
            "    pass\n"
            "def _probe():\n"
            "    return dist.barrier()\n")
    assert not [f for f in _lint(bare, _DIST_PATH) if f.rule == "FL015"]
    # out-of-scope modules (telemetry/, ops/) are untouched
    out = ("from ..parallel import dist\n"
           "def sync(x):\n"
           "    return dist.allreduce(x)\n")
    assert not [f for f in _lint(out, _OPS_PATH) if f.rule == "FL015"]


def test_fl015_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL015"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# framework_lint FL016 — telemetry series index (ISSUE 14)
# ---------------------------------------------------------------------------

_TELE_PATH = "incubator_mxnet_tpu/telemetry/fleet.py"


def _lint_doc(src, path, telemetry_text):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    return framework_lint.lint_source(src, path,
                                      telemetry_text=telemetry_text)


def test_fl016_flags_undocumented_series():
    src = ("from . import registry\n"
           "c = registry.counter('mx_widget_total', 'widgets')\n"
           "g = registry.gauge('mx_widget_depth', 'depth')\n")
    doc = "## Series index\n\n`mx_widget_depth` — queue depth\n"
    hits = [f for f in _lint_doc(src, _TELE_PATH, doc)
            if f.rule == "FL016"]
    assert len(hits) == 1
    assert "mx_widget_total" in hits[0].message
    assert hits[0].line == 2


def test_fl016_accepts_documented_noqa_and_scoping():
    # documented: clean
    src = "registry.counter('mx_widget_total', 'w')\n"
    doc = "mx_widget_total is counted here"
    assert not [f for f in _lint_doc(src, _TELE_PATH, doc)
                if f.rule == "FL016"]
    # noqa escape on the registration line
    noqa = "registry.counter('mx_widget_total', 'w')  # noqa: FL016\n"
    assert not [f for f in _lint_doc(noqa, _TELE_PATH, "nothing")
                if f.rule == "FL016"]
    # non-mx_ series and dynamic names are out of scope
    other = ("registry.counter('t_reqs_total', 'n')\n"
             "registry.counter(name, 'n')\n")
    assert not [f for f in _lint_doc(other, _TELE_PATH, "nothing")
                if f.rule == "FL016"]
    # the registry factory itself is exempt (helpers build names there)
    reg = "registry.counter('mx_widget_total', 'w')\n"
    assert not [f for f in _lint_doc(
        reg, "incubator_mxnet_tpu/telemetry/registry.py", "nothing")
        if f.rule == "FL016"]
    # modules outside the package are out of scope
    assert not [f for f in _lint_doc(reg, "tools/bench.py", "nothing")
                if f.rule == "FL016"]
    # no TELEMETRY.md found -> the rule stays silent, never guesses
    assert not [f for f in _lint_doc(reg, _TELE_PATH, None)
                if f.rule == "FL016"]


def test_fl016_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL016"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# framework_lint FL017 — serve/ placement-spec provenance (ISSUE 15)
# ---------------------------------------------------------------------------

_SERVE_PATH = "incubator_mxnet_tpu/serve/sharded.py"


def _lint_src(src, path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    return framework_lint.lint_source(src, path)


def test_fl017_flags_bare_spec_literals_at_placement_sites():
    src = ("import jax\n"
           "from jax.sharding import NamedSharding, PartitionSpec as P\n"
           "def place(x, mesh):\n"
           "    return jax.device_put(x, NamedSharding(mesh, P('tp')))\n"
           "def pin(x, mesh):\n"
           "    return jax.lax.with_sharding_constraint(\n"
           "        x, NamedSharding(mesh, P(None, 'tp')))\n")
    hits = [f for f in _lint_src(src, _SERVE_PATH) if f.rule == "FL017"]
    assert len(hits) == 2
    assert "ServeLayout" in hits[0].message
    assert {h.line for h in hits} == {4, 6}


def test_fl017_accepts_layout_derived_noqa_and_scoping():
    # specs flowing through a layout: clean
    good = ("import jax\n"
            "def place(x, layout, path):\n"
            "    s = layout.sharding(layout.spec_for(path))\n"
            "    return jax.device_put(x, s)\n")
    assert not [f for f in _lint_src(good, _SERVE_PATH)
                if f.rule == "FL017"]
    # noqa escape with a reason
    noqa = ("import jax\n"
            "from jax.sharding import NamedSharding as NS\n"
            "def stage(x, mesh, p):\n"
            "    return jax.device_put(x, NS(mesh, p))  "
            "# noqa: FL017 — host staging, layout-free\n")
    assert not [f for f in _lint_src(noqa, _SERVE_PATH)
                if f.rule == "FL017"]
    # keyword form is still caught
    kw = ("import jax\n"
          "from jax.sharding import PartitionSpec\n"
          "def f(x):\n"
          "    return jax.device_put(x, device=PartitionSpec('tp'))\n")
    assert [f for f in _lint_src(kw, _SERVE_PATH) if f.rule == "FL017"]
    # outside serve/ the rule is silent (parallel/ owns its own idiom)
    bad = ("import jax\n"
           "from jax.sharding import PartitionSpec\n"
           "def f(x):\n"
           "    return jax.device_put(x, PartitionSpec('tp'))\n")
    assert not [f for f in _lint_src(
        bad, "incubator_mxnet_tpu/parallel/mesh.py") if f.rule == "FL017"]


def test_fl017_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL017"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# framework_lint FL018 — control-plane tracked-lock provenance (ISSUE 16)
# ---------------------------------------------------------------------------

def test_fl018_flags_raw_locks_in_control_plane():
    src = ("import threading\n"
           "class Engine:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.RLock()\n"
           "        self._cv = threading.Condition()\n"
           "_MOD_LOCK = threading.Lock()\n")
    for path in ("incubator_mxnet_tpu/serve/api.py",
                 "incubator_mxnet_tpu/fault/retry.py",
                 "incubator_mxnet_tpu/telemetry/stages.py"):
        hits = [f for f in _lint_src(src, path) if f.rule == "FL018"]
        assert len(hits) == 3, (path, hits)
        assert "tracked_lock" in hits[0].message
        assert {h.line for h in hits} == {4, 5, 6}


def test_fl018_accepts_tracked_noqa_registry_and_scoping():
    # tracked_lock construction: clean
    good = ("from ..telemetry.locks import tracked_lock\n"
            "class Engine:\n"
            "    def __init__(self):\n"
            "        self._lock = tracked_lock('serve.engine')\n")
    assert not [f for f in _lint_src(
        good, "incubator_mxnet_tpu/serve/api.py") if f.rule == "FL018"]
    # noqa escape with a reason
    noqa = ("import threading\n"
            "_CELLS = threading.Lock()  "
            "# noqa: FL018 - backs the tracked locks themselves\n")
    assert not [f for f in _lint_src(
        noqa, "incubator_mxnet_tpu/telemetry/registry.py")
        if f.rule == "FL018"]
    # the tracked-lock registry module is exempt (it wraps raw locks)
    raw = "import threading\n_G = threading.Lock()\n"
    assert not [f for f in _lint_src(
        raw, "incubator_mxnet_tpu/telemetry/locks.py")
        if f.rule == "FL018"]
    # outside serve//fault//telemetry/ the rule is silent
    assert not [f for f in _lint_src(
        raw, "incubator_mxnet_tpu/parallel/dist.py") if f.rule == "FL018"]


def test_fl018_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL018"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# framework_lint FL020 — serve/ replica-set choke point (ISSUE 18)
# ---------------------------------------------------------------------------

def test_fl020_flags_replica_list_mutations_outside_choke_point():
    src = ("class Gateway:\n"
           "    def grow(self, m, rep):\n"
           "        m.replicas.append(rep)\n"
           "    def shrink(self, m):\n"
           "        m.replicas.pop()\n"
           "    def reset(self, m):\n"
           "        m.replicas = []\n"
           "    def merge(self, m, more):\n"
           "        m.replicas += more\n")
    hits = [f for f in _lint_src(
        src, "incubator_mxnet_tpu/serve/gateway.py") if f.rule == "FL020"]
    assert len(hits) == 4, hits
    assert "ReplicaSetController" in hits[0].message
    assert {h.line for h in hits} == {3, 5, 7, 9}


def test_fl020_accepts_init_noqa_choke_point_and_scoping():
    # construction-time assignment in __init__: the sanctioned exception
    good = ("class _Model:\n"
            "    def __init__(self, replicas):\n"
            "        self.replicas = replicas\n"
            "    def read(self):\n"
            "        return list(self.replicas)\n")
    assert not [f for f in _lint_src(
        good, "incubator_mxnet_tpu/serve/gateway.py")
        if f.rule == "FL020"]
    # noqa escape with a reason
    noqa = ("def retire(m, rep):\n"
            "    m.replicas.remove(rep)  "
            "# noqa: FL020 - test-only fixture teardown\n")
    assert not [f for f in _lint_src(
        noqa, "incubator_mxnet_tpu/serve/gateway.py")
        if f.rule == "FL020"]
    # the choke point itself is exempt (mutations hold the tracked lock)
    raw = "def spawn(m, rep):\n    m.replicas.append(rep)\n"
    assert not [f for f in _lint_src(
        raw, "incubator_mxnet_tpu/serve/elastic.py")
        if f.rule == "FL020"]
    # outside serve/ the rule is silent (no routers there)
    assert not [f for f in _lint_src(
        raw, "incubator_mxnet_tpu/parallel/dist.py")
        if f.rule == "FL020"]
    # a local list named `replicas` (gateway construction) is not an
    # attribute mutation and stays clean
    local = ("def build():\n"
             "    replicas = []\n"
             "    replicas.append(1)\n"
             "    return replicas\n")
    assert not [f for f in _lint_src(
        local, "incubator_mxnet_tpu/serve/gateway.py")
        if f.rule == "FL020"]


def test_fl020_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL020"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# framework_lint FL021 — serve/ migration choke point (ISSUE 19)
# ---------------------------------------------------------------------------

def test_fl021_flags_cross_replica_pool_access():
    src = ("def steal(dst, src, pages, payload, prompt):\n"
           "    k = src.slots._pools\n"
           "    payload = src.slots.copy_pages_out(pages)\n"
           "    dst.slots.copy_pages_in(pages, payload)\n"
           "    dst.slots.allocator.alloc(3)\n"
           "    dst.slots.allocator.incref(pages)\n"
           "    src.slots.allocator.decref(pages)\n"
           "    dst.slots.prefix_cache.register(prompt, pages)\n")
    hits = [f for f in _lint_src(
        src, "incubator_mxnet_tpu/serve/gateway.py") if f.rule == "FL021"]
    assert len(hits) == 7, hits
    assert "serve/disagg.py" in hits[0].message
    assert {h.line for h in hits} == {2, 3, 4, 5, 6, 7, 8}


def test_fl021_exempts_choke_point_self_and_reads():
    raw = ("def move(dst, src, pages, payload):\n"
           "    payload = src.slots.copy_pages_out(pages)\n"
           "    dst.slots.copy_pages_in(pages, payload)\n")
    # serve/disagg.py IS the choke point
    assert not [f for f in _lint_src(
        raw, "incubator_mxnet_tpu/serve/disagg.py") if f.rule == "FL021"]
    # outside serve/ the rule is silent
    assert not [f for f in _lint_src(
        raw, "incubator_mxnet_tpu/parallel/dist.py") if f.rule == "FL021"]
    # an engine touching ITS OWN pool is the normal serving path
    own = ("class SlotDecoder:\n"
           "    def _gather(self, pages):\n"
           "        k = self.slots._pools\n"
           "        self.slots.allocator.decref(pages)\n")
    assert not [f for f in _lint_src(
        own, "incubator_mxnet_tpu/serve/gateway.py") if f.rule == "FL021"]
    # read-only probes + lifecycle calls stay clean (gateway shutdown,
    # elastic release, capacity accounting all use these)
    reads = ("def probe(rep):\n"
             "    n = rep.slots.allocator.free_pages\n"
             "    m = rep.slots.allocator.usable_pages\n"
             "    rep.slots.prefix_cache.clear()\n"
             "    rep.slots.prefix_cache.evict_unused(4)\n"
             "    w = rep.slots.prefix_cache.shared_tokens([1])\n"
             "    rep.slots.release()\n")
    assert not [f for f in _lint_src(
        reads, "incubator_mxnet_tpu/serve/elastic.py") if f.rule == "FL021"]
    # noqa escape with a reason
    noqa = ("def fixture(rep, pages):\n"
            "    rep.slots.allocator.decref(pages)  "
            "# noqa: FL021 - test fixture teardown\n")
    assert not [f for f in _lint_src(
        noqa, "incubator_mxnet_tpu/serve/gateway.py") if f.rule == "FL021"]


def test_fl021_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL021"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# framework_lint FL022 — serve/ duration-accounting choke point (ISSUE 20)
# ---------------------------------------------------------------------------

def test_fl022_flags_adhoc_perf_counter_durations():
    # a direct subtraction outside any charge call
    direct = ("import time\n"
              "def step(self):\n"
              "    t0 = time.perf_counter()\n"
              "    work()\n"
              "    dur = time.perf_counter() - t0\n")
    hits = [f for f in _lint_src(
        direct, "incubator_mxnet_tpu/serve/scheduler.py")
        if f.rule == "FL022"]
    assert len(hits) == 1 and hits[0].line == 5, hits
    assert "charge call" in hits[0].message
    # an assigned duration that never feeds a charge call
    stray = ("import time\n"
             "def step(self, t0):\n"
             "    dt = time.perf_counter() - t0\n"
             "    self.stats.append(dt)\n")
    hits = [f for f in _lint_src(
        stray, "incubator_mxnet_tpu/serve/gateway.py")
        if f.rule == "FL022"]
    assert len(hits) == 1 and hits[0].line == 3, hits


def test_fl022_exempts_charge_fed_durations():
    # the sanctioned shape: the subtraction is an argument of the
    # capacity/anatomy charge call itself
    inline = ("import time\n"
              "def step(self, t0):\n"
              "    capacity.split_device_seconds(\n"
              "        ('t',), 'm', 'decode',\n"
              "        time.perf_counter() - t0)\n"
              "    anatomy.on_decode_step(self, t0,\n"
              "                           time.perf_counter())\n")
    assert not [f for f in _lint_src(
        inline, "incubator_mxnet_tpu/serve/scheduler.py")
        if f.rule == "FL022"]
    # an assigned dt whose name feeds a charge call is sanctioned too
    fed = ("import time\n"
           "def accrue(self, req, last):\n"
           "    t = time.perf_counter()\n"
           "    dt = t - last\n"
           "    capacity.charge_kv_page_seconds(\n"
           "        req.tenant, self.model, len(req.pages) * dt)\n")
    assert not [f for f in _lint_src(
        fed, "incubator_mxnet_tpu/serve/scheduler.py")
        if f.rule == "FL022"]
    # the choke points themselves own the subtraction
    own = ("import time\n"
           "def _transition(self, t0):\n"
           "    dur = time.perf_counter() - t0\n")
    assert not [f for f in _lint_src(
        own, "incubator_mxnet_tpu/telemetry/anatomy.py")
        if f.rule == "FL022"]
    assert not [f for f in _lint_src(
        own, "incubator_mxnet_tpu/telemetry/capacity.py")
        if f.rule == "FL022"]
    # outside serve/ the rule is silent
    assert not [f for f in _lint_src(
        own, "incubator_mxnet_tpu/parallel/dist.py")
        if f.rule == "FL022"]
    # noqa escape with a reason
    noqa = ("import time\n"
            "def step(self, t0):\n"
            "    dur = time.perf_counter() - t0  "
            "# noqa: FL022 - bench-only probe\n")
    assert not [f for f in _lint_src(
        noqa, "incubator_mxnet_tpu/serve/scheduler.py")
        if f.rule == "FL022"]


def test_fl022_tree_is_clean():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import framework_lint
    finally:
        sys.path.pop(0)
    findings = [f for f in framework_lint.lint_paths(
        [os.path.join(REPO, "incubator_mxnet_tpu")])
        if f.rule == "FL022"]
    assert not findings, findings


# ---------------------------------------------------------------------------
# bench_regress — trajectory regression gate (ISSUE 10)
# ---------------------------------------------------------------------------

def _bench_regress():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import bench_regress
    finally:
        sys.path.pop(0)
    return bench_regress


def test_bench_regress_green_on_committed_history(capsys):
    br = _bench_regress()
    assert br.main([]) == 0
    out = capsys.readouterr().out
    # the latest committed round's headline metric must be in the table
    latest = sorted(br.glob.glob(os.path.join(REPO, "BENCH_r*.json")))[-1]
    with open(latest, encoding="utf-8") as f:
        headline = json.load(f)["parsed"]["metric"]
    assert "clean" in out and headline in out


def test_bench_regress_catches_both_polarities(tmp_path):
    br = _bench_regress()
    a = tmp_path / "BENCH_r01.json"
    b = tmp_path / "BENCH_r02.json"
    a.write_text(json.dumps({"n": 1, "parsed": {
        "metric": "tput_img_s", "value": 1000.0,
        "extras": {"step_latency_ms": 2.0, "mfu": 0.5}}}))
    # throughput -20% AND latency +50%: both directions must gate
    b.write_text(json.dumps({"n": 2, "parsed": {
        "metric": "tput_img_s", "value": 800.0,
        "extras": {"step_latency_ms": 3.0, "mfu": 0.5}}}))
    assert br.main(["--root", str(tmp_path)]) == 1
    rows = br.compare(br.flatten(json.loads(a.read_text())["parsed"]),
                      br.flatten(json.loads(b.read_text())["parsed"]))
    status = {r["metric"]: r["status"] for r in rows}
    assert status["tput_img_s"] == "REGRESS"
    assert status["step_latency_ms"] == "REGRESS"
    assert status["mfu"] == "ok"
    # within threshold is clean
    b.write_text(json.dumps({"n": 2, "parsed": {
        "metric": "tput_img_s", "value": 950.0,
        "extras": {"step_latency_ms": 2.1, "mfu": 0.51}}}))
    assert br.main(["--root", str(tmp_path)]) == 0


def test_bench_regress_family_drift_normalization(tmp_path):
    """Fleet-wide runner drift on a serving family is tolerated, but a
    single member regressing beyond the family's median delta still
    gates (the identical-code control case from the module docstring)."""
    br = _bench_regress()
    base = {"gpt_serve_ttft_p50_ms": 100.0,
            "gpt_serve_ttft_p99_ms": 300.0,
            "gpt_serve_longprompt_ttft_p99_ms": 400.0,
            "gpt_gateway_high_ttft_p99_ms": 60.0,
            "gpt_gateway_low_ttft_p99_ms": 350.0}
    # whole family +30% (slower runner): every member tracks the median
    drifted = {k: v * 1.30 for k, v in base.items()}
    rows = br.compare(base, drifted)
    status = {r["metric"]: r["status"] for r in rows}
    assert all(s == "ok" for s in status.values()), status
    assert all(r["drift_pct"] is not None for r in rows)
    # same drift, but ONE member blows 60% past it: that member gates
    drifted["gpt_serve_ttft_p99_ms"] = base["gpt_serve_ttft_p99_ms"] * 1.90
    rows = br.compare(base, drifted)
    status = {r["metric"]: r["status"] for r in rows}
    assert status["gpt_serve_ttft_p99_ms"] == "REGRESS"
    assert status["gpt_serve_ttft_p50_ms"] == "ok"
    # below MIN_FAMILY members the estimate is untrusted: absolute gate
    small = {k: base[k] for k in list(base)[:2]}
    rows = br.compare(small, {k: v * 1.30 for k, v in small.items()})
    assert {r["status"] for r in rows} == {"REGRESS"}
    # skip-listed gateway p50s inform the median but are never gated
    assert br.re.compile(br.DEFAULT_SKIP).search(
        "gpt_gateway_high_ttft_p50_ms")


def test_bench_regress_direction_and_edge_cases(tmp_path):
    br = _bench_regress()
    # direction heuristic: _ms/latency lower-better, _vs_ report-only
    assert br.direction("decode_latency_us") == "lower"
    assert br.direction("dot_framework_ms") == "lower"
    assert br.direction("bert_base_train_tokens_s") == "higher"
    assert br.direction("resnet50_int8_vs_fp32_wall") is None
    assert br.direction("gpt_serve_tracing_overhead_pct") is None
    assert br.direction("collective_wrapper_overhead_pct") is None
    assert br.direction("vs_baseline") == "higher"
    # <2 rounds: nothing to compare, clean exit
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "parsed": {"metric": "m", "value": 1.0}}))
    assert br.main(["--root", str(tmp_path)]) == 0
    # empty dir: usage error
    empty = tmp_path / "empty"
    empty.mkdir()
    assert br.main(["--root", str(empty)]) == 2


def test_kernel_schedule_reads_a_final_bundles_file():
    """`tools/kernel_schedule.py`'s parser on a few lines of a real dump:
    the grid loop, the kinds of work a bundle holds, and a branch's target
    found by its rank among the marked lines (a target is numbered with
    the delay slots the file leaves out)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import kernel_schedule as ks
    finally:
        sys.path.pop(0)
    with open(os.path.join(REPO, "tests", "data",
                           "final_bundles_sample.txt")) as f:
        text = f.read()
    bundles = ks.parse(text)
    assert [b["bundle"] for b in bundles] == list(range(16))
    assert bundles[0]["line"] == 7 and bundles[7]["line"] == 16
    # a range check names the copy it guards in a comment: not a copy
    assert dict(bundles[5]["kinds"]) == {"check": 1}
    assert dict(bundles[9]["kinds"]) == {"vmatmul": 1, "dma": 1}
    assert dict(bundles[11]["kinds"]) == {"vpop": 1}      # not `vpop.eup`
    assert bundles[3]["target"] == 12 and bundles[2]["mark"] == "LB"
    # targets 3 < 12 < 40 are the marked lines LB, PF, PF in order
    where = ks.branch_lines(bundles)
    assert where == {3: 9, 12: 16, 40: 24}
    loop = ks.grid_loop(bundles)
    assert (loop[0]["line"], loop[-1]["line"], len(loop)) == (9, 22, 12)
    parts = ks.stretches(loop, where)
    assert [(p["first"], p["last"], p["lines"], p["skip_to"], p["delayed"])
            for p in parts] == [
        (9, 10, 2, None, 3),        # the loop's start, up to the branch
        (11, 12, 2, 16, None),      # walked only where it is not taken
        (13, 13, 1, None, None),    # the first copy
        (16, 16, 1, None, 12),      # the branch's target: the one wait
        (17, 17, 1, None, None),
        (18, 18, 1, None, None),    # the first product, the last copy
        (19, 19, 1, None, None),    # the last product, the first result
        (20, 20, 1, None, None),    # the last result popped
        (21, 22, 2, None, None),
    ]
    assert dict(parts[1]["kinds"]) == {"check": 1}
    assert sum((p["kinds"] for p in parts), ks.collections.Counter()) == {
        "check": 1, "dma": 2, "wait": 1, "vmatmul": 2, "vpop": 2}
    import io
    out = io.StringIO()
    ks.report("mx_sample", text, out=out)
    assert "the grid loop 12 (file lines 9-22)" in out.getvalue()
    assert "[or skip to 16] 1 check" in out.getvalue()
    assert ("in the loop, every body counted: 2 vmatmul, 2 vpop, 0 vxpose, "
            "0 vmatpush.xpose, 0 vmatpush, 0 vld, 0 vor, 2 dma, 1 wait, "
            "1 check") in out.getvalue()
    ks.report("mx_none", "     0   :  { %s1 = smov 1 }", out=out)
    assert "mx_none: 1 bundles, no loop" in out.getvalue()


def test_kernel_schedule_counts_the_mxu_loads_and_the_vector_loads():
    """The weight pushes into the MXU, turned on their way in or not, and
    the loads and ORs that assemble a bfloat16 vreg (lines of the kind
    `mx_mla_decode`'s dump holds): what the products' orientation moves."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import kernel_schedule as ks
    finally:
        sys.path.pop(0)
    text = "\n".join([
        "     0 LB: > { %v1_v1 = vld [vmem:[%s2_s3] sm:$0xf]  ;;  "
        "%v2_v2 = vld [vmem:[%s2_s3 + $0x4] sm:$0xf0] }",
        "   0x1   : > { %v3_v3 = vor.u32 %v1_v1, %v2_v2  ;;  "
        "%5 = vmatpush.bf16.xpose.msra.mxu0 %v3_v3 }",
        "   0x2   : > { %6 = vmatpush.bf16.msrb.mxu1 %v3_v3  ;;  "
        "%7 = vmatmul.bf16.gmra.mxu0 %v3_v3  ;;  %v8_v4 = vpop.eup %9 }",
        "   0x3   : > { %v10_v5 = vld [vmem:[%s2_s3]]  ;;  "
        "%v11_v6 = vpop.f32.mrf.mxu0  ;;  %12 = vxpose.binary.c.b16.cont "
        "%v3_v3 }"])
    kinds = [dict(b["kinds"]) for b in ks.parse(text)]
    assert kinds == [{"vld": 2}, {"vor": 1, "vmatpush.xpose": 1},
                     {"vmatpush": 1, "vmatmul": 1},
                     {"vld": 1, "vpop": 1, "vxpose": 1}]
    import io
    out = io.StringIO()
    ks.report("mx_mla", text, out=out)
    assert ("every body counted: 1 vmatmul, 1 vpop, 1 vxpose, "
            "1 vmatpush.xpose, 1 vmatpush, 3 vld, 1 vor, 0 dma, 0 wait, "
            "0 check") in out.getvalue()


def test_kernel_schedule_compiles_paged_decode_at_the_three_cells_shapes():
    """`paged_decode --shape`: the cells that run `mx_paged_decode`, each
    with a body of its own (packed float32 heads on the VPU; bfloat16 heads
    of 128 on the MXU, one query row a head or sixteen), GPT-2 XL's by
    default as before."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import kernel_schedule as ks
    finally:
        sys.path.pop(0)
    assert set(ks.PAGED_SHAPES) == {"gpt2xl", "evabyte", "nemotron"}
    assert ks.PAGED_SHAPES["gpt2xl"] == (8, 64, 25, 25, 64, "float32")
    assert ks.PAGED_SHAPES["evabyte"] == (8, 248, 32, 32, 128, "bfloat16")
    assert ks.PAGED_SHAPES["nemotron"] == (64, 192, 32, 2, 128, "bfloat16")
    with pytest.raises(SystemExit):
        ks.main(["paged_decode", "--shape", "bert"])


def test_mla_decode_micro_rehearses_on_the_cpu():
    """`tools/mla_decode_micro.py`: off the chip the kernel runs interpreted
    at toy widths, one line a set of slot lengths, each kernel beside the
    XLA expression and timed; `--parent` times another checkout beside."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mla_decode_micro.py"),
         "--parent", REPO, "--reps", "1", "--calls", "1"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = dict(ln.split(" ", 1) for ln in proc.stdout.splitlines()
                 if ln.split(" ", 1)[0] in ("cell_mix", "all_long", "short"))
    assert set(lines) == {"cell_mix", "all_long", "short"}
    for res in map(json.loads, lines.values()):
        assert res["grid_steps"] > 0
        for side in ("parent", "change"):
            assert res[side + "_max_abs_diff"] <= 2e-2 * max(
                1.0, res["ref_scale"])
            assert len(res[side + "_us_call"]) == 1
