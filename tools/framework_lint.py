"""Framework lint: AST-based invariant checks over the framework source.

Companion to `incubator_mxnet_tpu.analysis` (the *program* auditor): this
tool audits the *framework source itself* for invariants learned from real
bugs, without importing anything it scans (pure `ast` — safe to run in CI
before the package can even import).

Rules
-----
FL001  pallas pad guard: ``pad = (-rows) % block`` must carry the
       ``if block else 0`` guard (``layer_norm.py`` idiom). An unguarded
       negate-mod ZeroDivisionErrors on empty inputs (the advisor-found
       `ops/fused_block.py` empty-batch crash).
FL002  bool leak: bare ``isinstance(key, int)`` in indexing-path functions
       (name contains getitem/setitem/index/slice). `bool` is a subclass of
       `int`, and True/False are numpy NEW-AXIS indexing — an int check
       without a bool exclusion silently reinterprets the index. Use
       ``numbers.Integral`` with an explicit ``isinstance(x, bool)`` guard.
FL003  host numpy in kernel-reachable op bodies: ``numpy.*`` calls inside
       function bodies of ``ops/`` modules force host constant-folding in
       traced code. Exemption: `jax.dtypes.float0` cotangent zeros, which
       jax REQUIRES to be numpy arrays.
FL004  ledger completeness: every statically-registered op name
       (literal `register_op_meta(...)` calls and the
       `_ELEMWISE_AND_FRIENDS` generation list) must appear in
       OPS_COVERAGE.md — the audit trail must not silently lag the code.
FL005  ad-hoc timing in kernel bodies: ``time.time()`` /
       ``time.perf_counter()`` / ``time.perf_counter_ns()`` calls inside
       function bodies of ``ops/`` modules bypass the telemetry API
       (`incubator_mxnet_tpu.telemetry`). Kernel-local wall clocks (a)
       measure dispatch, not device execution, on an async backend, and
       (b) produce numbers nobody owns (the VERDICT r5 drift class) —
       route timing through `telemetry.registry` / `profiler.Scope`.
FL006  silent swallow: a broad handler (``except Exception:`` /
       ``except BaseException:`` / bare ``except:``) whose body does
       NOTHING (only pass/continue/break/...). Silent swallows hid the
       DataLoader and dist failure modes ISSUE 3 is about — log and
       classify instead (`fault.retry.suppressed`), or, where silence is
       genuinely required (interpreter teardown), annotate the handler
       line with ``# noqa: FL006`` and a justifying comment.
FL007  serving-loop TPU hazards (scoped to ``serve/`` modules): (a) a
       ``jax.jit`` call without ``donate_argnums``/``donate_argnames`` —
       the serving programs carry the persistent KV cache, and an
       undonated cache is copied whole every step; (b) an ``if``/
       ``while`` condition calling ``.any()``/``.all()``/``.item()``/
       ``.block_until_ready()`` — data-dependent Python branching on a
       device value blocks the step loop on a host sync (and invites
       shape-dependent recompiles). Keep slot state host-side and fetch
       device results once per step (`serve/scheduler.py` idiom).
FL009  paged-serving hazards (scoped to ``serve/`` modules): (a) a
       ``for`` loop iterating a device KV *pool* value (identifier
       containing "pool") — host-side iteration over per-page device
       values syncs once per page and defeats the single
       gather-by-page-table design; (b) a ``jnp.take``/``.take`` call or
       an ``.at[...]`` scatter whose index operand is built host-side
       with a dynamic shape (list/tuple literal of non-constants, list
       comprehension, ``list(...)``/``range(...)`` call) — every
       distinct index shape compiles a fresh program, breaking the
       zero-steady-state-recompile invariant. Pass indices as
       static-shape arrays (the page table) instead.
FL010  sharding-spec hygiene (scoped to ``parallel/`` and ``serve/``
       modules): (a) a string axis name inside a ``PartitionSpec``/
       ``NamedSharding`` literal that is not drawn from any mesh in
       scope in that file — ``make_mesh``/``Mesh`` axis names, or a
       function parameter default whose name contains "axis" — is a
       typo'd or phantom axis that GSPMD silently treats as absent
       (the layout quietly degrades to replicated; `mx.analysis
       .shardcheck` rule SC003 is the runtime-level twin); (b) a
       ``with_sharding_constraint`` call whose spec is a bare
       ``PartitionSpec`` outside any ``mesh_scope``/``Mesh`` context
       manager — without an active mesh the constraint either throws or
       no-ops depending on the jax version. Pass a ``NamedSharding``
       (mesh attached) or move the call under the mesh scope.
FL008  span-tracing hygiene (`telemetry/tracing.py`): (a) a
       ``start_span(...)`` call used anywhere but directly as a ``with``
       item — a bare start_span leaks an open span into the ambient
       stack and the duration never stamps; use ``with ...start_span()``
       (or `open_span()`, the EXPLICIT-lifecycle API, when the span must
       cross function/thread boundaries); (b) any span creation
       (``span``/``open_span``/``start_span`` via a tracing import)
       inside function bodies of ``ops/`` modules — kernel-reachable
       bodies get traced by XLA, where a host-side span is at best a
       constant-folded lie and at worst a recompile-per-call hazard.
FL011  serving-queue bounds (scoped to ``serve/`` modules): (a) an
       unbounded ``deque()`` / ``Queue()`` / ``LifoQueue()`` /
       ``PriorityQueue()`` / ``SimpleQueue()`` construction without a
       ``maxlen``/``maxsize`` — gateway/scheduler queues grow without
       limit under load unless admission bounds them, and OOM-by-queue
       is the classic serving outage; (b) a zero-argument blocking wait
       (``.get()`` / ``.wait()`` / ``.join()`` / ``.acquire()``) —
       forever-blocking waits wedge the driver/step loop when the
       producer dies. Where the bound genuinely lives elsewhere (the
       loud `QueueFull` admission check; a stream bounded by max_new),
       annotate the line with ``# noqa: FL011`` and the justifying
       comment.
FL012  compile-observatory coverage (scoped to ``incubator_mxnet_tpu/``
       modules): a direct ``jax.jit(`` / ``<alias>.jit(`` call site
       outside the registered observatory entry points
       (`telemetry.compiles.OBSERVATORY_ENTRY_POINTS`). Every jitted
       program family is supposed to appear in the per-program compile
       ledger with recompile forensics; a raw ``jax.jit`` creates a
       family the observatory never sees, so steady-state recompiles in
       it are invisible. Wrap the callable with ``telemetry.compiles
       .ledgered_jit(fn, family=...)`` (or ``instrument_jit`` for an
       existing jitted object), or — where the program genuinely cannot
       be ledgered (trace-time inner jits, analysis tooling that
       compiles programs about programs) — annotate the line with
       ``# noqa: FL012`` and the justifying comment.
FL013  KV-pool aliasing (scoped to ``serve/`` modules): (a) a
       ``jax.jit`` whose wrapped function takes a KV-pool parameter
       (``pk``/``pv``/``sk``/``sv``, ``*pool*``, ``kv*``) at a
       position NOT covered by its ``donate_argnums`` — an undonated
       pool input cannot alias the output, so XLA materializes a full
       pool copy every step and the decode cost scales with
       ``n_pages`` instead of active tokens; (b) a ``lax.scan`` whose
       ``xs`` carries a pool name — scanning over a stacked pool
       re-stacks the whole carry on every step for the same O(pool)
       cost (the per-layer-pool layout exists precisely to avoid
       this). Where the pool argument genuinely must not be donated
       (a read-only analysis pass), annotate with ``# noqa: FL013``
       and the justifying comment.
FL014  collective hygiene (scoped to ``parallel/`` and ``serve/``
       modules): (a) a raw in-graph collective (``lax.psum`` /
       ``pmean`` / ``pmax`` / ``pmin`` / ``all_gather`` /
       ``psum_scatter`` / ``ppermute`` / ``all_to_all`` /
       ``pshuffle`` / ``pvary``) anywhere except
       ``parallel/collectives.py`` — the wrappers there are the fleet
       profiler's census point (payload bytes + call counts per
       op/axis), so a raw ``lax`` call is comms traffic the
       cross-rank observability plane never sees; (b) an ad-hoc
       ``time.*`` wall clock inside a function that also issues a
       host-level dist collective (``dist.allreduce`` / ``broadcast``
       / ``barrier`` / ``exchange_objs``) — the fleet profiler owns
       collective timing (``mx_collective_seconds``), and a local
       stopwatch around a blocking collective double-counts peer skew
       as local cost. Where a raw primitive is genuinely required
       (the wrappers themselves, rep-typing internals), annotate the
       line with ``# noqa: FL014`` and the justifying comment.
FL015  membership-epoch guard (scoped to ``fault/`` and ``parallel/``
       modules, excluding ``parallel/dist.py`` — the guard's home): a
       host-level dist collective call (``dist.allreduce`` /
       ``broadcast`` / ``barrier`` / ``exchange_objs``) without a
       ``generation=`` argument. After an elastic topology transition
       (RESILIENCE.md "Elastic topology") the fleet is on membership
       epoch N+1; an unguarded collective issued by a rank still
       holding epoch N hangs the survivors instead of failing loudly
       with ``StaleGenerationError``. Thread the generation the caller
       observed at its drained step boundary
       (``dist.allreduce(x, generation=gen)``). Where the ambient
       membership check alone is provably sufficient (single-epoch
       tooling, test scaffolding), annotate the line with
       ``# noqa: FL015`` and the justifying comment.
FL016  telemetry series index (scoped to ``incubator_mxnet_tpu/``
       modules, excluding ``telemetry/registry.py`` — the factory's
       home): every statically-registered metric series — a literal
       ``mx_*`` first argument to ``.counter(`` / ``.gauge(`` /
       ``.histogram(`` / ``.register_pull_gauge(`` — must appear in
       TELEMETRY.md (the FL004 ledger rule, applied to the metrics
       plane). An undocumented series is a number nobody owns:
       dashboards can't be built against it, renames break consumers
       silently, and telemetry drift starts exactly here. Add the
       series to the TELEMETRY.md index (what it measures, labels, who
       reads it), or — for a genuinely private/test-scaffolding series
       — annotate the line with ``# noqa: FL016`` and the justifying
       comment.
FL017  serve/ placement-spec provenance (scoped to ``serve/``
       modules): a ``device_put`` / ``with_sharding_constraint`` call
       whose sharding argument is a direct ``PartitionSpec`` /
       ``NamedSharding`` constructor call. Pod-scale serving places
       params and KV pools via the `serve.sharded.ServeLayout` rule
       table — ONE audited source of truth that shardcheck, the
       hot-swap path, and the replica builder all share. An inline
       spec literal at a placement site is a second, unaudited layout
       opinion: it drifts from the rule table silently and the
       SC001/SC004 pre-flight never sees it. Derive the sharding from
       a layout (``layout.sharding(layout.spec_for(...))``,
       ``pool_spec()``, ...) or — for genuinely layout-free plumbing
       (host staging buffers, tests) — annotate the line with
       ``# noqa: FL017`` and the justifying comment.
FL018  tracked-lock provenance (scoped to ``serve/`` / ``fault/`` /
       ``telemetry/`` module bodies, excluding
       ``telemetry/locks.py`` — the registry cannot be built out of
       itself): a raw ``threading.Lock()`` / ``RLock()`` /
       ``Condition()`` construction instead of
       ``telemetry.locks.tracked_lock(name)``. A raw lock is invisible
       to the racecheck runtime witness — its acquisition order never
       reaches the lock-order graph, so an ABBA inversion through it
       (RC005) cannot be caught before it deadlocks a pod, and its
       contention never shows in ``mx_lock_wait_seconds``. Construct
       control-plane locks through the registry, or — where a raw
       primitive is structurally required (the metric cells backing
       the tracked locks themselves) — annotate the line with
       ``# noqa: FL018`` and the justifying comment.
FL019  wall-clock durations (scoped to ``telemetry/`` / ``serve/``
       module bodies): a duration computed by subtracting
       ``time.time()`` readings — either a direct
       ``time.time() - x`` / ``x - time.time()`` expression or a
       subtraction of names assigned from ``time.time()`` in the same
       function. ``time.time()`` is NOT monotonic: NTP slews and step
       corrections make such a "duration" occasionally negative or
       wildly wrong, which silently corrupts latency histograms, the
       cost ledger's device-second attribution, and every burn-rate
       window computed over them. Use ``time.perf_counter()`` (or
       ``time.monotonic()`` for coarse scheduling deadlines) for
       anything subtracted; ``time.time()`` stays legitimate as an
       absolute wall-clock TIMESTAMP (log lines, snapshot metadata).
       Where a wall-clock delta is genuinely wanted (cross-host epoch
       math), annotate the line with ``# noqa: FL019`` and the
       justifying comment.
FL020  replica-set choke point (scoped to ``serve/`` module bodies,
       excluding ``serve/elastic.py`` — the choke point itself): a
       mutation of a ReplicaRouter replica list — a mutating method
       call on a ``.replicas`` attribute (``append``/``remove``/
       ``pop``/``insert``/``extend``/``clear``/``sort``/``reverse``)
       or an assignment/augmented assignment to one outside an
       ``__init__`` body. Every replica-set mutation must go through
       `serve.elastic.ReplicaSetController`'s single ``tracked_lock``
       choke point: a mutation anywhere else races the controller's
       reap/drain/heal/advice tick (the router iterates that list
       lock-free under the gateway lock), skips the warm-before-
       dispatch and page-budget funding gates, and never lands in the
       scale-event journal the bench audits. Construction-time
       assignment in ``__init__`` is the one sanctioned exception;
       anywhere else route through the controller, or annotate the
       line with ``# noqa: FL020`` and the justifying comment.
FL021  migration choke point (scoped to ``serve/`` module bodies,
       excluding ``serve/disagg.py`` — the choke point itself):
       cross-replica KV pool access — reading or writing a pool leaf
       through ``<other>.slots._pools/_draft_pools``, calling
       ``<other>.slots.copy_pages_out/copy_pages_in``, mutating
       refcounts via ``<other>.slots.allocator.alloc/incref/decref``,
       or filling a prefix cache via
       ``<other>.slots.prefix_cache.register`` where the receiver is
       not the engine's own ``self``. Page migration is the ONE
       sanctioned cross-replica data path and `serve/disagg.py` is its
       choke point: it owns the alloc-copy-register-adopt-decref
       ordering, the mid-copy rollback (``page_migration`` seam), and
       the ``mx_serve_page_migration_*`` byte accounting — a pool
       touch anywhere else can leak pages, double-free them, or move
       bytes the audit never sees. Read-only capacity probes
       (``free_pages``, ``shared_tokens``, ``usable_pages``) and
       lifecycle calls (``clear``, ``release``, ``evict_unused``) stay
       clean; a genuinely needed new path routes through
       serve.disagg or annotates with ``# noqa: FL021`` and the
       justifying comment.

Usage
-----
    python tools/framework_lint.py incubator_mxnet_tpu/ [more paths...]
                                   [--coverage OPS_COVERAGE.md]
                                   [--telemetry-doc TELEMETRY.md]
                                   [--list-rules]

Exit status 0 when clean, 1 when any rule fires.
"""
from __future__ import annotations

import argparse
import ast
import os
import sys

RULES = {
    "FL001": "pallas pad computation must be guarded: "
             "`pad = (-rows) % block if block else 0`",
    "FL002": "bare isinstance(x, int) in an indexing-path function "
             "(bool leaks into the int path)",
    "FL003": "host numpy call inside an ops/ kernel-reachable body "
             "(float0 cotangents exempt)",
    "FL004": "registered op name missing from OPS_COVERAGE.md",
    "FL005": "ad-hoc time.time()/perf_counter() in an ops/ kernel body "
             "(bypasses the telemetry API)",
    "FL006": "silent `except Exception: pass` swallow (log/classify via "
             "fault.retry.suppressed, or `# noqa: FL006` with a reason)",
    "FL007": "serve/ TPU-serving hazard: jax.jit without donate_argnums "
             "(KV cache copied every step) or if/while branching on a "
             "device value (.any()/.all()/.item() host sync in the step "
             "loop)",
    "FL008": "span hygiene: start_span() must be a `with` item (use "
             "open_span() for explicit lifecycle), and no span creation "
             "inside ops/ kernel-reachable bodies (jit-traced code)",
    "FL009": "serve/ paged-KV hazard: host iteration over a device pool "
             "value, or jnp.take/.at[] scatter with host-built "
             "dynamic-shape indices (recompile per index shape) — use "
             "static-shape page-table arrays",
    "FL010": "parallel//serve/ sharding hygiene: PartitionSpec/"
             "NamedSharding axis-name string not drawn from any mesh in "
             "scope (make_mesh/Mesh axis names or *axis* param "
             "defaults), or with_sharding_constraint with a bare "
             "PartitionSpec outside a mesh_scope/Mesh context",
    "FL011": "serve/ queue bounds: unbounded deque()/Queue() without "
             "maxlen/maxsize (OOM-by-queue under load) or a "
             "zero-argument blocking .get()/.wait()/.join()/.acquire() "
             "(wedges the step loop) — bound it, pass a timeout, or "
             "`# noqa: FL011` with the admission-bound justification",
    "FL012": "direct jax.jit( in an incubator_mxnet_tpu/ module outside "
             "the registered compile-observatory entry points — the "
             "program family silently bypasses the compile ledger and "
             "recompile forensics; route through telemetry.compiles."
             "ledgered_jit/instrument_jit, or `# noqa: FL012` with a "
             "comment saying why the program can't be ledgered",
    "FL013": "serve/ KV-pool aliasing: jax.jit whose wrapped function "
             "takes a pool parameter (pk/pv/sk/sv, *pool*, kv*) not "
             "covered by donate_argnums (XLA copies the whole pool "
             "every step — decode cost O(n_pages) instead of O(active "
             "tokens)), or lax.scan carrying a pool in xs (re-stacks "
             "the pool per step) — donate the pool / unroll the layer "
             "loop, or `# noqa: FL013` with a reason",
    "FL014": "parallel//serve/ collective hygiene: raw lax collective "
             "outside parallel/collectives.py bypasses the fleet "
             "census (route through the wrappers), and ad-hoc time.* "
             "around dist collectives double-counts peer skew (the "
             "profiler owns mx_collective_seconds); `# noqa: FL014` "
             "with a reason where a raw primitive is required",
    "FL015": "fault//parallel/ membership-epoch guard: dist collective "
             "call without a generation= argument — a rank holding a "
             "stale epoch after an elastic transition hangs the fleet "
             "instead of raising StaleGenerationError; thread the "
             "generation observed at the drained step boundary, or "
             "`# noqa: FL015` with a reason",
    "FL016": "registered metric series name (literal mx_* first arg of "
             ".counter/.gauge/.histogram/.register_pull_gauge) missing "
             "from TELEMETRY.md — document the series (what it "
             "measures, labels, who reads it), or `# noqa: FL016` with "
             "a reason",
    "FL017": "serve/ placement-spec provenance: device_put/"
             "with_sharding_constraint handed a bare PartitionSpec/"
             "NamedSharding literal — serving placements must flow "
             "from the ServeLayout rule table (the audited source of "
             "truth shardcheck pre-flights), not inline spec opinions; "
             "derive via layout.sharding/spec_for/pool_spec, or "
             "`# noqa: FL017` with a reason",
    "FL018": "serve//fault//telemetry/ lock provenance: raw "
             "threading.Lock()/RLock()/Condition() construction — "
             "invisible to the racecheck runtime witness (RC005) and "
             "the mx_lock_* contention series; use telemetry.locks."
             "tracked_lock(name) (telemetry/locks.py itself exempt), "
             "or `# noqa: FL018` with a reason",
    "FL019": "telemetry//serve/ wall-clock duration: subtracting "
             "time.time() readings — NTP slew makes the delta "
             "non-monotonic, corrupting latency histograms and the "
             "capacity cost ledger; use time.perf_counter() (or "
             "time.monotonic()) for durations, keep time.time() for "
             "absolute timestamps, or `# noqa: FL019` with a reason",
    "FL020": "serve/ replica-set choke point: mutating a `.replicas` "
             "list outside serve/elastic.py — races the elastic "
             "controller's tick (reap/drain/heal/advice mutate under "
             "ONE tracked_lock) and skips the warm-before-dispatch "
             "and page-funding gates; route through "
             "ReplicaSetController (scale_up/scale_down), keep "
             "construction-time assignment in __init__, or "
             "`# noqa: FL020` with a reason",
    "FL021": "serve/ cross-replica pool access outside the "
             "serve/disagg.py migration choke point: touching another "
             "replica's pool leaves (`.slots._pools/_draft_pools`), page "
             "copies (`.slots.copy_pages_out/copy_pages_in`), allocator "
             "refcounts (`.slots.allocator.alloc/incref/decref`) or "
             "prefix-cache fills (`.slots.prefix_cache.register`) "
             "bypasses the migration plane's rollback + byte accounting "
             "and can leak or double-free pages; route through "
             "serve.disagg (an engine's OWN `self.slots...` is exempt), "
             "or `# noqa: FL021` with a reason",
    "FL022": "serve/ ad-hoc perf_counter duration accounting outside "
             "the telemetry charge choke points: a time.perf_counter() "
             "delta computed in serve/ but not handed to a "
             "capacity.*/anatomy.* charge call is wall time the cost "
             "ledger and the request-anatomy sum-to-wall invariant "
             "never see; pass the reading into the charge call "
             "(telemetry/capacity.py + telemetry/anatomy.py own the "
             "subtraction), or `# noqa: FL022` with a reason",
}

_INDEXING_NAME_PARTS = ("getitem", "setitem", "index", "slice")


class LintFinding:
    __slots__ = ("path", "line", "rule", "message")

    def __init__(self, path, line, rule, message):
        self.path = path
        self.line = line
        self.rule = rule
        self.message = message

    def __repr__(self):
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


# ---------------------------------------------------------------------------
# FL001 — pad guard
# ---------------------------------------------------------------------------

def _is_neg_mod(node):
    """Matches `(-X) % Y`."""
    return (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod)
            and isinstance(node.left, ast.UnaryOp)
            and isinstance(node.left.op, ast.USub))


def _check_pad_guard(tree, path, findings):
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        value = node.value
        if value is None:
            continue
        if isinstance(value, ast.IfExp):
            continue                      # guarded form: `... if block else 0`
        if _is_neg_mod(value):
            findings.append(LintFinding(
                path, value.lineno, "FL001",
                f"unguarded `{ast.unparse(value)}`: ZeroDivisionError when "
                "the block size is 0 (empty input); write "
                f"`{ast.unparse(value)} if "
                f"{ast.unparse(value.right)} else 0` and early-return the "
                "empty result (see ops/layer_norm.py)"))


# ---------------------------------------------------------------------------
# FL002 — isinstance-int bool leak in indexing paths
# ---------------------------------------------------------------------------

def _isinstance_target_types(call):
    """For `isinstance(x, T)` return the set of plain type names tested."""
    names = set()
    t = call.args[1]
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    for e in elts:
        if isinstance(e, ast.Name):
            names.add(e.id)
    return names


def _check_bool_leak(tree, path, findings):
    seen = set()
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        lowered = fn.name.lower()
        if not any(part in lowered for part in _INDEXING_NAME_PARTS):
            continue
        int_checks = []      # (call node, var source)
        bool_checked = set()  # var sources with an isinstance(x, bool) test
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "isinstance"
                    and len(node.args) == 2):
                continue
            var = ast.unparse(node.args[0])
            types = _isinstance_target_types(node)
            if "bool" in types:
                bool_checked.add(var)
            elif "int" in types:
                int_checks.append((node, var))
        for node, var in int_checks:
            if var in bool_checked:
                continue
            key = (path, node.lineno)
            if key in seen:
                continue
            seen.add(key)
            findings.append(LintFinding(
                path, node.lineno, "FL002",
                f"`isinstance({var}, int)` in indexing path `{fn.name}`: "
                "bool is a subclass of int, so True/False (numpy new-axis "
                "indices) leak into the integer path — exclude bool "
                "explicitly or test numbers.Integral with a bool guard"))


# ---------------------------------------------------------------------------
# FL003 — host numpy inside ops/ kernel-reachable bodies
# ---------------------------------------------------------------------------

def _numpy_aliases(tree):
    aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    aliases.add(a.asname or "numpy")
    return aliases


def _mentions_float0(node):
    return any(isinstance(n, ast.Attribute) and n.attr == "float0"
               for n in ast.walk(node))


def _check_host_numpy(tree, path, findings):
    norm = path.replace(os.sep, "/")
    if "/ops/" not in norm:
        return
    aliases = _numpy_aliases(tree)
    if not aliases:
        return
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in aliases
                    and not _mentions_float0(node)):
                findings.append(LintFinding(
                    path, node.lineno, "FL003",
                    f"host numpy call `{ast.unparse(node.func)}` inside "
                    f"`{fn.name}` in an ops/ module: traced code would "
                    "constant-fold on host (or fail); use jnp, or keep "
                    "host math out of kernel-reachable bodies"))


# ---------------------------------------------------------------------------
# FL005 — ad-hoc wall clocks inside ops/ kernel bodies
# ---------------------------------------------------------------------------

_TIMING_FUNCS = ("time", "perf_counter", "perf_counter_ns", "monotonic",
                 "monotonic_ns", "process_time")


def _time_aliases(tree):
    """Names the `time` module is bound to (`import time [as t]`) plus
    direct `from time import perf_counter [as pc]` bindings."""
    mod_aliases, fn_aliases = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "time":
                    mod_aliases.add(a.asname or "time")
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name in _TIMING_FUNCS:
                    fn_aliases.add(a.asname or a.name)
    return mod_aliases, fn_aliases


def _check_adhoc_timing(tree, path, findings):
    norm = path.replace(os.sep, "/")
    if "/ops/" not in norm:
        return
    mod_aliases, fn_aliases = _time_aliases(tree)
    if not mod_aliases and not fn_aliases:
        return
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            hit = None
            if (isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in mod_aliases
                    and node.func.attr in _TIMING_FUNCS):
                hit = f"{node.func.value.id}.{node.func.attr}"
            elif (isinstance(node.func, ast.Name)
                    and node.func.id in fn_aliases):
                hit = node.func.id
            if hit:
                findings.append(LintFinding(
                    path, node.lineno, "FL005",
                    f"ad-hoc `{hit}()` inside `{fn.name}` in an ops/ "
                    "module: kernel-local wall clocks measure dispatch "
                    "(async backend) and create metrics nobody owns — "
                    "use telemetry.registry / profiler.Scope instead"))


# ---------------------------------------------------------------------------
# FL006 — silent broad-exception swallows
# ---------------------------------------------------------------------------

_BROAD_EXC_NAMES = ("Exception", "BaseException")


def _is_broad_handler(handler):
    t = handler.type
    if t is None:                               # bare `except:`
        return True
    if isinstance(t, ast.Name):
        return t.id in _BROAD_EXC_NAMES
    if isinstance(t, ast.Tuple):
        return any(isinstance(e, ast.Name) and e.id in _BROAD_EXC_NAMES
                   for e in t.elts)
    return False


def _is_silent_body(body):
    """True when the handler body cannot possibly record the error: only
    pass/continue/break/... statements (a docstring-only body counts)."""
    return all(
        isinstance(s, (ast.Pass, ast.Continue, ast.Break))
        or (isinstance(s, ast.Expr) and isinstance(s.value, ast.Constant))
        for s in body)


def _check_silent_swallow(tree, path, findings, src_lines):
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad_handler(node) or not _is_silent_body(node.body):
            continue
        last = getattr(node.body[-1], "end_lineno", node.body[-1].lineno)
        span = src_lines[node.lineno - 1:last] if src_lines else []
        if any("noqa: FL006" in ln for ln in span):
            continue
        caught = "bare except" if node.type is None \
            else f"except {ast.unparse(node.type)}"
        findings.append(LintFinding(
            path, node.lineno, "FL006",
            f"silent `{caught}` swallow: the error vanishes without a "
            "trace — log+classify it (fault.retry.suppressed) or mark "
            "the handler `# noqa: FL006` with a justifying comment"))


# ---------------------------------------------------------------------------
# FL007 — serving-loop TPU hazards (serve/ modules only)
# ---------------------------------------------------------------------------

_DEVICE_SYNC_METHODS = ("any", "all", "item", "block_until_ready")


def _is_jit_call(node):
    """Matches `jax.jit(...)` / `<alias>.jit(...)` / bare `jit(...)`."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "jit":
        return True
    return isinstance(f, ast.Name) and f.id == "jit"


def _check_serve_hazards(tree, path, findings):
    norm = path.replace(os.sep, "/")
    if "/serve/" not in norm:
        return
    for node in ast.walk(tree):
        # (a) undonated jit: the serving programs thread the persistent
        # KV cache through every call — without donation XLA copies the
        # whole cache each step instead of aliasing it in place
        if _is_jit_call(node):
            kw = {k.arg for k in node.keywords}
            if not kw & {"donate_argnums", "donate_argnames"}:
                findings.append(LintFinding(
                    path, node.lineno, "FL007",
                    "`jax.jit` without donate_argnums in a serve/ module: "
                    "the persistent KV-cache buffers must be donated or "
                    "XLA copies them whole on every serving step"))
        # (b) device-value branching: .any()/.all()/.item() in an
        # if/while condition forces a host sync inside the step loop
        if isinstance(node, (ast.If, ast.While)):
            for sub in ast.walk(node.test):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _DEVICE_SYNC_METHODS):
                    findings.append(LintFinding(
                        path, sub.lineno, "FL007",
                        f"branching on `.{sub.func.attr}()` in a serve/ "
                        "step path: data-dependent Python control flow on "
                        "a device value stalls the loop on a host sync — "
                        "keep slot state host-side (numpy) and fetch "
                        "device results once per step"))


# ---------------------------------------------------------------------------
# FL011 — serving-queue bounds (serve/ modules only)
# ---------------------------------------------------------------------------

_UNBOUNDED_QUEUE_CTORS = ("Queue", "LifoQueue", "PriorityQueue",
                          "SimpleQueue")
_BLOCKING_WAIT_METHODS = ("get", "wait", "join", "acquire")


def _ctor_name(func):
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _check_gateway_bounds(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if "/serve/" not in norm:
        return

    def _noqa(node):
        last = getattr(node, "end_lineno", node.lineno)
        span = src_lines[node.lineno - 1:last] if src_lines else []
        return any("noqa: FL011" in ln for ln in span)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _ctor_name(node.func)
        kwargs = {k.arg for k in node.keywords}
        # (a) unbounded queue construction: a queue nothing bounds is an
        # OOM waiting for a load spike — the serving contract is a LOUD
        # admission bound (QueueFull) or an explicit maxlen/maxsize
        if name == "deque":
            # deque(iterable, maxlen): 2nd positional arg IS the bound
            if len(node.args) < 2 and "maxlen" not in kwargs \
                    and not _noqa(node):
                findings.append(LintFinding(
                    path, node.lineno, "FL011",
                    "unbounded deque() in a serve/ module: bound it with "
                    "maxlen=, or `# noqa: FL011` with a comment naming "
                    "the admission check that bounds it"))
        elif name in _UNBOUNDED_QUEUE_CTORS:
            # Queue(maxsize): 1st positional arg is the bound;
            # SimpleQueue can never be bounded, so it always needs the
            # justifying noqa
            if (name == "SimpleQueue"
                    or (not node.args and "maxsize" not in kwargs)) \
                    and not _noqa(node):
                findings.append(LintFinding(
                    path, node.lineno, "FL011",
                    f"unbounded {name}() in a serve/ module: bound it "
                    "with maxsize=, or `# noqa: FL011` with a comment "
                    "naming what bounds it"))
        # (b) forever-blocking waits: when the producer thread dies, a
        # timeout-less wait wedges the caller instead of failing loudly
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr in _BLOCKING_WAIT_METHODS \
                and not node.args and not node.keywords \
                and not _noqa(node):
            findings.append(LintFinding(
                path, node.lineno, "FL011",
                f"zero-argument blocking `.{node.func.attr}()` in a "
                "serve/ module waits forever if the producer dies — "
                "pass a timeout and handle expiry loudly"))


# ---------------------------------------------------------------------------
# FL012 — compile-observatory coverage (incubator_mxnet_tpu/ modules)
# ---------------------------------------------------------------------------

# Mirror of telemetry.compiles.OBSERVATORY_ENTRY_POINTS (path suffixes).
# The lint must not import the framework, so the list is duplicated here —
# keep the two in sync (compiles.py carries the matching comment).
_OBSERVATORY_ENTRY_POINTS = (
    "ndarray/ndarray.py",
    "gluon/block.py",
    "serve/engine.py",
    "parallel/sharded.py",
    "telemetry/compiles.py",
)


def _check_observatory_coverage(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if "incubator_mxnet_tpu/" not in norm:
        return
    if norm.endswith(_OBSERVATORY_ENTRY_POINTS):
        return

    def _noqa(node):
        last = getattr(node, "end_lineno", node.lineno)
        span = src_lines[node.lineno - 1:last] if src_lines else []
        return any("noqa: FL012" in ln for ln in span)

    for node in ast.walk(tree):
        if _is_jit_call(node) and not _noqa(node):
            findings.append(LintFinding(
                path, node.lineno, "FL012",
                "direct `jax.jit(` outside the registered observatory "
                "entry points: this program family bypasses the compile "
                "ledger/recompile forensics — wrap with telemetry."
                "compiles.ledgered_jit(fn, family=...) (or "
                "instrument_jit), or `# noqa: FL012` with a comment "
                "saying why it can't be ledgered"))


# ---------------------------------------------------------------------------
# FL013 — KV-pool aliasing (serve/ modules only)
# ---------------------------------------------------------------------------

_POOL_PARAM_EXACT = ("pk", "pv", "sk", "sv")


def _is_pool_name(name):
    if not isinstance(name, str):
        return False
    low = name.lower()
    return (low in _POOL_PARAM_EXACT or "pool" in low
            or low.startswith("kv"))


def _donated_positions(call):
    """The literal donate_argnums of a jit call, or None when absent or
    not statically evaluable (a variable — give the benefit of the
    doubt rather than false-positive)."""
    for k in call.keywords:
        if k.arg != "donate_argnums":
            continue
        v = k.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return {v.value}
        if isinstance(v, (ast.Tuple, ast.List)):
            out = set()
            for el in v.elts:
                if not (isinstance(el, ast.Constant)
                        and isinstance(el.value, int)):
                    return None
                out.add(el.value)
            return out
        return None
    return set()


def _check_pool_aliasing(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if "/serve/" not in norm:
        return

    def _noqa(node):
        last = getattr(node, "end_lineno", node.lineno)
        span = src_lines[node.lineno - 1:last] if src_lines else []
        return any("noqa: FL013" in ln for ln in span)

    defs = [n for n in ast.walk(tree) if isinstance(
        n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def _resolve(name, before_line):
        """The nearest preceding def with this name (the one a
        `jax.jit(fn, ...)` call site closes over)."""
        best = None
        for d in defs:
            if d.name == name and d.lineno < before_line:
                if best is None or d.lineno > best.lineno:
                    best = d
        return best

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        # (a) pool parameter outside the donation map: the input can't
        # alias the output, so every call rewrites the whole pool
        if _is_jit_call(node) and node.args \
                and isinstance(node.args[0], ast.Name):
            fn = _resolve(node.args[0].id, node.lineno)
            donated = _donated_positions(node)
            if fn is not None and donated is not None and not _noqa(node):
                params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
                named = {k.arg for k in node.keywords
                         if k.arg == "donate_argnames"}
                for i, p in enumerate(params):
                    if _is_pool_name(p) and i not in donated and not named:
                        findings.append(LintFinding(
                            path, node.lineno, "FL013",
                            f"jitted `{fn.name}` takes KV-pool parameter "
                            f"`{p}` (position {i}) outside donate_argnums"
                            f"={sorted(donated)}: an undonated pool can't "
                            "alias the output, so XLA copies the whole "
                            "pool every step — donate it, or `# noqa: "
                            "FL013` with a reason"))
        # (b) scanning over a stacked pool: the carry re-stacks the
        # whole pool on every layer step (the pre-per-layer layout bug)
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "scan":
            xs = node.args[2] if len(node.args) > 2 else None
            if xs is None:
                for k in node.keywords:
                    if k.arg == "xs":
                        xs = k.value
            if xs is not None and not _noqa(node):
                for sub in ast.walk(xs):
                    if isinstance(sub, ast.Name) and _is_pool_name(sub.id):
                        findings.append(LintFinding(
                            path, node.lineno, "FL013",
                            f"lax.scan carries pool `{sub.id}` in xs: "
                            "scanning over a stacked pool re-stacks the "
                            "whole buffer every step (O(n_pages) per "
                            "token) — unroll the layer loop over "
                            "per-layer pools, or `# noqa: FL013` with a "
                            "reason"))
                        break


# ---------------------------------------------------------------------------
# FL010 — sharding-spec hygiene (parallel/ and serve/ modules)

_SPEC_CTOR_NAMES = ("PartitionSpec", "NamedSharding")


def _spec_ctor_aliases(tree):
    """Local names bound to PartitionSpec / NamedSharding (imports and
    `P = jax.sharding.PartitionSpec`-style assignments)."""
    aliases = set(_SPEC_CTOR_NAMES)
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name in _SPEC_CTOR_NAMES:
                    aliases.add(a.asname or a.name)
        elif isinstance(node, ast.Assign):
            v = node.value
            if (isinstance(v, ast.Attribute)
                    and v.attr in _SPEC_CTOR_NAMES):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        aliases.add(t.id)
    return aliases


def _call_name(node):
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    if isinstance(node.func, ast.Name):
        return node.func.id
    return None


def _axis_universe(tree):
    """Every axis name a mesh in this file could carry: make_mesh dict
    keys / (axis, size) pairs, Mesh(..., axis_names) strings, and string
    defaults of parameters whose name mentions 'axis'."""
    axes = set()

    def add_strings(node):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                axes.add(sub.value)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _call_name(node)
            if name == "make_mesh" and node.args:
                add_strings(node.args[0])
            elif name == "Mesh":
                if len(node.args) >= 2:
                    add_strings(node.args[1])
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        add_strings(kw.value)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            a = node.args
            params = a.posonlyargs + a.args + a.kwonlyargs
            defaults = a.defaults + a.kw_defaults
            for arg, d in zip(params[len(params) - len(defaults):],
                              defaults):
                if (d is not None and "axis" in arg.arg
                        and isinstance(d, ast.Constant)
                        and isinstance(d.value, str)):
                    axes.add(d.value)
    return axes


def _mesh_context_ranges(tree):
    """(lineno, end_lineno) of every `with` whose context expression
    involves mesh_scope(...) or Mesh(...) — incl. conditional forms like
    `with (mesh_scope(m) if m else nullcontext()):`."""
    ranges = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for item in node.items:
            hit = any(isinstance(sub, ast.Call)
                      and _call_name(sub) in ("mesh_scope", "Mesh")
                      for sub in ast.walk(item.context_expr))
            if hit:
                ranges.append((node.lineno, node.end_lineno or node.lineno))
                break
    return ranges


def _check_sharding_hygiene(tree, path, findings):
    norm = path.replace(os.sep, "/")
    if "/parallel/" not in norm and "/serve/" not in norm:
        return
    aliases = _spec_ctor_aliases(tree)
    axes = _axis_universe(tree)
    mesh_ranges = _mesh_context_ranges(tree)

    def spec_ctor(node):
        return (isinstance(node, ast.Call)
                and (_call_name(node) in aliases
                     or _call_name(node) in _SPEC_CTOR_NAMES))

    def literal_axes(call):
        """String constants in a spec-constructor call, skipping nested
        spec constructors (they are visited on their own)."""
        out = []
        stack = list(call.args) + [kw.value for kw in call.keywords]
        while stack:
            sub = stack.pop()
            if spec_ctor(sub):
                continue
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                out.append(sub)
            else:
                stack.extend(ast.iter_child_nodes(sub))
        return out

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if spec_ctor(node):
            for const in literal_axes(node):
                if const.value not in axes:
                    findings.append(LintFinding(
                        path, const.lineno, "FL010",
                        f"axis name {const.value!r} in a "
                        f"{_call_name(node)} literal is not drawn from "
                        "any mesh in scope in this file (make_mesh/Mesh "
                        "axis names or an *axis* parameter default) — a "
                        "typo'd axis silently degrades the layout to "
                        "replicated (shardcheck SC003 is the runtime "
                        "twin)"))
        elif _call_name(node) == "with_sharding_constraint":
            spec_arg = node.args[1] if len(node.args) >= 2 else None
            if spec_arg is None or not spec_ctor(spec_arg):
                continue
            if _call_name(spec_arg) == "NamedSharding":
                continue          # carries its own mesh
            in_scope = any(lo <= node.lineno <= hi
                           for lo, hi in mesh_ranges)
            if not in_scope:
                findings.append(LintFinding(
                    path, node.lineno, "FL010",
                    "with_sharding_constraint with a bare PartitionSpec "
                    "outside any mesh_scope/Mesh context manager: "
                    "without an active mesh the constraint throws or "
                    "silently no-ops — pass a NamedSharding or move the "
                    "call under the mesh scope"))


# ---------------------------------------------------------------------------
# FL017 — serve/ placement-spec provenance
# ---------------------------------------------------------------------------

_PLACEMENT_CALLS = ("device_put", "with_sharding_constraint")


def _check_placement_provenance(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if "/serve/" not in norm:
        return
    aliases = _spec_ctor_aliases(tree)

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL017" in line

    def spec_ctor(node):
        return isinstance(node, ast.Call) and _call_name(node) in aliases

    for node in ast.walk(tree):
        if (not isinstance(node, ast.Call)
                or _call_name(node) not in _PLACEMENT_CALLS):
            continue
        # the sharding operand: 2nd positional, or the keyword forms
        # jax uses (device_put(x, device=...), wsc(x, shardings=...))
        cand = node.args[1] if len(node.args) >= 2 else None
        if cand is None:
            for kw in node.keywords:
                if kw.arg in ("device", "shardings", "sharding"):
                    cand = kw.value
                    break
        if cand is None or not spec_ctor(cand) or noqa(node.lineno):
            continue
        findings.append(LintFinding(
            path, node.lineno, "FL017",
            f"`{_call_name(node)}` handed a bare `{_call_name(cand)}` "
            "literal — serve/ placements must derive their specs from "
            "the ServeLayout rule table (layout.sharding/spec_for/"
            "pool_spec), the one layout shardcheck pre-flights; an "
            "inline spec is a second unaudited layout opinion, or "
            "`# noqa: FL017` with a reason"))


# ---------------------------------------------------------------------------
# FL018 — tracked-lock provenance (serve/ + fault/ + telemetry/ bodies)
# ---------------------------------------------------------------------------

_RAW_LOCK_CTORS = ("Lock", "RLock", "Condition")


def _check_tracked_locks(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if not any(d in norm for d in ("/serve/", "/fault/", "/telemetry/")):
        return
    if norm.endswith("telemetry/locks.py"):
        return  # the registry builds the tracked wrappers out of raw locks

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL018" in line

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute):
            if not (isinstance(fn.value, ast.Name)
                    and fn.value.id == "threading"
                    and fn.attr in _RAW_LOCK_CTORS):
                continue
            name = f"threading.{fn.attr}"
        elif isinstance(fn, ast.Name) and fn.id in _RAW_LOCK_CTORS:
            name = fn.id
        else:
            continue
        if noqa(node.lineno):
            continue
        findings.append(LintFinding(
            path, node.lineno, "FL018",
            f"raw `{name}()` in a control-plane module — invisible to "
            "the racecheck runtime witness (no lock-order edges, no "
            "RC005 inversion detection) and to the mx_lock_wait/"
            "held_seconds contention series; construct it via "
            "telemetry.locks.tracked_lock(name), or `# noqa: FL018` "
            "with a reason"))


# ---------------------------------------------------------------------------
# FL020 — replica-set choke point (serve/ modules, except the choke point)
# ---------------------------------------------------------------------------

_LIST_MUTATORS = ("append", "remove", "pop", "insert", "extend", "clear",
                  "sort", "reverse")


def _check_replica_choke_point(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if "/serve/" not in norm:
        return
    if norm.endswith("serve/elastic.py"):
        return  # THE choke point: its mutations hold the tracked lock

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL020" in line

    # construction-time `self.replicas = ...` in an __init__ body is the
    # sanctioned exception (the object is not yet published to a router)
    init_assigns = set()
    for fn in ast.walk(tree):
        if isinstance(fn, ast.FunctionDef) and fn.name == "__init__":
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.Assign, ast.AugAssign)):
                    init_assigns.add(id(sub))

    def is_replicas_attr(node):
        return isinstance(node, ast.Attribute) and node.attr == "replicas"

    for node in ast.walk(tree):
        what = None
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _LIST_MUTATORS \
                and is_replicas_attr(node.func.value):
            what = f".replicas.{node.func.attr}(...)"
        elif isinstance(node, ast.Assign) and id(node) not in init_assigns \
                and any(is_replicas_attr(t) for t in node.targets):
            what = ".replicas = ..."
        elif isinstance(node, ast.AugAssign) \
                and id(node) not in init_assigns \
                and is_replicas_attr(node.target):
            what = ".replicas += ..."
        if what is None or noqa(node.lineno):
            continue
        findings.append(LintFinding(
            path, node.lineno, "FL020",
            f"`{what}` outside serve/elastic.py — replica-set mutations "
            "must go through ReplicaSetController's tracked_lock choke "
            "point (scale_up/scale_down/tick): anywhere else races the "
            "controller and skips the warm-before-dispatch and "
            "page-funding gates, or `# noqa: FL020` with a reason"))


# ---------------------------------------------------------------------------
# FL021 — migration choke point (serve/ modules, except serve/disagg.py)
# ---------------------------------------------------------------------------

_MIGRATION_POOL_LEAVES = ("_pools", "_draft_pools")
_MIGRATION_COPY_CALLS = ("copy_pages_out", "copy_pages_in")
_MIGRATION_REFCOUNT_CALLS = ("alloc", "incref", "decref")


def _check_migration_choke_point(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if "/serve/" not in norm:
        return
    if norm.endswith("serve/disagg.py"):
        return  # THE migration choke point: rollback + byte accounting

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL021" in line

    def base_is_self(node):
        # an engine/scheduler touching its OWN pool (`self.slots...`)
        # is the sanctioned intra-replica path
        return isinstance(node, ast.Name) and node.id == "self"

    def slots_attr(node):
        return isinstance(node, ast.Attribute) and node.attr == "slots"

    for node in ast.walk(tree):
        what = None
        if isinstance(node, ast.Attribute) \
                and node.attr in _MIGRATION_POOL_LEAVES \
                and slots_attr(node.value) \
                and not base_is_self(node.value.value):
            what = f".slots.{node.attr}"
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute):
            f = node.func
            if f.attr in _MIGRATION_COPY_CALLS \
                    and slots_attr(f.value) \
                    and not base_is_self(f.value.value):
                what = f".slots.{f.attr}(...)"
            elif f.attr in _MIGRATION_REFCOUNT_CALLS \
                    and isinstance(f.value, ast.Attribute) \
                    and f.value.attr == "allocator" \
                    and slots_attr(f.value.value) \
                    and not base_is_self(f.value.value.value):
                what = f".slots.allocator.{f.attr}(...)"
            elif f.attr == "register" \
                    and isinstance(f.value, ast.Attribute) \
                    and f.value.attr == "prefix_cache" \
                    and slots_attr(f.value.value) \
                    and not base_is_self(f.value.value.value):
                what = ".slots.prefix_cache.register(...)"
        if what is None or noqa(node.lineno):
            continue
        findings.append(LintFinding(
            path, node.lineno, "FL021",
            f"`{what}` outside serve/disagg.py — cross-replica pool "
            "access must go through the migration choke point (it owns "
            "the alloc-copy-register-adopt-decref ordering, mid-copy "
            "rollback and mx_serve_page_migration_* accounting; a pool "
            "touch anywhere else can leak or double-free pages), or "
            "`# noqa: FL021` with a reason"))


# ---------------------------------------------------------------------------
# FL019 — wall-clock durations (telemetry/ + serve/ modules)
# ---------------------------------------------------------------------------

def _is_time_time_call(node):
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "time"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time")


def _check_wallclock_durations(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if not any(d in norm for d in ("/serve/", "/telemetry/")):
        return

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL019" in line

    def flag(node, what):
        if noqa(node.lineno):
            return
        findings.append(LintFinding(
            path, node.lineno, "FL019",
            f"duration from wall-clock time.time() ({what}) — NTP "
            "slew/step makes the delta non-monotonic, silently "
            "corrupting latency/cost series; use time.perf_counter() "
            "(or time.monotonic()), or `# noqa: FL019` with a reason"))

    # pass 1: direct `time.time() - x` / `x - time.time()`
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub) \
                and (_is_time_time_call(node.left)
                     or _is_time_time_call(node.right)):
            flag(node, "direct subtraction of a time.time() reading")

    # pass 2: per function, names assigned from time.time() later used
    # as a Sub operand in the same function body
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        wall_names = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and _is_time_time_call(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        wall_names.add(tgt.id)
        if not wall_names:
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.BinOp) \
                    and isinstance(node.op, ast.Sub):
                for side in (node.left, node.right):
                    if isinstance(side, ast.Name) \
                            and side.id in wall_names:
                        flag(node, f"`{side.id}` was assigned from "
                                   "time.time() in this function")
                        break


# ---------------------------------------------------------------------------
# FL022 — serve/ duration-accounting choke point
# ---------------------------------------------------------------------------

def _is_perf_counter_call(node):
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "perf_counter"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time")


def _charge_call_base(node):
    """The leading dotted name of a Call's func ('capacity' for
    `capacity.split_device_seconds(...)`), or None."""
    func = node.func if isinstance(node, ast.Call) else None
    while isinstance(func, ast.Attribute):
        func = func.value
    return func.id if isinstance(func, ast.Name) else None


def _check_duration_choke_point(tree, path, findings, src_lines):
    """FL022: a perf_counter delta computed in serve/ must be an
    argument of a `capacity.*`/`anatomy.*` charge call (directly, or
    via a name whose value feeds one) — anywhere else it is duration
    accounting the telemetry ledgers never see. The telemetry modules
    that OWN the choke points are exempt."""
    norm = path.replace(os.sep, "/")
    if "/serve/" not in norm:
        return
    if norm.endswith(("telemetry/anatomy.py", "telemetry/capacity.py")):
        return

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL022" in line

    def flag(node, what):
        if noqa(node.lineno):
            return
        findings.append(LintFinding(
            path, node.lineno, "FL022",
            f"ad-hoc perf_counter duration accounting ({what}) — wall "
            "time the capacity ledger and the request-anatomy "
            "sum-to-wall invariant never see; hand the readings to a "
            "capacity.*/anatomy.* charge call (the telemetry module "
            "owns the subtraction), or `# noqa: FL022` with a reason"))

    # nodes living inside the args of a charge call are sanctioned
    sanctioned_ids = set()
    for node in ast.walk(tree):
        if _charge_call_base(node) in ("capacity", "anatomy"):
            for arg in list(node.args) + [k.value for k in node.keywords]:
                for sub in ast.walk(arg):
                    sanctioned_ids.add(id(sub))

    # pass 1: direct `time.perf_counter() - x` subtraction
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub) \
                and (_is_perf_counter_call(node.left)
                     or _is_perf_counter_call(node.right)) \
                and id(node) not in sanctioned_ids:
            flag(node, "direct subtraction of a time.perf_counter() "
                       "reading outside a charge call")

    # pass 2: per function — Subs over names read from perf_counter,
    # unless the delta's own name feeds a charge call in the function
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        perf_names = set()
        charge_fed_names = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and _is_perf_counter_call(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        perf_names.add(tgt.id)
            if _charge_call_base(node) in ("capacity", "anatomy"):
                args = list(node.args) + [k.value for k in node.keywords]
                for arg in args:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Name):
                            charge_fed_names.add(sub.id)
        if not perf_names:
            continue
        # `dt = t - last` is fine when `dt` feeds a charge call in the
        # same function — sanction the Subs inside such assignments
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            tgts = [t.id for t in node.targets
                    if isinstance(t, ast.Name)]
            if tgts and all(t in charge_fed_names for t in tgts):
                for sub in ast.walk(node.value):
                    sanctioned_ids.add(id(sub))
        for sub in ast.walk(fn):
            if not (isinstance(sub, ast.BinOp)
                    and isinstance(sub.op, ast.Sub)) \
                    or id(sub) in sanctioned_ids:
                continue
            if _is_perf_counter_call(sub.left) \
                    or _is_perf_counter_call(sub.right):
                continue               # pass 1 owns direct subtractions
            for side in (sub.left, sub.right):
                if isinstance(side, ast.Name) and side.id in perf_names:
                    flag(sub, f"`{side.id}` was read from time."
                              "perf_counter() and the delta never "
                              "reaches a charge call")
                    break


# ---------------------------------------------------------------------------
# FL009 — paged-serving hazards (serve/ modules only)
# ---------------------------------------------------------------------------

def _mentions_pool(node):
    """True when `node` (or a sub-expression) names a device pool —
    identifiers containing 'pool' are reserved for device-resident KV
    pool arrays in serve/ (host page lists are 'pages'/'free'/'table')."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and "pool" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "pool" in sub.attr.lower():
            return True
    return False


def _dynamic_shape_index(node):
    """True for index operands whose SHAPE is host-built and call-varying:
    list/tuple literals with non-constant elements, comprehensions, and
    list()/range() calls. Constant literals (e.g. `[0, 1]`) are static."""
    if isinstance(node, (ast.ListComp, ast.GeneratorExp)):
        return True
    if isinstance(node, (ast.List, ast.Tuple)):
        return any(not isinstance(e, ast.Constant) for e in node.elts)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("list", "range"):
        return True
    return False


def _take_index_arg(call):
    """The indices operand of a `*.take(...)` call, or None."""
    if len(call.args) >= 2:
        return call.args[1]
    for kw in call.keywords:
        if kw.arg == "indices":
            return kw.value
    return None


def _check_paged_hazards(tree, path, findings):
    norm = path.replace(os.sep, "/")
    if "/serve/" not in norm:
        return
    for node in ast.walk(tree):
        # (a) host-side iteration over a device pool value: one implicit
        # device->host sync per page instead of one gather per step
        if isinstance(node, (ast.For, ast.AsyncFor)) \
                and _mentions_pool(node.iter):
            findings.append(LintFinding(
                path, node.lineno, "FL009",
                f"`for` over `{ast.unparse(node.iter)}`: host iteration "
                "over per-page device values syncs per page — gather the "
                "slot view with one static-shape jnp.take over the page "
                "table instead"))
        # (b) take/scatter with host-built dynamic-shape indices: every
        # distinct length compiles a fresh program
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "take":
            idx = _take_index_arg(node)
            if idx is not None and _dynamic_shape_index(idx):
                findings.append(LintFinding(
                    path, node.lineno, "FL009",
                    f"`take` with host-built indices "
                    f"`{ast.unparse(idx)}`: the index SHAPE varies per "
                    "call, recompiling the program — pass a static-shape "
                    "index array (the page table)"))
        if isinstance(node, ast.Subscript) \
                and isinstance(node.value, ast.Attribute) \
                and node.value.attr == "at":
            sl = node.slice
            parts = sl.elts if isinstance(sl, ast.Tuple) else [sl]
            for part in parts:
                if _dynamic_shape_index(part):
                    findings.append(LintFinding(
                        path, part.lineno, "FL009",
                        f"`.at[...]` scatter with host-built index "
                        f"`{ast.unparse(part)}`: dynamic index shapes "
                        "recompile per call — scatter through a "
                        "static-shape page array"))


# ---------------------------------------------------------------------------
# FL008 — span-tracing hygiene
# ---------------------------------------------------------------------------

_SPAN_MAKERS = ("span", "open_span", "start_span")


def _tracing_aliases(tree):
    """Names bound to the tracing module (`from ..telemetry import
    tracing [as t]`, `import ...telemetry.tracing as t`) and to span
    constructors imported directly from it (`from ...tracing import
    span [as s]`)."""
    mod_aliases, fn_aliases = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.endswith("telemetry.tracing"):
                    mod_aliases.add(a.asname or a.name.split(".")[-1])
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module.endswith("telemetry") or node.module == "telemetry":
                for a in node.names:
                    if a.name == "tracing":
                        mod_aliases.add(a.asname or "tracing")
            if node.module.endswith("tracing"):
                for a in node.names:
                    if a.name in _SPAN_MAKERS:
                        fn_aliases.add(a.asname or a.name)
    return mod_aliases, fn_aliases


def _span_call_kind(node, mod_aliases, fn_aliases):
    """'start_span' / 'span' / 'open_span' when `node` creates a span
    through a known tracing binding (or any `X.start_span(...)` — the
    Tracer method is unambiguous by name); else None."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "start_span":       # Tracer.start_span: name is enough
            return "start_span"
        if (f.attr in _SPAN_MAKERS and isinstance(f.value, ast.Name)
                and f.value.id in mod_aliases):
            return f.attr
    elif isinstance(f, ast.Name) and f.id in fn_aliases:
        # direct-import form: resolve through the alias's original name
        return "start_span" if f.id == "start_span" else f.id
    return None


def _check_span_hygiene(tree, path, findings):
    mod_aliases, fn_aliases = _tracing_aliases(tree)
    with_items = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                with_items.add(id(item.context_expr))
    norm = path.replace(os.sep, "/")
    in_ops = "/ops/" in norm
    ops_body_calls = set()
    if in_ops:
        for fn in ast.walk(tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(fn):
                    ops_body_calls.add(id(sub))
    for node in ast.walk(tree):
        kind = _span_call_kind(node, mod_aliases, fn_aliases)
        if kind is None:
            continue
        # (a) start_span is the context-manager API: anywhere but a
        # `with` item, the span never closes (and pollutes the ambient
        # stack) — explicit lifecycles go through open_span()
        if kind == "start_span" and id(node) not in with_items:
            findings.append(LintFinding(
                path, node.lineno, "FL008",
                "`start_span(...)` outside a `with` item: the span is "
                "never closed and stays on the ambient stack — write "
                "`with ...start_span(...):`, or use open_span()/"
                "Span.close() for an explicit cross-scope lifecycle"))
        # (b) no span creation in kernel-reachable ops/ bodies (same
        # function-body scoping as FL003/FL005)
        if id(node) in ops_body_calls:
            findings.append(LintFinding(
                path, node.lineno, "FL008",
                f"span creation `{kind}(...)` inside a function body in "
                "an ops/ module: these bodies are jit-traced — a "
                "host-side span inside a traced body measures nothing "
                "and invites trace-time side effects; put spans at the "
                "call sites instead"))


# ---------------------------------------------------------------------------
# FL004 — registered op names present in OPS_COVERAGE.md
# ---------------------------------------------------------------------------

def collect_registered_ops(tree):
    """Statically-visible op registrations: literal first args of
    `register_op_meta(...)` plus the `_ELEMWISE_AND_FRIENDS` generation
    list (the two registration idioms of this codebase)."""
    names = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "register_op_meta" and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            names.add((node.args[0].value, node.args[0].lineno))
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "_ELEMWISE_AND_FRIENDS"
                and isinstance(node.value, (ast.List, ast.Tuple))):
            for e in node.value.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    names.add((e.value, e.lineno))
    return names


def _check_ops_ledger(tree, path, findings, coverage_text):
    if coverage_text is None:
        return
    for name, lineno in sorted(collect_registered_ops(tree)):
        if name not in coverage_text:
            findings.append(LintFinding(
                path, lineno, "FL004",
                f"registered op `{name}` is not recorded in "
                "OPS_COVERAGE.md — regenerate/extend the ledger so the "
                "audit trail tracks the code"))


# ---------------------------------------------------------------------------
# FL016 — telemetry series index (TELEMETRY.md)
# ---------------------------------------------------------------------------

_SERIES_FACTORIES = ("counter", "gauge", "histogram", "register_pull_gauge")


def collect_registered_series(tree):
    """Statically-visible metric registrations: literal ``mx_*`` first
    args of ``<x>.counter/gauge/histogram/register_pull_gauge(...)``
    calls (the registry's four factory idioms). The ``mx_`` prefix
    filter keeps unrelated ``.counter(...)`` methods (itertools-style
    helpers, third-party objects) out of scope."""
    names = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _SERIES_FACTORIES
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("mx_")):
            names.add((node.args[0].value, node.args[0].lineno))
    return names


def _check_series_doc(tree, path, findings, src_lines, telemetry_text):
    if telemetry_text is None:
        return
    norm = path.replace(os.sep, "/")
    if "incubator_mxnet_tpu/" not in norm:
        return
    if norm.endswith("telemetry/registry.py"):
        return      # the factory itself — docstring examples, not series

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL016" in line

    for name, lineno in sorted(collect_registered_series(tree)):
        if name in telemetry_text or noqa(lineno):
            continue
        findings.append(LintFinding(
            path, lineno, "FL016",
            f"metric series `{name}` is not documented in TELEMETRY.md "
            "— an undocumented series is a number nobody owns; add it "
            "to the series index (what it measures, labels, who reads "
            "it), or `# noqa: FL016` with a reason"))


# ---------------------------------------------------------------------------
# FL014 — collective hygiene (parallel/ and serve/ modules)
# ---------------------------------------------------------------------------

_COLLECTIVE_PRIMS = ("psum", "pmean", "pmax", "pmin", "all_gather",
                     "psum_scatter", "ppermute", "all_to_all", "pshuffle",
                     "pvary")
_DIST_OPS = ("allreduce", "broadcast", "barrier", "exchange_objs")


def _lax_aliases(tree):
    """Names bound to the lax module (`from jax import lax [as l]`,
    `import jax.lax as jl`), names bound to jax itself (for
    `jax.lax.psum`), and collective prims imported directly
    (`from jax.lax import psum [as p]`)."""
    lax_names, jax_names, prim_names = set(), set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax":
                    jax_names.add(a.asname or "jax")
                elif a.name == "jax.lax" and a.asname:
                    lax_names.add(a.asname)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for a in node.names:
                    if a.name == "lax":
                        lax_names.add(a.asname or "lax")
            elif node.module == "jax.lax":
                for a in node.names:
                    if a.name in _COLLECTIVE_PRIMS:
                        prim_names.add(a.asname or a.name)
    return lax_names, jax_names, prim_names


def _raw_collective_hit(node, lax_names, jax_names, prim_names):
    """`lax.psum` / `jax.lax.psum` / bare `psum` (imported from jax.lax)
    call → the dotted name, else None."""
    f = node.func
    if isinstance(f, ast.Name) and f.id in prim_names:
        return f.id
    if not (isinstance(f, ast.Attribute) and f.attr in _COLLECTIVE_PRIMS):
        return None
    v = f.value
    if isinstance(v, ast.Name) and v.id in lax_names:
        return f"{v.id}.{f.attr}"
    if (isinstance(v, ast.Attribute) and v.attr == "lax"
            and isinstance(v.value, ast.Name)
            and v.value.id in jax_names):
        return f"{v.value.id}.lax.{f.attr}"
    return None


def _check_collective_hygiene(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if "/parallel/" not in norm and "/serve/" not in norm:
        return
    if norm.endswith("parallel/collectives.py"):
        return      # the census point itself — raw prims live here

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL014" in line

    # (a) raw in-graph collectives bypassing the census wrappers
    lax_names, jax_names, prim_names = _lax_aliases(tree)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        hit = _raw_collective_hit(node, lax_names, jax_names, prim_names)
        if hit and not noqa(node.lineno):
            findings.append(LintFinding(
                path, node.lineno, "FL014",
                f"raw `{hit}` bypasses the fleet census — route through "
                "parallel/collectives.py (all_reduce/all_gather/"
                "reduce_scatter/broadcast/ring_permute/all_to_all/pvary) "
                "so payload bytes and call counts reach "
                "mx_collective_*, or `# noqa: FL014` with a reason"))

    # (b) ad-hoc wall clocks in functions that issue dist collectives
    mod_aliases, fn_aliases = _time_aliases(tree)
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        calls_dist = any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr in _DIST_OPS
            and isinstance(n.func.value, ast.Name)
            and n.func.value.id == "dist"
            for n in ast.walk(fn))
        if not calls_dist:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            hit = None
            if (isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in mod_aliases
                    and node.func.attr in _TIMING_FUNCS):
                hit = f"{node.func.value.id}.{node.func.attr}"
            elif (isinstance(node.func, ast.Name)
                    and node.func.id in fn_aliases):
                hit = node.func.id
            if hit and not noqa(node.lineno):
                findings.append(LintFinding(
                    path, node.lineno, "FL014",
                    f"ad-hoc `{hit}()` inside `{fn.name}`, which issues "
                    "dist collectives: a local stopwatch around a "
                    "blocking collective charges peer skew to this rank "
                    "— the fleet profiler owns mx_collective_seconds; "
                    "`# noqa: FL014` with a reason if this clock is not "
                    "timing the collective"))


# ---------------------------------------------------------------------------
# FL015 — membership-epoch guard (fault/ and parallel/ modules)
# ---------------------------------------------------------------------------

def _check_generation_guard(tree, path, findings, src_lines):
    norm = path.replace(os.sep, "/")
    if "/fault/" not in norm and "/parallel/" not in norm:
        return
    if norm.endswith("parallel/dist.py"):
        return      # the guard's own home: check_generation lives here

    def noqa(lineno):
        line = src_lines[lineno - 1] if lineno - 1 < len(src_lines) else ""
        return "noqa: FL015" in line

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _DIST_OPS
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "dist"):
            continue
        # generation= threaded, or a **kwargs splat we can't see through
        if any(kw.arg == "generation" or kw.arg is None
               for kw in node.keywords):
            continue
        if noqa(node.lineno):
            continue
        findings.append(LintFinding(
            path, node.lineno, "FL015",
            f"`dist.{node.func.attr}(...)` without `generation=`: after "
            "an elastic membership transition a stale rank must fail "
            "loudly (StaleGenerationError), not hang the fleet — thread "
            "the epoch observed at the drained step boundary "
            "(`dist.generation()`), or `# noqa: FL015` with a reason"))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def lint_source(src, path, coverage_text=None, telemetry_text=None):
    """Lint one source string; `path` is used for reporting and for the
    ops/-scoped rules. Returns a list of LintFinding."""
    findings = []
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        findings.append(LintFinding(path, e.lineno or 0, "FL000",
                                    f"syntax error: {e.msg}"))
        return findings
    _check_pad_guard(tree, path, findings)
    _check_bool_leak(tree, path, findings)
    _check_host_numpy(tree, path, findings)
    _check_adhoc_timing(tree, path, findings)
    _check_silent_swallow(tree, path, findings, src.splitlines())
    _check_serve_hazards(tree, path, findings)
    _check_gateway_bounds(tree, path, findings, src.splitlines())
    _check_observatory_coverage(tree, path, findings, src.splitlines())
    _check_pool_aliasing(tree, path, findings, src.splitlines())
    _check_sharding_hygiene(tree, path, findings)
    _check_placement_provenance(tree, path, findings, src.splitlines())
    _check_tracked_locks(tree, path, findings, src.splitlines())
    _check_replica_choke_point(tree, path, findings, src.splitlines())
    _check_migration_choke_point(tree, path, findings, src.splitlines())
    _check_wallclock_durations(tree, path, findings, src.splitlines())
    _check_duration_choke_point(tree, path, findings, src.splitlines())
    _check_paged_hazards(tree, path, findings)
    _check_span_hygiene(tree, path, findings)
    _check_collective_hygiene(tree, path, findings, src.splitlines())
    _check_generation_guard(tree, path, findings, src.splitlines())
    _check_ops_ledger(tree, path, findings, coverage_text)
    _check_series_doc(tree, path, findings, src.splitlines(),
                      telemetry_text)
    return findings


def lint_file(path, coverage_text=None, telemetry_text=None):
    with open(path, encoding="utf-8") as f:
        return lint_source(f.read(), path, coverage_text=coverage_text,
                           telemetry_text=telemetry_text)


def _iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".git", "build")]
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def _find_doc(paths, explicit, filename):
    """Walk up from cwd / the linted paths / the repo root until
    `filename` is found (the FL004/FL016 ledger-discovery rule)."""
    if explicit:
        return explicit
    candidates = [os.getcwd()]
    candidates += [os.path.abspath(p) for p in paths]
    candidates.append(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for c in candidates:
        d = c if os.path.isdir(c) else os.path.dirname(c)
        while True:
            probe = os.path.join(d, filename)
            if os.path.isfile(probe):
                return probe
            parent = os.path.dirname(d)
            if parent == d:
                break
            d = parent
    return None


def _find_coverage(paths, explicit):
    return _find_doc(paths, explicit, "OPS_COVERAGE.md")


def _read_doc(paths, explicit, filename):
    doc = _find_doc(paths, explicit, filename)
    if doc is None:
        return None
    with open(doc, encoding="utf-8") as f:
        return f.read()


def lint_paths(paths, coverage_path=None, telemetry_path=None):
    coverage_text = _read_doc(paths, coverage_path, "OPS_COVERAGE.md")
    telemetry_text = _read_doc(paths, telemetry_path, "TELEMETRY.md")
    findings = []
    for path in _iter_py_files(paths):
        findings.extend(lint_file(path, coverage_text=coverage_text,
                                  telemetry_text=telemetry_text))
    return findings


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="AST-based framework lint (see module docstring)")
    ap.add_argument("paths", nargs="*", default=["incubator_mxnet_tpu"],
                    help="files or directories to lint")
    ap.add_argument("--coverage", default=None,
                    help="path to OPS_COVERAGE.md (default: auto-discover)")
    ap.add_argument("--telemetry-doc", default=None,
                    help="path to TELEMETRY.md (default: auto-discover)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)
    if args.list_rules:
        for rid, doc in sorted(RULES.items()):
            print(f"{rid}  {doc}")
        return 0
    findings = lint_paths(args.paths or ["incubator_mxnet_tpu"],
                          coverage_path=args.coverage,
                          telemetry_path=args.telemetry_doc)
    for f in findings:
        print(f)
    if findings:
        print(f"framework_lint: {len(findings)} finding(s)")
        return 1
    print("framework_lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
