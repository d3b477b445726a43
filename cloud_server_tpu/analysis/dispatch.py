"""Dispatch-discipline pass (checker id: ``dispatch-discipline``).

PR 2/9's load-bearing invariant: each scheduler iteration runs ONE
fused jitted dispatch and pays ONE host<->device sync (the
``device_get`` of the sampled tokens). The runtime regression tests
count dispatches on one driven path; this pass pins the invariant
statically across the whole scheduler loop of the server.

Rules:

  * ``DD1 jit inventory`` — jitted callables are auto-discovered in
    each audited server file (``name = partial(jax.jit, ...)``
    assignments and ``@partial(jax.jit, ...)`` / ``@jax.jit``
    decorations), along with their ``static_argnames``.
  * ``DD2 sanctioned sync`` — ``jax.device_get`` may appear ONLY in
    the functions listed in ``SANCTIONED_SYNCS`` (the per-iteration
    commit points). Any other ``device_get`` on the scheduler loop,
    and ANY ``block_until_ready`` / ``.item()`` /
    ``.copy_to_host_async()``, flags. Each sanctioned function must
    exist and actually contain a ``device_get`` (sanction rot is a
    finding too). Async host->device feeds (``jnp.asarray`` /
    ``device_put``) are deliberately NOT flagged: they overlap with
    compute and are the dispatch input path.
  * ``DD3 host-policy purity`` — modules in ``HOST_POLICY_MODULES``
    (admission policy, SLO math, tracing, speculation control,
    metrics) must never import or touch ``jax`` / ``jnp`` / ``lax``;
    device work belongs to the servers, which ARE the allowlist.
  * ``DD4 static-arg boundedness`` — every value flowing into a
    jitted callable's static argument from a scheduler-loop function
    must come from a STATICALLY BOUNDED set, because each distinct
    value compiles a new program variant (the compile-variant
    invariant PR 9's ``{0, spec_drafts}`` draft-width quantization
    depends on). Bounded means: constants, ``self.*`` configuration,
    boolean expressions, callee parameters declared ``bool``, the
    audited bucketing helpers in ``BOUNDED_HELPERS`` (power-of-two
    rounding / bucket tables / round planners) — composed through
    arithmetic, min/max, and conditionals — and the reviewed
    ``plan.*`` fields in ``PLAN_BOUNDED_FIELDS`` (the async
    scheduler's ``_launch_plan`` replays statics the planner already
    computed through those same bounded helpers). A raw ``len(...)``,
    a request field, or any other data-dependent value flags.
  * ``DD5 overlap write-safety`` — the scheduler plans iteration
    N+1 WHILE iteration N's dispatch is in flight. A page released
    during that window can be re-allocated to a new admission while
    the device still writes it, so a plan made under a dispatch in
    flight must never release one: the functions in
    ``OVERLAP_PLAN_FUNCS`` (the plan/launch path and the sweep) must
    never reach — directly or transitively through same-class
    helpers — any of the page-releasing / slot-teardown functions in
    ``PAGE_RELEASING_FUNCS``. Releases belong to the commit
    (``_commit_inflight`` / ``_apply_reaps``) and to a plan made with
    nothing in flight: the one exemption is a call in the body of an
    ``if <in-flight dispatch> is None:`` statement, where the tested
    expression is ``self._inflight`` or a local bound once, by
    ``name = self._inflight``, in that function.

Stdlib-only (ast); never imports jax or the serving stack.
"""

from __future__ import annotations

import ast

from cloud_server_tpu.analysis.framework import (Finding, Pass,
                                                 collect_functions,
                                                 default_root,
                                                 dotted_name,
                                                 enclosing_class_line,
                                                 read_rostered,
                                                 register_pass)

CHECKER = "dispatch-discipline"

# Scheduler-loop functions per server file: everything reachable from
# step() on every iteration. The jit call sites and sync sites this
# pass polices all live here.
SCHEDULER_LOOPS: dict[str, tuple[str, ...]] = {
    "cloud_server_tpu/inference/paged_server.py": (
        "PagedInferenceServer.step",
        "PagedInferenceServer.serve_forever",
        "PagedInferenceServer._plan_iteration",
        "PagedInferenceServer._launch_plan",
        "PagedInferenceServer._commit_inflight",
        "PagedInferenceServer._sweep",
        "PagedInferenceServer._apply_reaps",
        "PagedInferenceServer._extend_chains_planned",
        "PagedInferenceServer._build_prefill_group",
        "PagedInferenceServer._select_prefill",
        "PagedInferenceServer._expire_pending",
        "PagedInferenceServer._start_admissions",
        "PagedInferenceServer._commit_decode_rows",
        "PagedInferenceServer._complete_admission_chunks",
        "PagedInferenceServer._record_iteration",
        "PagedInferenceServer._stage_decode_spans",
        "PagedInferenceServer._stage_spec_stats",
        "PagedInferenceServer._gather_decode_rows",
        "PagedInferenceServer._spec_plan",
        "PagedInferenceServer._chunk_bucket",
        "PagedInferenceServer._drafted_rows",
        "PagedInferenceServer._chunk_rounds",
        "PagedInferenceServer._mixed_rounds",
        "PagedInferenceServer._extend_chains",
        "PagedInferenceServer._preempt_youngest",
        "PagedInferenceServer._ensure_penalty_state",
        "PagedInferenceServer._emit",
        "PagedInferenceServer._finish",
        # deferred delivery: the commit records, `_deliver` wakes the
        # clients after the next launch — host work only, under a
        # running program, so a sync here would stall two iterations
        "PagedInferenceServer._complete_later",
        "PagedInferenceServer._deliver",
        "PagedInferenceServer._release_slot",
        "PagedInferenceServer._committed",
        "PagedInferenceServer._next_dispatch",
        "PagedInferenceServer._to_device",
        "PagedInferenceServer._feed_patch",
        # live-migration path: off the step loop (it runs on router /
        # drain threads), but policed by the same sync discipline — the
        # export owns its ONE sanctioned device_get (below), and the
        # import must stay async (its scatter is a dispatch; jnp.asarray
        # feeds are the input path DD2 deliberately allows)
        "PagedInferenceServer.migrate_export",
        "PagedInferenceServer.migrate_salvage",
        "PagedInferenceServer._export_request_locked",
        "PagedInferenceServer._build_snapshot",
        "PagedInferenceServer._evacuate_request_locked",
        "PagedInferenceServer._evacuate",
        "PagedInferenceServer.migrate_import",
        "PagedInferenceServer._import_pages",
        # disaggregation handoff: the prefetch runs on the iteration
        # path right before the mixed dispatch (its copy_to_host_async
        # STARTS are pragma-sanctioned — they are not host syncs), the
        # drain runs at the end of every step outside the step lock,
        # and pending_prefill_tokens is the router's prefill-load read
        "PagedInferenceServer._handoff_prefetch",
        "PagedInferenceServer._drain_handoff_ready",
        "PagedInferenceServer.pending_prefill_tokens",
    ),
}

# The ONE sanctioned per-iteration host sync: the commit point where
# the sampled tokens come home. Everything else on the loop must stay
# async.
SANCTIONED_SYNCS: dict[str, tuple[str, ...]] = {
    "cloud_server_tpu/inference/paged_server.py": (
        # a launched dispatch's commit point: ONE device_get per
        # committed iteration; _launch_plan itself must stay sync-free
        # (DD2 covers it like every other loop function)
        "PagedInferenceServer._commit_inflight",
        # live migration: the request export's KV gather — ONE sync per
        # migration, at the commit point (inflight work committed
        # first), under the step lock and off the plan path, so DD5's
        # overlap window never sees it
        "PagedInferenceServer._export_request_locked",
    ),
}

# DD5: the plan/launch path — everything that runs while a dispatch
# may be in flight — and the page-releasing functions it must never
# reach there. Transitive through same-class helper calls.
OVERLAP_PLAN_FUNCS: dict[str, tuple[str, ...]] = {
    "cloud_server_tpu/inference/paged_server.py": (
        "PagedInferenceServer._plan_iteration",
        "PagedInferenceServer._extend_chains_planned",
        "PagedInferenceServer._sweep",
        "PagedInferenceServer._launch_plan",
        "PagedInferenceServer._build_prefill_group",
        "PagedInferenceServer._select_prefill",
        # the handoff KV prefetch runs inside _launch_plan while the
        # PREVIOUS dispatch may still be in flight: it reads committed
        # pages and starts D2H copies but must never release a page —
        # and must never reach the export path (whose device_get is
        # sanctioned only OFF the plan path)
        "PagedInferenceServer._handoff_prefetch",
    ),
}
PAGE_RELEASING_FUNCS = frozenset({
    "_release_slot", "_preempt_youngest", "_finish", "_extend_chains",
    "_fail_all", "_apply_reaps",
    # allocator page release (self.allocator.release / the lock-free
    # variants); plan-path code may alloc, never release
    "release",
})

# DD4: reviewed fields of the scheduler's _Plan snapshot that
# are bounded BY CONSTRUCTION — _plan_iteration computes them through
# the same audited helpers this pass already trusts (n_rounds via the
# _mixed_rounds/_chunk_rounds pow2 planners, g_iter via _spec_plan's
# {0, spec_drafts} quantization, chunk_w via _chunk_bucket's bucket
# table) — so _launch_plan replaying them into the jits' static
# arguments cannot mint new compile variants. Adding a field here is a
# reviewed decision, exactly like BOUNDED_HELPERS.
PLAN_BOUNDED_FIELDS = frozenset({"n_rounds", "g_iter", "chunk_w"})

# Pure host-side policy modules: scheduling decisions, accounting,
# telemetry. The servers are the only modules allowed to touch jax.
HOST_POLICY_MODULES: tuple[str, ...] = (
    "cloud_server_tpu/inference/qos.py",
    "cloud_server_tpu/inference/faults.py",
    "cloud_server_tpu/inference/migration.py",
    "cloud_server_tpu/inference/slo.py",
    "cloud_server_tpu/inference/request_trace.py",
    "cloud_server_tpu/inference/spec_control.py",
    "cloud_server_tpu/inference/iteration_profile.py",
    "cloud_server_tpu/inference/cache_telemetry.py",
    "cloud_server_tpu/inference/anomaly.py",
    "cloud_server_tpu/utils/serving_metrics.py",
    # scenario harness: workload generation, replay, the discrete-event
    # simulator, and the autoscaler are all pure host policy — the
    # simulator MODELS device iterations from fitted flight-record
    # costs, it must never run one
    "cloud_server_tpu/scenarios/workload.py",
    "cloud_server_tpu/scenarios/replay.py",
    "cloud_server_tpu/scenarios/simulator.py",
    "cloud_server_tpu/scenarios/autoscaler.py",
)

# Call leaves whose results are statically bounded REGARDLESS of their
# arguments — the audited bucketing/planning helpers. Adding a name
# here is a reviewed decision: the helper must quantize its output to
# a fixed set (powers of two, a bucket table, {0, spec_drafts}).
BOUNDED_HELPERS = {
    "_pad_pow2",       # next power of two, log2-many values
    "_bucket",         # fixed bucket table lookup
    "_chunk_bucket",   # a prefill group's chunk width, through _bucket
    "_chunk_rounds",   # power-of-two round planner (paged)
    "_mixed_rounds",   # power-of-two round planner (mixed budget)
    "_spec_plan",      # draft width quantized to {0, spec_drafts}
    "bool",
}
# bounded only when every argument is bounded (len is NOT here: a
# data-dependent length is exactly the unbounded source this rule
# exists to catch — route it through a bucketing helper instead)
_ARG_BOUNDED_CALLS = {"min", "max", "int", "abs", "round"}

_SYNC_LEAVES = {"block_until_ready", "item", "copy_to_host_async"}
_DEVICE_ROOTS = {"jax", "jnp", "lax"}


_dotted = dotted_name


def _self_rooted(node: ast.AST) -> bool:
    while isinstance(node, ast.Attribute):
        node = node.value
    return isinstance(node, ast.Name) and node.id == "self"


# -- DD1: jit inventory -----------------------------------------------------

def _partial_jit_call(node: ast.AST) -> ast.Call | None:
    """The `partial(jax.jit, ...)` Call, from either `partial(...)`
    itself or a `partial(...)(core)` application."""
    if not isinstance(node, ast.Call):
        return None
    name = _dotted(node.func)
    if name in ("partial", "functools.partial"):
        if node.args and _dotted(node.args[0]) in ("jax.jit", "jit"):
            return node
        return None
    # application form: partial(jax.jit, ...)(core_fn)
    return _partial_jit_call(node.func)


def _static_names(pcall: ast.Call) -> tuple[str, ...] | None:
    """Declared static_argnames; () when none are declared; None when
    the declaration exists but is NOT a literal — boundedness cannot
    be verified then, which must be a finding, not a silent skip."""
    for kw in pcall.keywords:
        if kw.arg == "static_argnames":
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                if all(isinstance(e, ast.Constant)
                       for e in kw.value.elts):
                    return tuple(e.value for e in kw.value.elts)
                return None
            if isinstance(kw.value, ast.Constant):
                return (kw.value.value,)
            return None
    return ()


def _bool_statics(fn: ast.AST | None) -> set[str]:
    """Static params annotated/defaulted bool on the traced callee:
    at most two compile variants each — intrinsically bounded."""
    out: set[str] = set()
    if fn is None:
        return out
    args = fn.args
    pairs = list(zip(args.kwonlyargs, args.kw_defaults))
    n_def = len(args.defaults)
    pos = args.posonlyargs + args.args
    pairs += list(zip(pos[len(pos) - n_def:], args.defaults))
    for a, default in pairs:
        ann = a.annotation
        if (isinstance(ann, ast.Name) and ann.id == "bool") or \
                isinstance(getattr(default, "value", None), bool):
            out.add(a.arg)
    return out


class _JitInfo:
    __slots__ = ("name", "statics", "bool_statics", "params", "node")

    def __init__(self, name, statics, bool_statics, params, node):
        self.name = name
        self.statics = statics
        self.bool_statics = bool_statics
        self.params = params          # positional-capable param names
        self.node = node


def _positional_params(fn: ast.AST | None) -> tuple[str, ...]:
    if fn is None:
        return ()
    return tuple(a.arg for a in fn.args.posonlyargs + fn.args.args)


def inventory_jits(tree: ast.Module) -> dict[str, _JitInfo]:
    """Every jitted callable declared in the module, by name."""
    defs = {n.name: n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    out: dict[str, _JitInfo] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            pcall = _partial_jit_call(node.value)
            if pcall is None:
                continue
            name = node.targets[0].id
            core = None
            if isinstance(node.value, ast.Call) and node.value.args:
                core = defs.get(_dotted(node.value.args[0]) or "")
            out[name] = _JitInfo(name, _static_names(pcall),
                                 _bool_statics(core),
                                 _positional_params(core), node)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _dotted(dec) in ("jax.jit", "jit"):
                    out[node.name] = _JitInfo(node.name, (), set(),
                                              _positional_params(node),
                                              node)
                    break
                pcall = _partial_jit_call(dec)
                if pcall is not None:
                    out[node.name] = _JitInfo(
                        node.name, _static_names(pcall),
                        _bool_statics(node),
                        _positional_params(node), node)
                    break
    return out


# -- DD4: static-arg boundedness --------------------------------------------

class _Boundedness:
    """Optimistic per-function classifier: local names start bounded
    and are demoted whenever any assignment feeds them an unbounded
    expression, to fixpoint. Function parameters are unbounded."""

    def __init__(self, fn: ast.AST):
        self.fn = fn
        params = {a.arg for a in fn.args.posonlyargs + fn.args.args
                  + fn.args.kwonlyargs}
        params.discard("self")
        self.unbounded: set[str] = set(params)
        self.assigns: list[tuple[list, ast.AST]] = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                self.assigns.append((node.targets, node.value))
            elif isinstance(node, ast.AugAssign):
                synth = ast.BinOp(left=node.target, op=node.op,
                                  right=node.value)
                self.assigns.append(([node.target], synth))
            elif isinstance(node, ast.AnnAssign) and node.value:
                self.assigns.append(([node.target], node.value))
            elif isinstance(node, ast.NamedExpr):
                # walrus: `(n := expr)` binds like an assignment
                self.assigns.append(([node.target], node.value))
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                self._demote_target(node.target)
            elif isinstance(node, ast.comprehension):
                self._demote_target(node.target)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.optional_vars is not None:
                        self._demote_target(item.optional_vars)
        changed = True
        while changed:
            changed = False
            for targets, value in self.assigns:
                for tgt, expr in self._pair(targets, value):
                    name = tgt.id if isinstance(tgt, ast.Name) else None
                    if name and name not in self.unbounded \
                            and not self.bounded(expr):
                        self.unbounded.add(name)
                        changed = True

    def _demote_target(self, tgt: ast.AST) -> None:
        for n in ast.walk(tgt):
            if isinstance(n, ast.Name):
                self.unbounded.add(n.id)

    def _pair(self, targets, value):
        """(target, value-expr) pairs; tuple targets fed by a bounded
        helper call (e.g. `a, b = self._spec_plan(...)`) bind every
        name to that call."""
        for tgt in targets:
            if isinstance(tgt, (ast.Tuple, ast.List)):
                if isinstance(value, (ast.Tuple, ast.List)) \
                        and len(value.elts) == len(tgt.elts):
                    yield from zip(tgt.elts, value.elts)
                else:
                    for e in tgt.elts:
                        yield e, value
            else:
                yield tgt, value

    def bounded(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id not in self.unbounded
        if isinstance(node, ast.Attribute):
            if (isinstance(node.value, ast.Name)
                    and node.value.id == "plan"
                    and node.attr in PLAN_BOUNDED_FIELDS):
                return True  # reviewed _Plan statics (see the constant)
            return _self_rooted(node)  # init-time configuration
        if isinstance(node, (ast.Compare, ast.BoolOp)):
            return True  # boolean-valued: at most two variants
        if isinstance(node, ast.UnaryOp):
            return isinstance(node.op, ast.Not) \
                or self.bounded(node.operand)
        if isinstance(node, ast.BinOp):
            return self.bounded(node.left) and self.bounded(node.right)
        if isinstance(node, ast.IfExp):
            return self.bounded(node.body) and self.bounded(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(self.bounded(e) for e in node.elts)
        if isinstance(node, ast.Call):
            leaf = (_dotted(node.func) or "?").rsplit(".", 1)[-1]
            if leaf in BOUNDED_HELPERS:
                return True
            if leaf in _ARG_BOUNDED_CALLS:
                return all(self.bounded(a) for a in node.args)
            return False
        return False


# -- the pass ---------------------------------------------------------------

def check_scheduler_source(path: str, source: str,
                           loop_quals: tuple[str, ...],
                           sanctioned: tuple[str, ...]) -> list[Finding]:
    """DD1/DD2/DD4 over one server module."""
    tree = ast.parse(source, filename=path)
    jits = inventory_jits(tree)
    found, classes = collect_functions(tree)
    out: list[Finding] = []

    def missing(qual: str, what: str) -> None:
        out.append(Finding(path, enclosing_class_line(classes, qual),
                           CHECKER, qual,
                           f"{what} (renamed? update the "
                           "dispatch-discipline roster)"))

    for qual in sanctioned:
        fn = found.get(qual)
        if fn is None:
            missing(qual, "sanctioned-sync function not found")
        elif not any(isinstance(n, ast.Call)
                     and (_dotted(n.func) or "").endswith("device_get")
                     for n in ast.walk(fn)):
            out.append(Finding(
                path, fn.lineno, CHECKER, qual,
                "sanctioned-sync function no longer contains a "
                "device_get — the sanction list has rotted"))

    for qual in loop_quals:
        fn = found.get(qual)
        if fn is None:
            missing(qual, "scheduler-loop function not found")
            continue
        bound = None  # built lazily: most loop functions call no jits
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            leaf = (name or "?").rsplit(".", 1)[-1]
            if leaf == "device_get" and qual not in sanctioned:
                out.append(Finding(
                    path, node.lineno, CHECKER, qual,
                    "device sync device_get() outside the sanctioned "
                    "per-iteration commit points (DD2)"))
            elif leaf in _SYNC_LEAVES:
                out.append(Finding(
                    path, node.lineno, CHECKER, qual,
                    f"device sync {name or leaf}() on the scheduler "
                    "loop (DD2)"))
            ji = jits.get(leaf) if name == leaf else None
            if ji is None:
                continue
            if ji.statics is None:
                out.append(Finding(
                    path, node.lineno, CHECKER, qual,
                    f"static_argnames of {ji.name} is not a literal "
                    "— static-argument boundedness cannot be "
                    "verified (DD4)"))
                continue
            if not ji.statics:
                continue
            if bound is None:
                bound = _Boundedness(fn)

            def unbounded(argname, expr):
                out.append(Finding(
                    path, expr.lineno, CHECKER, qual,
                    f"static argument {argname!r} of {ji.name} fed "
                    "from a statically UNBOUNDED expression — every "
                    "distinct value compiles a new program variant "
                    "(DD4)"))

            # statics can ride POSITIONALLY too: map call positions
            # onto the traced callee's parameter names (a *splat makes
            # later positions unknowable — stop mapping there, the
            # remaining statics arrive as keywords or defaults)
            for i, arg in enumerate(node.args):
                if isinstance(arg, ast.Starred):
                    break
                if i < len(ji.params) \
                        and ji.params[i] in ji.statics \
                        and ji.params[i] not in ji.bool_statics \
                        and not bound.bounded(arg):
                    unbounded(ji.params[i], arg)
            for kw in node.keywords:
                if kw.arg is None:
                    # **splat: statics may hide inside — opaque to
                    # this analysis, so it is a finding by itself
                    out.append(Finding(
                        path, kw.value.lineno, CHECKER, qual,
                        f"**-splat into jitted {ji.name} — static "
                        "arguments cannot be verified through it "
                        "(DD4)"))
                    continue
                if kw.arg not in ji.statics \
                        or kw.arg in ji.bool_statics:
                    continue
                if not bound.bounded(kw.value):
                    unbounded(kw.arg, kw.value)
    return out


def check_overlap_source(path: str, source: str,
                         plan_quals: tuple[str, ...]) -> list[Finding]:
    """DD5 over one server module: no page-releasing function is
    reachable from the plan path under a dispatch in flight — directly,
    or transitively through same-class ``self.*`` helper calls. What
    stands in the body of ``if <the in-flight dispatch> is None:`` runs
    with nothing in flight and is not walked."""
    tree = ast.parse(source, filename=path)
    found, classes = collect_functions(tree)
    out: list[Finding] = []

    def nothing_in_flight(fn: ast.AST) -> set[int]:
        """ids of the nodes under an ``if X is None:`` body, X being
        ``self._inflight`` or a local this function binds once, from
        it."""
        bound: dict[str, int] = {}
        alias: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        bound[t.id] = bound.get(t.id, 0) + 1
                        if _dotted(node.value) == "self._inflight":
                            alias.add(t.id)
        names = {"self._inflight"} | {a for a in alias if bound[a] == 1}
        exempt: set[int] = set()
        for node in ast.walk(fn):
            if (isinstance(node, ast.If)
                    and isinstance(node.test, ast.Compare)
                    and len(node.test.ops) == 1
                    and isinstance(node.test.ops[0], ast.Is)
                    and _dotted(node.test.left) in names
                    and isinstance(node.test.comparators[0], ast.Constant)
                    and node.test.comparators[0].value is None):
                for stmt in node.body:
                    exempt.update(id(n) for n in ast.walk(stmt))
        return exempt

    def self_calls(fn: ast.AST):
        """(leaf name, node) for every self.X(...) / X(...) call made
        while a dispatch may be in flight."""
        exempt = nothing_in_flight(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in exempt:
                continue
            name = _dotted(node.func)
            if name is None:
                continue
            yield name.rsplit(".", 1)[-1], name, node

    for qual in plan_quals:
        fn = found.get(qual)
        if fn is None:
            out.append(Finding(
                path, enclosing_class_line(classes, qual), CHECKER,
                qual, "overlap-plan function not found (renamed? "
                      "update OVERLAP_PLAN_FUNCS)"))
            continue
        cls = qual.rsplit(".", 1)[0]
        seen: set[str] = set()
        stack: list[tuple[str, ast.AST]] = [(qual, fn)]
        while stack:
            cur_qual, cur_fn = stack.pop()
            if cur_qual in seen:
                continue
            seen.add(cur_qual)
            for leaf, name, node in self_calls(cur_fn):
                if leaf in PAGE_RELEASING_FUNCS:
                    out.append(Finding(
                        path, node.lineno, CHECKER, qual,
                        f"overlap-plan path reaches page-releasing "
                        f"{name}() (via {cur_qual}) while a dispatch "
                        "may be in flight — releases belong to the "
                        "commit (DD5)"))
                    continue
                callee_qual = f"{cls}.{leaf}"
                callee = found.get(callee_qual)
                if callee is not None and name.startswith("self."):
                    stack.append((callee_qual, callee))
    return out


def check_host_policy_source(path: str, source: str) -> list[Finding]:
    """DD3: no jax/jnp/lax anywhere in a host-policy module."""
    tree = ast.parse(source, filename=path)
    out: list[Finding] = []
    seen: set[int] = set()

    def flag(node: ast.AST, msg: str) -> None:
        if node.lineno not in seen:
            seen.add(node.lineno)
            out.append(Finding(path, node.lineno, CHECKER, "", msg))

    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            mod = (getattr(node, "module", None) or "").split(".")[0]
            names = {a.name.split(".")[0] for a in node.names}
            hit = ({mod} | names) & _DEVICE_ROOTS
            if hit:
                flag(node, f"host-policy module imports {sorted(hit)} "
                           "— device work belongs to the servers (DD3)")
        elif isinstance(node, ast.Name) and node.id in _DEVICE_ROOTS:
            flag(node, f"host-policy module touches {node.id}.* — "
                       "device work belongs to the servers (DD3)")
    return out


def check_dispatch(root: str | None = None) -> list[Finding]:
    if root is None:
        root = default_root()
    out: list[Finding] = []
    for rel, quals in SCHEDULER_LOOPS.items():
        source, missing = read_rostered(root, rel, CHECKER)
        if missing is not None:
            out.append(missing)
            continue
        out.extend(check_scheduler_source(
            rel, source, quals, SANCTIONED_SYNCS.get(rel, ())))
        plan_quals = OVERLAP_PLAN_FUNCS.get(rel)
        if plan_quals:
            out.extend(check_overlap_source(rel, source, plan_quals))
    for rel in HOST_POLICY_MODULES:
        source, missing = read_rostered(root, rel, CHECKER)
        if missing is not None:
            out.append(missing)
            continue
        out.extend(check_host_policy_source(rel, source))
    return out


register_pass(Pass(
    id=CHECKER,
    title="one sanctioned device_get per scheduler iteration, jax-free "
          "host-policy modules, statically bounded jit static "
          "arguments, and a plan path that releases no page under a "
          "dispatch in flight",
    run=check_dispatch,
    roster=lambda root: tuple(SCHEDULER_LOOPS) + HOST_POLICY_MODULES,
))
