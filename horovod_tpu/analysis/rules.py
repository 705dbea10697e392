"""hvd-lint rule catalog — AST checks for the collective contract.

Every rule encodes one way real jobs deadlock or silently diverge at scale
(SURVEY §7 hard-part (a): collectives are only correct when every rank
issues the same collectives in the same program order).  The stall detector
(core/src/controller.cc) reports these failures at runtime after the fact;
these rules reject them before launch.

Rules are pluggable: subclass :class:`Rule`, set ``code``/``name``/``hint``,
implement ``run``, and append to :data:`RULES`.  Each finding carries the
rule's error code (suppress with ``# hvd-lint: disable=CODE`` on the
flagged line) and a fix-it hint.  Pure stdlib (ast only) — linting a tree
must never require importing jax.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass


@dataclass(frozen=True)
class Finding:
    code: str
    line: int
    col: int
    message: str
    hint: str


# Public collective entry points (ops/collective_ops.py, ops/async_ops.py,
# training.py object/parameter helpers).  All of these must be issued in
# identical program order on every rank.
COLLECTIVE_CALLS = frozenset({
    "allreduce", "allgather", "broadcast", "alltoall",
    "grouped_allreduce", "quantized_grouped_allreduce", "allreduce_sparse",
    "allreduce_async", "allgather_async", "broadcast_async",
    "alltoall_async", "barrier",
    "allgather_object", "broadcast_object", "broadcast_parameters",
    "broadcast_optimizer_state",
})

# The subset that routes through the native engine's name table, where a
# reused auto-name aborts with the duplicate-tensor-name error
# (core/engine.py enqueue) and cross-rank name sequences must agree.
ENGINE_COLLECTIVES = frozenset({
    "allreduce_async", "allgather_async", "broadcast_async",
    "alltoall_async", "barrier",
})

# Zero-argument process-identity calls (basics.py).  The zero-arg
# requirement keeps tensor-rank helpers like ``tf.rank(x)`` out.
RANK_CALLS = frozenset({"rank", "local_rank", "cross_rank"})

# lax collectives that consume a mesh axis name; value = index of the
# positional axis argument (axis_name= kwarg also accepted everywhere).
LAX_AXIS_ARG = {
    "psum": 1, "pmax": 1, "pmin": 1, "pmean": 1, "psum_scatter": 1,
    "all_gather": 1, "all_to_all": 1, "ppermute": 1, "pshuffle": 1,
    "pswapaxes": 1, "axis_index": 0, "axis_size": 0,
}

# Axis names every horovod_tpu job has without declaring anything
# (mesh.py: the global data mesh).
BUILTIN_AXES = frozenset({"hvd", "ici", "dcn"})


def call_name(node: ast.Call) -> str | None:
    """Terminal name of a call: ``hvd.ops.allreduce(...)`` -> ``allreduce``."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def dotted(node: ast.AST) -> str | None:
    """Full dotted path of a Name/Attribute chain, or None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def kwarg(node: ast.Call, name: str) -> ast.expr | None:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _collective_calls(tree: ast.AST) -> list[ast.Call]:
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.Call) and call_name(n) in COLLECTIVE_CALLS]


class Context:
    """Per-module facts shared by rules (import table, etc.)."""

    def __init__(self, module: ast.Module):
        self.module = module
        # local alias -> imported dotted module/symbol path
        self.imports: dict[str, str] = {}
        for node in ast.walk(module):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.imports[a.asname or a.name] = (
                        f"{node.module}.{a.name}")

    def resolve(self, path: str) -> str:
        """Rewrite the root of a dotted path through the import table:
        ``np.random.uniform`` -> ``numpy.random.uniform``."""
        root, _, rest = path.partition(".")
        base = self.imports.get(root, root)
        return f"{base}.{rest}" if rest else base


class Rule:
    code = "HVD000"
    name = "abstract"
    hint = ""

    def run(self, ctx: Context) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, node: ast.AST, message: str) -> Finding:
        return Finding(self.code, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message, self.hint)


class RankDivergentCollective(Rule):
    """Collective reachable only under rank-dependent control flow.

    ``if hvd.rank() == 0: hvd.allreduce(x)`` deadlocks: the other ranks
    never issue the matching call, so rank 0 waits forever (the stall
    detector's #1 customer).  Branches are compared as multisets of
    collective call names — a broadcast in both arms is fine.
    """

    code = "HVD101"
    name = "rank-divergent-collective"
    hint = ("issue the same collective on every rank (hoist it out of the "
            "rank() branch, or mirror it on the other branch)")

    def _rank_dependent(self, test: ast.expr) -> bool:
        for n in ast.walk(test):
            if (isinstance(n, ast.Call) and call_name(n) in RANK_CALLS
                    and not n.args and not n.keywords):
                return True
        return False

    def run(self, ctx: Context) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(ctx.module):
            if isinstance(node, ast.If):
                body, orelse = node.body, node.orelse
            elif isinstance(node, ast.IfExp):
                body, orelse = [node.body], [node.orelse]
            else:
                continue
            if not self._rank_dependent(node.test):
                continue
            body_calls = [c for stmt in body
                          for c in _collective_calls(stmt)]
            else_calls = [c for stmt in orelse
                          for c in _collective_calls(stmt)]
            bn = sorted(call_name(c) or "" for c in body_calls)
            en = sorted(call_name(c) or "" for c in else_calls)
            if bn == en:
                continue
            # Report at the collective(s) present on one side only.
            lonely = body_calls if len(bn) >= len(en) else else_calls
            c = lonely[0]
            other = "the other branch" if orelse else "the implicit else"
            out.append(self.finding(c, (
                f"collective '{call_name(c)}' is only reached when the "
                f"rank()-dependent condition holds; {other} issues "
                f"{en if len(bn) >= len(en) else bn or 'no collectives'} — "
                f"the ranks that take it will never match this call "
                f"(cross-rank deadlock)")))
        return out


class UnnamedCollectiveInLoop(Rule):
    """Engine-path collective inside a loop without an explicit ``name=``.

    Auto-names come from a per-process counter (ops/async_ops.py
    ``_auto_name``); any rank that issues one extra or one fewer op shifts
    every later auto-name, so the coordinator matches unrelated tensors or
    aborts with the duplicate-tensor-name error (core/engine.py).  Loops
    are where the counts drift (data-dependent trip counts).
    """

    code = "HVD102"
    name = "unnamed-collective-in-loop"
    hint = ("pass an explicit name= derived from stable loop state, e.g. "
            "name=f\"grad.{step}.{param}\"")

    def run(self, ctx: Context) -> list[Finding]:
        out: list[Finding] = []

        def visit(node: ast.AST, in_loop: bool) -> None:
            if isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
                for child in ast.iter_child_nodes(node):
                    visit(child, True)
                return
            if (isinstance(node, ast.Call)
                    and call_name(node) in ENGINE_COLLECTIVES and in_loop):
                name_kw = kwarg(node, "name")
                if name_kw is None or (isinstance(name_kw, ast.Constant)
                                       and name_kw.value is None):
                    out.append(self.finding(node, (
                        f"'{call_name(node)}' inside a loop without an "
                        f"explicit name=: auto-generated names come from a "
                        f"per-process counter and abort with the engine's "
                        f"duplicate-tensor-name error (or silently pair "
                        f"unrelated tensors) once rank op counts drift")))
            for child in ast.iter_child_nodes(node):
                visit(child, in_loop)

        visit(ctx.module, False)
        return out


class NondeterministicName(Rule):
    """Collective ``name=`` derived from ``id()`` or set/dict iteration.

    ``id()`` differs per process; set iteration order differs per process
    (hash randomization), and dict order reflects insertion order, which
    rank-dependent code paths easily perturb.  Either way two ranks
    announce different name sequences and the job deadlocks or pairs the
    wrong tensors.  ``sorted(...)`` over the same container is fine.
    """

    code = "HVD103"
    name = "nondeterministic-collective-name"
    hint = ("derive names from deterministic, rank-invariant data: "
            "sorted(container) instead of raw set/dict iteration, a "
            "parameter name instead of id()")

    _UNORDERED_CALLS = frozenset({
        "set", "frozenset", "keys", "values", "items", "vars", "globals",
        "locals",
    })

    def _unordered_iter(self, it: ast.expr) -> bool:
        if isinstance(it, (ast.Set, ast.SetComp, ast.Dict, ast.DictComp)):
            return True
        if isinstance(it, ast.Call):
            return call_name(it) in self._UNORDERED_CALLS
        return False

    def _tainted_names(self, scope: ast.AST) -> set[str]:
        tainted: set[str] = set()
        for node in ast.walk(scope):
            if isinstance(node, (ast.For, ast.AsyncFor)) and \
                    self._unordered_iter(node.iter):
                for t in ast.walk(node.target):
                    if isinstance(t, ast.Name):
                        tainted.add(t.id)
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                for comp in node.generators:
                    if self._unordered_iter(comp.iter):
                        for t in ast.walk(comp.target):
                            if isinstance(t, ast.Name):
                                tainted.add(t.id)
        return tainted

    def run(self, ctx: Context) -> list[Finding]:
        out: list[Finding] = []
        tainted = self._tainted_names(ctx.module)
        for node in ast.walk(ctx.module):
            if not (isinstance(node, ast.Call)
                    and call_name(node) in COLLECTIVE_CALLS):
                continue
            name_kw = kwarg(node, "name")
            if name_kw is None:
                continue
            for sub in ast.walk(name_kw):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Name)
                        and sub.func.id == "id"):
                    out.append(self.finding(node, (
                        f"'{call_name(node)}' name derives from id(): "
                        f"object addresses differ across processes, so "
                        f"ranks announce different tensor names")))
                    break
                if isinstance(sub, ast.Name) and sub.id in tainted:
                    out.append(self.finding(node, (
                        f"'{call_name(node)}' name derives from "
                        f"'{sub.id}', bound by iterating an unordered "
                        f"set/dict: iteration order differs across "
                        f"processes, so ranks announce names in different "
                        f"orders")))
                    break
        return out


class ImpureJitStep(Rule):
    """``random``/``time``/``np.random`` inside a jit/shard step function.

    The traced program is compiled once and replayed: the "random" value
    is frozen at trace time (and frozen *differently* per process, turning
    SPMD lockstep into silent divergence).  Use ``jax.random`` with an
    explicitly broadcast key, and pass timestamps in as arguments.
    """

    code = "HVD104"
    name = "impure-jit-step"
    hint = ("inside jit/shard use jax.random with a broadcast PRNG key; "
            "pass wall-clock values in as arguments")

    _JIT_DECOS = frozenset({"jit", "shard", "pmap"})

    def _jit_decorated(self, fn: ast.AST) -> bool:
        for deco in getattr(fn, "decorator_list", []):
            d = deco
            if isinstance(d, ast.Call):
                if call_name(d) == "partial" and d.args:
                    inner = dotted(d.args[0])
                    if inner and inner.split(".")[-1] in self._JIT_DECOS:
                        return True
                    continue
                name = call_name(d)
            else:
                path = dotted(d)
                name = path.split(".")[-1] if path else None
            if name in self._JIT_DECOS:
                return True
        return False

    def _impure(self, ctx: Context, node: ast.Call) -> str | None:
        path = dotted(node.func)
        if path is None:
            return None
        resolved = ctx.resolve(path)
        if resolved.startswith("numpy.random.") or resolved == "numpy.random":
            return resolved
        if resolved == "random" or resolved.startswith("random."):
            return resolved
        if resolved == "time" or resolved.startswith("time."):
            return resolved
        if resolved.startswith("datetime.") and resolved.endswith(".now"):
            return resolved
        return None

    def run(self, ctx: Context) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(ctx.module):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not self._jit_decorated(node):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    resolved = self._impure(ctx, sub)
                    if resolved is not None:
                        out.append(self.finding(sub, (
                            f"'{resolved}' called inside jit/shard-"
                            f"decorated '{node.name}': the value is frozen "
                            f"at trace time, differently on every process "
                            f"(silent SPMD divergence)")))
        return out


class UnknownAxisName(Rule):
    """lax collective over an axis name no mesh in this module declares.

    A typo'd ``axis_name`` raises NameError deep inside the trace on real
    meshes — or, worse, resolves against a *different* axis than intended
    on multi-axis meshes.  Active only in modules that declare a mesh
    (``Mesh(...)``, ``build_global_mesh(extra_axes=...)``,
    ``init(mesh_axes=...)``, ``pmap(axis_name=...)``); the builtin data
    axes ("hvd", "ici", "dcn") are always allowed.
    """

    code = "HVD105"
    name = "unknown-axis-name"
    hint = ("declare the axis on the mesh (extra_axes= / mesh_axes=) or "
            "fix the axis_name to one the mesh defines")

    def _declared_axes(self, ctx: Context) -> set[str] | None:
        declared: set[str] = set()
        saw_mesh = False
        for node in ast.walk(ctx.module):
            if not isinstance(node, ast.Call):
                continue
            cname = call_name(node)
            if cname == "Mesh":
                saw_mesh = True
                src = (node.args[1] if len(node.args) > 1
                       else kwarg(node, "axis_names"))
                if src is not None:
                    for sub in ast.walk(src):
                        if isinstance(sub, ast.Constant) and \
                                isinstance(sub.value, str):
                            declared.add(sub.value)
            elif cname in ("build_global_mesh", "init"):
                axes = (kwarg(node, "extra_axes") if cname ==
                        "build_global_mesh" else kwarg(node, "mesh_axes"))
                if isinstance(axes, ast.Dict):
                    saw_mesh = True
                    for k in axes.keys:
                        if isinstance(k, ast.Constant) and \
                                isinstance(k.value, str):
                            declared.add(k.value)
            elif cname in ("pmap", "vmap", "shard_map", "xmap"):
                ax = kwarg(node, "axis_name")
                if isinstance(ax, ast.Constant) and isinstance(ax.value, str):
                    saw_mesh = True
                    declared.add(ax.value)
        return declared if saw_mesh else None

    def run(self, ctx: Context) -> list[Finding]:
        declared = self._declared_axes(ctx)
        if declared is None:  # no mesh declared here: nothing to check against
            return []
        allowed = declared | BUILTIN_AXES
        out: list[Finding] = []
        for node in ast.walk(ctx.module):
            if not isinstance(node, ast.Call):
                continue
            cname = call_name(node)
            if cname not in LAX_AXIS_ARG:
                continue
            idx = LAX_AXIS_ARG[cname]
            axis = (node.args[idx] if len(node.args) > idx
                    else kwarg(node, "axis_name"))
            if axis is None:
                continue
            for sub in ast.walk(axis):
                if isinstance(sub, ast.Constant) and \
                        isinstance(sub.value, str) and \
                        sub.value not in allowed:
                    out.append(self.finding(node, (
                        f"'{cname}' reduces over axis '{sub.value}', but "
                        f"the mesh declared in this module only defines "
                        f"axes {sorted(allowed)}")))
        return out


class StaleTopologyConstant(Rule):
    """``hvd.size()``/``hvd.rank()`` cached where elastic resize can't
    reach it: a module- or class-level constant, or a default parameter
    value (frozen at ``def`` time — the classic closure-constant idiom).

    Under ``HVD_TPU_ELASTIC=1`` (docs/fault_tolerance.md "In-place
    recovery") a membership reconfiguration reassigns ranks and changes
    the world size *inside a live process*: every such cached value is
    silently stale afterwards — wrong data shards, wrong LR scale, wrong
    rank-0 gating.  Exempt: names that are refreshed inside an
    ``on_reconfigure`` callback, which is exactly where such caches
    belong.
    """

    code = "HVD106"
    name = "stale-topology-constant"
    hint = ("call hvd.size()/hvd.rank() at use time, or refresh the cached "
            "value inside an @hvd.on_reconfigure callback (elastic resize "
            "changes both in a live process)")

    _TOPO = frozenset({"rank", "size", "local_rank", "local_size",
                       "cross_rank", "cross_size", "num_chips"})
    _ROOTS = frozenset({"horovod_tpu", "hvd"})

    def _topo_call(self, ctx: Context, node: ast.AST) -> str | None:
        """Dotted path of a zero-arg topology call inside ``node``."""
        for sub in ast.walk(node):
            if not (isinstance(sub, ast.Call) and not sub.args
                    and not sub.keywords):
                continue
            path = dotted(sub.func)
            if path is None or path.split(".")[-1] not in self._TOPO:
                continue
            # Bare ``size()`` only counts when imported from horovod_tpu;
            # ``q.size()`` on some queue object must not trip the rule.
            if "." not in path and ctx.resolve(path) == path:
                continue
            if ctx.resolve(path).split(".")[0] in self._ROOTS:
                return path
        return None

    @staticmethod
    def _is_on_reconfigure(deco: ast.expr) -> bool:
        target = deco.func if isinstance(deco, ast.Call) else deco
        path = dotted(target)
        return path is not None and path.split(".")[-1] == "on_reconfigure"

    def run(self, ctx: Context) -> list[Finding]:
        out: list[Finding] = []
        # Names some on_reconfigure callback refreshes are exempt — the
        # cache is elastic-aware by construction.
        refreshed: set[str] = set()
        for node in ast.walk(ctx.module):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(self._is_on_reconfigure(d)
                       for d in node.decorator_list):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, (ast.Assign, ast.AugAssign,
                                    ast.AnnAssign)):
                    targets = sub.targets if isinstance(sub, ast.Assign) \
                        else [sub.target]
                    for t in targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                refreshed.add(n.id)

        def scan_body(body: list[ast.stmt]) -> None:
            for stmt in body:
                if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                    value = getattr(stmt, "value", None)
                    if value is None:
                        continue
                    path = self._topo_call(ctx, value)
                    if path is None:
                        continue
                    targets = stmt.targets if isinstance(stmt, ast.Assign) \
                        else [stmt.target]
                    names = {n.id for t in targets for n in ast.walk(t)
                             if isinstance(n, ast.Name)}
                    if names and names <= refreshed:
                        continue
                    out.append(self.finding(stmt, (
                        f"'{path}()' cached into a module/class-level "
                        f"constant: an elastic membership resize "
                        f"(HVD_TPU_ELASTIC) changes rank/size in a live "
                        f"process, leaving this value silently stale")))
                elif isinstance(stmt, ast.ClassDef):
                    scan_body(stmt.body)

        scan_body(ctx.module.body)
        for node in ast.walk(ctx.module):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                path = self._topo_call(ctx, d)
                if path is not None:
                    out.append(self.finding(d, (
                        f"'{path}()' used as a default parameter value of "
                        f"'{node.name}': defaults are evaluated once at "
                        f"def time and go stale when an elastic resize "
                        f"(HVD_TPU_ELASTIC) changes rank/size")))
        return out


class HandTunedContextLayout(Rule):
    """Hand-set long-context layout or flash kernel tiles.

    Since the context planner (ops/schedule_plan.py ``plan_context``) the
    sequence layout and the flash ``block_q``/``block_k`` are one joint
    decision from one memory model: causal multi-shard work routes to the
    zigzag layout (on the plain ring, rank r's first ``n-1-r`` steps
    attend fully-masked K blocks — the planner retires that idle
    triangle), and tiles are clamped to the kernel's VMEM budget (the
    hand-picked ``block_k=4096`` that wins at S=8K OOMs at S=32K).  Two
    idioms opt out of that by accident:

    * calling ``ring_flash_attention`` with ``causal=True`` (or leaving
      ``causal`` to its True default) — causal work on the plain layout;
    * passing integer-literal ``block_q=``/``block_k=`` to any ring
      attention entry point — tiles pinned at one sequence length.

    Passing variables (e.g. ``plan.block_q``) is fine — that is the
    planner speaking.  Audit/fixture sites that pin the plain causal path
    on purpose carry ``# hvd-lint: disable=HVD108``.
    """

    code = "HVD108"
    name = "hand-tuned-context-layout"
    hint = ("derive layout and kernel tiles from one plan: "
            "ops/schedule_plan.plan_context (parallel/context.py wires it "
            "into a TransformerConfig); mark deliberate plain-causal "
            "fixtures with `# hvd-lint: disable=HVD108`")

    # call name -> (positional index of causal, of block_q, of block_k);
    # causal None = the entry point has no causal parameter at call time.
    _RING_CALLS = {
        "ring_flash_attention": (4, 5, 6),
        "zigzag_ring_flash_attention": (4, 5, 6),
        "make_ring_flash_attention": (None, 1, 2),
        "make_zigzag_ring_flash_attention": (None, 1, 2),
    }

    @staticmethod
    def _arg(node: ast.Call, idx: int | None, name: str) -> ast.expr | None:
        if idx is not None and len(node.args) > idx:
            return node.args[idx]
        return kwarg(node, name)

    def run(self, ctx: Context) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(ctx.module):
            if not isinstance(node, ast.Call):
                continue
            cname = call_name(node)
            if cname not in self._RING_CALLS:
                continue
            causal_idx, bq_idx, bk_idx = self._RING_CALLS[cname]
            if cname == "ring_flash_attention":
                causal = self._arg(node, causal_idx, "causal")
                if causal is None or (isinstance(causal, ast.Constant)
                                      and causal.value is True):
                    out.append(self.finding(node, (
                        "causal attention on the plain ring layout: rank "
                        "r's first n-1-r steps attend fully-masked K "
                        "blocks — plan_context routes causal multi-shard "
                        "work to the zigzag layout instead")))
            for bname, bidx in (("block_q", bq_idx), ("block_k", bk_idx)):
                val = self._arg(node, bidx, bname)
                if isinstance(val, ast.Constant) and \
                        isinstance(val.value, int):
                    out.append(self.finding(node, (
                        f"'{cname}' pins {bname}={val.value}: a tile that "
                        f"fits one sequence length VMEM-OOMs at another — "
                        f"plan_context clamps tiles to the kernel budget "
                        f"per workload")))
        return out


class UnbucketedServeShape(Rule):
    """Request-length-shaped inputs to a compiled function in a serve loop.

    A serving loop calls its jitted prefill/decode once per request (or
    per step); jax compiles one program per INPUT SHAPE.  An argument
    whose shape is derived from ``len(prompt)`` — ``jnp.zeros((len(p),
    ...))``, ``tokens[:len(p)]`` — therefore recompiles for every novel
    request length: the compile cache grows without bound, tail latency
    absorbs multi-second XLA compiles mid-traffic, and on a fleet the
    ranks' response caches never warm because every shape is a fresh
    negotiation.  The serving engine's contract (serving/engine.py) is a
    fixed bucket menu: pad the prompt to the smallest bucket that holds
    it and pass the true length as a SCALAR (scalars are 0-d operands,
    not shapes — they never recompile).  Passing ``len(p)`` as a plain
    argument is accordingly fine; only shape-position uses are flagged.

    Callees considered serve-loop entry points: names bound from
    ``jax.jit(...)`` in the same module, and ``prefill``/``decode``-named
    calls (the backend protocol's verbs).  Deliberate one-shape fixtures
    carry ``# hvd-lint: disable=HVD109``.
    """

    code = "HVD109"
    name = "unbucketed-serve-shape"
    hint = ("pad the prompt to a fixed bucket (ServingConfig.buckets; "
            "smallest bucket >= len(prompt)) and pass the true length as "
            "a scalar argument — one compile per bucket, not per request "
            "length; mark deliberate one-shape fixtures with "
            "`# hvd-lint: disable=HVD109`")

    _SHAPE_CTORS = frozenset({"zeros", "ones", "full", "empty", "arange"})
    _SERVE_VERBS = ("prefill", "decode")

    @staticmethod
    def _jit_bound_names(ctx: Context) -> frozenset[str]:
        """Names assigned from ``jax.jit(...)`` / ``jit(...)`` — including
        ``self.f = jax.jit(...)`` method-style bindings."""
        out: set[str] = set()
        for node in ast.walk(ctx.module):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            path = dotted(node.value.func)
            if path is None or ctx.resolve(path).split(".")[-1] != "jit":
                continue
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
                elif isinstance(t, ast.Attribute):
                    out.add(t.attr)
        return frozenset(out)

    @classmethod
    def _len_shaped(cls, arg: ast.expr) -> ast.AST | None:
        """A node inside ``arg`` whose SHAPE depends on ``len(...)``:
        a shape-constructor with len() in its arguments, or a slice
        bounded by len().  Scalar len() uses return None."""
        for node in ast.walk(arg):
            if isinstance(node, ast.Call) and \
                    call_name(node) in cls._SHAPE_CTORS:
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and \
                            call_name(sub) == "len":
                        return node
            elif isinstance(node, ast.Subscript):
                for sub in ast.walk(node.slice):
                    if isinstance(sub, ast.Call) and \
                            call_name(sub) == "len":
                        return node
        return None

    def run(self, ctx: Context) -> list[Finding]:
        jit_names = self._jit_bound_names(ctx)
        out: list[Finding] = []
        for loop in ast.walk(ctx.module):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                cname = call_name(node)
                if cname is None:
                    continue
                is_serve = cname in jit_names or any(
                    v in cname.lower() for v in self._SERVE_VERBS)
                if not is_serve:
                    continue
                for arg in list(node.args) + [k.value for k in
                                              node.keywords]:
                    if self._len_shaped(arg) is not None:
                        out.append(self.finding(node, (
                            f"'{cname}' is called in a serve loop with an "
                            f"argument shaped by len(...): one XLA "
                            f"compile per novel request length, unbounded "
                            f"compile cache, cold response cache")))
                        break
        return out


class CollectiveBeforeReconfigure(Rule):
    """Collective issued inside ``except MembershipChanged:`` before
    ``elastic.reconfigure()``.

    ``MembershipChanged`` (elastic.py) means the membership epoch just
    bumped: the engine that raised it is stopping, every in-flight
    collective is failing, and any frame stamped with the old epoch is
    rejected as ``stale_epoch`` by the new control plane (message.h
    FrameHeader).  Retrying the collective from the handler therefore
    hangs or aborts — the protocol model checker derives the wedge
    mechanically (analysis/protocol: the RECONFIG-in-wait interleavings).
    The contract is the serving/worker.py shape: call
    ``elastic.reconfigure()`` FIRST (it re-forms the control plane under
    the new epoch and returns the resize event), rebuild per-epoch state,
    then re-issue work.  Handlers that only clean up and re-raise are
    fine; only collectives issued before any ``reconfigure()`` call in
    the same handler are flagged.
    """

    code = "HVD110"
    name = "collective-before-reconfigure"
    hint = ("call elastic.reconfigure() before issuing collectives from a "
            "MembershipChanged handler (it re-forms the control plane "
            "under the new epoch; old-epoch frames are rejected as "
            "stale_epoch), then rebuild per-epoch state and retry")

    # The engine-level enqueue is how serving/background loops issue work
    # without the public wrappers; it speaks the same stale-epoch protocol.
    _RETRY_CALLS = COLLECTIVE_CALLS | {"enqueue"}

    def _catches_membership_changed(self, handler: ast.ExceptHandler) -> bool:
        if handler.type is None:
            return False
        types = (handler.type.elts if isinstance(handler.type, ast.Tuple)
                 else [handler.type])
        for ty in types:
            path = dotted(ty)
            if path is not None and \
                    path.split(".")[-1] == "MembershipChanged":
                return True
        return False

    def run(self, ctx: Context) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(ctx.module):
            if not isinstance(node, ast.Try):
                continue
            for handler in node.handlers:
                if not self._catches_membership_changed(handler):
                    continue
                calls = [c for stmt in handler.body
                         for c in ast.walk(stmt) if isinstance(c, ast.Call)]
                calls.sort(key=lambda c: (c.lineno, c.col_offset))
                reconfigured = False
                for c in calls:
                    cname = call_name(c)
                    if cname == "reconfigure":
                        reconfigured = True
                    elif cname in self._RETRY_CALLS and not reconfigured:
                        out.append(self.finding(c, (
                            f"'{cname}' issued inside an "
                            f"'except MembershipChanged' handler before "
                            f"elastic.reconfigure(): the epoch just "
                            f"bumped, so the retry's frames are rejected "
                            f"as stale_epoch by the new control plane "
                            f"(or hang against the stopping engine)")))
        return out


RULES: list[Rule] = [
    RankDivergentCollective(),
    UnnamedCollectiveInLoop(),
    NondeterministicName(),
    ImpureJitStep(),
    UnknownAxisName(),
    StaleTopologyConstant(),
    HandTunedContextLayout(),
    UnbucketedServeShape(),
    CollectiveBeforeReconfigure(),
]
