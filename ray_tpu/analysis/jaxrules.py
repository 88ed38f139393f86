"""RL020-RL024: the accelerator-hazard rule family over the JAX surface.

These rules run on :mod:`ray_tpu.analysis.dataflow` — a per-function
CFG with traced / static-python / host-materialized value provenance —
and target the XLA invariants the runtime compile-once counters guard
only on executed paths (docs/ANALYSIS.md has the catalog with
before/after examples):

- RL020 retrace-hazard-v2   — Python control flow or host concretization
                              of a traced value inside a jitted function;
                              shape-derived ints fed into static_argnums;
                              jit constructed per call (the retired
                              lexical RL006's checks, folded in)
- RL021 host-sync-in-hot-loop — device→host materialization inside a
                              loop of a per-step/per-token method; the
                              prescribed idiom is one sync before the
                              loop, indexing the host copy after
- RL022 use-after-donate    — an argument listed in ``donate_argnums``
                              read again on any CFG path after the
                              jitted call without being rebound from
                              the call's result
- RL023 sharding-spec-hygiene (whole-program) — PartitionSpec axes not
                              declared by any mesh in the package;
                              trailing-``None`` specs jit normalizes
                              into a different cache key (the PR-8 bug)
- RL024 jit-boundary-capture — a jitted closure capturing a mutable
                              ``self`` attribute the class also mutates
                              in steady state (silent staleness: jit
                              baked the first-trace value in)

Per-file rules fire only in files that mention jax at all, so the
control plane never pays for the dataflow pass.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from ray_tpu.analysis.engine import (
    FileContext,
    Finding,
    dotted,
    last_segment,
    project_rule,
    rule,
    walk_excluding_nested_functions,
)
from ray_tpu.analysis import dataflow as df

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)

_JAX_SCOPE = ("JAX surface: files importing jax (models/, inference/, "
              "ops/, train/, shardgroup/)")

_FACTORY_PREFIXES = ("make", "build", "create", "get", "init", "setup",
                     "compile", "_make", "_build", "_create", "_get",
                     "_init", "_setup", "_compile", "__init__")
_PERSTEP_NAMES = {"forward", "decode", "prefill", "generate", "sample"}
_HOT_NAMES = {"_run", "decode", "prefill", "generate", "sample",
              "propose", "verify", "forward"}


def _uses_jax(ctx: FileContext) -> bool:
    return "jax" in ctx.source or "jnp" in ctx.source


def _functions(ctx: FileContext) -> Iterator[ast.AST]:
    for node in ast.walk(ctx.tree):
        if isinstance(node, _FUNC_NODES):
            yield node


class _FileFlows:
    """Shared per-file dataflow state, computed once and reused by
    RL020/RL021/RL022/RL024 (the engine hands every rule the same
    FileContext object)."""

    def __init__(self, ctx: FileContext):
        self.ctx = ctx
        self.sites = df.jit_sites(ctx)
        self.bound: Dict[str, df.JitSite] = {
            s.bound_to: s for s in self.sites if s.bound_to}
        self.jit_fn_ids = {id(s.fn_def) for s in self.sites
                           if s.fn_def is not None}
        self._flows: Dict[int, df.FlowAnalysis] = {}
        self._traced_flows: Dict[int, df.FlowAnalysis] = {}

    def flow(self, fn: ast.AST) -> df.FlowAnalysis:
        """Provenance of an ordinary (host-side) function body."""
        got = self._flows.get(id(fn))
        if got is None:
            got = df.FlowAnalysis(self.ctx, fn,
                                  device_callables=self.bound)
            self._flows[id(fn)] = got
        return got

    def traced_flow(self, site: df.JitSite) -> df.FlowAnalysis:
        """Provenance INSIDE a jitted function: non-static formals are
        tracers."""
        fn = site.fn_def
        got = self._traced_flows.get(id(fn))
        if got is None:
            seed = {name: df.TRACED for name in site.traced_params()}
            got = df.FlowAnalysis(self.ctx, fn, seed=seed,
                                  device_callables=self.bound)
            self._traced_flows[id(fn)] = got
        return got


def _file_flows(ctx: FileContext) -> _FileFlows:
    got = getattr(ctx, "_jax_flows", None)
    if got is None or got.ctx is not ctx:
        got = _FileFlows(ctx)
        ctx._jax_flows = got
    return got


def _in_loop_within(ctx: FileContext, node: ast.AST,
                    fn: ast.AST) -> bool:
    for anc in ctx.ancestors(node):
        if anc is fn:
            return False
        if isinstance(anc, (ast.For, ast.AsyncFor, ast.While)):
            return True
    return False


def _enclosing_stmt(ctx: FileContext, node: ast.AST,
                    cfg: df.CFG) -> Optional[ast.stmt]:
    ids = {id(s) for s in cfg.stmts}
    cur: Optional[ast.AST] = node
    while cur is not None:
        if id(cur) in ids:
            return cur
        cur = ctx.parent(cur)
    return None


# =====================================================================
# RL020 retrace-hazard-v2
# =====================================================================


def _cached_behind_none_check(ctx: FileContext, call: ast.Call) -> bool:
    for anc in ctx.ancestors(call):
        if isinstance(anc, _FUNC_NODES):
            return False
        if isinstance(anc, ast.If):
            test = ast.unparse(anc.test)
            if "is None" in test or "not " in test:
                return True
    return False


def _lexical_retrace(ctx: FileContext,
                     flows: _FileFlows) -> Iterator[Finding]:
    """The retired RL006's checks: jit constructed in a loop or a
    per-step method instead of cached at factory scope."""
    for site in flows.sites:
        if site.call is None:
            continue                       # decorator: module scope
        if site.in_loop and not _cached_behind_none_check(ctx, site.call):
            yield ctx.finding(
                site.call, "RL020",
                "jax.jit constructed inside a loop — every iteration "
                "builds a fresh trace cache and recompiles; hoist the "
                "jit to module/factory scope")
            continue
        name = site.enclosing_fn
        if name is None:
            continue
        lowered = name.lower()
        if lowered.startswith(_FACTORY_PREFIXES):
            continue
        perstep = ("step" in lowered) or (lowered in _PERSTEP_NAMES)
        if perstep and not _cached_behind_none_check(ctx, site.call):
            yield ctx.finding(
                site.call, "RL020",
                f"jax.jit constructed inside per-step method '{name}' — "
                "each call recompiles; cache the jitted callable at "
                "factory scope or on self behind an `is None` check")


def _traced_body_hazards(ctx: FileContext,
                         flows: _FileFlows) -> Iterator[Finding]:
    seen: Set[int] = set()
    for site in flows.sites:
        fn = site.fn_def
        if fn is None or isinstance(fn, ast.Lambda) or id(fn) in seen:
            continue
        seen.add(id(fn))
        flow = flows.traced_flow(site)
        for stmt in flow.cfg.stmts:
            if isinstance(stmt, (ast.If, ast.While)) and \
                    df.is_traced(flow.mask(stmt.test)):
                kind = "if" if isinstance(stmt, ast.If) else "while"
                yield ctx.finding(
                    stmt, "RL020",
                    f"Python `{kind}` on a traced value inside jitted "
                    f"function '{getattr(fn, 'name', '<lambda>')}' — "
                    "the tracer cannot be coerced to bool (trace-time "
                    "error or silent retrace per value); use "
                    "jax.lax.cond/while_loop or mark the operand "
                    "static")
            elif isinstance(stmt, (ast.For, ast.AsyncFor)) and \
                    df.is_traced(flow.mask(stmt.iter)):
                yield ctx.finding(
                    stmt, "RL020",
                    "Python loop over a traced value inside jitted "
                    f"function '{getattr(fn, 'name', '<lambda>')}' — "
                    "the loop unrolls at trace time against concrete "
                    "iteration; use jax.lax.fori_loop/scan")
        for ev in flow.materializations:
            yield ctx.finding(
                ev.node, "RL020",
                f"host materialization ({ev.kind}) of a traced value "
                "inside jitted function "
                f"'{getattr(fn, 'name', '<lambda>')}' — the value has "
                "no concrete data at trace time "
                "(ConcretizationTypeError, or a silent constant burned "
                "into the program); keep the computation in jnp or "
                "move the sync outside the jit boundary")


def _static_arg_feedback(ctx: FileContext,
                         flows: _FileFlows) -> Iterator[Finding]:
    """Shape-derived ints fed back into a static_argnums position:
    every distinct runtime shape mints a new cache entry."""
    for fn in _functions(ctx):
        if id(fn) in flows.jit_fn_ids:
            continue
        calls = [c for c in walk_excluding_nested_functions(fn)
                 if isinstance(c, ast.Call)]
        relevant = []
        for call in calls:
            site = flows.bound.get(dotted(call.func) or "")
            if site is not None and (site.static_argnums
                                     or site.static_argnames):
                relevant.append((call, site))
        if not relevant:
            continue
        flow = flows.flow(fn)
        for call, site in relevant:
            static_exprs: List[ast.AST] = []
            for pos in site.static_argnums:
                if pos < len(call.args):
                    static_exprs.append(call.args[pos])
            for kw in call.keywords:
                if kw.arg in site.static_argnames:
                    static_exprs.append(kw.value)
            for expr in static_exprs:
                mask = flow.mask(expr)
                if df.tag_of(mask) == df.STATIC and \
                        df.is_shape_derived(mask):
                    yield ctx.finding(
                        expr, "RL020",
                        "shape-derived value fed into a static arg of "
                        f"jitted '{site.bound_to}' — every distinct "
                        "runtime shape recompiles (unbounded cache "
                        "growth); pad to a fixed shape or derive the "
                        "static from config, not from a per-call array")


@rule("RL020", "retrace-hazard-v2: traced-value control flow, host "
               "concretization, or shape→static feedback inside/around "
               "jitted functions (supersedes RL006)",
      scope=_JAX_SCOPE)
def check_retrace_v2(ctx: FileContext) -> Iterable[Finding]:
    if not _uses_jax(ctx):
        return
    flows = _file_flows(ctx)
    yield from _lexical_retrace(ctx, flows)
    yield from _traced_body_hazards(ctx, flows)
    yield from _static_arg_feedback(ctx, flows)


# =====================================================================
# RL021 host-sync-in-hot-loop
# =====================================================================
#
# The inference engine's decode loop budget is one device sync per
# execution: `self._tokens, self._arenas = self._call(...)`, then at
# harvest ONE `np.asarray(rec.tokens)` before the per-request
# bookkeeping loop reads plain host memory.  A
# materializer inside the loop instead blocks on the device once per
# request per token.  The provenance layer is what keeps this precise:
# `int(host_copy[slot])` after the hoisted sync is silent, `int(nxt[
# slot])` on the device value fires.


def _is_hot(name: str) -> bool:
    low = name.lower()
    return "step" in low or low in _HOT_NAMES or low.endswith("_loop")


@rule("RL021", "host-sync-in-hot-loop: device value materialized to "
               "host inside a loop of a per-step/per-token method",
      scope=_JAX_SCOPE)
def check_host_sync_in_hot_loop(ctx: FileContext) -> Iterable[Finding]:
    if not _uses_jax(ctx):
        return
    flows = _file_flows(ctx)
    for fn in _functions(ctx):
        if not _is_hot(fn.name) or id(fn) in flows.jit_fn_ids:
            continue
        flow = flows.flow(fn)
        for ev in flow.materializations:
            if not (ev.in_comprehension
                    or _in_loop_within(ctx, ev.stmt, fn)):
                continue                   # the deliberate post-step sync
            yield ctx.finding(
                ev.node, "RL021",
                f"host sync ({ev.kind}) of a device value inside a loop "
                f"of per-step method '{fn.name}' — every iteration "
                "blocks on the device; sync once before the loop "
                "(host = np.asarray(x)) and index the host copy")


# =====================================================================
# RL022 use-after-donate
# =====================================================================
#
# `donate_argnums` hands the argument's buffer to XLA: after the call
# the old array is invalid (reading it raises, or worse, returns
# aliased garbage on some backends).  The safe idiom is the engine's
# arena lifecycle: `toks, self._arenas = self._call(..., self._arenas,
# ...)` — the donated name is rebound from the call's result in the
# same statement, and the failure path rebuilds the arenas outright.


def _donated_call_sites(flows: _FileFlows, fn: ast.AST
                        ) -> Iterator[Tuple[ast.Call, df.JitSite, int]]:
    """(call, site, base) where call.args[base + d] is the expression
    donated for argnum d — base 0 for direct calls, fn-arg-index + 1
    for dispatch wrappers handed the jitted callable."""
    for call in walk_excluding_nested_functions(fn):
        if not isinstance(call, ast.Call):
            continue
        site = flows.bound.get(dotted(call.func) or "")
        if site is not None and site.donate_argnums:
            yield call, site, 0
            continue
        for i, a in enumerate(call.args):
            d = dotted(a)
            s = flows.bound.get(d or "")
            if s is not None and s.donate_argnums:
                yield call, s, i + 1
                break


@rule("RL022", "use-after-donate: donate_argnums argument read on a "
               "CFG path after the jitted call without rebinding",
      scope=_JAX_SCOPE)
def check_use_after_donate(ctx: FileContext) -> Iterable[Finding]:
    if not _uses_jax(ctx):
        return
    flows = _file_flows(ctx)
    if not any(s.donate_argnums for s in flows.bound.values()):
        return
    for fn in _functions(ctx):
        cfg: Optional[df.CFG] = None
        for call, site, base in _donated_call_sites(flows, fn):
            if cfg is None:
                cfg = df.build_cfg(fn)
            stmt = _enclosing_stmt(ctx, call, cfg)
            if stmt is None:
                continue
            for dn in site.donate_argnums:
                idx = base + dn
                if idx >= len(call.args):
                    continue
                dname = dotted(call.args[idx])
                if dname is None:
                    continue
                if df.writes_name(stmt, dname):
                    continue               # rebound from the result
                hit = df.first_read_after(cfg, stmt, dname)
                if hit is None:
                    continue
                read_stmt, _node = hit
                yield ctx.finding(
                    read_stmt, "RL022",
                    f"`{dname}` was donated to jitted "
                    f"'{site.bound_to}' (donate_argnums={dn}) at line "
                    f"{call.lineno} and is read here without being "
                    "rebound — the buffer now belongs to XLA and the "
                    "old array is invalid; rebind it from the call's "
                    "result (`new, {0} = fn(...)`) or drop the "
                    "donation".format(dname))


# =====================================================================
# RL023 sharding-spec-hygiene (whole-program)
# =====================================================================
#
# Joined over the per-file `jax_extract` summaries (dataflow.
# jax_extract, cached with the project graph): every literal
# PartitionSpec axis must be declared by SOME mesh in the package, and
# no spec may end in a literal None — jit normalizes trailing-None
# output specs away, so the annotated program and the inferred one get
# DIFFERENT cache keys and the second call recompiles (the PR-8 arena
# bug, docs/INFERENCE.md).


@project_rule("RL023", "sharding-spec-hygiene: PartitionSpec axes "
                       "declared by no mesh; trailing-None specs jit "
                       "normalizes into a different cache key",
              scope=_JAX_SCOPE)
def rl023_sharding_spec_hygiene(graph) -> Iterable[Finding]:
    declared: Set[str] = set()
    for m in graph.mesh_axes:
        declared.update(m["axes"])
    for s in graph.specs:
        if s.get("trailing_none"):
            yield Finding(
                s["file"], s["line"], "RL023",
                "PartitionSpec ends in a literal None — jit drops "
                "trailing Nones when normalizing specs, so this "
                "annotation and the inferred one produce different jit "
                "cache keys (one silent recompile per program); drop "
                "the trailing None")
        if not declared:
            continue                       # no mesh in the tree: nothing
            # to check axes against (fixture files)
        where = (f" (partition rule {s['rule']!r})"
                 if s.get("rule") else "")
        for dim in s["dims"]:
            axes = dim if isinstance(dim, list) else [dim]
            for a in axes:
                if isinstance(a, str) and a != "?" and a not in declared:
                    yield Finding(
                        s["file"], s["line"], "RL023",
                        f"PartitionSpec{where} names mesh axis '{a}' "
                        "but no mesh in the package declares it "
                        f"(declared: {', '.join(sorted(declared))}) — "
                        "placement fails at runtime with an "
                        "unknown-axis error, or silently replicates if "
                        "the spec is filtered; fix the axis name or "
                        "declare the mesh")


# =====================================================================
# RL024 jit-boundary-capture
# =====================================================================
#
# A closure passed to jax.jit captures `self` by reference, but jit
# reads captured array values ONCE, at trace time, and burns them into
# the compiled program as constants.  If the class later rebinds the
# attribute in steady state, the program silently keeps computing with
# the stale value — no error, no recompile, wrong numbers.  The static
# sibling of the compile-once counters.


def _steady_state_mutations(cls: ast.ClassDef) -> Dict[str, Tuple[str, int]]:
    out: Dict[str, Tuple[str, int]] = {}
    for m in cls.body:
        if not isinstance(m, _FUNC_NODES):
            continue
        if m.name.lower().startswith(_FACTORY_PREFIXES):
            continue                       # construction, not steady state
        for sub in walk_excluding_nested_functions(m):
            targets: List[ast.AST] = []
            if isinstance(sub, ast.Assign):
                targets = list(sub.targets)
            elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
                targets = [sub.target]
            for tgt in targets:
                flat = tgt.elts if isinstance(tgt, (ast.Tuple, ast.List)) \
                    else [tgt]
                for t in flat:
                    d = dotted(t)
                    if d and d.startswith("self.") and d.count(".") == 1:
                        out.setdefault(d[len("self."):],
                                       (m.name, sub.lineno))
    return out


@rule("RL024", "jit-boundary-capture: jitted closure captures a "
               "mutable self attribute the class rebinds in steady "
               "state",
      scope=_JAX_SCOPE)
def check_jit_boundary_capture(ctx: FileContext) -> Iterable[Finding]:
    if not _uses_jax(ctx):
        return
    flows = _file_flows(ctx)
    closure_sites = [
        s for s in flows.sites
        if s.fn_def is not None
        and ctx.enclosing_function(s.fn_def) is not None]
    if not closure_sites:
        return
    for site in closure_sites:
        cls = ctx.enclosing_class(site.fn_def)
        if cls is None:
            continue
        steady = _steady_state_mutations(cls)
        if not steady:
            continue
        reported: Set[str] = set()
        for sub in ast.walk(site.fn_def):
            d = dotted(sub) if isinstance(sub, ast.Attribute) else None
            if not d or not d.startswith("self.") or d.count(".") != 1:
                continue
            if not isinstance(sub.ctx, ast.Load):
                continue
            attr = d[len("self."):]
            if attr not in steady or attr in reported:
                continue
            reported.add(attr)
            mname, mline = steady[attr]
            yield ctx.finding(
                sub, "RL024",
                f"jitted closure captures self.{attr}, which "
                f"'{mname}' (line {mline}) rebinds in steady state — "
                "jit reads captures once at trace time and bakes the "
                "value into the compiled program, so later "
                "assignments are silently ignored; pass the value as "
                "a traced argument or rebuild the program on change")
