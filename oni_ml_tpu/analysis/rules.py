"""The rule catalog.

Four rules migrate the grep-lints that lived in tests/test_telemetry.py
(monotonic-clock, tuned-constant, quantile, harvest-coverage), now
AST-accurate: a docstring that *mentions* `jax.jit` or `time.time()` no
longer counts, and the hand-kept per-rule allowlists collapse into the
engine's one suppression mechanism.  Five rules are new: retrace-hazard
(Python control flow on non-static jit parameters), hidden-host-sync
(device->host materialization inside hot loops outside a span),
lock-discipline (a lightweight static race detector for the
telemetry/serving thread mesh), journal-schema (record-vocabulary drift
against the committed schema/journal_schema.json), and journal-docs
(every emitted kind documented in docs/observability.md).

docs/analysis.md carries the operator-facing catalog: what each rule
flags, why, and the sanctioned ways out (fix, suppress with reason,
baseline).
"""

from __future__ import annotations

import ast
import os

from .engine import (
    Finding,
    ParsedModule,
    Rule,
    ancestors,
    dotted_name,
    in_loop,
    parent,
    under_span_with,
)

PKG = "oni_ml_tpu/"


def default_rules() -> list:
    return [
        MonotonicClockRule(),
        TunedConstantRule(),
        QuantileRule(),
        HarvestCoverageRule(),
        RetraceHazardRule(),
        HiddenHostSyncRule(),
        HotPathEventLoopRule(),
        LockDisciplineRule(),
        NoPickleWireRule(),
        JournalSchemaRule(),
        JournalDocsRule(),
    ]


# ---------------------------------------------------------------------------
# monotonic-clock — migrated from test_no_bare_time_time_for_span_timing
# ---------------------------------------------------------------------------


class MonotonicClockRule(Rule):
    """`time.time()` is a wall clock: it steps under NTP and is banned
    for interval/span timing everywhere (package, tools, bench).  The
    two legitimate wall-clock TIMESTAMP sites (the journal's `t` field,
    the registry's publish stamp) carry inline suppressions instead of
    the old hand-kept allowlist."""

    id = "monotonic-clock"
    description = ("bare time.time() call (wall clock) where interval "
                   "timing needs a monotonic clock")
    hint = ("use time.monotonic_ns()/time.perf_counter() for intervals; "
            "a true wall-clock timestamp gets "
            "`# lint: ok(monotonic-clock, <why>)`")

    def check(self, mod: ParsedModule, ctx):
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Call)
                    and dotted_name(node.func) == "time.time"):
                yield self.finding(
                    mod, node.lineno,
                    "bare time.time() — wall clocks step under NTP; "
                    "time intervals with a monotonic clock",
                )


# ---------------------------------------------------------------------------
# tuned-constant — migrated from test_no_hardcoded_tuned_constants_...
# ---------------------------------------------------------------------------


class TunedConstantRule(Rule):
    """Measured knob names may take numeric-literal defaults only in
    config.py (the tuned-constant home) and under oni_ml_tpu/plans/
    (the registry).  A literal re-hardcoded at a consumer is exactly
    the drift the plan cache exists to end."""

    id = "tuned-constant"
    description = ("tuned-knob name assigned a numeric literal outside "
                   "config.py / oni_ml_tpu/plans/")
    hint = ("route the value through config or a plans.resolve lookup; "
            "only config.py and plans/ may hold the literal")

    NAMES = frozenset((
        "fused_em_chunk", "host_sync_every", "device_chunk",
        "DEFAULT_CHUNK", "device_score_min", "max_batch", "max_wait_ms",
        "pre_workers", "break_even",
    ))
    ALLOWED = ("oni_ml_tpu/config.py", "oni_ml_tpu/plans/")

    @staticmethod
    def _is_numeric_literal(node) -> bool:
        if isinstance(node, ast.UnaryOp) and isinstance(
                node.op, (ast.USub, ast.UAdd)):
            node = node.operand
        return (isinstance(node, ast.Constant)
                and isinstance(node.value, (int, float))
                and not isinstance(node.value, bool))

    def _target_name(self, t) -> "str | None":
        if isinstance(t, ast.Name):
            return t.id
        if isinstance(t, ast.Attribute):
            return t.attr
        return None

    def check(self, mod: ParsedModule, ctx):
        if not mod.rel.startswith(PKG):
            return
        if any(mod.rel.startswith(p) for p in self.ALLOWED):
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign):
                pairs = [(self._target_name(t), node.value)
                         for t in node.targets]
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                pairs = [(self._target_name(node.target), node.value)]
            elif isinstance(node, ast.Call):
                # Keyword re-hardcoding at a call site
                # (`BatchScorer(..., max_batch=64)`) — the grep
                # version's `name\s*=\s*digit` caught these too.
                pairs = [(kw.arg, kw.value) for kw in node.keywords]
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.Lambda)):
                # Parameter defaults (`def flush(self, max_batch=256)`).
                a = node.args
                pos = [*a.posonlyargs, *a.args]
                pairs = list(zip(
                    (p.arg for p in pos[len(pos) - len(a.defaults):]),
                    a.defaults,
                ))
                pairs += [(p.arg, d) for p, d in
                          zip(a.kwonlyargs, a.kw_defaults)
                          if d is not None]
            else:
                continue
            for name, value in pairs:
                if name not in self.NAMES or value is None \
                        or not self._is_numeric_literal(value):
                    continue
                yield self.finding(
                    mod, value.lineno,
                    f"tuned constant {name!r} hardcoded to a "
                    "numeric literal outside config.py / plans/",
                )


# ---------------------------------------------------------------------------
# quantile — migrated from test_no_adhoc_percentile_math_outside_telemetry
# ---------------------------------------------------------------------------


class QuantileRule(Rule):
    """One quantile estimator: telemetry/spans.Histogram's fixed
    log-boundary buckets.  Ad-hoc percentile math anywhere else (now
    including tools/ and bench.py) would make p99 mean different things
    in different records."""

    id = "quantile"
    description = ("ad-hoc percentile/quantile math outside "
                   "oni_ml_tpu/telemetry/")
    hint = ("observe into a shared telemetry Histogram and read "
            ".quantile()/summary() back")

    CALLS = frozenset((
        "np.percentile", "numpy.percentile", "np.quantile",
        "numpy.quantile", "np.nanpercentile", "np.nanquantile",
        "statistics.quantiles",
    ))

    def check(self, mod: ParsedModule, ctx):
        if mod.rel.startswith(PKG + "telemetry/"):
            return
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Call)
                    and dotted_name(node.func) in self.CALLS):
                yield self.finding(
                    mod, node.lineno,
                    f"{dotted_name(node.func)}() outside telemetry/ — "
                    "quantiles must come from the shared Histogram",
                )


# ---------------------------------------------------------------------------
# harvest-coverage — migrated (AST-accurate) from
# test_every_jit_entry_point_file_is_harvest_covered
# ---------------------------------------------------------------------------


def _jit_nodes(mod: ParsedModule):
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Attribute)
                and dotted_name(node) == "jax.jit"):
            yield node


class HarvestCoverageRule(Rule):
    """Every package file with a real `jax.jit` AST node must appear in
    telemetry/roofline.py's HARVEST_COVERAGE registry (naming its
    cost-analysis harvest hook or exemption), and the registry must
    carry no entries for files without one.  The registry keys are read
    from the parsed dict literal — no import, and a docstring that
    merely mentions jax.jit no longer counts as an entry point (the
    false positive the grep version had)."""

    id = "harvest-coverage"
    description = ("jax.jit entry-point file missing from (or stale in) "
                   "roofline HARVEST_COVERAGE")
    hint = ("register the file in telemetry/roofline.py "
            "HARVEST_COVERAGE, naming the harvest hook or the exemption")

    REGISTRY_REL = PKG + "telemetry/roofline.py"

    def _registry(self, ctx) -> "tuple[dict, int]":
        """({pkg-relative file: entry line}, dict line) parsed from the
        HARVEST_COVERAGE literal."""
        mod = ctx.module(self.REGISTRY_REL)
        if mod is None:
            return {}, 0
        for node in ast.walk(mod.tree):
            if (isinstance(node, (ast.Assign, ast.AnnAssign))):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                named = any(
                    isinstance(t, ast.Name) and t.id == "HARVEST_COVERAGE"
                    for t in targets
                )
                if named and isinstance(node.value, ast.Dict):
                    keys = {}
                    for k in node.value.keys:
                        if isinstance(k, ast.Constant) \
                                and isinstance(k.value, str):
                            keys[k.value] = k.lineno
                    return keys, node.lineno
        return {}, 0

    def finalize(self, ctx):
        registry, registry_line = self._registry(ctx)
        jit_files: dict[str, int] = {}
        for mod in ctx.modules:
            if not mod.rel.startswith(PKG) or mod.rel == self.REGISTRY_REL:
                continue
            for node in _jit_nodes(mod):
                jit_files.setdefault(mod.rel, node.lineno)
        for rel, lineno in sorted(jit_files.items()):
            pkg_rel = rel[len(PKG):]
            if pkg_rel not in registry:
                yield self.finding(
                    rel, lineno,
                    f"jax.jit entry point in {pkg_rel!r} which is not "
                    "registered for cost-analysis harvest",
                )
        for pkg_rel, lineno in sorted(registry.items()):
            rel = PKG + pkg_rel
            mod = ctx.module(rel)
            if mod is None:
                yield self.finding(
                    self.REGISTRY_REL, lineno,
                    f"HARVEST_COVERAGE names {pkg_rel!r}, which does "
                    "not exist",
                    "delete the stale registry entry",
                )
            elif rel not in jit_files:
                yield self.finding(
                    self.REGISTRY_REL, lineno,
                    f"HARVEST_COVERAGE names {pkg_rel!r}, which has no "
                    "jax.jit entry point (drift cuts both ways)",
                    "delete the stale registry entry",
                )


# ---------------------------------------------------------------------------
# retrace-hazard — NEW
# ---------------------------------------------------------------------------


class RetraceHazardRule(Rule):
    """A `jax.jit`-wrapped function whose parameter drives PYTHON
    control flow (`if p:`, `while p`, `p if ... else`, `range(p)`)
    must declare that parameter in static_argnums/static_argnames:
    traced, the comparison raises a concretization error on some paths
    and — worse — silently retraces per distinct value on others.
    models/lda.py's update_alpha is the house style this rule
    cross-checks (explicit static_argnums AND static_argnames).

    Precision notes: only tests reachable through pure
    Compare/BoolOp/Not chains count (`if len(batch) == 2`,
    `if x.shape[0] == 1`, `if isinstance(...)` are trace-stable and
    ignored), and only targets resolvable in the same module are
    analyzed (a jit over an imported function is out of scope)."""

    id = "retrace-hazard"
    description = ("non-static jit parameter used in Python control "
                   "flow (concretization / per-value retrace hazard)")
    hint = ("add the parameter to static_argnames (or bind it via "
            "functools.partial) at the jax.jit site")

    # -- jit-site discovery ------------------------------------------------

    def check(self, mod: ParsedModule, ctx):
        defs = self._local_defs(mod)
        # Dedup per (target, statics), not per target: two jit sites
        # over the same function with DIFFERENT statics are different
        # hazards — first-site-wins would let a properly-static site
        # shadow a bare jax.jit(f) later in the module.
        seen: set = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    statics = self._jit_statics(dec, node)
                    if statics is None:
                        continue
                    key = (id(node), frozenset(statics))
                    if key in seen:
                        continue
                    seen.add(key)
                    yield from self._analyze(mod, node, statics,
                                             node.name)
            elif isinstance(node, ast.Call) \
                    and dotted_name(node.func) == "jax.jit" and node.args:
                target, statics = self._resolve_call_target(
                    node, defs
                )
                if target is None:
                    continue
                key = (id(target), frozenset(statics))
                if key in seen:
                    continue
                seen.add(key)
                label = getattr(target, "name", "<lambda>")
                yield from self._analyze(mod, target, statics, label)

    @staticmethod
    def _local_defs(mod: ParsedModule) -> dict:
        """Module-SCOPE names only: `jax.jit(name)` resolves `name` in
        the module namespace, so a same-named class method must not
        shadow the function actually being jitted."""
        defs: dict[str, ast.AST] = {}
        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[node.name] = node
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Lambda):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        defs[t.id] = node.value
        return defs

    def _jit_statics(self, dec, fn) -> "set | None":
        """For a decorator node: the declared-static parameter names if
        this is a jit decorator, else None."""
        if dotted_name(dec) == "jax.jit":
            return set()
        if isinstance(dec, ast.Call):
            callee = dotted_name(dec.func)
            if callee == "jax.jit":
                return self._statics_from_kwargs(dec.keywords, fn)
            if callee in ("partial", "functools.partial") and dec.args \
                    and dotted_name(dec.args[0]) == "jax.jit":
                return self._statics_from_kwargs(dec.keywords, fn)
        return None

    def _resolve_call_target(self, call: ast.Call, defs: dict):
        """(target_def, static_names) for `jax.jit(X, ...)`; partial-
        bound arguments count as static."""
        arg = call.args[0]
        statics: set[str] = set()
        if isinstance(arg, ast.Call) and dotted_name(arg.func) in (
                "partial", "functools.partial") and arg.args:
            inner = arg.args[0]
            target = self._lookup(inner, defs)
            if target is None:
                return None, set()
            params = self._params(target)
            statics |= {kw.arg for kw in arg.keywords
                        if kw.arg is not None}
            statics |= set(params[: len(arg.args) - 1])
        elif isinstance(arg, ast.Lambda):
            target = arg
        else:
            target = self._lookup(arg, defs)
        if target is None:
            return None, set()
        statics |= self._statics_from_kwargs(call.keywords, target)
        return target, statics

    @staticmethod
    def _lookup(node, defs: dict):
        if isinstance(node, ast.Name):
            return defs.get(node.id)
        if isinstance(node, ast.Lambda):
            return node
        return None

    @staticmethod
    def _params(fn) -> list:
        a = fn.args
        return [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]

    def _statics_from_kwargs(self, keywords, fn) -> set:
        statics: set[str] = set()
        params = self._params(fn)
        for kw in keywords:
            if kw.arg == "static_argnames":
                statics |= set(self._const_strs(kw.value))
            elif kw.arg == "static_argnums":
                for i in self._const_ints(kw.value):
                    if 0 <= i < len(params):
                        statics.add(params[i])
        return statics

    @staticmethod
    def _const_strs(node) -> list:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return [node.value]
        if isinstance(node, (ast.Tuple, ast.List)):
            return [e.value for e in node.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)]
        return []

    @staticmethod
    def _const_ints(node) -> list:
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return [node.value]
        if isinstance(node, (ast.Tuple, ast.List)):
            return [e.value for e in node.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, int)]
        return []

    # -- hazard scan -------------------------------------------------------

    @staticmethod
    def _walk_same_scope(stmt):
        """ast.walk that stops at nested def/lambda boundaries: a
        nested callable's same-named parameter is its OWN binding, not
        the traced argument."""
        stack = [stmt]
        while stack:
            node = stack.pop()
            yield node
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                stack.append(child)

    def _analyze(self, mod, fn, statics: set, label: str):
        dyn = set(self._params(fn)) - statics
        if not dyn:
            return
        body = fn.body if isinstance(body := fn.body, list) else [body]
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # a nested def is its own scope, not fn's
            for node in self._walk_same_scope(stmt):
                tests = []
                if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                    tests.append(node.test)
                elif isinstance(node, ast.Assert):
                    tests.append(node.test)
                for test in tests:
                    for name in sorted(self._bare_names(test) & dyn):
                        yield self.finding(
                            mod, test.lineno,
                            f"parameter {name!r} of jitted "
                            f"{label!r} drives Python control flow "
                            "but is not declared static",
                        )
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name) \
                        and node.func.id == "range":
                    for a in node.args:
                        if isinstance(a, ast.Name) and a.id in dyn:
                            yield self.finding(
                                mod, node.lineno,
                                f"parameter {a.id!r} of jitted "
                                f"{label!r} sets a Python range() "
                                "bound but is not declared static",
                            )

    @classmethod
    def _bare_names(cls, test) -> set:
        """Names reachable from a test through ONLY
        Compare/BoolOp/Not — i.e. uses whose truthiness concretizes a
        traced value.  Anything behind a call, attribute (x.shape),
        or subscript is trace-stable or out of scope."""
        out: set[str] = set()
        if isinstance(test, ast.Name):
            out.add(test.id)
        elif isinstance(test, ast.Compare):
            for sub in (test.left, *test.comparators):
                out |= cls._bare_names(sub)
        elif isinstance(test, ast.BoolOp):
            for sub in test.values:
                out |= cls._bare_names(sub)
        elif isinstance(test, ast.UnaryOp) \
                and isinstance(test.op, ast.Not):
            out |= cls._bare_names(test.operand)
        return out


# ---------------------------------------------------------------------------
# hidden-host-sync — NEW
# ---------------------------------------------------------------------------


class HiddenHostSyncRule(Rule):
    """In the dispatch-critical modules, materializing a device value
    on the host inside a loop (`float(x)`, `int(x)`, `bool(x)`,
    `x.item()`, `np.asarray(x)`) blocks the loop on the device — the
    exact stall the chunked/double-buffered drivers exist to amortize.
    Deliberate syncs are fine when they are VISIBLE: wrap them in a
    `maybe_span(...)`/`rec.span(...)` block (the flight recorder then
    prices them, e.g. `em.host_sync`) or suppress with a reason (e.g.
    the value is a host ndarray, not a device buffer)."""

    id = "hidden-host-sync"
    description = ("host materialization inside a hot loop outside a "
                   "telemetry span")
    hint = ("wrap the sync in `with maybe_span(...)` so the flight "
            "recorder prices it, or suppress with a reason if the "
            "value is host-side")

    HOT_MODULES = frozenset((
        PKG + "models/fused.py",
        PKG + "models/lda.py",
        PKG + "scoring/pipeline.py",
        PKG + "serving/batcher.py",
    ))
    NAME_COERCIONS = frozenset(("float", "int", "bool"))
    ARRAY_CALLS = frozenset((
        "np.asarray", "numpy.asarray", "np.array", "numpy.array",
    ))

    def check(self, mod: ParsedModule, ctx):
        if mod.rel not in self.HOT_MODULES:
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not in_loop(node):
                continue
            label = self._sync_label(node)
            if label is None or under_span_with(node):
                continue
            yield self.finding(
                mod, node.lineno,
                f"{label} inside a hot loop blocks on the device "
                "outside any telemetry span",
            )

    def _sync_label(self, node: ast.Call) -> "str | None":
        simple = (ast.Name, ast.Attribute, ast.Subscript)
        func = node.func
        if isinstance(func, ast.Name) \
                and func.id in self.NAME_COERCIONS \
                and len(node.args) == 1 and not node.keywords \
                and isinstance(node.args[0], simple):
            return f"{func.id}()"
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not node.args:
            return ".item()"
        name = dotted_name(func)
        if name in self.ARRAY_CALLS and node.args \
                and isinstance(node.args[0], simple):
            return f"{name}()"
        return None


# ---------------------------------------------------------------------------
# hot-path-event-loop — NEW
# ---------------------------------------------------------------------------


class HotPathEventLoopRule(Rule):
    """In the serving/continuous flush paths and the featurize plane,
    a Python-level loop that CALLS something per event is the scaling
    ceiling the device featurizer exists to remove: at fleet rates the
    interpreter dispatch dominates the flush.  The rule flags `for`
    statements and comprehensions that iterate an event-shaped
    collection (rows/lines/col/...) and invoke a non-trivial call per
    element.

    Sanctioned per-event loops stay, visibly: the golden-oracle host
    featurizers (the byte-identity reference the device compiler is
    pinned against) and the per-UNIQUE memo passes (entropy/port
    interning — O(distinct), not O(events)) carry inline
    `# lint: ok(hot-path-event-loop, <why>)` suppressions."""

    id = "hot-path-event-loop"
    description = ("per-event Python loop with a call in a serving/"
                   "continuous flush path")
    hint = ("vectorize (numpy pass or the device featurize plane), "
            "hoist to a per-unique memo, or suppress with a reason "
            "(golden-oracle host featurizers are the sanctioned case)")

    HOT_MODULES = frozenset((
        PKG + "serving/fleet.py",
        PKG + "serving/batcher.py",
        PKG + "serving/events.py",
        PKG + "runner/continuous.py",
        PKG + "sources/device.py",
        PKG + "sources/generic.py",
        PKG + "features/flow.py",
        PKG + "features/dns.py",
    ))
    #: names that hold per-event collections in these modules — the
    #: rule keys on the ITERATION SOURCE, so per-tenant / per-field /
    #: per-source loops (small, bounded) never trip it.
    EVENT_NAMES = frozenset((
        "rows", "lines", "raws", "events", "values", "col", "cols",
        "uq", "queries", "words",
    ))
    #: calls cheap enough to never matter (C-level, no dispatch fan-out).
    CHEAP = frozenset(("len",))

    def check(self, mod: ParsedModule, ctx):
        if mod.rel not in self.HOT_MODULES:
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.For):
                src, bodies = node.iter, node.body
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.GeneratorExp, ast.DictComp)):
                src = node.generators[0].iter
                bodies = [node.key, node.value] if isinstance(
                    node, ast.DictComp) else [node.elt]
                bodies += [c.iter for c in node.generators[1:]]
                bodies += [i for c in node.generators for i in c.ifs]
            else:
                continue
            name = self._iter_base(src)
            if name not in self.EVENT_NAMES:
                continue
            call = self._per_element_call(bodies)
            if call is None:
                continue
            yield self.finding(
                mod, node.lineno,
                f"per-event Python loop over {name!r} calls {call} "
                "per element in a flush/featurize hot path",
            )

    def _iter_base(self, src) -> "str | None":
        """The collection NAME a loop iterates, through the wrappers
        that preserve per-event cardinality: enumerate/zip/sorted/
        reversed, `.tolist()`, and a subscript of a name (`cols[i]` is
        one per-event column)."""
        if isinstance(src, ast.Name):
            return src.id
        if isinstance(src, ast.Subscript):
            return self._iter_base(src.value)
        if isinstance(src, ast.Call):
            fname = dotted_name(src.func)
            if fname in ("enumerate", "zip", "sorted", "reversed") \
                    and src.args:
                for a in src.args:
                    base = self._iter_base(a)
                    if base is not None:
                        return base
                return None
            if isinstance(src.func, ast.Attribute) \
                    and src.func.attr == "tolist":
                return self._iter_base(src.func.value)
        return None

    def _per_element_call(self, bodies) -> "str | None":
        """The first non-cheap call made per iteration (nested defs
        are their own scope and don't count)."""
        stack = [b for b in bodies if b is not None]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(node, ast.Call):
                name = dotted_name(node.func) or (
                    node.func.attr if isinstance(node.func,
                                                 ast.Attribute)
                    else "<call>")
                if name not in self.CHEAP:
                    return f"{name}()"
            stack.extend(ast.iter_child_nodes(node))
        return None


# ---------------------------------------------------------------------------
# lock-discipline — NEW
# ---------------------------------------------------------------------------


class LockDisciplineRule(Rule):
    """Per class that owns a lock (threading.Lock/RLock/Condition
    assigned in __init__, or any `with self._lock`-style guard):

    1. an attribute accessed under the lock anywhere must not be
       WRITTEN outside it elsewhere (mixed guarding — the classic
       forgot-the-lock race);
    2. when the class also starts threads, an attribute written outside
       __init__ without the lock and touched from more than one method
       is flagged too — that is cross-thread shared state with no
       guard at all (the exporter/heartbeat/batcher mesh pattern).

    Helper methods documented as running under the caller's lock
    ("caller holds self._lock" in the docstring, or a name ending in
    `_locked`) are exempt."""

    id = "lock-discipline"
    description = ("shared attribute mutated without the lock that "
                   "guards it elsewhere")
    hint = ("take the class's lock around the write, or document a "
            "lock-held helper (docstring 'caller holds self._lock' / "
            "name ending in _locked)")

    LOCK_FACTORY_SUFFIXES = (".Lock", ".RLock", ".Condition",
                             ".Semaphore", ".BoundedSemaphore")
    LOCKISH_NAMES = ("lock", "cond", "mutex")

    def check(self, mod: ParsedModule, ctx):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_class(mod, node)

    # -- per-class analysis ------------------------------------------------

    def _check_class(self, mod, cls: ast.ClassDef):
        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]
        lock_attrs = self._lock_attrs(cls, methods)
        if not lock_attrs:
            return
        threaded = any(
            isinstance(n, ast.Call)
            and dotted_name(n.func) == "threading.Thread"
            for n in ast.walk(cls)
        )
        # accesses[attr] = list of (method, is_write, under_lock, line)
        accesses: dict[str, list] = {}
        for m in methods:
            exempt = self._lock_held_helper(m)
            for attr, is_write, lineno, locked in self._self_accesses(
                    m, lock_attrs):
                if attr in lock_attrs:
                    continue
                accesses.setdefault(attr, []).append(
                    (m.name, is_write, locked or exempt, lineno)
                )
        for attr, acc in sorted(accesses.items()):
            guarded = any(locked for _, _, locked, _ in acc)
            methods_touching = {m for m, _, _, _ in acc}
            for m_name, is_write, locked, lineno in acc:
                if not is_write or locked or m_name in (
                        "__init__", "__new__", "__post_init__"):
                    continue
                if guarded:
                    yield self.finding(
                        mod, lineno,
                        f"{cls.name}.{attr} is guarded by "
                        f"{'/'.join(sorted(lock_attrs))} elsewhere but "
                        f"written without it in {m_name}()",
                    )
                elif threaded and len(methods_touching) > 1:
                    yield self.finding(
                        mod, lineno,
                        f"{cls.name}.{attr} is written in {m_name}() "
                        "without any lock, in a thread-spawning class "
                        "where other methods also touch it",
                    )

    def _lock_attrs(self, cls, methods) -> set:
        out: set[str] = set()
        for m in methods:
            if m.name != "__init__":
                continue
            for node in ast.walk(m):
                if isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Call):
                    callee = dotted_name(node.value.func)
                    if any(callee.endswith(s)
                           for s in self.LOCK_FACTORY_SUFFIXES):
                        for t in node.targets:
                            if isinstance(t, ast.Attribute) \
                                    and isinstance(t.value, ast.Name) \
                                    and t.value.id == "self":
                                out.add(t.attr)
        for node in ast.walk(cls):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    expr = item.context_expr
                    if isinstance(expr, ast.Attribute) \
                            and isinstance(expr.value, ast.Name) \
                            and expr.value.id == "self" \
                            and any(n in expr.attr
                                    for n in self.LOCKISH_NAMES):
                        out.add(expr.attr)
        return out

    @staticmethod
    def _lock_held_helper(m) -> bool:
        if m.name.endswith("_locked"):
            return True
        doc = ast.get_docstring(m) or ""
        low = doc.lower()
        return "caller holds" in low or "holds self._lock" in low \
            or "holds self._cond" in low

    def _self_accesses(self, method, lock_attrs: set):
        """(attr, is_write, lineno, under_lock) for every self.X access
        in `method`, including its nested functions (worker closures
        share the instance)."""
        for node in ast.walk(method):
            attr = None
            is_write = False
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "self":
                attr = node.attr
                is_write = isinstance(node.ctx, (ast.Store, ast.Del))
            elif isinstance(node, ast.AugAssign) \
                    and isinstance(node.target, ast.Attribute) \
                    and isinstance(node.target.value, ast.Name) \
                    and node.target.value.id == "self":
                continue  # the Attribute child carries Store ctx already
            if attr is None:
                continue
            yield attr, is_write, node.lineno, self._under_lock(
                node, method, lock_attrs)

    @staticmethod
    def _under_lock(node, method, lock_attrs: set) -> bool:
        for a in ancestors(node):
            if a is method:
                return False
            if isinstance(a, (ast.With, ast.AsyncWith)):
                for item in a.items:
                    expr = item.context_expr
                    if isinstance(expr, ast.Attribute) \
                            and isinstance(expr.value, ast.Name) \
                            and expr.value.id == "self" \
                            and expr.attr in lock_attrs:
                        return True
        return False


# ---------------------------------------------------------------------------
# journal-schema — NEW
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# no-pickle-wire — the columnar wire's containment rule
# ---------------------------------------------------------------------------


class NoPickleWireRule(Rule):
    """Pickle on the serving wire deserializes attacker-adjacent bytes
    with an arbitrary-code codec and pins both peers to one Python.
    The columnar wire (serving/wire.py) replaced it; what remains is
    the ONE negotiated fallback module (serving/wire_pickle.py), whose
    two call sites carry reasoned suppressions.  This rule keeps the
    budget at exactly that: any new pickle call — or a
    ``allow_pickle=True`` numpy load, the same codec by the back
    door — inside the serving layer or the TCP membership transport
    fails the lint."""

    id = "no-pickle-wire"
    description = ("pickle (or allow_pickle=True) in the serving/"
                   "membership layer outside the negotiated fallback")
    hint = ("encode through serving/wire.py's columnar frames; a "
            "deliberate non-wire pickle surface gets "
            "`# lint: ok(no-pickle-wire, <why>)`")

    SCOPES = ("oni_ml_tpu/serving/", "oni_ml_tpu/parallel/membership.py")
    CALLS = frozenset((
        "pickle.dumps", "pickle.loads", "pickle.dump", "pickle.load",
        "pickle.Pickler", "pickle.Unpickler",
    ))

    def check(self, mod: ParsedModule, ctx):
        if not any(mod.rel.startswith(s) for s in self.SCOPES):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in self.CALLS:
                yield self.finding(
                    mod, node.lineno,
                    f"{name}() on the serving/membership path — the "
                    "wire is columnar; pickle lives only in the "
                    "negotiated wire_pickle fallback",
                )
                continue
            for kw in node.keywords:
                if (kw.arg == "allow_pickle"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True):
                    yield self.finding(
                        mod, kw.value.lineno,
                        "allow_pickle=True load in the serving layer "
                        "— object-dtype arrays round-trip through the "
                        "pickle codec",
                    )


def _extracted_schema(ctx) -> dict:
    """The journal vocabulary extracted from this run's modules, via
    ctx.cache so the two journal rules walk the ASTs once."""
    if "journal_schema" not in ctx.cache:
        from . import schema as schema_mod

        ctx.cache["journal_schema"] = schema_mod.extract_schema(
            ctx.modules)
    return ctx.cache["journal_schema"]


class JournalSchemaRule(Rule):
    """The journal record vocabulary (every `kind` and its field set,
    statically harvested from journal_record/append/annotation sites)
    must match the committed analysis/schema/journal_schema.json: a
    new record kind, a silently dropped field, or an undeclared one
    fails CI until the schema (and docs) are deliberately updated."""

    id = "journal-schema"
    description = ("journal record vocabulary drifted from the "
                   "committed schema/journal_schema.json")
    hint = ("intentional change? update docs/observability.md, then "
            "run `python tools/graftlint.py --update-schema`")

    SCHEMA_REL = PKG + "analysis/schema/journal_schema.json"

    def __init__(self, schema: "dict | None" = None) -> None:
        self._schema_override = schema

    def finalize(self, ctx):
        from . import schema as schema_mod

        extracted = _extracted_schema(ctx)
        committed = (self._schema_override
                     if self._schema_override is not None
                     else schema_mod.load_schema(
                         os.path.join(ctx.root, self.SCHEMA_REL)))
        if not committed:
            if not extracted:
                return  # nothing emitted, nothing to contract
            yield self.finding(
                self.SCHEMA_REL, 0,
                "committed journal schema is missing or empty",
                "run `python tools/graftlint.py --update-schema`",
            )
            return
        for kind, message in schema_mod.diff_schema(extracted, committed):
            yield self.finding(self.SCHEMA_REL, 0, message)


class JournalDocsRule(Rule):
    """Every emitted record kind must be documented: the kind's
    backticked name has to appear in docs/observability.md (whose
    record table is the narrative copy of the authoritative
    journal_schema.json)."""

    id = "journal-docs"
    description = ("journal record kind missing from "
                   "docs/observability.md")
    hint = ("add the kind to the record-kinds table in "
            "docs/observability.md")

    DOC_REL = "docs/observability.md"

    def finalize(self, ctx):
        extracted = _extracted_schema(ctx)
        if not extracted:
            return  # no record vocabulary, nothing to document
        doc_path = os.path.join(ctx.root, self.DOC_REL)
        if not os.path.exists(doc_path):
            yield self.finding(
                self.DOC_REL, 0,
                "docs/observability.md not found — the journal "
                "vocabulary has no narrative documentation",
            )
            return
        with open(doc_path, encoding="utf-8") as f:
            doc = f.read()
        for kind in sorted(extracted):
            if f"`{kind}`" not in doc:
                yield self.finding(
                    self.DOC_REL, 0,
                    f"record kind {kind!r} is emitted but never "
                    "documented in docs/observability.md",
                )
