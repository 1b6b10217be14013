"""Package hygiene: every module in odigos_tpu is imported from somewhere
(no dead modules — round-2 review item 9's CI check), the feature-gate
system actually gates behavior, every jit path declares its shape
bucketing, and every metric recorded through the Meter carries a
Prometheus-legal name with sanitized label values."""

import ast
import os
import re

import pytest

PKG_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "odigos_tpu")
REPO_ROOT = os.path.dirname(PKG_ROOT)

# modules that are entrypoints by design: imported by the interpreter
# (python -m) or the driver, not by other modules
ENTRYPOINTS = {"odigos_tpu.cli.__main__", "odigos_tpu.pipeline.__main__"}


def _module_name(path: str) -> str:
    rel = os.path.relpath(path, REPO_ROOT)
    mod = rel[:-3].replace(os.sep, ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _imports_of(path: str, mod: str) -> set:
    """Absolute module names this file imports (relative resolved)."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    pkg_parts = mod.split(".")
    if not path.endswith("__init__.py"):
        pkg_parts = pkg_parts[:-1]
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out.add(a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.level == 0:
                base = node.module or ""
            else:
                parent = pkg_parts[: len(pkg_parts) - (node.level - 1)]
                base = ".".join(parent + ([node.module] if node.module
                                          else []))
            if base:
                out.add(base)
            for a in node.names:
                out.add(f"{base}.{a.name}" if base else a.name)
    return out


def test_every_module_is_imported_somewhere():
    files = {}
    for dirpath, _dirs, names in os.walk(PKG_ROOT):
        for n in names:
            if n.endswith(".py"):
                p = os.path.join(dirpath, n)
                files[_module_name(p)] = p
    # tests and the driver entry also count as importers
    extra = [os.path.join(REPO_ROOT, "__graft_entry__.py")]
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    extra += [os.path.join(tests_dir, n) for n in os.listdir(tests_dir)
              if n.endswith(".py")]

    imported: set = set()
    for mod, path in files.items():
        imported |= _imports_of(path, mod)
    for path in extra:
        imported |= _imports_of(path, _module_name(path))

    orphans = []
    for mod in files:
        if mod == "odigos_tpu" or mod in ENTRYPOINTS:
            continue
        if mod in imported:
            continue
        # a package is live if any of its submodules is imported (the
        # import necessarily executes the package __init__)
        if files[mod].endswith("__init__.py") and any(
                i.startswith(mod + ".") for i in imported):
            continue
        # `from pkg import submodule` arrives as pkg.submodule above, but
        # `import pkg` alone also loads __init__ re-exports — accept a
        # parent-package import only for modules the parent re-exports
        parent = mod.rsplit(".", 1)[0]
        leaf = mod.rsplit(".", 1)[1]
        init = files.get(parent)
        if init and parent in imported:
            if f".{leaf}" in open(init).read():
                continue
        orphans.append(mod)
    assert not orphans, f"modules nothing imports (dead weight): {orphans}"


class TestJitShapeBucketing:
    """Every jitted scoring/training entry point in ``models/`` and
    ``parallel/`` must declare its shape-bucketing strategy (ISSUE 2
    satellite): an undeclared ``jax.jit`` path is an unbounded-recompile
    hazard — each novel input shape silently pays an XLA compile on the
    serving hot path. The contract: a module that jits exports a
    module-level ``SHAPE_BUCKETING`` dict, and every jit site resolves to
    one of its keys (the decorated/wrapped function name, the enclosing
    factory, or the lazy ``self._<name>_jit`` attribute, underscores and
    the ``_jit``/``_impl``/``_kernel`` suffixes stripped)."""

    # serving + features joined the scan with the ingest fast path
    # (ISSUE 6 satellite): the adaptive coalescer sizes batches onto
    # ladder rungs precisely because every jitted scoring entry point
    # promises bucketed shapes — a jit site appearing in those packages
    # without a SHAPE_BUCKETING declaration would void that promise
    JIT_DIRS = ("models", "parallel", "serving", "features")

    @staticmethod
    def _is_jit_call(node: ast.AST) -> bool:
        """jax.jit(...) or partial(jax.jit, ...) in decorator/call form."""
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "jit":
            return True
        if isinstance(f, ast.Name) and f.id == "partial" and node.args:
            a = node.args[0]
            return isinstance(a, ast.Attribute) and a.attr == "jit"
        return False

    @classmethod
    def _jit_sites(cls, tree: ast.Module) -> list[tuple[int, set]]:
        """(lineno, candidate names) per jit site: enclosing defs plus any
        assignment target of the jit(...) call."""
        parents: dict = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        sites = []
        for node in ast.walk(tree):
            is_site = False
            names: set = set()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(cls._is_jit_call(d) or
                       (isinstance(d, ast.Attribute) and d.attr == "jit")
                       for d in node.decorator_list):
                    is_site = True
            elif cls._is_jit_call(node):
                # every jit(...) call is a site — assigned, returned, or
                # passed straight through (the `return jax.jit(fn)` factory
                # idiom must not escape the declaration contract)
                is_site = True
                p = parents.get(node)
                if isinstance(p, ast.Assign):
                    for t in p.targets:
                        if isinstance(t, ast.Attribute):
                            names.add(t.attr)
                        elif isinstance(t, ast.Name):
                            names.add(t.id)
            if not is_site:
                continue
            cur = node
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    names.add(cur.name)
                cur = parents.get(cur)
            sites.append((node.lineno, names))
        return sites

    @staticmethod
    def _normalize(name: str) -> str:
        name = name.strip("_")
        for suffix in ("_jit", "_impl", "_kernel"):
            if name.endswith(suffix):
                name = name[: -len(suffix)]
        return name.strip("_")

    def test_every_jit_path_declares_bucketing_strategy(self):
        problems = []
        for sub in self.JIT_DIRS:
            root = os.path.join(PKG_ROOT, sub)
            for fn in sorted(os.listdir(root)):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(root, fn)
                with open(path) as f:
                    src = f.read()
                if "jax.jit" not in src:
                    continue
                tree = ast.parse(src, path)
                declared = None
                for node in tree.body:
                    if isinstance(node, ast.Assign) and any(
                            isinstance(t, ast.Name) and
                            t.id == "SHAPE_BUCKETING"
                            for t in node.targets):
                        declared = ast.literal_eval(node.value)
                if declared is None:
                    problems.append(
                        f"{sub}/{fn}: jits but exports no SHAPE_BUCKETING")
                    continue
                assert all(isinstance(v, str) and v
                           for v in declared.values()), \
                    f"{sub}/{fn}: SHAPE_BUCKETING values must be non-empty"
                keys = {self._normalize(k) for k in declared}
                for lineno, names in self._jit_sites(tree):
                    cands = {self._normalize(n) for n in names}
                    if not (cands & keys):
                        problems.append(
                            f"{sub}/{fn}:{lineno}: jit site "
                            f"{sorted(names)} has no SHAPE_BUCKETING entry")
        assert not problems, (
            "jit paths without a declared shape-bucketing strategy "
            "(unbounded-recompile hazard):\n  " + "\n  ".join(problems))


class TestPartitionSpecHygiene:
    """Every sharded jit/shard_map site in ``parallel/`` must declare its
    partition spec (ISSUE 7 satellite): a new kernel placed under a mesh
    without a declared spec silently runs replicated — dp-fold HBM and
    zero speedup, invisible until someone profiles. The contract mirrors
    SHAPE_BUCKETING: a module whose source shards (NamedSharding /
    in_shardings / shard_map) exports a module-level ``PARTITION_SPECS``
    dict, and every module-level function or class that itself contains
    a sharding marker resolves to one of its keys (underscores and
    ``_jit``/``_impl``/``_kernel`` suffixes stripped)."""

    MARKER_CALLS = ("NamedSharding", "shard_map")
    MARKER_KWARGS = ("in_shardings", "out_shardings")

    @classmethod
    def _has_marker(cls, node: ast.AST) -> bool:
        """AST-level sharding detection: a call to NamedSharding/
        shard_map, or a call carrying in_shardings/out_shardings —
        never a plain-text scan (docstrings mention these words)."""
        for n in ast.walk(node):
            if not isinstance(n, ast.Call):
                continue
            f = n.func
            name = f.attr if isinstance(f, ast.Attribute) \
                else getattr(f, "id", "")
            if name in cls.MARKER_CALLS:
                return True
            if any(kw.arg in cls.MARKER_KWARGS for kw in n.keywords):
                return True
        return False

    @classmethod
    def _sharded_defs(cls, tree: ast.Module) -> list[tuple[int, str]]:
        return [(node.lineno, node.name) for node in tree.body
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.ClassDef))
                and cls._has_marker(node)]

    def test_every_sharded_site_declares_partition_spec(self):
        root = os.path.join(PKG_ROOT, "parallel")
        problems = []
        for fn in sorted(os.listdir(root)):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            with open(path) as f:
                src = f.read()
            tree = ast.parse(src, path)
            if not self._has_marker(tree):
                continue
            declared = None
            for node in tree.body:
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name)
                        and t.id == "PARTITION_SPECS"
                        for t in node.targets):
                    declared = ast.literal_eval(node.value)
            if declared is None:
                problems.append(
                    f"parallel/{fn}: shards but exports no "
                    f"PARTITION_SPECS")
                continue
            assert all(isinstance(v, str) and v
                       for v in declared.values()), \
                f"parallel/{fn}: PARTITION_SPECS values must be non-empty"
            norm = TestJitShapeBucketing._normalize
            keys = {norm(k) for k in declared}
            for lineno, name in self._sharded_defs(tree):
                if norm(name) not in keys:
                    problems.append(
                        f"parallel/{fn}:{lineno}: sharded site {name!r} "
                        f"has no PARTITION_SPECS entry")
        assert not problems, (
            "sharded sites without a declared partition spec (would "
            "silently run replicated):\n  " + "\n  ".join(problems))


class TestColumnarAttrsHygiene:
    """No hot-path module may fall back to per-span attribute Python
    (ISSUE 4 satellite): span attributes are canonically the columnar
    AttrStore, and a ``for ... in batch.span_attrs`` loop or an
    ``np.fromiter(... span_attrs ...)`` scan re-introduces O(n)
    interpreter work per batch exactly where throughput is bought.
    Scope: the scoring-route processors/connectors, the featurizer, and
    the serving engine. The sanctioned dict-path reference lives in
    ``components/processors/_attrs_dictpath.py`` (bench A/B + parity
    oracle) and is deliberately outside this list."""

    HOT_MODULES = (
        "features/featurizer.py",
        "serving/engine.py",
        "serving/fastpath.py",
        "components/processors/filter.py",
        "components/processors/attributes.py",
        "components/processors/batch.py",
        "components/processors/tpuanomaly.py",
        "components/processors/redaction.py",
        "components/processors/groupbyattrs.py",
        "components/processors/ottl.py",
        "components/processors/transform.py",
        "components/connectors/anomalyrouter.py",
        "components/connectors/exceptions.py",
    )
    FORBIDDEN = (
        re.compile(r"for\s+.+?\s+in\s+[\w.]*\bspan_attrs\b"),
        re.compile(r"np\.fromiter\([^)]*span_attrs", re.S),
    )

    def test_no_per_span_attr_python_on_hot_paths(self):
        problems = []
        for rel in self.HOT_MODULES:
            path = os.path.join(PKG_ROOT, rel)
            with open(path) as f:
                src = f.read()
            for rx in self.FORBIDDEN:
                m = rx.search(src)
                if m:
                    line = src[:m.start()].count("\n") + 1
                    problems.append(
                        f"{rel}:{line}: {m.group(0)[:60]!r}")
        assert not problems, (
            "per-span attribute Python on a hot-path module — use "
            "batch.attrs() (mask_eq/mask_has/column/set_column) or move "
            "the dict path to _attrs_dictpath.py:\n  "
            + "\n  ".join(problems))

    def test_dictpath_module_is_the_only_processor_fallback(self):
        """The reference module must still exist (parity oracle) and the
        lint list must keep covering every file it is the fallback for."""
        assert os.path.exists(os.path.join(
            PKG_ROOT, "components", "processors", "_attrs_dictpath.py"))
        for rel in self.HOT_MODULES:
            assert os.path.exists(os.path.join(PKG_ROOT, rel)), rel


class TestFastPathHygiene:
    """The ingest fast path exists to remove per-span Python from the
    wire→device column (ISSUE 6 satellite), so the rule is stricter than
    the span_attrs lint: NO ``for``/comprehension in
    ``serving/fastpath.py`` — or the retirement-lane module it hands
    frames to (``serving/lanes.py``, ISSUE 9) — may iterate anything
    span- or batch-sized. Iterating ``batch``/``spans``/``scores``/
    feature arrays re-introduces O(n) interpreter work exactly where
    these PRs bought it out. The bounded-cardinality loops the modules
    legitimately need (flag lists via list-multiply, lane pools bounded
    by lane count, window drains bounded by frame count) don't iterate
    those names.

    Also pins the adaptive-batching shape contract: the engine's
    deadline sizing must snap onto ``BucketLadder`` rungs (floor_rows),
    never invent a new padded shape — the jit sites it feeds declare
    SHAPE_BUCKETING for *bucketed* rows.
    """

    FASTPATH_MODULES = ("serving/fastpath.py", "serving/lanes.py")
    # identifiers whose iteration is per-span/per-batch-row work
    SPAN_SIZED = re.compile(
        r"\b(batch|spans|scores|span_attrs|categorical|continuous"
        r"|features)\b")

    def _iter_exprs(self, tree):
        for node in ast.walk(tree):
            if isinstance(node, ast.For):
                yield node.lineno, ast.unparse(node.iter)
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.DictComp, ast.GeneratorExp)):
                for gen in node.generators:
                    yield node.lineno, ast.unparse(gen.iter)

    def test_no_per_span_iteration_in_fastpath_modules(self):
        problems = []
        for rel in self.FASTPATH_MODULES:
            path = os.path.join(PKG_ROOT, rel)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            problems.extend(
                f"{rel}:{lineno}: iterates {expr!r}"
                for lineno, expr in self._iter_exprs(tree)
                if self.SPAN_SIZED.search(expr))
        assert not problems, (
            "per-span Python iteration in a fast-path module — the "
            "whole point of this route is columnar flow:\n  "
            + "\n  ".join(problems))

    def test_adaptive_batching_snaps_to_ladder_rungs(self):
        """AST-level: ``_budget`` (the one place a coalesced call is
        sized; ``_adaptive_cap`` and ``_collect`` both go through it)
        must consult the backend ladder's ``floor_rows`` — the
        declaration that deadline-sized AND cap-sized batches land on
        SHAPE_BUCKETING'd precompiled shapes."""
        path = os.path.join(PKG_ROOT, "serving", "engine.py")
        with open(path) as f:
            tree = ast.parse(f.read(), path)

        def attr_calls(name):
            fns = [n for n in ast.walk(tree)
                   if isinstance(n, ast.FunctionDef) and n.name == name]
            assert fns, f"engine lost its {name} stage"
            return {n.func.attr for n in ast.walk(fns[0])
                    if isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)}

        assert "floor_rows" in attr_calls("_budget"), (
            "_budget no longer snaps span budgets onto BucketLadder "
            "rungs — coalesced batches would pay recompiles")
        for caller in ("_adaptive_cap", "_collect"):
            assert "_budget" in attr_calls(caller), (
                f"{caller} sizes a call without _budget's rung snapping")


class TestSteadyStateAllocHygiene:
    """Zero-allocation steady state (ISSUE 12): the featurize/pack
    kernels and the fast path may not call ``np.zeros``/``np.empty``/
    ``np.full`` directly — every per-frame tensor goes through
    ``bufferpool.alloc`` so a leased frame recycles pinned buffers
    instead of paying the allocator. Cold/setup paths that OUTLIVE a
    frame (memoized hash/slot tables, the pool's own backing
    allocation) are allowlisted with a justification: a lease must
    never own an array that survives it.
    """

    MODULES = ("features/featurizer.py", "features/bufferpool.py",
               "serving/fastpath.py", "serving/lanes.py",
               "serving/fused.py")
    ALLOC_FNS = {"zeros", "empty", "full"}
    ALLOWLIST = {
        ("serving/fused.py", "_device_tables"):
            "value-keyed LRU memo of padded device hash tables — a "
            "setup path that outlives any frame, like _hash_table",
        ("features/featurizer.py", "_hash_table"):
            "value-keyed LRU memo: the frozen table outlives any frame",
        ("features/featurizer.py", "_attr_slot_matrix"):
            "memoized on the immutable attr store (lives with the "
            "batch, not the lease); frozen before caching",
        ("features/bufferpool.py", "_fresh"):
            "the pool's ONE backing allocation site (a counted miss)",
        ("features/bufferpool.py", "_plain"):
            "the explicit no-lease fallback (training/tools/cold "
            "paths; counted as fallback_allocs)",
    }

    def _direct_allocs(self, tree):
        """(enclosing function name, lineno) of every direct np
        zeros/empty/full call, tracked via a function-def stack."""
        out = []

        def walk(node, fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = node.name
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self.ALLOC_FNS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "np"):
                out.append((fn, node.lineno))
            for child in ast.iter_child_nodes(node):
                walk(child, fn)

        walk(tree, "<module>")
        return out

    def test_no_direct_np_alloc_in_steady_state_kernels(self):
        problems = []
        for rel in self.MODULES:
            path = os.path.join(PKG_ROOT, rel)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for fn, lineno in self._direct_allocs(tree):
                if (rel, fn) in self.ALLOWLIST:
                    continue
                problems.append(
                    f"{rel}:{lineno}: np.{{zeros,empty,full}} in "
                    f"{fn}() — route it through bufferpool.alloc or "
                    f"allowlist with a justification")
        assert not problems, (
            "direct numpy allocation on a steady-state kernel — the "
            "zero-allocation hot path (ISSUE 12) leaks per-frame "
            "mallocs:\n  " + "\n  ".join(problems))

    def test_allowlisted_sites_still_allocate(self):
        """Stale-allowlist oracle: every allowlisted function still
        exists AND still contains a direct allocation — a rewritten
        kernel must shed its stale exemption."""
        by_file: dict = {}
        for (rel, fn), _why in self.ALLOWLIST.items():
            by_file.setdefault(rel, set()).add(fn)
        for rel, fns in by_file.items():
            path = os.path.join(PKG_ROOT, rel)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            present = {fn for fn, _ in self._direct_allocs(tree)}
            stale = fns - present
            assert not stale, (
                f"{rel}: allowlisted functions {sorted(stale)} no "
                f"longer allocate directly — drop the exemption")

    def test_kernels_import_the_pool_allocator(self):
        """featurizer.py must actually route through bufferpool.alloc
        (the lint above only proves absence; this proves presence)."""
        path = os.path.join(PKG_ROOT, "features", "featurizer.py")
        with open(path) as f:
            src = f.read()
        assert "from .bufferpool import alloc" in src


class TestLatencyStageHygiene:
    """Latency-attribution lint (ISSUE 8 satellite): every ``Stage``
    enum member is stamped exactly once per frame on the fast path.
    A member with no stamp site silently vanishes from the waterfall (a
    stage whose wall is attributed to its neighbor); a member stamped
    at two sites double-counts its wall and breaks the Σstages == wall
    accounting the acceptance criterion pins. Static AST scan over the
    package: ``<clock>.stamp(Stage.X)`` call sites plus the
    ``ENGINE_STAGES`` tuple (the four stages merged from the engine's
    per-call boundary dict count as one site each)."""

    def _stamp_sites(self) -> dict[str, list[str]]:
        sites: dict[str, list[str]] = {}
        for dirpath, _dirs, names in os.walk(PKG_ROOT):
            for n in names:
                if not n.endswith(".py"):
                    continue
                path = os.path.join(dirpath, n)
                rel = os.path.relpath(path, PKG_ROOT)
                with open(path) as f:
                    tree = ast.parse(f.read(), path)
                for node in ast.walk(tree):
                    # ``<clock>.stamp(Stage.X)``, or the helper that
                    # stamps on its way out:
                    # ``annotate("<name>", clock, Stage.X)``
                    if not (isinstance(node, ast.Call) and (
                            (isinstance(node.func, ast.Attribute)
                             and node.func.attr == "stamp")
                            or (isinstance(node.func, ast.Name)
                                and node.func.id == "annotate"))):
                        continue
                    for arg in node.args:
                        if (isinstance(arg, ast.Attribute)
                                and isinstance(arg.value, ast.Name)
                                and arg.value.id == "Stage"):
                            sites.setdefault(arg.attr, []).append(
                                f"{rel}:{node.lineno}")
        return sites

    def test_every_stage_member_stamped_exactly_once(self):
        from odigos_tpu.selftelemetry.latency import (
            ENGINE_STAGES, ENGINE_STAGES_FUSED, Stage)

        sites = self._stamp_sites()
        # the engine's merged boundary dict counts as ONE site per
        # member whichever taxonomy (host or fused) stamps it — the two
        # tuples share QUEUE/DEVICE/HARVEST and are mutually exclusive
        # per frame, so the union credits each member once
        for s in set(ENGINE_STAGES) | set(ENGINE_STAGES_FUSED):
            sites.setdefault(s.name, []).append(
                "selftelemetry/latency.py:ENGINE_STAGES")
        problems = []
        for member in Stage:
            where = sites.pop(member.name, [])
            if len(where) != 1:
                problems.append(
                    f"Stage.{member.name}: {len(where)} stamp sites "
                    f"{where} (must be exactly 1)")
        for name, where in sites.items():
            problems.append(
                f"stamp of unknown Stage.{name} at {where}")
        assert not problems, (
            "stage-stamp coverage broken — the waterfall would "
            "under- or double-count:\n  " + "\n  ".join(problems))

    def test_stage_taxonomy_is_closed_and_labeled(self):
        """Stage values are the metric label vocabulary: lowercase,
        label-safe, and unique (the closed-taxonomy contract). STAGES
        stays the host-route traversal; ALL_STAGES is the vocabulary
        (the fused route swaps featurize+pack for one `fused` stage)."""
        from odigos_tpu.selftelemetry.latency import (ALL_STAGES, STAGES,
                                                      Stage)

        assert len(ALL_STAGES) == len(set(ALL_STAGES)) == len(list(Stage))
        assert set(ALL_STAGES) - set(STAGES) == {Stage.FUSED.value}
        for v in ALL_STAGES:
            assert re.fullmatch(r"[a-z_]+", v), v


class TestAnnotationHygiene:
    """Trace-annotation lint (ISSUE 25): ``latency.ANNOTATIONS`` and the
    ``annotate("<name>", ...)`` sites hold each other, in both
    directions. A name with no site is a stage the trace never shows; a
    name at two sites is two different stretches of work under one
    label; a site whose name is not in the tuple is an annotation no
    reader knows of. Names are literals, so that the trace reduction
    (benchmark/hosttrace.py) and this lint can find them."""

    def _sites(self) -> dict[str, list[str]]:
        sites: dict[str, list[str]] = {}
        for dirpath, _dirs, names in os.walk(PKG_ROOT):
            for n in names:
                if not n.endswith(".py"):
                    continue
                path = os.path.join(dirpath, n)
                rel = os.path.relpath(path, PKG_ROOT)
                with open(path) as f:
                    tree = ast.parse(f.read(), path)
                for node in ast.walk(tree):
                    if not (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Name)
                            and node.func.id == "annotate"):
                        continue
                    first = node.args[0] if node.args else None
                    name = first.value if isinstance(first, ast.Constant) \
                        else f"<not a literal: {ast.dump(first)[:40]}>"
                    sites.setdefault(name, []).append(
                        f"{rel}:{node.lineno}")
        return sites

    def test_tuple_and_sites_hold_each_other(self):
        from odigos_tpu.selftelemetry.latency import ANNOTATIONS

        assert len(ANNOTATIONS) == len(set(ANNOTATIONS))
        sites = self._sites()
        problems = []
        for name in ANNOTATIONS:
            where = sites.pop(name, [])
            if len(where) != 1:
                problems.append(f"{name}: {len(where)} sites {where} "
                                f"(must be exactly 1)")
        for name, where in sites.items():
            problems.append(f"annotate({name!r}) at {where} is not in "
                            f"latency.ANNOTATIONS")
        assert not problems, "\n  ".join(problems)

    def test_a_stamping_annotation_is_named_for_its_stage(self):
        """``annotate("<layer>/<stage>", clock, Stage.X)``: the name's
        last word is the stage's label, so the trace and the waterfall
        speak of the same thing."""
        from odigos_tpu.selftelemetry.latency import ANNOTATIONS, Stage

        labels = {s.value for s in Stage}
        assert {"pack", "harvest", "featurize", "enqueue", "admission",
                "decode", "tag", "forward"} <= labels
        for name in ANNOTATIONS:
            layer, _, stage = name.partition("/")
            assert layer in ("wire", "fastpath", "engine", "lane",
                             "python"), name
            assert re.fullmatch(r"[a-z]+", stage), name
        # the waits have no annotation: nobody works in them
        for wait in ("submit", "queue", "device", "wait"):
            assert not any(n.endswith("/" + wait) for n in ANNOTATIONS)


class TestFleetRuleHygiene:
    """Fleet alert/recommender lint (ISSUE 10 satellite): every metric
    name referenced in an in-repo alert expression or recommender rule
    must resolve against the registered ``odigos_*`` metric names (the
    ISSUE 3 name-lint registry: every odigos_* string literal in the
    package) — a typo'd rule would otherwise match zero series and
    silently never fire. Recommender knobs must resolve against
    ``config.sizing.TUNING_KNOBS`` (a recommendation must never point
    at a knob that does not exist)."""

    # the flat snapshot also carries derived histogram-stat keys
    # (Meter._stat_key) — an expression over a _p99 series is legal
    STAT_SUFFIXES = ("_count", "_mean", "_p50", "_p90", "_p99", "_max")

    @staticmethod
    def _registered_metric_names() -> set:
        """Every odigos_* string literal in odigos_tpu/ — metric name
        constants, gauge-table values, f-string prefixes (the prefix of
        a JoinedStr before its label block)."""
        names = set()
        name_re = re.compile(r"^odigos_[a-z0-9_]+$")
        for dirpath, _dirs, files in os.walk(PKG_ROOT):
            for n in files:
                if not n.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, n)) as f:
                    tree = ast.parse(f.read())
                for node in ast.walk(tree):
                    if isinstance(node, ast.Constant) \
                            and isinstance(node.value, str):
                        v = node.value.split("{")[0]
                        if name_re.fullmatch(v):
                            names.add(v)
        return names

    def _resolves(self, metric: str, registry: set) -> bool:
        if metric in registry:
            return True
        for suffix in self.STAT_SUFFIXES:
            if metric.endswith(suffix) \
                    and metric[: -len(suffix)] in registry:
                return True
        return False

    def test_recommender_rules_resolve(self):
        from odigos_tpu.config.sizing import TUNING_KNOBS
        from odigos_tpu.selftelemetry.fleet import (
            RECOMMENDER_RULES, referenced_metric)

        registry = self._registered_metric_names()
        problems = []
        for rule in RECOMMENDER_RULES:
            metric = referenced_metric(rule.expr)  # raises on bad expr
            if not self._resolves(metric, registry):
                problems.append(f"{rule.name}: metric {metric!r} is not "
                                f"a registered odigos_* name")
            if rule.knob not in TUNING_KNOBS:
                problems.append(f"{rule.name}: knob {rule.knob!r} not "
                                f"in sizing.TUNING_KNOBS")
        assert not problems, "\n".join(problems)

    def test_typoed_metric_fails_resolution(self):
        """The lint's own oracle: a plausible-but-wrong name must NOT
        resolve (guards against the registry scan degenerating into
        matching everything)."""
        registry = self._registered_metric_names()
        assert not self._resolves("odigos_engine_queue_dpeth", registry)
        assert self._resolves("odigos_engine_queue_depth", registry)
        assert self._resolves("odigos_latency_e2e_ms_p99", registry)


class TestActuatorKnobHygiene:
    """Closed-loop actuator lint (ISSUE 15 satellite): every ACTUATABLE
    node-config knob in ``sizing.KNOB_SPECS`` must resolve to a
    ``validate_config``-accepted config path whose edit the structural
    differ classifies reconfigure/replace — never FULL — on a
    representative config of the knob's kind. A knob addition that
    silently classifies FULL would make the actuator tear down the very
    pipeline it exists to tune without a teardown. With a stale-entry
    oracle: a spec pointing at a key the validator refuses (or that
    resolves to no site) must be flagged."""

    @staticmethod
    def _representative_config(spec) -> dict:
        """A minimal valid config of the knob's kind: fastpath knobs
        need a fast_path pipeline; processor knobs a componentwise
        chain (the same knob under a fast_path alias may legitimately
        classify FULL — the actuator refuses that at runtime)."""
        import odigos_tpu.components  # noqa: F401 — factories

        cfg: dict = {
            "receivers": {"otlpwire": {}},
            "processors": {"tpuanomaly": {}},
            "exporters": {"tracedb": {}},
            "service": {"pipelines": {"traces/in": {
                "receivers": ["otlpwire"],
                "processors": ["tpuanomaly"],
                "exporters": ["tracedb"]}}},
        }
        if spec.kind == "fastpath":
            cfg["service"]["pipelines"]["traces/in"]["fast_path"] = {
                "deadline_ms": 25.0}
        return cfg

    def _check(self, knob, spec) -> list:
        """Problems for one actuatable node-config knob (the lint body,
        factored so the stale-entry oracle can drive it)."""
        import copy

        from odigos_tpu.config.sizing import bounded_step, knob_sites
        from odigos_tpu.pipeline.configdiff import FULL, diff_configs
        from odigos_tpu.pipeline.graph import validate_config

        problems = []
        cfg = self._representative_config(spec)
        sites = [(path, cur) for path, cur in knob_sites(knob, cfg)]
        if not sites:
            return [f"{knob}: resolves to no edit site in its "
                    f"representative config (stale entry)"]
        new = copy.deepcopy(cfg)
        for path, cur in sites:
            node = new
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = bounded_step(knob, cur,
                                          direction="down"
                                          if cur >= spec.max_value
                                          else "up", max_step=2.0)
            if node[path[-1]] == cur:
                problems.append(f"{knob}: bounded_step produced a "
                                f"no-op edit at {path}")
        bad = validate_config(new)
        if bad:
            problems.append(f"{knob}: edited config refused by "
                            f"validate_config: {bad}")
            return problems
        diff = diff_configs(cfg, new)
        if diff.mode == FULL:
            problems.append(f"{knob}: edit classifies FULL "
                            f"({diff.reasons}) — the actuator would "
                            f"refuse every proposal for this knob")
        return problems

    def test_every_actuatable_knob_classifies_incremental(self):
        from odigos_tpu.config.sizing import KNOB_SPECS

        problems = []
        checked = 0
        for knob, spec in KNOB_SPECS.items():
            if not spec.actuatable or spec.kind == "controlplane":
                continue
            checked += 1
            problems.extend(self._check(knob, spec))
        assert checked, "no actuatable node-config knobs at all?"
        assert not problems, "\n".join(problems)

    def test_stale_entry_oracle(self):
        """The lint's own oracle: a fabricated spec whose key the
        validator refuses (ghost fast_path key) and one that resolves
        to no site must both be flagged."""
        import dataclasses

        from odigos_tpu.config.sizing import KNOB_SPECS, KnobSpec

        ghost = dataclasses.replace(KNOB_SPECS["admission_deadline"],
                                    key="ghost_knob")
        KNOB_SPECS["_ghost"] = ghost
        try:
            problems = self._check("_ghost", ghost)
        finally:
            del KNOB_SPECS["_ghost"]
        assert problems and "validate_config" in problems[0]
        orphan = KnobSpec(knob="_orphan", path="x", kind="processor",
                          component="nosuchprocessor", key="k",
                          min_value=1, max_value=10, default=5,
                          actuatable=True)
        KNOB_SPECS["_orphan"] = orphan
        try:
            problems = self._check("_orphan", orphan)
        finally:
            del KNOB_SPECS["_orphan"]
        assert problems and "no edit site" in problems[0]

    def test_actuator_metric_names_registered(self):
        """The odigos_actuator_* family must resolve against the
        registered name registry (the TestFleetRuleHygiene scan)."""
        registry = TestFleetRuleHygiene._registered_metric_names()
        for name in ("odigos_actuator_proposals_total",
                     "odigos_actuator_canaries_total",
                     "odigos_actuator_promotions_total",
                     "odigos_actuator_rollbacks_total",
                     "odigos_actuator_refusals_total",
                     "odigos_actuator_state"):
            assert name in registry, name

    def test_fused_route_metric_names_registered(self):
        """The fused-route counters (ISSUE 19 satellite) must resolve
        against the registered name registry, match the constants the
        fast path actually exports, and the fallback-reason vocabulary
        must stay a closed, label-safe set — a renamed constant or a
        free-form reason string would mint unregistered series."""
        from odigos_tpu.serving.fused import FALLBACK_REASONS

        registry = TestFleetRuleHygiene._registered_metric_names()
        for name in ("odigos_fastpath_fused_frames_total",
                     "odigos_fastpath_fused_fallback_total"):
            assert name in registry, name
        from odigos_tpu.serving.fastpath import (
            FUSED_FALLBACK_METRIC, FUSED_FRAMES_METRIC)
        assert FUSED_FRAMES_METRIC == "odigos_fastpath_fused_frames_total"
        assert FUSED_FALLBACK_METRIC == \
            "odigos_fastpath_fused_fallback_total"
        assert len(FALLBACK_REASONS) == len(set(FALLBACK_REASONS))
        for reason in FALLBACK_REASONS:
            assert re.fullmatch(r"[a-z_]+", reason), reason


class TestChaosInjectorHygiene:
    """Chaos injector lint (ISSUE 13 satellite): every ``inject_*`` in
    ``e2e/chaos.py`` must have a paired ``clear_*`` (a fault someone
    can inject but nobody can lift WILL leak into the next test the
    first time a scenario dies mid-fault) and must appear in at least
    one scenario of ``tests/test_chaos_matrix.py`` (an injector nobody
    exercises is a fault mode nobody has proven the pipeline degrades
    through)."""

    CHAOS_PATH = os.path.join(PKG_ROOT, "e2e", "chaos.py")
    MATRIX_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "test_chaos_matrix.py")

    @staticmethod
    def _toplevel_defs(source: str) -> set:
        tree = ast.parse(source)
        return {node.name for node in tree.body
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))}

    @staticmethod
    def _unpaired(defs: set) -> list:
        return sorted(
            name for name in defs
            if name.startswith("inject_")
            and f"clear_{name[len('inject_'):]}" not in defs)

    def test_every_injector_has_a_paired_clear(self):
        with open(self.CHAOS_PATH) as f:
            defs = self._toplevel_defs(f.read())
        assert {n for n in defs if n.startswith("inject_")}, \
            "chaos.py lost its injectors?"
        assert self._unpaired(defs) == []

    def test_pairing_check_catches_an_unpaired_injector(self):
        """The lint's own oracle: an injector without a clear must be
        flagged (guards against the scan degenerating into a no-op)."""
        defs = self._toplevel_defs(
            "def inject_gremlins(env):\n    pass\n"
            "def clear_goblins(env):\n    pass\n")
        assert self._unpaired(defs) == ["inject_gremlins"]

    def test_registry_covers_every_pair(self):
        from odigos_tpu.e2e.chaos import INJECTORS

        with open(self.CHAOS_PATH) as f:
            defs = self._toplevel_defs(f.read())
        expected = {n[len("inject_"):] for n in defs
                    if n.startswith("inject_")}
        assert set(INJECTORS) == expected
        for name, (inject, clear) in INJECTORS.items():
            assert inject.__name__ == f"inject_{name}"
            assert clear.__name__ == f"clear_{name}"

    @staticmethod
    def _names_used_outside_imports(source: str) -> set:
        """Name references in the module's NON-import statements — an
        injector that only appears in the import block is imported,
        not exercised, and must not satisfy the coverage lint."""
        used = set()
        for node in ast.parse(source).body:
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name):
                    used.add(sub.id)
        return used

    def test_every_injector_appears_in_a_scenario(self):
        with open(self.CHAOS_PATH) as f:
            defs = self._toplevel_defs(f.read())
        with open(self.MATRIX_PATH) as f:
            used = self._names_used_outside_imports(f.read())
        missing = sorted(
            name for name in defs
            if name.startswith("inject_") and name not in used)
        assert not missing, (
            f"chaos injectors never exercised by any scenario in "
            f"tests/test_chaos_matrix.py: {missing}")

    def test_import_only_reference_does_not_count(self):
        """The coverage lint's own oracle: an injector that is merely
        IMPORTED by the matrix module must still read as missing."""
        used = self._names_used_outside_imports(
            "from odigos_tpu.e2e import inject_gremlins\n"
            "def test_x():\n    other_fn()\n")
        assert "inject_gremlins" not in used
        assert "other_fn" in used


class TestReconfigureHygiene:
    """Incremental-reload lint (ISSUE 14 satellite): the
    ``RECONFIGURABLE_KEYS`` table is the differ's classification
    oracle, so it must stay CLOSED and honest — every class declaring
    it implements ``reconfigure`` and vice versa (a declared key
    without an implementation would classify a change as retunable and
    then replace the node anyway; an implementation without the table
    could never be reached), and every declared key must actually be
    READ by the class (a stale key would classify a change as handled
    while reconfigure silently ignores it — the config lies). AST
    scan over the whole package, so a new reconfigurable component
    cannot ship half-wired."""

    @staticmethod
    def _scan_classes(source: str):
        """(class_name, declared_keys|None, has_reconfigure,
        string_constants) per class in ``source``; declared_keys is
        None when the class has no RECONFIGURABLE_KEYS assignment."""
        out = []
        for node in ast.walk(ast.parse(source)):
            if not isinstance(node, ast.ClassDef):
                continue
            keys = None
            has_rec = False
            consts = set()
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    if sub.name == "reconfigure":
                        has_rec = True
                    for inner in ast.walk(sub):
                        if isinstance(inner, ast.Constant) \
                                and isinstance(inner.value, str):
                            consts.add(inner.value)
                if isinstance(sub, ast.Assign) and any(
                        isinstance(t, ast.Name)
                        and t.id == "RECONFIGURABLE_KEYS"
                        for t in sub.targets):
                    keys = {
                        n.value for n in ast.walk(sub.value)
                        if isinstance(n, ast.Constant)
                        and isinstance(n.value, str)}
            out.append((node.name, keys, has_rec, consts))
        return out

    def _all_classes(self):
        for dirpath, _dirs, names in os.walk(PKG_ROOT):
            for n in names:
                if not n.endswith(".py"):
                    continue
                path = os.path.join(dirpath, n)
                with open(path) as f:
                    for row in self._scan_classes(f.read()):
                        yield path, row

    def test_declaration_and_implementation_are_paired(self):
        problems = []
        for path, (cls, keys, has_rec, _consts) in self._all_classes():
            if keys is not None and not has_rec:
                problems.append(
                    f"{path}:{cls} declares RECONFIGURABLE_KEYS but "
                    f"implements no reconfigure()")
            if has_rec and keys is None:
                problems.append(
                    f"{path}:{cls} implements reconfigure() but "
                    f"declares no RECONFIGURABLE_KEYS")
        assert not problems, problems

    def test_no_stale_keys(self):
        """Every declared key appears as a string literal inside the
        class's methods (config.get("key") in __init__/reconfigure/
        helpers) — the stale-key oracle."""
        stale = []
        found_any = False
        for path, (cls, keys, _has_rec, consts) in self._all_classes():
            if not keys:
                continue
            found_any = True
            for key in sorted(keys - consts):
                stale.append(f"{path}:{cls} declares {key!r} but never "
                             f"reads it")
        assert found_any, "no RECONFIGURABLE_KEYS tables found at all?"
        assert not stale, stale

    def test_lint_catches_unpaired_and_stale(self):
        """The lint's own oracle (guards against the scan degenerating
        into a no-op): an unpaired declaration, an unpaired
        implementation, and a stale key must all be flagged."""
        rows = {r[0]: r for r in self._scan_classes(
            "class NoImpl:\n"
            "    RECONFIGURABLE_KEYS = frozenset({'a'})\n"
            "class NoTable:\n"
            "    def reconfigure(self, cfg):\n        pass\n"
            "class Stale:\n"
            "    RECONFIGURABLE_KEYS = frozenset({'a', 'ghost'})\n"
            "    def reconfigure(self, cfg):\n"
            "        self.a = cfg.get('a')\n")}
        name, keys, has_rec, consts = rows["NoImpl"]
        assert keys == {"a"} and not has_rec
        name, keys, has_rec, consts = rows["NoTable"]
        assert keys is None and has_rec
        name, keys, has_rec, consts = rows["Stale"]
        assert keys - consts == {"ghost"}

    def test_differ_fastpath_table_matches_validated_keys(self):
        """Every fast-path reconfigurable key must be a key
        graph.validate_config accepts — a key the validator refuses
        could never reach reconfigure."""
        from odigos_tpu.serving.fastpath import IngestFastPath

        validated = {"deadline_ms", "max_pending_spans", "lanes",
                     "submit_lanes", "ordered", "drain_timeout_s",
                     "name", "predictive", "predictive_margin",
                     "predictive_min_frames", "pooled", "fused"}
        assert IngestFastPath.RECONFIGURABLE_KEYS <= validated


class TestFlowAccounting:
    """Flow-ledger lint (ISSUE 5 satellite): any processor/connector
    module whose ``process``/``consume``/``_emit`` method conditionally
    returns without forwarding a batch — a ``<batch>.filter(...)`` call
    or a ``return None`` inside those methods marks the shed — must name
    the loss through ``FlowContext.drop(...)``, or the conservation
    checker would report it as a silent leak. Static AST scan, so a new
    shedding component cannot ship unaccounted.

    The allowlist carries the modules whose filter/return patterns are
    NOT sheds (buffer splits, selection for derivation, aggregating
    connectors whose input stream terminates by design) plus the
    dict-reference oracle."""

    SCAN_DIRS = ("components/processors", "components/connectors")
    SHED_METHODS = ("process", "consume", "_emit")
    ALLOWLIST = {
        # dict-reference oracle (parity fallback, never in a graph)
        "components/processors/_attrs_dictpath.py",
        # buffer split: filter() separates released/retained spans;
        # everything is eventually forwarded (eviction releases early)
        "components/processors/groupbytrace.py",
        # filter() SELECTS source metrics; output = input + generated
        "components/processors/metricsgeneration.py",
        # filter()+concat reassembly; nothing is shed
        "components/processors/metricstransform.py",
        # aggregating connectors: the input stream terminates here by
        # design — a derived stream (metrics/logs) continues instead
        "components/connectors/count.py",
        "components/connectors/exceptions.py",
        "components/connectors/servicegraph.py",
        "components/connectors/spanmetrics.py",
    }

    @staticmethod
    def _is_drop_call(n: ast.AST) -> bool:
        return (isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "drop"
                and isinstance(n.func.value, ast.Name)
                and n.func.value.id == "FlowContext")

    @classmethod
    def _class_sheds(cls, tree: ast.Module) -> list[tuple[str, int]]:
        """(class name, first shed lineno) for classes whose
        SHED_METHODS shed without any FlowContext.drop(...) call
        anywhere in the SAME class — scoped per class, so one ported
        class (or a docstring mention) cannot exempt another class in
        the file."""
        out = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            hits = []
            for m in node.body:
                if not (isinstance(m, ast.FunctionDef)
                        and m.name in cls.SHED_METHODS):
                    continue
                for n in ast.walk(m):
                    if isinstance(n, ast.Return) and (
                            n.value is None
                            or (isinstance(n.value, ast.Constant)
                                and n.value.value is None)):
                        hits.append(n.lineno)
                    elif (isinstance(n, ast.Call)
                          and isinstance(n.func, ast.Attribute)
                          and n.func.attr == "filter"):
                        hits.append(n.lineno)
            if not hits:
                continue
            if not any(cls._is_drop_call(n) for n in ast.walk(node)):
                out.append((node.name, hits[0]))
        return out

    def test_shedding_modules_report_to_flow_ledger(self):
        problems = []
        for sub in self.SCAN_DIRS:
            root = os.path.join(PKG_ROOT, sub)
            for fn in sorted(os.listdir(root)):
                if not fn.endswith(".py") or fn == "__init__.py":
                    continue
                rel = f"{sub}/{fn}"
                if rel in self.ALLOWLIST:
                    continue
                path = os.path.join(root, fn)
                with open(path) as f:
                    src = f.read()
                for cname, lineno in self._class_sheds(
                        ast.parse(src, path)):
                    problems.append(
                        f"{rel}:{lineno}: class {cname} sheds data "
                        f"(filter/early return in process/consume/"
                        f"_emit) without a FlowContext.drop(...) call")
        assert not problems, (
            "components shedding data outside the flow ledger — name "
            "the loss via FlowContext.drop(n, reason) or allowlist with "
            "a justification:\n  " + "\n  ".join(problems))

    def test_allowlist_entries_exist(self):
        for rel in self.ALLOWLIST:
            assert os.path.exists(os.path.join(PKG_ROOT, rel)), rel


class TestMetricNameHygiene:
    """Every instrument name that reaches the ``Meter`` (``meter.add`` /
    ``record`` / ``set_gauge`` and ``labeled_key``) must match the
    Prometheus metric-name regex, and every DATA-DERIVED label value
    interpolated into a flat ``name{key=value}`` key must be routed
    through ``label_value`` (ISSUE 3 satellite): one unsanitized value
    with a ',' corrupts the whole exposition line, and one bad name
    breaks the scrape. Static over ``odigos_tpu/`` so a new metric
    cannot silently break /metrics.

    Allowed label-value expressions inside metric f-strings:

    * a ``label_value(...)`` call (sanitized at the site),
    * a bare name assigned from ``label_value(...)`` in the same file
      (the precompute idiom),
    * an attribute ending in ``.name`` — component ids, which come from
      config keys with identifier-like shape (``otlp/ui``), not from
      span data.
    """

    NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
    METER_FNS = {"add", "record", "set_gauge", "counter", "gauge",
                 "quantile"}
    UNRESOLVED = "\x00"

    @staticmethod
    def _module_constants(tree: ast.Module) -> dict:
        out = {}
        for node in tree.body:
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Constant) and isinstance(
                    node.value.value, str):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = node.value.value
        return out

    @classmethod
    def _metric_args(cls, tree: ast.Module):
        """First-arg AST node of every meter.<fn>(...) / labeled_key(...)
        call, with its line number."""
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in cls.METER_FNS \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id == "meter":
                yield node.lineno, node.args[0]
            elif isinstance(f, ast.Name) and f.id == "labeled_key":
                yield node.lineno, node.args[0]

    @classmethod
    def _render(cls, arg: ast.AST, constants: dict) -> str:
        """Flatten a metric-name expression to text; unresolvable pieces
        become the UNRESOLVED marker."""
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value
        if isinstance(arg, ast.JoinedStr):
            parts = []
            for v in arg.values:
                if isinstance(v, ast.Constant):
                    parts.append(str(v.value))
                elif isinstance(v, ast.FormattedValue) and isinstance(
                        v.value, ast.Name) and v.value.id in constants:
                    parts.append(constants[v.value.id])
                else:
                    parts.append(cls.UNRESOLVED)
            return "".join(parts)
        if isinstance(arg, ast.Name):
            return constants.get(arg.id, cls.UNRESOLVED)
        return cls.UNRESOLVED

    def _label_value_ok(self, expr: str, src: str) -> bool:
        expr = expr.strip()
        if "label_value(" in expr or expr.endswith(".name"):
            return True
        # precompute idiom: `svc = label_value(...)` earlier in the file
        return bool(re.search(
            rf"\b{re.escape(expr)}\s*=\s*label_value\(", src)) \
            if expr.isidentifier() else False

    def test_metric_names_and_label_values(self):
        problems = []
        all_constants: dict = {}
        trees: dict = {}
        for dirpath, _dirs, names in os.walk(PKG_ROOT):
            for n in sorted(names):
                if not n.endswith(".py"):
                    continue
                path = os.path.join(dirpath, n)
                with open(path) as f:
                    src = f.read()
                tree = ast.parse(src, path)
                trees[path] = (tree, src)
                all_constants.update(self._module_constants(tree))
        for path, (tree, src) in sorted(trees.items()):
            rel = os.path.relpath(path, PKG_ROOT)
            constants = dict(all_constants)
            constants.update(self._module_constants(tree))
            for lineno, arg in self._metric_args(tree):
                text = self._render(arg, constants)
                base = text.split("{")[0]
                if self.UNRESOLVED in base:
                    if isinstance(arg, ast.Name) or not isinstance(
                            arg, (ast.Constant, ast.JoinedStr)):
                        # precomputed keys (labeled_key results bound to
                        # attributes/locals) are validated at their own
                        # labeled_key call site
                        continue
                    problems.append(
                        f"{rel}:{lineno}: metric name prefix is not a "
                        f"string/constant — name cannot be lint-checked")
                    continue
                if not self.NAME_RE.fullmatch(base):
                    problems.append(
                        f"{rel}:{lineno}: metric name {base!r} violates "
                        f"[a-zA-Z_:][a-zA-Z0-9_:]*")
                # label VALUES interpolated into the flat key must be
                # sanitized: find `...=<expr>` FormattedValue positions
                if isinstance(arg, ast.JoinedStr):
                    prev = ""
                    for v in arg.values:
                        if isinstance(v, ast.Constant):
                            prev = str(v.value)
                            continue
                        if isinstance(v, ast.FormattedValue):
                            if not prev.endswith("="):
                                prev = ""
                                continue  # name-prefix position
                            expr = ast.unparse(v.value)
                            if not self._label_value_ok(expr, src):
                                problems.append(
                                    f"{rel}:{lineno}: label value "
                                    f"{{{expr}}} is not routed through "
                                    f"label_value()")
                            prev = ""
        assert not problems, (
            "metric hygiene violations (exposition-breaking):\n  "
            + "\n  ".join(problems))


class TestFeatureGates:
    def test_gate_stages_by_version(self):
        from odigos_tpu.utils.feature import Features

        old = Features(k8s_version="1.28", jax_version="0.3")
        new = Features(k8s_version="1.34", jax_version="0.6")
        assert not old.enabled("shard-map-scoring")
        assert new.enabled("shard-map-scoring")
        assert old.stage("native-sidecar-containers") == "alpha"
        assert not old.enabled("native-sidecar-containers")  # alpha opt-in
        assert Features(k8s_version="1.28",
                        enable_alpha=True).enabled(
                            "native-sidecar-containers")
        assert new.stage("native-sidecar-containers") == "ga"

    def test_effective_config_clamps_dp_without_gate(self, monkeypatch):
        import odigos_tpu.config.effective as eff_mod
        from odigos_tpu.config.effective import calculate_effective_config
        from odigos_tpu.config.model import Configuration

        monkeypatch.setattr(eff_mod, "_jax_version", lambda: "0.3")
        cfg = Configuration()
        cfg.anomaly.enabled = True
        cfg.anomaly.devices = 8
        eff = calculate_effective_config(cfg)
        assert eff.config.anomaly.devices == 1
        assert any("shard-map-scoring" in p for p in eff.problems)
        assert eff.features["shard-map-scoring"]["enabled"] is False

    def test_effective_config_keeps_dp_with_gate(self):
        from odigos_tpu.config.effective import calculate_effective_config
        from odigos_tpu.config.model import Configuration

        cfg = Configuration()
        cfg.anomaly.enabled = True
        cfg.anomaly.devices = 8
        eff = calculate_effective_config(cfg)  # real jax is new enough
        assert eff.config.anomaly.devices == 8
        assert eff.features["shard-map-scoring"]["enabled"] is True

    def test_snapshot_lands_in_effective_configmap(self):
        from odigos_tpu.api import ControllerManager, Store
        from odigos_tpu.config.model import Configuration
        from odigos_tpu.controlplane import Scheduler
        from odigos_tpu.controlplane.scheduler import (
            EFFECTIVE_CONFIG_NAME, ODIGOS_NAMESPACE)

        store = Store()
        mgr = ControllerManager(store)
        sched = Scheduler(store, mgr)
        sched.apply_authored(Configuration())
        mgr.run_once()
        cm = store.get("ConfigMap", ODIGOS_NAMESPACE, EFFECTIVE_CONFIG_NAME)
        assert cm is not None and "features" in cm.data
        assert "shard-map-scoring" in cm.data["features"]


class TestComponentObservability:
    """Every registered data-path component factory must record at least
    one own-telemetry metric or span (ISSUE 1 satellite): a component
    whose class hierarchy never touches ``meter`` or ``tracer`` ships
    invisible to the self-telemetry pipeline, /metrics, and the diagnose
    bundle. Static import-and-inspect — no runtime pipeline needed.

    Components inheriting the instrumented ``Processor.consume`` /
    ``Exporter.consume`` weave pass through their base class; components
    that OVERRIDE consume (stateful batching, memory limiting, routing)
    must record their own metric or span. Extensions are exempt: they sit
    outside the data path (health/zpages/pprof serve diagnostics, they do
    not carry batches)."""

    DATA_PATH_KINDS = ("receiver", "processor", "exporter", "connector")
    MARKERS = ("meter.", "tracer.")

    def test_every_component_factory_records_own_telemetry(self):
        import inspect

        import odigos_tpu.components  # noqa: F401  (registers factories)
        from odigos_tpu.components.api import registry

        unobservable = []
        for (kind, type_name), factory in sorted(
                registry._factories.items(),
                key=lambda kv: (kv[0][0].value, kv[0][1])):
            if kind.value not in self.DATA_PATH_KINDS:
                continue
            create = factory.create
            classes = getattr(create, "__mro__", None) or [create]
            blob = []
            for cls in classes:
                if getattr(cls, "__module__", "").startswith("odigos_tpu"):
                    try:
                        blob.append(inspect.getsource(cls))
                    except (OSError, TypeError):
                        pass
            source = "\n".join(blob)
            if not any(m in source for m in self.MARKERS):
                unobservable.append(f"{kind.value}/{type_name} "
                                    f"({create!r})")
        assert not unobservable, (
            "components with no own-telemetry metric or span — add a "
            "meter counter or tracer span before registering:\n  "
            + "\n  ".join(unobservable))


class TestFlightTriggerHygiene:
    """Flight-recorder trigger lint (ISSUE 16 satellite): the TRIGGERS
    registry is the closed vocabulary of incident causes, so it must
    stay honest in both directions — every registered trigger has at
    least one literal ``flight_recorder.trigger("name", ...)`` call
    site in the package (a trigger nobody can fire is a dead registry
    entry that pads the /debug/incidentz table), and every literal
    call site names a registered trigger (the runtime check raises
    ValueError, but the lint catches the typo before any test has to
    reach that code path). With a stale-entry oracle, and the
    odigos_flightrecorder_* metric family checked against the ISSUE 3
    name registry."""

    @staticmethod
    def _trigger_call_sites() -> dict:
        """trigger-name -> [file:line, ...] for every literal
        ``<recv>.trigger("name", ...)`` call in odigos_tpu/."""
        sites: dict = {}
        for dirpath, _dirs, files in os.walk(PKG_ROOT):
            for n in files:
                if not n.endswith(".py"):
                    continue
                path = os.path.join(dirpath, n)
                with open(path) as f:
                    tree = ast.parse(f.read())
                for node in ast.walk(tree):
                    if (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Attribute)
                            and node.func.attr == "trigger"
                            and node.args
                            and isinstance(node.args[0], ast.Constant)
                            and isinstance(node.args[0].value, str)):
                        sites.setdefault(node.args[0].value, []).append(
                            f"{os.path.relpath(path, PKG_ROOT)}:"
                            f"{node.lineno}")
        return sites

    @staticmethod
    def _check(registry: dict, sites: dict) -> list:
        """Problems for a (registry, call-sites) pair — factored so the
        stale-entry oracle can drive it with a doctored registry."""
        problems = []
        for name in sorted(registry):
            if name not in sites:
                problems.append(
                    f"trigger {name!r} registered but never fired "
                    f"anywhere in the package (stale entry)")
        for name, where in sorted(sites.items()):
            if name not in registry:
                problems.append(
                    f"trigger {name!r} fired at {where} but not in "
                    f"the TRIGGERS registry")
        return problems

    def test_trigger_registry_closed_both_directions(self):
        from odigos_tpu.selftelemetry.flightrecorder import TRIGGERS

        sites = self._trigger_call_sites()
        assert sites, "no flight_recorder.trigger call sites at all?"
        assert self._check(TRIGGERS, sites) == []

    def test_stale_entry_oracle(self):
        """The lint's own oracle: a ghost registry entry nobody fires,
        and a call site naming an unregistered trigger, must both be
        flagged (guards against the scan degenerating into a no-op)."""
        from odigos_tpu.selftelemetry.flightrecorder import TRIGGERS

        sites = self._trigger_call_sites()
        ghost = dict(TRIGGERS)
        ghost["_ghost_trigger"] = "never fired by anyone"
        problems = self._check(ghost, sites)
        assert any("_ghost_trigger" in p and "stale" in p
                   for p in problems), problems
        rogue = dict(sites)
        rogue["_rogue_trigger"] = ["nowhere.py:1"]
        problems = self._check(TRIGGERS, rogue)
        assert any("_rogue_trigger" in p and "registry" in p
                   for p in problems), problems

    def test_unregistered_trigger_raises_at_runtime(self):
        """The runtime half of the closed registry: trigger() on an
        unknown name is a programming error, not a silent no-op."""
        from odigos_tpu.selftelemetry.flightrecorder import FlightRecorder

        fr = FlightRecorder()
        with pytest.raises(ValueError, match="_not_a_trigger"):
            fr.trigger("_not_a_trigger", detail="x")

    def test_trigger_descriptions_nonempty(self):
        """Every registry entry carries a human description — the
        /debug/incidentz trigger table renders these."""
        from odigos_tpu.selftelemetry.flightrecorder import TRIGGERS

        assert TRIGGERS, "TRIGGERS registry empty?"
        for name, desc in TRIGGERS.items():
            assert re.fullmatch(r"[a-z_]+", name), name
            assert isinstance(desc, str) and desc.strip(), name

    def test_flightrecorder_metric_names_registered(self):
        """The odigos_flightrecorder_* family must resolve against the
        registered name registry (the TestFleetRuleHygiene scan) — the
        constants must stay string literals for the AST scan to see
        them."""
        from odigos_tpu.selftelemetry import flightrecorder as fr

        registry = TestFleetRuleHygiene._registered_metric_names()
        for name in (fr.EVENTS_METRIC, fr.EVENTS_EVICTED_METRIC,
                     fr.INCIDENTS_METRIC, fr.SUPPRESSED_METRIC,
                     fr.INCIDENTS_EVICTED_METRIC):
            assert name.startswith("odigos_flightrecorder_"), name
            assert name in registry, name


class TestDeviceSubStageHygiene:
    """Device-attribution vocabulary lint (ISSUE 20 satellite):
    ``SUB_STAGES`` is the closed intra-fused sub-stage vocabulary, so it
    must stay honest in both directions — every entry has exactly one
    ``_stage_<name>`` builder in ``serving/deviceattrib.py`` (an entry
    with no builder is a stale vocabulary row the waterfall can never
    fill), and every ``_stage_*`` builder names a vocabulary entry (a
    builder outside the vocabulary would publish an unaggregatable
    stage). Same discipline for ``SKIP_REASONS`` against the literal
    ``_skip("reason")`` call sites, with stale-entry oracles for both
    scans, plus the ISSUE 3 name-registry check for the new
    ``odigos_xla_*`` / ``odigos_device_*`` metric families."""

    DEVICEATTRIB = os.path.join(PKG_ROOT, "serving", "deviceattrib.py")

    @classmethod
    def _builder_names(cls) -> dict:
        """sub-stage name -> lineno for every module-level
        ``_stage_<name>`` def in serving/deviceattrib.py."""
        with open(cls.DEVICEATTRIB) as f:
            tree = ast.parse(f.read())
        out = {}
        for node in tree.body:
            if isinstance(node, ast.FunctionDef) \
                    and node.name.startswith("_stage_"):
                out[node.name[len("_stage_"):]] = node.lineno
        return out

    @classmethod
    def _skip_call_sites(cls) -> dict:
        """reason -> [lineno, ...] for every literal
        ``<recv>._skip("reason")`` call in serving/deviceattrib.py."""
        with open(cls.DEVICEATTRIB) as f:
            tree = ast.parse(f.read())
        out: dict = {}
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "_skip"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                out.setdefault(node.args[0].value, []).append(node.lineno)
        return out

    @staticmethod
    def _check(vocab, sites, what) -> list:
        problems = []
        for name in vocab:
            if name not in sites:
                problems.append(
                    f"{what} {name!r} declared but has no call/builder "
                    f"site (stale entry)")
        for name in sorted(sites):
            if name not in vocab:
                problems.append(
                    f"{what} {name!r} present in code at {sites[name]} "
                    f"but not in the declared vocabulary")
        return problems

    def test_substage_vocabulary_closed_both_directions(self):
        from odigos_tpu.serving.deviceattrib import (
            _STAGE_BUILDERS, SUB_STAGES)

        builders = self._builder_names()
        assert builders, "no _stage_* builders found at all?"
        assert self._check(SUB_STAGES, builders, "sub-stage") == []
        # the dispatch table agrees with both sides and keeps order
        assert tuple(_STAGE_BUILDERS) == SUB_STAGES

    def test_skip_reasons_closed_both_directions(self):
        from odigos_tpu.serving.deviceattrib import SKIP_REASONS

        sites = self._skip_call_sites()
        assert sites, "no _skip call sites found at all?"
        assert self._check(SKIP_REASONS, sites, "skip reason") == []

    def test_stale_entry_oracle(self):
        """The scans' own oracle: a ghost vocabulary entry with no
        builder/site, and a builder/site outside the vocabulary, must
        both be flagged (guards against either scan degenerating into
        a no-op)."""
        from odigos_tpu.serving.deviceattrib import (
            SKIP_REASONS, SUB_STAGES)

        builders = self._builder_names()
        problems = self._check(SUB_STAGES + ("_ghost",), builders,
                               "sub-stage")
        assert any("_ghost" in p and "stale" in p for p in problems)
        doctored = dict(builders)
        doctored["_rogue"] = 1
        problems = self._check(SUB_STAGES, doctored, "sub-stage")
        assert any("_rogue" in p and "vocabulary" in p for p in problems)
        sites = self._skip_call_sites()
        problems = self._check(SKIP_REASONS + ("_ghost",), sites,
                               "skip reason")
        assert any("_ghost" in p and "stale" in p for p in problems)

    def test_device_metric_names_registered(self):
        """The odigos_xla_* / odigos_device_* / compile-event metric
        families must resolve against the registered name registry (the
        TestFleetRuleHygiene scan) — the constants must stay string
        literals for the AST scan to see them."""
        from odigos_tpu.models import costmodel, jitstats
        from odigos_tpu.serving import deviceattrib

        registry = TestFleetRuleHygiene._registered_metric_names()
        for name in (costmodel.XLA_FLOPS_METRIC,
                     costmodel.XLA_BYTES_METRIC,
                     costmodel.XLA_WASTE_METRIC,
                     costmodel.XLA_EFFICIENCY_METRIC):
            assert name.startswith("odigos_xla_"), name
            assert name in registry, name
        for name in (deviceattrib.ATTRIB_FRAMES_METRIC,
                     deviceattrib.ATTRIB_SKIPPED_METRIC):
            assert name.startswith("odigos_device_attrib_"), name
            assert name in registry, name
        assert jitstats.COMPILE_EVENTS_METRIC in registry
        # the footprint gauge is published with a literal name in the
        # DeviceRuntimeCollector — the registry scan must see it
        assert "odigos_device_table_bytes" in registry


class TestRootAndDocumentHygiene:
    """One way to measure, one record (ISSUE 32): ``benchmark/`` and the
    ledger are the measurement, so the root keeps no other record, and a
    document names no file that is not there — a how-to built on a
    deleted harness is worse than none."""

    DOCUMENTS = ("README.md", "DEVELOPMENT.md", "docs/architecture.md",
                 "docs/benchmarks.md", "docs/self-telemetry.md",
                 "docs/migration.md", "Makefile",
                 ".claude/skills/verify/SKILL.md")
    PREFIXES = ("odigos_tpu/", "tests/", "benchmark/", "tools/", "docs/",
                "distribution/")
    ROOT_FILE = re.compile(r"[\w.-]+\.(?:py|jsonl|json|md)")

    @staticmethod
    def _bundle_members() -> set:
        """Members of the diagnose archive (``latency.json`` ...): files
        of a bundle, not of the repository."""
        with open(os.path.join(PKG_ROOT, "cli", "diagnose.py")) as f:
            return set(re.findall(r'add\(\s*"([\w.-]+)"', f.read()))

    @classmethod
    def _stale_paths(cls, text: str, recipes: bool) -> list:
        import glob

        spans = re.findall(r"`([^`\n]+)`", text)
        if recipes:
            spans += [ln for ln in text.splitlines() if ln.startswith("\t")]
        members = cls._bundle_members()
        stale = set()
        for span in spans:
            for tok in span.split():
                tok = tok.strip("\"'()[],;").split("::")[0]
                tok = re.sub(r":\d+(-\d+)?$", "", tok).rstrip(".:")
                if "<" in tok or "{" in tok or "$" in tok or tok in members:
                    continue
                if not (cls.ROOT_FILE.fullmatch(tok)
                        or tok.startswith(cls.PREFIXES)):
                    continue
                if not glob.glob(os.path.join(REPO_ROOT, tok)):
                    stale.add(tok)
        return sorted(stale)

    def test_root_json_is_the_benchmark_and_the_baseline(self):
        import fnmatch

        with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
            ignored = [ln.strip() for ln in f if ln.strip()]
        found = sorted(
            n for n in os.listdir(REPO_ROOT) if n.endswith(".json")
            and not any(fnmatch.fnmatch(n, pat) for pat in ignored))
        assert found == ["BASELINE.json", "BENCHMARK.json"], (
            "a record at the root that nothing reads; the driver's "
            "readings live in PERF_LEDGER.jsonl")

    @pytest.mark.parametrize("document", DOCUMENTS)
    def test_documents_name_only_paths_that_exist(self, document):
        with open(os.path.join(REPO_ROOT, document)) as f:
            text = f.read()
        stale = self._stale_paths(text, recipes=document == "Makefile")
        assert not stale, f"{document} names {stale}, which do not exist"

    def test_scan_catches_a_stale_path(self):
        """The lint's own oracle: a deleted harness in backticks or in a
        recipe is caught, a bundle member and a placeholder are not."""
        text = ("run `python tools/gone.py --x` then `GONE.json`, see "
                "`tests/test_package_hygiene.py::TestX::test_y`, "
                "`latency.json` and `benchmark/configs/<name>.json`\n"
                "\tpython gone_too.py\n")
        assert self._stale_paths(text, recipes=True) == [
            "GONE.json", "gone_too.py", "tools/gone.py"]
        assert self._stale_paths(text, recipes=False) == [
            "GONE.json", "tools/gone.py"]
