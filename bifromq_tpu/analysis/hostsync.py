"""R1 — hot-path host-sync detector.

The device serving path is fast exactly as long as nothing on it forces
a host round-trip: one stray ``.item()`` / ``np.asarray`` /
``block_until_ready`` inside a jit'd walk body (a tracer leak) or the
async dispatch/fetch legs (a hidden synchronize) silently serializes the
dispatch ring and the whole pipeline degrades to the PR-6-era blocking
path. This rule walks every *hot zone* — functions decorated with (or
wrapped by) ``jax.jit`` anywhere in the package, plus the configured
dispatch/fetch-leg scopes in the four hot-path modules — and flags the
known host-sync shapes. Designated sync points (``_fetch_walk`` is THE
readback) carry suppression entries; everything else is a bug.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set

from .core import (Context, Finding, ParsedFile, Rule, dotted_name,
                   walk_local)

# scopes that are hot by construction even though nothing decorates them:
# the async dispatch/fetch legs, the patch-flush device update, and the
# helpers the jit'd walk bodies call into (reachability is configured,
# not inferred — an AST pass has no call graph across jit boundaries)
HOT_SCOPES: Dict[str, Set[str]] = {
    "models/matcher.py": {
        "TpuMatcher._prepare_probes", "TpuMatcher._dispatch_device",
        "TpuMatcher._dispatch_prepared", "TpuMatcher._walk_primary",
        "TpuMatcher._await_ready_sync",
        "TpuMatcher._fetch_walk", "TpuMatcher._expand_walk",
        "TpuMatcher._device_leg_async", "TpuMatcher._flush_patches",
    },
    "models/pipeline.py": {
        "DispatchRing.start_fetch", "DispatchRing.wait_ready",
    },
    "ops/match.py": {
        "_mix_u32", "_edge_lookup", "_bitonic_desc", "_advance",
        "_count_walk", "_route_walk", "_walk_routes_fn",
        "walk_routes_donated", "patch_device_trie", "_patch_device_trie",
        # ISSUE 19 device fan-out: the expansion/bucketing bodies the
        # jit'd expand stage traces, plus its dispatch wrapper — the
        # compact-pair readback lives in _fetch_walk, nothing here may
        # synchronize
        "_expand_pairs", "_bucket_pairs", "expand_routes",
    },
    # ISSUE 11 byte-plane prep: the device hash kernel's math + the
    # upload/dispatch wrappers feeding it
    # (+ ISSUE 17: the retained FILTER-probe twin — same host-structure
    # + device-hash split, wildcard kind lanes post-masked on device)
    "ops/tokenize.py": {"_hash_lanes", "hash_topics_device",
                        "device_tokenize", "device_tokenize_filters"},
    # ISSUE 12: the standby's per-batch device flush runs after every
    # applied delta batch — it must stay a pure dispatch wrapper (the
    # narrow scatters live in ops/match, already covered above)
    # (+ ISSUE 18: the apply loop itself now folds lag/audit telemetry
    # per record — that instrumentation must stay host-array-free too)
    "replication/standby.py": {"WarmStandby._flush_device",
                               "WarmStandby._offer_inner"},
    # ISSUE 18: the migration copy stream runs between serving batches;
    # its per-chunk progress accounting must not synchronize the ring
    "parallel/reshard.py": {"TenantMigration.step"},
    # ISSUE 15: the mesh serving legs — stage-1 prep (shard routing +
    # tokenize + grid upload), the step enqueue, the per-shard patch
    # flush, and the expansion that runs against the in-flight snapshot
    "parallel/sharded.py": {
        "MeshMatcher._prepare_probes", "MeshMatcher._dispatch_prepared",
        "MeshMatcher._flush_patches", "MeshMatcher._expand_walk",
        "make_match_step", "_shard_scatter", "_shard_scatter_donated",
        "_shard_slice_set", "_shard_slice_set_donated",
        # ISSUE 19: the per-shard expand step (shard_map body) that
        # returns pre-bucketed per-peer pair grids over the permute ring
        "make_expand_step",
    },
    # ISSUE 13 retained serving plane: the scan dispatch leg (patch
    # flush + walk enqueue) and the async ring leg must stay sync-free;
    # the one true synchronization lives in RetainedIndex.fetch_scan —
    # the retained twin of the matcher's designated _fetch_walk readback
    "models/retained.py": {"RetainedIndex.dispatch_scan",
                           "RetainedIndex.flush_device"},
    "ops/retained.py": {"_bucket_lookup", "retained_walk_ext",
                        "patch_retained_tables", "_patch_retained"},
    "retained_plane/scan.py": {"RetainedScanPlane._device_serve_async"},
}

# host-sync call shapes (module-qualified callee names)
_SYNC_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get", "device_get",
}
# host-sync method names (attribute calls on anything)
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}


def _jit_wrapped_names(tree: ast.Module) -> Set[str]:
    """Function names that are jit'd: ``@jax.jit`` /
    ``@functools.partial(jax.jit, ...)`` decorations, plus
    ``name = functools.partial(jax.jit, ...)(fn)`` / ``jax.jit(fn)``
    wrappings (the wrapped ``fn`` becomes hot)."""
    hot: Set[str] = set()

    def is_jit_expr(node: ast.AST) -> bool:
        name = dotted_name(node)
        if name in ("jax.jit", "jit"):
            return True
        if isinstance(node, ast.Call) \
                and dotted_name(node.func) in ("functools.partial",
                                               "partial"):
            return any(dotted_name(a) in ("jax.jit", "jit")
                       for a in node.args)
        return False

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(is_jit_expr(d) for d in node.decorator_list):
                hot.add(node.name)
        elif isinstance(node, ast.Call):
            # jax.jit(fn) / functools.partial(jax.jit, ...)(fn)
            target = None
            if dotted_name(node.func) in ("jax.jit", "jit") and node.args:
                target = node.args[0]
            elif isinstance(node.func, ast.Call) \
                    and is_jit_expr(node.func) and node.args:
                target = node.args[0]
            if isinstance(target, ast.Name):
                hot.add(target.id)
    return hot


class HostSyncRule(Rule):
    rule_id = "R1"
    title = "hot-path host sync"

    def run(self, ctx: Context) -> List[Finding]:
        out: List[Finding] = []
        for pf in ctx.files:
            jit_names = _jit_wrapped_names(pf.tree)
            configured = HOT_SCOPES.get(pf.path.replace("\\", "/"), set())
            seen = self._scan(pf, jit_names, configured, out)
            # dead-config validation (same no-rot contract as dead
            # suppressions): a configured hot scope that matches no def
            # in its file means a rename silently dropped coverage
            for entry in sorted(configured - seen):
                out.append(Finding(
                    rule=self.rule_id, path=pf.path, line=0,
                    scope="<config>", symbol=entry,
                    message=(f"HOT_SCOPES entry `{entry}` matches no "
                             f"function in {pf.path} — renamed hot "
                             f"scope silently lost R1 coverage; update "
                             f"the config")))
        return out

    def _scan(self, pf: ParsedFile, jit_names: Set[str],
              configured: Set[str], out: List[Finding]) -> Set[str]:
        seen: Set[str] = set()

        def visit_defs(node: ast.AST, prefix: str, hot: bool) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qual = f"{prefix}.{child.name}" if prefix \
                        else child.name
                    if qual in configured:
                        seen.add(qual)
                    child_hot = (hot or child.name in jit_names
                                 or qual in configured)
                    if child_hot:
                        self._check_body(pf, child, qual, out)
                    # nested defs inherit hotness (a jit body's inner
                    # step()/body() functions are traced too)
                    visit_defs(child, qual, child_hot)
                elif isinstance(child, ast.ClassDef):
                    cls_prefix = f"{prefix}.{child.name}" if prefix \
                        else child.name
                    visit_defs(child, cls_prefix, hot)
                else:
                    visit_defs(child, prefix, hot)

        visit_defs(pf.tree, "", False)
        return seen

    def _check_body(self, pf: ParsedFile, fn: ast.AST, qual: str,
                    out: List[Finding]) -> None:
        # walk_local: visit_defs re-checks nested defs under their own
        # qualname (with inherited hotness) — descending here too would
        # report one site twice under two suppression keys
        for node in walk_local(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            symbol = None
            if callee in _SYNC_CALLS:
                symbol = callee
                msg = (f"host sync `{callee}(...)` in hot zone `{qual}` "
                       f"— forces a device round-trip on the match path")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_METHODS \
                    and not node.args and not node.keywords:
                symbol = f".{node.func.attr}"
                msg = (f"host sync `.{node.func.attr}()` in hot zone "
                       f"`{qual}` — blocks until the device result "
                       f"lands on host")
            elif callee in ("float", "int") and len(node.args) == 1:
                if self._scalar_coercion_suspect(node.args[0]):
                    symbol = f"{callee}()"
                    msg = (f"`{callee}(...)` on a (possibly device) "
                           f"array in hot zone `{qual}` — scalar "
                           f"coercion is an implicit blocking fetch")
            if symbol is not None:
                out.append(Finding(
                    rule=self.rule_id, path=pf.path, line=node.lineno,
                    scope=qual, symbol=symbol, message=msg))

    @staticmethod
    def _scalar_coercion_suspect(arg: ast.AST) -> bool:
        """float(x)/int(x) is only suspect when x could be a device
        array: bare names and subscripts qualify; attribute reads of
        host-side shape/size metadata (``a.shape[0]``, ``a.nbytes``)
        and literals do not."""
        if isinstance(arg, ast.Constant):
            return False
        if isinstance(arg, ast.Name):
            return True
        if isinstance(arg, ast.Subscript):
            base = arg.value
            if isinstance(base, ast.Attribute) and base.attr == "shape":
                return False
            return True
        return False
