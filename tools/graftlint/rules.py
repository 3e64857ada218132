"""graftlint rules: the seven project-specific TPU-hot-path checks.

Every rule has a code, a one-line fix-it in its message, and a scope:

  JGL001  implicit device->host sync inside a hot module
  JGL002  jit-cache churn (jit in a function body, lambda targets,
          unhashable static specs)
  JGL003  tracer leak (traced values stored on self / globals from inside
          a jitted function)
  JGL004  silent fallback (broad except on a device-dispatch path with no
          log/metric and no re-raise)
  JGL005  module-level mutable state mutated without a lock
  JGL006  dtype drift (float64 spellings in kernel-adjacent code)
  JGL007  span leak (a trace span opened in serving/db code without a
          structural close: neither a `with` nor a close in `finally`)
  JGL008  blocking device fetch under a held lock (np.asarray /
          .block_until_ready() on a device value inside a
          `with <lock>:` block — lexically, or one call deep through a
          same-module helper via the ModuleIndex call graph) — the
          read-path serialization the snapshot-isolated dispatch plane
          removed
  JGL009  unbounded blocking wait (`wait()`/`get()`/`acquire()` with no
          timeout) on the serving path — directly, or one call deep
          through a same-module helper invoked under a lock — one
          wedged producer then hangs a client forever instead of
          failing fast
  JGL010  dynamically-constructed metric label value (f-string/.format/
          %-format/concat of a runtime value passed to `.labels(...)`) —
          unbounded label cardinality mints a Prometheus series per
          distinct value (10k tenants = 10k series); route identities
          through a bounded mapper (metrics.TenantLabeler) or a fixed
          enum instead
  JGL011  unguarded background-thread run-loop (a loop in a
          threading.Thread target with no exception guard) — one
          surprise exception then kills the daemon silently; a dead
          audit thread reads as recall=perfect, a dead flusher as an
          empty queue
  JGL012  unaccounted HBM allocation (a call result — jnp.asarray /
          jax.device_put / a kernel output — bound to a snapshot/slab
          field in index/ from a method that never stamps the memory
          ledger) — buffers the ledger cannot see make /debug/memory's
          exhaustion forecast a lie
  JGL013  unregistered/dynamic ops-journal event kind (an incidents.emit
          call site outside monitoring/incidents.py whose kind argument
          is not a literal from the registered EVENT_KINDS taxonomy) —
          a dynamic kind would fold to "other" at runtime (losing its
          identity in every bundle) and an unregistered literal is a
          typo the fold would silently swallow
  JGL014  controller-owned knob actuated outside the control plane's
          clamped actuate helper (a call to a knob setter —
          set_knob/set_sample_rate/set_pipeline_depth — or a non-self
          write to a controller knob field, anywhere but serving/
          controller.py) — an unclamped, unjournaled, unleased write
          bypasses every fail-static guarantee the control plane makes

Scope model: the ISSUE's hot modules (ops/, index/tpu.py, index/mesh.py,
compress/pq.py, inverted/bm25_device.py, parallel/mesh_search.py) gate
JGL001/JGL004/JGL006; JGL002/JGL003/JGL005 apply package-wide; JGL007
gates the request-tracing scope (weaviate_tpu/serving/, weaviate_tpu/db/ —
where spans cross the coalescer's thread handoffs and a leaked one
corrupts every rider's trace tree); JGL008 gates weaviate_tpu/index/ +
weaviate_tpu/db/ (where a fetch inside a lock convoys every concurrent
reader AND writer on one mutex for a whole device round trip); JGL009
gates weaviate_tpu/serving/ + weaviate_tpu/db/ (the request path whose
every wait must be bounded by a deadline or a liveness cap —
serving/robustness.py); JGL010 gates all of weaviate_tpu/ (every
monitoring/metrics.py call site — labels are registered in one place but
observed everywhere); JGL011 gates all of weaviate_tpu/ too (daemon
threads are spawned from every layer — monitors, compaction cycles,
gossip, the coalescer flusher, the quality auditor). JGL001
additionally skips boundary functions whose JOB is host materialization —
that allowlist lives here, in one place, so reviewers see every waiver.

The analysis is intentionally type-free (pure ast): device residency is
tracked with a small per-function dataflow over names assigned from jnp.*
calls, jax.device_put, module-level jitted functions, and the known device
attributes of the index classes. That catches the real regressions (a new
`.item()` or `np.asarray(self._store...)` on the serving path) without a
type checker; what it over-reports lands in the baseline with a written
justification, which is the point.
"""

from __future__ import annotations

import ast
from typing import Optional

from tools.graftflow import resolve
from tools.graftlint.engine import Finding

# -- scope configuration -----------------------------------------------------

HOT_PREFIXES = (
    "weaviate_tpu/ops/",
    "weaviate_tpu/parallel/mesh_search.py",
    "weaviate_tpu/index/tpu.py",
    "weaviate_tpu/index/mesh.py",
    "weaviate_tpu/compress/pq.py",
    "weaviate_tpu/inverted/bm25_device.py",
)

# (path, qualname) pairs whose JOB is crossing the device->host boundary:
# JGL001 stays silent inside them. Keep this list tiny and obvious.
JGL001_BOUNDARY = {
    ("weaviate_tpu/ops/topk.py", "unpack_topk"),
    ("weaviate_tpu/ops/bm25_scan.py", "unpack_topk"),
}

# instance attributes that hold device arrays in the index/engine classes;
# reading them into float()/np.asarray() is a sync
DEVICE_ATTRS = frozenset({
    "_store", "_codes", "_tombs", "_sq_norms", "_recon_norms",
    "_rescore_dev", "_rescore_sq_norms", "_shards", "_masks", "_rows",
})

MUTATING_METHODS = frozenset({
    "append", "add", "update", "pop", "popitem", "clear", "setdefault",
    "extend", "remove", "insert", "move_to_end", "discard",
})

# JGL007 scope: the serving/trace path, where an unclosed span survives the
# request and corrupts the trace tree of every later rider in its lane
JGL007_PREFIXES = (
    "weaviate_tpu/serving/",
    "weaviate_tpu/db/",
)

# span-opening call names: the tracing API's open-ended constructors. The
# safe forms are `with tracing.span(...)` / `with tracing.request(...)`
# (structurally closed) — these names are the escape hatches that return an
# open object the caller must close.
SPAN_OPEN_NAMES = frozenset({
    "span_start", "start_span", "child_start", "dispatch_record",
    "start_request",
})

# calls that close a span-like object when they appear in a finally block
SPAN_CLOSE_NAMES = frozenset({"end", "finish", "close"})

# JGL008 scope: the index + db layers, where the snapshot-isolated read
# plane (index/tpu.py IndexSnapshot) guarantees device fetches happen
# OUTSIDE any lock — a fetch that creeps back under one convoys every
# reader and stalls every writer for a device round trip
JGL008_PREFIXES = (
    "weaviate_tpu/index/",
    "weaviate_tpu/db/",
)

# JGL009 scope: the serving path, where every blocking wait must carry a
# timeout (deadline-derived where one exists, a liveness cap otherwise) —
# a bare wait() is how a wedged flush thread hangs a client forever
JGL009_PREFIXES = (
    "weaviate_tpu/serving/",
    "weaviate_tpu/db/",
)

# zero-positional-arg attribute calls that block forever without a bound.
# `.get(key)` / `.wait(5)` / `.acquire(timeout=...)` all pass: any
# positional argument or a timeout/block(ing) kwarg counts as bounded
# (approximate on purpose — what it over-reports lands in the baseline
# with a written justification, the JGL001 philosophy). Shared with
# graftflow's interprocedural wait summaries — one definition.
UNBOUNDED_WAIT_NAMES = resolve.UNBOUNDED_WAIT_NAMES

RULE_DOCS = {
    "JGL000": "suppression hygiene: every inline disable needs a reason and "
              "must still match a finding",
    "JGL001": "implicit device->host sync in a hot module — batch the "
              "fetch at the boundary instead",
    "JGL002": "jit-cache churn — hoist jax.jit to module scope / cache the "
              "compiled callable; never jit a lambda or pass an unhashable "
              "static spec",
    "JGL003": "tracer leak — a traced value stored on self/globals escapes "
              "the trace; return it instead",
    "JGL004": "silent fallback — a broad except on a device-dispatch path "
              "must log (rate-limited) and count a fallback metric, or "
              "re-raise",
    "JGL005": "module-level mutable state mutated without holding a lock — "
              "serving threads share module globals",
    "JGL006": "dtype drift — float64 in kernel-adjacent code silently "
              "doubles bandwidth and falls off the MXU fast path",
    "JGL007": "span leak — a trace span opened in serving/db code must "
              "close structurally: `with tracing.span(...)`, or open "
              "inside a `try:` whose `finally:` calls .end()/.finish()",
    "JGL008": "blocking device fetch under a held lock — lexically, or "
              "one call deep through a same-module helper (the "
              "interprocedural one-level call graph) — dispatch inside, "
              "fetch OUTSIDE the critical section (snapshot two-phase "
              "pattern, index/tpu.py _dispatch_search)",
    "JGL009": "unbounded blocking wait — wait()/get()/acquire()/join() "
              "with no timeout on the serving path (directly, or one "
              "call deep through a same-module helper invoked under a "
              "lock) can hang a request forever; pass an explicit "
              "timeout (deadline-derived where one exists — "
              "serving/robustness.py)",
    "JGL010": "dynamically-constructed metric label value — an f-string/"
              ".format/%-format/concat of a runtime value at a "
              ".labels(...) call site mints one Prometheus series per "
              "distinct value; pass a bounded variable (route identities "
              "through metrics.TenantLabeler or a fixed enum)",
    "JGL011": "unguarded background-thread run-loop — a loop inside a "
              "threading.Thread target with no try/except anywhere in or "
              "around it dies silently on the first surprise exception "
              "(a dead audit thread reads as recall=perfect); wrap the "
              "loop body in try/except (log + continue) or the loop in a "
              "guarded supervisor",
    "JGL012": "unaccounted HBM allocation — a device-buffer-creating call "
              "bound to a snapshot/slab field must flow through the "
              "ledger-registered builder: the enclosing method must call "
              "_stamp_memory()/_publish_snapshot() (monitoring/memory.py) "
              "so /debug/memory's bytes and exhaustion forecast stay "
              "truthful, or carry a justified suppression",
    "JGL013": "unregistered or dynamically-built ops-journal event kind — "
              "incidents.emit() call sites outside monitoring/incidents.py "
              "must pass a literal kind from the registered EVENT_KINDS "
              "taxonomy (the static twin of the runtime bounded-kind "
              "fold): a dynamic kind loses its identity in every incident "
              "bundle, an unregistered literal is a silently-swallowed "
              "typo; register the kind in incidents.EVENT_KINDS (and the "
              "JOURNAL_EVENT_KINDS mirror here) or use an existing one",
    "JGL014": "controller-owned knob actuated outside serving/"
              "controller.py's clamped actuate helper — knob writes "
              "must ride ControlPlane._set_knob (clamped, leased, "
              "journaled) or the controller's own object actuations; a "
              "direct setter call or knob-field write elsewhere bypasses "
              "the clamp, the journal, and the fail-static revert",
    "JGL015": "host post-processing in a fused finalize/unpack path — "
              "inside index-layer functions named `finalize` (or "
              "containing `unpack`), per-row Python loops over fetched "
              "results and np.asarray on anything but the one packed "
              "buffer are findings: the fused dispatch contract is ONE "
              "blocking fetch that already carries final doc ids, "
              "consumed with vectorized dtype views "
              "(ops/topk.unpack_fused) — a loop or a second asarray "
              "re-grows the host hop the fusion deleted",
    "JGL999": "file does not parse",
}

# JGL013: the registered ops-journal event kinds. A MIRROR of
# weaviate_tpu/monitoring/incidents.py EVENT_KINDS — graftlint is a pure
# ast tool and must not import the package it lints; the two sets are
# pinned equal by tests/test_incidents.py, so drift fails the suite.
JOURNAL_EVENT_KINDS = frozenset({
    "breaker_open", "breaker_half_open", "breaker_closed",
    "shed_burst", "deadline_burst",
    "quality_degraded", "quality_recovered",
    "memory_alert", "memory_recovered",
    "jit_compile", "device_fallback", "flusher_dead",
    "write_phase", "fault_injected",
    "slo_burn", "slo_recovered",
    "incident_dump", "teardown",
    "controller_actuation", "controller_brownout", "controller_revert",
})

# JGL013 scope: everywhere in the package EXCEPT the journal module
# itself (whose emit() implementation and internal re-emissions own the
# taxonomy). The kinds are registered in one place but emitted from
# every plane — the JGL010 shape, applied to event kinds.
JGL013_PREFIXES = ("weaviate_tpu/",)
JGL013_EXEMPT_SUFFIX = "monitoring/incidents.py"

# JGL014 scope: everywhere in the package EXCEPT the control plane
# itself (serving/controller.py owns the clamped actuate helper and the
# object actuations it makes). Knob setters are defined on the objects
# they steer (tracing.Tracer.set_sample_rate, QualityAuditor.
# set_sample_rate, QueryCoalescer.set_pipeline_depth) but may be CALLED
# only by the controller — anywhere else, the write bypasses the clamp,
# the actuation journal, and the fail-static revert/lease machinery.
JGL014_PREFIXES = ("weaviate_tpu/",)
JGL014_EXEMPT_SUFFIX = "serving/controller.py"

# the knob setter methods only the control plane may call
CONTROLLER_KNOB_SETTERS = frozenset({
    "_set_knob", "set_sample_rate", "set_pipeline_depth",
})

# controller-owned knob FIELDS: distinctly-named attributes of the
# plane's store/consumers that nothing outside controller.py may assign
# (self-writes are the owner's constructor/defaults and stay legal)
CONTROLLER_KNOB_FIELDS = frozenset({
    "admission_margin", "tenant_cap_scale", "retry_after_scale",
    "rescore_r_cap", "rate_scale", "brownout_stage", "_knobs",
    # the IVF probe-count cap — the second recall-guarded budget
    "ivf_top_p", "ivf_top_p_cap",
    # the 4-bit funnel's stage budgets — the third and fourth
    # recall-guarded budgets (serving/controller.py FC_/FR_BUCKETS)
    "funnel_c_cap", "funnel_rescore_cap",
})

# JGL010 scope: the whole package — metric vecs are registered once in
# monitoring/metrics.py but label values are supplied at every call site,
# and ONE dynamic value anywhere unbounds the series set
JGL010_PREFIXES = ("weaviate_tpu/",)

# JGL011 scope: the whole package — daemon threads are spawned from every
# layer (monitors, compaction cycles, gossip, the coalescer flusher, the
# quality audit workers), and any of them dying silently inverts a signal
JGL011_PREFIXES = ("weaviate_tpu/",)

# JGL012 scope: the index layer, where HBM-resident snapshot/slab buffers
# are born — an allocation bound to one of these fields from a method
# that never stamps the memory ledger is a byte the capacity forecast
# cannot see (an unaccounted buffer reads as headroom that isn't there)
JGL012_PREFIXES = ("weaviate_tpu/index/",)

# the snapshot/slab fields that hold device buffers (index/tpu.py
# IndexSnapshot fields + index/mesh.py slab fields)
SNAPSHOT_FIELDS = frozenset({
    "_store", "_sq_norms", "_tombs", "_codes", "_recon_norms",
    "_rescore_dev", "_rescore_sq_norms", "_zero_words", "_s2d_dev",
    # the IVF scan plane's device slabs (index/tpu.py): centroids,
    # padded partition buckets, PCA projection + per-slot low-dim rows
    "_ivf_centroids", "_ivf_buckets", "_ivf_pca_proj", "_ivf_pca_rows",
    # the 4-bit Quick-ADC ladder's slabs (index/tpu.py): packed codes,
    # reconstruction norms, and the shared OPQ rotation matrix
    "_codes4", "_recon_norms4", "_opq_rot_dev",
})

# calls that route an allocation through the ledger: the per-class
# stamping hook, or snapshot publication (which stamps as its last step)
LEDGER_STAMP_CALLS = frozenset({"_stamp_memory", "_publish_snapshot"})

# JGL015 scope: the index layer's finalize/unpack code paths — where a
# dispatch's fetched results are turned into caller-visible arrays. The
# static twin of the fused dispatch's zero-host-post-processing contract
# (index/tpu.py _finalize_fused): the one legal asarray is the packed
# fetch itself, and nothing iterates rows in Python.
JGL015_PREFIXES = ("weaviate_tpu/index/",)


def in_metric_label_scope(rel_path: str) -> bool:
    """JGL010 scope check (same interior-boundary matching as is_hot)."""
    rp = rel_path.replace("\\", "/")
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL010_PREFIXES)


def in_thread_runloop_scope(rel_path: str) -> bool:
    """JGL011 scope check (same interior-boundary matching as is_hot)."""
    rp = rel_path.replace("\\", "/")
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL011_PREFIXES)


def in_snapshot_ledger_scope(rel_path: str) -> bool:
    """JGL012 scope check (same interior-boundary matching as is_hot)."""
    rp = rel_path.replace("\\", "/")
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL012_PREFIXES)


def in_finalize_hostwork_scope(rel_path: str) -> bool:
    """JGL015 scope check (same interior-boundary matching as is_hot)."""
    rp = rel_path.replace("\\", "/")
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL015_PREFIXES)


def _is_finalize_name(name: str) -> bool:
    """JGL015 path predicate: finalize closures and unpack helpers."""
    return name == "finalize" or "unpack" in name


def in_journal_kind_scope(rel_path: str) -> bool:
    """JGL013 scope check: package-wide, minus the journal module."""
    rp = rel_path.replace("\\", "/")
    if rp.endswith(JGL013_EXEMPT_SUFFIX):
        return False
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL013_PREFIXES)


def in_controller_knob_scope(rel_path: str) -> bool:
    """JGL014 scope check: package-wide, minus the control plane."""
    rp = rel_path.replace("\\", "/")
    if rp.endswith(JGL014_EXEMPT_SUFFIX):
        return False
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL014_PREFIXES)


def in_span_scope(rel_path: str) -> bool:
    """JGL007 scope check (same interior-boundary matching as is_hot)."""
    rp = rel_path.replace("\\", "/")
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL007_PREFIXES)


def in_unbounded_wait_scope(rel_path: str) -> bool:
    """JGL009 scope check (same interior-boundary matching as is_hot)."""
    rp = rel_path.replace("\\", "/")
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL009_PREFIXES)


def in_lock_fetch_scope(rel_path: str) -> bool:
    """JGL008 scope check (same interior-boundary matching as is_hot)."""
    rp = rel_path.replace("\\", "/")
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in JGL008_PREFIXES)


def is_hot(rel_path: str) -> bool:
    """Hot-module check; prefixes also match at an interior path boundary so
    a checkout analyzed from outside the repo root still scopes correctly."""
    rp = rel_path.replace("\\", "/")
    return any(rp == p or rp.startswith(p) or f"/{p}" in rp
               for p in HOT_PREFIXES)


# -- small AST helpers -------------------------------------------------------

# one resolution engine: the dotted/jit helpers live in graftflow's
# resolve module now (the module-local layer both tools build on); the
# old names stay as aliases so rule code and tests read unchanged
dotted = resolve.dotted
_is_jit_expr = resolve.is_jit_expr
_jit_decorated = resolve.jit_decorated


def _const_str(node: ast.AST) -> Optional[str]:
    return node.value if isinstance(node, ast.Constant) and isinstance(
        node.value, str) else None


# -- module-level pre-pass ---------------------------------------------------

class ModuleIndex:
    """Facts the rules need before walking function bodies: names of
    module-level jitted callables (JGL001 dataflow), module-level mutable
    registries and locks (JGL005)."""

    def __init__(self, tree: ast.Module):
        self.jitted_fns: set[str] = set()
        self.registries: dict[str, int] = {}   # name -> def line
        self.locks: set[str] = set()
        # module-level ContextVars: their zero-arg .get() is a lookup, not
        # a blocking wait — JGL009 must not flag it
        self.contextvars: set[str] = set()
        # names of functions handed to threading.Thread(target=...) — bare
        # names and `self.<attr>` forms — anywhere in the module; these
        # are the run-loop candidates JGL011 audits. Deeper attribute
        # chains (self.httpd.serve_forever) point outside this module and
        # are skipped (under-approximation on purpose).
        self.thread_targets: set[str] = set()
        # one-level intra-module call graph (the interprocedural upgrade
        # for JGL008/JGL009): module-level functions by bare name, class
        # methods by (class, name) — the targets a `with <lock>:` body can
        # reach in one hop via `helper(...)` or `self.helper(...)`. The
        # indexing and the helper-body summaries (does it sync? does it
        # block unbounded?) live in tools/graftflow/resolve.py — the ONE
        # resolution engine graftflow's whole-program call graph also
        # builds on — and are cached here per function node. ONE level
        # deep on purpose in graftlint: a sync two calls down is
        # graftflow JGL016's job (any depth), and the runtime graftsan
        # device-sync sanitizer witnesses it too.
        self.defs = resolve.ModuleDefs(tree)
        self.functions = self.defs.functions
        self.methods = self.defs.methods
        self.jitted_fns = set(self.defs.jitted_fns)
        self._sync_cache: dict[int, list] = {}
        self._wait_cache: dict[int, list] = {}
        # local names bound to the incidents journal's emit() by a
        # `from ...monitoring.incidents import emit [as X]` — JGL013
        # audits bare-name calls through these too, so aliasing the
        # import can't dodge the kind check
        self.incident_emit_names: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) \
                    and (node.module or "").endswith("monitoring.incidents"):
                for a in node.names:
                    if a.name == "emit":
                        self.incident_emit_names.add(a.asname or "emit")
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if (dotted(node.func) or "") not in ("threading.Thread",
                                                 "Thread"):
                continue
            for kw in node.keywords:
                if kw.arg != "target":
                    continue
                t = dotted(kw.value)
                if t is None:
                    continue
                parts = t.split(".")
                if len(parts) == 1:
                    self.thread_targets.add(parts[0])
                elif len(parts) == 2 and parts[0] == "self":
                    self.thread_targets.add(parts[1])
        # defs/methods/jit callables come from the shared ModuleDefs index
        # above; this pass owns only the graftlint-specific module facts
        # (mutable registries, module locks, ContextVars)
        for node in tree.body:
            targets: list[ast.expr] = []
            value: Optional[ast.expr] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            if value is None:
                continue
            names = [t.id for t in targets if isinstance(t, ast.Name)]
            if not names:
                continue
            if self._is_mutable_literal(value):
                for n in names:
                    if n != "__all__":
                        self.registries[n] = node.lineno
            if isinstance(value, ast.Call) and (dotted(value.func) or "") in (
                    "threading.Lock", "threading.RLock", "Lock", "RLock"):
                self.locks.update(names)
            if isinstance(value, ast.Call) and (dotted(value.func) or "") in (
                    "contextvars.ContextVar", "ContextVar"):
                self.contextvars.update(names)

    @staticmethod
    def _is_mutable_literal(value: ast.expr) -> bool:
        if isinstance(value, (ast.Dict, ast.List, ast.Set)):
            return True
        if isinstance(value, ast.Call):
            f = dotted(value.func) or ""
            return f.split(".")[-1] in (
                "dict", "list", "set", "OrderedDict", "defaultdict", "deque")
        return False

    # -- one-level helper-body summaries (interprocedural JGL008/JGL009) -----
    # The traversal and fact extraction live in tools/graftflow/resolve.py
    # (the one resolution engine); this class keeps only the per-node
    # memoization and the graftlint-specific constants it feeds in.

    _walk_own_body = staticmethod(resolve.walk_own_body)

    def _helper_device_names(self, fn) -> set:
        return resolve.bound_device_names(fn, DEVICE_ATTRS, self.jitted_fns)

    def _is_device_expr(self, node, device_names: set) -> bool:
        return resolve.is_device_expr(node, device_names, DEVICE_ATTRS,
                                      self.jitted_fns)

    def helper_syncs(self, fn) -> list:
        """(line, description) for each blocking device->host sync in
        `fn`'s own body — the facts the interprocedural JGL008 reports at
        a lock-held call site one level up."""
        cached = self._sync_cache.get(id(fn))
        if cached is None:
            cached = resolve.sync_facts(fn, DEVICE_ATTRS, self.jitted_fns)
            self._sync_cache[id(fn)] = cached
        return cached

    def helper_waits(self, fn) -> list:
        """(line, description) for each unbounded blocking wait in `fn`'s
        own body — the interprocedural JGL009 facts."""
        cached = self._wait_cache.get(id(fn))
        if cached is None:
            cached = resolve.wait_facts(fn, self.contextvars)
            self._wait_cache[id(fn)] = cached
        return cached


# -- the walker --------------------------------------------------------------

class RuleWalker(ast.NodeVisitor):
    def __init__(self, rel_path: str, mod: ModuleIndex):
        self.rel = rel_path
        self.hot = is_hot(rel_path)
        self.span_scope = in_span_scope(rel_path)
        self.lock_fetch_scope = in_lock_fetch_scope(rel_path)
        self.unbounded_wait_scope = in_unbounded_wait_scope(rel_path)
        self.metric_label_scope = in_metric_label_scope(rel_path)
        self.journal_kind_scope = in_journal_kind_scope(rel_path)
        self.controller_knob_scope = in_controller_knob_scope(rel_path)
        self.thread_runloop_scope = in_thread_runloop_scope(rel_path)
        self.snapshot_ledger_scope = in_snapshot_ledger_scope(rel_path)
        self.finalize_hostwork_scope = in_finalize_hostwork_scope(rel_path)
        self.mod = mod
        # JGL012 state: per enclosing function, does it lexically call a
        # ledger stamping hook (_stamp_memory / _publish_snapshot)?
        self._stamp_fns: list[bool] = []
        # JGL015 state: per enclosing function, are we inside a
        # finalize/unpack path (nested helpers inherit — they run as part
        # of the finalize flow)?
        self._finalize_fns: list[bool] = []
        self.findings: list[Finding] = []
        self.scope: list[str] = []            # qualname stack
        self.class_stack: list[str] = []      # enclosing class names
        self.fn_stack: list = []              # enclosing function nodes
        self.fn_depth = 0
        self.loop_depth = 0
        self.jit_depth = 0                    # inside a jit-decorated fn
        self.with_locks = 0                   # enclosing `with <lock>:` blocks
        self.device_vars: list[set[str]] = []  # per-function device names
        self.global_names: list[set[str]] = []
        # JGL007 state: span-open calls that ARE a with-statement's context
        # expression (structurally closed), and the depth of enclosing
        # try-blocks whose finally calls a span close
        self._span_with_ctx: set[int] = set()
        self._span_finally_depth = 0

    # -- plumbing --

    def qualname(self) -> str:
        return ".".join(self.scope) or "<module>"

    def emit(self, code: str, node: ast.AST, message: str) -> None:
        self.findings.append(Finding(
            code, self.rel, getattr(node, "lineno", 1),
            getattr(node, "col_offset", 0), self.qualname(), message))

    def _track_device(self, name: str) -> None:
        if self.device_vars:
            self.device_vars[-1].add(name)

    def _is_device_value(self, node: ast.AST) -> bool:
        """Heuristic: does this expression hold a device array?"""
        if isinstance(node, ast.Subscript):
            return self._is_device_value(node.value)
        if isinstance(node, ast.Name):
            return bool(self.device_vars) and node.id in self.device_vars[-1]
        if isinstance(node, ast.Attribute):
            return node.attr in DEVICE_ATTRS
        if isinstance(node, ast.Call):
            f = dotted(node.func) or ""
            if f.startswith(("jnp.", "jax.lax.", "jax.numpy.")):
                return True
            if f in ("jax.device_put",):
                return True
            root = f.split(".")[0]
            return f in self.mod.jitted_fns or root in self.mod.jitted_fns
        return False

    # -- scope visitors --

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.scope.append(node.name)
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()
        self.scope.pop()

    def _visit_fn(self, node) -> None:
        # decorators and default values evaluate in the ENCLOSING scope at
        # def time — visit them before entering the function, so a
        # module-level `@functools.partial(jax.jit, ...)` is not mistaken
        # for a per-call jit (while a nested function's jit decorator still
        # correctly reads as inside the outer body)
        for dec in node.decorator_list:
            self.visit(dec)
        for default in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]:
            self.visit(default)
        self.scope.append(node.name)
        self.fn_stack.append(node)
        self._check_thread_runloop(node)
        self._stamp_fns.append(self._fn_calls_stamp(node))
        self._finalize_fns.append(
            _is_finalize_name(node.name)
            or bool(self._finalize_fns and self._finalize_fns[-1]))
        self.fn_depth += 1
        jitted = _jit_decorated(node)
        if jitted:
            self.jit_depth += 1
        self.device_vars.append(set())
        self.global_names.append(set())
        outer_loops, self.loop_depth = self.loop_depth, 0
        # a nested def's body runs LATER, outside any enclosing try/finally
        # — an enclosing close must not waive its span opens (JGL007) —
        # and outside any enclosing `with <lock>:` — the two-phase pattern
        # (dispatch under the lock, finalize-closure fetches after release)
        # must not read as a lock-held fetch (JGL008), nor may an
        # enclosing lock waive a closure's registry mutation (JGL005)
        outer_span_depth, self._span_finally_depth = \
            self._span_finally_depth, 0
        outer_locks, self.with_locks = self.with_locks, 0
        for stmt in node.body:  # decorators/defaults already visited above
            self.visit(stmt)
        self.with_locks = outer_locks
        self._span_finally_depth = outer_span_depth
        self.loop_depth = outer_loops
        self.global_names.pop()
        self.device_vars.pop()
        if jitted:
            self.jit_depth -= 1
        self.fn_depth -= 1
        self._stamp_fns.pop()
        self._finalize_fns.pop()
        self.fn_stack.pop()
        self.scope.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_Global(self, node: ast.Global) -> None:
        if self.global_names:
            self.global_names[-1].update(node.names)

    def _visit_loop(self, node) -> None:
        # For AND While: a `while i < rows:` loop is the same per-row
        # host post-processing JGL015 forbids, just spelled differently
        self._check_finalize_loop(node)
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop

    def visit_With(self, node: ast.With) -> None:
        locked = any(self._looks_like_lock(item.context_expr)
                     for item in node.items)
        if locked:
            self.with_locks += 1
        # a span-open call used AS the context expression is structurally
        # closed — mark it before visit_Call sees it (JGL007)
        marked = []
        for item in node.items:
            if isinstance(item.context_expr, ast.Call) \
                    and self._span_open_name(item.context_expr):
                marked.append(id(item.context_expr))
                self._span_with_ctx.add(id(item.context_expr))
        self.generic_visit(node)
        for i in marked:
            self._span_with_ctx.discard(i)
        if locked:
            self.with_locks -= 1

    def visit_Try(self, node: ast.Try) -> None:
        """A try whose finally closes a span opened IN its body covers the
        opens in that body (and handlers/else) — the
        `rec = tracing.dispatch_record(...)` + `finally: rec.finish()`
        idiom (JGL007). The close must be called ON a name the try body
        assigned from a span-open call: an unrelated `fh.close()` in the
        finally must not waive a genuinely leaked span."""
        opened: set[str] = set()
        for stmt in node.body + node.handlers + node.orelse:
            for sub in ast.walk(stmt):
                targets: list[ast.expr] = []
                value = None
                if isinstance(sub, ast.Assign):
                    targets, value = sub.targets, sub.value
                elif isinstance(sub, ast.AnnAssign) and sub.value is not None:
                    targets, value = [sub.target], sub.value
                if isinstance(value, ast.Call) and self._span_open_name(value):
                    for t in targets:
                        d = dotted(t)
                        if d:
                            opened.add(d)
        closes = False
        for stmt in node.finalbody:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr in SPAN_CLOSE_NAMES \
                        and (dotted(sub.func.value) or "") in opened:
                    closes = True
        if closes:
            self._span_finally_depth += 1
        for stmt in node.body + node.handlers + node.orelse:
            self.visit(stmt)
        if closes:
            self._span_finally_depth -= 1
        for stmt in node.finalbody:  # opens in the finally itself: uncovered
            self.visit(stmt)

    @staticmethod
    def _call_last_name(node: ast.Call) -> str:
        if isinstance(node.func, ast.Attribute):
            return node.func.attr
        return (dotted(node.func) or "").split(".")[-1]

    def _span_open_name(self, node: ast.Call) -> bool:
        return self._call_last_name(node) in SPAN_OPEN_NAMES

    def _span_close_name(self, node: ast.Call) -> bool:
        return self._call_last_name(node) in SPAN_CLOSE_NAMES

    def _looks_like_lock(self, expr: ast.expr) -> bool:
        d = dotted(expr) or ""
        last = d.split(".")[-1].lower()
        return d.split(".")[-1] in self.mod.locks or "lock" in last \
            or "mutex" in last

    # -- JGL001 / JGL002 / JGL006 on calls --

    def visit_Call(self, node: ast.Call) -> None:
        self._check_sync(node)
        self._check_jit_churn(node)
        self._check_mutation_call(node)
        self._check_span_leak(node)
        self._check_lock_fetch(node)
        self._check_lock_helper_call(node)
        self._check_unbounded_wait(node)
        self._check_dynamic_label(node)
        self._check_journal_kind(node)
        self._check_knob_setter_call(node)
        self._check_finalize_asarray(node)
        self.generic_visit(node)

    # -- JGL015: host post-processing in a fused finalize/unpack path --

    def _in_finalize_path(self) -> bool:
        return bool(self.finalize_hostwork_scope and self._finalize_fns
                    and self._finalize_fns[-1])

    def _check_finalize_loop(self, node) -> None:
        if not self._in_finalize_path():
            return
        self.emit(
            "JGL015", node,
            "per-row Python loop in a finalize/unpack path — fetched "
            "results must be consumed with vectorized dtype views "
            "(ops/topk.unpack_fused); a row loop re-grows the host hop "
            "the fused dispatch deleted")

    def _check_finalize_asarray(self, node: ast.Call) -> None:
        if not self._in_finalize_path():
            return
        f = dotted(node.func) or ""
        if f not in ("np.asarray", "numpy.asarray"):
            return
        if node.args and isinstance(node.args[0], ast.Name) \
                and "packed" in node.args[0].id:
            return  # the dispatch's ONE packed-buffer materialization
        self.emit(
            "JGL015", node,
            "np.asarray on something other than the one packed buffer in "
            "a finalize/unpack path — the dispatch's single blocking "
            "fetch is _fetch_packed's; any other asarray is a second "
            "device sync or host copy (the zero-host-post-processing "
            "contract)")

    # -- JGL011: unguarded background-thread run-loop --

    def _check_thread_runloop(self, fn) -> None:
        """A function handed to threading.Thread(target=...) is a daemon's
        whole life: an exception that escapes any loop in it kills the
        thread SILENTLY (no caller observes the future), and the signal
        the thread fed inverts — a dead audit worker reads as
        recall=perfect, a dead monitor as disk=healthy. Each OUTERMOST
        loop in the target must be exception-guarded: an enclosing
        try/except, or a try/except somewhere inside the loop body (the
        `while: try/except` idiom). Nested loops inside a guarded outer
        loop are the guard's problem, not this rule's."""
        if not self.thread_runloop_scope \
                or fn.name not in self.mod.thread_targets:
            return
        self._scan_runloop_stmts(fn.body, False)

    def _scan_runloop_stmts(self, stmts, guarded: bool) -> None:
        for st in stmts:
            if isinstance(st, (ast.While, ast.For, ast.AsyncFor)):
                if not guarded and not self._loop_has_guard(st):
                    self.emit(
                        "JGL011", st,
                        "run-loop in a threading.Thread target with no "
                        "exception guard — the first surprise exception "
                        "kills the thread silently and its signal reads "
                        "as healthy; wrap the loop body in try/except "
                        "(log + continue) or the loop itself in a "
                        "guarded supervisor")
                continue  # outermost loops only
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue  # nested defs run on their own thread/lifecycle
            if isinstance(st, ast.Try):
                self._scan_runloop_stmts(st.body,
                                         guarded or bool(st.handlers))
                for h in st.handlers:
                    self._scan_runloop_stmts(h.body, guarded)
                self._scan_runloop_stmts(st.orelse, guarded)
                self._scan_runloop_stmts(st.finalbody, guarded)
                continue
            if isinstance(st, ast.Match):
                # match holds statements under cases[i].body, not .body —
                # a run-loop inside a case must not silently escape audit
                for case in st.cases:
                    self._scan_runloop_stmts(case.body, guarded)
                continue
            for attr in ("body", "orelse", "finalbody"):
                blk = getattr(st, attr, None)
                if blk:
                    self._scan_runloop_stmts(blk, guarded)

    @staticmethod
    def _loop_has_guard(loop) -> bool:
        """Any try-with-except inside the loop (nested defs excluded —
        their bodies run elsewhere). Approximate on purpose: a try that
        covers only part of the body still counts; what matters is that
        the author THOUGHT about thread survival at all."""
        stack = list(ast.iter_child_nodes(loop))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(n, ast.Try) and n.handlers:
                return True
            stack.extend(ast.iter_child_nodes(n))
        return False

    # -- JGL010: dynamically-constructed metric label value --

    @classmethod
    def _is_dynamic_string(cls, node: ast.expr) -> bool:
        """A string whose VALUE depends on runtime data: an f-string with
        interpolations, a .format(...) call, or a +/% expression mixing a
        string with a non-constant. A plain Name/Attribute/Subscript is
        fine — it may carry a bounded value (reason enums, a TenantLabeler
        label); only CONSTRUCTION proves unboundedness statically."""
        if isinstance(node, ast.JoinedStr):
            return any(isinstance(v, ast.FormattedValue) for v in node.values)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "format" \
                and (node.args or node.keywords):
            return True
        if isinstance(node, ast.BinOp) \
                and isinstance(node.op, (ast.Add, ast.Mod)):
            leaves: list[ast.expr] = []

            def flatten(n: ast.expr) -> None:
                if isinstance(n, ast.BinOp) \
                        and isinstance(n.op, (ast.Add, ast.Mod)):
                    flatten(n.left)
                    flatten(n.right)
                else:
                    leaves.append(n)

            flatten(node)
            stringish = any(
                isinstance(x, ast.JoinedStr)
                or (isinstance(x, ast.Constant) and isinstance(x.value, str))
                for x in leaves)
            dynamic = any(not isinstance(x, ast.Constant) for x in leaves)
            return stringish and dynamic
        return False

    def _check_dynamic_label(self, node: ast.Call) -> None:
        if not self.metric_label_scope or self.fn_depth == 0:
            return
        f = node.func
        if not isinstance(f, ast.Attribute) or f.attr != "labels":
            return
        values = list(node.args) + [kw.value for kw in node.keywords]
        for v in values:
            if self._is_dynamic_string(v):
                self.emit("JGL010", v,
                          "metric label value built from a runtime string "
                          "at a `.labels(...)` call site — every distinct "
                          "value mints a Prometheus series forever; pass a "
                          "bounded value (metrics.TenantLabeler top-K + "
                          "'other', or a fixed enum) instead")

    # -- JGL013: ops-journal event kind must be a registered literal --

    def _is_incident_emit(self, node: ast.Call) -> bool:
        """Is this call the incidents journal's emit()? Recognized forms:
        ``incidents.emit(...)`` (any dotted path ending there — the
        canonical ``from ... import incidents`` spelling), and a bare
        name bound by ``from ...monitoring.incidents import emit``."""
        f = node.func
        if isinstance(f, ast.Name):
            return f.id in self.mod.incident_emit_names
        d = dotted(f) or ""
        return d == "incidents.emit" or d.endswith(".incidents.emit")

    def _check_journal_kind(self, node: ast.Call) -> None:
        if not self.journal_kind_scope or not self._is_incident_emit(node):
            return
        kind = node.args[0] if node.args else None
        if kind is None:
            for kw in node.keywords:
                if kw.arg == "kind":
                    kind = kw.value
                    break
        if kind is None:
            self.emit("JGL013", node,
                      "incidents.emit() with no kind argument — pass a "
                      "literal kind from the registered EVENT_KINDS "
                      "taxonomy")
            return
        value = _const_str(kind)
        if value is None:
            self.emit("JGL013", kind,
                      "ops-journal event kind built/passed dynamically — "
                      "a non-literal kind would fold to 'other' at "
                      "runtime, losing its identity in every incident "
                      "bundle; pass a literal from the registered "
                      "EVENT_KINDS taxonomy")
        elif value not in JOURNAL_EVENT_KINDS:
            self.emit("JGL013", kind,
                      f"ops-journal event kind {value!r} is not in the "
                      "registered EVENT_KINDS taxonomy — the runtime fold "
                      "would silently swallow it as 'other'; register it "
                      "in monitoring/incidents.py EVENT_KINDS (and the "
                      "JOURNAL_EVENT_KINDS mirror in graftlint) or use an "
                      "existing kind")

    # -- JGL014: controller-owned knob actuated outside controller.py --

    def _check_knob_setter_call(self, node: ast.Call) -> None:
        """A call to a knob setter (X.set_knob / X.set_sample_rate /
        X.set_pipeline_depth) anywhere but serving/controller.py: the
        setters exist FOR the control plane — any other caller bypasses
        the clamp, the actuation journal, and the fail-static revert."""
        if not self.controller_knob_scope or self.fn_depth == 0:
            return
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in CONTROLLER_KNOB_SETTERS:
            self.emit(
                "JGL014", node,
                f"`.{f.attr}()` is a controller-owned knob setter — only "
                "serving/controller.py's clamped actuate path may call "
                "it; route the change through the control plane (or make "
                "it a constructor default)")

    def _check_knob_write(self, targets) -> None:
        """A non-self assignment to a controller knob field (margin/
        scale/cap fields, or the plane's `_knobs` store itself) outside
        controller.py is an unclamped, unjournaled, unleased actuation."""
        if not self.controller_knob_scope or self.fn_depth == 0:
            return
        flat: list = []
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                flat.extend(t.elts)
            else:
                flat.append(t)
        for t in flat:
            # plane._knobs[...] = v reaches the store through a Subscript
            base = t.value if isinstance(t, ast.Subscript) else t
            if not isinstance(base, ast.Attribute):
                continue
            if base.attr not in CONTROLLER_KNOB_FIELDS:
                continue
            owner = base.value
            if isinstance(owner, ast.Name) and owner.id == "self":
                continue  # the owner's own constructor/defaults
            self.emit(
                "JGL014", base,
                f"write to controller-owned knob field `.{base.attr}` "
                "outside serving/controller.py — knob actuations must "
                "ride ControlPlane._set_knob (clamped, leased, "
                "journaled); a direct write bypasses the fail-static "
                "revert")

    # -- JGL009: unbounded blocking wait --

    def _check_unbounded_wait(self, node: ast.Call) -> None:
        if not self.unbounded_wait_scope or self.fn_depth == 0:
            return
        f = node.func
        if not isinstance(f, ast.Attribute) \
                or f.attr not in UNBOUNDED_WAIT_NAMES:
            return
        if node.args:
            return  # wait(5) / d.get(key) / acquire(True, 2): bounded or
            # not a blocking primitive at all
        if any(kw.arg in ("timeout", "block", "blocking")
               for kw in node.keywords):
            return
        if f.attr == "get" \
                and (dotted(f.value) or "") in self.mod.contextvars:
            return  # ContextVar.get(): a lookup, not a blocking wait
        self.emit("JGL009", node,
                  f"`.{f.attr}()` with no timeout on the serving path "
                  "blocks forever if the producer wedges or dies; bound "
                  "it with the request's remaining deadline (serving/"
                  "robustness.py) or an explicit liveness cap")

    # -- JGL008: blocking device fetch under a held lock --

    def _check_lock_fetch(self, node: ast.Call) -> None:
        if not self.lock_fetch_scope or self.fn_depth == 0 \
                or self.with_locks == 0:
            return
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "block_until_ready":
            self.emit("JGL008", node,
                      "`block_until_ready()` inside a `with <lock>:` block "
                      "serializes every concurrent reader on this mutex for "
                      "a device round trip; dispatch under the lock, block "
                      "outside it (snapshot two-phase pattern)")
            return
        fd = dotted(f) or ""
        arg = node.args[0] if node.args else None
        if fd in ("np.asarray", "np.array", "numpy.asarray", "numpy.array",
                  "jax.device_get") and arg is not None \
                and self._is_device_value(arg):
            self.emit("JGL008", node,
                      f"`{fd}(...)` on a device value inside a "
                      "`with <lock>:` block holds the mutex across a "
                      "blocking device->host transfer — every reader and "
                      "writer convoys on it; pin the state in a snapshot "
                      "and fetch outside the critical section")

    # -- interprocedural JGL008/JGL009: a `with <lock>:` body calling a
    # -- local helper that syncs/blocks (one level deep) ----------------------

    def _resolve_local_helper(self, node: ast.Call):
        """The same-module function a call reaches, when resolvable with
        zero type inference (tools/graftflow/resolve.py — the shared
        resolution engine). Imported names, deeper attribute chains, and
        other receivers are graftflow's whole-program scope, not this
        one-level analysis'."""
        return resolve.resolve_local(
            self.mod.defs, node.func,
            self.class_stack[-1] if self.class_stack else None)

    def _check_lock_helper_call(self, node: ast.Call) -> None:
        if self.with_locks == 0 or self.fn_depth == 0:
            return
        if not (self.lock_fetch_scope or self.unbounded_wait_scope):
            return
        helper = self._resolve_local_helper(node)
        if helper is None or (self.fn_stack and helper is self.fn_stack[-1]):
            return  # unresolvable, or direct recursion (already audited)
        name = self._call_last_name(node)
        if self.lock_fetch_scope:
            syncs = self.mod.helper_syncs(helper)
            if syncs:
                line, what = syncs[0]
                self.emit(
                    "JGL008", node,
                    f"calls local helper `{name}()` which {what} (line "
                    f"{line}) — a device fetch one call deep still holds "
                    "this lock across the whole round trip; dispatch "
                    "under the lock, fetch OUTSIDE it (snapshot two-phase "
                    "pattern), or hoist the helper call out of the "
                    "critical section")
        if self.unbounded_wait_scope:
            waits = self.mod.helper_waits(helper)
            if waits:
                line, what = waits[0]
                self.emit(
                    "JGL009", node,
                    f"calls local helper `{name}()` which {what} (line "
                    f"{line}) while this thread holds a lock — a wedged "
                    "producer then hangs every thread that wants the "
                    "mutex, not just this request; bound the helper's "
                    "wait (deadline-derived where one exists) or move "
                    "the call outside the critical section")

    # -- JGL007: span leak --

    def _check_span_leak(self, node: ast.Call) -> None:
        if not self.span_scope or self.fn_depth == 0:
            return
        if not self._span_open_name(node):
            return
        if id(node) in self._span_with_ctx or self._span_finally_depth > 0:
            return
        self.emit("JGL007", node,
                  f"`{self._call_last_name(node)}(...)` returns an OPEN "
                  "span/dispatch record with no structural close: use "
                  "`with tracing.span(...)`, or open it inside a `try:` "
                  "whose `finally:` calls .end()/.finish() — a leaked span "
                  "corrupts every rider's trace tree")

    def _check_sync(self, node: ast.Call) -> None:
        if not self.hot or (self.rel, self.qualname()) in JGL001_BOUNDARY:
            return
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == "item" and not node.args:
                self.emit("JGL001", node,
                          "`.item()` forces a device->host sync per element; "
                          "fetch the whole batch once at the boundary")
                return
            if f.attr == "block_until_ready":
                self.emit("JGL001", node,
                          "`block_until_ready()` stalls the dispatch "
                          "pipeline; only benchmarks may block")
                return
        fd = dotted(f) or ""
        arg = node.args[0] if node.args else None
        if fd in ("np.asarray", "np.array", "numpy.asarray", "numpy.array",
                  "jax.device_get"):
            if arg is not None and self._is_device_value(arg):
                self.emit("JGL001", node,
                          f"`{fd}(...)` on a device value is a blocking "
                          "transfer; keep the data on device or batch the "
                          "fetch at the boundary")
        elif fd in ("float", "int", "bool") and arg is not None \
                and self._is_device_value(arg):
            self.emit("JGL001", node,
                      f"`{fd}()` on a device value syncs one scalar per "
                      "call; fetch arrays once and convert host-side")

    def _check_jit_churn(self, node: ast.Call) -> None:
        fd = dotted(node.func)
        is_partial_jit = (
            fd in ("functools.partial", "partial") and node.args
            and _is_jit_expr(node.args[0]))
        if fd not in ("jax.jit", "jit") and not is_partial_jit:
            return
        jit_call = node
        if self.fn_depth > 0:
            where = "a loop body" if self.loop_depth else "a function body"
            self.emit("JGL002", node,
                      f"jax.jit invoked inside {where} builds a fresh cache "
                      "entry per call path; hoist the jitted callable to "
                      "module scope (or cache it once)")
        for a in jit_call.args:
            if isinstance(a, ast.Lambda):
                self.emit("JGL002", a,
                          "jitting a lambda gives every call site a distinct "
                          "function identity (zero cache hits); def a named "
                          "function at module scope")
        for kw in jit_call.keywords:
            if kw.arg in ("static_argnums", "static_argnames") and isinstance(
                    kw.value, (ast.List, ast.Set, ast.Dict)):
                self.emit("JGL002", kw.value,
                          f"{kw.arg} given a mutable literal is unhashable "
                          "under cache lookup; use a tuple")

    # -- JGL003: tracer leak --

    def visit_Assign(self, node: ast.Assign) -> None:
        if self.jit_depth:
            for t in node.targets:
                self._check_leak_target(t)
        self._check_registry_mutation_target(node)
        self._check_unledgered_alloc(node)
        self._check_knob_write(node.targets)
        self._track_assign(node)
        self.generic_visit(node)

    # -- JGL012: unaccounted HBM allocation --

    @staticmethod
    def _fn_calls_stamp(fn) -> bool:
        """Does this function lexically call a ledger stamping hook?
        A stamp in a nested closure still counts (the closure runs as
        part of the method's mutation flow) — approximate on purpose."""
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call):
                f = sub.func
                name = f.attr if isinstance(f, ast.Attribute) else (
                    dotted(f) or "").split(".")[-1]
                if name in LEDGER_STAMP_CALLS:
                    return True
        return False

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        """Annotated assignments bind values too: `self._store: Array =
        device_put(...)` must not escape the JGL012 audit."""
        if node.value is not None:
            self._check_unledgered_alloc(node)
            # a value-less AnnAssign declares, it does not write — only an
            # actual binding can actuate a controller-owned knob
            self._check_knob_write([node.target])
        self.generic_visit(node)

    def _check_unledgered_alloc(self, node) -> None:
        """A call result (jnp.asarray / jax.device_put / a write-kernel
        output — any Call: kernels are calls) bound to a snapshot/slab
        field must come from a method that stamps the memory ledger;
        otherwise the allocation is HBM the capacity forecast cannot
        see. Constants (field = None teardown) are exempt."""
        if not self.snapshot_ledger_scope or self.fn_depth == 0:
            return
        if not isinstance(node.value, ast.Call):
            return
        if self._stamp_fns and self._stamp_fns[-1]:
            return
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        flat: list = []
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                flat.extend(t.elts)
            else:
                flat.append(t)
        for t in flat:
            if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name) \
                    and t.value.id == "self" and t.attr in SNAPSHOT_FIELDS:
                self.emit(
                    "JGL012", t,
                    f"device buffer bound to snapshot field `self.{t.attr}` "
                    "in a method that never stamps the memory ledger — an "
                    "unaccounted HBM allocation makes /debug/memory's "
                    "headroom and exhaustion forecast lie; call "
                    "self._stamp_memory() (or publish a snapshot) in this "
                    "method, or suppress with a written justification")

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if self.jit_depth:
            self._check_leak_target(node.target)
        self._check_registry_mutation_target(node)
        self._check_knob_write([node.target])
        self.generic_visit(node)

    def _check_leak_target(self, t: ast.expr) -> None:
        if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name) \
                and t.value.id == "self":
            self.emit("JGL003", t,
                      f"storing to `self.{t.attr}` inside a jitted function "
                      "leaks a tracer (and re-runs only while tracing); "
                      "return the value instead")
        elif isinstance(t, ast.Name) and self.global_names \
                and t.id in self.global_names[-1]:
            self.emit("JGL003", t,
                      f"assigning global `{t.id}` inside a jitted function "
                      "leaks a tracer; return the value instead")
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self._check_leak_target(e)

    def _track_assign(self, node: ast.Assign) -> None:
        if not self.device_vars:
            return
        if self._is_device_value(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self._track_device(t.id)
                elif isinstance(t, (ast.Tuple, ast.List)):
                    for e in t.elts:
                        if isinstance(e, ast.Name):
                            self._track_device(e.id)

    # -- JGL004: silent fallback --

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if self.hot and self._broad(node.type) and self.fn_depth > 0:
            if not self._handler_is_honest(node):
                self.emit(
                    "JGL004", node,
                    "broad `except` degrades to a host fallback with no "
                    "trace: log once (rate-limited) and count a fallback "
                    "metric — see monitoring.metrics.record_device_fallback")
        self.generic_visit(node)

    @staticmethod
    def _broad(t: Optional[ast.expr]) -> bool:
        return t is None or dotted(t) in ("Exception", "BaseException")

    def _handler_is_honest(self, node: ast.ExceptHandler) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Raise):
                return True
            if isinstance(sub, ast.Call):
                # the last attribute alone, so chained receivers like
                # logging.getLogger(__name__).warning(...) still count
                if isinstance(sub.func, ast.Attribute):
                    last = sub.func.attr
                else:
                    last = (dotted(sub.func) or "").split(".")[-1]
                if last in ("warning", "error", "exception", "critical",
                            "log", "inc", "observe", "record_device_fallback",
                            "count_exception", "fail"):
                    return True
        return False

    # -- JGL005: unlocked registry mutation --

    def _check_registry_mutation_target(self, node) -> None:
        targets = node.targets if isinstance(node, ast.Assign) else [node.target]
        for t in targets:
            base = t
            while isinstance(base, ast.Subscript):
                base = base.value
            if isinstance(base, ast.Name) and base.id in self.mod.registries \
                    and base is not t:
                self._emit_registry(node, base.id, "item assignment")

    def visit_Delete(self, node: ast.Delete) -> None:
        for t in node.targets:
            base = t
            while isinstance(base, ast.Subscript):
                base = base.value
            if isinstance(base, ast.Name) and base.id in self.mod.registries \
                    and base is not t:
                self._emit_registry(node, base.id, "del")
        self.generic_visit(node)

    def _check_mutation_call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in MUTATING_METHODS \
                and isinstance(f.value, ast.Name) \
                and f.value.id in self.mod.registries:
            self._emit_registry(node, f.value.id, f".{f.attr}()")

    def _emit_registry(self, node, name: str, how: str) -> None:
        # mutation at import time (module scope) is serialized by the import
        # lock; only function bodies race
        if self.fn_depth == 0 or self.with_locks > 0:
            return
        self.emit("JGL005", node,
                  f"module-level `{name}` mutated ({how}) without holding a "
                  "lock; serving threads share this object — wrap the "
                  "mutation in `with <module lock>:`")

    # -- JGL006: dtype drift --

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self.hot:
            d = dotted(node)
            if d in ("np.float64", "numpy.float64", "jnp.float64",
                     "np.double", "numpy.double"):
                self.emit("JGL006", node,
                          f"`{d}` in kernel-adjacent code: TPUs have no f64 "
                          "units — use float32 (or keep f64 strictly "
                          "host-side and cast before upload)")
        self.generic_visit(node)

    def visit_keyword(self, node: ast.keyword) -> None:
        if self.hot and node.arg in ("dtype",) \
                and _const_str(node.value) in ("float64", "double"):
            self.emit("JGL006", node.value,
                      "dtype=\"float64\" in kernel-adjacent code: use "
                      "float32 on the device path")
        self.generic_visit(node)


def run_rules(tree: ast.Module, source: str, rel_path: str) -> list[Finding]:
    mod = ModuleIndex(tree)
    walker = RuleWalker(rel_path, mod)
    walker.visit(tree)
    return walker.findings
