"""graftflow: call-graph resolution, fixed-point dataflow, the four
interprocedural rules (JGL016-JGL019), the graftsan hierarchy drift
check, and tier-1 enforcement over the real tree.

Everything here is pure AST — no JAX device — synthetic packages are
written to tmp_path; the real-tree checks share one module-scoped build.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graftflow import DEFAULT_BASELINE, callgraph, dataflow
from tools.graftflow import rules as flow_rules
from tools.graftflow.engine import analyze_program, parse_suppressions
from tools.graftlint.engine import apply_baseline, load_baseline

PACKAGE = os.path.join(REPO, "weaviate_tpu")

# a synthetic hierarchy for the rule tests: three levels, fetch banned
# under the middle one
TEST_HIERARCHY = {
    "locks": [
        {"name": "t.low", "level": 10, "no_fetch_under": False},
        {"name": "t.mid", "level": 20, "no_fetch_under": True},
        {"name": "t.high", "level": 30, "no_fetch_under": False},
    ]
}


def _build(tmp_path, files: dict, hierarchy=TEST_HIERARCHY):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    hpath = tmp_path / "hierarchy.json"
    hpath.write_text(json.dumps(hierarchy))
    prog = callgraph.build_program(str(pkg), root=str(tmp_path),
                                   hierarchy_path=str(hpath))
    return prog


def _findings(tmp_path, files: dict, hierarchy=TEST_HIERARCHY):
    prog = _build(tmp_path, files, hierarchy)
    s = dataflow.analyze(prog)
    return prog, s, flow_rules.run_rules(prog, s)


LOCKED_CLASS_HEADER = """\
    import threading
    import numpy as np
    import jax.numpy as jnp
    from pkg.san import register_lock

    class Idx:
        def __init__(self):
            self._lock = register_lock(threading.RLock(), "t.mid")
            self._store = jnp.zeros((4, 4))
"""

SAN = """\
    def register_lock(lock, name):
        return lock
"""


# -- call-graph resolution ---------------------------------------------------

class TestResolution:
    def test_method_dispatch_via_constructor_attr_type(self, tmp_path):
        prog = _build(tmp_path, {
            "san.py": SAN,
            "a.py": """\
                from pkg.b import Worker

                class Owner:
                    def __init__(self):
                        self.w = Worker()

                    def go(self):
                        self.w.run()
            """,
            "b.py": """\
                class Worker:
                    def run(self):
                        return 1
            """,
        })
        s = dataflow.analyze(prog)
        scan = s.scans["pkg/a.py:Owner.go"]
        (cs,) = [c for c in scan.calls]
        assert [c.qual for c in cs.callees] == ["pkg/b.py:Worker.run"]

    def test_factory_return_union_resolves_every_branch(self, tmp_path):
        prog = _build(tmp_path, {
            "san.py": SAN,
            "a.py": """\
                from pkg.b import make_index

                class Owner:
                    def __init__(self, kind):
                        self.idx = make_index(kind)

                    def go(self):
                        self.idx.add()
            """,
            "b.py": """\
                class Tpu:
                    def add(self):
                        return "tpu"

                class Mesh:
                    def add(self):
                        return "mesh"

                def make_index(kind):
                    if kind == "tpu":
                        return Tpu()
                    return Mesh()
            """,
        })
        s = dataflow.analyze(prog)
        scan = s.scans["pkg/a.py:Owner.go"]
        quals = sorted(c.qual for cs in scan.calls for c in cs.callees)
        assert quals == ["pkg/b.py:Mesh.add", "pkg/b.py:Tpu.add"]

    def test_self_callback_idiom_resolves_to_bound_method(self, tmp_path):
        prog = _build(tmp_path, {
            "a.py": """\
                class C:
                    def __init__(self, fast):
                        if fast:
                            self._cb = self._fast
                        else:
                            self._cb = self._slow

                    def _fast(self):
                        return 1

                    def _slow(self):
                        return 2

                    def go(self):
                        return self._cb()
            """,
        })
        info = prog.functions["pkg/a.py:C.go"]
        scan = dataflow._scan_function(prog, info)
        quals = sorted(c.qual for cs in scan.calls for c in cs.callees)
        assert quals == ["pkg/a.py:C._fast", "pkg/a.py:C._slow"]

    def test_lambda_callback_participates_in_the_graph(self, tmp_path):
        # facts inside a lambda-bound callback flow to the call site
        prog, s, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": LOCKED_CLASS_HEADER + """\

        def go(self):
            with self._lock:
                self._cb()

        def wire(self):
            self._cb = lambda: np.asarray(self._store)
            """,
        })
        f16 = [f for f in findings if f.code == "JGL016"]
        assert len(f16) == 1 and f16[0].symbol == "Idx.go"
        assert "<lambda" in f16[0].message

    def test_decorator_wrapped_jit_entry_static_names(self, tmp_path):
        prog = _build(tmp_path, {
            "a.py": """\
                from functools import partial
                import jax

                @partial(jax.jit, static_argnames=("k", "metric"))
                def score(rows, q, k, metric):
                    return rows

                plain = jax.jit(score, static_argnums=(2,))
            """,
        })
        mi = prog.modules["pkg.a"]
        assert sorted(mi.jit_entries["score"].static_names) == [
            "k", "metric"]
        assert sorted(mi.jit_entries["plain"].static_names) == ["k"]

    def test_from_import_resolves_cross_module(self, tmp_path):
        prog = _build(tmp_path, {
            "a.py": """\
                from pkg.b import helper

                def go():
                    return helper()
            """,
            "b.py": """\
                def helper():
                    return 1
            """,
        })
        info = prog.functions["pkg/a.py:go"]
        scan = dataflow._scan_function(prog, info)
        quals = [c.qual for cs in scan.calls for c in cs.callees]
        assert quals == ["pkg/b.py:helper"]


# -- fixed-point termination -------------------------------------------------

def test_fixpoint_terminates_on_mutual_recursion(tmp_path):
    prog, s, _ = _findings(tmp_path, {
        "a.py": """\
            import numpy as np
            import jax.numpy as jnp

            def ping(n, store):
                x = jnp.dot(store, store)
                np.asarray(x)
                if n:
                    return pong(n - 1, store)
                return n

            def pong(n, store):
                if n:
                    return ping(n - 1, store)
                return n
        """,
    })
    # both directions of the cycle carry the sync summary
    assert s.syncs["pkg/a.py:ping"]
    assert s.syncs["pkg/a.py:pong"]


def test_fixpoint_terminates_on_self_recursion(tmp_path):
    prog, s, findings = _findings(tmp_path, {
        "a.py": """\
            def rec(n):
                if n:
                    return rec(n - 1)
                return 0
        """,
    })
    assert s.acquires["pkg/a.py:rec"] == {}


# -- JGL016: device sync under a no-fetch lock, any depth --------------------

class TestJGL016:
    def test_deep_chain_flagged_with_call_chain(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": LOCKED_CLASS_HEADER + """\

        def go(self):
            with self._lock:
                self.step1()

        def step1(self):
            self.step2()

        def step2(self):
            import numpy as np
            np.asarray(self._store)
            """,
        })
        f16 = [f for f in findings if f.code == "JGL016"]
        assert len(f16) == 1
        assert f16[0].symbol == "Idx.go"
        assert "depth 2" in f16[0].message
        assert "Idx.step1" in f16[0].message
        assert "Idx.step2" in f16[0].message

    def test_lock_without_no_fetch_under_is_not_flagged(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": LOCKED_CLASS_HEADER.replace('"t.mid"', '"t.low"') + """\

        def go(self):
            with self._lock:
                self.step()

        def step(self):
            import numpy as np
            np.asarray(self._store)
            """,
        })
        assert [f for f in findings if f.code == "JGL016"] == []

    def test_sync_in_nested_closure_does_not_count(self, tmp_path):
        # the finalize-closure idiom: deferred work runs outside the lock
        _, _, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": LOCKED_CLASS_HEADER + """\

        def go(self):
            with self._lock:
                return self.step()

        def step(self):
            import numpy as np

            def finalize():
                return np.asarray(self._store)

            return finalize
            """,
        })
        assert [f for f in findings if f.code == "JGL016"] == []

    def test_clean_tree_yields_no_findings(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": LOCKED_CLASS_HEADER + """\

        def go(self):
            with self._lock:
                self.step()

        def step(self):
            return self._store
            """,
        })
        assert [f for f in findings if f.code == "JGL016"] == []


# -- JGL017: static lock-order conformance -----------------------------------

HIER_CLASS = """\
    import threading
    from pkg.san import register_lock

    class Planes:
        def __init__(self):
            self._low = register_lock(threading.Lock(), "t.low")
            self._mid = register_lock(threading.Lock(), "t.mid")
            self._high = register_lock(threading.Lock(), "t.high")
"""


class TestJGL017:
    def test_descending_acquisition_through_a_call_is_flagged(
            self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": HIER_CLASS + """\

        def go(self):
            with self._mid:
                self.grab()

        def grab(self):
            with self._low:
                return 1
            """,
        })
        f17 = [f for f in findings if f.code == "JGL017"]
        assert len(f17) == 1
        assert "`t.low` (level 10)" in f17[0].message
        assert "`t.mid` (level 20)" in f17[0].message
        assert "Planes.grab" in f17[0].message

    def test_ab_ba_cycle_reports_both_chains(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": HIER_CLASS + """\

        def forward(self):
            with self._mid:
                with self._high:
                    return 1

        def backward(self):
            with self._high:
                self.grab_mid()

        def grab_mid(self):
            with self._mid:
                return 1
            """,
        })
        f17 = [f for f in findings if f.code == "JGL017"]
        assert len(f17) == 1
        msg = f17[0].message
        assert "closes a cycle via" in msg
        # both static chains: the violating path and the legal one back
        assert "Planes.backward" in msg and "Planes.forward" in msg

    def test_conformant_nesting_is_clean_and_edges_derive(self, tmp_path):
        prog, s, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": HIER_CLASS + """\

        def go(self):
            with self._low:
                self.mid_work()

        def mid_work(self):
            with self._mid:
                with self._high:
                    return 1
            """,
        })
        assert [f for f in findings if f.code == "JGL017"] == []
        edges = set(dataflow.lock_edges(prog, s))
        assert ("t.low", "t.mid") in edges
        assert ("t.mid", "t.high") in edges
        # holding low while mid_work eventually grabs high: also an edge
        assert ("t.low", "t.high") in edges

    def test_condition_aliasing_folds_to_the_registered_lock(
            self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "san.py": SAN,
            "a.py": HIER_CLASS + """\

        def setup(self):
            self._cv = threading.Condition(self._mid)

        def go(self):
            with self._high:
                with self._cv:
                    return 1
            """,
        })
        f17 = [f for f in findings if f.code == "JGL017"]
        assert len(f17) == 1
        assert "`t.mid`" in f17[0].message


# -- JGL018: snapshot escape -------------------------------------------------

SNAP_MOD = """\
    class IndexSnapshot:
        def __init__(self, store):
            self.gen = 1
            self.n = 2
            self.store = store

    REGISTRY = {}
"""


class TestJGL018:
    def test_snapshot_bound_to_instance_attr_is_flagged(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "a.py": SNAP_MOD + """\

    class Reader:
        def pin(self, snap):
            self._last_snap = snap
            """,
        })
        f18 = [f for f in findings if f.code == "JGL018"]
        assert len(f18) == 1
        assert "self._last_snap" in f18[0].message

    def test_derived_view_escapes_interprocedurally(self, tmp_path):
        # rows comes back from a helper that returns a view of
        # snap.store — the tuple binding into self state is the escape
        _, _, findings = _findings(tmp_path, {
            "a.py": SNAP_MOD + """\

    def host_rows(snap):
        rows = snap.store[: snap.n]
        return rows, snap.gen

    class Reader:
        def cache(self, snap):
            rows, gen = host_rows(snap)
            self._cache = (gen, rows)
            """,
        })
        f18 = [f for f in findings if f.code == "JGL018"]
        assert len(f18) == 1
        assert "self._cache" in f18[0].message
        assert "view of a snapshot's arrays" in f18[0].message

    def test_module_registry_subscript_is_flagged(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "a.py": SNAP_MOD + """\

    def stash(key, snap):
        REGISTRY[key] = snap
            """,
        })
        f18 = [f for f in findings if f.code == "JGL018"]
        assert len(f18) == 1
        assert "REGISTRY[...]" in f18[0].message

    def test_local_use_and_publish_are_clean(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "a.py": SNAP_MOD + """\

    class Index:
        def publish(self, store):
            snap = IndexSnapshot(store)
            self._snap = snap

        def read(self, snap):
            rows = snap.store[: snap.n]
            return rows.sum()
            """,
        })
        assert [f for f in findings if f.code == "JGL018"] == []

    def test_scalar_fields_do_not_taint(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "a.py": SNAP_MOD + """\

    class Index:
        def note(self, snap):
            self._last_gen = snap.gen
            """,
        })
        assert [f for f in findings if f.code == "JGL018"] == []


# -- JGL019: jit-shape churn -------------------------------------------------

JIT_MOD = """\
    from functools import partial
    import jax

    @partial(jax.jit, static_argnames="k")
    def score(rows, q, k):
        return rows

    def _bucket_rows(n):
        return max(64, n)
"""


class TestJGL019:
    def test_len_into_static_param_is_flagged(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "a.py": JIT_MOD + """\

    def go(rows, q, xs):
        return score(rows, q, k=len(xs))
            """,
        })
        f19 = [f for f in findings if f.code == "JGL019"]
        assert len(f19) == 1
        assert "`k`" in f19[0].message and "score" in f19[0].message

    def test_interprocedural_sink_flags_the_caller(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "a.py": JIT_MOD + """\

    def wrapper(rows, q, k):
        return score(rows, q, k=k)

    def go(rows, q, xs):
        n = xs.shape[0]
        return wrapper(rows, q, n)
            """,
        })
        f19 = [f for f in findings if f.code == "JGL019"]
        assert [f.symbol for f in f19] == ["go"]
        assert "wrapper" in f19[0].message

    def test_bucket_snapped_dim_is_clean(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "a.py": JIT_MOD + """\

    def go(rows, q, xs):
        k = _bucket_rows(len(xs))
        return score(rows, q, k=k)
            """,
        })
        assert [f for f in findings if f.code == "JGL019"] == []

    def test_tainted_non_static_arg_is_clean(self, tmp_path):
        _, _, findings = _findings(tmp_path, {
            "a.py": JIT_MOD + """\

    def go(rows, xs, k):
        return score(rows, xs[: len(xs)], k=k)
            """,
        })
        assert [f for f in findings if f.code == "JGL019"] == []


# -- suppressions ------------------------------------------------------------

def test_reasoned_suppression_is_honored_and_bare_is_not(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text(textwrap.dedent("""\
        class Reader:
            def pin(self, snap):
                self._a = snap  # graftflow: disable=JGL018 audit pin, TLS-bounded
                self._b = snap  # graftflow: disable=JGL018
    """))
    hpath = tmp_path / "h.json"
    hpath.write_text(json.dumps(TEST_HIERARCHY))
    findings = analyze_program(str(pkg), root=str(tmp_path),
                               hierarchy_path=str(hpath))
    f18 = [f for f in findings if f.code == "JGL018"]
    assert len(f18) == 1 and f18[0].line == 4  # bare disable not honored


def test_parse_suppressions_requires_reason():
    src = "x = 1  # graftflow: disable=JGL016\ny = 2  # graftflow: disable=JGL016,JGL017 declared fetch\n"
    sup = parse_suppressions(src)
    assert 1 not in sup
    assert sup[2] == {"JGL016", "JGL017"}


# -- the call-graph cache ----------------------------------------------------

def test_cache_hits_and_invalidates_on_mtime(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text("def f():\n    return 1\n")
    cache = tmp_path / "graph.pkl"
    p1 = callgraph.load_or_build(str(pkg), root=str(tmp_path),
                                 cache_path=str(cache))
    assert cache.exists()
    p2 = callgraph.load_or_build(str(pkg), root=str(tmp_path),
                                 cache_path=str(cache))
    assert sorted(p2.functions) == sorted(p1.functions)
    # grow the file: the mtime+size key must invalidate
    (pkg / "a.py").write_text("def f():\n    return 1\n\ndef g():\n    return 2\n")
    p3 = callgraph.load_or_build(str(pkg), root=str(tmp_path),
                                 cache_path=str(cache))
    assert "pkg/a.py:g" in p3.functions


# -- the real tree: build once, assert many ----------------------------------

@pytest.fixture(scope="module")
def real_program():
    prog = callgraph.build_program(PACKAGE, root=REPO)
    return prog, dataflow.analyze(prog)


def test_hierarchy_edges_are_statically_rediscovered(real_program):
    """The acceptance pin: the lock-order relationships graftsan witnesses
    at runtime must be derivable with zero execution."""
    prog, s = real_program
    edges = set(dataflow.lock_edges(prog, s))
    for expected in [
        ("db.shard", "index.tpu"),       # Shard.put_object -> index.add
        ("db.shard", "index.mesh"),      # same path, mesh engine
        ("index.tpu", "index.tpu.stage_pool"),  # drop() under the index lock
    ]:
        assert expected in edges, (
            f"edge {expected} no longer derivable — the static call graph "
            f"lost a resolution path the runtime sanitizers witness; "
            f"derived: {sorted(edges)}")
    # and every derived edge between table locks must climb levels —
    # JGL017 clean on the committed tree
    levels = {n: row["level"] for n, row in prog.hierarchy.items()}
    for (a, b) in edges:
        if a in levels and b in levels:
            assert levels[a] < levels[b], f"hierarchy violation {a}->{b}"


def test_lock_table_drift_both_directions(real_program):
    """Satellite: tools/graftsan/lock_hierarchy.json vs the locks
    graftflow discovers. A lock in code but not the table (or vice versa)
    fails tier-1 — the hierarchy check is only as good as its table."""
    prog, _ = real_program
    with open(os.path.join(REPO, "tools", "graftsan",
                           "lock_hierarchy.json")) as f:
        table = {e["name"] for e in json.load(f)["locks"]}
    discovered = set(prog.registered_locks)
    assert discovered - table == set(), (
        f"locks registered in code but missing from lock_hierarchy.json: "
        f"{sorted(discovered - table)}")
    assert table - discovered == set(), (
        f"locks in lock_hierarchy.json no longer registered in code: "
        f"{sorted(table - discovered)}")


# every unregistered Lock/RLock inside the hierarchy-governed planes
# (db/, index/, serving/) needs an entry here with its reason — adding a
# lock to these planes means either registering it or justifying it
UNREGISTERED_ALLOWLIST = {
    "weaviate_tpu/db/class_index.py:ClassIndex._lock":
        "class-map mutation guard; never held across index/device calls",
    "weaviate_tpu/db/db.py:DB._lock":
        "top-of-stack class registry guard; only wraps dict ops",
    "weaviate_tpu/index/geo.py:GeoIndex._lock":
        "host-only geo index, no device work, leaf lock",
    "weaviate_tpu/index/hnsw.py:_lib_lock":
        "one-time native library load guard (module import scope)",
    "weaviate_tpu/index/rescore_native.py:_lib_lock":
        "one-time native library load guard: taken when an index enters "
        "the compressed form, never by a request; no lock taken under it "
        "but _native's own build lock",
    "weaviate_tpu/index/hnsw.py:HnswIndex._lock":
        "host-only hnswlib engine, leaf lock, no device calls under it",
    "weaviate_tpu/serving/controller.py:_TokenBuckets._lock":
        "token-bucket arithmetic only, leaf lock, microsecond hold",
    "weaviate_tpu/serving/controller.py:_summaries_lock":
        "module summary counters, leaf lock",
    "weaviate_tpu/serving/robustness.py:TenantConcurrencyGate._lock":
        "per-tenant admission counters, leaf lock",
    "weaviate_tpu/serving/robustness.py:CircuitBreaker._lock":
        "breaker state flips only, leaf lock",
}

GOVERNED_PREFIXES = ("weaviate_tpu/db/", "weaviate_tpu/index/",
                     "weaviate_tpu/serving/")


def test_unregistered_locks_in_governed_planes_are_allowlisted(
        real_program):
    prog, _ = real_program
    governed = {f"{rel}:{owner}"
                for rel, line, owner in prog.unregistered_locks
                if rel.startswith(GOVERNED_PREFIXES)}
    unexpected = governed - set(UNREGISTERED_ALLOWLIST)
    assert unexpected == set(), (
        f"new unregistered lock(s) in a hierarchy-governed plane — "
        f"register them (sanitizers.register_lock + lock_hierarchy.json) "
        f"or allowlist with a reason: {sorted(unexpected)}")
    gone = set(UNREGISTERED_ALLOWLIST) - governed
    assert gone == set(), (
        f"allowlist entries whose locks vanished — prune them: "
        f"{sorted(gone)}")


# -- tier-1 enforcement over the real tree (the graftlint pattern) -----------

def _apply_real_baseline():
    findings = analyze_program(PACKAGE, root=REPO)
    return apply_baseline(findings, load_baseline(DEFAULT_BASELINE))


def test_tree_has_zero_unbaselined_graftflow_violations():
    new, _, _ = _apply_real_baseline()
    assert new == [], (
        "graftflow found violations outside the baseline — fix them or "
        "suppress inline with a reason (do NOT grow the baseline):\n"
        + "\n".join(f.render() for f in new))


def test_graftflow_baseline_has_no_stale_entries():
    _, _, stale = _apply_real_baseline()
    assert stale == [], (
        "stale graftflow baseline entries (their findings are fixed) — "
        "run python -m tools.graftflow weaviate_tpu --prune-baseline: "
        + json.dumps(stale, indent=2))


def test_graftflow_baseline_entries_all_carry_real_justifications():
    base = load_baseline(DEFAULT_BASELINE)
    assert base["entries"], "baseline unexpectedly empty (fine, but update this test)"
    for e in base["entries"]:
        j = e.get("justification", "")
        assert j and "TODO" not in j, f"unjustified baseline entry: {e}"
        assert e["code"] in ("JGL016", "JGL017", "JGL018", "JGL019"), (
            f"graftflow's baseline only holds its own codes: {e}")


def test_graftflow_cli_gate_is_green_on_the_tree(tmp_path):
    cache = tmp_path / "graftflow-graph.pkl"
    for _ in range(2):  # second run exercises the cache-hit path
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftflow", "weaviate_tpu",
             "--strict-baseline", "--cache", str(cache)],
            cwd=REPO, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
    assert cache.exists()
