"""hslint (hyperspace_tpu/analysis) — tier-1 gate + checker self-tests.

Three layers:

* the GATE: the analyzer over the real package must report zero
  unsuppressed findings (every rule violation on the tree is either
  fixed or carries a justified ``# hslint: disable``);
* fixture-based unit tests per checker: a seeded violation is caught,
  a suppression comment silences it, and a clean tree stays clean;
* golden stability: the ruleset and the finding schema are part of the
  repo's contract (CI configs and suppression comments reference rule
  ids), so changing them must be a deliberate act.
"""

import os
import subprocess
import sys
import textwrap

import pytest

import hyperspace_tpu
from hyperspace_tpu.analysis import (
    ALL_RULES,
    CHECKERS,
    FINDING_FIELDS,
    Finding,
    run_analysis,
)

PKG_DIR = os.path.dirname(os.path.abspath(hyperspace_tpu.__file__))
TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _write_tree(root, files):
    for rel, text in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))


def _lint(tmp_path, files, tests=None):
    """Unsuppressed findings for a fixture package tree."""
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    _write_tree(pkg, files)
    tests_dir = None
    if tests is not None:
        tdir = tmp_path / "tests"
        tdir.mkdir(exist_ok=True)
        _write_tree(tdir, tests)
        tests_dir = str(tdir)
    findings = run_analysis(str(pkg), tests_dir=tests_dir)
    return [f for f in findings if not f.suppressed]


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# The gate
# ---------------------------------------------------------------------------


class TestPackageClean:
    def test_no_unsuppressed_findings(self):
        findings = run_analysis(PKG_DIR, tests_dir=TESTS_DIR)
        active = [f for f in findings if not f.suppressed]
        assert not active, "unsuppressed hslint findings:\n" + "\n".join(
            f.render() for f in active
        )

    def test_analyzer_covers_real_surfaces(self):
        """The gate is only meaningful if the checkers engage: the real
        tree must contain native exports, actions, and traced functions
        for them to look at (guards against a silent no-op analyzer)."""
        from hyperspace_tpu.analysis.core import Project
        from hyperspace_tpu.analysis import kernel_parity, log_state, purity

        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        with open(project.native_cpp_path()) as f:
            exports = kernel_parity.cpp_exports(f.read())
        assert len(exports) >= 5
        machine, _ = log_state._extract_machine(project)
        assert machine.rollback and machine.stable
        traced = [
            fn.name
            for _rel, sf in project.files_under(*purity.HOT_DIRS)
            if sf.tree is not None
            for fn in purity._traced_functions(sf.tree)
        ]
        assert len(traced) >= 5

    def test_shared_state_checker_engages(self):
        """The HS6xx sweep must actually see the concurrency surfaces:
        a populated registry that resolves, thread-pool boundaries, a
        non-trivial reachable set, and written mutable globals."""
        from hyperspace_tpu.analysis.core import Project
        from hyperspace_tpu.analysis import shared_state as ss

        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        entries, _line = ss.parse_registry(project)
        assert len(entries) >= 10
        idx = ss._PkgIndex(project)
        for e in entries:
            assert idx.resolve_state_path(e.path) is not None, e.path
        checker = ss._Checker(project)
        checker.analyze()
        submits = {t for i in checker.infos.values() for t in i.submits}
        assert len(submits) >= 5, submits  # scan pool, frontend, tails…
        reachable = checker.pool_reachable()
        assert len(reachable) >= 20
        assert len(checker.candidate_globals()) >= 5

    def test_contracts_checker_engages(self):
        """HS7xx must see the config-key and fault-point surfaces."""
        from hyperspace_tpu.analysis.core import Project
        from hyperspace_tpu.analysis import contracts

        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        keys, defaults, prefixes = contracts._constants_keys(project)
        assert len(keys) >= 20 and len(defaults) >= 20
        assert "hyperspace.faults." in prefixes
        used, _literals = contracts._reads(
            project, {n for n, _l in keys.values()}
        )
        assert len(used) >= 20
        points, _line, _path = contracts._fault_points(project)
        assert set(points) >= {"parquet_read", "kernel_dispatch"}
        assert project.doc_lines(contracts.CONFIG_DOC)
        # the collective-site ↔ dryrun matrix must be live too
        assert project.aux_lines("scripts", contracts.DRYRUN_FILE)

    def test_spmd_checker_engages(self):
        """The HS8xx sweep must actually see the multi-host plane: a
        populated COLLECTIVE_SITES registry that resolves, every
        collective-bearing function registered, and the identity-branch
        scan examining real process-identity sites."""
        from hyperspace_tpu.analysis.core import Project
        from hyperspace_tpu.analysis import spmd

        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        entries, rel = spmd.parse_sites(project)
        assert rel == "parallel/collectives.py"
        assert len(entries) >= 8
        analysis = spmd._Analysis(project)
        for e in entries:
            assert analysis.resolver.resolve_site_path(e.path) is not None, e.path
        bearing = {
            analysis.site_name(k)
            for k, f in analysis.facts.items()
            if f.primitives
        }
        assert bearing >= {
            "hyperspace_tpu.parallel.shuffle._compact_program",
            "hyperspace_tpu.parallel.shuffle._twostage_program",
            "hyperspace_tpu.parallel.shuffle._twostage_exchange_mp",
            "hyperspace_tpu.indexes.covering_build._global_written",
            "hyperspace_tpu.actions.base._action_rendezvous",
        }
        # every collective-bearing function carries a registry entry
        assert bearing <= {e.path for e in entries}
        # the action protocol's coordinator dispatch is an examined
        # identity branch (the contract HS801 verifies)
        import ast as _ast

        # the protocol body (and its coordinator dispatch) lives in
        # _run_protocol since the obs plane wrapped run() in a root span
        facts = analysis.facts[("actions/base.py", "Action", "_run_protocol")]
        tainted = spmd._identity_tainted_names(facts.node)
        examined = [
            n
            for n in _ast.walk(facts.node)
            if isinstance(n, _ast.If)
            and spmd._expr_has_identity_source(n.test, tainted)
        ]
        assert examined, "coordinator dispatch branch not examined"


# ---------------------------------------------------------------------------
# Checker 1: kernel parity (HS1xx)
# ---------------------------------------------------------------------------


CPP = '''
    extern "C" {
    int hs_foo(const int* a, long long n) {
      return 0;
    }
    }  // extern "C"
'''

NATIVE_OK = '''
    KERNEL_TWINS = {
        "hs_foo": ("foo", "numpy.lexsort"),
    }

    def foo():
        return None
'''

CPP_FUSED = '''
    extern "C" {
    int64_t hs_fused_bar(const int* a, long long n) {
      return 0;
    }
    }  // extern "C"
'''


class TestKernelParity:
    def test_missing_registry_entry(self, tmp_path):
        files = {
            "native/hs_native.cpp": CPP,
            "native/__init__.py": "KERNEL_TWINS = {}\n",
        }
        assert "HS101" in _rules(_lint(tmp_path, files))

    def test_no_registry_at_all(self, tmp_path):
        files = {
            "native/hs_native.cpp": CPP,
            "native/__init__.py": "def foo():\n    return None\n",
        }
        assert "HS101" in _rules(_lint(tmp_path, files))

    def test_stale_entry_and_unresolved_twin(self, tmp_path):
        files = {
            "native/hs_native.cpp": CPP,
            "native/__init__.py": (
                "KERNEL_TWINS = {\n"
                '    "hs_foo": ("missing_wrapper", "pkg.nowhere.fn"),\n'
                '    "hs_gone": ("foo", "numpy.lexsort"),\n'
                "}\n"
                "def foo():\n    return None\n"
            ),
        }
        rules = _rules(_lint(tmp_path, files))
        assert "HS102" in rules and "HS103" in rules

    def test_missing_differential_test(self, tmp_path):
        files = {"native/hs_native.cpp": CPP, "native/__init__.py": NATIVE_OK}
        findings = _lint(
            tmp_path, files, tests={"test_other.py": "def test_x():\n    pass\n"}
        )
        assert "HS104" in _rules(findings)

    def test_clean(self, tmp_path):
        files = {"native/hs_native.cpp": CPP, "native/__init__.py": NATIVE_OK}
        findings = _lint(
            tmp_path,
            files,
            tests={"test_foo.py": "def test_foo():\n    assert foo\n"},
        )
        assert findings == []

    def test_fused_export_with_numpy_twin_flagged(self, tmp_path):
        # seeded violation: a fused-pipeline export registered against a
        # numpy single-op twin — HS105 requires the in-package
        # interpreted chain as the parity reference
        files = {
            "native/hs_native.cpp": CPP_FUSED,
            "native/__init__.py": (
                "KERNEL_TWINS = {\n"
                '    "hs_fused_bar": ("fused_bar", "numpy.lexsort"),\n'
                "}\n"
                "def fused_bar():\n    return None\n"
            ),
        }
        findings = _lint(
            tmp_path,
            files,
            tests={"test_bar.py": "def test_bar():\n    assert fused_bar\n"},
        )
        assert "HS105" in _rules(findings)

    def test_fused_export_with_interpreted_twin_clean(self, tmp_path):
        files = {
            "native/hs_native.cpp": CPP_FUSED,
            "native/__init__.py": (
                "KERNEL_TWINS = {\n"
                '    "hs_fused_bar": ("fused_bar", "pkg.chain.interpreted_bar"),\n'
                "}\n"
                "def fused_bar():\n    return None\n"
            ),
            "chain.py": "def interpreted_bar():\n    return None\n",
        }
        findings = _lint(
            tmp_path,
            files,
            tests={"test_bar.py": "def test_bar():\n    assert fused_bar\n"},
        )
        assert findings == []


# ---------------------------------------------------------------------------
# Checker 2: log state machine (HS2xx)
# ---------------------------------------------------------------------------


CONSTANTS = '''
    class States:
        DOESNOTEXIST = "DOESNOTEXIST"
        CREATING = "CREATING"
        ACTIVE = "ACTIVE"
        DELETING = "DELETING"
        DELETED = "DELETED"

        STABLE_STATES = frozenset({ACTIVE, DELETED, DOESNOTEXIST})

        ROLLBACK = {
            CREATING: DOESNOTEXIST,
            DELETING: ACTIVE,
        }
'''

ACTIONS_CLEAN = '''
    from pkg.constants import States

    class CreateAction:
        transient_state = States.CREATING
        final_state = States.ACTIVE

    class DeleteAction:
        transient_state = States.DELETING
        final_state = States.DELETED
        required_state = States.ACTIVE
'''


class TestLogStateMachine:
    def test_clean(self, tmp_path):
        files = {"constants.py": CONSTANTS, "actions/act.py": ACTIONS_CLEAN}
        assert _lint(tmp_path, files) == []

    def test_illegal_transient_without_rollback(self, tmp_path):
        # seeded illegal transition: ACTIVE used as a transient state —
        # there is no rollback edge, cancel() could never recover it
        files = {
            "constants.py": CONSTANTS,
            "actions/act.py": ACTIONS_CLEAN,
            "actions/bad.py": """
                from pkg.constants import States

                class BadAction:
                    transient_state = States.ACTIVE
                    final_state = States.ACTIVE
            """,
        }
        assert "HS201" in _rules(_lint(tmp_path, files))

    def test_commit_to_unstable_state(self, tmp_path):
        files = {
            "constants.py": CONSTANTS,
            "actions/act.py": ACTIONS_CLEAN,
            "actions/bad.py": """
                from pkg.constants import States

                class BadAction:
                    transient_state = States.CREATING
                    final_state = States.DELETING
            """,
        }
        assert "HS202" in _rules(_lint(tmp_path, files))

    def test_unknown_state_name(self, tmp_path):
        files = {
            "constants.py": CONSTANTS,
            "actions/act.py": ACTIONS_CLEAN
            + "\n    BOGUS = States.FROBNICATING\n",
        }
        assert "HS203" in _rules(_lint(tmp_path, files))

    def test_required_state_mismatch(self, tmp_path):
        files = {
            "constants.py": CONSTANTS,
            "actions/act.py": ACTIONS_CLEAN,
            "actions/bad.py": """
                from pkg.constants import States

                class BadAction:
                    transient_state = States.CREATING
                    final_state = States.ACTIVE
                    required_state = States.ACTIVE
            """,
        }
        assert "HS204" in _rules(_lint(tmp_path, files))

    def test_unused_rollback_state(self, tmp_path):
        files = {
            "constants.py": CONSTANTS,
            "actions/act.py": """
                from pkg.constants import States

                class CreateAction:
                    transient_state = States.CREATING
                    final_state = States.ACTIVE
            """,
        }
        assert "HS205" in _rules(_lint(tmp_path, files))

    def test_rollback_edge_to_unstable_state(self, tmp_path):
        # seeded broken recovery edge: DELETING rolls back to CREATING
        # (transient) — cancel()/crash recovery would strand differently
        constants = CONSTANTS.replace(
            "DELETING: ACTIVE,", "DELETING: CREATING,"
        )
        files = {"constants.py": constants, "actions/act.py": ACTIONS_CLEAN}
        assert "HS206" in _rules(_lint(tmp_path, files))

    def test_suppression(self, tmp_path):
        files = {
            "constants.py": CONSTANTS,
            "actions/act.py": ACTIONS_CLEAN,
            "actions/bad.py": """
                from pkg.constants import States

                class BadAction:
                    transient_state = States.ACTIVE  # hslint: disable=HS201
                    final_state = States.ACTIVE
            """,
        }
        assert _lint(tmp_path, files) == []


# ---------------------------------------------------------------------------
# Checker 3: hot-path purity (HS3xx)
# ---------------------------------------------------------------------------


class TestPurity:
    def test_numpy_in_jit(self, tmp_path):
        files = {
            "ops/k.py": """
                import jax
                import jax.numpy as jnp
                import numpy as np

                @jax.jit
                def bad(x):
                    return np.concatenate([x, x])
            """
        }
        assert "HS301" in _rules(_lint(tmp_path, files))

    def test_host_sync_in_jit(self, tmp_path):
        files = {
            "ops/k.py": """
                import jax

                @jax.jit
                def bad(x):
                    return x.item()
            """
        }
        assert "HS302" in _rules(_lint(tmp_path, files))

    def test_shard_map_by_name_and_partial_jit(self, tmp_path):
        files = {
            "parallel/k.py": """
                import functools
                import jax
                import numpy as np
                from jax.experimental.shard_map import shard_map

                def local(x):
                    return np.argsort(x)

                def run(mesh, x):
                    return shard_map(local, mesh=mesh)(x)

                @functools.partial(jax.jit, static_argnames=("n",))
                def also_bad(x, n):
                    return np.asarray(x)
            """
        }
        findings = _lint(tmp_path, files)
        assert "HS301" in _rules(findings)  # np.argsort in shard_map'd fn
        assert "HS302" in _rules(findings)  # np.asarray under jit

    def test_clean_and_allowlist(self, tmp_path):
        files = {
            "ops/k.py": """
                import jax
                import jax.numpy as jnp
                import numpy as np

                @jax.jit
                def good(x):
                    return jnp.sum(x) + np.uint32(1)

                def host_helper(x):
                    # not traced: host numpy is fine here
                    return np.asarray(x).item()
            """
        }
        assert _lint(tmp_path, files) == []

    def test_suppression(self, tmp_path):
        files = {
            "ops/k.py": """
                import jax
                import numpy as np

                @jax.jit
                def bad(x):
                    # callback runs host-side by contract here
                    return np.log(x)  # hslint: disable=HS301
            """
        }
        assert _lint(tmp_path, files) == []

    def test_suppression_with_inline_justification(self, tmp_path):
        # text after the rule id must not break the suppression match
        files = {
            "ops/k.py": """
                import jax
                import numpy as np

                @jax.jit
                def bad(x):
                    return np.log(x)  # hslint: disable=HS301 host cb contract
            """
        }
        assert _lint(tmp_path, files) == []

    def test_annotations_are_not_traced(self, tmp_path):
        # np.ndarray annotations evaluate at def time, never under trace
        files = {
            "ops/k.py": """
                import jax
                import jax.numpy as jnp
                import numpy as np

                @jax.jit
                def good(x: np.ndarray) -> np.ndarray:
                    y: np.ndarray = jnp.sum(x)
                    return y
            """
        }
        assert _lint(tmp_path, files) == []


# ---------------------------------------------------------------------------
# Checker 4: exception policy (HS4xx)
# ---------------------------------------------------------------------------


class TestExceptPolicy:
    def test_bare_except(self, tmp_path):
        files = {
            "m.py": """
                def f():
                    try:
                        return 1
                    except:
                        return None
            """
        }
        assert "HS401" in _rules(_lint(tmp_path, files))

    def test_broad_except_without_reraise(self, tmp_path):
        files = {
            "m.py": """
                def f():
                    try:
                        return 1
                    except Exception:
                        return None
            """
        }
        assert "HS402" in _rules(_lint(tmp_path, files))

    def test_reraise_is_allowed(self, tmp_path):
        files = {
            "m.py": """
                def f():
                    try:
                        return 1
                    except Exception as e:
                        print(e)
                        raise
            """
        }
        assert _lint(tmp_path, files) == []

    def test_typed_is_clean_and_suppression_works(self, tmp_path):
        files = {
            "m.py": """
                def f():
                    try:
                        return 1
                    except ValueError:
                        return None

                def g():
                    try:
                        return 1
                    # deliberate catch-all: fallback is the contract
                    except Exception:  # hslint: disable=HS402
                        return None
            """
        }
        assert _lint(tmp_path, files) == []


# ---------------------------------------------------------------------------
# Checker 5: locks (HS5xx)
# ---------------------------------------------------------------------------


class TestLocks:
    def test_seeded_lock_order_cycle(self, tmp_path):
        files = {
            "a.py": """
                import threading

                A = threading.Lock()
                B = threading.Lock()

                def f():
                    with A:
                        with B:
                            pass

                def g():
                    with B:
                        with A:
                            pass
            """
        }
        assert "HS501" in _rules(_lint(tmp_path, files))

    def test_cross_function_cycle(self, tmp_path):
        # f holds A and calls helper() which takes B; g does the reverse
        # through its own callee — only the transitive call graph sees it
        files = {
            "a.py": """
                import threading

                A = threading.Lock()
                B = threading.Lock()

                def take_b():
                    with B:
                        pass

                def take_a():
                    with A:
                        pass

                def f():
                    with A:
                        take_b()

                def g():
                    with B:
                        take_a()
            """
        }
        assert "HS501" in _rules(_lint(tmp_path, files))

    def test_lock_held_io_direct_and_via_callee(self, tmp_path):
        files = {
            "a.py": """
                import threading

                A = threading.Lock()

                def io_helper(p):
                    with open(p) as f:
                        return f.read()

                def direct(p):
                    with A:
                        return open(p).read()

                def via_callee(p):
                    with A:
                        return io_helper(p)
            """
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS502"]
        assert len(findings) == 2

    def test_consistent_order_is_clean(self, tmp_path):
        files = {
            "a.py": """
                import threading

                A = threading.Lock()
                B = threading.Lock()

                def f():
                    with A:
                        with B:
                            pass

                def g():
                    with A:
                        with B:
                            pass
            """
        }
        assert _lint(tmp_path, files) == []

    def test_same_class_name_in_two_modules_does_not_alias(self, tmp_path):
        # instance locks are keyed by (module, class): two classes both
        # named Cache must be distinct lock identities, or their edges
        # would merge and could fake a cycle across unrelated modules
        from hyperspace_tpu.analysis.core import Project
        from hyperspace_tpu.analysis.locks import _collect_defs

        src = """
            import threading

            class Cache:
                def __init__(self):
                    self._lock = threading.Lock()
        """
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        _write_tree(pkg, {"a.py": src, "b.py": src})
        _indexes, locks = _collect_defs(Project(str(pkg)))
        assert len(locks) == 2
        assert {scope for scope, _ in locks} == {
            "cls:a.py:Cache",
            "cls:b.py:Cache",
        }

    def test_instance_locks_and_suppression(self, tmp_path):
        files = {
            "a.py": """
                import threading

                class Cache:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def load(self, p):
                        # one-time load is serialized by design
                        with self._lock:  # hslint: disable=HS502
                            return open(p).read()

                    def get(self, k):
                        with self._lock:
                            return k
            """
        }
        assert _lint(tmp_path, files) == []


# ---------------------------------------------------------------------------
# Checker 6: shared state (HS6xx)
# ---------------------------------------------------------------------------


STATE_OK = """
    import threading

    _lock = threading.Lock()
    cache = {}

    def put(k, v):
        with _lock:
            cache[k] = v

    def read_all():
        with _lock:
            return dict(cache)
"""

SERVE_SUBMIT = """
    from pkg import state

    def worker(item):
        state.put(item, 1)

    def run(pool, items):
        return [pool.submit(worker, i) for i in items]
"""

REGISTRY_OK = '''
    SHARED_STATE = {
        "pkg.state.cache": (
            "pkg.state._lock",
            "guarded",
            "all access under the lock",
        ),
    }
'''


class TestSharedState:
    def test_registered_guarded_is_clean(self, tmp_path):
        files = {
            "concurrency.py": REGISTRY_OK,
            "state.py": STATE_OK,
            "serve.py": SERVE_SUBMIT,
        }
        assert _lint(tmp_path, files) == []

    def test_unregistered_pool_reachable_global(self, tmp_path):
        # seeded violation: a written module global reached from a
        # pool-submitted closure with no SHARED_STATE entry
        files = {
            "concurrency.py": REGISTRY_OK,
            "state.py": STATE_OK,
            "serve.py": SERVE_SUBMIT
            + """
    stats = {}

    def telemetry(item):
        stats[item] = 1

    def run2(pool, items):
        return [pool.submit(telemetry, i) for i in items]
""",
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS601"]
        assert findings and "stats" in findings[0].message

    def test_nested_closure_is_reached(self, tmp_path):
        # the submitted callable is a closure DEFINED INSIDE the
        # submitting function — the resolver must still reach it
        files = {
            "concurrency.py": REGISTRY_OK,
            "state.py": STATE_OK,
            "serve.py": """
    totals = {}

    def run(pool, items):
        def one(i):
            totals[i] = totals.get(i, 0) + 1
        return [pool.submit(one, i) for i in items]
""",
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS601"]
        assert findings and "totals" in findings[0].message

    def test_never_written_global_is_config_not_state(self, tmp_path):
        # a module dict nothing writes (a KERNEL_TWINS-style registry
        # literal) is configuration, not shared state
        files = {
            "concurrency.py": REGISTRY_OK,
            "state.py": STATE_OK,
            "serve.py": SERVE_SUBMIT
            + """
    TABLE = {"a": 1}

    def lookup(item):
        return TABLE.get(item)

    def run3(pool, items):
        return [pool.submit(lookup, i) for i in items]
""",
        }
        assert _lint(tmp_path, files) == []

    def test_guarded_policy_violation(self, tmp_path):
        # seeded violation: a lock-free read of "guarded" state
        files = {
            "concurrency.py": REGISTRY_OK,
            "state.py": STATE_OK
            + """
    def peek(k):
        return cache.get(k)
""",
            "serve.py": SERVE_SUBMIT,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS602"]
        assert findings and "peek" in findings[0].message

    def test_guarded_writes_allows_racy_reads(self, tmp_path):
        registry = REGISTRY_OK.replace('"guarded"', '"guarded-writes"')
        files = {
            "concurrency.py": registry,
            "state.py": STATE_OK
            + """
    def peek(k):
        return cache.get(k)
""",
            "serve.py": SERVE_SUBMIT,
        }
        assert _lint(tmp_path, files) == []

    def test_rebind_only_flags_in_place_mutation(self, tmp_path):
        files = {
            "concurrency.py": '''
    SHARED_STATE = {
        "pkg.state.last_stats": (
            "",
            "rebind-only",
            "published as one atomic rebind",
        ),
    }
''',
            "state.py": """
    last_stats = {}

    def publish_ok(d):
        global last_stats
        last_stats = dict(d)

    def publish_torn(d):
        last_stats.clear()
        last_stats.update(d)
""",
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS602"]
        assert len(findings) == 2  # clear + update; the rebind is clean

    def test_stale_registry_entries(self, tmp_path):
        # three distinct staleness shapes: unknown state path, unknown
        # lock, unknown policy — one HS603 each
        files = {
            "concurrency.py": '''
    SHARED_STATE = {
        "pkg.state.cache": (
            "pkg.state._lock",
            "guarded",
            "all access under the lock",
        ),
        "pkg.state.gone": (
            "pkg.state._lock",
            "guarded",
            "stale",
        ),
        "pkg.state.cache2": (
            "pkg.state._missing_lock",
            "guarded",
            "bad lock",
        ),
        "pkg.state.cache3": (
            "pkg.state._lock",
            "bogus-policy",
            "bad policy",
        ),
    }
''',
            "state.py": STATE_OK + "\n    cache2 = {}\n    cache3 = {}\n",
        }
        rules = [f.rule for f in _lint(tmp_path, files)]
        assert rules.count("HS603") == 3

    def test_missing_justification(self, tmp_path):
        files = {
            "concurrency.py": REGISTRY_OK.replace(
                '"all access under the lock"', '""'
            ),
            "state.py": STATE_OK,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS603"]
        assert findings and "justification" in findings[0].message

    def test_suppression(self, tmp_path):
        files = {
            "concurrency.py": REGISTRY_OK,
            "state.py": STATE_OK,
            "serve.py": SERVE_SUBMIT
            + """
    stats = {}

    def telemetry(item):
        # single-writer bench counter by contract
        stats[item] = 1  # hslint: disable=HS601

    def run2(pool, items):
        return [pool.submit(telemetry, i) for i in items]
""",
        }
        assert _lint(tmp_path, files) == []

    def test_instance_attr_policy(self, tmp_path):
        # registered class attribute: __init__ is exempt, unlocked
        # method access is flagged
        files = {
            "concurrency.py": '''
    SHARED_STATE = {
        "pkg.cachemod.Cache._entries": (
            "self._lock",
            "guarded",
            "map guarded by the instance lock",
        ),
    }
''',
            "cachemod.py": """
    import threading

    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
            self._entries = {}

        def get(self, k):
            with self._lock:
                return self._entries.get(k)

        def size_unlocked(self):
            return len(self._entries)
""",
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS602"]
        assert len(findings) == 1 and "size_unlocked" in findings[0].message


# ---------------------------------------------------------------------------
# Checker 7: contracts (HS7xx)
# ---------------------------------------------------------------------------


CONTRACT_CONSTANTS = """
    FOO = "hyperspace.foo.enabled"
    FOO_DEFAULT = True
    BAR = "hyperspace.bar.limit"
"""

CONTRACT_CONFIG = """
    from pkg import constants as C

    def foo(conf):
        return conf.get_bool(C.FOO, C.FOO_DEFAULT)

    def bar(conf):
        return conf.get_int(C.BAR, 3)
"""

CONTRACT_DOC = """\
# Config

| Key | Default | Meaning |
|---|---|---|
| `hyperspace.foo.enabled` | `true` | the foo switch |
| `hyperspace.bar.limit` | `3` | the bar bound |
"""


def _write_doc(tmp_path, text=CONTRACT_DOC):
    d = tmp_path / "docs"
    d.mkdir(exist_ok=True)
    (d / "CONFIG.md").write_text(text)


class TestContracts:
    def test_missing_default(self, tmp_path):
        _write_doc(tmp_path)
        files = {
            "constants.py": CONTRACT_CONSTANTS,
            "config.py": CONTRACT_CONFIG,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS701"]
        assert len(findings) == 1 and "BAR" in findings[0].message

    def test_literal_key_read(self, tmp_path):
        _write_doc(tmp_path)
        files = {
            "constants.py": CONTRACT_CONSTANTS + "    BAR_DEFAULT = 3\n",
            "config.py": CONTRACT_CONFIG
            + """
    def sneaky(conf):
        return conf.get("hyperspace.sneaky.key")
""",
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS701"]
        assert len(findings) == 1 and "sneaky" in findings[0].message

    def test_undocumented_key(self, tmp_path):
        _write_doc(
            tmp_path,
            CONTRACT_DOC.replace(
                "| `hyperspace.bar.limit` | `3` | the bar bound |\n", ""
            ),
        )
        files = {
            "constants.py": CONTRACT_CONSTANTS + "    BAR_DEFAULT = 3\n",
            "config.py": CONTRACT_CONFIG,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS702"]
        assert len(findings) == 1 and "hyperspace.bar.limit" in findings[0].message

    def test_dead_documented_key(self, tmp_path):
        _write_doc(
            tmp_path,
            CONTRACT_DOC + "| `hyperspace.ghost.key` | `x` | gone |\n",
        )
        files = {
            "constants.py": CONTRACT_CONSTANTS + "    BAR_DEFAULT = 3\n",
            "config.py": CONTRACT_CONFIG,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS704"]
        assert len(findings) == 1 and "ghost" in findings[0].message

    def test_dead_declared_key(self, tmp_path):
        _write_doc(tmp_path)
        files = {
            "constants.py": CONTRACT_CONSTANTS
            + '    BAR_DEFAULT = 3\n    BAZ = "hyperspace.baz.unused"\n',
            "config.py": CONTRACT_CONFIG,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS704"]
        assert len(findings) == 1 and "BAZ" in findings[0].message

    def test_fault_matrix_hole(self, tmp_path):
        _write_doc(tmp_path)
        files = {
            "constants.py": CONTRACT_CONSTANTS + "    BAR_DEFAULT = 3\n",
            "config.py": CONTRACT_CONFIG,
            "testing/faults.py": 'POINTS = ("a_point", "b_point")\n',
        }
        tests = {
            "test_faults.py": "def test_matrix():\n    assert 'a_point'\n"
        }
        findings = [
            f for f in _lint(tmp_path, files, tests=tests) if f.rule == "HS703"
        ]
        assert len(findings) == 1 and "b_point" in findings[0].message

    def test_crash_matrix_hole(self, tmp_path):
        # crash points have their own matrix file: a point missing from
        # tests/test_crash_recovery.py is an untested crash mode
        _write_doc(tmp_path)
        files = {
            "constants.py": CONTRACT_CONSTANTS + "    BAR_DEFAULT = 3\n",
            "config.py": CONTRACT_CONFIG,
            "testing/faults.py": (
                'POINTS = ("a_point",)\n'
                'CRASH_POINTS = ("after_x", "mid_y")\n'
            ),
        }
        tests = {
            "test_faults.py": "def test_matrix():\n    assert 'a_point'\n",
            "test_crash_recovery.py": (
                "def test_crash():\n    assert 'after_x'\n"
            ),
        }
        findings = [
            f for f in _lint(tmp_path, files, tests=tests) if f.rule == "HS703"
        ]
        assert len(findings) == 1 and "mid_y" in findings[0].message
        assert "test_crash_recovery.py" in findings[0].message

    def test_clean_and_prefix_family(self, tmp_path):
        _write_doc(
            tmp_path,
            CONTRACT_DOC
            + "| `hyperspace.faults.<point>` | unset | injection |\n",
        )
        files = {
            "constants.py": CONTRACT_CONSTANTS
            + '    BAR_DEFAULT = 3\n    FAULTS_PREFIX = "hyperspace.faults."\n',
            "config.py": CONTRACT_CONFIG
            + """
    def faults(conf):
        return conf.prefixed(C.FAULTS_PREFIX)
""",
        }
        assert _lint(tmp_path, files) == []

    def test_suppression_in_constants(self, tmp_path):
        _write_doc(tmp_path)
        files = {
            "constants.py": CONTRACT_CONSTANTS.replace(
                'BAR = "hyperspace.bar.limit"',
                '    # required key: no default by design\n'
                '    BAR = "hyperspace.bar.limit"  # hslint: disable=HS701',
            ),
            "config.py": CONTRACT_CONFIG,
        }
        assert _lint(tmp_path, files) == []


# ---------------------------------------------------------------------------
# The lock witness: record → cross-check round trip
# ---------------------------------------------------------------------------


class TestLockWitness:
    @pytest.fixture
    def witness(self):
        # the recorder is process-global: these tests reset and
        # uninstall it, which would gut a session-level recording
        if os.environ.get("HS_LOCK_WITNESS"):
            pytest.skip("HS_LOCK_WITNESS session recording is active")
        from hyperspace_tpu.testing import lock_witness

        lock_witness.reset()
        lock_witness.install()
        try:
            yield lock_witness
        finally:
            lock_witness.uninstall()
            lock_witness.reset()

    def test_round_trip_clean(self, tmp_path, witness):
        # drive real guarded paths: module lock + instance lock
        from hyperspace_tpu.execution.serve_cache import ServeCache
        from hyperspace_tpu.indexes import zonemaps

        cache = ServeCache(1 << 20)
        cache.put(("scan", "fp"), "v", 8)
        assert cache.get(("scan", "fp")) == "v"
        zonemaps.invalidate_local_cache()
        path = str(tmp_path / "witness.json")
        doc = witness.dump(path)
        assert doc["locks"]["execution/serve_cache.py::ServeCache._lock"] >= 2
        assert doc["locks"]["indexes/zonemaps.py::_local_lock"] >= 1
        from hyperspace_tpu.analysis import shared_state as ss
        from hyperspace_tpu.analysis.core import Project

        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        gaps, _warnings = ss.witness_cross_check(
            [project], ss.load_witness(path), "witness.json"
        )
        assert gaps == []

    def test_model_gap_detected(self, tmp_path, witness):
        # manufacture a nested acquisition the static graph does NOT
        # contain: the cross-check must call it a hard model gap
        from hyperspace_tpu.execution import join_exec
        from hyperspace_tpu.indexes import zonemaps

        with zonemaps._local_lock:
            with join_exec._serve_bd_lock:
                pass
        path = str(tmp_path / "witness.json")
        witness.dump(path)
        from hyperspace_tpu.analysis import shared_state as ss
        from hyperspace_tpu.analysis.core import Project

        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        gaps, _warnings = ss.witness_cross_check(
            [project], ss.load_witness(path), "witness.json"
        )
        assert len(gaps) == 1 and gaps[0].rule == "HS604"
        assert "_local_lock" in gaps[0].message
        assert "_serve_bd_lock" in gaps[0].message

    def test_artifacts_merge(self, tmp_path, witness):
        from hyperspace_tpu.indexes import zonemaps

        path = str(tmp_path / "witness.json")
        zonemaps.invalidate_local_cache()
        first = witness.dump(path)
        witness.reset()
        zonemaps.invalidate_local_cache()
        second = witness.dump(path)
        key = "indexes/zonemaps.py::_local_lock"
        assert second["locks"][key] == first["locks"][key] + 1

    def test_malformed_artifact_rejected(self, tmp_path):
        # every malformed shape must raise ValueError (the CLI's exit-2
        # contract), never crash downstream with a raw traceback
        from hyperspace_tpu.analysis import shared_state as ss

        bad_docs = [
            '{"not": "a witness"}',
            '{"version": 1, "locks": {}, "edges": [["one_element"]]}',
            '{"version": 1, "locks": ["a"], "edges": []}',
            '{"version": 1, "locks": {"a": "n"}, "edges": []}',
        ]
        for i, text in enumerate(bad_docs):
            p = tmp_path / f"bad{i}.json"
            p.write_text(text)
            with pytest.raises(ValueError):
                ss.load_witness(str(p))


# ---------------------------------------------------------------------------
# Checker 8: SPMD collective symmetry (HS8xx)
# ---------------------------------------------------------------------------


SPMD_REGISTRY = '''
    COLLECTIVE_SITES = {
        "pkg.comm.exchange": (
            "all_to_all",
            "symmetric-all",
            "every process exchanges at the same step",
        ),
    }
'''

SPMD_COMM = """
    from jax import lax

    def exchange(x):
        return lax.all_to_all(x, "s", 0, 0)
"""

SPMD_GATED_REGISTRY = '''
    COLLECTIVE_SITES = {
        "pkg.comm.exchange": (
            "all_to_all",
            "symmetric-all",
            "every process exchanges at the same step",
        ),
        "pkg.logplane.publish": (
            "log_write",
            "coordinator-gated",
            "single-writer metadata seam",
        ),
    }
'''


class TestSpmd:
    def test_identity_branch_skipping_collective(self, tmp_path):
        # seeded violation: process 0 exchanges, everyone else returns —
        # the PR 11 bug shape, statically
        files = {
            "collectives.py": SPMD_REGISTRY,
            "comm.py": SPMD_COMM,
            "driver.py": """
                import jax

                from pkg.comm import exchange

                def run(x):
                    if jax.process_index() == 0:
                        return exchange(x)
                    return x
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS801"]
        assert findings and "exchange" in findings[0].message

    def test_identity_branch_via_tainted_local(self, tmp_path):
        # the identity value rides a local name; the taint must follow
        files = {
            "collectives.py": SPMD_REGISTRY,
            "comm.py": SPMD_COMM,
            "driver.py": """
                import jax

                from pkg.comm import exchange

                def run(x):
                    pid = jax.process_index()
                    if pid == 0:
                        exchange(x)
                    return x
            """,
        }
        assert "HS801" in _rules(_lint(tmp_path, files))

    def test_symmetric_branch_is_clean(self, tmp_path):
        # both paths reach the collective (the branch only picks the
        # payload): no divergence
        files = {
            "collectives.py": SPMD_REGISTRY,
            "comm.py": SPMD_COMM,
            "driver.py": """
                import jax

                from pkg.comm import exchange

                def run(x, y):
                    if jax.process_index() == 0:
                        out = exchange(x)
                    else:
                        out = exchange(y)
                    return out
            """,
        }
        assert _lint(tmp_path, files) == []

    def test_process_count_branch_is_uniform(self, tmp_path):
        # every process agrees on process_count(): gating a collective
        # on it cannot diverge and must stay clean (the single-vs-multi
        # guard idiom all over covering_build)
        files = {
            "collectives.py": SPMD_REGISTRY,
            "comm.py": SPMD_COMM,
            "driver.py": """
                import jax

                from pkg.comm import exchange

                def run(x):
                    if jax.process_count() > 1:
                        return exchange(x)
                    return x
            """,
        }
        assert _lint(tmp_path, files) == []

    def test_coordinator_gated_branch_is_clean(self, tmp_path):
        # gating a coordinator-gated site on is_coordinator IS the
        # contract; the symmetric collective after the branch is reached
        # by both paths
        files = {
            "collectives.py": SPMD_GATED_REGISTRY,
            "comm.py": SPMD_COMM,
            "logplane.py": """
                from jax.experimental import multihost_utils as mhu

                def publish(x):
                    return mhu.broadcast_one_to_all(x)
            """,
            "driver.py": """
                from pkg.comm import exchange
                from pkg.logplane import publish

                def run(mesh, x):
                    if mesh.is_coordinator:
                        publish(x)
                    return exchange(x)
            """,
        }
        assert _lint(tmp_path, files) == []

    def test_unregistered_collective(self, tmp_path):
        # seeded violation: a ppermute with no COLLECTIVE_SITES entry
        files = {
            "collectives.py": SPMD_REGISTRY,
            "comm.py": SPMD_COMM,
            "rogue.py": """
                from jax import lax

                def sneak(x):
                    return lax.ppermute(x, "s", [(0, 1)])
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS802"]
        assert findings and "sneak" in findings[0].message

    def test_stale_registry_entries(self, tmp_path):
        # four staleness shapes: unresolved path, unknown contract,
        # missing justification, non-gated entry with no collective
        files = {
            "collectives.py": '''
    COLLECTIVE_SITES = {
        "pkg.comm.exchange": (
            "all_to_all",
            "symmetric-all",
            "every process exchanges at the same step",
        ),
        "pkg.comm.gone": ("all_to_all", "symmetric-all", "stale"),
        "pkg.comm.exchange2": ("all_to_all", "bogus-contract", "bad"),
        "pkg.comm.exchange3": ("all_to_all", "symmetric-all", ""),
        "pkg.comm.quiet": ("all_to_all", "symmetric-all", "no op inside"),
    }
''',
            "comm.py": SPMD_COMM
            + """
    def exchange2(x):
        return lax.all_to_all(x, "s", 0, 0)

    def exchange3(x):
        return lax.all_to_all(x, "s", 0, 0)

    def quiet(x):
        return x
""",
        }
        rules = [f.rule for f in _lint(tmp_path, files)]
        assert rules.count("HS802") == 4

    def test_process_local_loop_bound(self, tmp_path):
        # seeded violation: the wave-count bug — a collective inside a
        # loop over this process's file stripe
        files = {
            "collectives.py": SPMD_REGISTRY,
            "comm.py": SPMD_COMM,
            "driver.py": """
                import jax

                from pkg.comm import exchange

                def waves(files, x):
                    mine = files[jax.process_index()::jax.process_count()]
                    for f in mine:
                        x = exchange(x)
                    return x
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS803"]
        assert findings and "exchange" in findings[0].message

    def test_allgathered_loop_bound_is_clean(self, tmp_path):
        # process_allgather sanitizes: the bound is global by contract
        files = {
            "collectives.py": SPMD_REGISTRY,
            "comm.py": SPMD_COMM,
            "driver.py": """
                import jax
                from jax.experimental import multihost_utils as mhu

                from pkg.comm import exchange

                def waves(local_counts, x):
                    counts = mhu.process_allgather(local_counts)
                    for c in counts:
                        x = exchange(x)
                    return x
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS803"]
        assert findings == []

    def test_suppression(self, tmp_path):
        files = {
            "collectives.py": SPMD_REGISTRY,
            "comm.py": SPMD_COMM,
            "driver.py": """
                import jax

                from pkg.comm import exchange

                def run(x):
                    # single-process probe path by contract
                    if jax.process_index() == 0:  # hslint: disable=HS801
                        return exchange(x)
                    return x
            """,
        }
        assert _lint(tmp_path, files) == []


# ---------------------------------------------------------------------------
# The collective witness: record → merge → cross-check round trip
# ---------------------------------------------------------------------------


def _spmd_project(tmp_path, registry=SPMD_REGISTRY):
    from hyperspace_tpu.analysis.core import Project

    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    _write_tree(pkg, {"collectives.py": registry, "comm.py": SPMD_COMM})
    return Project(str(pkg))


def _cw_artifact(tmp_path, process, sequence, prefix="cw"):
    import json

    doc = {
        "version": 1,
        "package": "pkg",
        "process": process,
        "process_count": 2,
        "registered": {},
        "sequence": sequence,
    }
    p = tmp_path / f"{prefix}.p{process}.json"
    p.write_text(json.dumps(doc))
    return str(tmp_path / prefix)


def _rec(site, wave=0, op="all_to_all", sig="(int32[1d])", contract="symmetric-all"):
    return {"site": site, "op": op, "wave": wave, "sig": sig, "contract": contract}


class TestCollectiveWitness:
    SITE = "pkg.comm.exchange"

    def test_round_trip_clean(self, tmp_path):
        from hyperspace_tpu.analysis import spmd

        project = _spmd_project(tmp_path)
        seq = [_rec(self.SITE, 0), _rec(self.SITE, 1)]
        _cw_artifact(tmp_path, 0, seq)
        prefix = _cw_artifact(tmp_path, 1, seq)
        docs = spmd.load_collective_witness(prefix)
        assert [d["process"] for d in docs] == [0, 1]
        findings, warnings = spmd.collective_cross_check([project], docs, "cw")
        assert findings == []
        assert warnings == []  # the one registered site was witnessed

    def test_desynchronized_sequences(self, tmp_path):
        # process 1 skipped the second exchange: hard divergence
        from hyperspace_tpu.analysis import spmd

        project = _spmd_project(tmp_path)
        _cw_artifact(tmp_path, 0, [_rec(self.SITE, 0), _rec(self.SITE, 1)])
        prefix = _cw_artifact(tmp_path, 1, [_rec(self.SITE, 0)])
        docs = spmd.load_collective_witness(prefix)
        findings, _w = spmd.collective_cross_check([project], docs, "cw")
        assert len(findings) == 1 and findings[0].rule == "HS804"
        assert "divergence" in findings[0].message

    def test_signature_divergence_on_symmetric_site(self, tmp_path):
        from hyperspace_tpu.analysis import spmd

        project = _spmd_project(tmp_path)
        _cw_artifact(tmp_path, 0, [_rec(self.SITE, sig="(int32[1d])")])
        prefix = _cw_artifact(tmp_path, 1, [_rec(self.SITE, sig="(int64[1d])")])
        docs = spmd.load_collective_witness(prefix)
        findings, _w = spmd.collective_cross_check([project], docs, "cw")
        assert len(findings) == 1 and "signatures differ" in findings[0].message

    def test_witnessed_unregistered_site(self, tmp_path):
        from hyperspace_tpu.analysis import spmd

        project = _spmd_project(tmp_path)
        seq = [_rec(self.SITE, 0), _rec("pkg.rogue.sneak", 0, op="ppermute")]
        _cw_artifact(tmp_path, 0, seq)
        prefix = _cw_artifact(tmp_path, 1, seq)
        docs = spmd.load_collective_witness(prefix)
        findings, _w = spmd.collective_cross_check([project], docs, "cw")
        assert len(findings) == 1 and findings[0].rule == "HS804"
        assert "pkg.rogue.sneak" in findings[0].message

    def test_coordinator_gated_on_worker(self, tmp_path):
        from hyperspace_tpu.analysis import spmd

        project = _spmd_project(tmp_path, registry=SPMD_GATED_REGISTRY)
        pkg = tmp_path / "pkg"
        _write_tree(
            pkg,
            {
                "logplane.py": """
    from jax.experimental import multihost_utils as mhu

    def publish(x):
        return mhu.broadcast_one_to_all(x)
"""
            },
        )
        from hyperspace_tpu.analysis.core import Project

        project = Project(str(pkg))
        gated = _rec(
            "pkg.logplane.publish",
            op="log_write",
            contract="coordinator-gated",
        )
        _cw_artifact(tmp_path, 0, [_rec(self.SITE), gated])
        prefix = _cw_artifact(tmp_path, 1, [_rec(self.SITE), gated])
        docs = spmd.load_collective_witness(prefix)
        findings, _w = spmd.collective_cross_check([project], docs, "cw")
        # gated on process 1 is the single hard error; the gated records
        # are FILTERED from the sequence comparison (no false divergence)
        assert len(findings) == 1 and findings[0].rule == "HS804"
        assert "coordinator-gated" in findings[0].message

    def test_never_witnessed_is_warning(self, tmp_path):
        from hyperspace_tpu.analysis import spmd

        project = _spmd_project(tmp_path)
        _cw_artifact(tmp_path, 0, [])
        prefix = _cw_artifact(tmp_path, 1, [])
        docs = spmd.load_collective_witness(prefix)
        findings, warnings = spmd.collective_cross_check([project], docs, "cw")
        assert findings == []
        assert warnings and "never witnessed" in warnings[0]

    def test_malformed_artifacts_rejected(self, tmp_path):
        import json

        from hyperspace_tpu.analysis import spmd

        bad_docs = [
            '{"not": "a witness"}',
            '{"process": "zero", "sequence": []}',
            '{"process": 0, "sequence": [{"site": 1}]}',
            '{"process": 0, "sequence": [], "registered": []}',
        ]
        for i, text in enumerate(bad_docs):
            p = tmp_path / f"bad{i}.json"
            p.write_text(text)
            with pytest.raises(ValueError):
                spmd.load_collective_witness(str(p))
        with pytest.raises(ValueError):
            spmd.load_collective_witness(str(tmp_path / "absent_prefix"))
        # duplicate process indexes across a family are torn recordings
        doc = {"process": 0, "sequence": [], "registered": {}}
        (tmp_path / "dup.p0.json").write_text(json.dumps(doc))
        (tmp_path / "dup.p00.json").write_text(json.dumps(doc))
        with pytest.raises(ValueError):
            spmd.load_collective_witness(str(tmp_path / "dup"))

    def test_runtime_record_and_dump(self, tmp_path):
        # the real recorder against the real registry: wrap, drive one
        # registered site single-process, dump, reload, cross-check
        from hyperspace_tpu.analysis import spmd
        from hyperspace_tpu.analysis.core import Project
        from hyperspace_tpu.testing import collective_witness as cw

        cw.reset()
        wrapped = cw.install()
        try:
            assert (
                wrapped["hyperspace_tpu.actions.base._publish_log"]
                == "coordinator-gated"
            )
            from hyperspace_tpu.indexes import covering_build

            # single-process _global_written returns early but the call
            # itself is recorded — in-module callers resolve the name
            # through module globals, so the wrapper is seen
            out = covering_build._global_written(None, ["a.parquet"])
            assert out == ["a.parquet"]
            prefix = str(tmp_path / "cw")
            doc = cw.dump(prefix)
        finally:
            cw.uninstall()
            cw.reset()
        assert doc["process"] == 0
        sites = [r["site"] for r in doc["sequence"]]
        assert sites == [
            "hyperspace_tpu.indexes.covering_build._global_written"
        ]
        assert doc["sequence"][0]["wave"] == 0
        docs = spmd.load_collective_witness(prefix)
        findings, _w = spmd.collective_cross_check(
            [Project(PKG_DIR, tests_dir=TESTS_DIR)], docs, "cw"
        )
        assert findings == []

    def test_contracts_require_dryrun_coverage(self, tmp_path):
        # the HS703 extension: a registered collective site absent from
        # scripts/dryrun_multihost.py is a witness-matrix hole
        scripts = tmp_path / "scripts"
        scripts.mkdir()
        (scripts / "dryrun_multihost.py").write_text(
            'WITNESS = ("pkg.comm.exchange",)\n'
        )
        files = {
            "collectives.py": SPMD_GATED_REGISTRY,
            "comm.py": SPMD_COMM,
            "logplane.py": """
    from jax.experimental import multihost_utils as mhu

    def publish(x):
        return mhu.broadcast_one_to_all(x)
""",
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS703"]
        assert len(findings) == 1
        assert "pkg.logplane.publish" in findings[0].message
        # trailing-name (prefix-family) match: naming just the callable
        # in a WITNESS_* tuple satisfies the rule
        (scripts / "dryrun_multihost.py").write_text(
            'WITNESS = ("pkg.comm.exchange", "publish")\n'
        )
        assert [f for f in _lint(tmp_path, files) if f.rule == "HS703"] == []


# ---------------------------------------------------------------------------
# HS9xx — observability-site lints (analysis/obs.py)
# ---------------------------------------------------------------------------

OBS_REGISTRY = """
    KINDS = ("span", "metric", "view")
    SERVE_STAGES = ("scan", "prepare")
    BUILD_STAGES = ("write",)
    ROOT_NAMES = ("serve.query",)
    OBS_SITES = {
        "pkg.app.serve": ("span", "roots the query at admission"),
    }
"""

OBS_APP = """
    from pkg.obs import trace

    def serve():
        r = trace.root("serve.query")
        trace.stage("scan", 0.0)
        return r
"""


class TestObsSites:
    def test_clean_tree(self, tmp_path):
        findings = _lint(
            tmp_path, {"sites.py": OBS_REGISTRY, "app.py": OBS_APP}
        )
        assert [f for f in findings if f.rule.startswith("HS9")] == []

    def test_no_registry_skips_checker(self, tmp_path):
        # trees without an OBS_SITES registry have no obs plane to lint
        findings = _lint(tmp_path, {"app.py": OBS_APP})
        assert [f for f in findings if f.rule.startswith("HS9")] == []

    def test_undeclared_site_flagged(self, tmp_path):
        files = {
            "sites.py": OBS_REGISTRY,
            "app.py": OBS_APP,
            "rogue.py": """
                from pkg.obs import trace

                def hot_loop():
                    with trace.span("scan"):
                        return 1
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS901"]
        assert len(findings) == 1
        assert "pkg.rogue.hot_loop" in findings[0].message

    @pytest.mark.parametrize("declared", [False, True])
    def test_attrs_on_the_callers_span_are_a_site(self, tmp_path, declared):
        """A pass that reaches the live span (``trace.current()``) to say
        what its caller's stage's seconds went to is instrumentation
        too: declared with kind ``attr``, or HS901."""
        registry = OBS_REGISTRY.replace(
            'KINDS = ("span", "metric", "view")',
            'KINDS = ("span", "metric", "view", "attr")',
        )
        if declared:
            registry = registry.replace(
                '"pkg.app.serve": ("span", "roots the query at admission"),',
                '"pkg.app.serve": ("span", "roots the query at admission"),\n'
                '        "pkg.scan.read_files": ("attr", "read_s on the scan"),',
            )
        files = {
            "sites.py": registry,
            "app.py": OBS_APP,
            "scan.py": """
                from pkg.obs import trace as _obs_trace

                def read_files(files):
                    sp = _obs_trace.current()
                    if sp is not None:
                        sp.set("read_s", 0.0)
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule.startswith("HS9")]
        if declared:
            assert findings == []
        else:
            assert [f.rule for f in findings] == ["HS901"]
            assert "pkg.scan.read_files" in findings[0].message
            assert "'current'" in findings[0].message

    def test_nested_def_attributes_to_outermost(self, tmp_path):
        files = {
            "sites.py": OBS_REGISTRY,
            "rogue.py": """
                from pkg.obs import trace

                def outer():
                    def inner():
                        trace.stage("scan", 0.0)
                    return inner
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS901"]
        assert len(findings) == 1
        assert "pkg.rogue.outer" in findings[0].message

    def test_module_level_metric_site(self, tmp_path):
        files = {
            "sites.py": OBS_REGISTRY.replace(
                '"pkg.app.serve": ("span", "roots the query at admission"),',
                '"pkg.app.serve": ("span", "roots the query at admission"),\n'
                '        "pkg.instruments": ("metric", "module-level '
                'registration"),',
            ),
            "app.py": OBS_APP,
            "instruments.py": """
                from pkg.obs import metrics

                registry = metrics.registry
                c = registry.counter("hs_x_total", "x")
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS901"]
        assert findings == []

    def test_suppression_silences(self, tmp_path):
        files = {
            "sites.py": OBS_REGISTRY,
            "rogue.py": """
                from pkg.obs import trace

                def hot_loop():
                    # justified one-off probe
                    trace.stage("scan", 0.0)  # hslint: disable=HS901
            """,
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS901"]
        assert findings == []

    def test_stage_name_outside_vocabulary(self, tmp_path):
        files = {
            "sites.py": OBS_REGISTRY,
            "app.py": OBS_APP.replace('trace.stage("scan", 0.0)',
                                      'trace.stage("scanx", 0.0)'),
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS902"]
        assert len(findings) == 1
        assert "'scanx'" in findings[0].message

    def test_root_name_outside_vocabulary(self, tmp_path):
        files = {
            "sites.py": OBS_REGISTRY,
            "app.py": OBS_APP.replace('trace.root("serve.query")',
                                      'trace.root("mystery")'),
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS902"]
        assert len(findings) == 1
        assert "'mystery'" in findings[0].message

    def test_build_stage_hook_is_an_obs_primitive(self, tmp_path):
        """``covering_build.stage(...)`` from outside, and a bare
        ``stage(...)`` inside the module that defines the hook, are span
        sites: declared, and named from the vocabulary."""
        hook = """
            import contextlib
            from pkg.obs import trace

            @contextlib.contextmanager
            def stage(name):
                with trace.span(name) as sp:
                    yield sp

            def build():
                with stage("write"):
                    pass
                with stage("wrte"):
                    pass
        """
        user = """
            from pkg.indexes import covering_build

            def capture():
                with covering_build.stage("sidecar"):
                    pass
        """
        registry = OBS_REGISTRY.replace(
            '"pkg.app.serve": ("span", "roots the query at admission"),',
            '"pkg.app.serve": ("span", "roots the query at admission"),\n'
            '        "pkg.indexes.covering_build.stage": ("span", "the hook"),'
            '\n        "pkg.indexes.covering_build.build": ("span", "stages"),',
        )
        files = {
            "sites.py": registry,
            "app.py": OBS_APP,
            "indexes/__init__.py": "",
            "indexes/covering_build.py": hook,
            "indexes/sidecar.py": user,
        }
        findings = _lint(tmp_path, files)
        undeclared = [f for f in findings if f.rule == "HS901"]
        assert len(undeclared) == 1
        assert "pkg.indexes.sidecar.capture" in undeclared[0].message
        drifted = sorted(
            f.message for f in findings if f.rule == "HS902"
        )
        assert len(drifted) == 2
        assert "'sidecar'" in drifted[0] and "'wrte'" in drifted[1]

    def test_stale_entries_flagged(self, tmp_path):
        stale_registry = """
            KINDS = ("span", "metric", "view")
            SERVE_STAGES = ("scan",)
            ROOT_NAMES = ("serve.query",)
            OBS_SITES = {
                "pkg.app.serve": ("span", "roots the query"),
                "pkg.gone.fn": ("span", "site no longer exists"),
                "pkg.app.serve_other": ("wat", "unknown kind"),
                "pkg.app.quiet": ("span", "declared but never calls"),
                "pkg.app.nowhy": ("span", ""),
            }
        """
        files = {
            "sites.py": stale_registry,
            "app.py": OBS_APP + """
    def serve_other():
        return 1

    def quiet():
        return 2

    def nowhy():
        return 3
""",
        }
        findings = [f for f in _lint(tmp_path, files) if f.rule == "HS903"]
        msgs = "\n".join(f.message for f in findings)
        assert "pkg.gone.fn" in msgs and "does not resolve" in msgs
        assert "unknown kind" in msgs
        assert "no obs primitive call" in msgs
        assert "no justification" in msgs
        assert len(findings) == 4

    def test_real_registry_resolves_and_engages(self):
        """Engagement guard over the real tree: the registry parses,
        every entry resolves and is exercised, and the serve/build
        taxonomies cover the breakdown keys the spans mirror."""
        from hyperspace_tpu.analysis import obs as obs_checker
        from hyperspace_tpu.analysis.core import Project
        from hyperspace_tpu.obs import sites as obs_sites

        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        entries, stages, roots, rel = obs_checker.parse_sites(project)
        assert rel == "obs/sites.py"
        assert len(entries) >= 10
        assert stages == set(obs_sites.STAGE_NAMES)
        assert "serve.query" in roots
        resolvable = obs_checker._resolvable_paths(project)
        for e in entries:
            assert e.path in resolvable, e.path
        calls = obs_checker._scan_calls(project)
        called = {c.site for c in calls}
        # every declared site calls a primitive; every primitive call
        # site is declared (the package-clean gate enforces the same,
        # this asserts the checker actually SEES them)
        assert {e.path for e in entries} <= called
        # the serve breakdown keys all have span vocabulary entries
        for key in ("scan", "prepare", "match", "expand", "verify",
                    "assemble", "delta"):
            assert key in obs_sites.SERVE_STAGES, key
        for key in ("scan", "hash_shuffle", "sort", "write"):
            assert key in obs_sites.BUILD_STAGES, key


# ---------------------------------------------------------------------------
# HS10xx: memory-residency contract (analysis/residency.py)
# ---------------------------------------------------------------------------

RES_REGISTRY = """
    PLANES = ("build", "serve", "maintenance")
    BOUND_CLASSES = (
        "cache-governed",
        "wave-budget",
        "chunk-bounded",
        "row-group-bounded",
        "const-bounded",
    )
    ALLOC_SITES = {
        "pkg.io.reader.load_table": (
            "serve",
            "cache-governed",
            "materialized table is charged into the serve cache",
        ),
        "pkg.execution.scan.stream_chunks": (
            "build",
            "chunk-bounded",
            "reads the file list in fixed-size chunks",
        ),
    }
"""

RES_IO = """
    def read_table(paths):
        return paths

    def load_table(cache, paths):
        t = read_table(paths)
        cache.put("t", t)
        return t
"""

RES_EXEC = """
    from pkg.io.reader import read_table

    def stream_chunks(files):
        out = []
        for start in range(0, len(files), 8):
            out.append(read_table(files[start : start + 8]))
        return out
"""

RES_FILES = {
    "memory.py": RES_REGISTRY,
    "io/reader.py": RES_IO,
    "execution/scan.py": RES_EXEC,
}


def _res(findings):
    return [f for f in findings if f.rule.startswith("HS10")]


class TestResidency:
    def test_clean_tree(self, tmp_path):
        assert _res(_lint(tmp_path, RES_FILES)) == []

    def test_no_registry_skips_checker(self, tmp_path):
        # trees without an ALLOC_SITES registry have no residency
        # contract to lint — even with unbounded hot-path reads
        files = {
            "io/reader.py": RES_IO,
            "io/rogue.py": """
                def hot_read(paths):
                    return read_table(paths)
            """,
        }
        assert _res(_lint(tmp_path, files)) == []

    def test_undeclared_materialization_flagged(self, tmp_path):
        files = dict(RES_FILES)
        files["io/rogue.py"] = """
            def hot_read(paths):
                return read_table(paths)
        """
        findings = [
            f for f in _lint(tmp_path, files) if f.rule == "HS1001"
        ]
        assert len(findings) == 1
        assert "pkg.io.rogue.hot_read" in findings[0].message
        assert "read_table" in findings[0].message

    def test_arrow_materializer_on_tainted_value(self, tmp_path):
        # the read AND the decode of its (relation-sized) result are
        # both row-proportional materializations
        files = dict(RES_FILES)
        files["io/wide.py"] = """
            def widen(files):
                t = read_table(files)
                return t.to_numpy()
        """
        findings = [
            f for f in _lint(tmp_path, files) if f.rule == "HS1001"
        ]
        assert len(findings) == 2
        msgs = "\n".join(f.message for f in findings)
        assert "to_numpy" in msgs

    def test_unbounded_accumulation_flagged(self, tmp_path):
        # an accumulator appended to once per file of the relation is
        # itself relation-proportional; concatenating it materializes
        files = dict(RES_FILES)
        files["execution/gather.py"] = """
            import numpy as np

            def gather(files):
                parts = []
                for f in files:
                    parts.append(decode(f))
                return np.concatenate(parts)
        """
        findings = [
            f for f in _lint(tmp_path, files) if f.rule == "HS1001"
        ]
        assert len(findings) == 1
        assert "concatenate" in findings[0].message

    def test_slice_read_not_flagged(self, tmp_path):
        # the row-group read path is bounded by construction
        files = dict(RES_FILES)
        files["io/rg.py"] = """
            def per_group(paths, sel):
                return read_table_row_groups(paths, sel)
        """
        assert [
            f for f in _lint(tmp_path, files) if f.rule == "HS1001"
        ] == []

    def test_private_helper_outside_closure_not_flagged(self, tmp_path):
        # HS1001 audits the reach closure from the public surface;
        # an uncalled private helper is not on the hot path
        files = dict(RES_FILES)
        files["io/cold.py"] = """
            def _cold(paths):
                return read_table(paths)
        """
        assert [
            f for f in _lint(tmp_path, files) if f.rule == "HS1001"
        ] == []

    def test_cold_dir_not_flagged(self, tmp_path):
        # only execution/ indexes/ io/ serve/ are the hot path
        files = dict(RES_FILES)
        files["tooling.py"] = """
            def offline_read(paths):
                return read_table(paths)
        """
        assert [
            f for f in _lint(tmp_path, files) if f.rule == "HS1001"
        ] == []

    def test_suppression_silences(self, tmp_path):
        files = dict(RES_FILES)
        files["io/rogue.py"] = """
            def hot_read(paths):
                # justified: caller holds one row group at a time
                return read_table(paths)  # hslint: disable=HS1001
        """
        assert [
            f for f in _lint(tmp_path, files) if f.rule == "HS1001"
        ] == []

    def test_cache_governed_without_put_flagged(self, tmp_path):
        files = dict(RES_FILES)
        files["io/reader.py"] = RES_IO.replace('cache.put("t", t)', "pass")
        findings = [
            f for f in _lint(tmp_path, files) if f.rule == "HS1002"
        ]
        assert len(findings) == 1
        assert "pkg.io.reader.load_table" in findings[0].message
        assert "never flows through" in findings[0].message

    def test_chunk_bounded_without_loop_flagged(self, tmp_path):
        files = dict(RES_FILES)
        files["execution/scan.py"] = """
            from pkg.io.reader import read_table

            def stream_chunks(files):
                return read_table(files)
        """
        findings = [
            f for f in _lint(tmp_path, files) if f.rule == "HS1002"
        ]
        assert len(findings) == 1
        assert "no chunk loop" in findings[0].message

    def test_stale_entries_flagged(self, tmp_path):
        stale_registry = """
            ALLOC_SITES = {
                "pkg.io.reader.load_table": (
                    "serve", "cache-governed", "cached"
                ),
                "pkg.gone.fn": (
                    "serve", "cache-governed", "site no longer exists"
                ),
                "pkg.io.reader.read_table": (
                    "orbit", "cache-governed", "unknown plane"
                ),
                "pkg.io.reader.badbound": (
                    "serve", "mystery", "unknown bound class"
                ),
                "pkg.io.reader.nowhy": ("serve", "const-bounded", ""),
                "pkg.io.reader.quiet": (
                    "serve", "const-bounded", "never allocates"
                ),
            }
        """
        files = {
            "memory.py": stale_registry,
            "io/reader.py": RES_IO + """
    def badbound():
        return 1

    def nowhy():
        return 2

    def quiet():
        return 3
""",
        }
        findings = [
            f for f in _lint(tmp_path, files) if f.rule == "HS1003"
        ]
        msgs = "\n".join(f.message for f in findings)
        assert "pkg.gone.fn" in msgs and "does not resolve" in msgs
        assert "unknown plane" in msgs
        assert "unknown bound" in msgs
        assert "no justification" in msgs
        assert "neither allocates" in msgs
        assert len(findings) == 5

    def test_witness_cross_check_unit(self, tmp_path):
        """Model gaps and ceiling breaches from a crafted artifact
        against a fixture registry — the `hslint --witness` core."""
        from hyperspace_tpu.analysis import residency
        from hyperspace_tpu.analysis.core import Project

        pkg = tmp_path / "pkg"
        pkg.mkdir()
        _write_tree(pkg, RES_FILES)
        project = Project(str(pkg))
        doc = {
            "version": 1,
            "sites": {
                "pkg.io.reader.load_table": {
                    "peak_bytes": 150,
                    "calls": 2,
                },
                "ghost.mod.fn": {"peak_bytes": 7, "calls": 1},
            },
            "budgets": {"cache-governed": 100, "chunk-bounded": 50},
        }
        gaps, warnings = residency.witness_cross_check(
            [project], doc, "res.json"
        )
        assert sorted(f.rule for f in gaps) == ["HS1004", "HS1004"]
        msgs = "\n".join(f.message for f in gaps)
        assert "ghost.mod.fn" in msgs and "absent from ALLOC_SITES" in msgs
        assert "ceiling" in msgs and "150" in msgs
        # the never-driven registered site warns, never errors
        assert any("stream_chunks" in w for w in warnings)
        # malformed artifacts raise (the CLI maps this to exit 2)
        with pytest.raises(ValueError):
            residency.load_witness("x.json", doc={"sites": {"a": 3}})
        with pytest.raises(ValueError):
            residency.load_witness("x.json", doc={"version": 1})

    def test_witness_round_trip(self, tmp_path):
        """install → drive a registered site → dump → merge → static
        cross-check: the full runtime loop over the REAL registry."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        from hyperspace_tpu.analysis import residency
        from hyperspace_tpu.analysis.core import Project
        from hyperspace_tpu.testing import residency_witness

        f = tmp_path / "t.parquet"
        pq.write_table(
            pa.table({"a": pa.array(range(1000), type=pa.int64())}),
            str(f),
        )
        art = str(tmp_path / "res.json")
        site = "hyperspace_tpu.io.parquet.read_table"
        residency_witness.reset()
        wrapped = residency_witness.install()
        try:
            from hyperspace_tpu.io import parquet as hp

            hp.read_table([str(f)])
            residency_witness.dump(art)
            residency_witness.reset()
            hp.read_table([str(f)])
            doc = residency_witness.dump(art)  # merges with the first
        finally:
            residency_witness.uninstall()
            residency_witness.reset()
        # every registered site resolves to something wrappable
        assert all(wrapped.values()), [
            s for s, ok in wrapped.items() if not ok
        ]
        rec = doc["sites"][site]
        assert rec["calls"] == 2  # merge sums calls across dumps
        assert rec["peak_bytes"] >= 1000 * 8  # the int64 column
        assert doc["rss_high_water"] > 0
        # budgets are stamped from memory.BOUND_CLASS_CEILINGS
        from hyperspace_tpu import memory

        assert doc["budgets"] == memory.BOUND_CLASS_CEILINGS
        # the artifact round-trips through the static cross-check clean
        loaded = residency.load_witness(art)
        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        gaps, warnings = residency.witness_cross_check(
            [project], loaded, "res.json"
        )
        assert gaps == []
        assert warnings  # sites this run never drove warn as stale

    def test_real_registry_resolves_and_engages(self):
        """Engagement guard over the real tree: the registry parses,
        every entry resolves to an indexed function/method with a live
        allocation, and the declared taxonomy covers all five bound
        classes the witness gates on."""
        from hyperspace_tpu import memory
        from hyperspace_tpu.analysis import residency
        from hyperspace_tpu.analysis.core import Project

        project = Project(PKG_DIR, tests_dir=TESTS_DIR)
        entries, rel = residency.parse_sites(project)
        assert rel == "memory.py"
        assert len(entries) >= 20
        # the parsed (never-imported) registry matches the runtime one
        assert {e.path for e in entries} == set(memory.ALLOC_SITES)
        for e in entries:
            assert e.plane in residency.PLANES, e.path
            assert e.bound in residency.BOUND_CLASSES, e.path
            assert e.why.strip(), e.path
        index = residency.build_index(project)
        by_site = {fn.site for fn in index.values()}
        for e in entries:
            assert e.path in by_site, e.path
        # declared sites are actually on the audited hot path
        closure_sites = {
            index[k].site for k in residency.reach_closure(index)
        }
        assert "hyperspace_tpu.io.parquet.read_table" in closure_sites
        assert (
            "hyperspace_tpu.execution.join_exec.prepare_join_side"
            in closure_sites
        )
        # every bound class is exercised by some declared site, and
        # every class has a witness ceiling
        assert {e.bound for e in entries} == set(residency.BOUND_CLASSES)
        assert set(memory.BOUND_CLASS_CEILINGS) == set(
            residency.BOUND_CLASSES
        )
        assert residency.PLANES == memory.PLANES
        assert residency.BOUND_CLASSES == memory.BOUND_CLASSES


# ---------------------------------------------------------------------------
# Golden: ruleset + finding schema stability
# ---------------------------------------------------------------------------


class TestGolden:
    EXPECTED_RULES = [
        "HS001",
        "HS1001",
        "HS1002",
        "HS1003",
        "HS1004",
        "HS101",
        "HS102",
        "HS103",
        "HS104",
        "HS105",
        "HS201",
        "HS202",
        "HS203",
        "HS204",
        "HS205",
        "HS206",
        "HS301",
        "HS302",
        "HS401",
        "HS402",
        "HS501",
        "HS502",
        "HS601",
        "HS602",
        "HS603",
        "HS604",
        "HS701",
        "HS702",
        "HS703",
        "HS704",
        "HS801",
        "HS802",
        "HS803",
        "HS804",
        "HS901",
        "HS902",
        "HS903",
    ]

    def test_ruleset_is_stable(self):
        assert sorted(ALL_RULES) == self.EXPECTED_RULES
        for rule, desc in ALL_RULES.items():
            assert desc and isinstance(desc, str)

    def test_every_checker_owns_rules(self):
        owned = [r for mod in CHECKERS for r in mod.RULES]
        assert sorted(owned) == self.EXPECTED_RULES[1:]  # HS001 is core's
        assert len(owned) == len(set(owned))

    def test_finding_schema_is_stable(self):
        assert FINDING_FIELDS == ("rule", "path", "line", "message", "suppressed")
        f = Finding("HS999", "pkg/x.py", 3, "msg")
        assert f.to_dict() == {
            "rule": "HS999",
            "path": "pkg/x.py",
            "line": 3,
            "message": "msg",
            "suppressed": False,
        }
        assert f.render() == "pkg/x.py:3: HS999 msg"


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


class TestCli:
    def _run(self, *args):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, "-m", "hyperspace_tpu.analysis", *args],
            capture_output=True,
            text=True,
            env=env,
            cwd=os.path.dirname(PKG_DIR),
            timeout=120,
        )

    def test_exit_nonzero_on_violation(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(
            pkg,
            {
                "m.py": """
                    def f():
                        try:
                            return 1
                        except:
                            return None
                """
            },
        )
        proc = self._run(str(pkg))
        assert proc.returncode == 1
        assert "HS401" in proc.stdout

    def test_exit_zero_on_clean_tree(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"m.py": "def f():\n    return 1\n"})
        proc = self._run(str(pkg))
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_list_rules(self, tmp_path):
        proc = self._run("--list-rules")
        assert proc.returncode == 0
        for rule in TestGolden.EXPECTED_RULES:
            assert rule in proc.stdout

    def test_witness_clean_exits_zero(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"m.py": "def f():\n    return 1\n"})
        wit = tmp_path / "wit.json"
        wit.write_text('{"version": 1, "locks": {}, "edges": []}')
        proc = self._run(str(pkg), "--witness", str(wit))
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_witness_model_gap_exits_one(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"m.py": "def f():\n    return 1\n"})
        wit = tmp_path / "wit.json"
        wit.write_text(
            '{"version": 1, "locks": {"ghost.py::_x": 1}, "edges": []}'
        )
        proc = self._run(str(pkg), "--witness", str(wit))
        assert proc.returncode == 1
        assert "HS604" in proc.stdout

    def test_witness_malformed_exits_two(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"m.py": "def f():\n    return 1\n"})
        wit = tmp_path / "wit.json"
        wit.write_text("{not json")
        proc = self._run(str(pkg), "--witness", str(wit))
        assert proc.returncode == 2
        proc = self._run(str(pkg), "--witness", str(tmp_path / "absent.json"))
        assert proc.returncode == 2

    def test_collective_witness_clean_exits_zero(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"collectives.py": SPMD_REGISTRY, "comm.py": SPMD_COMM})
        seq = [_rec("pkg.comm.exchange")]
        _cw_artifact(tmp_path, 0, seq)
        prefix = _cw_artifact(tmp_path, 1, seq)
        proc = self._run(str(pkg), "--witness", prefix)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_collective_witness_divergence_exits_one(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"collectives.py": SPMD_REGISTRY, "comm.py": SPMD_COMM})
        _cw_artifact(tmp_path, 0, [_rec("pkg.comm.exchange")])
        prefix = _cw_artifact(tmp_path, 1, [])
        proc = self._run(str(pkg), "--witness", prefix)
        assert proc.returncode == 1
        assert "HS804" in proc.stdout

    def test_residency_witness_clean_exits_zero(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, RES_FILES)
        wit = tmp_path / "res.json"
        wit.write_text(
            '{"version": 1, "sites": {"pkg.io.reader.load_table": '
            '{"peak_bytes": 10, "calls": 1}}, '
            '"budgets": {"cache-governed": 100}}'
        )
        proc = self._run(str(pkg), "--witness", str(wit))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        # the never-driven registered site warns on stderr
        assert "never witnessed" in proc.stderr

    def test_residency_witness_model_gap_exits_one(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"m.py": "def f():\n    return 1\n"})
        wit = tmp_path / "res.json"
        wit.write_text(
            '{"version": 1, "sites": {"ghost.mod.fn": '
            '{"peak_bytes": 7, "calls": 1}}}'
        )
        proc = self._run(str(pkg), "--witness", str(wit))
        assert proc.returncode == 1
        assert "HS1004" in proc.stdout

    def test_residency_witness_budget_breach_exits_one(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, RES_FILES)
        wit = tmp_path / "res.json"
        wit.write_text(
            '{"version": 1, "sites": {"pkg.io.reader.load_table": '
            '{"peak_bytes": 101, "calls": 1}}, '
            '"budgets": {"cache-governed": 100}}'
        )
        proc = self._run(str(pkg), "--witness", str(wit))
        assert proc.returncode == 1
        assert "HS1004" in proc.stdout
        assert "ceiling" in proc.stdout

    def test_residency_witness_malformed_exits_two(self, tmp_path):
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"m.py": "def f():\n    return 1\n"})
        wit = tmp_path / "res.json"
        wit.write_text('{"version": 1, "sites": {"x": 3}}')
        proc = self._run(str(pkg), "--witness", str(wit))
        assert proc.returncode == 2

    def test_both_witness_kinds_in_one_run(self, tmp_path):
        # --witness is repeatable: one lock artifact + one residency
        # artifact + one collective family, each dispatched by content
        pkg = tmp_path / "pkg"
        _write_tree(pkg, {"collectives.py": SPMD_REGISTRY, "comm.py": SPMD_COMM})
        lock_wit = tmp_path / "locks.json"
        lock_wit.write_text('{"version": 1, "locks": {}, "edges": []}')
        res_wit = tmp_path / "res.json"
        res_wit.write_text('{"version": 1, "sites": {}}')
        seq = [_rec("pkg.comm.exchange")]
        _cw_artifact(tmp_path, 0, seq)
        prefix = _cw_artifact(tmp_path, 1, seq)
        proc = self._run(
            str(pkg),
            "--witness",
            str(lock_wit),
            "--witness",
            str(res_wit),
            "--witness",
            prefix,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
