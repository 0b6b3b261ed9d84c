"""graftlint: the repo-specific static-analysis gate (ISSUE 6).

Covers: each rule against its golden fixtures (positive / negative /
suppressed), suppression comment forms, the ratchet baseline, the
CLI (`python -m tools.graftlint`: formats, --rule, --stats,
--write-baseline, exit codes), --changed-only git scoping, the GL005
port of tools/check_perf_claims.py plus its deprecation shim, and
the SELF-CHECK: the analyzer runs clean on the committed tree modulo
the committed baseline — introducing any golden-fixture violation
into the package fails CI.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "graftlint")

sys.path.insert(0, REPO)

from tools.graftlint import (ALL_RULES, Baseline, run_lint)  # noqa: E402
from tools.graftlint.core import Finding, Suppressions  # noqa: E402


def lint_fixture(name, rules=None):
    return run_lint(REPO, paths=[os.path.join(FIXTURES, name)],
                    rules=rules)


# ---------------------------------------------------------------------------
# per-rule golden fixtures
# ---------------------------------------------------------------------------

class TestGL001JitPurity:
    def test_positive(self):
        r = lint_fixture("gl001_positive.py", ["GL001"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 7, "\n".join(msgs)
        for needle in ("time.time", "random.random", "print()",
                       "logger.info", "metrics_registry.inc",
                       "time.sleep", "nonlocal"):
            assert any(needle in m for m in msgs), needle
        # the scan body reached through lax.scan, the alias-resolved
        # nonlocal through jax.jit(body) + local helper
        syms = {f.symbol for f in r.new}
        assert "plain_body" in syms and "bump" in syms

    def test_negative(self):
        assert lint_fixture("gl001_negative.py", ["GL001"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl001_suppressed.py", ["GL001"])
        assert r.new == [] and r.suppressed == 2


class TestGL002Recompile:
    def test_positive(self):
        r = lint_fixture("gl002_positive.py", ["GL002"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 5, "\n".join(msgs)
        for needle in ("Python `if` on traced value 'x'",
                       "shape-derived value passed as static arg",
                       "f-string passed as static arg",
                       "evaluated inside a loop",
                       "keyed on a raw .shape"):
            assert any(needle in m for m in msgs), needle

    def test_negative(self):
        assert lint_fixture("gl002_negative.py", ["GL002"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl002_suppressed.py", ["GL002"])
        assert r.new == [] and r.suppressed == 1


class TestGL003Donation:
    def test_positive(self):
        r = lint_fixture("gl003_positive.py", ["GL003"])
        assert len(r.new) == 3, [f.render() for f in r.new]
        names = sorted(f.message.split("'")[1] for f in r.new)
        assert names == ["opt_state", "params", "params"]
        # the conditional use is a may-use: still flagged
        assert any(f.symbol == "bad_conditional" for f in r.new)

    def test_negative(self):
        assert lint_fixture("gl003_negative.py", ["GL003"]).new == []

    def test_augassign_is_a_use(self, tmp_path):
        # `params += g` after donating params reads the dead buffer:
        # the Store-ctx target must still count as a use
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            "import jax\n\n"
            "def f(params, g):\n"
            "    step = jax.jit(lambda p, q: p + q,"
            " donate_argnums=(0,))\n"
            "    out = step(params, g)\n"
            "    params += g\n"
            "    return out, params\n")
        r = run_lint(str(tmp_path), rules=["GL003"])
        assert len(r.new) == 1 and "'params'" in r.new[0].message

    def test_key_is_line_independent(self, tmp_path):
        # shifting the donating call down one line must not change
        # the finding's baseline identity (core.py contract)
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        src = ("import jax\n{pad}\n"
               "def f(params, g):\n"
               "    step = jax.jit(lambda p, q: p + q,"
               " donate_argnums=(0,))\n"
               "    out = step(params, g)\n"
               "    bad = params\n"
               "    return out, bad\n")
        (pkg / "m.py").write_text(src.format(pad=""))
        k1 = run_lint(str(tmp_path), rules=["GL003"]).new[0].key
        (pkg / "m.py").write_text(src.format(pad="import os\n"))
        k2 = run_lint(str(tmp_path), rules=["GL003"]).new[0].key
        assert k1 == k2

    def test_donate_in_loop_without_rebind(self, tmp_path):
        # the canonical fit-loop violation: iteration 2 passes the
        # buffer iteration 1 already donated — caught by the symbolic
        # second pass over loop bodies
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import jax

            step = jax.jit(lambda p, b: p + b, donate_argnums=(0,))

            def fit(params, batches):
                outs = []
                for b in batches:
                    outs.append(step(params, b))
                return outs
            """))
        r = run_lint(str(tmp_path), rules=["GL003"])
        assert len(r.new) == 1 and "'params'" in r.new[0].message

    def test_loop_rebind_idiom_is_clean(self, tmp_path):
        # x = step(x, ...) inside the loop clears the poison before
        # the next iteration — and a fresh per-iteration binding
        # before the donating call must not false-positive either
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import jax

            step = jax.jit(lambda p, b: p + b, donate_argnums=(0,))

            def fit(params, batches):
                for b in batches:
                    params = step(params, b)
                return params

            def fit2(base, batches):
                for b in batches:
                    p = base + 0
                    r = step(p, b)
                return r
            """))
        assert run_lint(str(tmp_path), rules=["GL003"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl003_suppressed.py", ["GL003"])
        assert r.new == [] and r.suppressed == 1


class TestGL004Locks:
    def test_positive(self):
        r = lint_fixture("gl004_positive.py", ["GL004"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 5, "\n".join(msgs)
        assert sum("inconsistent lock order" in m for m in msgs) == 2
        assert any("re-acquired while already held" in m
                   for m in msgs)
        assert any("written without its lock" in m for m in msgs)
        assert any("check-then-act" in m for m in msgs)

    def test_negative(self):
        # locked-helper fixpoint, RLock re-entry, __init__ writes and
        # guarded check-then-act must all pass
        assert lint_fixture("gl004_negative.py", ["GL004"]).new == []

    def test_write_in_thread_target_closure_is_unlocked(self,
                                                        tmp_path):
        # a closure defined under `with self._lock:` runs LATER, on
        # the spawned thread, with no lock held — the lexical parent
        # walk must stop at the def boundary (this is where the
        # repo's actual unlocked writes live: worker loops)
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import threading

            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def start(self):
                    with self._lock:
                        def loop():
                            self._n = self._n + 1
                        threading.Thread(target=loop).start()

                def bump(self):
                    with self._lock:
                        self._n = self._n + 1
            """))
        r = run_lint(str(tmp_path), rules=["GL004"])
        assert len(r.new) == 1, [f.render() for f in r.new]
        assert "written without its lock" in r.new[0].message

    def test_lock_taken_inside_closure_counts(self, tmp_path):
        # the converse: a closure that takes the lock around its own
        # write is properly held — no finding
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import threading

            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def start(self):
                    def loop():
                        with self._lock:
                            self._n = self._n + 1
                    threading.Thread(target=loop).start()

                def bump(self):
                    with self._lock:
                        self._n = self._n + 1
            """))
        assert run_lint(str(tmp_path), rules=["GL004"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl004_suppressed.py", ["GL004"])
        assert r.new == [] and r.suppressed == 1

    def test_cross_file_order_inversion(self):
        # module B imports module A's locks and nests them in the
        # opposite order: the acquisition graph must unify the
        # imported names with their defining module's identities
        r = lint_fixture("gl004_crossfile", ["GL004"])
        assert len(r.new) == 2, [f.render() for f in r.new]
        paths = {f.path for f in r.new}
        assert any(p.endswith("locks_a.py") for p in paths)
        assert any(p.endswith("locks_b.py") for p in paths)
        assert all("inconsistent lock order" in f.message
                   for f in r.new)

    def test_each_crossfile_module_alone_is_clean(self):
        # one consistent order per module: only the UNION deadlocks
        for name in ("gl004_crossfile/locks_a.py",
                     "gl004_crossfile/locks_b.py"):
            assert lint_fixture(name, ["GL004"]).new == [], name


class TestGL005LiteralDrift:
    def _fake_repo(self, tmp_path, readme, bench=None, pkg_src=None):
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(pkg_src or (
            'C = registry.counter("foo_requests_total")\n'
            'G = metrics.register_gauge(f"{name}_queue_depth", fn)\n'
            'SITE = "checkpoint.write"\n'))
        (tmp_path / "BENCH_DETAIL.json").write_text(
            json.dumps(bench if bench is not None else {}))
        (tmp_path / "README.md").write_text(readme)
        return str(tmp_path)

    def test_positive_all_three_drifts(self, tmp_path):
        repo = self._fake_repo(
            tmp_path,
            "ours is 9.7x faster\n"
            "alert on `bar_bogus_total`\n"
            "# Fault injection\n"
            "site `data.bogus` can crash\n",
            bench={"configs": [{"value": 1.0, "unit": "u",
                                "vs_baseline": 1.3}]})
        r = run_lint(repo, paths=[], rules=["GL005"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 3, "\n".join(msgs)
        assert any("9.7x" in m for m in msgs)
        assert any("bar_bogus_total" in m for m in msgs)
        assert any("data.bogus" in m for m in msgs)

    def test_negative(self, tmp_path):
        repo = self._fake_repo(
            tmp_path,
            "measured 1.3x vs baseline\n"
            "derived 2.0x between configs\n"
            "goal (target: 0.7x) is exempt\n"
            "alert on `foo_requests_total` and "
            "`predict_v1_queue_depth`\n"
            "# Fault injection\n"
            "site `checkpoint.write` can fail\n",
            bench={"configs": [{"value": 200.0, "unit": "u",
                                "vs_baseline": 1.31},
                               {"value": 100.0, "unit": "u"}]})
        assert run_lint(repo, paths=[], rules=["GL005"]).new == []

    def test_missing_artifact_means_nothing_was_measured(self,
                                                         tmp_path):
        # no BENCH_DETAIL.json: every multiplier is a finding (both
        # through the rule and through the legacy check()), targets
        # stay exempt, and the other sub-checks still run
        from tools.graftlint.rules import gl005_literal_drift as gl5
        repo = self._fake_repo(
            tmp_path,
            "measured 1.3x vs baseline\n"
            "derived 2.0x between configs\n"
            "goal (target: 0.7x) is exempt\n"
            "alert on `foo_requests_total`\n")
        os.remove(os.path.join(repo, "BENCH_DETAIL.json"))
        r = run_lint(repo, paths=[], rules=["GL005"])
        assert sorted(f.line for f in r.new) == [1, 2]
        assert len(gl5.check(repo)) == 2

    def test_suppressed_markdown_comment(self, tmp_path):
        repo = self._fake_repo(
            tmp_path,
            "<!-- graftlint: disable=GL005 -->\n"
            "ours is 9.7x faster\n")
        r = run_lint(repo, paths=[], rules=["GL005"])
        assert r.new == [] and r.suppressed == 1

    def test_legacy_string_api(self, tmp_path):
        from tools.graftlint.rules import gl005_literal_drift as gl5
        repo = self._fake_repo(
            tmp_path, "alert on the renamed `bar_bogus_total`.\n")
        errors = gl5.check_metric_names(repo)
        assert len(errors) == 1 and "bar_bogus_total" in errors[0]
        assert errors[0].startswith("README.md:1:")

    def test_fleet_prefix_cited_but_unregistered(self, tmp_path):
        # fleet_* gauges don't all carry a typed suffix
        # (fleet_targets_up), so the prefix family alone must pull a
        # doc token into the must-exist check
        repo = self._fake_repo(
            tmp_path, "watch `fleet_targets_up` on the collector\n")
        r = run_lint(repo, paths=[], rules=["GL005"])
        assert len(r.new) == 1
        assert "fleet_targets_up" in r.new[0].message

    def test_fleet_prefix_registered_is_clean(self, tmp_path):
        repo = self._fake_repo(
            tmp_path,
            "watch `fleet_targets_up` and `fleet_scrapes_total`\n",
            pkg_src=(
                'U = registry.gauge("fleet_targets_up", fn)\n'
                'C = registry.counter("fleet_scrapes_total")\n'
                'SITE = "checkpoint.write"\n'))
        assert run_lint(repo, paths=[], rules=["GL005"]).new == []


class TestGL006MetricsHygiene:
    def test_positive(self):
        r = lint_fixture("gl006_positive.py", ["GL006"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 6, "\n".join(msgs)
        for needle in ("label key 'trace_id'",
                       "label key 'request_id'",
                       "label value reads 'trace_id'",
                       "label value reads 'request_id'",
                       "registry.counter() inside a loop",
                       "registry.histogram() inside a loop"):
            assert any(needle in m for m in msgs), needle
        syms = {f.symbol for f in r.new}
        assert "creates_counter_per_event" in syms
        assert "discards_in_loop" in syms

    def test_negative(self):
        # bounded labels, import-time creation, the loop-stored
        # cache-fill pattern, exemplars, and a non-metric `labels=`
        # kwarg all stay clean
        assert lint_fixture("gl006_negative.py", ["GL006"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl006_suppressed.py", ["GL006"])
        assert r.new == [] and r.suppressed == 2

    def test_package_tree_is_clean(self):
        # the serving/observability stack itself obeys the rule it
        # ships with: trace ids ride exemplars, never labels
        r = run_lint(REPO, rules=["GL006"])
        assert r.new == [], "\n".join(f.render() for f in r.new)


class TestGL007ThreadLifecycle:
    def test_positive(self):
        r = lint_fixture("gl007_positive.py", ["GL007"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 3, "\n".join(msgs)
        assert any("never joined" in m for m in msgs)
        assert any("FRESH Event per generation" in m for m in msgs)
        assert any("started anonymously" in m for m in msgs)
        syms = {f.symbol for f in r.new}
        assert "LeakyServer._thread" in syms
        assert "LeakyServer._stop" in syms

    def test_negative(self):
        # swap-idiom join, per-generation events, __init__+close
        # threads and locally-joined threads all stay clean
        assert lint_fixture("gl007_negative.py", ["GL007"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl007_suppressed.py", ["GL007"])
        assert r.new == [] and r.suppressed == 1

    def test_unrelated_local_start_does_not_mark_attr(self,
                                                      tmp_path):
        # a never-started attribute thread next to an unrelated
        # (started AND joined) local thread must not be flagged:
        # start credit flows only through the local actually stored
        # to the attribute
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import threading


            class C:
                def go(self):
                    self._maybe = threading.Thread(target=self.run)
                    t = threading.Thread(target=self.run)
                    t.start()
                    t.join(timeout=1.0)

                def run(self):
                    pass
            """))
        r = run_lint(str(tmp_path), rules=["GL007"])
        assert r.new == [], [f.render() for f in r.new]

    def test_local_alias_start_and_join_credit_their_attr(
            self, tmp_path):
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        # started via the local alias, never joined -> one finding
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import threading


            class Leaky:
                def start(self):
                    t = threading.Thread(target=self.run)
                    t.start()
                    self._w = t

                def run(self):
                    pass


            class Clean:
                def start(self):
                    t = threading.Thread(target=self.run)
                    t.start()
                    self._w = t
                    t.join(timeout=1.0)

                def run(self):
                    pass
            """))
        r = run_lint(str(tmp_path), rules=["GL007"])
        assert len(r.new) == 1, [f.render() for f in r.new]
        assert r.new[0].symbol == "Leaky._w"


class TestGL008DeadlineDiscipline:
    def test_positive(self):
        r = lint_fixture("gl008_positive.py", ["GL008"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 4, "\n".join(msgs)
        for needle in ("queue.get", "HTTPConnection",
                       "lock.acquire", "`wait`"):
            assert any(needle in m for m in msgs), needle
        # both root kinds are named
        assert any("HTTP handler" in m for m in msgs)
        assert any("worker loop" in m for m in msgs)

    def test_interprocedural_two_calls_deep(self):
        # THE acceptance fixture: the bare queue.get() sits two
        # resolved calls below do_POST and is still flagged there
        r = lint_fixture("gl008_positive.py", ["GL008"])
        deep = [f for f in r.new if f.symbol == "MiniServer._dequeue_one"]
        assert len(deep) == 1
        assert "reachable from HTTP handler" in deep[0].message

    def test_negative_includes_unreachable_twin(self):
        # same blocking shapes with deadlines — and the IDENTICAL
        # bare get() in offline_drain(), which no handler or worker
        # reaches, stays silent
        assert lint_fixture("gl008_negative.py", ["GL008"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl008_suppressed.py", ["GL008"])
        assert r.new == [] and r.suppressed == 1


class TestInterproceduralResolution:
    """Call-graph engine behaviors the serving-stack findings relied
    on: annotated-return typing and base-to-subclass dispatch."""

    def test_annotated_return_types_local(self, tmp_path):
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import queue
            from typing import Tuple


            class Backend:
                def __init__(self):
                    self._q = queue.Queue()

                def pull(self):
                    return self._q.get()


            class Front:
                def backend_for(self, name) -> Tuple[Backend, int]:
                    return Backend(), 1

                def _handle_predict(self, body):
                    b, v = self.backend_for(body["model"])
                    return b.pull()
            """))
        r = run_lint(str(tmp_path), rules=["GL008"])
        assert len(r.new) == 1, [f.render() for f in r.new]
        assert r.new[0].symbol == "Backend.pull"

    def test_base_run_reaches_subclass_loop(self, tmp_path):
        # Thread(target=self._run) on the BASE class must make the
        # SUBCLASS _loop override a worker root too
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import queue
            import threading


            class Base:
                def __init__(self):
                    self._q = queue.Queue()
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    self._loop()

                def _loop(self):
                    raise NotImplementedError

                def close(self):
                    self._t.join(timeout=1.0)


            class Impl(Base):
                def _loop(self):
                    while True:
                        self._q.get()
            """))
        r = run_lint(str(tmp_path), rules=["GL008"])
        assert len(r.new) == 1, [f.render() for f in r.new]
        assert r.new[0].symbol == "Impl._loop"

    def test_no_handler_no_worker_no_finding(self, tmp_path):
        pkg = tmp_path / "deeplearning4j_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(textwrap.dedent("""\
            import queue


            class Offline:
                def __init__(self):
                    self._q = queue.Queue()

                def drain(self):
                    return self._q.get()
            """))
        assert run_lint(str(tmp_path), rules=["GL008"]).new == []


class TestGL009ResourcePairing:
    def test_positive(self):
        r = lint_fixture("gl009_positive.py", ["GL009"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 4, "\n".join(msgs)
        assert any("never unregisters" in m for m in msgs)
        assert any("server_close" in m for m in msgs)
        assert any("acquired inline" in m for m in msgs)
        assert any("never close()d" in m for m in msgs)

    def test_negative(self):
        # paired skeletons, labeled-constant pairs, server_close,
        # with/finally idioms and ownership handoff all stay clean
        assert lint_fixture("gl009_negative.py", ["GL009"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl009_suppressed.py", ["GL009"])
        assert r.new == [] and r.suppressed == 1


class TestGL010ErrorContract:
    def test_positive(self):
        r = lint_fixture("gl010_positive.py", ["GL010"])
        msgs = [f.message for f in r.new]
        assert len(r.new) == 2, "\n".join(msgs)
        assert any("without retry_after_s" in m for m in msgs)
        assert any("README failure matrix" in m for m in msgs)
        # the matrix half names both the wrong and the documented code
        matrix = next(m for m in msgs if "failure matrix" in m)
        assert "500" in matrix and "429" in matrix

    def test_negative(self):
        # priced admission errors, the documented mapping, plain
        # client errors, and non-handler-reachable raises stay clean
        assert lint_fixture("gl010_negative.py", ["GL010"]).new == []

    def test_suppressed(self):
        r = lint_fixture("gl010_suppressed.py", ["GL010"])
        assert r.new == [] and r.suppressed == 1


class TestGL011ChaosCoverage:
    def _lint(self, name):
        return run_lint(os.path.join(FIXTURES, name),
                        paths=["deeplearning4j_tpu"],
                        rules=["GL011"])

    def test_positive_three_way(self):
        r = self._lint("gl011_positive")
        msgs = [f.message for f in r.new]
        assert len(r.new) == 4, "\n".join(msgs)
        assert any("never threaded" in m for m in msgs)
        assert any("SITES does not declare" in m for m in msgs)
        assert any("missing from the README" in m for m in msgs)
        assert any("silent no-op" in m for m in msgs)
        syms = {f.symbol for f in r.new}
        assert {"fixture.unthreaded", "fixture.typo",
                "fixture.undocumented",
                "fixture.undocumented/ghost"} == syms

    def test_net_positive_three_way(self):
        # netproxy drift: each of the four net checks fires once
        r = self._lint("gl011_net_positive")
        msgs = {f.symbol: f.message for f in r.new}
        assert len(r.new) == 4, "\n".join(msgs.values())
        assert {"ghostkind", "reset", "vanish",
                "net.ghost"} == set(msgs)
        assert "silent no-op" in msgs["ghostkind"]
        assert ("missing from the README network-fault kind table"
                in msgs["reset"])
        assert "fails to parse" in msgs["vanish"]
        assert ("missing from the README network fault-injection "
                "docs" in msgs["net.ghost"])
        # the documented-but-undeclared finding points at the table
        # row, not at line 0
        vanish = next(f for f in r.new if f.symbol == "vanish")
        assert vanish.path == "README.md" and vanish.line > 0

    def test_negative(self):
        # negative tree includes a fully consistent netproxy too
        assert self._lint("gl011_negative").new == []

    def test_suppressed(self):
        r = self._lint("gl011_suppressed")
        assert r.new == [] and r.suppressed == 1

    def test_real_tree_is_covered(self):
        # the committed injector/call-sites/README agree three-way
        r = run_lint(REPO, rules=["GL011"])
        assert r.new == [], "\n".join(f.render() for f in r.new)


class TestCheckPerfClaimsShim:
    """The deprecated tools/check_perf_claims.py keeps its API."""

    def _mod(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import check_perf_claims
        finally:
            sys.path.pop(0)
        return check_perf_claims

    def test_module_api_preserved(self):
        mod = self._mod()
        for name in ("check", "check_metric_names",
                     "check_site_names", "measured_numbers",
                     "claim_matches", "find_claims", "main"):
            assert callable(getattr(mod, name)), name

    def test_committed_docs_pass_via_shim(self):
        mod = self._mod()
        assert mod.check(REPO) == []

    def test_cli_still_works(self):
        p = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "check_perf_claims.py")],
            capture_output=True, text=True, cwd=REPO)
        assert p.returncode == 0, p.stderr
        assert "deprecated" in p.stderr


# ---------------------------------------------------------------------------
# framework: suppressions, baseline, report
# ---------------------------------------------------------------------------

class TestSuppressions:
    def test_forms(self):
        s = Suppressions(textwrap.dedent("""\
            x = 1  # graftlint: disable=GL001
            # graftlint: disable=GL002,GL003
            y = 2
            z = 3
        """))
        assert s.suppressed("GL001", 1)
        assert s.suppressed("GL002", 3) and s.suppressed("GL003", 3)
        assert not s.suppressed("GL002", 4)
        assert not s.suppressed("GL001", 3)

    def test_file_level_and_all(self):
        s = Suppressions("# graftlint: disable-file=GL004\n"
                         "a = 1  # graftlint: disable=all\n")
        assert s.suppressed("GL004", 999)
        assert s.suppressed("GL001", 2)
        assert not s.suppressed("GL001", 3)


class TestBaseline:
    def _finding(self, msg="m", path="p.py", rule="GL001"):
        return Finding(rule=rule, path=path, line=3, message=msg)

    def test_ratchet_absorbs_up_to_count(self):
        f = self._finding()
        base = Baseline({f.key: {"count": 1, "why": "legacy"}})
        new, old = base.split([f, f])
        assert len(old) == 1 and len(new) == 1

    def test_key_ignores_line(self):
        a = Finding(rule="GL001", path="p.py", line=3, message="m")
        b = Finding(rule="GL001", path="p.py", line=99, message="m")
        assert a.key == b.key

    def test_roundtrip_preserves_why(self, tmp_path):
        f = self._finding()
        base = Baseline({f.key: {"count": 1, "why": "kept: reason"}})
        path = str(tmp_path / "b.json")
        base.save(path)
        loaded = Baseline.load(path)
        assert loaded.entries[f.key]["why"] == "kept: reason"
        rewritten = Baseline.from_findings([f], previous=loaded)
        assert rewritten.entries[f.key]["why"] == "kept: reason"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *args],
        capture_output=True, text=True, cwd=cwd)


class TestCLI:
    def test_violation_fails_json(self):
        p = run_cli(os.path.join(FIXTURES, "gl001_positive.py"),
                    "--no-baseline", "--format", "json")
        assert p.returncode == 1
        data = json.loads(p.stdout)
        assert not data["ok"] and len(data["new"]) == 7
        assert all(f["rule"] == "GL001" for f in data["new"])

    def test_rule_selection(self):
        p = run_cli(os.path.join(FIXTURES, "gl001_positive.py"),
                    "--no-baseline", "--rule", "GL002,GL003")
        assert p.returncode == 0, p.stdout

    def test_unknown_rule_is_usage_error(self):
        p = run_cli("--rule", "GL999")
        assert p.returncode == 2 and "GL999" in p.stderr

    def test_nonexistent_path_is_usage_error(self):
        # a typo'd path must NOT lint nothing and exit 0
        p = run_cli("deeplearning4j_tpu/servng")
        assert p.returncode == 2 and "does not exist" in p.stderr

    def test_explicit_non_py_file_is_usage_error(self, tmp_path):
        # same contract for an EXISTING file that would silently be
        # excluded by the .py filter (e.g. an extensionless typo)
        f = tmp_path / "cli"
        f.write_text("x = 1\n")
        p = run_cli(str(f))
        assert p.returncode == 2 and "not a .py file" in p.stderr

    def test_package_runs_clean_against_committed_baseline(self):
        # THE SELF-CHECK: the committed tree + committed baseline =
        # exit 0. A new violation anywhere under deeplearning4j_tpu/
        # flips this to exit 1.
        p = run_cli()
        assert p.returncode == 0, p.stdout + p.stderr

    def test_examples_and_chip_smoke_clean_too(self):
        p = run_cli("examples", "chip_smoke.py", "--no-baseline")
        assert p.returncode == 0, p.stdout

    def test_stats_report(self):
        p = run_cli("--stats")
        assert p.returncode == 0, p.stdout
        for rid in ALL_RULES:
            assert rid in p.stdout
        assert "baselined" in p.stdout

    def test_write_baseline_then_clean(self, tmp_path):
        bpath = str(tmp_path / "base.json")
        fixture = os.path.join(FIXTURES, "gl004_positive.py")
        p = run_cli(fixture, "--baseline", bpath,
                    "--write-baseline")
        assert p.returncode == 0, p.stderr
        # now the same findings are absorbed...
        p2 = run_cli(fixture, "--baseline", bpath)
        assert p2.returncode == 0, p2.stdout
        # ...but a second copy of one finding would be NEW
        base = Baseline.load(bpath)
        assert sum(e["count"] for e in base.entries.values()) == 5

    def test_module_main_importable(self):
        # `python -m tools.graftlint` path bootstrap must not depend
        # on cwd being the repo root
        p = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "--stats"],
            capture_output=True, text=True, cwd=REPO)
        assert p.returncode == 0


class TestChangedOnly:
    def _git(self, cwd, *args):
        return subprocess.run(["git", *args], cwd=cwd,
                              capture_output=True, text=True)

    def test_scopes_to_changed_files(self, tmp_path):
        repo = tmp_path / "r"
        pkg = repo / "deeplearning4j_tpu"
        pkg.mkdir(parents=True)
        clean = ("import jax\n\n"
                 "@jax.jit\n"
                 "def ok(x):\n"
                 "    return x\n")
        dirty = ("import time\n"
                 "import jax\n\n"
                 "@jax.jit\n"
                 "def bad(x):\n"
                 "    time.time()\n"
                 "    return x\n")
        (pkg / "committed_bad.py").write_text(dirty)
        (pkg / "other.py").write_text(clean)
        self._git(repo, "init", "-q")
        self._git(repo, "-c", "user.email=t@t", "-c", "user.name=t",
                  "add", "-A")
        self._git(repo, "-c", "user.email=t@t", "-c", "user.name=t",
                  "commit", "-qm", "seed")
        # untouched tree: --changed-only lints nothing -> clean even
        # though committed_bad.py contains a violation
        r = run_lint(str(repo), rules=["GL001"], changed_only=True)
        assert r.new == [] and r.files_checked == 0
        # touch a NEW bad file: only it is linted
        (pkg / "fresh_bad.py").write_text(dirty)
        r = run_lint(str(repo), rules=["GL001"], changed_only=True)
        assert r.files_checked == 1
        assert len(r.new) == 1
        assert r.new[0].path.endswith("fresh_bad.py")
        # a changed path CONTAINING A SPACE must still be matched
        # (git prints one path per line; whitespace-splitting the
        # output used to fragment it and silently skip the file)
        (pkg / "fresh_bad.py").unlink()
        (pkg / "my module.py").write_text(dirty)
        r = run_lint(str(repo), rules=["GL001"], changed_only=True)
        assert r.files_checked == 1
        assert len(r.new) == 1
        assert r.new[0].path.endswith("my module.py")

    def test_repo_rule_sees_unchanged_files_for_context(self,
                                                        tmp_path):
        # a NEW module inverting a lock order established by an
        # UNCHANGED committed module must fail under --changed-only:
        # the acquisition graph needs the full tree even when
        # reporting is scoped to the change set
        repo = tmp_path / "r"
        pkg = repo / "deeplearning4j_tpu"
        pkg.mkdir(parents=True)
        (pkg / "a.py").write_text(textwrap.dedent("""\
            import threading

            L1 = threading.Lock()
            L2 = threading.Lock()

            def fwd():
                with L1:
                    with L2:
                        pass
            """))
        self._git(repo, "init", "-q")
        self._git(repo, "-c", "user.email=t@t", "-c", "user.name=t",
                  "add", "-A")
        self._git(repo, "-c", "user.email=t@t", "-c", "user.name=t",
                  "commit", "-qm", "seed")
        (pkg / "b.py").write_text(textwrap.dedent("""\
            from deeplearning4j_tpu.a import L1, L2

            def rev():
                with L2:
                    with L1:
                        pass
            """))
        r = run_lint(str(repo), rules=["GL004"], changed_only=True)
        assert len(r.new) == 1, [f.render() for f in r.new]
        # reported at the CHANGED site only — a.py's half of the
        # inversion is pre-existing
        assert r.new[0].path.endswith("b.py")
        assert "inconsistent lock order" in r.new[0].message


class TestChangedOnlyDeleted:
    """ISSUE 14 satellite: --changed-only must skip files the change
    deleted or renamed away instead of erroring, while triggered
    repo-scope rules still see the full tree."""

    def _git(self, cwd, *args):
        return subprocess.run(["git", *args], cwd=cwd,
                              capture_output=True, text=True)

    def _seed(self, tmp_path, files):
        repo = tmp_path / "r"
        pkg = repo / "deeplearning4j_tpu"
        pkg.mkdir(parents=True)
        for name, content in files.items():
            (pkg / name).write_text(content)
        self._git(repo, "init", "-q")
        self._git(repo, "-c", "user.email=t@t", "-c", "user.name=t",
                  "add", "-A")
        self._git(repo, "-c", "user.email=t@t", "-c", "user.name=t",
                  "commit", "-qm", "seed")
        return repo, pkg

    CLEAN = ("import jax\n\n"
             "@jax.jit\n"
             "def ok(x):\n"
             "    return x\n")
    DIRTY = ("import time\n"
             "import jax\n\n"
             "@jax.jit\n"
             "def bad(x):\n"
             "    time.time()\n"
             "    return x\n")

    def test_deleted_file_is_skipped(self, tmp_path):
        repo, pkg = self._seed(tmp_path, {"a.py": self.CLEAN,
                                          "b.py": self.CLEAN})
        (pkg / "b.py").unlink()
        r = run_lint(str(repo), rules=["GL001"], changed_only=True)
        assert r.new == [] and r.files_checked == 0
        # an EXPLICIT path naming the deleted file (what a hook
        # feeding `git diff --name-only` through xargs produces)
        # must be skipped too, not fatal
        r = run_lint(str(repo), paths=["deeplearning4j_tpu/b.py"],
                     rules=["GL001"], changed_only=True)
        assert r.new == [] and r.files_checked == 0
        # ...while outside --changed-only a missing path stays an
        # invocation error
        with pytest.raises(ValueError):
            run_lint(str(repo), paths=["deeplearning4j_tpu/b.py"],
                     rules=["GL001"])

    def test_rename_lints_new_path_only(self, tmp_path):
        repo, pkg = self._seed(tmp_path, {"a.py": self.DIRTY})
        self._git(repo, "mv", "deeplearning4j_tpu/a.py",
                  "deeplearning4j_tpu/b.py")
        r = run_lint(str(repo), rules=["GL001"], changed_only=True)
        assert r.files_checked == 1
        assert len(r.new) == 1
        assert r.new[0].path.endswith("b.py")

    def test_repo_rules_still_fed_full_tree_after_delete(self,
                                                         tmp_path):
        # deleting one file must not stop a triggered repo-scope
        # rule from seeing the UNCHANGED half of the tree
        repo, pkg = self._seed(tmp_path, {
            "a.py": ("import threading\n\n"
                     "L1 = threading.Lock()\n"
                     "L2 = threading.Lock()\n\n"
                     "def fwd():\n"
                     "    with L1:\n"
                     "        with L2:\n"
                     "            pass\n"),
            "gone.py": self.CLEAN})
        (pkg / "gone.py").unlink()
        (pkg / "b.py").write_text(
            "from deeplearning4j_tpu.a import L1, L2\n\n"
            "def rev():\n"
            "    with L2:\n"
            "        with L1:\n"
            "            pass\n")
        r = run_lint(str(repo), rules=["GL004"], changed_only=True)
        assert len(r.new) == 1, [f.render() for f in r.new]
        assert r.new[0].path.endswith("b.py")


class TestJobsAndCache:
    """ISSUE 14 satellite: --jobs N parallel per-file analysis and
    the content-hash result cache agree with the serial path."""

    def test_jobs_matches_serial(self):
        kw = dict(paths=[FIXTURES], rules=["GL001", "GL007"])
        serial = run_lint(REPO, **kw)
        par = run_lint(REPO, jobs=2, **kw)
        assert ([f.key for f in par.new]
                == [f.key for f in serial.new])
        assert par.suppressed == serial.suppressed
        assert par.files_checked == serial.files_checked

    def test_cache_roundtrip_and_invalidation(self, tmp_path):
        repo = tmp_path / "r"
        pkg = repo / "deeplearning4j_tpu"
        pkg.mkdir(parents=True)
        (pkg / "m.py").write_text(TestChangedOnlyDeleted.DIRTY)
        cache = str(repo / "cache.json")
        r1 = run_lint(str(repo), rules=["GL001"], cache_path=cache)
        assert (r1.cache_hits, r1.cache_misses) == (0, 1)
        assert len(r1.new) == 1
        r2 = run_lint(str(repo), rules=["GL001"], cache_path=cache)
        assert (r2.cache_hits, r2.cache_misses) == (1, 0)
        assert [f.key for f in r2.new] == [f.key for f in r1.new]
        # a content edit invalidates exactly that file
        (pkg / "m.py").write_text(TestChangedOnlyDeleted.CLEAN)
        r3 = run_lint(str(repo), rules=["GL001"], cache_path=cache)
        assert (r3.cache_hits, r3.cache_misses) == (0, 1)
        assert r3.new == []

    def test_cache_entry_scoped_to_rules(self, tmp_path):
        # an entry written for GL001 must not satisfy a GL001+GL007
        # request (different file-rule set)
        repo = tmp_path / "r"
        pkg = repo / "deeplearning4j_tpu"
        pkg.mkdir(parents=True)
        (pkg / "m.py").write_text(TestChangedOnlyDeleted.CLEAN)
        cache = str(repo / "cache.json")
        run_lint(str(repo), rules=["GL001"], cache_path=cache)
        r = run_lint(str(repo), rules=["GL001", "GL007"],
                     cache_path=cache)
        assert r.cache_misses == 1

    def test_stats_reports_wall_time(self):
        p = run_cli("--stats", "--no-cache")
        assert p.returncode == 0, p.stdout + p.stderr
        assert "wall_s" in p.stdout
        assert "rule wall time" in p.stdout


class TestPrePushHook:
    """ISSUE 14 satellite: the pre-push gate ships, is executable,
    and runs the changed-only lint with exit-code gating."""

    HOOK = os.path.join(REPO, "tools", "hooks", "pre-push")

    def test_hook_exists_and_is_executable(self):
        assert os.path.isfile(self.HOOK)
        assert os.access(self.HOOK, os.X_OK)

    def test_hook_invokes_changed_only_lint(self):
        text = open(self.HOOK).read()
        # the hook must cover BOTH lint scopes, not just the default
        # package path — a rule edit under tools/ gates the push too
        assert ("python -m tools.graftlint deeplearning4j_tpu/ "
                "tools/ --changed-only") in text
        assert "exit" in text          # exit-code gating
        assert "--no-verify" in text   # documents the escape hatch


# ---------------------------------------------------------------------------
# the rules stay registered + documented
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_all_eleven_rules_present(self):
        assert sorted(ALL_RULES) == [f"GL{i:03d}"
                                     for i in range(1, 12)]
        for cls in ALL_RULES.values():
            assert cls.title and cls.rationale
            assert cls.scope in ("file", "repo")

    def test_readme_documents_every_rule(self):
        text = open(os.path.join(REPO, "README.md")).read()
        for rid in ALL_RULES:
            assert rid in text, f"{rid} missing from README"
        assert "graftlint: disable=" in text
        # the pre-push hook install one-liner ships in the README
        assert "tools/hooks/pre-push" in text

    def test_pytest_ini_marker_covers_all_rules(self):
        text = open(os.path.join(REPO, "pytest.ini")).read()
        assert "GL001-GL011" in text
