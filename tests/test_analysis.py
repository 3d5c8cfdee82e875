"""graft-lint: the AST invariant checker (analysis/).

Every pass is proven LIVE with a red/green fixture pair: a minimal
synthetic source tree that violates the contract (the pass must flag
it) next to the corrected tree (the pass must stay silent).  The
fixtures are dicts of repo-relative path -> source text — exactly the
``run(sources)`` interface the real runner feeds from disk — so the
tests exercise the same discovery-by-content code paths as a live
scan.

Also pinned here:

- the PR 7 sticky-map race as a LOCK-HELD regression fixture (the
  read / health-check / LRU-touch split across two lock holds that
  shipped a KeyError);
- the bert ``causal`` shadowing case as a JIT-BRANCH precision
  regression (a nested def's param name must not taint an OUTER
  branch on a closure-captured static);
- allowlist comments (``sync-ok`` / ``lock-ok`` / ``jit-ok`` /
  ``noqa``) silencing each pass;
- the baseline ratchet: counts may only decrease, and the runner
  fails on any increase;
- the live repo itself scanning clean against the shipped baseline.

Host-only and fast (pure ``ast`` work, no jax arrays) — tier-1 safe.
"""

import json
import textwrap

from mpi_tensorflow_tpu.analysis import (core, host_sync, jit_stability,
                                         locks, names, runner)


def _src(text):
    return textwrap.dedent(text).lstrip("\n")


def _ids(findings):
    return [f.pass_id for f in findings]


# ---------------------------------------------------------------------
# recompile-hazard (jit_stability)
# ---------------------------------------------------------------------

def test_jit_branch_red():
    tree = {"pkg/m.py": _src("""
        import jax

        @jax.jit
        def f(x, flag):
            if flag:
                return x + 1
            return x
        """)}
    found = jit_stability.run(tree)
    assert _ids(found) == ["JIT-BRANCH"]
    assert "'flag'" in found[0].message


def test_jit_branch_static_forms_green():
    tree = {"pkg/m.py": _src("""
        import jax

        @jax.jit
        def f(x, y):
            if x is None:
                return y
            if isinstance(y, tuple):
                y = y[0]
            if x.shape[0] > 4:
                return x * 2
            if len(x.shape) == 2:
                return x
            return x + y
        """)}
    assert jit_stability.run(tree) == []


def test_jit_branch_reaches_through_jit_callsite():
    tree = {"pkg/m.py": _src("""
        import jax

        def impl(x, n):
            while n > 0:
                x = x + 1
            return x

        step = jax.jit(impl)
        """)}
    assert _ids(jit_stability.run(tree)) == ["JIT-BRANCH"]


def test_jit_branch_nested_param_does_not_shadow_outer_static():
    # the bert `causal` regression: a DESCENDANT def's param name must
    # not mark the same name traced at an OUTER branch, where it binds
    # to a closure-captured static config value
    tree = {"pkg/m.py": _src("""
        import jax

        def make(causal):
            def outer(q):
                if causal:
                    def inner(q, causal=False):
                        return q
                    return inner(q)
                return q
            return jax.jit(outer)
        """)}
    assert jit_stability.run(tree) == []


def test_jit_loop_red_and_allowlist():
    body = """
        import jax

        def probe(chunks, f):
            for s in chunks:
                {marker}jax.jit(f).lower(s).compile()
            return True
        """
    red = {"pkg/m.py": _src(body.format(marker=""))}
    assert _ids(jit_stability.run(red)) == ["JIT-LOOP"]
    green = {"pkg/m.py": _src(body.format(
        marker="# graft-lint: jit-ok(compile probe)\n"
               "                "))}
    assert jit_stability.run(green) == []


def test_jit_shape_red_in_serving_only():
    body = _src("""
        import numpy as np

        def dispatch(live):
            n = len(live)
            buf = np.zeros((n, 4), np.int32)
            return buf
        """)
    assert _ids(jit_stability.run({"pkg/serving/d.py": body})) \
        == ["JIT-SHAPE"]
    # outside serving/ the discipline doesn't apply
    assert jit_stability.run({"pkg/train/d.py": body}) == []


def test_jit_shape_bucketed_green():
    tree = {"pkg/serving/d.py": _src("""
        import numpy as np

        def pow2_ceil(n):
            return max(1, 1 << (n - 1).bit_length())

        def dispatch(live):
            n = pow2_ceil(len(live))
            return np.zeros((n, 4), np.int32)
        """)}
    assert jit_stability.run(tree) == []


# ---------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------

def _hot_module(step_body):
    return {"pkg/serving/iteration.py": _src(f"""
        import jax
        import numpy as np

        class Loop:
            def __init__(self, impl):
                self._decode_fn = jax.jit(impl)

            def step(self, tokens):
                {step_body}
        """)}


def test_host_sync_cast_red():
    tree = _hot_module("""nxt = self._decode_fn(tokens)
                return int(nxt)""")
    found = host_sync.run(tree)
    assert _ids(found) == ["HOST-SYNC"]
    assert "int()" in found[0].message


def test_host_sync_item_red():
    tree = _hot_module("""nxt = self._decode_fn(tokens)
                return nxt.item()""")
    assert any(".item()" in f.message for f in host_sync.run(tree))


def test_host_sync_allowlist_green():
    tree = _hot_module("""nxt = self._decode_fn(tokens)
                # graft-lint: sync-ok(the one budgeted bulk sync)
                return np.asarray(nxt)""")
    assert host_sync.run(tree) == []


def test_host_sync_untainted_green():
    # int() on plain host data is not a sync
    tree = _hot_module("""n = len(tokens)
                return int(n)""")
    assert host_sync.run(tree) == []


def test_host_sync_rebinding_clears_taint():
    tree = _hot_module("""nxt = self._decode_fn(tokens)
                nxt = [1, 2, 3]
                return int(nxt[0])""")
    assert host_sync.run(tree) == []


def test_host_sync_trace_stamp_red():
    # a span-stamping callback must not smuggle a device sync: reading
    # the dispatched output to decorate a trace event blocks the serve
    # loop on the device — tracing's contract is host clocks ONLY
    tree = _hot_module("""nxt = self._decode_fn(tokens)
                self.tracer.event(float(nxt), "first_token")
                return nxt""")
    found = host_sync.run(tree)
    assert _ids(found) == ["HOST-SYNC"]
    assert "float()" in found[0].message


def _two_step_module(hold_body, read_body):
    return {"pkg/serving/iteration.py": _src(f"""
        import jax
        import numpy as np

        class Loop:
            def __init__(self, impl):
                self._decode_fn = jax.jit(impl)
                self._unread = []

            def step(self, tokens):
                nxt = self._decode_fn(tokens)
                {hold_body}

            def _hold(self, nxt, rows):
                self._unread.append((nxt, rows))

            def _deliver(self, n):
                {read_body}
        """)}


def test_host_sync_follows_a_kept_value_red():
    # the lookahead's shape: one function keeps the dispatch's result,
    # another reads it later — the read is still a sync
    tree = _two_step_module(
        "self._unread.append((nxt, tokens))",
        """for nxt, rows in self._unread[:n]:
                    toks = np.asarray(nxt)""")
    found = host_sync.run(tree)
    assert _ids(found) == ["HOST-SYNC"]
    assert "np.asarray()" in found[0].message


def test_host_sync_follows_a_tainted_argument_red():
    tree = _two_step_module(
        "self._hold(nxt, tokens)",
        """nxt, rows = self._unread.pop(0)
                return int(nxt[0])""")
    found = host_sync.run(tree)
    assert _ids(found) == ["HOST-SYNC"]
    assert "int()" in found[0].message


def test_host_sync_kept_value_allowlist_green():
    tree = _two_step_module(
        "self._hold(nxt, tokens)",
        """for nxt, rows in self._unread[:n]:
                    # graft-lint: sync-ok(read after the next dispatch)
                    toks = np.asarray(nxt)""")
    assert host_sync.run(tree) == []


def test_engine_dispatch_path_reads_no_device_value():
    """The static half of the lookahead's pin (tests/test_lookahead.py
    holds the run-time half): with every ``sync-ok`` mark of the engine
    struck, the pass finds a read of a device value in ``_deliver`` (the
    one bulk read a dispatch, taken after the next dispatch is issued),
    in the traced run's counter read beside it and in the verify step
    (which reads at once, by design) — and NONE in the functions that
    assemble and issue the plain path's dispatches."""
    import ast

    sources = core.load_sources()
    rel = next(r for r in sources if r.endswith("serving/engine.py"))
    struck = {rel: sources[rel].replace("sync-ok", "sync-struck")}
    spans = {fn.name: (fn.lineno, fn.end_lineno)
             for fn in core.iter_functions(ast.parse(sources[rel]))}
    where = sorted(name for f in host_sync.run(struck)
                   for name, (a, b) in spans.items() if a <= f.line <= b)
    assert where == ["_deliver", "_read_counters", "_step_verify"]
    for name in ("step", "_advance_prefill", "_dispatch_decode", "_hold",
                 "_count_dispatch", "_log_dispatch"):
        a, b = spans[name]
        body = sources[rel].splitlines()[a - 1:b]
        assert not any("sync-ok" in line for line in body), name


def test_host_sync_cold_namespace_green():
    # same code outside the hot namespace: not this pass's business
    tree = {"pkg/serving/other.py": _src("""
        import jax

        class Loop:
            def __init__(self, impl):
                self._decode_fn = jax.jit(impl)

            def step(self, tokens):
                return int(self._decode_fn(tokens))
        """)}
    assert host_sync.run(tree) == []


# ---------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------

_PR7_RACE = """
    import threading
    from collections import OrderedDict

    class Router:
        _GUARDED_BY = {"_lock": ("_sticky",)}

        def __init__(self):
            self._lock = threading.Lock()
            self._sticky = OrderedDict()

        def route(self, session):
            with self._lock:
                replica = self._sticky.get(session)
            if replica is not None and self.healthy(replica):
                with self._lock:
                    self._sticky.move_to_end(session)
            return replica

        def healthy(self, replica):
            return True
    """

_PR7_FIXED = _PR7_RACE.replace(
    """with self._lock:
                replica = self._sticky.get(session)
            if replica is not None and self.healthy(replica):
                with self._lock:
                    self._sticky.move_to_end(session)""",
    """with self._lock:
                replica = self._sticky.get(session)
                if replica is not None and self.healthy(replica):
                    self._sticky.move_to_end(session)""")


def test_lock_pr7_sticky_race_fixture():
    # the shipped PR 7 bug shape: get() under one hold, the LRU touch
    # under ANOTHER — a concurrent trim can evict the key between them.
    # Lexically both accesses ARE under some `with self._lock`, so the
    # per-access proof passes; what the fixed shape pins is ONE hold
    # spanning read + health check + touch.
    red = {"pkg/r.py": _src(_PR7_RACE)}
    assert locks.run(red) == []          # each access is under A lock…
    green = {"pkg/r.py": _src(_PR7_FIXED)}
    assert locks.run(green) == []        # …and so is the fixed shape;
    # the race the pass DOES catch statically: the touch with no hold
    naked = {"pkg/r.py": _src(_PR7_RACE.replace(
        """with self._lock:
                    self._sticky.move_to_end(session)""",
        "self._sticky.move_to_end(session)"))}
    found = locks.run(naked)
    assert _ids(found) == ["LOCK-HELD"]
    assert "PR 7" in found[0].message


def test_lock_init_and_locked_suffix_exempt():
    tree = {"pkg/r.py": _src("""
        import threading

        class Router:
            _GUARDED_BY = {"_lock": ("_state",)}

            def __init__(self):
                self._lock = threading.Lock()
                self._state = {}

            def _trim_locked(self):
                self._state.clear()

            def trim(self):
                with self._lock:
                    self._trim_locked()
        """)}
    assert locks.run(tree) == []


def test_lock_allowlist_comment():
    tree = {"pkg/r.py": _src("""
        import threading

        class Router:
            _GUARDED_BY = {"_lock": ("_state",)}

            def __init__(self):
                self._lock = threading.Lock()
                self._state = {}

            def reset(self):
                # graft-lint: lock-ok(cold path: no workers yet)
                self._state = {}
        """)}
    assert locks.run(tree) == []
    # without the comment the same store is a finding
    stripped = {"pkg/r.py": tree["pkg/r.py"].replace(
        "        # graft-lint: lock-ok(cold path: no workers yet)\n",
        "")}
    assert _ids(locks.run(stripped)) == ["LOCK-HELD"]


def test_lock_undeclared_class_not_checked():
    tree = {"pkg/r.py": _src("""
        class Plain:
            def poke(self):
                self._state = 1
        """)}
    assert locks.run(tree) == []


# ---------------------------------------------------------------------
# names
# ---------------------------------------------------------------------

def test_names_undefined_red():
    # the reference-repo bug shape: an exception handler raising a
    # never-imported name
    tree = {"pkg/m.py": _src("""
        def fetch(url):
            try:
                return open(url)
            except OSError:
                raise DownloadError(url)
        """)}
    found = names.run(tree)
    assert _ids(found) == ["NAMES-UNDEF"]
    assert "DownloadError" in found[0].message


def test_names_unused_import_red_and_noqa():
    tree = {"pkg/m.py": "import os\nimport sys\n\nprint(sys.argv)\n"}
    found = names.run(tree)
    assert _ids(found) == ["NAMES-IMPORT"]
    assert "'os'" in found[0].message
    quiet = {"pkg/m.py": tree["pkg/m.py"].replace(
        "import os", "import os  # noqa: re-export")}
    assert names.run(quiet) == []


def test_names_init_reexports_and_star_imports_skipped():
    tree = {
        "pkg/__init__.py": "from pkg.m import helper\n",
        "pkg/star.py": "from os.path import *\n\nprint(join('a'))\n",
    }
    assert names.run(tree) == []


def test_names_clean_module_green():
    tree = {"pkg/m.py": _src("""
        import os

        def here():
            return os.getcwd()
        """)}
    assert names.run(tree) == []


# ---------------------------------------------------------------------
# runner + baseline ratchet
# ---------------------------------------------------------------------

def _fake_repo(tmp_path, n_bugs):
    pkg = tmp_path / "mpi_tensorflow_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    body = "import jax\n\n\n@jax.jit\ndef f(x, flag):\n"
    for _ in range(n_bugs):
        body += "    if flag:\n        x = x + 1\n"
    body += "    return x\n"
    (pkg / "m.py").write_text(body)
    return tmp_path


def test_runner_exit_codes_and_ratchet(tmp_path, capsys):
    root = _fake_repo(tmp_path, n_bugs=1)
    baseline = tmp_path / "baseline.json"

    # no baseline: the finding is new -> exit 1, printed
    rc = runner.main(["--root", str(root), "--baseline", str(baseline)])
    out = capsys.readouterr().out
    assert rc == 1 and "JIT-BRANCH" in out

    # baseline it -> clean run exits 0 and stays silent about it
    assert runner.main(["--root", str(root), "--baseline",
                        str(baseline), "--update-baseline"]) == 0
    capsys.readouterr()
    rc = runner.main(["--root", str(root), "--baseline", str(baseline)])
    out = capsys.readouterr().out
    assert rc == 0 and "JIT-BRANCH" not in out

    # a SECOND violation exceeds the baselined count -> exit 1, and
    # only the excess is reported as new
    _fake_repo(tmp_path, n_bugs=2)
    rc = runner.main(["--root", str(root), "--baseline", str(baseline)])
    out = capsys.readouterr().out
    assert rc == 1 and out.count("JIT-BRANCH") == 1

    # the ratchet: --update-baseline REFUSES to grow a count
    rc = runner.main(["--root", str(root), "--baseline",
                      str(baseline), "--update-baseline"])
    err = capsys.readouterr().err
    assert rc == 1 and "ratchet" in err
    assert json.loads(baseline.read_text()) \
        == {"JIT-BRANCH:mpi_tensorflow_tpu/m.py": 1}

    # fixing BOTH and re-baselining ratchets down to empty
    _fake_repo(tmp_path, n_bugs=0)
    assert runner.main(["--root", str(root), "--baseline",
                        str(baseline), "--update-baseline"]) == 0
    assert json.loads(baseline.read_text()) == {}


def test_runner_all_passes_registered():
    mods = {m.__name__.rsplit(".", 1)[-1] for m in runner.PASSES}
    assert mods == {"jit_stability", "host_sync", "locks", "names"}
    ids = [pid for m in runner.PASSES for pid in m.PASS_IDS]
    assert len(ids) == len(set(ids)) == 7


def test_live_repo_scans_clean():
    """The repo's own contracts hold: every finding either fixed or
    allowlisted in-source, baseline (near-)empty — the PR's acceptance
    bar, pinned."""
    sources = core.load_sources(core.repo_root())
    assert "mpi_tensorflow_tpu/serving/router.py" in sources
    assert "mpi_tensorflow_tpu/serving/__main__.py" in sources
    findings = runner.run_all(sources)
    baseline = runner.load_baseline(runner._DEFAULT_BASELINE)
    assert sum(baseline.values()) <= 5, \
        "the baseline is a ratchet, not a dumping ground"
    over = runner.compare(runner.counts_by_key(findings), baseline)
    assert over == {}, [f.format() for f in findings]


def test_finding_format_matches_contract():
    f = core.Finding("pkg/m.py", 7, "HOST-SYNC", "boom")
    assert f.format() == "pkg/m.py:7: HOST-SYNC boom"
    assert f.baseline_key == "HOST-SYNC:pkg/m.py"
