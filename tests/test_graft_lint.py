"""graft-lint (megatron_llm_tpu/analysis + tools/graft_lint.py):
per-checker positive/negative fixtures over tiny synthetic repos,
baseline round-trip with mandatory justifications, and the tier-1
acceptance gate — the linter must be green over THIS repo at HEAD.

The fixtures recreate the canonical paths each checker targets
(megatron_llm_tpu/arguments.py, megatron_llm_tpu/serving/engine.py,
tools/serve_report.py, tests/conftest.py, ...) inside tmp_path;
checkers degrade gracefully when a target file is absent, so each
fixture only writes the files its checker reads."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from megatron_llm_tpu.analysis import (
    Baseline,
    BaselineError,
    Repo,
    flags,
    locks,
    markers,
    recompile,
    run_checkers,
    stdlib_gate,
    telemetry_schema,
    threads,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_CLI = os.path.join(REPO_ROOT, "tools", "graft_lint.py")


def _mk(tmp_path, files):
    """Write a synthetic repo: {relpath: source} -> Repo."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Repo(str(tmp_path))


def _codes(violations):
    return sorted(v.code for v in violations)


def _cli(root, *extra):
    return subprocess.run(
        [sys.executable, LINT_CLI, "--root", str(root), *extra],
        capture_output=True, text=True, timeout=120)


# ---------------------------------------------------------------------------
# recompile
# ---------------------------------------------------------------------------

_JIT_HOT = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return _helper(x)

    def _helper(x):
        y = jnp.sum(x)
        return {body}
"""


def test_recompile_flags_item_reachable_from_jit_root(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/ops/hot.py":
                          _JIT_HOT.format(body="y.item()")})
    vs = recompile.check(repo)
    assert "RC001" in _codes(vs)
    assert any(v.path == "megatron_llm_tpu/ops/hot.py" for v in vs)


def test_recompile_clean_on_pure_math(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/ops/hot.py":
                          _JIT_HOT.format(body="y * 2")})
    assert recompile.check(repo) == []


def test_recompile_ignores_cold_functions(tmp_path):
    # .item() in a function no jit root reaches is host-side code — fine
    repo = _mk(tmp_path, {"megatron_llm_tpu/ops/cold.py": """\
        import jax.numpy as jnp

        def host_summary(x):
            return jnp.sum(x).item()
    """})
    assert recompile.check(repo) == []


# ---------------------------------------------------------------------------
# flags
# ---------------------------------------------------------------------------

_FLAGS_REPO = {
    "megatron_llm_tpu/arguments.py": """\
        def _add_training_args(parser):
            g = parser.add_argument_group("training")
            g.add_argument("--alpha", type=int, default=1)
            g.add_argument("--dead_flag", action="store_true")

        def _add_compat_noop_args(parser):
            g = parser.add_argument_group("compat")
            g.add_argument("--noop_thing", action="store_true")
    """,
    "megatron_llm_tpu/training.py": """\
        def run(args):
            return args.alpha + args.phantom
    """,
    "megatron_llm_tpu/config.py": """\
        class TransformerConfig:
            live: int = 1
            dead_knob: int = 0

        def use(cfg):
            return cfg.live
    """,
}


def test_flags_dead_phantom_and_dead_field(tmp_path):
    repo = _mk(tmp_path, _FLAGS_REPO)
    vs = flags.check(repo)
    by_code = {v.code: v for v in vs}
    assert set(by_code) == {"FW001", "FW002", "FW003"}
    assert by_code["FW001"].symbol == "dead_flag"       # --alpha is read
    assert by_code["FW002"].symbol == "phantom"
    assert by_code["FW003"].symbol == "TransformerConfig.dead_knob"
    # the documented noop group is exempt by design
    assert not any(v.symbol == "noop_thing" for v in vs)


def test_flags_clean_when_everything_is_wired(tmp_path):
    fixed = dict(_FLAGS_REPO)
    fixed["megatron_llm_tpu/training.py"] = """\
        def run(args, cfg):
            return args.alpha + int(args.dead_flag) + cfg.dead_knob
    """
    repo = _mk(tmp_path, fixed)
    assert flags.check(repo) == []


# ---------------------------------------------------------------------------
# telemetry schema
# ---------------------------------------------------------------------------

def _telemetry_repo(tmp_path, writer_keys, golden_keys, module_version=3,
                    pinned_version=3):
    writer = "\n".join(f'                "{k}": 1,' for k in writer_keys)
    golden = ", ".join(f'"{k}"' for k in golden_keys)
    return _mk(tmp_path, {
        "megatron_llm_tpu/serving/engine.py": f"""\
            class InferenceEngine:
                def _retire(self, req):
                    record = {{
            {writer}
                    }}
                    return record
        """,
        "megatron_llm_tpu/telemetry.py": f"""\
            TELEMETRY_SCHEMA_VERSION = {module_version}
        """,
        "tests/test_serving_engine.py": f"""\
            from megatron_llm_tpu import telemetry

            def test_request_done_schema_golden():
                rec = {{}}
                assert telemetry.TELEMETRY_SCHEMA_VERSION == {pinned_version}
                assert frozenset(rec) == frozenset(({golden},))
        """,
    })


def test_telemetry_writer_golden_drift_is_ts001(tmp_path):
    repo = _telemetry_repo(tmp_path, ["event", "sneaky_new_key"], ["event"])
    vs = telemetry_schema.check(repo)
    assert _codes(vs) == ["TS001"]
    assert "sneaky_new_key" in vs[0].message


def test_telemetry_key_change_without_version_bump_is_ts004(tmp_path):
    repo = _telemetry_repo(tmp_path, ["event", "added"], ["event", "added"])
    snap = Baseline(telemetry_schema={"version": 3,
                                      "request_done_keys": ["event"]})
    vs = telemetry_schema.check(repo, snap)
    assert _codes(vs) == ["TS004"]
    # bumping the version turns TS004 into TS005 (stale snapshot)
    repo2 = _telemetry_repo(tmp_path, ["event", "added"], ["event", "added"],
                            module_version=4, pinned_version=4)
    assert _codes(telemetry_schema.check(repo2, snap)) == ["TS005"]


def test_telemetry_pinned_version_drift_is_ts006(tmp_path):
    repo = _telemetry_repo(tmp_path, ["event"], ["event"],
                           module_version=4, pinned_version=3)
    assert _codes(telemetry_schema.check(repo)) == ["TS006"]


def test_telemetry_agreement_is_clean(tmp_path):
    repo = _telemetry_repo(tmp_path, ["event", "kind"], ["event", "kind"])
    snap = Baseline(telemetry_schema={"version": 3,
                                      "request_done_keys": ["event", "kind"]})
    assert telemetry_schema.check(repo, snap) == []


def test_telemetry_record_snapshot_roundtrip(tmp_path):
    repo = _telemetry_repo(tmp_path, ["event", "kind"], ["event", "kind"])
    b = Baseline()
    snap = telemetry_schema.record_snapshot(repo, b)
    assert snap == {"version": 3, "request_done_keys": ["event", "kind"]}
    assert telemetry_schema.check(repo, b) == []


# ---------------------------------------------------------------------------
# stdlib gate
# ---------------------------------------------------------------------------

def test_stdlib_gate_flags_jax_in_gated_tool(tmp_path):
    repo = _mk(tmp_path, {"tools/serve_report.py": """\
        import json
        import jax
    """})
    vs = stdlib_gate.check(repo)
    assert _codes(vs) == ["SG001"]
    assert vs[0].symbol == "jax"


def test_stdlib_gate_allows_stdlib_and_guarded_imports(tmp_path):
    repo = _mk(tmp_path, {"tools/serve_report.py": """\
        import argparse
        import json

        try:
            import numpy as np
        except ImportError:
            np = None
    """})
    assert stdlib_gate.check(repo) == []


def test_stdlib_gate_only_applies_to_gated_files(tmp_path):
    repo = _mk(tmp_path, {"tools/random_helper.py": "import jax\n"})
    assert stdlib_gate.check(repo) == []


# ---------------------------------------------------------------------------
# locks
# ---------------------------------------------------------------------------

_LOCKS_REPO = {"megatron_llm_tpu/serving/engine.py": """\
    import threading
    import time

    class Manager:
        _lock_protected_ = ("count",)

        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0          # __init__ is exempt

        def bad_sleep(self):
            with self._lock:
                time.sleep(1)       # LD001

        def bad_write(self):
            self.count += 1         # LD002

        def good_write(self):
            with self._lock:
                self.count += 1

        def bump_locked(self):
            self.count += 1         # *_locked: caller holds the lock
"""}


def test_locks_blocking_and_unlocked_write(tmp_path):
    repo = _mk(tmp_path, _LOCKS_REPO)
    vs = locks.check(repo)
    assert _codes(vs) == ["LD001", "LD002"]
    ld2 = next(v for v in vs if v.code == "LD002")
    assert "bad_write" in ld2.symbol


def test_locks_clean_class_without_annotation(tmp_path):
    # no _lock_protected_ declaration -> LD002 never fires; LD001 still
    # guards any with-lock block
    repo = _mk(tmp_path, {"megatron_llm_tpu/serving/engine.py": """\
        import threading

        class Plain:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def write(self):
                self.count += 1
    """})
    assert locks.check(repo) == []


# ---------------------------------------------------------------------------
# threads (graft-race)
# ---------------------------------------------------------------------------

_TH001_REPO = {"megatron_llm_tpu/shared.py": """\
    import threading

    class Shared:
        def __init__(self):
            self.count = 0
            threading.Thread(target=self._a, name="writer-a",
                             daemon=True).start()
            threading.Thread(target=self._b, name="writer-b",
                             daemon=True).start()

        def _a(self):
            while True:
                self.count += 1

        def _b(self):
            while True:
                self.count += 1
"""}


def test_th001_two_roots_no_lock(tmp_path):
    repo = _mk(tmp_path, _TH001_REPO)
    vs = threads.check(repo)
    assert "TH001" in _codes(vs)
    v = next(v for v in vs if v.code == "TH001")
    assert v.symbol == "Shared.count"
    assert "writer-a" in v.message and "writer-b" in v.message
    # the fix-hint is a paste-able annotation
    assert '_lock_protected_ = {"count": "_lock"}' in v.message


def test_th001_clean_under_common_lock(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/shared.py": """\
        import threading

        class Shared:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
                threading.Thread(target=self._a, name="writer-a",
                                 daemon=True).start()
                threading.Thread(target=self._b, name="writer-b",
                                 daemon=True).start()

            def _a(self):
                while True:
                    with self._lock:
                        self.count += 1

            def _b(self):
                while True:
                    with self._lock:
                        self.count += 1
    """})
    assert [v for v in threads.check(repo) if v.code == "TH001"] == []


def test_th001_single_writer_root_is_clean(tmp_path):
    # one thread publishes, others only read: scalar publish is fine
    repo = _mk(tmp_path, {"megatron_llm_tpu/shared.py": """\
        import threading

        class Shared:
            def __init__(self):
                self.count = 0
                threading.Thread(target=self._a, name="writer-a",
                                 daemon=True).start()
                threading.Thread(target=self._b, name="reader-b",
                                 daemon=True).start()

            def _a(self):
                while True:
                    self.count += 1

            def _b(self):
                while True:
                    print(self.count)
    """})
    assert [v for v in threads.check(repo) if v.code == "TH001"] == []


def test_th002_deliberate_lock_order_cycle(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/ab.py": """\
        import threading

        class AB:
            def __init__(self):
                self._alock = threading.Lock()
                self._block = threading.Lock()
                threading.Thread(target=self.fwd, name="fwd",
                                 daemon=True).start()
                threading.Thread(target=self.rev, name="rev",
                                 daemon=True).start()

            def fwd(self):
                with self._alock:
                    with self._block:
                        pass

            def rev(self):
                with self._block:
                    with self._alock:
                        pass
    """})
    vs = [v for v in threads.check(repo) if v.code == "TH002"]
    assert vs, "lock-order inversion not detected"
    assert "AB._alock" in vs[0].symbol and "AB._block" in vs[0].symbol


def test_th002_nonreentrant_self_acquire(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/ab.py": """\
        import threading

        class AB:
            def __init__(self):
                self._alock = threading.Lock()
                threading.Thread(target=self.outer, name="w",
                                 daemon=True).start()

            def outer(self):
                with self._alock:
                    self.inner()

            def inner(self):
                with self._alock:
                    pass
    """})
    vs = [v for v in threads.check(repo) if v.code == "TH002"]
    assert vs and "AB._alock->AB._alock" in vs[0].symbol
    # an RLock makes the same shape legal
    repo2 = _mk(tmp_path / "r", {"megatron_llm_tpu/ab.py": """\
        import threading

        class AB:
            def __init__(self):
                self._alock = threading.RLock()
                threading.Thread(target=self.outer, name="w",
                                 daemon=True).start()

            def outer(self):
                with self._alock:
                    self.inner()

            def inner(self):
                with self._alock:
                    pass
    """})
    assert [v for v in threads.check(repo2) if v.code == "TH002"] == []


def test_th002_consistent_order_is_clean(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/ab.py": """\
        import threading

        class AB:
            def __init__(self):
                self._alock = threading.Lock()
                self._block = threading.Lock()
                threading.Thread(target=self.fwd, name="fwd",
                                 daemon=True).start()
                threading.Thread(target=self.fwd2, name="fwd2",
                                 daemon=True).start()

            def fwd(self):
                with self._alock:
                    with self._block:
                        pass

            def fwd2(self):
                with self._alock:
                    with self._block:
                        pass
    """})
    assert [v for v in threads.check(repo) if v.code == "TH002"] == []


def test_th003_blocking_under_contested_lock(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/svc.py": """\
        import threading
        import time

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                threading.Thread(target=self.worker, name="worker",
                                 daemon=True).start()
                threading.Thread(target=self.poller, name="poller",
                                 daemon=True).start()

            def worker(self):
                with self._lock:
                    time.sleep(1.0)

            def poller(self):
                while True:
                    with self._lock:
                        pass
    """})
    vs = [v for v in threads.check(repo) if v.code == "TH003"]
    assert vs, "blocking under contested lock not detected"
    assert "time.sleep" in vs[0].message
    assert "poller" in vs[0].message


def test_th003_clean_when_sleep_is_outside_lock(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/svc.py": """\
        import threading
        import time

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                threading.Thread(target=self.worker, name="worker",
                                 daemon=True).start()
                threading.Thread(target=self.poller, name="poller",
                                 daemon=True).start()

            def worker(self):
                with self._lock:
                    pass
                time.sleep(1.0)

            def poller(self):
                while True:
                    with self._lock:
                        pass
    """})
    assert [v for v in threads.check(repo) if v.code == "TH003"] == []


def test_th004_use_after_drain_daemon(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/pump.py": """\
        import threading
        import time

        class Pump:
            _lock_protected_ = {"total": "_lock"}

            def __init__(self):
                self._lock = threading.Lock()
                self._stop = False
                self.total = 0
                threading.Thread(target=self._run, name="pump",
                                 daemon=True).start()

            def _run(self):
                while not self._stop:
                    time.sleep(0.05)
                    self.total += 1
    """})
    vs = [v for v in threads.check(repo) if v.code == "TH004"]
    assert vs, "use-after-drain not detected"
    assert "total" in vs[0].symbol
    assert "time.sleep" in vs[0].message


def test_th004_clean_when_flag_rechecked(tmp_path):
    repo = _mk(tmp_path, {"megatron_llm_tpu/pump.py": """\
        import threading
        import time

        class Pump:
            _lock_protected_ = {"total": "_lock"}

            def __init__(self):
                self._lock = threading.Lock()
                self._stop = False
                self.total = 0
                threading.Thread(target=self._run, name="pump",
                                 daemon=True).start()

            def _run(self):
                while not self._stop:
                    time.sleep(0.05)
                    if self._stop:
                        return
                    self.total += 1
    """})
    assert [v for v in threads.check(repo) if v.code == "TH004"] == []


def test_threads_baseline_roundtrip(tmp_path):
    repo = _mk(tmp_path, _TH001_REPO)
    vs = [v for v in threads.check(repo) if v.code == "TH001"]
    assert vs
    b = Baseline()
    for v in vs:
        b.add(v.fingerprint, "fixture: deliberate race for the test")
    path = str(tmp_path / ".graftlint.json")
    b.save(path)
    loaded = Baseline.load(path)
    unsuppressed, suppressed, stale = run_checkers(repo, loaded,
                                                   names=["threads"])
    assert unsuppressed == []
    assert len(suppressed) == len(vs)
    assert stale == []


def test_threads_fingerprint_is_line_number_free(tmp_path):
    repo = _mk(tmp_path, _TH001_REPO)
    fp1 = {v.fingerprint for v in threads.check(repo)}
    shifted = {"megatron_llm_tpu/shared.py":
               "# comment pushing every line down\n\n"
               + textwrap.dedent(_TH001_REPO["megatron_llm_tpu/shared.py"])}
    repo2 = _mk(tmp_path / "shifted", shifted)
    assert fp1 == {v.fingerprint for v in threads.check(repo2)}


def test_suggest_locks_emits_annotation(tmp_path):
    repo = _mk(tmp_path, _TH001_REPO)
    text = threads.suggest_locks(repo)
    assert "class Shared" in text
    assert '"count": "_lock"' in text
    assert "writer-a" in text


# ---------------------------------------------------------------------------
# the real concurrency fixes are regression-guarded by the checker:
# a synthetic copy of the drain-counter pattern with the fix deleted
# must turn graft_lint red (TH001), and the fixed shape stays green
# ---------------------------------------------------------------------------

_DRAIN_FIXED = """\
    import signal
    import threading
    from http.server import BaseHTTPRequestHandler

    class Metrics:
        _lock_protected_ = {"drained": "_lock"}

        def __init__(self):
            self._lock = threading.Lock()
            self.drained = 0

        def note_drained(self):
            with self._lock:
                self.drained += 1

    class Server:
        def __init__(self):
            self.metrics = Metrics()

        def begin_drain(self):
            self.metrics.note_drained()

        def run(self):
            server = self

            class Handler(BaseHTTPRequestHandler):
                def do_PUT(self):
                    server.begin_drain()

            signal.signal(signal.SIGTERM,
                          lambda *_: server.begin_drain())
"""


def test_deleting_the_drain_fix_turns_lint_red(tmp_path):
    # fixed shape (mirrors ServerMetrics.note_drained): green
    _mk(tmp_path, {"megatron_llm_tpu/server_sim.py": _DRAIN_FIXED})
    res = _cli(tmp_path, "--checkers", "threads")
    assert res.returncode == 0, res.stdout + res.stderr
    # delete the fix: bump the counter directly, without the lock —
    # the signal and HTTP-handler roots now race on Metrics.drained
    broken = _DRAIN_FIXED.replace("self.metrics.note_drained()",
                                  "self.metrics.drained += 1")
    _mk(tmp_path / "broken",
        {"megatron_llm_tpu/server_sim.py": broken})
    res = _cli(tmp_path / "broken", "--checkers", "threads")
    assert res.returncode == 1, res.stdout + res.stderr
    assert "TH001" in res.stdout
    assert "Metrics.drained" in res.stdout


# ---------------------------------------------------------------------------
# markers
# ---------------------------------------------------------------------------

_MARKERS_REPO = {
    "tests/conftest.py": """\
        def pytest_configure(config):
            config.addinivalue_line("markers", "slow: long-running")
    """,
    "tests/test_x.py": """\
        import pytest

        @pytest.mark.slow
        def test_registered():
            pass

        @pytest.mark.solw
        def test_typo():
            pass

        @pytest.mark.parametrize("n", [1])
        def test_builtin(n):
            pass
    """,
}


def test_markers_typo_is_pm001(tmp_path):
    repo = _mk(tmp_path, _MARKERS_REPO)
    vs = markers.check(repo)
    assert _codes(vs) == ["PM001"]
    assert vs[0].symbol == "solw"


def test_markers_registered_and_builtin_are_clean(tmp_path):
    fixed = dict(_MARKERS_REPO)
    fixed["tests/test_x.py"] = fixed["tests/test_x.py"].replace("solw",
                                                                "slow")
    repo = _mk(tmp_path, fixed)
    assert markers.check(repo) == []


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------

def test_baseline_suppression_roundtrip(tmp_path):
    repo = _mk(tmp_path, _LOCKS_REPO)
    vs = locks.check(repo)
    assert len(vs) == 2
    b = Baseline()
    for v in vs:
        b.add(v.fingerprint, "fixture: intentionally bad on purpose")
    path = str(tmp_path / ".graftlint.json")
    b.save(path)

    loaded = Baseline.load(path)
    unsuppressed, suppressed, stale = run_checkers(repo, loaded,
                                                   names=["locks"])
    assert unsuppressed == []
    assert len(suppressed) == 2
    assert stale == []


def test_baseline_stale_suppression_is_reported(tmp_path):
    repo = _mk(tmp_path, _LOCKS_REPO)
    b = Baseline()
    b.add("locks:LD001:megatron_llm_tpu/serving/gone.py:Ghost.f/time.sleep",
          "excuses a violation that no longer exists")
    _un, _sup, stale = run_checkers(repo, b, names=["locks"])
    assert stale == ["locks:LD001:megatron_llm_tpu/serving/gone.py:"
                     "Ghost.f/time.sleep"]
    # a suppression for a checker that did NOT run is never "stale"
    _un, _sup, stale = run_checkers(repo, b, names=["markers"])
    assert stale == []


def test_baseline_requires_justification(tmp_path):
    path = tmp_path / ".graftlint.json"
    path.write_text(json.dumps({
        "version": 1,
        "suppressions": [{"id": "locks:LD001:x.py:f", "justification": ""}],
    }))
    with pytest.raises(BaselineError, match="justification"):
        Baseline.load(str(path))
    with pytest.raises(BaselineError):
        Baseline().add("locks:LD001:x.py:f", "   ")


def test_baseline_rejects_unknown_keys(tmp_path):
    path = tmp_path / ".graftlint.json"
    path.write_text(json.dumps({"version": 1, "ignore": ["everything"]}))
    with pytest.raises(BaselineError, match="unknown keys"):
        Baseline.load(str(path))


def test_baseline_fingerprint_is_line_number_free(tmp_path):
    # moving the violation within the file must not invalidate the
    # suppression — that is the whole point of symbol fingerprints
    repo = _mk(tmp_path, _LOCKS_REPO)
    fp1 = {v.fingerprint for v in locks.check(repo)}
    shifted = {"megatron_llm_tpu/serving/engine.py":
               "# a comment pushing every line down\n\n"
               + textwrap.dedent(_LOCKS_REPO[
                   "megatron_llm_tpu/serving/engine.py"])}
    repo2 = _mk(tmp_path / "shifted", shifted)
    assert fp1 == {v.fingerprint for v in locks.check(repo2)}


# ---------------------------------------------------------------------------
# CLI: non-zero on injected violations, zero over the real repo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("checker,files", [
    ("recompile", {"megatron_llm_tpu/ops/hot.py":
                   _JIT_HOT.format(body="y.item()")}),
    ("flags", _FLAGS_REPO),
    ("telemetry", None),  # built by _telemetry_repo below
    ("stdlib", {"tools/serve_report.py": "import jax\n"}),
    ("locks", _LOCKS_REPO),
])
def test_cli_exits_nonzero_on_each_checker(tmp_path, checker, files):
    if files is None:
        _telemetry_repo(tmp_path, ["event", "drifted"], ["event"])
    else:
        _mk(tmp_path, files)
    res = _cli(tmp_path, "--checkers", checker)
    assert res.returncode == 1, res.stdout + res.stderr
    assert checker in res.stdout


def test_cli_exit_2_on_malformed_baseline(tmp_path):
    (tmp_path / ".graftlint.json").write_text("{not json")
    res = _cli(tmp_path)
    assert res.returncode == 2


def test_graft_lint_is_green_over_this_repo():
    """Tier-1 acceptance: the checked-in baseline keeps the real repo
    clean — every violation is either fixed or suppressed with a
    justification.  A red run here means a hot-path host sync, a dead
    flag, a schema drift, a jax import in a stdlib tool, a lock
    violation, or a thread-topology race landed since the last
    ratchet.  --expect-checkers pins the full set (incl. threads) so
    the gate cannot silently narrow."""
    res = subprocess.run([sys.executable, LINT_CLI,
                          "--expect-checkers", "7"],
                         capture_output=True, text=True, timeout=300,
                         cwd=REPO_ROOT)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 violation(s)" in res.stdout
    assert "7 checker(s) ran" in res.stdout


def test_cli_expect_checkers_guards_narrowed_set(tmp_path):
    _mk(tmp_path, {"megatron_llm_tpu/empty.py": "x = 1\n"})
    res = _cli(tmp_path, "--checkers", "locks", "--expect-checkers", "7")
    assert res.returncode == 2
    assert "expected >= 7" in res.stderr


def test_cli_threads_table_and_doc_agree(tmp_path):
    """--threads output is embedded verbatim in docs/guide/serving.md
    ("Threading model"); diffing doc against tool keeps the doc honest
    when a thread root is added, renamed, or removed."""
    table = threads.threads_table(Repo(REPO_ROOT))
    doc = open(os.path.join(REPO_ROOT, "docs", "guide",
                            "serving.md")).read()
    missing = [row for row in table.splitlines() if row not in doc]
    assert not missing, (
        "docs/guide/serving.md 'Threading model' table is stale; "
        "regenerate with `python tools/graft_lint.py --threads` and "
        "paste.  Missing rows:\n" + "\n".join(missing))
    # CLI smoke on a small fixture root: a second full-repo parse in a
    # subprocess would add no coverage over the in-process table above.
    repo = _mk(tmp_path, _TH001_REPO)
    res = subprocess.run([sys.executable, LINT_CLI, "--threads",
                          "--root", str(tmp_path)],
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0
    assert res.stdout.strip() == threads.threads_table(repo).strip()


def test_cli_changed_only_reports_only_changed_files(tmp_path):
    """--changed-only parity: the reported set is exactly the full
    run's violations intersected with the files changed vs the ref
    (checkers still analyze the whole repo)."""
    _mk(tmp_path, {
        "megatron_llm_tpu/serving/engine.py":
            _LOCKS_REPO["megatron_llm_tpu/serving/engine.py"],
        "tools/serve_report.py": "import jax\n",
    })
    git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
    subprocess.run([*git, "add", "-A"], cwd=tmp_path, check=True)
    subprocess.run([*git, "commit", "-q", "-m", "seed"], cwd=tmp_path,
                   check=True)
    # touch only the locks fixture
    p = tmp_path / "megatron_llm_tpu" / "serving" / "engine.py"
    p.write_text(p.read_text() + "\n# touched\n")

    full = _cli(tmp_path, "--checkers", "locks,stdlib")
    assert full.returncode == 1
    assert "LD001" in full.stdout and "SG001" in full.stdout

    res = _cli(tmp_path, "--checkers", "locks,stdlib",
               "--changed-only", "HEAD")
    assert res.returncode == 1, res.stdout + res.stderr
    assert "LD001" in res.stdout and "LD002" in res.stdout
    assert "SG001" not in res.stdout     # unchanged file not reported
    # parity: reported lines == full-run lines for the changed file
    want = sorted(ln for ln in full.stdout.splitlines()
                  if ln.startswith("megatron_llm_tpu/serving/engine.py"))
    got = sorted(ln for ln in res.stdout.splitlines()
                 if ": LD" in ln or ": SG" in ln)
    assert got == want


def test_cli_changed_only_clean_when_no_violating_file_changed(tmp_path):
    _mk(tmp_path, {"tools/serve_report.py": "import jax\n",
                   "megatron_llm_tpu/ok.py": "x = 1\n"})
    git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
    subprocess.run([*git, "add", "-A"], cwd=tmp_path, check=True)
    subprocess.run([*git, "commit", "-q", "-m", "seed"], cwd=tmp_path,
                   check=True)
    p = tmp_path / "megatron_llm_tpu" / "ok.py"
    p.write_text("x = 2\n")
    res = _cli(tmp_path, "--checkers", "stdlib",
               "--changed-only", "HEAD")
    assert res.returncode == 0, res.stdout + res.stderr
