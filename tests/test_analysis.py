"""The static-analysis layer: lint rules, pragmas, baseline, CLI, and
the plancheck plan validator (+ its HistogramEngine.validate wiring).

Each rule gets a failing-then-passing fixture trio: a triggering
snippet, a clean snippet, and a suppressed-with-pragma snippet.
Plancheck gets golden verdicts for the two scenarios test_engine.py
already golden-tests (640x480/32-bin, §4.6 8192²/128-bin) and the
static rejections the ISSUE requires (budget-infeasible and
uint16-overflow plans that previously failed only at run time).
"""

import dataclasses
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (
    RULES,
    gate,
    lint_paths,
    lint_source,
    load_baseline,
    write_baseline,
)
from repro.analysis.__main__ import main as analysis_main

ROOT = Path(__file__).resolve().parent.parent


def _lint(src: str, relpath: str, rule: str):
    """Findings of one rule on one dedented snippet."""
    found = lint_source(textwrap.dedent(src), relpath)
    return [f for f in found if f.rule == rule]


# ---------------------------------------------------------------------------
# rule: sharded-concat
# ---------------------------------------------------------------------------
CORE = "src/repro/core"


def test_sharded_concat_triggers_in_assembly_module():
    bad = """\
        import jax.numpy as jnp
        def dense(pieces):
            return jnp.concatenate(pieces, axis=-2)
    """
    hits = _lint(bad, f"{CORE}/hsource.py", "sharded-concat")
    assert len(hits) == 1 and hits[0].line == 3 and not hits[0].suppressed


def test_sharded_concat_triggers_on_band_operands_anywhere_in_core():
    bad = """\
        import jax.numpy as jnp
        def f(bands):
            return jnp.stack([b.H for b in bands])
    """
    hits = _lint(bad, f"{CORE}/somewhere.py", "sharded-concat")
    assert len(hits) == 1
    # ...but a concat with no band/shard operand outside the assembly
    # modules is fine (zero-padding in region_query.py stays clean)
    ok = """\
        import jax.numpy as jnp
        def pad(H):
            return jnp.concatenate([H, H[..., :1]], axis=-1)
    """
    assert _lint(ok, f"{CORE}/somewhere.py", "sharded-concat") == []


def test_sharded_concat_clean_and_suppressed():
    ok = """\
        import numpy as np
        def dense(pieces):
            return np.concatenate(pieces, axis=-2)
    """
    assert _lint(ok, f"{CORE}/hsource.py", "sharded-concat") == []
    sup = """\
        import jax.numpy as jnp
        def dense(pieces):
            # analysis: allow-sharded-concat(single-device path, operands verified colocated)
            return jnp.concatenate(pieces, axis=-2)
    """
    hits = _lint(sup, f"{CORE}/hsource.py", "sharded-concat")
    assert len(hits) == 1 and hits[0].suppressed
    assert "colocated" in hits[0].suppression_reason


def test_sharded_concat_out_of_scope_elsewhere():
    src = """\
        import jax.numpy as jnp
        def f(bands):
            return jnp.concatenate(bands)
    """
    assert _lint(src, "src/repro/train/grad.py", "sharded-concat") == []


# ---------------------------------------------------------------------------
# rule: host-sync
# ---------------------------------------------------------------------------
def test_host_sync_triggers():
    bad = """\
        import jax, numpy as np
        def retire(out):
            out = jax.block_until_ready(out)
            n = out.sum().item()
            return np.asarray(out), n
    """
    hits = _lint(bad, "src/repro/core/runtime.py", "host-sync")
    assert sorted(h.line for h in hits) == [3, 4, 5]
    # kernel wrappers are in scope too
    assert len(_lint(bad, "src/repro/kernels/ops.py", "host-sync")) == 3


def test_host_sync_clean_suppressed_and_scoped():
    ok = """\
        import jax
        def dispatch(fn, chunk):
            return fn(chunk)
    """
    assert _lint(ok, "src/repro/core/runtime.py", "host-sync") == []
    sup = """\
        import jax
        def retire(out):
            # analysis: allow-host-sync(retire-time sync is the contract)
            return jax.block_until_ready(out)
    """
    hits = _lint(sup, "src/repro/core/runtime.py", "host-sync")
    assert len(hits) == 1 and hits[0].suppressed
    # outside the hot paths np.asarray is fine
    bad = """\
        import numpy as np
        def f(x): return np.asarray(x)
    """
    assert _lint(bad, "src/repro/core/hsource.py", "host-sync") == []


# ---------------------------------------------------------------------------
# rule: carry-contract
# ---------------------------------------------------------------------------
def test_carry_contract_triggers_on_malformed_step():
    one_arg = """\
        from repro.core.runtime import FrameRuntime
        rt = FrameRuntime(lambda chunk: chunk)
    """
    hits = _lint(one_arg, "src/repro/core/x.py", "carry-contract")
    assert len(hits) == 1
    no_pair = """\
        from repro.core.runtime import FrameRuntime
        def step(chunk, carry):
            return chunk
        rt = FrameRuntime(step)
    """
    hits = _lint(no_pair, "src/repro/core/x.py", "carry-contract")
    assert len(hits) == 1 and hits[0].line == 3


def test_carry_contract_clean_stateless_and_suppressed():
    ok = """\
        from repro.core.runtime import FrameRuntime
        def step(chunk, carry):
            return chunk * 2, carry
        rt = FrameRuntime(step)
        rt2 = FrameRuntime(lambda chunk, carry: (chunk, carry))
        rt3 = FrameRuntime(FrameRuntime.stateless(abs))
    """
    assert _lint(ok, "src/repro/core/x.py", "carry-contract") == []
    sup = """\
        from repro.core.runtime import FrameRuntime
        # analysis: allow-carry-contract(adapter normalizes the signature downstream)
        rt = FrameRuntime(lambda chunk: chunk)
    """
    hits = _lint(sup, "src/repro/core/x.py", "carry-contract")
    assert len(hits) == 1 and hits[0].suppressed


# ---------------------------------------------------------------------------
# rule: no-shim-use
# ---------------------------------------------------------------------------
def test_no_shim_use_triggers():
    imp = """\
        from repro.core.region_query import banded_region_histogram
    """
    assert len(_lint(imp, "src/repro/core/x.py", "no-shim-use")) == 1
    attr = """\
        from repro.core import region_query
        f = region_query.banded_likelihood_map
    """
    assert len(_lint(attr, "src/repro/core/x.py", "no-shim-use")) == 1


def test_no_shim_use_clean_defining_module_and_suppressed():
    ok = """\
        from repro.core.region_query import region_histogram
    """
    assert _lint(ok, "src/repro/core/x.py", "no-shim-use") == []
    # the defining module is exempt — it IS the shim
    definition = """\
        def banded_region_histogram(bands, rects):
            return banded_region_histogram
    """
    assert _lint(definition, "src/repro/core/region_query.py",
                 "no-shim-use") == []
    sup = """\
        from repro.core import region_query
        # analysis: allow-shim-use(public deprecated alias kept until 2.0)
        f = region_query.banded_region_histogram
    """
    hits = _lint(sup, "src/repro/core/x.py", "no-shim-use")
    assert len(hits) == 1 and hits[0].suppressed


# ---------------------------------------------------------------------------
# rule: overflow-policy
# ---------------------------------------------------------------------------
def test_overflow_policy_triggers():
    no_bound = """\
        import numpy as np
        STORAGE_POLICIES = {"uint16": np.uint16}
    """
    assert len(_lint(no_bound, "src/repro/core/bands.py",
                     "overflow-policy")) == 1
    dyn_bound = """\
        import numpy as np
        def limit(): return 65535
        STORAGE_POLICIES = {"uint16": (np.uint16, limit())}
    """
    assert len(_lint(dyn_bound, "src/repro/core/bands.py",
                     "overflow-policy")) == 1
    no_method = """\
        from repro.core.hsource import HSource
        class SpilledIH(HSource):
            storage: str
    """
    hits = _lint(no_method, "src/repro/core/bands.py", "overflow-policy")
    assert len(hits) == 1 and "exact_region_bound" in hits[0].message


def test_overflow_policy_clean_and_suppressed():
    ok = """\
        import numpy as np
        BITS = 16
        STORAGE_POLICIES = {"uint16": (np.uint16, (1 << BITS) - 1)}
        from repro.core.hsource import HSource
        class SpilledIH(HSource):
            storage: str
            def exact_region_bound(self):
                return STORAGE_POLICIES[self.storage][1]
    """
    assert _lint(ok, "src/repro/core/bands.py", "overflow-policy") == []
    sup = """\
        import numpy as np
        # analysis: allow-overflow-policy(prototype policy, bound enforced by caller)
        STORAGE_POLICIES = {"uint16": np.uint16}
    """
    hits = _lint(sup, "src/repro/core/bands.py", "overflow-policy")
    assert len(hits) == 1 and hits[0].suppressed


# ---------------------------------------------------------------------------
# rule: lock-discipline
# ---------------------------------------------------------------------------
LOCKED_CLASS = """\
    import threading
    class Svc:
        _LOCK_PROTECTED = ("_cache", "stats")
        _LOCK_PROTECTED_MUTATORS = ("observe",)
        def __init__(self):
            self._lock = threading.Lock()
            self._cache = {}     # __init__ is exempt
            self.stats = None
        def {body}
"""


def _locked(body: str):
    return textwrap.dedent(LOCKED_CLASS).replace(
        "def {body}", textwrap.dedent(body).replace("\n", "\n        ").rstrip()
    )


def test_lock_discipline_triggers():
    bad_write = _locked("""\
        def hit(self, k):
            self._cache[k] = 1
    """)
    hits = lint_source(bad_write, "src/repro/serve/service.py")
    assert [f.rule for f in hits] == ["lock-discipline"]
    bad_mutator = _locked("""\
        def note(self, dt):
            self.stats.observe(dt)
    """)
    hits = lint_source(bad_mutator, "src/repro/serve/service.py")
    assert [f.rule for f in hits] == ["lock-discipline"]
    bad_aug = _locked("""\
        def bump(self):
            self.stats.requests += 1
    """)
    hits = lint_source(bad_aug, "src/repro/serve/service.py")
    assert [f.rule for f in hits] == ["lock-discipline"]


def test_lock_discipline_clean_and_suppressed():
    ok = _locked("""\
        def hit(self, k):
            with self._lock:
                self._cache[k] = 1
                self.stats.observe(0.0)
            return self._cache.get(k)   # reads need no lock
    """)
    assert lint_source(ok, "src/repro/serve/service.py") == []
    sup = _locked("""\
        def hit(self, k):
            # analysis: allow-lock-discipline(single-threaded setup path)
            self._cache[k] = 1
    """)
    hits = lint_source(sup, "src/repro/serve/service.py")
    assert len(hits) == 1 and hits[0].suppressed
    # classes without a declaration are out of scope
    undeclared = """\
        class Free:
            def f(self):
                self._cache = {}
    """
    assert _lint(undeclared, "src/repro/serve/service.py",
                 "lock-discipline") == []


# ---------------------------------------------------------------------------
# rule: lock-order
# ---------------------------------------------------------------------------
SVC = "src/repro/serve/service.py"


def _lock_order(src: str):
    return _lint(src, SVC, "lock-order")


def test_lock_order_flags_acquisition_cycle():
    bad = """\
        import threading
        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._cache_lock = threading.Lock()
            def a(self):
                with self._lock:
                    with self._cache_lock:
                        pass
            def b(self):
                with self._cache_lock:
                    with self._lock:
                        pass
    """
    hits = _lock_order(bad)
    assert len(hits) == 1 and "lock-order cycle" in hits[0].message


def test_lock_order_flags_reacquisition_direct_and_via_call():
    direct = """\
        import threading
        class S:
            def __init__(self):
                self._lock = threading.Lock()
            def a(self):
                with self._lock:
                    with self._lock:
                        pass
    """
    hits = _lock_order(direct)
    assert len(hits) == 1 and "self-deadlock" in hits[0].message
    via_call = """\
        import threading
        class S:
            def __init__(self):
                self._lock = threading.Lock()
            def close(self):
                with self._lock:
                    self.flush()
            def flush(self):
                with self._lock:
                    pass
    """
    hits = _lock_order(via_call)
    assert len(hits) == 1
    assert "calls `self.flush()`, which acquires it again" \
        in hits[0].message
    # an RLock is reentrant: the same shape is legal
    rlock = via_call.replace("threading.Lock()", "threading.RLock()")
    assert _lock_order(rlock) == []


def test_lock_order_flags_blocking_under_lock():
    joins = """\
        import threading
        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._worker = threading.Thread()
            def close(self):
                with self._lock:
                    self._worker.join()
    """
    hits = _lock_order(joins)
    assert len(hits) == 1 and "join" in hits[0].message
    future_under_lock = """\
        import threading
        class S:
            def __init__(self):
                self._lock = threading.Lock()
            def done(self, fut, out):
                with self._lock:
                    fut.set_result(out)
    """
    hits = _lock_order(future_under_lock)
    assert len(hits) == 1 and "done-callbacks" in hits[0].message
    # the blocking call may hide behind a self.method() hop
    via_callee = """\
        import threading
        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._queue = None
            def drain(self):
                with self._lock:
                    self.take()
            def take(self):
                return self._queue.get(timeout=1)
    """
    hits = _lock_order(via_callee)
    assert len(hits) == 1 and "which blocks" in hits[0].message


def test_lock_order_clean_and_suppressed():
    # the shipped service's shape: lock only around state, blocking
    # calls (join / queue.get / set_result) all outside the lock
    ok = """\
        import threading
        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._queue = None
                self._worker = None
            def submit(self, p):
                self._queue.put(p, block=True)
                with self._lock:
                    self.n = 1
            def close(self):
                self._worker.join()
                p = self._queue.get_nowait()
                p.future.set_result(None)
    """
    assert _lock_order(ok) == []
    sup = """\
        import threading
        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._worker = threading.Thread()
            def close(self):
                with self._lock:
                    # analysis: allow-lock-order(worker never takes this lock)
                    self._worker.join()
    """
    found = _lint(sup, SVC, "lock-order")
    assert len(found) == 1 and found[0].suppressed
    # classes without locks are out of scope
    assert _lock_order("""\
        class Free:
            def f(self):
                self._worker.join()
    """) == []


# ---------------------------------------------------------------------------
# pragmas, baseline, CLI
# ---------------------------------------------------------------------------
def test_bad_pragmas_are_reported_and_do_not_suppress():
    empty_reason = """\
        import jax.numpy as jnp
        def dense(p):
            # analysis: allow-sharded-concat()
            return jnp.concatenate(p)
    """
    found = lint_source(textwrap.dedent(empty_reason), f"{CORE}/hsource.py")
    rules = sorted(f.rule for f in found)
    assert rules == ["pragma", "sharded-concat"]
    assert not [f for f in found if f.suppressed]
    unknown = """\
        x = 1  # analysis: allow-no-such-rule(whatever)
    """
    found = lint_source(textwrap.dedent(unknown), f"{CORE}/x.py")
    assert [f.rule for f in found] == ["pragma"]
    assert "no registered rule" in found[0].message


def test_baseline_roundtrip_and_gate(tmp_path):
    src = textwrap.dedent("""\
        import jax.numpy as jnp
        def dense(p):
            return jnp.concatenate(p)
    """)
    findings = lint_source(src, f"{CORE}/hsource.py")
    assert len(findings) == 1
    path = tmp_path / "baseline.json"
    assert write_baseline(findings, path) == 1
    baseline = load_baseline(path)
    assert gate(findings, baseline) == []
    assert gate(findings, set()) == findings
    # fingerprints survive the finding moving to another line
    moved = lint_source("\n\n" + src, f"{CORE}/hsource.py")
    assert gate(moved, baseline) == []


def test_cli_check_exit_codes(tmp_path, capsys):
    pkg = tmp_path / "src" / "repro" / "core"
    pkg.mkdir(parents=True)
    (pkg / "hsource.py").write_text(textwrap.dedent("""\
        import jax.numpy as jnp
        def dense(p):
            return jnp.concatenate(p)
    """))
    root = str(tmp_path)
    assert analysis_main(["--check", "--root", root]) == 1
    assert analysis_main(["--write-baseline", "--root", root]) == 0
    assert analysis_main(["--check", "--root", root]) == 0
    report = tmp_path / "report.json"
    assert analysis_main(["--check", "--root", root,
                          "--json", str(report)]) == 0
    data = json.loads(report.read_text())
    assert data["counts"]["gating"] == 0 and data["counts"]["total"] == 1
    assert set(data["rules"]) == set(RULES)
    capsys.readouterr()


BAD_CONCAT = """\
import jax.numpy as jnp
def dense(p):
    return jnp.concatenate(p)
"""


def test_write_baseline_is_a_ratchet(tmp_path):
    """Once a baseline exists, rewriting it can only prune: fixed debt
    drops out, NEW findings are refused (never laundered in)."""
    old = lint_source(BAD_CONCAT, f"{CORE}/hsource.py")
    path = tmp_path / "baseline.json"
    assert write_baseline(old, path) == 1          # seed: full write
    seeded = load_baseline(path)
    # the old finding is fixed; a new one appears elsewhere
    new = lint_source(BAD_CONCAT, f"{CORE}/bands.py")
    assert write_baseline(new, path) == 0          # old∩current = {}
    assert load_baseline(path) == set()
    assert seeded != set()
    # the new finding still gates — it was not written into the baseline
    assert gate(new, load_baseline(path)) == new


def test_stale_fingerprints_detects_fixed_debt():
    from repro.analysis import stale_fingerprints

    findings = lint_source(BAD_CONCAT, f"{CORE}/hsource.py")
    live = {f.fingerprint for f in findings}
    baseline = live | {"sharded-concat:src/repro/core/gone.py:abc123def456"}
    assert stale_fingerprints(findings, baseline) == baseline - live
    assert stale_fingerprints(findings, live) == set()


def _seed_repo(tmp_path) -> Path:
    pkg = tmp_path / "src" / "repro" / "core"
    pkg.mkdir(parents=True)
    (pkg / "hsource.py").write_text(BAD_CONCAT)
    return pkg / "hsource.py"


def test_cli_check_fails_on_stale_baseline(tmp_path, capsys):
    """The committed baseline may only shrink: once debt is fixed,
    --check forces the prune."""
    bad_file = _seed_repo(tmp_path)
    root = str(tmp_path)
    assert analysis_main(["--write-baseline", "--root", root]) == 0
    assert analysis_main(["--check", "--root", root]) == 0
    bad_file.write_text("def dense(p):\n    return p\n")   # debt fixed
    report = tmp_path / "report.json"
    assert analysis_main(["--check", "--root", root,
                          "--json", str(report)]) == 1
    out = capsys.readouterr().out
    assert "stale baseline entry" in out and "--write-baseline" in out
    data = json.loads(report.read_text())
    assert data["counts"]["stale_baseline"] == 1
    assert data["counts"]["gating"] == 0
    assert len(data["stale_baseline"]) == 1
    # pruning restores a clean --check, and the baseline shrank to empty
    assert analysis_main(["--write-baseline", "--root", root]) == 0
    assert analysis_main(["--check", "--root", root]) == 0
    assert load_baseline(tmp_path / "analysis-baseline.json") == set()
    capsys.readouterr()


def test_cli_usage_errors_exit_2(tmp_path, capsys):
    # conflicting modes
    assert analysis_main(["--check", "--write-baseline"]) == 2
    assert analysis_main(["--list-rules", "--check"]) == 2
    # no lintable paths under the given root
    assert analysis_main(["--check", "--root", str(tmp_path)]) == 2
    capsys.readouterr()


def test_cli_json_schema_roundtrip(tmp_path, capsys):
    """The JSON artifact carries everything the text render shows, keyed
    so CI tooling can diff runs: findings with fingerprints, the gating
    and stale sets, per-rule metadata."""
    _seed_repo(tmp_path)
    report = tmp_path / "report.json"
    assert analysis_main(["--root", str(tmp_path),
                          "--json", str(report)]) == 0
    data = json.loads(report.read_text())
    assert data["version"] == 1
    assert set(data["rules"]) == set(RULES)
    for meta in data["rules"].values():
        assert meta["pragma"].startswith("allow-") and meta["description"]
    (finding,) = data["findings"]
    assert finding["rule"] == "sharded-concat"
    assert finding["fingerprint"].startswith(
        "sharded-concat:src/repro/core/hsource.py:")
    assert data["gating"] == [finding["fingerprint"]]
    assert data["stale_baseline"] == []
    assert data["counts"] == {
        "total": 1, "suppressed": 0, "gating": 1, "stale_baseline": 0}
    capsys.readouterr()


def test_cli_pragma_suppression_end_to_end(tmp_path, capsys):
    """A pragma with a reason suppresses through the CLI; --check passes
    and the report records the suppression."""
    pkg = tmp_path / "src" / "repro" / "core"
    pkg.mkdir(parents=True)
    (pkg / "hsource.py").write_text(textwrap.dedent("""\
        import jax.numpy as jnp
        def dense(p):
            # analysis: allow-sharded-concat(single-device fast path)
            return jnp.concatenate(p)
    """))
    report = tmp_path / "report.json"
    assert analysis_main(["--check", "--root", str(tmp_path),
                          "--json", str(report)]) == 0
    data = json.loads(report.read_text())
    (finding,) = data["findings"]
    assert finding["suppressed"] is True
    assert finding["suppression_reason"] == "single-device fast path"
    assert data["counts"] == {
        "total": 1, "suppressed": 1, "gating": 0, "stale_baseline": 0}
    capsys.readouterr()


def test_tree_is_clean():
    """The acceptance gate: the repo's own tree lints clean."""
    findings = lint_paths(
        [p for p in ("src/repro", "benchmarks", "examples")
         if (ROOT / p).exists()],
        root=ROOT,
    )
    gating = gate(findings, load_baseline(ROOT / "analysis-baseline.json"))
    assert gating == [], "\n".join(f.render() for f in gating)


def test_cli_runs_without_jax_imported():
    """The CI analysis job runs the CLI on a bare interpreter; the lint
    layer must not drag jax in."""
    code = (
        "import sys; import repro.analysis; "
        "assert 'jax' not in sys.modules, 'lint layer imported jax'"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=str(ROOT), env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# plancheck: golden verdicts (the scenarios test_engine.py golden-tests)
# ---------------------------------------------------------------------------
GOLDEN_VERDICT_640 = """\
plan verdict    : OK (statically feasible)
  OK   representation  dense
  OK   h-shape         (32, 480, 640) float32 via wf_tis/jnp
  SKIP carry-chain     single-band plan has no carry
  SKIP memory-budget   no memory budget declared
  SKIP vmem-fit        jnp backend uses HBM
  OK   count-validity  307200-px frame within fp32 exact range"""

GOLDEN_VERDICT_64MB = """\
plan verdict    : OK (statically feasible)
  OK   representation  banded
  OK   h-shape         (128, 8192, 8192) int32 via wf_tis/jnp
  OK   carry-chain     128 bands (heights [64]) thread a (128, 8192) carry
  OK   memory-budget   largest band (64 rows): 268435456 B <= \
268435456 B budget
  SKIP vmem-fit        jnp backend uses HBM
  OK   count-validity  67108864-px frame in an int32 H; in-tile fp32 \
scans <= 1048576 < 2**24"""


def _plan(engine, shape):
    from repro.core.engine import plan

    return plan(engine.spec_for(shape))


def test_plancheck_golden_640x480():
    from repro.core.engine import HistogramEngine

    e = HistogramEngine(32, backend="jnp")
    v = e.validate(_plan(e, (480, 640)))
    assert v.ok and v.render() == GOLDEN_VERDICT_640


def test_plancheck_golden_8192_paper_scale():
    from repro.core.engine import HistogramEngine

    e = HistogramEngine(128, backend="jnp", memory_budget_bytes=256 << 20)
    v = e.validate(_plan(e, (8192, 8192)))
    assert v.ok and v.render() == GOLDEN_VERDICT_64MB
    # past 2**24 pixels the H is int32, so every count is exact: no warning
    assert [c.status for c in v.checks].count("warn") == 0


# ---------------------------------------------------------------------------
# plancheck: static rejections (previously run-time failures)
# ---------------------------------------------------------------------------
def test_validate_rejects_budget_infeasible_plan():
    from repro.core.engine import HistogramEngine

    e = HistogramEngine(32, backend="jnp")
    p = _plan(e, (480, 640))
    bad = dataclasses.replace(
        p, microbatch=64,
        spec=dataclasses.replace(p.spec, memory_budget_bytes=1 << 20,
                                 num_frames=64),
    )
    v = e.validate(bad)
    assert not v.ok
    assert [c.name for c in v.failures] == ["memory-budget"]


def test_validate_rejects_uint16_overflow_query():
    from repro.core.engine import (
        HistogramEngine, PlanValidationError, RegionQuery,
    )

    e = HistogramEngine(16, backend="jnp", storage="uint16",
                        memory_budget_bytes=1 << 20)
    big = RegionQuery(np.array([[0, 0, 400, 400]]))   # 160801 px > 65535
    v = e.validate(_plan(e, (512, 512)), [big])
    assert not v.ok
    assert [c.name for c in v.failures] == ["query-validity"]
    # ...and run() refuses before any dispatch
    with pytest.raises(PlanValidationError, match="query-validity"):
        e.run(np.zeros((512, 512), np.uint8), [big])
    # the same plan with an in-bounds query sails through
    ok = e.validate(e.last_plan, [RegionQuery(np.array([[0, 0, 99, 99]]))])
    assert ok.ok


def test_validate_rejects_vmem_infeasible_pallas_plan():
    from repro.core.engine import HistogramEngine

    e = HistogramEngine(32, backend="pallas", tile=1024)
    v = e.validate(_plan(e, (2048, 2048)))
    assert not v.ok
    assert [c.name for c in v.failures] == ["vmem-fit"]
    # the default tile fits
    e2 = HistogramEngine(32, backend="pallas")
    assert e2.validate(_plan(e2, (2048, 2048))).ok


def test_validate_catches_carry_and_shape_breakage():
    from repro.core.engine import HistogramEngine

    e = HistogramEngine(128, backend="jnp", memory_budget_bytes=256 << 20)
    p = _plan(e, (8192, 8192))
    bad = dataclasses.replace(p, method="no_such_method")
    v = e.validate(bad)
    names = [c.name for c in v.failures]
    assert "h-shape" in names


def test_engine_run_validates_and_surfaces_verdict():
    from repro.core.engine import HistogramEngine, RegionQuery

    e = HistogramEngine(8, backend="jnp")
    out = e.run(np.zeros((32, 48), np.uint8),
                [RegionQuery(np.array([[0, 0, 7, 7]]))])
    assert e.last_verdict is not None and e.last_verdict.ok
    text = e.explain()
    assert "plan verdict    : OK" in text
    # plain plan.explain() output is unchanged (golden tests elsewhere)
    assert "plan verdict" not in out.plan.explain()
    assert out.plan.explain(e.last_verdict).endswith(
        e.last_verdict.render().replace("\n", "\n  "))


def test_map_frames_validates_before_first_dispatch():
    from repro.core.engine import HistogramEngine

    e = HistogramEngine(8, backend="jnp")
    frames = [np.zeros((16, 16), np.uint8)] * 2
    outs = list(e.map_frames(frames))
    assert len(outs) == 2 and e.last_verdict is not None


def test_validate_structural_verdict_is_cached():
    from repro.analysis.plancheck import _structural_checks
    from repro.core.engine import HistogramEngine

    e = HistogramEngine(8, backend="jnp")
    p = _plan(e, (64, 64))
    _structural_checks.cache_clear()
    e.validate(p)
    before = _structural_checks.cache_info().hits
    e.validate(p)
    assert _structural_checks.cache_info().hits == before + 1
