"""tpudl.analysis — the ISSUE-12 static + runtime analysis tier.

Three families, each tested on seeded fixture violations (caught) and
clean fixtures (silent), plus the gate acceptance: the SHIPPED tree
has zero unbaselined findings, and the two dispatch audits pass over a
50-step serving decode steady state and a K=8 fused training window.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import tpudl
from tpudl.analysis import concurrency as conc
from tpudl.analysis import findings as F
from tpudl.analysis import registry as reg
from tpudl.analysis.dispatch import (
    DispatchHygieneError,
    RecompileWatcher,
    assert_no_host_transfers,
    assert_no_recompiles,
)
from tpudl.analysis.donation import (
    DonationError,
    assert_donation,
    audit_donation,
)
from tpudl.analysis.lint import lint_source, run_lint

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# concurrency: seeded violations caught, clean fixtures pass
# ---------------------------------------------------------------------------


def test_lock_order_inversion_direct_nesting_caught():
    src = textwrap.dedent(
        """
        import threading
        class T:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
            def one(self):
                with self._a:
                    with self._b:
                        pass
            def two(self):
                with self._b:
                    with self._a:
                        pass
        """
    )
    found = conc.analyze_source(src, "fix.py")
    assert _rules(found) == {"lock-order-inversion"}
    assert found[0].severity == "P0"
    assert "_a" in found[0].message and "_b" in found[0].message


def test_lock_order_inversion_through_method_call_caught():
    """one() holds _a and calls _grab_b(); two() holds _b and calls
    one() — the inversion only exists through the call graph."""
    src = textwrap.dedent(
        """
        import threading
        class T:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
            def one(self):
                with self._a:
                    self._grab_b()
            def _grab_b(self):
                with self._b:
                    pass
            def two(self):
                with self._b:
                    self.one()
        """
    )
    assert _rules(conc.analyze_source(src, "call.py")) == {
        "lock-order-inversion"
    }


def test_unguarded_shared_write_caught_and_init_excluded():
    src = textwrap.dedent(
        """
        import threading
        class T:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0      # construction: never a finding
            def bump(self):
                with self._lock:
                    self.n += 1
            def race(self):
                self.n = 5
        """
    )
    found = conc.analyze_source(src, "write.py")
    assert [f.rule for f in found] == ["unguarded-shared-write"]
    assert found[0].symbol == "T.race"


def test_container_mutation_counts_as_write():
    src = textwrap.dedent(
        """
        import threading
        class T:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []
            def push(self, x):
                with self._lock:
                    self.items.append(x)
            def race(self, x):
                self.items.append(x)
        """
    )
    assert _rules(conc.analyze_source(src, "mut.py")) == {
        "unguarded-shared-write"
    }


def test_condition_aliases_to_underlying_lock():
    """``with self._not_empty:`` counts as holding _lock — the
    bounded-queue idiom (prefetch) must analyze clean."""
    src = textwrap.dedent(
        """
        import threading
        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self._not_empty = threading.Condition(self._lock)
                self.items = []
            def put(self, x):
                with self._not_empty:
                    self.items.append(x)
            def reset(self):
                with self._lock:
                    self.items = []
        """
    )
    assert conc.analyze_source(src, "cond.py") == []


def test_private_method_inherits_callers_lock():
    """The "callers hold _books" idiom: a private helper written only
    under its callers' lock is not an unguarded write."""
    src = textwrap.dedent(
        """
        import threading
        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
            def bump(self):
                with self._lock:
                    self.n += 1
            def _helper(self):
                self.n = 5
            def outer(self):
                with self._lock:
                    self._helper()
        """
    )
    assert conc.analyze_source(src, "inherit.py") == []


def test_lockless_class_is_skipped():
    src = textwrap.dedent(
        """
        class Engine:
            def __init__(self):
                self.n = 0
            def step(self):
                self.n += 1
        """
    )
    assert conc.analyze_source(src, "engine.py") == []


def test_derive_lock_ranks_orders_acquisition_graph():
    src_path = os.path.join("/tmp", "tpudl_rank_fixture.py")
    with open(src_path, "w") as f:
        f.write(textwrap.dedent(
            """
            import threading
            class T:
                def __init__(self):
                    self._outer = threading.Lock()
                    self._inner = threading.Lock()
                def go(self):
                    with self._outer:
                        with self._inner:
                            pass
            """
        ))
    ranks = conc.derive_lock_ranks([src_path])
    assert ranks["T._outer"] < ranks["T._inner"]


# ---------------------------------------------------------------------------
# runtime lock-order monitor
# ---------------------------------------------------------------------------


def test_ordered_lock_detects_live_cycle():
    mon = conc.LockOrderMonitor()
    a = conc.OrderedLock(threading.Lock(), "A", mon)
    b = conc.OrderedLock(threading.Lock(), "B", mon)
    with a:
        with b:
            pass
    with pytest.raises(conc.LockOrderViolation, match="inversion"):
        with b:
            with a:
                pass


def test_ordered_lock_asserts_static_ranks():
    mon = conc.LockOrderMonitor(ranks={"A": 0, "B": 1})
    a = conc.OrderedLock(threading.Lock(), "A", mon)
    b = conc.OrderedLock(threading.Lock(), "B", mon)
    # The static ranks catch the inversion on its FIRST occurrence —
    # before any reverse path has ever run (which is what the live
    # cycle detector would need).
    with pytest.raises(conc.LockOrderViolation, match="static"):
        with b:
            with a:
                pass


def test_ordered_rlock_reentry_is_not_a_violation():
    mon = conc.LockOrderMonitor()
    r = conc.OrderedLock(threading.RLock(), "R", mon)
    with r:
        with r:
            pass
    assert mon.violations == []
    assert mon.acquisitions == 2


def test_wrap_instance_locks_wraps_locks_not_conditions():
    class Obj:
        def __init__(self):
            self._lock = threading.Lock()
            self._rlock = threading.RLock()
            self._cond = threading.Condition()

    obj = Obj()
    mon = conc.LockOrderMonitor()
    wrapped = conc.wrap_instance_locks(obj, mon)
    assert set(wrapped) == {"Obj._lock", "Obj._rlock"}
    assert isinstance(obj._lock, conc.OrderedLock)
    assert isinstance(obj._cond, threading.Condition)
    with obj._lock:  # still a working lock
        pass


def test_maybe_wrap_locks_is_noop_without_flag(monkeypatch):
    monkeypatch.delenv("TPUDL_DEBUG_LOCK_ORDER", raising=False)

    class Obj:
        def __init__(self):
            self._lock = threading.Lock()

    obj = Obj()
    assert conc.maybe_wrap_locks(obj) == []
    assert not isinstance(obj._lock, conc.OrderedLock)


# ---------------------------------------------------------------------------
# registry linter: seeded fixtures
# ---------------------------------------------------------------------------


def test_raw_env_read_caught_literal_and_constant():
    src = textwrap.dedent(
        """
        import os
        KNOB = "TPUDL_OBS_DIR"
        def direct():
            return os.environ.get("TPUDL_SERVE_SLOTS")
        def subscripted():
            return os.environ["TPUDL_OBS_DIR"]
        def via_constant():
            return os.environ.get(KNOB)
        """
    )
    found = lint_source(src, "raw.py")
    raws = [f for f in found if f.rule == "raw-env-read"]
    assert len(raws) == 3
    assert all(f.severity == "P0" for f in raws)


def test_env_write_and_non_tpudl_keys_pass():
    src = textwrap.dedent(
        """
        import os
        def ok():
            os.environ["TPUDL_SERVE_PAGE_SIZE"] = "64"   # a WRITE: pins
            flags = os.environ.get("XLA_FLAGS", "")
            return flags
        """
    )
    assert lint_source(src, "ok.py") == []


def test_undeclared_knob_literal_caught():
    src = 'FLAG = "TPUDL_TOTALLY_NEW_KNOB"\n'
    found = lint_source(src, "undecl.py")
    assert [f.rule for f in found] == ["undeclared-knob"]
    assert "TPUDL_TOTALLY_NEW_KNOB" in found[0].message


def test_bad_metric_name_caught_literal_and_fstring():
    src = textwrap.dedent(
        """
        def record(reg, suffix):
            reg.counter("serve ttft.ms").inc()
            reg.gauge(f"Replica-{suffix}_busy").set(1)
            reg.histogram("serve_ttft_ms").observe(1.0)
            reg.gauge(f"serve_replica_{suffix}_ready").set(1)
        """
    )
    found = lint_source(src, "metric.py")
    assert [f.rule for f in found] == [
        "bad-metric-name", "bad-metric-name"
    ]
    assert found[0].line == 3 and found[1].line == 4


# ---------------------------------------------------------------------------
# knob registry accessors
# ---------------------------------------------------------------------------


def test_env_accessors_semantics(monkeypatch):
    monkeypatch.setenv("TPUDL_SERVE_SLOTS", "8")
    assert reg.env_int("TPUDL_SERVE_SLOTS", 4) == 8
    monkeypatch.setenv("TPUDL_SERVE_SLOTS", "")
    assert reg.env_int("TPUDL_SERVE_SLOTS", 4) == 4  # empty == unset
    monkeypatch.setenv("TPUDL_SERVE_SLOTS", "zero")
    with pytest.raises(ValueError, match="TPUDL_SERVE_SLOTS"):
        reg.env_int("TPUDL_SERVE_SLOTS", 4)
    monkeypatch.setenv("TPUDL_SERVE_SLOTS", "0")
    with pytest.raises(ValueError, match=">= 1"):
        reg.env_int("TPUDL_SERVE_SLOTS", 4, min_value=1)
    for truthy in ("1", "true", "YES", "on"):
        monkeypatch.setenv("TPUDL_SERVE_PREFIX_SHARE", truthy)
        assert reg.env_flag("TPUDL_SERVE_PREFIX_SHARE")
    monkeypatch.setenv("TPUDL_SERVE_PREFIX_SHARE", "0")
    assert not reg.env_flag("TPUDL_SERVE_PREFIX_SHARE")
    monkeypatch.setenv("TPUDL_FT_GRACE_S", "2.5")
    assert reg.env_float("TPUDL_FT_GRACE_S", 15.0) == 2.5


def test_undeclared_knob_read_raises():
    with pytest.raises(reg.UnknownKnobError):
        reg.env_str("TPUDL_NOT_A_KNOB")


def test_knob_table_covers_every_declared_knob():
    table = reg.knob_table_markdown()
    for name in reg.KNOBS:
        assert f"`{name}`" in table, name


def test_readme_knob_table_is_in_sync():
    """The README embeds the GENERATED table between markers; drift
    fails here (and as an undocumented-knob lint finding)."""
    with open(os.path.join(REPO_ROOT, "README.md")) as f:
        readme = f.read()
    begin = "<!-- knob-table:begin -->\n"
    end = "<!-- knob-table:end -->"
    assert begin in readme and end in readme
    embedded = readme.split(begin, 1)[1].split(end, 1)[0]
    assert embedded == reg.knob_table_markdown(), (
        "README knob table drifted — regenerate with "
        "scripts/lint_tpudl.py --knob-table"
    )


# ---------------------------------------------------------------------------
# baseline ratchet
# ---------------------------------------------------------------------------


def _finding(msg="m", line=3):
    return F.Finding(
        rule="r", path="p.py", line=line, symbol="S.m", message=msg
    )


def test_fingerprint_survives_line_moves_not_message_changes():
    assert _finding(line=3).fingerprint == _finding(line=99).fingerprint
    assert _finding("a").fingerprint != _finding("b").fingerprint


def test_apply_baseline_new_known_stale():
    known = _finding("known")
    new = _finding("new")
    baseline = {
        known.fingerprint: F.BaselineEntry.from_finding(known, "ok"),
        "deadbeefdeadbeef": F.BaselineEntry(
            "deadbeefdeadbeef", "r", "gone.py", "S", "paid", "was fixed"
        ),
    }
    result = F.apply_baseline([known, new], baseline)
    assert not result.ok
    assert [f.message for f in result.new] == ["new"]
    assert [f.message for f in result.baselined] == ["known"]
    assert [e.fingerprint for e in result.stale] == ["deadbeefdeadbeef"]


def test_baseline_round_trip_preserves_justification(tmp_path):
    path = str(tmp_path / "baseline.json")
    entry = F.BaselineEntry.from_finding(
        _finding("debt"), "benign: single-writer publish"
    )
    F.save_baseline(path, [entry])
    loaded = F.load_baseline(path)
    assert loaded[entry.fingerprint].justification == (
        "benign: single-writer publish"
    )


# ---------------------------------------------------------------------------
# the gate on the shipped tree
# ---------------------------------------------------------------------------


def test_shipped_tree_has_zero_unbaselined_findings():
    """The ISSUE-12 acceptance bar: the analyzers run over the real
    tree and every finding is either fixed or baselined."""
    found = run_lint(REPO_ROOT)
    baseline_path = os.path.join(REPO_ROOT, "analysis_baseline.json")
    baseline = (
        F.load_baseline(baseline_path)
        if os.path.exists(baseline_path) else {}
    )
    result = F.apply_baseline(found, baseline)
    assert result.ok, "NEW findings:\n" + "\n".join(
        f.format() for f in result.new
    )
    assert not result.stale, (
        "stale baseline entries (debt was paid — delete them): "
        + ", ".join(e.fingerprint for e in result.stale)
    )


def test_lint_cli_exits_zero_on_tree_and_prints_knob_table():
    script = os.path.join(REPO_ROOT, "scripts", "lint_tpudl.py")
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    table = subprocess.run(
        [sys.executable, script, "--knob-table"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert table.returncode == 0
    assert table.stdout == reg.knob_table_markdown()
    js = subprocess.run(
        [sys.executable, script, "--json"],
        capture_output=True, text=True, cwd=REPO_ROOT,
    )
    assert js.returncode == 0
    doc = json.loads(js.stdout)
    assert doc["new"] == []


# ---------------------------------------------------------------------------
# dispatch hygiene: seeded violations + acceptance steady states
# ---------------------------------------------------------------------------


def test_assert_no_recompiles_catches_varying_shape():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4))  # warmup
    with pytest.raises(DispatchHygieneError, match="recompil"):
        with assert_no_recompiles():
            for n in range(5, 8):  # new shape per step: the seeded bug
                f(jnp.ones(n))


def test_assert_no_recompiles_passes_warm_loop():
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x)
    with assert_no_recompiles() as watcher:
        for _ in range(10):
            f(x)
    assert watcher.count == 0


def test_assert_no_host_transfers_catches_implicit_h2d():
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4, jnp.float32))
    with pytest.raises(DispatchHygieneError, match="implicit"):
        with assert_no_host_transfers():
            # np array into a jitted call = implicit h2d: the seeded
            # "host value leaked into the hot loop" bug.
            f(np.ones(4, np.float32))


def test_assert_no_host_transfers_allowance_and_explicit_pass():
    f = jax.jit(lambda x: x + 1)
    x = jnp.ones(4, jnp.float32)
    f(x)
    with assert_no_host_transfers(allow=("h2d",)):
        f(np.ones(4, np.float32))  # allowed direction
    with assert_no_host_transfers():
        y = f(jax.device_put(np.ones(4, np.float32)))  # explicit: fine
    assert jax.device_get(y).shape == (4,)
    with pytest.raises(ValueError, match="unknown transfer kinds"):
        with assert_no_host_transfers(allow=("sideways",)):
            pass


def test_serving_decode_steady_state_is_dispatch_clean():
    """ISSUE-12 acceptance: a 50-step serving decode steady state
    neither recompiles nor implicitly transfers (beyond the per-step
    h2d control arrays, which are by design — every intended readback
    in the engine is an explicit jax.device_get)."""
    from tests.serve_helpers import build_session, warmup_session
    from tpudl.serve import Request

    session = build_session(num_slots=2)
    warmup_session(session)
    steps0 = session.engine.num_decode_steps
    # 52 new tokens = 1 from prefill + 51 decode steps: the audited
    # window spans >= 50 decode dispatches.
    requests = [
        Request("steady0", [5, 6, 7], max_new_tokens=52),
        Request("steady1", [9, 4], max_new_tokens=30),
    ]
    with assert_no_recompiles(label="serve decode steady state"):
        with assert_no_host_transfers(
            allow=("h2d",), label="serve decode steady state"
        ):
            results = session.serve(requests)
    assert session.engine.num_decode_steps - steps0 >= 50
    assert all(r.ok for r in results.values())


def test_fused_training_window_is_dispatch_clean():
    """ISSUE-12 acceptance: one K=8 fused dispatch window (device-
    resident inputs, donated carry) runs with zero recompiles and
    zero implicit transfers in ANY direction after warmup."""
    from tpudl.models.bert import BertConfig, BertForSequenceClassification
    from tpudl.runtime.mesh import MeshSpec, make_mesh
    from tpudl.train.loop import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )

    cfg = BertConfig(
        vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
        intermediate_size=32, hidden_dropout=0.0, attention_dropout=0.0,
        dtype=jnp.float32,
    )
    model = BertForSequenceClassification(cfg)
    state = create_train_state(
        jax.random.key(0), model, jnp.zeros((1, 8), jnp.int32),
        optax.adamw(1e-3),
    )
    mesh = make_mesh(MeshSpec(dp=-1))
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"), label_key="label"
        ),
        mesh, state, None, steps_per_dispatch=8,
    )
    state = jax.device_put(state, step.state_shardings)
    rng_np = np.random.default_rng(0)
    # Batch 8: divisible by the fake 8-device dp mesh the test env
    # forces (XLA_FLAGS host platform device count).
    window = {
        "input_ids": rng_np.integers(0, 64, (8, 8, 8)).astype(np.int32),
        "attention_mask": np.ones((8, 8, 8), np.int32),
        "label": rng_np.integers(0, 2, (8, 8)).astype(np.int32),
    }
    window = jax.device_put(window)  # explicit H2D, outside the audit
    rng = jax.random.key(1)
    state, _ = step.window_step(state, window, rng)  # warmup compile
    with assert_no_recompiles(label="K=8 fused window"):
        with assert_no_host_transfers(label="K=8 fused window"):
            state, stacked = step.window_step(state, window, rng)
    assert np.asarray(jax.device_get(stacked["loss"])).shape == (8,)


def test_recompile_watcher_counts_without_raising():
    f = jax.jit(lambda x: x - 1)
    f(jnp.ones(3))
    with RecompileWatcher() as w:
        f(jnp.ones(6))
    assert w.count >= 1
    assert w.count == w.count  # stable after exit


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------


def test_audit_donation_passes_on_donating_program():
    g = jax.jit(lambda s: jax.tree.map(lambda x: x + 1, s),
                donate_argnums=0)
    s = jax.device_put({"w": jnp.ones((64, 64)), "b": jnp.ones(64)})
    out, report = audit_donation(g, (s,))
    assert report.ok and report.num_deleted == 2
    assert jax.device_get(out["b"])[0] == 2.0


def test_audit_donation_catches_lost_donation():
    h = jax.jit(lambda s: jax.tree.map(lambda x: x + 1, s))  # no donation
    s = jax.device_put({"w": jnp.ones((64, 64))})
    _, report = audit_donation(h, (s,))
    assert not report.ok
    assert report.undeleted  # names the copied leaves
    s2 = jax.device_put({"w": jnp.ones((64, 64))})
    with pytest.raises(DonationError, match="NOT consumed"):
        assert_donation(h, (s2,))
