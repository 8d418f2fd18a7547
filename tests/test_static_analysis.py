"""Static-analysis framework tier (ISSUE 12).

Three layers:

1. Seeded fixtures per pass: a snippet that MUST trip the pass and a
   twin that MUST pass — the linter's own regression suite, so a pass
   that silently stops detecting its bug class fails here, not in a
   production PR.
2. Framework contracts: allowlist round-trip (reason mandatory, stale
   entries fail), CLI exit codes, JSON report shape.
3. The live gate: `run_analysis()` over the real package must be clean
   — a new violation anywhere in ceph_tpu/ fails tier-1 (the CI wiring
   the ISSUE asks for), alongside a dynamic-lockdep regression that
   replays the aggregator→scheduler→pipeline→cache lock stack.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from ceph_tpu.analysis import (
    ALLOWLIST_DIR,
    SourceTree,
    load_allowlist,
    run_analysis,
)
from ceph_tpu.analysis.passes import ALL_PASSES, PASS_BY_ID
from ceph_tpu.analysis.passes.donation import DonationLifetimePass
from ceph_tpu.analysis.passes.exceptions import ExceptionSwallowPass
from ceph_tpu.analysis.passes.ledger import LedgerDisciplinePass
from ceph_tpu.analysis.passes.locks import LockDisciplinePass
from ceph_tpu.analysis.passes.options_coherence import OptionsCoherencePass
from ceph_tpu.analysis.passes.purity import JitPurityPass
from ceph_tpu.analysis.passes.section_await import SectionAwaitPass

REPO = Path(__file__).resolve().parent.parent


def _tree(tmp_path: Path, files: dict[str, str]) -> SourceTree:
    """Materialize {relpath: source} as a package tree for a pass."""
    root = tmp_path / "pkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return SourceTree(root, repo_root=tmp_path)


def _keys(findings):
    return {f.key for f in findings}


class TestDonationPass:
    def test_read_after_donation_trips(self, tmp_path):
        tree = _tree(tmp_path, {"ops/k.py": """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(buf):
                return buf + 1

            def caller(buf):
                out = step(buf)
                return buf.sum()  # use-after-donation
        """})
        findings = DonationLifetimePass()(tree)
        assert any("::caller::buf" in k for k in _keys(findings)), findings

    def test_rebind_idiom_passes(self, tmp_path):
        tree = _tree(tmp_path, {"ops/k.py": """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(buf):
                return buf + 1

            def caller(buf):
                buf = step(buf)   # donated name rebound to the result
                return buf.sum()  # fresh buffer: fine
        """})
        assert DonationLifetimePass()(tree) == []

    def test_sibling_branch_is_not_after(self, tmp_path):
        tree = _tree(tmp_path, {"ops/k.py": """
            import jax

            def caller(f, buf, fast):
                if fast:
                    exe = jax.jit(f, donate_argnums=(0,))
                    out = exe(buf)
                else:
                    out = f(buf)  # other branch: buf not donated here
                return out
        """})
        assert DonationLifetimePass()(tree) == []

    def test_factory_donate_true_trips(self, tmp_path):
        tree = _tree(tmp_path, {"parallel/s.py": """
            def caller(build, placed):
                result = build(donate=True)(placed)
                return placed[0]  # donated via the factory call
        """})
        findings = DonationLifetimePass()(tree)
        assert any("placed" in k for k in _keys(findings)), findings

    def test_offload_runtime_module_in_scope(self, tmp_path):
        """ISSUE 20: the hoisted offload runtime is covered exactly like
        the codec module its machinery came from."""
        tree = _tree(tmp_path, {"ops/offload_runtime.py": """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def launch(buf):
                return buf + 1

            def reap(buf):
                out = launch(buf)
                return buf.nbytes  # use-after-donation
        """})
        findings = DonationLifetimePass()(tree)
        assert any("::reap::buf" in k for k in _keys(findings)), findings

    def test_compressor_service_module_in_scope(self, tmp_path):
        """ISSUE 20: a compressor-package service module donating into
        its batched transform is covered too."""
        tree = _tree(tmp_path, {"compressor/device.py": """
            import functools, jax

            @functools.partial(jax.jit, donate_argnums=(0,))
            def transform(rows):
                return rows + 1

            def compress_batch(rows):
                out = transform(rows)
                return rows.sum()  # use-after-donation
        """})
        findings = DonationLifetimePass()(tree)
        assert any(
            "::compress_batch::rows" in k for k in _keys(findings)
        ), findings


class TestPurityPass:
    @pytest.mark.parametrize("body,what", [
        ("t = time.time()", "clock"),
        ("r = np.random.random()", "RNG"),
        ("lock.acquire()", "lock"),
        ("faultpoint('codec.launch')", "faultpoint"),
        ("counters.inc('launches')", "counter"),
    ])
    def test_impurity_inside_jit_trips(self, tmp_path, body, what):
        tree = _tree(tmp_path, {"ops/k.py": f"""
            import time, jax
            import numpy as np

            @jax.jit
            def kernel(x, lock=None, counters=None):
                {body}
                return x
        """})
        findings = JitPurityPass()(tree)
        assert findings, f"{what} inside @jax.jit not detected"

    def test_pure_jit_passes(self, tmp_path):
        tree = _tree(tmp_path, {"ops/k.py": """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def kernel(x):
                return jnp.sum(x ^ jnp.uint8(3))
        """})
        assert JitPurityPass()(tree) == []

    def test_host_code_outside_scope_dirs_ignored(self, tmp_path):
        # same impurity in mgr/ — the pass is scoped to the kernel dirs
        tree = _tree(tmp_path, {"mgr/m.py": """
            import time, jax

            @jax.jit
            def kernel(x):
                return time.time()
        """})
        assert JitPurityPass()(tree) == []

    def test_wrapped_local_def_trips(self, tmp_path):
        tree = _tree(tmp_path, {"codec/c.py": """
            import time, jax

            def build():
                def local(x):
                    time.monotonic()
                    return x
                return jax.jit(local)
        """})
        assert JitPurityPass()(tree), "jax.jit(local) closure not traced"


class TestExceptionPass:
    def test_silent_swallow_trips(self, tmp_path):
        tree = _tree(tmp_path, {"osd/x.py": """
            def f(store):
                try:
                    return store.read()
                except Exception:
                    pass
        """})
        findings = ExceptionSwallowPass()(tree)
        assert len(findings) == 1
        assert findings[0].key == "pkg/osd/x.py::f"

    @pytest.mark.parametrize("handler", [
        "raise",
        "dout('osd', 1, 'boom')",
        "perf.inc('errors')",
        "errors += 1",
        "return repr(e)",
        "guard.mark_degraded('x')",
    ])
    def test_traced_handlers_pass(self, tmp_path, handler):
        tree = _tree(tmp_path, {"osd/x.py": f"""
            def f(store, perf, guard, dout, errors=0):
                try:
                    return store.read()
                except Exception as e:
                    {handler}
        """})
        assert ExceptionSwallowPass()(tree) == []

    def test_narrow_except_ignored(self, tmp_path):
        tree = _tree(tmp_path, {"osd/x.py": """
            def f(store):
                try:
                    return store.read()
                except KeyError:
                    pass
        """})
        assert ExceptionSwallowPass()(tree) == []


class TestLockPass:
    @pytest.mark.parametrize("ctor", [
        "threading.Lock()", "threading.RLock()", "asyncio.Lock()",
        "threading.Condition()",
    ])
    def test_bare_lock_trips(self, tmp_path, ctor):
        tree = _tree(tmp_path, {"ops/x.py": f"""
            import asyncio, threading

            class C:
                def __init__(self):
                    self._lock = {ctor}
        """})
        assert LockDisciplinePass()(tree), f"bare {ctor} not detected"

    def test_factory_lock_passes(self, tmp_path):
        tree = _tree(tmp_path, {"ops/x.py": """
            from ceph_tpu.common.lockdep import make_lock

            class C:
                def __init__(self):
                    self._lock = make_lock("c")
        """})
        assert LockDisciplinePass()(tree) == []

    def test_condition_wrapping_factory_lock_passes(self, tmp_path):
        tree = _tree(tmp_path, {"ops/x.py": """
            import threading
            from ceph_tpu.common.lockdep import make_lock

            cv = threading.Condition(make_lock("cv"))
        """})
        assert LockDisciplinePass()(tree) == []

    def test_device_wait_under_lock_trips(self, tmp_path):
        tree = _tree(tmp_path, {"ops/x.py": """
            def f(self, buf):
                with self._lock:
                    jax.block_until_ready(buf)
        """})
        findings = LockDisciplinePass()(tree)
        assert any("wait.block_until_ready" in k for k in _keys(findings))


class TestOptionsPass:
    OPTS = {
        "knob_read": {"runtime": False},
        "knob_unread": {"runtime": False},
        "knob_rt_wired": {"runtime": True},
        "knob_rt_initonly": {"runtime": True},
    }

    def _pass(self):
        return OptionsCoherencePass(options=dict(self.OPTS))

    def _files(self):
        return {
            "common/options.py": """
                OPTIONS = {}  # synthetic table injected into the pass
            """,
            "osd/daemon.py": """
                class D:
                    def __init__(self, conf):
                        self.a = conf.get("knob_rt_initonly")
                        self.b = conf.get("knob_rt_wired")
                        conf.add_observer(
                            ["knob_rt_wired"], lambda n, v: None
                        )

                    def serve(self, conf):
                        return conf.get("knob_read")

                    def typo(self, conf):
                        return conf.get("knob_typod")
            """,
        }

    def _docs(self, tmp_path):
        docs = tmp_path / "docs"
        docs.mkdir(exist_ok=True)
        (docs / "OPTIONS.md").write_text(
            "`knob_read` `knob_unread` `knob_rt_wired` `knob_rt_initonly`"
        )

    def test_all_four_checks(self, tmp_path):
        tree = _tree(tmp_path, self._files())
        self._docs(tmp_path)
        keys = _keys(self._pass()(tree))
        assert "unread::knob_unread" in keys
        assert "unwired-runtime::knob_rt_initonly" in keys
        assert "unregistered-read::knob_typod" in keys
        # the observer-wired and live-read knobs are clean
        assert "unwired-runtime::knob_rt_wired" not in keys
        assert "unread::knob_read" not in keys
        # every option IS documented in the synthetic docs page
        assert not any(k.startswith("undocumented::") for k in keys)

    def test_undocumented_trips_without_docs(self, tmp_path):
        tree = _tree(tmp_path, self._files())
        keys = _keys(self._pass()(tree))
        assert "undocumented::knob_read" in keys


class TestLedgerPass:
    """ledger-discipline (ISSUE 13): device_put in the data-path
    packages must be threaded through a mempool-tracked helper."""

    def test_untracked_device_put_trips(self, tmp_path):
        tree = _tree(tmp_path, {"ops/stage.py": """
            import jax

            def stage(arr):
                return jax.device_put(arr)
        """})
        findings = LedgerDisciplinePass()(tree)
        assert any("::stage::device_put" in k for k in _keys(findings)), (
            findings
        )

    def test_offload_runtime_untracked_device_put_trips(self, tmp_path):
        """ISSUE 20: the offload runtime and its service modules are in
        scope — a bare device_put in ops/offload_runtime.py trips."""
        tree = _tree(tmp_path, {"ops/offload_runtime.py": """
            import jax

            def dispatch(batch):
                return jax.device_put(batch)
        """})
        findings = LedgerDisciplinePass()(tree)
        assert any(
            "::dispatch::device_put" in k for k in _keys(findings)
        ), findings

    def test_compressor_untracked_device_put_trips(self, tmp_path):
        """ISSUE 20: compressor/ joined the scoped data-path packages —
        the device plugin's placements must be ledger-tracked."""
        tree = _tree(tmp_path, {"compressor/device.py": """
            import jax

            def transform_rows_device(rows):
                return jax.device_put(rows)
        """})
        findings = LedgerDisciplinePass()(tree)
        assert any(
            "::transform_rows_device::device_put" in k
            for k in _keys(findings)
        ), findings

    def test_track_buffer_wrapper_passes(self, tmp_path):
        tree = _tree(tmp_path, {"parallel/place.py": """
            import jax
            from ceph_tpu.common.mempool import track_buffer

            def place(arr, sharding):
                return track_buffer(
                    jax.device_put(arr, sharding), "sharded_placement"
                )
        """})
        assert not LedgerDisciplinePass()(tree)

    def test_explicit_alloc_handle_passes(self, tmp_path):
        tree = _tree(tmp_path, {"ops/cache.py": """
            import jax

            def put(self, arr):
                buf = jax.device_put(arr)
                self.mem = ledger().alloc("device_cache", arr.nbytes, buf=buf)
                return buf
        """})
        assert not LedgerDisciplinePass()(tree)

    def test_unrelated_alloc_does_not_silence(self, tmp_path):
        """Only a LEDGER alloc counts: an `.alloc` on an arbitrary
        receiver must not excuse a bare device_put."""
        tree = _tree(tmp_path, {"ops/arena.py": """
            import jax

            def stage(self, arr):
                slot = self.arena.alloc(arr.nbytes)
                return jax.device_put(arr)
        """})
        findings = LedgerDisciplinePass()(tree)
        assert any("::stage::device_put" in k for k in _keys(findings)), (
            findings
        )

    def test_untracked_sibling_of_tracked_put_still_trips(self, tmp_path):
        """One wrapped placement must not silence a bare one next to
        it — wrapping is a per-call property."""
        tree = _tree(tmp_path, {"ops/mixed.py": """
            import jax
            from ceph_tpu.common.mempool import track_buffer

            def stage(a, b):
                placed = track_buffer(jax.device_put(a), "scratch")
                return placed, jax.device_put(b)
        """})
        findings = LedgerDisciplinePass()(tree)
        assert any("::stage::device_put" in k for k in _keys(findings)), (
            findings
        )

    def test_keyword_wrapped_device_put_passes(self, tmp_path):
        tree = _tree(tmp_path, {"ops/kw.py": """
            import jax
            from ceph_tpu.common.mempool import track_buffer

            def place(arr):
                return track_buffer(buf=jax.device_put(arr))
        """})
        assert not LedgerDisciplinePass()(tree)

    def test_out_of_scope_packages_ignored(self, tmp_path):
        tree = _tree(tmp_path, {"mgr/module.py": """
            import jax

            def stage(arr):
                return jax.device_put(arr)
        """})
        assert not LedgerDisciplinePass()(tree)


class TestSectionAwaitPass:
    """section-await: a layer section must not span a suspension point
    of the shared asyncio loop."""

    @pytest.mark.parametrize("body,kind", [
        ("await conn.send(msg)", "await"),
        ("async with lock: pass", "async with"),
        ("async for m in stream: pass", "async for"),
    ])
    def test_suspension_inside_section_trips(self, tmp_path, body, kind):
        tree = _tree(tmp_path, {"msg/x.py": f"""
            from ceph_tpu.common.tracer import section

            async def send(conn, msg, lock, stream):
                with section("msgr.encode"):
                    {body}
        """})
        keys = _keys(SectionAwaitPass()(tree))
        assert keys == {f"pkg/msg/x.py::send::{kind}"}, keys

    def test_layer_section_and_attribute_form_trip(self, tmp_path):
        tree = _tree(tmp_path, {"os/x.py": """
            from ceph_tpu.common import tracer

            async def read(store):
                with tracer.layer_section("crc32c"):
                    return await store.read()
        """})
        assert len(SectionAwaitPass()(tree)) == 1

    def test_sectioned_async_def_trips(self, tmp_path):
        tree = _tree(tmp_path, {"osd/x.py": """
            from ceph_tpu.common.tracer import sectioned

            @sectioned("osd.op")
            async def run(item):
                await item()
        """})
        assert _keys(SectionAwaitPass()(tree)) == {"pkg/osd/x.py::run::async def"}

    def test_sync_section_and_await_outside_pass(self, tmp_path):
        """The idiom the tree uses: close the section, then await; a
        coroutine only DEFINED in the block does not run there."""
        tree = _tree(tmp_path, {"msg/x.py": """
            from ceph_tpu.common.tracer import section, sectioned

            async def send(writer, frame):
                with section("msgr.encode"):
                    raw = frame.pack()
                    writer.write(raw)

                    async def later():
                        await writer.drain()
                await writer.drain()

            @sectioned("osd.pg")
            def do_op(msg):
                return msg
        """})
        assert SectionAwaitPass()(tree) == []


class TestAllowlist:
    def test_reason_mandatory(self, tmp_path):
        p = tmp_path / "x.allow"
        p.write_text("some::key\n")
        with pytest.raises(ValueError, match="no\\s+reason"):
            load_allowlist(p)
        p.write_text("some::key |   \n")
        with pytest.raises(ValueError, match="no\\s+reason"):
            load_allowlist(p)

    def test_round_trip_and_stale_detection(self, tmp_path):
        tree = _tree(tmp_path, {"osd/x.py": """
            def f(store):
                try:
                    return store.read()
                except Exception:
                    pass
        """})
        adir = tmp_path / "allow"
        adir.mkdir()
        # 1) unallowlisted -> finding
        report = run_analysis(tree, passes=[ExceptionSwallowPass()],
                              allowlist_dir=adir)
        assert not report["ok"]
        key = report["findings"][0]["key"]
        # 2) allowlisted with a reason -> clean, and the reason rides
        (adir / "exception-swallowing.allow").write_text(
            f"{key} | fixture: silence is the point\n"
        )
        report = run_analysis(tree, passes=[ExceptionSwallowPass()],
                              allowlist_dir=adir)
        assert report["ok"], report
        assert report["allowlisted"][0]["reason"].startswith("fixture")
        # 3) stale entry (code fixed, suppression left behind) -> fails
        clean = _tree(tmp_path / "clean", {"osd/x.py": "def f():\n    pass\n"})
        report = run_analysis(clean, passes=[ExceptionSwallowPass()],
                              allowlist_dir=adir)
        assert not report["ok"]
        assert report["stale_allowlist"], report

    def test_real_allowlists_parse_with_reasons(self):
        for p in ALL_PASSES:
            path = ALLOWLIST_DIR / f"{p.PASS_ID}.allow"
            entries = load_allowlist(path)
            for key, reason in entries.items():
                assert len(reason) > 20, (
                    f"{p.PASS_ID}: allowlist reason for {key!r} is too "
                    "thin to justify a suppression"
                )


class TestCli:
    def _run(self, *args, cwd=None):
        return subprocess.run(
            [sys.executable, "-m", "ceph_tpu.analysis", *args],
            capture_output=True, text=True, cwd=cwd or REPO, timeout=300,
        )

    def test_list_inventory(self):
        r = self._run("--list")
        assert r.returncode == 0
        for pid in PASS_BY_ID:
            assert pid in r.stdout

    def test_clean_tree_exits_zero_with_json(self, tmp_path):
        out = tmp_path / "report.json"
        r = self._run("--json", str(out))
        assert r.returncode == 0, r.stdout + r.stderr
        report = json.loads(out.read_text())
        assert report["ok"] is True
        assert set(report["passes"]) == set(PASS_BY_ID)

    SEEDS = {
        "lock-discipline": "import threading\nL = threading.Lock()\n",
        "exception-swallowing": (
            "def f(s):\n    try:\n        return s.read()\n"
            "    except Exception:\n        pass\n"
        ),
        "jit-purity": (
            "import time, jax\n\n@jax.jit\ndef k(x):\n"
            "    time.time()\n    return x\n"
        ),
        "section-await": (
            "from ceph_tpu.common.tracer import section\n\n"
            "async def f(w):\n    with section('msgr.encode'):\n"
            "        await w.drain()\n"
        ),
        "donation-lifetime": (
            "import functools, jax\n\n"
            "@functools.partial(jax.jit, donate_argnums=(0,))\n"
            "def step(b):\n    return b\n\n"
            "def caller(b):\n    out = step(b)\n    return b.sum()\n"
        ),
    }

    @pytest.mark.parametrize("pass_id", sorted(SEEDS))
    def test_seeded_violation_exits_nonzero(self, tmp_path, pass_id):
        """The exit-code contract, end to end: `python -m
        ceph_tpu.analysis --root <seeded tree> --pass <id>` exits 1 and
        names the pass."""
        root = tmp_path / "pkg"
        (root / "ops").mkdir(parents=True)
        (root / "ops" / "x.py").write_text(self.SEEDS[pass_id])
        r = self._run("--root", str(root), "--pass", pass_id)
        assert r.returncode == 1, (pass_id, r.stdout, r.stderr)
        assert pass_id in r.stdout

    def test_seeded_config_violation_exits_nonzero(self, tmp_path):
        """config-coherence via the CLI: a typo'd conf.get on a foreign
        tree (no other files, so only the unregistered-read finding plus
        table-side findings can fire)."""
        root = tmp_path / "pkg"
        (root / "osd").mkdir(parents=True)
        (root / "osd" / "x.py").write_text(
            "def f(conf):\n    return conf.get('no_such_knob_xyz')\n"
        )
        r = self._run("--root", str(root), "--pass", "config-coherence")
        assert r.returncode == 1
        assert "unregistered-read::no_such_knob_xyz" in r.stdout


class TestLiveTreeGate:
    """The CI wiring: the real package must stay clean — a new finding
    anywhere in ceph_tpu/ fails tier-1 here."""

    def test_package_runs_clean(self):
        report = run_analysis()
        msgs = [
            f"{f['file']}:{f['line']}: [{f['pass']}] {f['message']}"
            for f in report["findings"]
        ] + [s["message"] for s in report["stale_allowlist"]]
        assert report["ok"], (
            "static analysis found unallowlisted violations:\n"
            + "\n".join(msgs)
        )
        # every pass actually executed against the live tree
        assert set(report["passes"]) == set(PASS_BY_ID)


class TestLockdepStackRegression:
    """Dynamic half of the tentpole: replay the aggregator → launch
    scheduler → pipeline-gauge → device-cache → perf-counter lock stack
    with lockdep ON and assert the ordering graph is acyclic-consistent
    (zero violations) and actually engaged."""

    def test_aggregated_encode_stack_is_clean(self):
        from ceph_tpu.codec import ErasureCodeTpuRs
        from ceph_tpu.codec.matrix_codec import EncodeAggregator
        from ceph_tpu.common import lockdep
        from ceph_tpu.ops.device_cache import device_chunk_cache

        assert lockdep.enabled(), "tier-1 must run with CEPH_TPU_LOCKDEP=1"
        violations0 = lockdep.violations()
        ec = ErasureCodeTpuRs()
        ec.init({"k": "4", "m": "2"})
        agg = EncodeAggregator(window=4, pipeline_depth=2)
        rng = np.random.default_rng(7)
        tickets = [
            agg.submit(
                ec, rng.integers(0, 256, (2, 4, 512)).astype(np.uint8)
            )
            for _ in range(8)
        ]
        agg.flush()
        for t in tickets:
            np.asarray(t.result())
        # touch the device cache (the cache lock participates too)
        cache = device_chunk_cache()
        cache.put("lockdep-oid", 0, 1, np.zeros(256, dtype=np.uint8))
        cache.get("lockdep-oid", 0, 1)
        cache.invalidate_object("lockdep-oid")
        assert lockdep.violations() == violations0, (
            "lock-order violation in the aggregated encode stack"
        )
        edges = lockdep.edges()
        assert edges, "instrumented locks never engaged"
        # the aggregator lock is held around perf accounting — the
        # canonical edge that proves the stack is instrumented end to end
        assert any("ec_aggregator" in src for src in edges), edges

    def test_inverted_order_still_raises_and_counts(self):
        from ceph_tpu.common import lockdep
        from ceph_tpu.common.lockdep import (
            DebugLock,
            LockOrderError,
            make_rlock,
        )

        v0 = lockdep.violations()
        a, b = DebugLock("SA12_A"), DebugLock("SA12_B")
        with a:
            with b:
                pass
        with b:
            with pytest.raises(LockOrderError):
                a.acquire()
        assert lockdep.violations() == v0 + 1
        # make_rlock: reentrant on the same instance, still validated
        # for cross-lock ordering on the outermost acquire
        r = make_rlock("SA12_R")
        with r:
            with r:  # no self-deadlock false positive
                pass
        with b:
            with r:  # establishes SA12_B -> SA12_R
                pass
        with r:
            with pytest.raises(LockOrderError):
                b.acquire()  # inversion: SA12_R -> SA12_B
        assert lockdep.violations() == v0 + 2
