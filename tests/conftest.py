"""Test harness config: force an 8-device virtual CPU mesh.

Tests never assume real TPU hardware; sharding/collective paths are validated
on `--xla_force_host_platform_device_count=8` exactly as the driver's
multi-chip dry-run does.  The platform is pinned through jax.config as
well as the environment, so a process that already imported jax still
lands on the CPU.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Dynamic lock-order validation ON for the whole tier-1 suite (ISSUE 12):
# every make_lock/make_rlock/make_async_lock acquisition across the
# aggregator/scheduler/pipeline/cache stack validates against the
# observed ordering graph, so a latent deadlock introduced anywhere
# fails the suite even if the losing interleaving never runs — the
# reference's -DCEPH_DEBUG_MUTEX lockdep tier (PAPER.md layer 1).
# Set CEPH_TPU_LOCKDEP=0 explicitly to debug with validation off.
if os.environ.get("CEPH_TPU_LOCKDEP", "") != "0":
    os.environ["CEPH_TPU_LOCKDEP"] = "1"
    from ceph_tpu.common import lockdep  # noqa: E402

    lockdep.enable()

# HBM leak gate ON for the whole tier-1 suite (ISSUE 13, like lockdep):
# every test must leave the EC launch pipelines drained — the
# `ec_pipeline_inflight` and `verify` mempool pools read zero at
# teardown, or the test leaked a device hold (the host-fallback /
# sticky-error shapes the ledger exists to expose).  The drain step
# first settles anything legitimately still in flight (a depth-N ring
# the test simply didn't reap), so only holds that survive a full
# settle count as leaks.
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _hbm_leak_gate():
    yield
    from ceph_tpu.common.mempool import ledger

    led = ledger()

    def _held() -> int:
        return (
            led.current_bytes("ec_pipeline_inflight")
            + led.current_bytes("verify")
            + led.current_bytes("offload_inflight")
        )

    leaked = _held()
    if leaked:
        from ceph_tpu.codec.matrix_codec import drain_all_aggregators

        try:
            drain_all_aggregators()
        except Exception:
            pass  # sticky launch errors still settle; re-measure below
        leaked = _held()
    assert leaked == 0, (
        f"HBM ledger leak: {leaked} bytes still held in the EC launch "
        f"pools after drain (reconcile: {led.reconcile()})"
    )
