"""On-TPU correctness tier harness.

Unlike tests/conftest.py (which forces a virtual CPU mesh), this tier
runs against the REAL chip: the Pallas SWAR kernel non-interpret for
every tile geometry, decode matrices from the signature LRU, CLAY
coupling transforms, and a 1-device shard_map of the production sharded
entry point — bytes compared against the host GF oracle (the
exhaustive-erasure gtest pattern,
/root/reference/src/test/erasure-code/TestErasureCodeIsa.cc:51-90).

Gating: the whole tier is left uncollected unless ONCHIP=1 is exported.
It needs the chip, and a chip belongs to one process: the tier runs on
its own through the chip tool
(`chiprun -- env ONCHIP=1 python -m pytest tests_tpu -q`), never inside
the CPU suite, whose workers must not import jax for the TPU.
"""

import os

import pytest


def pytest_ignore_collect(collection_path, config):
    # Gate BEFORE collection: importing a test module here pulls in jax
    # (via ceph_tpu.ops), and only an ONCHIP run may bring up the TPU.
    if os.environ.get("ONCHIP") != "1":
        return True
    return None


@pytest.fixture(scope="session")
def tpu():
    """The real TPU device; the tier fails (not skips) without one."""
    import jax

    dev = jax.devices()[0]
    assert dev.platform == "tpu", f"ONCHIP=1 but the backend is {dev.platform}"
    return dev
