"""The CLAY shard reference (perfbench/clay_reference.py) on the CPU.

It must agree with the program's host CLAY codec byte for byte, differ
from plain RS in exactly the m parity shards of an object (the control
that shows the check tells the codes apart), count the work of any
systematic code, and import nothing of the program.
"""

from __future__ import annotations

import subprocess
import sys

import numpy as np
import pytest

from perfbench import clay_reference, harness, reference, verify, work

CFG = harness.load_json(harness.ROOT / "perfbench/configs/objstore_clay84.json")


@pytest.mark.parametrize("k,m,d", [(8, 4, 11), (4, 2, 5), (5, 3, 7)])
def test_clay_reference_equals_host_codec(k, m, d):
    from ceph_tpu.codec import registry

    ec = registry.instance().factory(
        "clay", {"k": str(k), "m": str(m), "d": str(d), "scalar_mds": "isa"})
    g = clay_reference.Geometry(k, m, d)
    data = np.random.default_rng(k + m + d).integers(
        0, 256, (2, k, g.planes * 64), dtype=np.uint8)
    parity = clay_reference.encode_parity(data, g)
    for s in range(2):
        chunks = ec.encode(set(range(k + m)), data[s].reshape(-1))
        assert all(np.array_equal(chunks[k + j], parity[s, j]) for j in range(m))


def test_clay_object_differs_from_rs_in_its_parity_shards():
    payload = np.random.default_rng(3).integers(0, 256, 3 << 16, dtype=np.uint8).tobytes()
    shards = clay_reference.object_shards(payload, CFG)
    copies = {s: [b] for s, b in enumerate(shards)}
    assert len(shards) == clay_reference.chunk_count(CFG) == 12
    assert verify.shard_mismatches(clay_reference, CFG, copies, payload) == 0
    assert verify.shard_mismatches(reference, CFG, copies, payload) == CFG["m"]
    rs = reference.object_shards(payload, CFG)
    assert shards[: CFG["k"]] == rs[: CFG["k"]]


def test_clay_work_counts_by_hand():
    # a 4 MiB object is 128 stripes of 32 KiB; each moves 12 chunks of 4 KiB
    assert work.stripes_per_object(clay_reference, CFG, 4 << 20) == 128
    assert clay_reference.encode_bytes(CFG, 128) == 128 * 12 * 4096
    assert clay_reference.decode_bytes(CFG, 128, 1) == 128 * 9 * 4096


def test_clay_reference_imports_nothing_of_the_program():
    code = ("import sys; import perfbench.clay_reference; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] == 'ceph_tpu'))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(harness.ROOT),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "[]"
