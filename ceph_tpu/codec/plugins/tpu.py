"""The `tpu` plugin — registers the flagship RS codec.

Reference plugin shell analog: /root/reference/src/erasure-code/isa/
ErasureCodePluginIsa.cc (technique selection :40-57) rebuilt for the TPU
codec.  Profile keys: k, m, technique in {reed_sol_van, cauchy}.
"""

from ceph_tpu.codec.registry import EC_VERSION, ErasureCodePlugin
from ceph_tpu.codec.rs import CAUCHY, VANDERMONDE, ErasureCodeTpuRs
from ceph_tpu.codec.tracing import instrument_codec

__erasure_code_version__ = EC_VERSION


def _factory(profile):
    technique = profile.get("technique") or VANDERMONDE
    ec = ErasureCodeTpuRs(technique=technique)
    ec.init(profile)
    # codec sub-spans on the device paths when an op trace is active
    # (codec/tracing.py); free when tracing is off
    return instrument_codec(ec, "tpu")


def __erasure_code_init__(registry):
    registry.add("tpu", ErasureCodePlugin("tpu", _factory))
