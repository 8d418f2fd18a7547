"""The `native` plugin — isa-style RS coding in C++ (libec_native.so).

Plugin shell analog of /root/reference/src/erasure-code/isa/
ErasureCodePluginIsa.cc: technique selection reed_sol_van|cauchy
(:40-57), the compute engine dlopen-loaded with the reference's
entry-point contract through registry.load_dynamic.
"""

import pathlib

from ceph_tpu.codec.registry import EC_VERSION, ErasureCodePlugin, load_dynamic

__erasure_code_version__ = EC_VERSION

# libec_native.so lives in the repo's native/ build directory (the
# erasure_code_dir role, global.yaml.in:431).
_NATIVE_DIR = str(pathlib.Path(__file__).resolve().parents[3] / "native")

_lib = None


def _get_lib():
    global _lib
    if _lib is None:
        import subprocess

        try:  # build on demand like utils/native.py
            subprocess.run(
                ["make", "-s", "libec_native.so"],
                cwd=_NATIVE_DIR, check=False, capture_output=True, timeout=120,
            )
        except (subprocess.SubprocessError, FileNotFoundError):
            pass
        _lib = load_dynamic("native", _NATIVE_DIR)
    return _lib


def _factory(profile):
    from ceph_tpu.codec.native_codec import ErasureCodeNative
    from ceph_tpu.codec.tracing import instrument_codec

    technique = profile.get("technique") or "reed_sol_van"
    ec = ErasureCodeNative(_get_lib(), technique=technique)
    ec.init(profile)
    # chunk-path calls (the C kernel) get a single `kernel` span; the
    # inherited device paths get codec spans like the tpu plugin
    return instrument_codec(ec, "native")


def __erasure_code_init__(registry):
    registry.add("native", ErasureCodePlugin("native", _factory))
