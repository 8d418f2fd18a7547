"""ceph-erasure-code-tool equivalent — file-level encode/decode CLI.

Mirror of /root/reference/src/tools/erasure-code/ceph-erasure-code-tool.cc,
whose command surface (and the byte-identity test harness built on it,
src/test/ceph-erasure-code-tool/test_ceph-erasure-code-tool.sh) is the model
for our parity checks:

  test-plugin-exists <plugin>
  validate-profile   <profile> [chunk_count|data_chunk_count|coding_chunk_count]
  calc-chunk-size    <profile> <object_size>
  encode             <profile> <stripe_unit> <want_to_encode> <file>
  decode             <profile> <stripe_unit> <want_to_read>   <file>

Profiles are comma-separated k=v lists (e.g. "plugin=tpu,technique=cauchy,
k=4,m=2").  encode reads <file> and writes <file>.<chunk> per requested
chunk; decode reads <file>.<chunk> fragments and writes <file>.decoded.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

from ceph_tpu.codec import registry as registry_mod
from ceph_tpu.codec.interface import EcError, Profile


def parse_profile(text: str) -> tuple[str, Profile]:
    profile: Profile = {}
    for token in text.split(","):
        token = token.strip()
        if not token:
            continue
        if "=" not in token:
            raise SystemExit(f"invalid profile token {token!r} (need k=v)")
        key, val = token.split("=", 1)
        profile[key] = val
    plugin = profile.pop("plugin", "tpu")
    return plugin, profile


def make_codec(text: str):
    plugin, profile = parse_profile(text)
    return registry_mod.instance().factory(plugin, profile)


def cmd_test_plugin_exists(args) -> int:
    try:
        registry_mod.instance().load(args.plugin)
        return 0
    except EcError as e:
        print(e, file=sys.stderr)
        return 1


def cmd_validate_profile(args) -> int:
    try:
        ec = make_codec(args.profile)
    except EcError as e:
        print(e, file=sys.stderr)
        return 1
    if args.quantity:
        values = {
            "chunk_count": ec.get_chunk_count(),
            "data_chunk_count": ec.get_data_chunk_count(),
            "coding_chunk_count": ec.get_coding_chunk_count(),
        }
        if args.quantity not in values:
            print(f"unknown quantity {args.quantity}", file=sys.stderr)
            return 1
        print(values[args.quantity])
    return 0


def cmd_calc_chunk_size(args) -> int:
    ec = make_codec(args.profile)
    print(ec.get_chunk_size(args.object_size))
    return 0


def _parse_want(text: str) -> set[int]:
    return {int(x) for x in text.split(",") if x.strip() != ""}


def cmd_encode(args) -> int:
    ec = make_codec(args.profile)
    try:
        with open(args.file, "rb") as f:
            data = f.read()
    except OSError as e:
        print(e, file=sys.stderr)
        return 1
    # stripe_unit semantics: the reference aligns the object to
    # stripe_unit * k before encoding (tool stripe handling).
    k = ec.get_data_chunk_count()
    stripe_width = args.stripe_unit * k
    padded_len = -(-len(data) // stripe_width) * stripe_width
    padded = data + b"\0" * (padded_len - len(data))
    want = _parse_want(args.want) if args.want else set(range(ec.get_chunk_count()))
    chunks = ec.encode(want, padded)
    for i, chunk in sorted(chunks.items()):
        with open(f"{args.file}.{i}", "wb") as f:
            f.write(np.asarray(chunk, dtype=np.uint8).tobytes())
    return 0


def cmd_decode(args) -> int:
    ec = make_codec(args.profile)
    chunks = {}
    for i in range(ec.get_chunk_count()):
        path = f"{args.file}.{i}"
        if os.path.exists(path):
            with open(path, "rb") as f:
                chunks[i] = np.frombuffer(f.read(), dtype=np.uint8)
    want = _parse_want(args.want) if args.want else None
    try:
        if want is None:
            out = ec.decode_concat(chunks)
            with open(f"{args.file}.decoded", "wb") as f:
                f.write(out.tobytes())
        else:
            decoded = ec.decode(want, chunks)
            for i in sorted(want):
                with open(f"{args.file}.{i}.decoded", "wb") as f:
                    f.write(np.asarray(decoded[i]).tobytes())
    except EcError as e:
        print(e, file=sys.stderr)
        return 1
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="ec_tool", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("test-plugin-exists")
    sp.add_argument("plugin")
    sp.set_defaults(func=cmd_test_plugin_exists)

    sp = sub.add_parser("validate-profile")
    sp.add_argument("profile")
    sp.add_argument("quantity", nargs="?")
    sp.set_defaults(func=cmd_validate_profile)

    sp = sub.add_parser("calc-chunk-size")
    sp.add_argument("profile")
    sp.add_argument("object_size", type=int)
    sp.set_defaults(func=cmd_calc_chunk_size)

    sp = sub.add_parser("encode")
    sp.add_argument("profile")
    sp.add_argument("stripe_unit", type=int)
    sp.add_argument("want")
    sp.add_argument("file")
    sp.set_defaults(func=cmd_encode)

    sp = sub.add_parser("decode")
    sp.add_argument("profile")
    sp.add_argument("stripe_unit", type=int)
    sp.add_argument("want")
    sp.add_argument("file")
    sp.set_defaults(func=cmd_decode)

    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.func(args)


if __name__ == "__main__":
    from ceph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
