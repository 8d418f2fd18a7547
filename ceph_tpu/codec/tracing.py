"""Span instrumentation for codec plugins.

`instrument_codec` wraps a codec instance's hot entry points with a
`codec:<plugin>:<op>` sub-span of the ACTIVE span (common/tracer.py's
contextvar), so a traced client write's `ec:write` span shows which
codec call it made, with its shape or erasures.  The wrappers pass the
caller's input through untouched: staging, launch and the device wait
happen where they always do, inside the offload runtime, whose layer
sections (`device.h2d`, `device.dispatch`, `device.block`, `device.d2h`,
`offload.*_wait`) time them on the launch worker and the caller.

Zero-cost when tracing is off: with no recorded active span each wrapper
is one contextvar read and a falsy check before tail-calling the
original.
"""

from __future__ import annotations

from ..common import tracer as tracer_mod


def active_span():
    """The active RECORDED span, or None (unrecorded spans would produce
    children the dump never shows — skip the bookkeeping entirely)."""
    sp = tracer_mod.current_span()
    return sp if sp is not None and sp.recorded else None


def instrument_codec(ec, plugin: str):
    """Wrap the device-path (encode_array/decode_array) and chunk-path
    (encode_chunks/decode_chunks) entry points of `ec` with codec
    sub-spans.  Idempotent; returns `ec` for factory tail-calls."""
    if getattr(ec, "_codec_spans_installed", False):
        return ec

    if hasattr(ec, "encode_array"):
        orig_encode_array = ec.encode_array

        def encode_array(data):
            parent = active_span()
            if parent is None:
                return orig_encode_array(data)
            with parent.child(f"codec:{plugin}:encode") as sp:
                sp.keyval("shape", lambda: str(getattr(data, "shape", len(data))))
                return orig_encode_array(data)

        ec.encode_array = encode_array

    if hasattr(ec, "decode_array"):
        orig_decode_array = ec.decode_array

        def decode_array(erasures, survivors):
            parent = active_span()
            if parent is None:
                return orig_decode_array(erasures, survivors)
            with parent.child(f"codec:{plugin}:decode") as sp:
                sp.keyval("erasures", lambda: ",".join(map(str, erasures)))
                return orig_decode_array(erasures, survivors)

        ec.decode_array = decode_array

    # chunk-level interface: synchronous host (or C) compute — one span
    for name in ("encode_chunks", "decode_chunks"):
        orig = getattr(ec, name, None)
        if orig is None:
            continue

        def wrapped(*args, _orig=orig, _name=name, **kwargs):
            parent = active_span()
            if parent is None:
                return _orig(*args, **kwargs)
            with parent.child(f"codec:{plugin}:{_name}") as sp:
                sp.event("kernel")
                return _orig(*args, **kwargs)

        setattr(ec, name, wrapped)

    ec._codec_spans_installed = True
    return ec
