"""The wire codecs (repro.cluster.wire), in isolation.

Property tests over the zero-copy ndarray codec (codec 2): dtypes, 0-d,
empty, non-contiguous and Fortran-order arrays, arrays nested inside
msgpack payloads (ExtType), and the fallback ladder (object arrays and
tuples -> pickle), run under hypothesis.

Also the regression for the deep-nesting guard: a payload too deep for any
codec must raise a clear ValueError, not a RecursionError from inside a
serializer.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cluster import wire
from repro.cluster.wire import (
    DEFAULT_HEARTBEAT_S,
    Frame,
    FrameType,
    _CodecId,
    encode_payload,
    pack_frame,
    unpack_frame,
)

DTYPES = ["float32", "float64", "int32", "uint8", "bool"]


def _roundtrip(payload):
    return unpack_frame(pack_frame(Frame(FrameType.RESULT, payload))).payload


def _codec_of(payload) -> int:
    return encode_payload(payload)[0]


# ---------------------------------------------------------------------------
# ndarray codec properties
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(
    dtype=st.sampled_from(DTYPES),
    shape=st.lists(st.integers(0, 5), min_size=0, max_size=3),
)
def test_ndarray_roundtrip_dtypes_and_shapes(dtype, shape):
    """Any dtype x shape (including 0-d and empty) round-trips exactly on
    the ndarray codec — values, dtype, and shape all preserved."""
    rng = np.random.default_rng(0)
    a = np.asarray(rng.random(tuple(shape)) * 100, dtype=dtype)
    assert _codec_of(a) == _CodecId.NDARRAY
    b = _roundtrip(a)
    assert b.dtype == a.dtype
    assert b.shape == a.shape
    assert np.array_equal(b, a)


@settings(max_examples=20, deadline=None)
@given(dtype=st.sampled_from(DTYPES), rows=st.integers(1, 6),
       cols=st.integers(1, 6))
def test_ndarray_roundtrip_fortran_and_noncontiguous(dtype, rows, cols):
    base = (np.arange(rows * cols * 4) % 7).astype(dtype).reshape(
        rows * 2, cols * 2
    )

    fortran = np.asfortranarray(base)
    assert fortran.flags.f_contiguous
    b = _roundtrip(fortran)
    assert np.array_equal(b, fortran) and b.shape == fortran.shape

    # A strided view pays one compaction copy; numpy counts a one-element
    # view as contiguous, and hypothesis tries rows = cols = 1 first.
    sliced = base[::2, ::2]
    assert sliced.flags.c_contiguous == (sliced.size == 1)
    b = _roundtrip(sliced)
    assert np.array_equal(b, sliced) and b.dtype == sliced.dtype


def test_ndarray_zero_copy_encode_for_contiguous():
    """The payload buffer of a contiguous array is a view of the array's own
    memory, not a copy."""
    a = np.arange(32, dtype=np.float32)
    codec, bufs = encode_payload(a)
    assert codec == _CodecId.NDARRAY
    raw = bufs[-1]
    assert isinstance(raw, memoryview)
    assert raw.obj is a or getattr(raw.obj, "base", None) is a


def test_ndarray_nested_in_msgpack_payload():
    """Arrays inside protocol dicts ride the msgpack ExtType, keeping the
    enclosing payload on the cheap codec."""
    a = np.linspace(0.0, 1.0, 7, dtype=np.float64)
    payload = {"id": 3, "value": a, "node_id": "node0"}
    assert _codec_of(payload) == _CodecId.MSGPACK
    back = _roundtrip(payload)
    assert back["id"] == 3 and back["node_id"] == "node0"
    assert np.array_equal(back["value"], a)


def test_empty_array_nested_in_msgpack_payload():
    """Regression: a size-0 array inside a dict used to crash the ExtType
    hook (bytes has no .tobytes) instead of encoding."""
    payload = {"id": 1, "value": np.empty(0, dtype=np.float32)}
    back = _roundtrip(payload)
    assert back["value"].shape == (0,) and back["value"].dtype == np.float32


def test_structured_and_datetime_dtypes_fall_back_to_pickle():
    """Regression: dtype.str cannot express record fields ('|V8' would
    silently drop names) and datetime64 refuses buffer export — both must
    ride pickle, preserving exact round-trips."""
    rec = np.zeros(3, dtype=[("x", "<f4"), ("y", "<i4")])
    rec["x"] = [1.0, 2.0, 3.0]
    assert _codec_of(rec) == _CodecId.PICKLE
    back = _roundtrip({"value": rec})["value"]
    assert back.dtype == rec.dtype
    assert np.array_equal(back["x"], rec["x"])

    dt = np.array(["2026-08-02", "2026-08-03"], dtype="datetime64[D]")
    assert _codec_of(dt) == _CodecId.PICKLE
    assert np.array_equal(_roundtrip(dt), dt)


def test_object_array_falls_back_to_pickle():
    o = np.array([{"a": 1}, None, (2, 3)], dtype=object)
    assert _codec_of(o) == _CodecId.PICKLE
    back = _roundtrip(o)
    assert back.dtype == object and list(back) == list(o)


def test_jax_array_ships_on_ndarray_codec():
    jnp = pytest.importorskip("jax.numpy")
    a = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    assert _codec_of(a) == _CodecId.NDARRAY
    back = _roundtrip(a)
    assert np.array_equal(back, np.asarray(a))


# ---------------------------------------------------------------------------
# single-pass encoder fallback ladder
# ---------------------------------------------------------------------------


def test_tuples_keep_exactness_via_pickle():
    payload = {"id": 1, "obj": (1, 2, [3, (4,)])}
    assert _codec_of(payload) == _CodecId.PICKLE
    back = _roundtrip(payload)
    assert back["obj"] == (1, 2, [3, (4,)])
    assert isinstance(back["obj"], tuple)


def test_plain_payloads_stay_on_msgpack():
    payload = {"node_id": "node0", "credits": 4,
               "results": [{"id": 0, "value": 1.5}]}
    assert _codec_of(payload) == _CodecId.MSGPACK
    assert _roundtrip(payload) == payload


def test_big_int_and_int_keys_roundtrip():
    assert _roundtrip({"value": 2**70})["value"] == 2**70
    assert _roundtrip({1: "a", "b": 2}) == {1: "a", "b": 2}


def test_deeply_nested_payload_raises_clear_error():
    """Regression: unbounded recursion in payload encoding used to surface
    as a RecursionError masquerading as a wire failure."""
    deep = []
    for _ in range(100_000):
        deep = [deep]
    with pytest.raises(ValueError, match="nested too deeply"):
        pack_frame(Frame(FrameType.WORK, deep))


# ---------------------------------------------------------------------------
# batched frame types + shared heartbeat constant
# ---------------------------------------------------------------------------


def test_batch_frames_roundtrip():
    items = [{"id": i, "obj": i * i} for i in range(5)]
    g = unpack_frame(pack_frame(
        Frame(FrameType.WORK_BATCH, {"items": items})
    ))
    assert g.ftype is FrameType.WORK_BATCH and g.payload["items"] == items

    results = {"node_id": "n0", "credits": 2,
               "results": [{"id": 0, "value": 9}, {"id": 1, "value": 16}]}
    g = unpack_frame(pack_frame(Frame(FrameType.RESULT_BATCH, results)))
    assert g.ftype is FrameType.RESULT_BATCH and g.payload == results


def test_heartbeat_interval_shared_between_sides():
    """Satellite regression: the node beacon's pre-LOAD interval and the
    host monitor default must be the same constant."""
    from repro.runtime.failures import HeartbeatMonitor

    assert HeartbeatMonitor().interval_s == DEFAULT_HEARTBEAT_S


# ---------------------------------------------------------------------------
# job_id header field (wire v2, multi-job multiplexing)
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(
    job_id=st.integers(0, 2**32 - 1),
    ftype=st.sampled_from([FrameType.WORK_BATCH, FrameType.RESULT_BATCH,
                           FrameType.LOAD, FrameType.JOB_CLOSE,
                           FrameType.WORK_REQUEST, FrameType.UT]),
)
def test_job_id_roundtrips_on_every_frame_type(job_id, ftype):
    """The v2 header's job tag survives pack/unpack for the full 32-bit
    range on every frame type, independent of payload codec."""
    f = Frame(ftype, {"node_id": "n0"}, wire.APP_WIRE_CHANNEL, job_id=job_id)
    g = unpack_frame(pack_frame(f))
    assert g.job_id == job_id
    assert g.ftype is ftype and g.channel == wire.APP_WIRE_CHANNEL


def test_job_id_defaults_to_zero():
    """job_id 0 = "no job": bootstrap and pool-control frames need no tag,
    and pre-service callers never mention it."""
    g = unpack_frame(pack_frame(Frame(FrameType.REGISTER, {"node_id": "n"})))
    assert g.job_id == 0


@settings(max_examples=20, deadline=None)
@given(
    job_id=st.integers(1, 2**32 - 1),
    dtype=st.sampled_from(DTYPES),
    n=st.integers(0, 16),
)
def test_job_id_roundtrips_with_ndarray_batches(job_id, dtype, n):
    """A codec-2 (zero-copy ndarray) result batch keeps its job tag — the
    header field and the multi-buffer payload path must not interfere."""
    a = (np.arange(n * 3) % 11).astype(dtype).reshape(n, 3)
    f = Frame(FrameType.RESULT_BATCH, a, wire.APP_WIRE_CHANNEL,
              job_id=job_id)
    g = unpack_frame(pack_frame(f))
    assert g.job_id == job_id
    assert np.array_equal(g.payload, a) and g.payload.dtype == a.dtype

    nested = {"node_id": "n0", "credits": 1,
              "results": [{"id": 0, "s": 0, "value": a}]}
    g = unpack_frame(pack_frame(
        Frame(FrameType.RESULT_BATCH, nested, wire.APP_WIRE_CHANNEL,
              job_id=job_id)
    ))
    assert g.job_id == job_id
    assert np.array_equal(g.payload["results"][0]["value"], a)


def test_wire_counters_track_traffic():
    import socket

    from repro.cluster.wire import FrameConnection

    a, b = socket.socketpair()
    left, right = FrameConnection(a), FrameConnection(b)
    try:
        f = Frame(FrameType.HEARTBEAT, {"node_id": "n"}, wire.LOAD_WIRE_CHANNEL)
        left.send(f)
        got = right.recv()
        assert got.payload == {"node_id": "n"}
        assert left.counters.frames_sent == 1
        assert right.counters.frames_recv == 1
        assert left.counters.bytes_sent == right.counters.bytes_recv > 0
    finally:
        left.close()
        right.close()
