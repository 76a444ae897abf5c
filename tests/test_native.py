"""Native runtime tests: C++ dependency engine, RecordIO, prefetcher.

Reference analog: tests/cpp/engine/threaded_engine_test.cc (ordering,
exception semantics) and python recordio round-trip tests. The engine
orders *host* tasks here (device work is XLA's job on TPU).
"""
import os
import struct
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _native, recordio
from mxnet_tpu.base import MXNetError

pytestmark = pytest.mark.skipif(not _native.available(),
                                reason="native lib unavailable (no g++?)")


def test_engine_write_ordering():
    # Ops writing the same var must run exclusively and in push order.
    eng = _native.NativeEngine(num_threads=4)
    var = eng.new_var()
    log = []
    for i in range(50):
        eng.push(lambda i=i: log.append(i), mutable_vars=[var])
    eng.wait_for_var(var)
    assert log == list(range(50))
    assert eng.var_version(var) == 50
    eng.close()


def test_engine_reads_parallel_writes_exclusive():
    eng = _native.NativeEngine(num_threads=4)
    var = eng.new_var()
    state = {"active": 0, "max_active": 0}
    lock = threading.Lock()

    def reader():
        with lock:
            state["active"] += 1
            state["max_active"] = max(state["max_active"], state["active"])
        time.sleep(0.01)
        with lock:
            state["active"] -= 1

    for _ in range(8):
        eng.push(reader, const_vars=[var])
    eng.wait_for_all()
    assert state["max_active"] > 1  # reads overlapped
    # now interleave a write: everything pushed after must see it done
    order = []
    eng.push(lambda: (time.sleep(0.02), order.append("w")), mutable_vars=[var])
    eng.push(lambda: order.append("r"), const_vars=[var])
    eng.wait_for_all()
    assert order == ["w", "r"]
    eng.close()


def test_engine_dependency_chain():
    # writer(a) -> reader(a) writer(b) -> reader(b); cross-var ordering
    eng = _native.NativeEngine(num_threads=4)
    a, b = eng.new_var(), eng.new_var()
    out = []
    eng.push(lambda: (time.sleep(0.02), out.append("wa")), mutable_vars=[a])
    eng.push(lambda: out.append("ra_wb"), const_vars=[a], mutable_vars=[b])
    eng.push(lambda: out.append("rb"), const_vars=[b])
    eng.wait_for_all()
    assert out == ["wa", "ra_wb", "rb"]
    eng.close()


def test_engine_exception_at_sync_point():
    # Async failures surface at wait_for_* (reference
    # threaded_engine.cc:422-436 exception propagation).
    eng = _native.NativeEngine(num_threads=2)
    var = eng.new_var()

    def boom():
        raise ValueError("kaboom from worker")

    eng.push(boom, mutable_vars=[var])
    with pytest.raises(MXNetError, match="kaboom"):
        eng.wait_for_var(var)
    # error is consumed; engine remains usable
    eng.push(lambda: None, mutable_vars=[var])
    eng.wait_for_var(var)
    eng.close()


@pytest.mark.parametrize("native_write,native_read",
                         [(True, True), (True, False), (False, True)])
def test_recordio_cross_compat(tmp_path, native_write, native_read,
                               monkeypatch):
    # native and pure-Python impls must interoperate byte-for-byte
    path = str(tmp_path / "data.rec")
    records = [b"hello", b"x" * 1021, b"", os.urandom(4096),
               struct.pack("<I", 0xced7230a)]  # payload containing magic
    w = (_native.NativeRecordIOWriter(path) if native_write
         else recordio._PyWriter(path))
    for r in records:
        w.write(r)
    w.close()
    r_ = (_native.NativeRecordIOReader(path) if native_read
          else recordio._PyReader(path))
    got = []
    while True:
        rec = r_.read()
        if rec is None:
            break
        got.append(rec)
    r_.close()
    assert got == records


def test_mxrecordio_api(tmp_path):
    path = str(tmp_path / "t.rec")
    rec = recordio.MXRecordIO(path, "w")
    for i in range(10):
        rec.write(f"record{i}".encode())
    rec.close()
    rec = recordio.MXRecordIO(path, "r")
    for i in range(10):
        assert rec.read() == f"record{i}".encode()
    assert rec.read() is None
    rec.reset()
    assert rec.read() == b"record0"
    rec.close()


def test_indexed_recordio(tmp_path):
    path = str(tmp_path / "t.rec")
    idx = str(tmp_path / "t.idx")
    rec = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(20):
        rec.write_idx(i, f"rec{i}".encode())
    rec.close()
    rec = recordio.MXIndexedRecordIO(idx, path, "r")
    assert rec.keys == list(range(20))
    assert rec.read_idx(13) == b"rec13"
    assert rec.read_idx(4) == b"rec4"
    rec.close()


def test_indexed_writer_tell(tmp_path):
    # tell() in write mode must advance identically native vs pure-Python
    # (reference index-building pattern: pos = tell(); write_idx(...)).
    paths = [(str(tmp_path / "n.rec"), _native.NativeRecordIOWriter),
             (str(tmp_path / "p.rec"), recordio._PyWriter)]
    tells = []
    for path, cls in paths:
        w = cls(path)
        t = [w.tell()]
        for i in range(5):
            w.write(b"x" * (i * 3 + 1))
            t.append(w.tell())
        w.close()
        tells.append(t)
    assert tells[0] == tells[1]
    assert tells[0][0] == 0 and sorted(tells[0]) == tells[0]


def test_pyreader_truncated_header(tmp_path):
    path = str(tmp_path / "trunc.rec")
    w = recordio._PyWriter(path)
    w.write(b"full record")
    w.close()
    with open(path, "ab") as f:
        f.write(struct.pack("<I", 0xced7230a) + b"\x01\x02")  # cut mid-header
    r = recordio._PyReader(path)
    assert r.read() == b"full record"
    with pytest.raises(MXNetError, match="truncated header"):
        r.read()
    r.close()


def test_pack_unpack_header():
    h = recordio.IRHeader(flag=0, label=3.5, id=42, id2=0)
    s = recordio.pack(h, b"payload")
    h2, payload = recordio.unpack(s)
    assert payload == b"payload" and h2.label == 3.5 and h2.id == 42
    # multi-label
    h = recordio.IRHeader(flag=0, label=onp.array([1.0, 2.0, 3.0]), id=7, id2=0)
    s = recordio.pack(h, b"xyz")
    h2, payload = recordio.unpack(s)
    assert payload == b"xyz"
    onp.testing.assert_allclose(h2.label, [1.0, 2.0, 3.0])


def test_prefetcher(tmp_path):
    path = str(tmp_path / "big.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = [os.urandom(onp.random.randint(1, 2000)) for _ in range(200)]
    for p in payloads:
        w.write(p)
    w.close()
    pf = _native.NativePrefetchReader(path, capacity=16)
    got = list(pf)
    pf.close()
    assert got == payloads


def test_native_batchify_stack_matches_numpy():
    """src/native/batchify.cc MXTBatchifyStack: GIL-free parallel collation
    must be byte-identical to numpy stack (reference StackBatchify,
    src/io/batchify.cc)."""
    from mxnet_tpu import _native
    from mxnet_tpu.gluon.data.batchify import Stack, _native_stack
    if not _native.available():
        pytest.skip("native library unavailable")
    rng = onp.random.RandomState(3)
    # large batch (>1MB) rides the native parallel copy
    arrs = [rng.randn(64, 512).astype("float32") for _ in range(16)]
    assert _native_stack(arrs) is not None
    onp.testing.assert_array_equal(Stack()(arrs).asnumpy(),
                                   onp.stack(arrs))
    # int dtype too
    iarrs = [rng.randint(0, 9, (256, 512)).astype("int32")
             for _ in range(16)]
    onp.testing.assert_array_equal(Stack()(iarrs).asnumpy(),
                                   onp.stack(iarrs))
    # small batches skip the thread spawn (numpy memcpy wins there)
    assert _native_stack([onp.zeros((4,), "float32")] * 8) is None
    # non-uniform shapes and object dtype refuse the raw-memcpy path
    assert _native_stack([onp.zeros((2,)), onp.zeros((3,))]) is None
    objs = [onp.array([{"x": 1}, [2]], dtype=object)] * 4
    assert _native_stack(objs) is None


def test_native_image_normalize_fused():
    """MXTBatchifyImageNormalize: HWC uint8 -> normalized NCHW float32,
    fused (reference image pipeline normalize+transpose on worker
    threads)."""
    from mxnet_tpu import _native
    from mxnet_tpu.gluon.data.batchify import ImageNormalize
    if not _native.available():
        pytest.skip("native library unavailable")
    rng = onp.random.RandomState(4)
    imgs = [rng.randint(0, 255, (16, 20, 3)).astype("uint8")
            for _ in range(6)]
    norm = ImageNormalize(mean=(0.5, 0.4, 0.3), std=(0.2, 0.25, 0.3))
    out = norm(imgs).asnumpy()
    ref = (onp.stack(imgs).astype("float32") / 255.0
           - onp.array([0.5, 0.4, 0.3], "float32")) \
        / onp.array([0.2, 0.25, 0.3], "float32")
    onp.testing.assert_allclose(out, ref.transpose(0, 3, 1, 2),
                                rtol=1e-5, atol=1e-6)
    # a non-uint8 sample anywhere in the batch must raise, not be
    # reinterpreted byte-wise
    with pytest.raises(ValueError, match="uint8"):
        norm([imgs[0], imgs[1].astype("float32")])


def test_dataloader_uses_native_batchify_end_to_end():
    from mxnet_tpu import _native
    from mxnet_tpu.gluon.data import DataLoader, ArrayDataset
    from mxnet_tpu.gluon.data.batchify import _native_stack
    import mxnet_tpu as mx
    if not _native.available():
        pytest.skip("native library unavailable")
    rng = onp.random.RandomState(5)
    # samples big enough that a 16-batch crosses the native threshold
    X = rng.randn(64, 128, 256).astype("float32")
    Y = rng.randint(0, 3, (64,)).astype("int32")
    assert _native_stack([X[i] for i in range(16)]) is not None  # precond
    ds = ArrayDataset(mx.nd.array(X), mx.nd.array(Y))
    dl = DataLoader(ds, batch_size=16, num_workers=2)
    seen = 0
    for xb, yb in dl:
        assert xb.shape == (16, 128, 256)
        idx = seen
        onp.testing.assert_array_equal(xb.asnumpy(), X[idx:idx + 16])
        seen += xb.shape[0]
    assert seen == 64


def test_native_jpeg_decode_matches_pil():
    """src/native/image.cc libjpeg decode (the OpenCV-decode-thread analog,
    iter_image_recordio_2.cc): RGB and grayscale paths match PIL."""
    import io
    from mxnet_tpu import _native
    from mxnet_tpu.image.image import imdecode, _native_jpeg_decode
    if not _native.available():
        pytest.skip("native library unavailable")
    try:
        from PIL import Image
    except ImportError:
        pytest.skip("PIL unavailable")
    rng = onp.random.RandomState(7)
    img = rng.randint(0, 255, (32, 40, 3)).astype("uint8")
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=95)
    payload = buf.getvalue()

    native = _native_jpeg_decode(payload, 1)
    assert native is not None
    pil = onp.asarray(Image.open(io.BytesIO(payload)).convert("RGB"))
    assert int(onp.abs(native.astype(int) - pil.astype(int)).max()) <= 2
    gray = _native_jpeg_decode(payload, 0)
    assert gray.shape == (32, 40, 1)
    # public imdecode rides the native path; BGR flip still applies
    rgb = imdecode(payload).asnumpy()
    bgr = imdecode(payload, to_rgb=False).asnumpy()
    onp.testing.assert_array_equal(rgb[..., ::-1], bgr)
    # non-JPEG bytes fall back cleanly (PNG through PIL)
    pbuf = io.BytesIO()
    Image.fromarray(img).save(pbuf, format="PNG")
    png = imdecode(pbuf.getvalue()).asnumpy()
    onp.testing.assert_array_equal(png, img)
    # corrupt JPEG raises through the fallback, not a crash
    with pytest.raises(Exception):
        imdecode(b"\xff\xd8corrupt")


def test_native_png_decode_lossless():
    """src/native/image_png.cc: PNG decodes bit-exact (lossless format),
    RGB and grayscale, dispatched by magic bytes through the same decode
    entry as JPEG."""
    import io
    from mxnet_tpu import _native
    from mxnet_tpu.image.image import imdecode, _native_jpeg_decode
    if not _native.available():
        pytest.skip("native library unavailable")
    lib = _native.get_lib()
    if not hasattr(lib, "MXTImagePNGDecode"):
        pytest.skip("built without libpng")
    try:
        from PIL import Image
    except ImportError:
        pytest.skip("PIL unavailable")
    rng = onp.random.RandomState(9)
    img = rng.randint(0, 255, (24, 30, 3)).astype("uint8")
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="PNG")
    payload = buf.getvalue()
    native = _native_jpeg_decode(payload, 1)
    assert native is not None
    onp.testing.assert_array_equal(native, img)
    # grayscale conversion parity with the PIL fallback: bit-exact (the
    # native path uses Pillow's own fixed-point luma, coefficients AND the
    # +0x8000 rounding term ImagingConvert's L24 path has carried since
    # 2013 — if a Pillow build without it ever appears, this drops to ±1)
    g = _native_jpeg_decode(payload, 0)[..., 0]
    pil_g = onp.asarray(Image.open(io.BytesIO(payload)).convert("L"))
    onp.testing.assert_array_equal(g, pil_g)
    onp.testing.assert_array_equal(imdecode(payload).asnumpy(), img)
    # RGBA: deterministic and PIL-parity (alpha DROPPED, not composited)
    rgba = rng.randint(0, 255, (12, 12, 4)).astype("uint8")
    abuf = io.BytesIO()
    Image.fromarray(rgba, "RGBA").save(abuf, format="PNG")
    ap = abuf.getvalue()
    d1 = _native_jpeg_decode(ap, 1)
    onp.testing.assert_array_equal(d1, _native_jpeg_decode(ap, 1))
    onp.testing.assert_array_equal(
        d1, onp.asarray(Image.open(io.BytesIO(ap)).convert("RGB")))
    # grayscale-source PNG expands to 3 channels on color decode
    gbuf = io.BytesIO()
    Image.fromarray(img[..., 0]).save(gbuf, format="PNG")
    g3 = _native_jpeg_decode(gbuf.getvalue(), 1)
    assert g3.shape == (24, 30, 3)
    onp.testing.assert_array_equal(g3[..., 0], img[..., 0])
    # corrupt PNG falls back (PIL raises) rather than crashing
    with pytest.raises(Exception):
        imdecode(b"\x89PNG\r\n\x1a\ncorrupt")


def test_png_colorspace_chunks_route_to_pil():
    """gAMA/iCCP/cHRM PNGs must decode through PIL (libpng's simplified
    API would sRGB-convert them, PIL ignores the tags) — identical pixels
    either way the library is built."""
    import io
    import struct as _s
    import zlib
    from mxnet_tpu.image.image import (_native_jpeg_decode, imdecode,
                                       _png_has_colorspace_chunk)
    try:
        from PIL import Image
    except ImportError:
        pytest.skip("PIL unavailable")
    rng = onp.random.RandomState(11)
    img = rng.randint(0, 255, (8, 8, 3)).astype("uint8")
    b = io.BytesIO()
    Image.fromarray(img).save(b, format="PNG")
    raw = b.getvalue()
    assert not _png_has_colorspace_chunk(raw)
    ihdr_end = raw.index(b"IHDR") + 4 + 13 + 4
    gama = _s.pack(">I", 100000)
    chunk = _s.pack(">I", 4) + b"gAMA" + gama + \
        _s.pack(">I", zlib.crc32(b"gAMA" + gama) & 0xffffffff)
    tagged = raw[:ihdr_end] + chunk + raw[ihdr_end:]
    assert _png_has_colorspace_chunk(tagged)
    assert _native_jpeg_decode(tagged, 1) is None
    pil = onp.asarray(Image.open(io.BytesIO(tagged)).convert("RGB"))
    onp.testing.assert_array_equal(imdecode(tagged).asnumpy(), pil)


def test_failed_build_with_stale_library_is_an_error(monkeypatch):
    """A library `make` could not vouch for is not a fallback: with
    build/libmxt_native.so present and the build failing, get_lib()
    raises; with no library at all it reports unavailable (the
    pure-Python paths take over)."""
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_load_failed", False)
    monkeypatch.setattr(_native, "_build_lib", lambda: False)
    assert os.path.exists(_native._LIB_PATH)
    with pytest.raises(MXNetError, match="refusing to load"):
        _native.get_lib()
    monkeypatch.setattr(_native, "_lib_mtime", lambda: None)
    assert _native.get_lib() is None
