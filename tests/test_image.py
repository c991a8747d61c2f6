"""Image pipeline tests (SURVEY.md §2.17 / VERDICT r1 Missing #2):
recordio pack/unpack, mx.image ops + augmenters, ImageRecordIter feeding
training. Mirrors reference tests/python/unittest/test_image.py +
test_recordio.py."""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, image, io as mio, nd, recordio


def _rand_img(rng, h=40, w=32):
    return rng.randint(0, 255, (h, w, 3)).astype(np.uint8)


@pytest.fixture(scope="module")
def rec_file(tmp_path_factory):
    """Synthetic indexed .rec of 32 encoded JPEGs, labels 0..3."""
    d = tmp_path_factory.mktemp("rec")
    rec_path = str(d / "train.rec")
    idx_path = str(d / "train.idx")
    rng = np.random.RandomState(0)
    writer = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    imgs = []
    for i in range(32):
        img = _rand_img(rng)
        imgs.append(img)
        header = recordio.IRHeader(0, float(i % 4), i, 0)
        writer.write_idx(i, recordio.pack_img(header, img, quality=95))
    writer.close()
    return rec_path, imgs


# ---------------------------------------------------------------------------
# recordio
# ---------------------------------------------------------------------------

def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "t.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = [bytes([i]) * (i + 1) for i in range(10)]
    for p in payloads:
        w.write(p)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    got = []
    while True:
        b = r.read()
        if b is None:
            break
        got.append(b)
    assert got == payloads


def test_indexed_recordio_random_access(tmp_path):
    rec, idx = str(tmp_path / "t.rec"), str(tmp_path / "t.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(20):
        w.write_idx(i, f"payload-{i}".encode())
    w.close()
    r = recordio.MXIndexedRecordIO(idx, rec, "r")
    assert r.read_idx(13) == b"payload-13"
    assert r.read_idx(2) == b"payload-2"
    assert r.keys == list(range(20))


def test_pack_unpack_scalar_and_multi_label():
    h = recordio.IRHeader(0, 3.0, 7, 0)
    hdr, data = recordio.unpack(recordio.pack(h, b"abc"))
    assert hdr.label == 3.0 and hdr.id == 7 and data == b"abc"
    h2 = recordio.IRHeader(0, np.array([1.0, 2.0, 3.0]), 9, 0)
    hdr2, data2 = recordio.unpack(recordio.pack(h2, b"xy"))
    np.testing.assert_allclose(hdr2.label, [1, 2, 3])
    assert data2 == b"xy"


def test_pack_img_decode_close(tmp_path):
    # smooth gradient: JPEG-friendly, so roundtrip must be close
    yy, xx = np.meshgrid(np.arange(40), np.arange(32), indexing="ij")
    img = np.stack([yy * 6, xx * 7, (yy + xx) * 3], -1).astype(np.uint8)
    payload = recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img,
                                quality=100)
    hdr, dec = recordio.unpack_img(payload)
    assert hdr.label == 1.0
    assert dec.shape == img.shape
    # JPEG is lossy: close, not exact
    assert np.abs(dec.astype(int) - img.astype(int)).mean() < 12


# ---------------------------------------------------------------------------
# image ops + augmenters
# ---------------------------------------------------------------------------

def test_imdecode_imresize():
    rng = np.random.RandomState(2)
    img = _rand_img(rng, 24, 16)
    payload = recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0), img,
                                img_fmt=".png")
    _, raw = recordio.unpack(payload)
    dec = image.imdecode(raw)
    assert dec.shape == (24, 16, 3)
    np.testing.assert_array_equal(dec.asnumpy(), img)  # png is lossless
    r = image.imresize(dec, 8, 12)
    assert r.shape == (12, 8, 3)


def test_resize_short_preserves_aspect():
    x = nd.array(np.zeros((40, 20, 3), np.uint8))
    out = image.resize_short(x, 10)
    assert out.shape == (20, 10, 3)
    out2 = image.resize_short(nd.array(np.zeros((20, 40, 3), np.uint8)), 10)
    assert out2.shape == (10, 20, 3)


def test_crops():
    x = nd.array(np.arange(6 * 8 * 3).reshape(6, 8, 3).astype(np.uint8))
    fc = image.fixed_crop(x, 2, 1, 4, 3)
    np.testing.assert_array_equal(fc.asnumpy(), x.asnumpy()[1:4, 2:6])
    cc, rect = image.center_crop(x, (4, 2))
    assert cc.shape == (2, 4, 3) and rect == (2, 2, 4, 2)
    rc, rect2 = image.random_crop(x, (4, 2))
    assert rc.shape == (2, 4, 3)
    rsc, _ = image.random_size_crop(x, (4, 2), (0.3, 1.0), (0.5, 2.0))
    assert rsc.shape == (2, 4, 3)


def test_color_normalize():
    x = nd.array(np.full((2, 2, 3), 10.0, np.float32))
    out = image.color_normalize(x, nd.array(np.array([1.0, 2.0, 3.0])),
                                nd.array(np.array([2.0, 2.0, 2.0])))
    np.testing.assert_allclose(out.asnumpy()[0, 0], [4.5, 4.0, 3.5])


def test_augmenter_stack_shapes_and_determinism():
    rng = np.random.RandomState(3)
    img = nd.array(_rand_img(rng, 50, 60))
    augs = image.CreateAugmenter((3, 24, 24), resize=30, rand_crop=True,
                                 rand_mirror=True, brightness=0.1,
                                 contrast=0.1, saturation=0.1, hue=0.1,
                                 pca_noise=0.05, mean=True, std=True)
    out = img
    for a in augs:
        out = a(out)
    arr = out.asnumpy() if isinstance(out, nd.NDArray) else np.asarray(out)
    assert arr.shape == (24, 24, 3)
    assert arr.dtype == np.float32


def test_horizontal_flip():
    img = nd.array(np.arange(12).reshape(2, 2, 3).astype(np.uint8))
    flip = image.HorizontalFlipAug(p=1.0)
    np.testing.assert_array_equal(flip(img).asnumpy(),
                                  img.asnumpy()[:, ::-1])


# ---------------------------------------------------------------------------
# ImageIter / ImageRecordIter
# ---------------------------------------------------------------------------

def test_image_iter_from_rec(rec_file):
    rec_path, _ = rec_file
    it = image.ImageIter(batch_size=8, data_shape=(3, 24, 24),
                         path_imgrec=rec_path)
    batch = it.next()
    assert batch.data[0].shape == (8, 3, 24, 24)
    assert batch.label[0].shape == (8,)
    n = 1 + sum(1 for _ in it)
    assert n == 4


def test_image_record_iter_batches(rec_file):
    rec_path, _ = rec_file
    it = mio.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 24, 24),
                             batch_size=8, shuffle=True, rand_crop=True,
                             rand_mirror=True, preprocess_threads=2)
    seen = 0
    for batch in it:
        assert batch.data[0].shape == (8, 3, 24, 24)
        assert np.isfinite(batch.data[0].asnumpy()).all()
        labels = batch.label[0].asnumpy()
        assert ((labels >= 0) & (labels <= 3)).all()
        seen += batch.data[0].shape[0] - batch.pad
    assert seen == 32
    # reset -> second epoch works
    it.reset()
    assert sum(1 for _ in it) == 4


def test_image_record_iter_nhwc_and_normalize(rec_file):
    rec_path, _ = rec_file
    it = mio.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 16, 16),
                             batch_size=4, layout="NHWC",
                             mean_r=123.68, mean_g=116.28, mean_b=103.53,
                             std_r=58.4, std_g=57.1, std_b=57.4)
    batch = it.next()
    assert batch.data[0].shape == (4, 16, 16, 3)
    arr = batch.data[0].asnumpy()
    assert np.abs(arr).max() < 5.0  # normalized range


def test_image_record_iter_label_content_unshuffled(rec_file):
    rec_path, _ = rec_file
    it = mio.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 16, 16),
                             batch_size=8, shuffle=False)
    batch = it.next()
    np.testing.assert_allclose(batch.label[0].asnumpy(),
                               np.arange(8) % 4)


def test_image_record_iter_feeds_module_fit(rec_file):
    """End-to-end: .rec -> ImageRecordIter -> Module.fit one epoch."""
    rec_path, _ = rec_file
    it = mio.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 16, 16),
                             batch_size=8, shuffle=True)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(mx.sym.Flatten(data), num_hidden=4)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
    mod.fit(it, num_epoch=1,
            optimizer_params={"learning_rate": 0.01})
    score = mod.score(it, "acc")
    assert 0.0 <= dict(score)["accuracy"] <= 1.0


def test_image_record_iter_feeds_fused_step(rec_file):
    """The TPU hot path: NHWC batches into a compiled train step."""
    from incubator_mxnet_tpu.parallel import FusedTrainStep
    rec_path, _ = rec_file
    it = mio.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 16, 16),
                             batch_size=8, layout="NHWC",
                             mean_r=128, mean_g=128, mean_b=128,
                             std_r=64, std_g=64, std_b=64)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, layout="NHWC"), gluon.nn.Flatten(),
            gluon.nn.Dense(4))
    net.initialize()
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd")
    losses = []
    for batch in it:
        losses.append(float(step(batch.data[0], batch.label[0])))
    assert len(losses) == 4 and all(np.isfinite(l) for l in losses)


def test_image_record_dataset(rec_file):
    """gluon.data.vision.ImageRecordDataset: .rec -> (HWC image, label)
    samples, DataLoader-composable (reference
    python/mxnet/gluon/data/vision/datasets.py ImageRecordDataset)."""
    from incubator_mxnet_tpu.gluon.data.vision import ImageRecordDataset
    rec_path, imgs = rec_file
    ds = ImageRecordDataset(rec_path)
    assert len(ds) == 32
    img, label = ds[5]
    assert img.shape == imgs[5].shape and label == 5 % 4
    # exact parity with the direct recordio decode of the same record
    from incubator_mxnet_tpu.gluon.data import RecordFileDataset
    _, direct = recordio.unpack_img(RecordFileDataset(rec_path)[5])
    assert np.array_equal(img.asnumpy(), direct.astype(np.float32))
    loader = gluon.data.DataLoader(ds, batch_size=8)
    xb, yb = next(iter(loader))
    assert xb.shape == (8, 40, 32, 3) and yb.shape == (8,)


def test_nd_module_level_surface():
    """mx.nd module functions mirroring NDArray methods (reference nd API)."""
    a = mx.nd.array([[1.0, 2.0], [3.0, 4.0]])
    assert mx.nd.empty_like(a).shape == (2, 2)
    assert np.allclose(mx.nd.mod(a, 2).asnumpy(), [[1, 0], [1, 0]])
    assert mx.nd.astype(a, "float16").dtype == np.float16
    b = mx.nd.zeros((2, 2))
    a.copyto(b)
    assert np.allclose(b.asnumpy(), a.asnumpy())


def test_native_jpeg_decoder_matches_pil():
    """runtime.decode_jpeg (libjpeg, GIL-free) decodes bit-identically to
    PIL and fails gracefully on junk (falls back to PIL in imdecode)."""
    from incubator_mxnet_tpu import runtime
    import io as _io
    from PIL import Image
    if not runtime.jpeg_decode_available():
        pytest.skip("native jpeg decoder unavailable (no g++/libjpeg)")
    rng = np.random.RandomState(9)
    img = rng.randint(0, 255, (32, 24, 3)).astype(np.uint8)
    buf = _io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=90)
    data = buf.getvalue()
    nat = runtime.decode_jpeg(data)
    pil = np.asarray(Image.open(_io.BytesIO(data)).convert("RGB"))
    np.testing.assert_array_equal(nat, pil)
    gray = runtime.decode_jpeg(data, channels=1)
    assert gray.shape == (32, 24, 1)
    assert runtime.decode_jpeg(data[:40]) is None      # cut inside header
    # cut inside scan data: libjpeg pads with a fake EOI + warning; the
    # decoder must surface that as failure, not silent garbage
    assert runtime.decode_jpeg(data[:len(data) // 2]) is None
    # imdecode grayscale is identical to PIL's convert('L') luma on both
    # native and fallback paths
    pil_gray = np.asarray(Image.open(_io.BytesIO(data)).convert("L"))
    np.testing.assert_array_equal(
        image.imdecode(data, flag=0).asnumpy()[..., 0], pil_gray)
    # imdecode routes JPEG through the native path and PNG through PIL
    d = image.imdecode(data)
    np.testing.assert_array_equal(d.asnumpy(), pil)
    png = _io.BytesIO()
    Image.fromarray(img).save(png, format="PNG")
    np.testing.assert_array_equal(image.imdecode(png.getvalue()).asnumpy(),
                                  img)


def test_copy_make_border():
    img = nd.array(np.arange(12, dtype=np.float32).reshape(2, 2, 3))
    b = image.copyMakeBorder(img, 1, 1, 2, 2, border_type=0,
                             values=5.0).asnumpy()
    assert b.shape == (4, 6, 3)
    assert (b[0] == 5.0).all() and (b[:, 0] == 5.0).all()
    np.testing.assert_array_equal(b[1:3, 2:4], img.asnumpy())
    r = image.copyMakeBorder(img, 1, 0, 0, 0, border_type=1).asnumpy()
    np.testing.assert_array_equal(r[0], img.asnumpy()[0])
    import pytest
    with pytest.raises(ValueError):
        image.copyMakeBorder(img, 1, 1, 1, 1, border_type=4)


@pytest.mark.slow
def test_im2rec_cli_roundtrip(tmp_path):
    """tools/im2rec.py: folder -> .lst/.rec/.idx consumable by
    ImageRecordIter with subdirectory labels (reference tools/im2rec)."""
    import subprocess
    import sys as _sys
    from PIL import Image as PILImage
    root = tmp_path / "imgs"
    rng = np.random.RandomState(0)
    for ci, cls in enumerate(["cat", "dog"]):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = rng.randint(0, 255, (20 + ci, 24, 3), np.uint8)
            PILImage.fromarray(arr).save(root / cls / f"{i}.jpg",
                                         quality=95)
    prefix = str(tmp_path / "data")
    out = subprocess.run(
        [_sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "im2rec.py"),
         prefix, str(root), "--resize", "16"],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-500:]
    assert os.path.exists(prefix + ".lst")
    assert os.path.exists(prefix + ".rec")
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             data_shape=(3, 16, 16), batch_size=6,
                             shuffle=False)
    batch = it.next()
    labels = batch.label[0].asnumpy()
    np.testing.assert_allclose(sorted(labels), [0, 0, 0, 1, 1, 1])
