"""mx.io DataIter tests (ref: tests/python/unittest/test_io.py)."""
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.io import (CSVIter, DataBatch, ImageRecordIter, NDArrayIter,
                          PrefetchingIter, ResizeIter, create_iter,
                          list_data_iters)
from mxnet_tpu.io.recordio import IRHeader, MXIndexedRecordIO, pack_img


def test_ndarray_iter_basic():
    data = onp.arange(40).reshape(10, 4).astype('float32')
    label = onp.arange(10).astype('float32')
    it = NDArrayIter(data, label, batch_size=3, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 4
    assert batches[-1].pad == 2
    assert batches[0].data[0].shape == (3, 4)
    # pad wraps around to the beginning
    got = onp.concatenate([b.label[0].asnumpy() for b in batches])
    assert list(got[:10]) == list(range(10))
    it.reset()
    assert len(list(it)) == 4


def test_ndarray_iter_discard_rollover():
    data = onp.arange(10).astype('float32')
    it = NDArrayIter(data, batch_size=4, last_batch_handle="discard")
    assert len(list(it)) == 2
    it = NDArrayIter(data, batch_size=4, last_batch_handle="roll_over")
    assert len(list(it)) == 2
    it.reset()  # 2 leftover + 10 = 12 -> 3 batches
    assert len(list(it)) == 3


def test_ndarray_iter_dict_and_shuffle():
    it = NDArrayIter({"a": onp.zeros((8, 2)), "b": onp.ones((8, 3))},
                     onp.arange(8), batch_size=4, shuffle=True)
    b = next(iter(it))
    assert b.data[0].shape == (4, 2) and b.data[1].shape == (4, 3)
    descs = it.provide_data
    assert [d.name for d in descs] == ["a", "b"]


def test_iter_registry():
    assert "NDArrayIter" in list_data_iters()
    assert "ImageRecordIter" in list_data_iters()
    it = create_iter("NDArrayIter", data=onp.zeros((4, 2)), batch_size=2)
    assert len(list(it)) == 2
    with pytest.raises(MXNetError):
        create_iter("NopeIter")


def test_csv_iter(tmp_path):
    p = str(tmp_path / "d.csv")
    onp.savetxt(p, onp.arange(12).reshape(6, 2), delimiter=",")
    it = CSVIter(p, data_shape=(2,), batch_size=2)
    assert len(list(it)) == 3


def _write_rec(tmp_path, n=20, hw=(36, 30)):
    prefix = str(tmp_path / "imgs")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rs = onp.random.RandomState(0)
    for i in range(n):
        img = rs.randint(0, 255, hw + (3,), dtype=onp.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, float(i % 5), i, 0), img,
                                  img_fmt=".png"))  # lossless for checks
    rec.close()
    return prefix


def test_image_record_iter(tmp_path):
    prefix = _write_rec(tmp_path)
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx",
                         data_shape=(3, 24, 24), batch_size=8)
    batches = list(it)
    assert len(batches) == 3  # 20 samples -> 2 full + 1 padded
    assert batches[0].data[0].shape == (8, 3, 24, 24)
    assert batches[-1].pad == 4
    labels = onp.concatenate([b.label[0].asnumpy() for b in batches])[:20]
    assert list(labels) == [i % 5 for i in range(20)]
    it.reset()
    assert len(list(it)) == 3


def test_image_record_iter_no_index_shuffle_augment(tmp_path):
    prefix = _write_rec(tmp_path)
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         data_shape=(3, 20, 20), batch_size=5, shuffle=True,
                         rand_crop=True, rand_mirror=True, seed=7,
                         mean_r=127.0, mean_g=127.0, mean_b=127.0,
                         std_r=58.0, std_g=58.0, std_b=58.0)
    b = next(iter(it))
    x = b.data[0].asnumpy()
    assert x.shape == (5, 3, 20, 20)
    assert abs(float(x.mean())) < 1.5  # roughly normalized


def test_prefetching_iter():
    data = onp.arange(64).reshape(16, 4).astype('float32')
    base = NDArrayIter(data, onp.arange(16), batch_size=4)
    it = PrefetchingIter(base)
    batches = list(it)
    assert len(batches) == 4
    it.reset()
    assert len(list(it)) == 4


def test_resize_iter():
    base = NDArrayIter(onp.zeros((8, 2)), batch_size=4)
    it = ResizeIter(base, size=5)  # wraps around
    assert len(list(it)) == 5


def test_im2rec_tool(tmp_path):
    from PIL import Image
    root = tmp_path / "images"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = onp.random.RandomState(i).randint(
                0, 255, (40, 40, 3), dtype=onp.uint8)
            Image.fromarray(arr).save(root / cls / f"{i}.png")
    prefix = str(tmp_path / "packed")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for extra in (["--list", "--recursive"], []):
        res = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "im2rec.py"),
             prefix, str(root)] + extra,
            capture_output=True, text=True, timeout=240, env=env)
        assert res.returncode == 0, res.stderr
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx",
                         data_shape=(3, 32, 32), batch_size=2)
    batches = list(it)
    assert len(batches) == 3
    labels = sorted(onp.concatenate([b.label[0].asnumpy() for b in batches]))
    assert labels == [0.0, 0.0, 0.0, 1.0, 1.0, 1.0]


def test_image_record_iter_mid_epoch_reset(tmp_path):
    """reset() with in-flight prefetch must not pollute the new epoch."""
    prefix = _write_rec(tmp_path, n=40)
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx",
                         data_shape=(3, 24, 24), batch_size=4,
                         prefetch_buffer=6)
    next(iter(it))          # schedules several prefetch batches
    it.reset()              # drains; must not deadlock or leak
    labels = onp.concatenate([b.label[0].asnumpy() for b in it])[:40]
    assert list(labels) == [i % 5 for i in range(40)]


def test_image_record_iter_seeded_determinism(tmp_path):
    prefix = _write_rec(tmp_path, n=16)
    def run():
        it = ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 24, 24), batch_size=4,
                             shuffle=True, rand_crop=True, rand_mirror=True,
                             seed=11)
        return onp.concatenate([b.data[0].asnumpy() for b in it])
    a, b = run(), run()
    assert onp.array_equal(a, b)


def test_prefetching_iter_rename():
    base = NDArrayIter(onp.zeros((8, 2)), onp.arange(8), batch_size=4)
    it = PrefetchingIter(base, rename_data=[{"data": "data_1"}],
                         rename_label=[{"softmax_label": "lab"}])
    assert [d.name for d in it.provide_data] == ["data_1"]
    assert [d.name for d in it.provide_label] == ["lab"]
    with pytest.raises(MXNetError):
        PrefetchingIter(base, rename_data=[{}, {}])


def test_engine_skipped_op_releases_closure():
    """Ops skipped via poisoned deps must still release their closures
    from the trampoline registry (no leak)."""
    from mxnet_tpu import _native
    if not _native.native_available():
        pytest.skip("native runtime unavailable")
    from mxnet_tpu import engine as em
    e = em.NativeEngine(2)
    v = e.new_var()
    e.push(lambda: (_ for _ in ()).throw(RuntimeError("x")), write=(v,))
    for _ in range(10):
        e.push(lambda: None, read=(v,))   # all skipped
    try:
        e.wait_for_all()
    except Exception:
        pass
    with em._op_lock:
        assert len(em._op_registry) == 0


def test_ndarray_iter_pad_exceeds_dataset():
    """pad wraps cyclically even when batch_size > 2x dataset size."""
    it = NDArrayIter(onp.arange(2).astype('float32'), batch_size=5,
                     last_batch_handle="pad")
    b = next(iter(it))
    assert b.data[0].shape == (5,)
    assert b.pad == 3
    assert list(b.data[0].asnumpy()) == [0, 1, 0, 1, 0]


def test_image_record_iter_batch_exceeds_dataset(tmp_path):
    prefix = _write_rec(tmp_path, n=2)
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx",
                         data_shape=(3, 24, 24), batch_size=5)
    b = next(iter(it))
    assert b.data[0].shape == (5, 3, 24, 24)
    assert b.pad == 3
    assert list(b.label[0].asnumpy()) == [0, 1, 0, 1, 0]


def test_image_record_iter_label_width(tmp_path):
    # multi-label records surface the full (B, label_width) vector
    # (ref ImageRecordIter label_width)
    prefix = str(tmp_path / "ml")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rs = onp.random.RandomState(0)
    for i in range(6):
        img = rs.randint(0, 255, (30, 30, 3), dtype=onp.uint8)
        rec.write_idx(i, pack_img(
            IRHeader(0, onp.array([i, i + 10, i + 20], onp.float32), i, 0),
            img, img_fmt=".png"))
    rec.close()
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx",
                         data_shape=(3, 24, 24), batch_size=3,
                         label_width=3)
    assert it.provide_label[0].shape == (3, 3)
    batches = list(it)
    lab = onp.concatenate([b.label[0].asnumpy() for b in batches])
    assert lab.shape == (6, 3)
    assert list(lab[:, 1]) == [i + 10 for i in range(6)]
    # label_width wider than the stored labels is a loud error
    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         path_imgidx=prefix + ".idx",
                         data_shape=(3, 24, 24), batch_size=3,
                         label_width=5)
    with pytest.raises(MXNetError, match="label_width"):
        list(it)
