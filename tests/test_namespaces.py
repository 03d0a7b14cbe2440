"""Namespace parity: nd.image (device-side image ops), nd.contrib
forwarding, npx.random (ref python/mxnet/ndarray/image.py,
ndarray/contrib.py, numpy_extension/random.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

np_ = mx.np
_RS = onp.random.RandomState(21)


def _img(h=10, w=8, dtype="uint8"):
    return _RS.randint(0, 255, (h, w, 3)).astype(dtype)


# -- nd.image ---------------------------------------------------------------

def test_image_to_tensor_and_normalize():
    x = _img()
    t = mx.nd.image.to_tensor(np_.array(x))
    assert t.shape == (3, 10, 8)
    onp.testing.assert_allclose(t.asnumpy(),
                                x.astype("float32").transpose(2, 0, 1) / 255,
                                rtol=1e-6)
    n = mx.nd.image.normalize(t, mean=(0.5, 0.4, 0.3), std=(0.2, 0.2, 0.2))
    onp.testing.assert_allclose(n.asnumpy()[1],
                                (t.asnumpy()[1] - 0.4) / 0.2, rtol=1e-5)
    # batched NHWC
    tb = mx.nd.image.to_tensor(np_.array(x[None]))
    assert tb.shape == (1, 3, 10, 8)


def test_image_crop_and_bounds():
    x = _img()
    out = mx.nd.image.crop(np_.array(x), 1, 2, 5, 6)
    onp.testing.assert_array_equal(out.asnumpy(), x[2:8, 1:6])
    with pytest.raises(MXNetError):
        mx.nd.image.crop(np_.array(x), -1, 0, 4, 4)
    with pytest.raises(MXNetError):
        mx.nd.image.crop(np_.array(x), 0, 0, 9, 4)


def test_image_resize_semantics():
    const = onp.full((4, 4, 3), 77, "uint8")
    out = mx.nd.image.resize(np_.array(const), (9, 7))
    assert out.shape == (7, 9, 3)
    onp.testing.assert_array_equal(out.asnumpy(),
                                   onp.full((7, 9, 3), 77, "uint8"))
    ramp = onp.arange(16, dtype="uint8").reshape(4, 4, 1) * 10
    near = mx.nd.image.resize(np_.array(ramp), (8, 8), interp=0)
    onp.testing.assert_array_equal(
        near.asnumpy(), onp.repeat(onp.repeat(ramp, 2, 0), 2, 1))


def test_image_resize_short_edge_semantics():
    """keep_ratio with an int scales the SHORT edge (reference
    resize-short; review finding round 4)."""
    x = onp.zeros((4, 8, 3), "uint8")
    out = mx.nd.image.resize(np_.array(x), 6, keep_ratio=True)
    assert out.shape == (6, 12, 3)          # short edge 4 -> 6
    # tuple size keeps fit-inside semantics
    out2 = mx.nd.image.resize(np_.array(x), (6, 6), keep_ratio=True)
    assert out2.shape == (3, 6, 3)


def test_image_random_contrast_per_image_mean():
    """Batched contrast must use each image's own luminance mean, not a
    batch-wide mean (review finding round 4)."""
    dark = onp.full((4, 4, 3), 20.0, "float32")
    bright = onp.full((4, 4, 3), 230.0, "float32")
    batch = onp.stack([dark, bright])
    mx.random.seed(6)
    out = mx.nd.image.random_contrast(np_.array(batch), 0.0, 0.0).asnumpy()
    # factor 0 collapses each image to ITS OWN mean
    onp.testing.assert_allclose(out[0], dark, atol=1e-3)
    onp.testing.assert_allclose(out[1], bright, atol=1e-3)


def test_image_flips():
    x = _img()
    lr = mx.nd.image.flip_left_right(np_.array(x))
    onp.testing.assert_array_equal(lr.asnumpy(), x[:, ::-1])
    tb = mx.nd.image.flip_top_bottom(np_.array(x))
    onp.testing.assert_array_equal(tb.asnumpy(), x[::-1])
    mx.random.seed(0)
    out = mx.nd.image.random_flip_left_right(np_.array(x))
    assert out.shape == x.shape


def test_image_random_crop_window():
    mx.random.seed(1)
    x = _img()
    out, (x0, y0, w, h) = mx.nd.image.random_crop(np_.array(x), (5, 6))
    assert (w, h) == (5, 6)
    onp.testing.assert_array_equal(out.asnumpy(),
                                   x[y0:y0 + h, x0:x0 + w])


def test_image_imresize_positional_signature():
    """imresize(src, w, h) matches mx.image.imresize's calling
    convention (review finding round 4: not a bare resize alias)."""
    const = onp.full((4, 4, 3), 9, "uint8")
    out = mx.nd.image.imresize(np_.array(const), 10, 6)
    assert out.shape == (6, 10, 3)


def test_image_random_flip_probability():
    """p is honored (review finding round 4: p was ignored)."""
    mx.random.seed(4)
    x = np_.array(_img())
    always = [mx.nd.image.random_flip_left_right(x, p=1.0).asnumpy()
              for _ in range(5)]
    for a in always:
        onp.testing.assert_array_equal(a, x.asnumpy()[:, ::-1])
    never = [mx.nd.image.random_flip_left_right(x, p=0.0).asnumpy()
             for _ in range(5)]
    for a in never:
        onp.testing.assert_array_equal(a, x.asnumpy())


def test_image_saturation_grayscale_passthrough():
    g = np_.array(_RS.randint(0, 255, (6, 5, 1)).astype("uint8"))
    out = mx.nd.image.random_saturation(g, 0.5, 1.5)
    onp.testing.assert_array_equal(out.asnumpy(), g.asnumpy())


def test_image_color_jitters():
    mx.random.seed(2)
    x = _img()
    b = mx.nd.image.random_brightness(np_.array(x), 0.5, 1.5)
    assert b.shape == x.shape and b.asnumpy().max() <= 255
    c = mx.nd.image.random_contrast(np_.array(x), 0.5, 1.5)
    assert c.shape == x.shape
    s = mx.nd.image.random_saturation(np_.array(x), 0.0, 0.0)
    # factor 0 == full desaturation: channels equal
    sv = s.asnumpy().astype("float32")
    assert abs(sv[..., 0] - sv[..., 1]).max() <= 1.0


# -- nd.contrib -------------------------------------------------------------

def test_contrib_forwarding():
    assert mx.nd.contrib.ROIAlign is mx.npx.roi_align
    assert mx.nd.contrib.roi_align is mx.npx.roi_align
    assert mx.nd.contrib.box_nms is mx.npx.box_nms
    from mxnet_tpu.contrib import dgl

    assert mx.nd.contrib.dgl_adjacency is dgl.dgl_adjacency
    with pytest.raises(AttributeError):
        mx.nd.contrib.definitely_not_an_op


def test_contrib_op_executes():
    x = np_.array(_RS.rand(1, 2, 6, 6).astype("float32"))
    rois = np_.array(onp.array([[0, 0, 0, 5, 5]], "float32"))
    out = mx.nd.contrib.ROIAlign(x, rois, (2, 2))
    assert out.shape == (1, 2, 2, 2)


# -- npx.random -------------------------------------------------------------

def test_npx_image_namespace():
    assert mx.npx.image.resize is mx.nd.image.resize
    assert mx.npx.image.to_tensor is mx.nd.image.to_tensor
    assert mx.npx.image.random_saturation is mx.nd.image.random_saturation
    # short-edge resize: short edge EXACTLY size, long edge integer-
    # scaled long*size//short (ref resize-inl.h GetHeightAndWidth)
    x = onp.zeros((3, 5, 3), "uint8")
    out = mx.npx.image.resize(np_.array(x), 4, keep_ratio=True)
    assert out.shape == (4, 6, 3)            # 5*4//3 == 6
    for (h, w, size) in ((7, 100, 61), (5, 15, 41), (100, 7, 61)):
        out = mx.npx.image.resize(
            np_.array(onp.zeros((h, w, 1), "uint8")), size,
            keep_ratio=True)
        oh, ow = out.shape[:2]
        assert min(oh, ow) == size, (h, w, size, out.shape)
        long_in, long_out = max(h, w), max(oh, ow)
        assert long_out == long_in * size // min(h, w), out.shape


def test_npx_random_namespace():
    assert mx.npx.random.bernoulli is mx.npx.bernoulli
    mx.npx.random.seed(5)
    a = mx.npx.random.uniform_n(0.0, 1.0, batch_shape=(3,)).asnumpy()
    mx.npx.random.seed(5)
    b = mx.npx.random.uniform_n(0.0, 1.0, batch_shape=(3,)).asnumpy()
    onp.testing.assert_array_equal(a, b)
    n = mx.npx.random.normal_n(onp.zeros(2, "float32"),
                               onp.ones(2, "float32"),
                               batch_shape=(4,))
    assert n.shape == (4, 2)
    mx.random.seed(3)
    draws = mx.npx.random.bernoulli(prob=np_.full((2000,), 0.3)).asnumpy()
    assert abs(draws.mean() - 0.3) < 0.05


def test_accelerator_context_never_stands_for_a_cpu_device():
    """mx.tpu(i)/mx.gpu(i) resolve to an accelerator chip or raise: on this
    CPU-only backend every one raises (no silent "some CPU device"), and
    an id past the chips a process holds raises instead of wrapping."""
    from mxnet_tpu import context as ctx_mod
    from mxnet_tpu.base import MXNetError

    for ctx in (mx.Context("tpu", 0), mx.tpu(3), mx.gpu(0)):
        with pytest.raises(MXNetError, match="accelerator"):
            ctx.jax_device()
    assert mx.cpu(0).jax_device().platform == "cpu"
    assert mx.current_context() == mx.cpu(0)    # a default, not a disguise

    chips = ["chip0", "chip1"]
    orig = ctx_mod._accelerator_devices
    ctx_mod._accelerator_devices = lambda: chips
    try:
        assert mx.tpu(1).jax_device() == "chip1"
        for bad in (2, 5, -1):
            with pytest.raises(MXNetError, match="holds 2 accelerator"):
                mx.tpu(bad).jax_device()
    finally:
        ctx_mod._accelerator_devices = orig
