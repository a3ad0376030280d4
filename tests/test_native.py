"""Native C++ image-ingest pipeline vs the Python/PIL reference path.

The invariant mirrors the reference's data-path behavior (SURVEY §3.4):
decode → resize-smallest-side → center-crop → normalize must produce the
same training distribution whichever backend runs it.  The no-resize path
must match the Python path exactly; the antialiased resize may differ
from PIL by sub-pixel-level amounts (different but equivalent filters —
the reference itself swaps Gaussian-lowpass+imresize for whatever
Images.jl does, src/preprocess.jl:30-42).
"""

import importlib
import os

import numpy as np
import pytest

from fluxdistributed_tpu.data import native

pp = importlib.import_module("fluxdistributed_tpu.data.preprocess")

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain/libjpeg unavailable"
)


@pytest.fixture(scope="module")
def img():
    rng = np.random.default_rng(0)
    grad = np.linspace(0, 255, 300)[:, None, None]
    return np.clip(grad + rng.normal(0, 25, (300, 400, 3)), 0, 255).astype(np.uint8)


@pytest.fixture(scope="module")
def jpeg_dir(tmp_path_factory, img):
    from PIL import Image

    d = tmp_path_factory.mktemp("jpegs")
    paths = []
    for i in range(8):
        p = str(d / f"im{i}.jpg")
        Image.fromarray(np.roll(img, i * 7, axis=1)).save(p, quality=95)
        paths.append(p)
    return paths


def test_no_resize_path_matches_python_exactly(img):
    sq = img[:224, :224]
    a = native.preprocess_rgb(sq, crop=224, resize=224)
    b = pp.preprocess(sq, crop=224, resize=224)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_resize_path_close_to_pil(img):
    a = native.preprocess_rgb(img)
    b = pp.preprocess(img)
    d = np.abs(a - b)
    # normalized units; 0.02 ≈ 1 pixel level (1/255 / 0.225)
    assert d.mean() < 0.02 and np.percentile(d, 99) < 0.06


def test_compat_double_normalize(img):
    a = native.preprocess_rgb(img, compat_double_normalize=True)
    b = pp.preprocess(img, compat_double_normalize=True)
    assert np.abs(a - b).mean() < 0.05
    # quirk output is per-image standardized
    assert abs(a.mean()) < 1e-3 and abs(a.std() - 1) < 1e-2


def test_decode_jpeg_file(jpeg_dir):
    from PIL import Image

    rgb = native.decode_jpeg_file(jpeg_dir[0])
    assert rgb.shape == (300, 400, 3) and rgb.dtype == np.uint8
    # both decoders sit on libjpeg → bit-identical output
    pil = np.asarray(Image.open(jpeg_dir[0]).convert("RGB"))
    np.testing.assert_array_equal(rgb, pil)


def test_load_batch_matches_per_image_pipeline(jpeg_dir):
    out = native.load_batch(jpeg_dir, num_threads=4)
    assert out.shape == (len(jpeg_dir), 224, 224, 3)
    ref = np.stack([pp.preprocess(p) for p in jpeg_dir])
    assert np.abs(out - ref).mean() < 0.02


def test_cmyk_jpeg_decodes(tmp_path, img):
    """ImageNet contains a few CMYK JPEGs; libjpeg can't emit RGB for
    them, so the native decoder converts explicitly."""
    from PIL import Image

    p = str(tmp_path / "cmyk.jpg")
    Image.fromarray(img).convert("CMYK").save(p, quality=95)
    rgb = native.decode_jpeg_file(p)
    pil = np.asarray(Image.open(p).convert("RGB"))
    assert rgb.shape == pil.shape
    # different CMYK→RGB roundings; stay within a couple of levels
    assert np.abs(rgb.astype(int) - pil.astype(int)).mean() < 3


def test_load_batch_fallback_handles_png_disguised_as_jpeg(jpeg_dir, tmp_path, img):
    """PNG bytes behind a .JPEG extension (real ImageNet quirk) must go
    through the Python fallback instead of poisoning the batch."""
    import importlib

    from PIL import Image

    ppm = importlib.import_module("fluxdistributed_tpu.data.preprocess")
    png = str(tmp_path / "sneaky.JPEG")
    Image.fromarray(img).save(png, format="PNG")
    out = native.load_batch([jpeg_dir[0], png], fallback=lambda p: ppm.preprocess(p))
    ref = ppm.preprocess(png)
    np.testing.assert_allclose(out[1], ref, atol=1e-5)


def test_augmented_preprocess_matches_python(img):
    """RandomResizedCrop+flip parity: the native and Python executors
    consume the same relative params and must produce near-identical
    output (shared _aug_rect/aug_rect contract)."""
    rng = np.random.default_rng(7)
    for row in pp.sample_augment_params(rng, 6):
        a = native.preprocess_rgb(img, augment=row)
        b = pp.preprocess(img, augment=row)
        d = np.abs(a - b)
        assert d.mean() < 0.03, f"params {row}: mean diff {d.mean()}"


def test_augmented_flip_actually_flips(img):
    row = np.array([0.5, 1.0, 0.5, 0.5, 0.0], np.float32)
    flipped = row.copy()
    flipped[4] = 1.0
    a = native.preprocess_rgb(img, augment=row)
    b = native.preprocess_rgb(img, augment=flipped)
    np.testing.assert_allclose(a, b[:, ::-1], atol=1e-5)


def test_load_batch_augs_matches_per_image(jpeg_dir):
    rng = np.random.default_rng(3)
    augs = pp.sample_augment_params(rng, len(jpeg_dir))
    out = native.load_batch(jpeg_dir, num_threads=4, augs=augs)
    ref = np.stack([pp.preprocess(p, augment=augs[i]) for i, p in enumerate(jpeg_dir)])
    assert np.abs(out - ref).mean() < 0.03


def test_degenerate_aug_row_is_eval_path_on_both_backends(img):
    """area <= 0 disables augmentation in the C executor; the Python
    executor applies the same gate, so both produce the eval output."""
    zero = np.zeros(5, np.float32)
    a = native.preprocess_rgb(img, augment=zero)
    b = pp.preprocess(img, augment=zero)
    ref = pp.preprocess(img)  # eval path
    np.testing.assert_allclose(b, ref, atol=1e-6)
    assert np.abs(a - ref).mean() < 0.02


def test_load_batch_augs_shape_checked(jpeg_dir):
    with pytest.raises(ValueError, match="augment params"):
        native.load_batch(jpeg_dir, augs=np.zeros((2, 5), np.float32))


def test_load_batch_augmented_fallback_gets_aug_row(jpeg_dir, tmp_path, img):
    """Slow-path (PIL) slots in an augmented batch must apply the same
    per-slot augmentation as the native slots."""
    from PIL import Image

    png = str(tmp_path / "sneaky2.JPEG")
    Image.fromarray(img).save(png, format="PNG")
    paths = [jpeg_dir[0], png]
    augs = pp.sample_augment_params(np.random.default_rng(5), 2)
    out = native.load_batch(
        paths, augs=augs, fallback=lambda p, aug=None: pp.preprocess(p, augment=aug)
    )
    ref = pp.preprocess(png, augment=augs[1])
    np.testing.assert_allclose(out[1], ref, atol=1e-5)


def test_load_batch_rejects_crop_larger_than_resize(jpeg_dir):
    with pytest.raises(ValueError, match="crop <= resize"):
        native.load_batch(jpeg_dir, crop=288, resize=256)


def test_load_batch_rejects_noncontiguous_out(jpeg_dir):
    big = np.empty((len(jpeg_dir), 224, 224, 6), np.float32)
    view = big[..., ::2]  # right shape/dtype, wrong strides
    with pytest.raises(ValueError, match="C-contiguous"):
        native.load_batch(jpeg_dir, out=view)


def test_load_batch_strict_raises_on_corrupt(jpeg_dir, tmp_path):
    bad = str(tmp_path / "bad.jpg")
    with open(bad, "wb") as f:
        f.write(b"not a jpeg at all")
    with pytest.raises(ValueError, match="failed to load"):
        native.load_batch([jpeg_dir[0], bad])
    out = native.load_batch([jpeg_dir[0], bad], strict=False)
    assert np.abs(out[1]).max() == 0.0  # zero-filled slot
    assert np.abs(out[0]).max() > 0.0  # good slot intact


def test_imagenet_dataset_uses_native(tmp_path, img):
    """ImageNetDataset(use_native=True) produces the same batches as the
    PIL path for the same indices."""
    from PIL import Image

    from fluxdistributed_tpu.data.imagenet import ImageNetDataset, SampleTable

    root = tmp_path
    d = root / "ILSVRC" / "Data" / "CLS-LOC" / "train" / "n01440764"
    os.makedirs(d)
    ids = []
    for i in range(4):
        iid = f"n01440764_{i}"
        Image.fromarray(np.roll(img, i * 11, axis=0)).save(
            str(d / f"{iid}.JPEG"), quality=95
        )
        ids.append(iid)
    table = SampleTable(np.asarray(ids, object), np.zeros(4, np.int32))
    ds_nat = ImageNetDataset(str(root), table, nclasses=1, use_native=True, augment=False)
    ds_py = ImageNetDataset(str(root), table, nclasses=1, use_native=False, augment=False)
    idx = np.array([0, 2, 3])
    a, la = ds_nat.batch(np.random.default_rng(0), 3, indices=idx)
    b, lb = ds_py.batch(np.random.default_rng(0), 3, indices=idx)
    np.testing.assert_array_equal(la, lb)
    assert np.abs(a - b).mean() < 0.02


def test_imagenet_dataset_augmented_backends_agree(tmp_path, img):
    """Train split defaults to augment=True; same rng → both backends
    draw the same RandomResizedCrop params → near-identical batches."""
    from PIL import Image

    from fluxdistributed_tpu.data.imagenet import ImageNetDataset, SampleTable

    root = tmp_path
    d = root / "ILSVRC" / "Data" / "CLS-LOC" / "train" / "n01440764"
    os.makedirs(d)
    ids = []
    for i in range(4):
        iid = f"n01440764_{i}"
        Image.fromarray(np.roll(img, i * 11, axis=0)).save(
            str(d / f"{iid}.JPEG"), quality=95
        )
        ids.append(iid)
    table = SampleTable(np.asarray(ids, object), np.zeros(4, np.int32))
    ds_nat = ImageNetDataset(str(root), table, nclasses=1, use_native=True)
    ds_py = ImageNetDataset(str(root), table, nclasses=1, use_native=False)
    assert ds_nat.augment and ds_py.augment  # train split defaults on
    idx = np.array([0, 1, 2, 3])
    a, _ = ds_nat.batch(np.random.default_rng(42), 4, indices=idx)
    b, _ = ds_py.batch(np.random.default_rng(42), 4, indices=idx)
    assert np.abs(a - b).mean() < 0.03
    # and augmentation actually changes the batch vs the eval path
    ds_eval = ImageNetDataset(str(root), table, nclasses=1, use_native=True, augment=False)
    c, _ = ds_eval.batch(np.random.default_rng(42), 4, indices=idx)
    assert np.abs(a - c).mean() > 0.05


def test_failed_build_is_reported_loudly_not_swallowed(monkeypatch, tmp_path):
    """A checkout builds the library from native/fd_native.cpp; when the
    toolchain is missing the failure is a RuntimeWarning that carries
    the compiler command — not a silent fall-back to PIL."""
    monkeypatch.setenv("CXX", str(tmp_path / "no-such-compiler"))
    monkeypatch.setattr(native, "_SO", str(tmp_path / "build" / "lib.so"))
    with pytest.warns(RuntimeWarning, match="failed to build.*no-such-compiler"):
        assert native._build() is False
    assert not os.path.exists(tmp_path / "build" / "lib.so")
