"""Data-layer tests: ImageNet metadata parsing, preprocessing, the
dataset registry and the CIFAR-10 binary loader — against generated
fixtures (the reference stores no data fixtures either, SURVEY §4)."""

import os

import numpy as np
import pytest

from fluxdistributed_tpu.data import (
    CIFAR10Dataset,
    ImageNetDataset,
    SyntheticDataset,
    labels,
    makepaths,
    minibatch,
    open_dataset,
    register_dataset,
    train_solutions,
)
from fluxdistributed_tpu.data.preprocess import (
    IMAGENET_MEAN,
    IMAGENET_STD,
    center_crop,
    decode_image,
    preprocess,
    resize_smallest_dimension,
)
from fluxdistributed_tpu.data.registry import load_registry

WNIDS = ["n01440764", "n01443537", "n01484850"]


@pytest.fixture(scope="module")
def imagenet_root(tmp_path_factory):
    """A miniature ILSVRC tree: synset mapping, train solution CSV, and
    real JPEG files (generated with PIL)."""
    from PIL import Image

    root = tmp_path_factory.mktemp("imagenet")
    with open(root / "LOC_synset_mapping.txt", "w") as f:
        f.write("n01440764 tench, Tinca tinca\n")
        f.write("n01443537 goldfish, Carassius auratus\n")
        f.write("n01484850 great white shark, white shark\n")
    rows = ["ImageId,PredictionString"]
    rng = np.random.default_rng(0)
    for wnid in WNIDS:
        d = root / "ILSVRC" / "Data" / "CLS-LOC" / "train" / wnid
        d.mkdir(parents=True)
        for i in range(3):
            image_id = f"{wnid}_{i}"
            arr = rng.integers(0, 255, (80, 100, 3), dtype=np.uint8)
            Image.fromarray(arr).save(d / f"{image_id}.JPEG")
            rows.append(f"{image_id},{wnid} 1 2 3 4 {wnid} 5 6 7 8")
    with open(root / "LOC_train_solution.csv", "w") as f:
        f.write("\n".join(rows) + "\n")
    return str(root)


def test_labels_parse(imagenet_root):
    lt = labels(os.path.join(imagenet_root, "LOC_synset_mapping.txt"))
    assert len(lt) == 3
    assert lt.wnids == WNIDS
    assert lt.names[0].startswith("tench")
    assert lt.class_idx["n01443537"] == 1


def test_train_solutions_parse_and_filter(imagenet_root):
    lt = labels(os.path.join(imagenet_root, "LOC_synset_mapping.txt"))
    csv = os.path.join(imagenet_root, "LOC_train_solution.csv")
    table = train_solutions(csv, lt)
    assert len(table) == 9
    # class filter, as the reference filters to requested classes
    sub = train_solutions(csv, lt, classes=["n01484850"])
    assert len(sub) == 3
    assert set(sub.class_idx.tolist()) == {2}


def test_sample_table_shard(imagenet_root):
    lt = labels(os.path.join(imagenet_root, "LOC_synset_mapping.txt"))
    table = train_solutions(os.path.join(imagenet_root, "LOC_train_solution.csv"), lt)
    shards = [table.shard(i, 4) for i in range(4)]
    assert sum(len(s) for s in shards) == len(table)


def test_makepaths_layout():
    p = makepaths("n01440764_42", "/data", "train")
    assert p == "/data/ILSVRC/Data/CLS-LOC/train/n01440764/n01440764_42.JPEG"
    v = makepaths("ILSVRC2012_val_00000001", "/data", "val")
    assert v.endswith("CLS-LOC/val/ILSVRC2012_val_00000001.JPEG")


def test_preprocess_pipeline_stats(imagenet_root):
    path = makepaths(f"{WNIDS[0]}_0", imagenet_root, "train")
    img = decode_image(path)
    assert img.dtype == np.uint8 and img.shape == (80, 100, 3)
    r = resize_smallest_dimension(img, 64)
    assert min(r.shape[:2]) == 64
    c = center_crop(r, 48)
    assert c.shape == (48, 48, 3)
    x = preprocess(path, crop=64, resize=72)
    assert x.shape == (64, 64, 3) and x.dtype == np.float32
    # uniform-random pixels: after (x-mu)/sigma the mean should sit near
    # (0.5 - mean)/std per channel
    expect = ((0.5 - IMAGENET_MEAN) / IMAGENET_STD)
    assert np.allclose(x.mean(axis=(0, 1)), expect, atol=0.3)
    # compat mode reproduces the reference's per-image standardization
    q = preprocess(path, crop=64, resize=72, compat_double_normalize=True)
    assert abs(float(q.mean())) < 1e-3 and abs(float(q.std()) - 1.0) < 1e-2


def test_imagenet_dataset_batch(imagenet_root):
    lt = labels(os.path.join(imagenet_root, "LOC_synset_mapping.txt"))
    table = train_solutions(os.path.join(imagenet_root, "LOC_train_solution.csv"), lt)
    ds = ImageNetDataset(imagenet_root, table, nclasses=3, crop=32, resize=40)
    imgs, y = ds.batch(np.random.default_rng(0), 8)
    assert imgs.shape == (8, 32, 32, 3) and y.shape == (8,)
    assert set(y.tolist()) <= {0, 1, 2}
    # exported minibatch analog gives one-hot labels
    mi, my = minibatch(ds, 4, np.random.default_rng(1))
    assert my.shape == (4, 3) and np.allclose(my.sum(axis=1), 1.0)


def test_registry_toml_and_overrides(imagenet_root, tmp_path):
    toml = tmp_path / "datasets.toml"
    toml.write_text(
        f"""
[[datasets]]
name = "imagenet_local"
driver = "imagenet"
path = "{imagenet_root}"
crop = 32
resize = 40

[[datasets]]
name = "fake"
driver = "synthetic"
nsamples = 64
nclasses = 5
shape = [8, 8, 3]
"""
    )
    load_registry(str(toml))
    ds = open_dataset("imagenet_local")
    assert isinstance(ds, ImageNetDataset) and ds.crop == 32
    fake = open_dataset("fake")
    assert isinstance(fake, SyntheticDataset) and fake.nclasses == 5
    with pytest.raises(KeyError, match="not registered"):
        open_dataset("nope")
    register_dataset("fake2", "synthetic", nsamples=16)
    assert len(open_dataset("fake2")) == 16
    with pytest.raises(ValueError, match="unknown driver"):
        register_dataset("bad", "imaginary")


def test_cifar10_binary_loader(tmp_path):
    # forge two records of the binary format: 1 label byte + 3072 CHW bytes
    rng = np.random.default_rng(0)
    base = tmp_path / "cifar-10-batches-bin"
    base.mkdir()
    for fname in [f"data_batch_{i}.bin" for i in range(1, 6)] + ["test_batch.bin"]:
        recs = []
        for lbl in (3, 7):
            recs.append(np.concatenate([[lbl], rng.integers(0, 255, 3072)]).astype(np.uint8))
        np.stack(recs).tofile(base / fname)
    ds = CIFAR10Dataset(str(tmp_path))
    assert len(ds) == 10  # 5 files x 2 records
    imgs, y = ds.batch(np.random.default_rng(1), 4)
    assert imgs.shape == (4, 32, 32, 3)
    assert set(y.tolist()) <= {3, 7}
    test = CIFAR10Dataset(str(tmp_path), split="test")
    assert len(test) == 2
    with pytest.raises(FileNotFoundError, match="binary"):
        CIFAR10Dataset(str(tmp_path / "missing"))


def test_registry_split_and_augment_keys(imagenet_root):
    """The registry plumbs split/augment through to ImageNetDataset:
    split selects the solution CSV + file layout and augment overrides
    the per-split default."""
    from fluxdistributed_tpu.data.registry import register_dataset

    register_dataset("inet_train", "imagenet", path=imagenet_root, crop=32, resize=40)
    ds = open_dataset("inet_train")
    assert ds.table.split == "train" and ds.augment is True
    ds2 = open_dataset("inet_train", augment=False)
    assert ds2.augment is False
    # a val registration reuses the same CSV via solution_csv but stamps
    # the val split → augment defaults off
    register_dataset(
        "inet_val", "imagenet", path=imagenet_root, split="val",
        solution_csv=os.path.join(imagenet_root, "LOC_train_solution.csv"),
        crop=32, resize=40,
    )
    dv = open_dataset("inet_val")
    assert dv.table.split == "val" and dv.augment is False


def test_byte_text_dataset(tmp_path):
    """Windows are exact byte slices; len counts non-overlapping windows;
    the registry's text driver opens it; decode round-trips."""
    from fluxdistributed_tpu.data import ByteTextDataset
    from fluxdistributed_tpu.data.registry import register_dataset

    corpus = (b"the quick brown fox jumps over the lazy dog. " * 50)
    p = tmp_path / "corpus.txt"
    p.write_bytes(corpus)

    ds = ByteTextDataset(str(p), seqlen=16)
    assert ds.vocab == 256
    assert len(ds) == len(corpus) // 16
    rng = np.random.default_rng(0)
    toks = ds.batch(rng, 8)
    assert toks.shape == (8, 16) and toks.dtype == np.int32
    # every window is a literal slice of the file
    blob = corpus
    for row in toks:
        assert bytes(row.astype(np.uint8)) in blob
    assert ByteTextDataset.decode(np.frombuffer(b"fox", np.uint8)) == "fox"

    register_dataset("corpus", "text", path=str(p), seqlen=16)
    ds2 = open_dataset("corpus")
    assert ds2.seqlen == 16 and len(ds2) == len(ds)

    with pytest.raises(ValueError, match="seqlen"):
        small = tmp_path / "small.txt"
        small.write_bytes(b"xy")
        ByteTextDataset(str(small), seqlen=16)


def test_byte_text_dataset_boundary(tmp_path):
    """A file of exactly seqlen bytes is one valid window, and the final
    byte of any corpus is reachable (window starts have an inclusive
    upper bound of len - seqlen)."""
    from fluxdistributed_tpu.data import ByteTextDataset

    exact = tmp_path / "exact.txt"
    exact.write_bytes(b"0123456789abcdef")  # exactly 16 bytes
    ds = ByteTextDataset(str(exact), seqlen=16)
    toks = ds.batch(np.random.default_rng(0), 4)
    assert (toks == np.frombuffer(b"0123456789abcdef", np.uint8)).all()

    tail = tmp_path / "tail.txt"
    tail.write_bytes(b"aaaaaaaaZ")  # 9 bytes, seqlen 8: starts in {0, 1}
    ds = ByteTextDataset(str(tail), seqlen=8)
    toks = ds.batch(np.random.default_rng(0), 256)
    assert (toks[:, -1] == ord("Z")).any(), "final corpus byte never sampled"


# ---------------------------------------------------------------------------
# The host one-hot, and a loader whose workers touch the device only to
# device_put finished numpy arrays
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("labels_, nclasses", [
    (np.array([0, 3, 9, 1], np.int32), 10),
    (np.array([0, 3, 9, 1], np.int64), 10),
    (np.arange(24, dtype=np.int32).reshape(2, 3, 4) % 7, 7),   # leading shape kept
    (np.array([[1, 0], [2, 2]], np.int64), 3),
    (np.array([2, 5, 4], np.int32), 5),          # a label equal to nclasses
    (np.array([-1, 0, -7], np.int64), 4),        # negative labels
    (np.array([0, 1, 0, -1], np.int32), 1),      # one class
    (np.array([0, 999, 1000, 517, -1], np.int64), 1000),
    (np.zeros((0,), np.int32), 6),               # an empty batch
], ids=["int32", "int64", "3d", "2d", "eq_nclasses", "negative", "one_class",
        "1000_classes", "empty"])
def test_host_onehot_is_bit_equal_to_the_device_onehot(labels_, nclasses):
    from fluxdistributed_tpu.data.loader import host_onehot
    from fluxdistributed_tpu.ops import onehot

    got = host_onehot(labels_, nclasses)
    want = np.asarray(onehot(labels_, nclasses))
    assert type(got) is np.ndarray and got.dtype == np.float32
    assert got.shape == labels_.shape + (nclasses,) == want.shape
    assert got.tobytes() == want.tobytes()
    valid = (labels_ >= 0) & (labels_ < nclasses)
    assert (got.sum(axis=-1) == valid).all()  # an all-zero row outside the range


def test_batch_to_dict_and_minibatch_onehot_on_the_host():
    from fluxdistributed_tpu.data.loader import batch_to_dict, host_onehot

    ds = SyntheticDataset(nsamples=32, nclasses=11, shape=(4, 4, 3))
    imgs, y = ds.batch(np.random.default_rng(5), 6)
    d = batch_to_dict((imgs, y), ds.nclasses)
    assert all(type(v) is np.ndarray for v in d.values())
    assert d["label"].tobytes() == host_onehot(y, 11).tobytes()
    assert batch_to_dict((imgs, y), one_hot=False)["label"].tobytes() == y.tobytes()
    with pytest.raises(ValueError, match="nclasses"):
        batch_to_dict((imgs, y))
    mi, my = minibatch(ds, 6, np.random.default_rng(5))
    assert type(my) is np.ndarray and my.tobytes() == d["label"].tobytes()
    assert minibatch(ds, 6, np.random.default_rng(5), one_hot=False)[1].tobytes() == y.tobytes()


@pytest.fixture(scope="module")
def mesh8():
    from fluxdistributed_tpu import mesh as mesh_lib

    return mesh_lib.data_mesh(8)


@pytest.mark.parametrize("chunk", [1, 2])
def test_loader_yields_the_documented_batches(mesh8, chunk):
    """Item ``c`` is ``device_put`` of ``batch_to_dict(dataset.batch(rng_i,
    n))`` with ``rng_i = default_rng((seed, process, i))``, bit for bit —
    stacked over steps ``c*chunk .. c*chunk+chunk-1`` when chunked."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fluxdistributed_tpu.data import PrefetchLoader
    from fluxdistributed_tpu.data.loader import batch_to_dict

    ds = SyntheticDataset(nsamples=96, nclasses=13, shape=(5, 5, 3), seed=2)
    n, seed, cycles = 16, 9, 6
    dl = PrefetchLoader(ds, mesh8, batch_size=n, cycles=cycles, seed=seed,
                        chunk=chunk, buffersize=2)
    items = list(dl)
    assert len(items) == cycles // chunk

    def step(i):
        rng = np.random.default_rng((seed, jax.process_index(), i))
        return batch_to_dict(ds.batch(rng, n), ds.nclasses)

    spec = P("data") if chunk == 1 else P(None, "data")
    for c, item in enumerate(items):
        steps = [step(c * chunk + j) for j in range(chunk)]
        assert sorted(item) == ["image", "label"]
        for k, got in item.items():
            want = steps[0][k] if chunk == 1 else np.stack([s[k] for s in steps])
            assert isinstance(got, jax.Array)
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.asarray(got).tobytes() == want.tobytes()
            assert got.sharding.is_equivalent_to(
                NamedSharding(mesh8, spec), got.ndim)


@pytest.mark.parametrize("chunk", [1, 2])
def test_loader_workers_stay_off_the_device(mesh8, monkeypatch, chunk):
    """While the workers run nothing compiles (shapes no test has used, so
    a jitted one-hot would have to), and what ``h2d`` is handed is a
    finished host batch: numpy leaves, one-hot included."""
    import threading

    from fluxdistributed_tpu.data import PrefetchLoader
    from fluxdistributed_tpu.obs import get_tracer, jaxmon

    ds = SyntheticDataset(nsamples=80, nclasses=37 + chunk, shape=(7, 3, 3))
    seen = []
    real_put = PrefetchLoader._put

    def put(self, host):
        seen.append((threading.current_thread() is threading.main_thread(),
                     {k: (type(v), v.dtype, v.shape) for k, v in host.items()}))
        return real_put(self, host)

    monkeypatch.setattr(PrefetchLoader, "_put", put)
    jaxmon.install()
    dl = PrefetchLoader(ds, mesh8, batch_size=24, cycles=8, chunk=chunk)
    compiles = jaxmon.compile_count()
    mark = len(get_tracer().trace_events())
    items = list(dl)
    assert jaxmon.compile_count() == compiles
    lead = () if chunk == 1 else (chunk,)
    assert len(seen) == len(items) == 8 // chunk
    for on_main, leaves in seen:
        assert not on_main
        assert leaves == {
            "image": (np.ndarray, np.dtype(np.float32), lead + (24, 7, 3, 3)),
            "label": (np.ndarray, np.dtype(np.float32), lead + (24, 37 + chunk)),
        }
    # the timeline holds one assemble and one h2d span an item
    new = get_tracer().trace_events()[mark:]
    for name in ("assemble", "h2d"):
        assert sorted(e["args"]["item"] for e in new if e["name"] == name) \
            == list(range(8 // chunk))
