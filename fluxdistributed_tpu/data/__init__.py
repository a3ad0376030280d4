from .cifar import CIFAR10Dataset
from .imagenet import ImageNetDataset, SampleTable, labels, makepaths, train_solutions
from .loader import PrefetchLoader
from .preprocess import preprocess
from .registry import load_registry, open_dataset, register_dataset
from .sources import FileSource, GCSSource, HTTPSource, make_source
from .synthetic import SyntheticDataset, SyntheticTextDataset
from .text import ByteTextDataset

__all__ = [
    "CIFAR10Dataset",
    "ImageNetDataset",
    "SampleTable",
    "labels",
    "makepaths",
    "train_solutions",
    "PrefetchLoader",
    "preprocess",
    "load_registry",
    "open_dataset",
    "register_dataset",
    "FileSource",
    "HTTPSource",
    "GCSSource",
    "make_source",
    "SyntheticDataset",
    "SyntheticTextDataset",
    "ByteTextDataset",
    "minibatch",
]


def minibatch(dataset, n: int, rng=None, one_hot: bool = True):
    """Sample one host-side minibatch — the exported ``minibatch`` analog
    (reference src/imagenet.jl:23-48, exported at src/FluxDistributed.jl:11).

    With-replacement sampling; returns ``(images [n,H,W,C] f32,
    labels)`` with labels one-hot (``Flux.onehotbatch`` analog) unless
    ``one_hot=False``.
    """
    import numpy as np

    from .loader import host_onehot

    if rng is None:
        rng = np.random.default_rng()
    imgs, y = dataset.batch(rng, n)
    if one_hot:
        y = host_onehot(y, dataset.nclasses)
    return imgs, y
