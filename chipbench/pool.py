"""The benchmark's dataset: a pool of images made once from the seed.

The repo's dataset protocol is ``batch(rng, n) -> (float32 images, int
labels)`` plus ``nclasses``.  ``SyntheticDataset`` draws every batch
anew on the host (38.5M normals for 256 images of 224x224x3), which
would make the host's random generator the benchmark.  Here the rows
exist before the window starts, as with a cache of decoded samples, and
a batch costs what the loader itself does: a gather, the one-hot, the
copy to the device.  A batch never holds a row twice.
"""

from __future__ import annotations

import numpy as np


class PoolDataset:
    def __init__(self, seed: int, rows: int, image_shape, nclasses: int):
        rng = np.random.default_rng([int(seed), 0x9001])
        self.images = rng.standard_normal((rows, *image_shape), dtype=np.float32)
        self.labels = rng.integers(0, nclasses, rows).astype(np.int32)
        self.nclasses = int(nclasses)

    def __len__(self) -> int:
        return len(self.images)

    def batch(self, rng, n: int, indices=None):
        if indices is None:
            if n > len(self):
                raise ValueError(f"a batch of {n} distinct rows needs a pool "
                                 f"of at least {n}, not {len(self)}")
            indices = rng.choice(len(self), size=n, replace=False)
        indices = np.asarray(indices)
        return self.images[indices], self.labels[indices]

    def rows_of(self, images: np.ndarray) -> np.ndarray:
        """Which pool row each image of a fed batch is, by its first
        pixel; -1 where no row has that pixel.  The caller compares the
        whole rows."""
        first = {float(v): i for i, v in enumerate(self.images[:, 0, 0, 0])}
        return np.array([first.get(float(v), -1) for v in images[:, 0, 0, 0]],
                        dtype=np.int64)
