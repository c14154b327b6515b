"""The benchmark's corpus: synthetic ``imagenet-sim`` images stored as SJPG.

The image synthesis is a copy of the repository's generator
(``repro.data.datasets.make_image`` for the ``imagenet-sim`` spec), kept here
so that no change to the program can move the benchmark's inputs.  The
encode into renditions stays the program's own (``StoredImage.from_array``):
SJPG is the system's storage format, and the corpus is stored as the system
stores it.
"""

from __future__ import annotations

import os

import numpy as np

# imagenet-sim: 1000 classes, 60% of the class signal in fine texture
NUM_CLASSES = 1000
FINE_FRACTION = 0.6


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream): any whole-number seed,
    negative or beyond 64 bits, maps to one stream of draws."""
    return np.random.default_rng([seed % (1 << 64), stream])


def make_image(label: int, size: int, rng: np.random.Generator) -> np.ndarray:
    """One (size, size, 3) uint8 image: a class-specific 4x4 colour layout
    (survives downsampling) plus a class-specific oriented grating of 4..8 px
    period (does not), plus per-image noise."""
    h = w = size
    cls_rng = np.random.default_rng(label)  # class-deterministic signature
    layout = cls_rng.uniform(0.2, 0.8, size=(4, 4, 3))
    coarse = np.kron(layout, np.ones((h // 4, w // 4, 1)))
    fy, fx = cls_rng.uniform(0.4, 1.0, 2) * 2 * np.pi / 6
    phase = cls_rng.uniform(0, 2 * np.pi)
    yy, xx = np.mgrid[0:h, 0:w]
    grating = 0.5 + 0.5 * np.sin(fy * yy + fx * xx + phase)
    fine = grating[..., None] * cls_rng.uniform(0.3, 1.0, size=(1, 1, 3))
    img = (1 - FINE_FRACTION) * coarse + FINE_FRACTION * fine
    img = img + rng.normal(0, 0.08, size=img.shape)
    return np.clip(img * 255, 0, 255).astype(np.uint8)


def formats(spec: dict) -> dict:
    """Rendition name -> the program's ImageFormat, from a traffic file's
    ``corpus.renditions``."""
    from repro.preprocessing.formats import ImageFormat

    return {
        name: ImageFormat("jpeg", r["short_side"], r["quality"], subsample=r["subsample"])
        for name, r in spec["renditions"].items()
    }


def _stored(task):
    """One corpus item: image ``i`` of ``seed`` in every rendition."""
    from repro.preprocessing.formats import StoredImage

    spec, seed, i, label = task
    rng = np.random.default_rng([seed % (1 << 64), 0, i])
    img = make_image(label, spec["native_size"], rng)
    return StoredImage.from_array(img, list(formats(spec).values()), uid=i)


def build(spec: dict, seed: int, items: int | None = None) -> list:
    """The corpus of a traffic file's ``corpus`` entry, from ``seed``: every
    item stored in every rendition the entry names.  Synthesis and encoding
    run in worker processes (they touch no device); each item draws from a
    stream of its own, so the corpus does not depend on how many there are."""
    import concurrent.futures
    import multiprocessing

    n = items if items is not None else spec["items"]
    labels = rng_for(seed, 0).integers(0, NUM_CLASSES, size=n)
    tasks = [(spec, seed, i, int(y)) for i, y in enumerate(labels)]
    workers = max(1, min(os.cpu_count() or 1, -(-n // 16)))
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        return list(pool.map(_stored, tasks, chunksize=8))
