"""Video aggregation query (paper §3.2 / Fig. 9, BlazeIt-style).

"How many objects per frame, +/- eps?" — answered by scanning every frame
with a cheap specialized predictor (this is where decode throughput bites)
and invoking the expensive target model only on a control-variate sample.
SMOL's lever: scan the LOW-RESOLUTION rendition (cheaper decode, same
variance reduction).

    PYTHONPATH=src python examples/video_aggregation.py
"""

import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import aggregation
from repro.data import datasets


def specialized_counts(frames: np.ndarray) -> np.ndarray:
    """Cheap 'specialized NN': bright-pixel blob-area counter."""
    g = frames.astype(np.float32).mean(axis=-1)
    return (g > 170).reshape(len(frames), -1).sum(axis=1) / 28.0


def main():
    enable_compile_cache()
    stored, counts = datasets.video_dataset("amsterdam", num_frames=120, size=64)
    fmts = stored.formats()
    full_fmt, low_fmt = fmts[0], fmts[1]
    truth = counts.mean()
    print(f"video: {len(counts)} frames, true mean objects/frame = {truth:.3f}")
    print(f"stored renditions: {[f.key for f in fmts]}, "
          f"bytes {[stored.nbytes(f) for f in fmts]}")

    def target_fn(idx):  # expensive target model (ground-truth oracle here)
        return counts[np.asarray(idx, dtype=int)]

    # BlazeIt: full-resolution scan
    t0 = time.perf_counter()
    frames = stored.decode(full_fmt)
    spec = specialized_counts(frames)
    res_b = aggregation.control_variate_aggregate(spec, target_fn, eps=0.3,
                                                  min_samples=20, batch=8)
    t_blazeit = time.perf_counter() - t0

    # SMOL: low-resolution scan, reduced-fidelity decode (no deblocking)
    t0 = time.perf_counter()
    frames_low = stored.decode(low_fmt, deblock=False)
    up = np.repeat(np.repeat(frames_low, 2, axis=1), 2, axis=2)
    spec_low = specialized_counts(up)
    res_s = aggregation.control_variate_aggregate(spec_low, target_fn, eps=0.3,
                                                  min_samples=20, batch=8)
    t_smol = time.perf_counter() - t0

    for name, res, t in (("BlazeIt(full-res)", res_b, t_blazeit),
                         ("SMOL(low-res)", res_s, t_smol)):
        print(f"{name:18s}: est={res.estimate:.3f} (err {abs(res.estimate-truth):.3f}) "
              f"targets={res.num_target_invocations} "
              f"var_reduction={res.variance_reduction:.1f}x wall={t:.2f}s")
    print(f"query speedup: {t_blazeit / t_smol:.2f}x")


if __name__ == "__main__":
    main()
