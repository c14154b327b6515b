"""Quickstart: SMOL in ~60 lines.

Builds a tiny image-classification deployment end-to-end: synthetic
dataset with natively-present formats, cost-model-driven plan selection
over 𝒟 x ℱ, and pipelined execution of the chosen plan.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import dag
from repro.core.cost_model import estimate_smol
from repro.core.engine import measure_plan
from repro.data import datasets
from repro.preprocessing import ops as P
from repro.preprocessing.formats import FULL_JPEG_Q95, THUMB_JPEG_161_Q75
from repro.preprocessing.ops import TensorMeta


def main():
    enable_compile_cache()
    # 1. data: one logical dataset, several physical encodings (ℱ)
    stored, labels = datasets.image_dataset("bike-bird", 24, seed=0)
    print(f"dataset: {len(stored)} images, formats {[f.key for f in stored[0].formats()]}")

    # 2. optimize the preprocessing DAG (paper §6.2)
    meta = TensorMeta(stored[0].native_shape, "uint8", "HWC")
    plan = dag.optimize(P.STANDARD_RESNET_CHAIN, meta)
    naive_cost = P.chain_flops(P.STANDARD_RESNET_CHAIN, meta)
    print(f"DAG optimizer: {naive_cost / plan.cost:.2f}x fewer weighted ops -> {plan.ops}")

    # 3. the cost model (paper Eq. 4): min(preproc, exec)
    def host_full(s):
        return plan.apply_host(s.decode(FULL_JPEG_Q95)).astype(np.float32)

    def host_thumb(s):
        return plan.apply_host(s.decode(THUMB_JPEG_161_Q75)).astype(np.float32)

    def tiny_dnn(batch):  # stand-in DNN
        return batch.mean(axis=(1, 2, 3))

    out_shape = plan.out_meta.shape
    for name, host_fn in (("full_jpeg", host_full), ("thumb_q75", host_thumb)):
        m = measure_plan(host_fn, tiny_dnn, stored, out_shape, np.float32,
                         batch_size=8, num_workers=2)
        est = estimate_smol(m["preproc"], [m["exec"]])
        print(
            f"plan {name:10s}: preproc={m['preproc']:7.1f} exec={m['exec']:9.1f} "
            f"pipelined={m['pipelined']:7.1f} im/s | min-model predicts {est:7.1f} "
            f"({abs(est - m['pipelined']) / m['pipelined']:.0%} err)"
        )
    print("-> SMOL picks the thumbnail plan: decoding is the bottleneck, "
          "and the low-res rendition decodes faster (paper §5.2).")


if __name__ == "__main__":
    main()
