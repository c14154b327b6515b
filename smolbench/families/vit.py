"""The Vision Transformer family (Dosovitskiy et al. 2021) on the served
path.

What the harness knows of one model family (see ``families/resnet.py`` for
the contract).  The program's network is ``repro.models.vit``, and its
weights are program arguments: ``program`` returns a ``ModelEntry``, so the
served programs take the weights as their first argument, one device copy
per replica, and hold no weight constants.  A control put in the network's
place runs on the same argument weights.

The program's symbols are imported here, at the top: a program without
argument weights fails to load this family at once, and never compiles the
weights into its programs as constants.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.device_compiler import ModelEntry
from repro.core.planner import ModelSpec
from repro.models.vit import ViTConfig, vit_forward
from smolbench.reference import vit as reference

# off a TPU: the same path with a toy network, at the configuration's input
# size, so that the dry run samples as many network-input values as a chip run
TINY = {"layers": 2, "hidden": 32, "heads": 2, "mlp": 64, "num_classes": 16}


def program(cfg: dict, params, accuracy: dict, forward=None):
    """``(ModelSpec, ModelEntry)`` of ``cfg`` with ``params``; ``accuracy``
    maps each format key to the configuration's assumed accuracy on it."""
    from smolbench.harness import pixel_sample

    spec = ModelSpec(cfg["name"], cfg["input_size"], cfg["assumed"]["exec_throughput_items_per_s"],
                     accuracy, resize_short=cfg["resize_short"])
    if forward is None:
        net = ViTConfig(cfg["input_size"], cfg["patch"], cfg["layers"], cfg["hidden"], cfg["heads"],
                        cfg["mlp"], cfg["num_classes"], cfg["layer_norm_eps"])

        def forward(p, _cfg, x):
            return vit_forward(p, net, x)

    def fn(p, x):
        logits = forward(p, cfg, x)
        return jnp.concatenate([logits, pixel_sample(x).astype(logits.dtype)], axis=1)

    return spec, ModelEntry(fn, params)
